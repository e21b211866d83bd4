"""Serving steps: prefill (prompt -> last-token logits + filled caches)
and decode (one token against the cache, greedy or sampled).

Prefill slices the residual stream to the final position *before* the
LM head — materializing (B, 32k, vocab) logits would be tens of GB per
device for the large-vocab archs.
"""

from __future__ import annotations

import weakref
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .. import obs
from ..models import ApproxPolicy
from ..models.config import ModelConfig
from ..models.transformer import (
    _embed,
    _logits,
    _scan_blocks,
    encode,
)
from ..models.common import make_rope

__all__ = ["Generator", "make_prefill_step", "make_decode_step"]


def _inv_freq(cfg: ModelConfig):
    return jnp.asarray(
        make_rope(cfg.resolved_head_dim, cfg.rope_theta,
                  fraction=0.5 if cfg.rope_style == "half" else 1.0)
    )


def make_prefill_step(cfg: ModelConfig, *, policy: Optional[ApproxPolicy] = None,
                      attn_chunk: int = 1024, scan_chunk: int = 128):
    def prefill(params, batch: Dict[str, jnp.ndarray], caches):
        """-> (last_logits (b, 1, V), caches, enc_out|None)"""
        parts = []
        if batch.get("embeds") is not None:
            parts.append(batch["embeds"].astype(jnp.bfloat16))
        if batch.get("tokens") is not None:
            parts.append(_embed(params, cfg, batch["tokens"]))
        x = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)
        if cfg.name.startswith("gemma"):
            x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
        enc_out = None
        if cfg.is_encoder_decoder:
            enc_out = encode(params, cfg, batch["enc_embeds"],
                             policy=policy, remat=False)
        x, caches, _ = _scan_blocks(
            params, cfg, x, _inv_freq(cfg), policy=policy, causal=True,
            caches=caches, pos=None, enc_out=enc_out, remat=False,
            attn_chunk=attn_chunk, scan_chunk=scan_chunk,
        )
        logits = _logits(params, cfg, x[:, -1:, :])
        if cfg.is_encoder_decoder:
            return logits, caches, enc_out
        return logits, caches

    return prefill


def make_decode_step(cfg: ModelConfig, *, policy: Optional[ApproxPolicy] = None,
                     greedy: bool = True):
    from ..models.transformer import decode_step as _ds

    def serve_step(params, caches, tokens, pos, enc_out=None):
        """-> (next_tokens (b, 1), logits, caches)"""
        logits, caches = _ds(params, cfg, caches, tokens, pos,
                             policy=policy, enc_out=enc_out)
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
        return nxt, logits, caches

    return serve_step


class Generator:
    """One (config, policy) pair's jitted prefill + decode steps, reused
    across prompt batches.

    The serving tier holds one Generator per front genome (the genome's
    decoded ``ApproxPolicy`` is baked into both jitted steps), so
    steady-state requests at a popular operating point never re-trace;
    ``launch.serve`` drives the same object for one-shot CLI runs.
    Caches are rebuilt per ``generate`` call — they are shape-keyed by
    (batch, prompt_len + gen), so distinct request shapes simply retrace
    the two steps once each."""

    def __init__(self, cfg: ModelConfig, *,
                 policy: Optional[ApproxPolicy] = None,
                 attn_chunk: int = 1024, scan_chunk: int = 128):
        self.cfg = cfg
        self.policy = policy
        self.prefill = jax.jit(make_prefill_step(
            cfg, policy=policy, attn_chunk=attn_chunk,
            scan_chunk=scan_chunk))
        self.decode = jax.jit(make_decode_step(cfg, policy=policy))
        self._tree_bytes: Dict[int, int] = {}

    def weight_bytes(self, params) -> int:
        """Bytes of the parameter tree, summed once per tree: the entry is
        keyed by the tree's identity and dropped with its first leaf."""
        key = id(params)
        n = self._tree_bytes.get(key)
        if n is None:
            leaves = jax.tree.leaves(params)
            n = sum(int(leaf.nbytes) for leaf in leaves)
            self._tree_bytes[key] = n
            if leaves:
                weakref.finalize(leaves[0], self._tree_bytes.pop, key, None)
        return n

    def generate(
        self,
        params,
        prompts,
        gen: int,
        *,
        key: Optional[jax.Array] = None,
    ) -> Tuple[jnp.ndarray, float]:
        """Greedy-decode ``gen`` tokens after ``prompts`` (b, L) int32.
        Synthesizes the frontend extras reduced archs need (encoder
        embeds for enc-dec, vision embeds for vision frontends).
        Returns (tokens (b, L + gen), decode tokens/s).  The prefill
        (through its first token) and the decode loop run under the
        ``lm.prefill`` and ``lm.decode`` spans; ``lm.prefill`` records the
        bytes of the weights that served (``weight_bytes``)."""
        import time

        from ..models.common import init_tree
        from ..models.transformer import cache_specs

        cfg = self.cfg
        if key is None:
            key = jax.random.PRNGKey(0)
        prompts = jnp.asarray(prompts, jnp.int32)
        batch, prompt_len = prompts.shape
        vis = cfg.frontend_len if cfg.frontend == "vision" else 0
        max_len = prompt_len + int(gen) + vis
        enc_len = 16 if cfg.is_encoder_decoder else 0
        caches = init_tree(
            cache_specs(cfg, batch, max_len, enc_len=enc_len), key)

        batch_in: Dict[str, Any] = {"tokens": prompts}
        if cfg.is_encoder_decoder:
            batch_in["enc_embeds"] = jax.random.normal(
                key, (batch, enc_len, cfg.d_model), jnp.float32) * 0.1
        if cfg.frontend == "vision":
            batch_in["embeds"] = jax.random.normal(
                key, (batch, cfg.frontend_len, cfg.d_model),
                jnp.float32) * 0.1

        with obs.span("lm.prefill", batch=batch, prompt_len=prompt_len,
                      weight_bytes=self.weight_bytes(params)):
            out = self.prefill(params, batch_in, caches)
            enc_out = None
            if cfg.is_encoder_decoder:
                logits, caches, enc_out = out
            else:
                logits, caches = out
            nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
            nxt.block_until_ready()

        toks = [prompts, nxt]
        pos0 = prompt_len + vis
        # the decode clock covers the device's work and nothing else:
        # prefill has finished when it starts, the last token when it stops
        t0 = time.perf_counter()
        with obs.span("lm.decode", batch=batch, steps=int(gen) - 1):
            for i in range(int(gen) - 1):
                nxt, logits, caches = self.decode(
                    params, caches, nxt, jnp.int32(pos0 + i), enc_out=enc_out
                )
                toks.append(nxt)
            nxt.block_until_ready()
        dt = time.perf_counter() - t0
        tokens = jnp.concatenate(toks, axis=1)
        tps = batch * (int(gen) - 1) / max(dt, 1e-9)
        return tokens, tps
