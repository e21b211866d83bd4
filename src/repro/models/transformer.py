"""Model assembly: parameter declaration, the scanned super-block stack,
full forward (train / prefill), single-token decode, encoder-decoder
composition, KV/SSM cache management.

Layer stacks are ``lax.scan``s over *super-blocks* (config.block_pattern)
with per-super-block remat, so compile time is O(1) in depth and
activation memory is one residual per super-block.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..dist.sharding import constrain
from .approx_linear import ApproxPolicy
from .attention import (
    attn_param_specs,
    cross_attention,
    init_kv_cache_spec,
    self_attention,
)
from .common import ParamSpec, make_rope, rms_norm
from .config import LayerKind, ModelConfig
from .moe import dense_mlp, dense_mlp_param_specs, moe_layer, moe_param_specs
from .ssm import mamba_cache_spec, mamba_layer, mamba_param_specs

__all__ = [
    "param_specs",
    "cache_specs",
    "forward",
    "decode_step",
    "encode",
]


# --------------------------------------------------------------------------
# parameter declaration
# --------------------------------------------------------------------------

def _layer_specs(cfg: ModelConfig, kind: LayerKind) -> Dict[str, Any]:
    out: Dict[str, Any] = {}
    if kind.mixer == "attn":
        out["attn"] = attn_param_specs(cfg)
    else:
        out["mamba"] = mamba_param_specs(cfg)
    if kind.cross_attn:
        out["cross"] = attn_param_specs(cfg, cross=True)
    if kind.mlp == "dense":
        out["mlp"] = dense_mlp_param_specs(cfg)
    elif kind.mlp == "moe":
        out["moe"] = moe_param_specs(cfg)
    return out


def _stack_specs(specs, n: int):
    """Add a leading scan dimension to every ParamSpec leaf."""
    return jax.tree.map(
        lambda s: ParamSpec((n,) + s.shape, (None,) + s.logical, s.dtype,
                            s.init, s.scale),
        specs,
        is_leaf=lambda s: isinstance(s, ParamSpec),
    )


def param_specs(cfg: ModelConfig) -> Dict[str, Any]:
    d, v, dt = cfg.d_model, cfg.padded_vocab, cfg.compute_dtype
    blk = {
        f"layer{i}": _layer_specs(cfg, kind)
        for i, kind in enumerate(cfg.block_pattern)
    }
    out: Dict[str, Any] = {
        "embed": ParamSpec((v, d), ("vocab", "embed"), dt),
        "blocks": _stack_specs(blk, cfg.n_superblocks),
        "final_norm": ParamSpec((d,), ("norm",), init="zeros"),
    }
    if not cfg.tie_embeddings:
        out["lm_head"] = ParamSpec((d, v), ("embed", "vocab"), dt)
    if cfg.is_encoder_decoder:
        enc_layer = {
            "attn": attn_param_specs(cfg),
            "mlp": dense_mlp_param_specs(cfg),
        }
        out["encoder"] = {
            "blocks": _stack_specs(enc_layer, cfg.n_enc_layers),
            "final_norm": ParamSpec((d,), ("norm",), init="zeros"),
        }
    return out


def cache_specs(
    cfg: ModelConfig, batch: int, max_len: int, enc_len: int = 0
) -> Dict[str, Any]:
    """Decode-cache declaration, stacked over super-blocks."""
    layer_caches = {}
    for i, kind in enumerate(cfg.block_pattern):
        c: Dict[str, Any] = {}
        if kind.mixer == "attn":
            c["kv"] = init_kv_cache_spec(cfg, batch, max_len)
        else:
            c["ssm_state"] = mamba_cache_spec(cfg, batch)
        if kind.cross_attn:
            hd = cfg.resolved_head_dim
            c["cross"] = {
                "k": ParamSpec((batch, cfg.n_kv_heads, enc_len, hd),
                               ("batch", "kv_heads", None, None),
                               dtype="bfloat16", init="zeros"),
                "v": ParamSpec((batch, cfg.n_kv_heads, enc_len, hd),
                               ("batch", "kv_heads", None, None),
                               dtype="bfloat16", init="zeros"),
            }
        layer_caches[f"layer{i}"] = c
    return _stack_specs(layer_caches, cfg.n_superblocks)


# --------------------------------------------------------------------------
# super-block
# --------------------------------------------------------------------------

def _superblock(
    blk: Dict[str, Any],
    x: jnp.ndarray,
    cfg: ModelConfig,
    inv_freq,
    *,
    policy: Optional[ApproxPolicy],
    causal: bool,
    caches: Optional[Dict[str, Any]] = None,
    pos: Optional[jnp.ndarray] = None,
    enc_out: Optional[jnp.ndarray] = None,
    attn_chunk: int = 1024,
    scan_chunk: int = 128,
) -> Tuple[jnp.ndarray, Optional[Dict[str, Any]], jnp.ndarray]:
    aux = jnp.zeros((), jnp.float32)
    new_caches: Dict[str, Any] = {}
    training = caches is None

    def ckpt(fn):
        # nested per-layer remat: the outer (superblock) checkpoint alone
        # would hold every layer's recomputed internals simultaneously
        # during backward; nesting bounds the live set to one layer
        return jax.checkpoint(fn) if training else fn

    for i, kind in enumerate(cfg.block_pattern):
        lp = blk[f"layer{i}"]
        ch = caches[f"layer{i}"] if caches is not None else None
        nch: Dict[str, Any] = {}
        if kind.mixer == "attn":
            def attn_fn(lp_, x_):
                return self_attention(
                    lp_, x_, cfg, inv_freq, policy=policy, causal=causal,
                    cache=ch["kv"] if ch is not None else None, pos=pos,
                    attn_chunk=attn_chunk,
                )
            y, kv = ckpt(attn_fn)(lp["attn"], x)
            if kv is not None:
                nch["kv"] = kv
            x = x + y
        else:
            def mamba_fn(lp_, x_):
                return mamba_layer(
                    lp_, x_, cfg, policy=policy,
                    cache=ch["ssm_state"] if ch is not None else None,
                    decode=pos is not None,
                    scan_chunk=scan_chunk,
                )
            y, sc = ckpt(mamba_fn)(lp["mamba"], x)
            if sc is not None:
                nch["ssm_state"] = sc
            x = x + y
        if kind.cross_attn:
            cached = ch["cross"] if (ch is not None and pos is not None) else None
            y, ckv = cross_attention(
                lp["cross"], x, enc_out, cfg, policy=policy, cached_kv=cached
            )
            if ch is not None:
                nch["cross"] = {
                    "k": ckv["k"].astype(jnp.bfloat16),
                    "v": ckv["v"].astype(jnp.bfloat16),
                }
            x = x + y
        if kind.mlp == "dense":
            def mlp_fn(lp_, x_):
                return dense_mlp(lp_, x_, cfg, policy=policy)
            x = x + ckpt(mlp_fn)(lp["mlp"], x)
        elif kind.mlp == "moe":
            def moe_fn(lp_, x_):
                return moe_layer(lp_, x_, cfg, policy=policy)
            y, a = ckpt(moe_fn)(lp["moe"], x)
            x = x + y
            aux = aux + a
        x = constrain(x, ("batch", "seq", "act_embed"))
        new_caches[f"layer{i}"] = nch
    return x, (new_caches if caches is not None else None), aux


# --------------------------------------------------------------------------
# full forward
# --------------------------------------------------------------------------

def _embed(params, cfg: ModelConfig, tokens: jnp.ndarray) -> jnp.ndarray:
    x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.bfloat16)
    return x


def _logits(params, cfg: ModelConfig, x: jnp.ndarray) -> jnp.ndarray:
    x = rms_norm(x, params["final_norm"], cfg.rms_eps)
    head = (
        params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    )
    logits = jnp.einsum(
        "bsd,dv->bsv", x.astype(jnp.bfloat16), head.astype(jnp.bfloat16)
    )
    return constrain(logits, ("batch", "seq", "vocab"))


def _scan_blocks(params, cfg, x, inv_freq, *, policy, causal, caches, pos,
                 enc_out, remat, attn_chunk, scan_chunk):
    from ..dist.sharding import constrain_cotangent

    inner_fn = functools.partial(
        _superblock, cfg=cfg, inv_freq=inv_freq, policy=policy,
        causal=causal, pos=pos, enc_out=enc_out,
        attn_chunk=attn_chunk, scan_chunk=scan_chunk,
    )
    # per-layer weight-gradient sharding: constrain cotangents inside the
    # scan body (see dist.sharding.constrain_cotangent)
    blk_specs = {
        f"layer{i}": _layer_specs(cfg, kind)
        for i, kind in enumerate(cfg.block_pattern)
    }

    def body_fn(blk, x, caches=None):
        # barrier: stops XLA hoisting per-layer weight transforms (e.g.
        # the CPU backend's bf16->f32 dot upcast) out of the loop, which
        # would materialize f32 copies of the ENTIRE stacked stack at
        # once (observed +20 GB on the 398B config)
        blk = jax.lax.optimization_barrier(blk)
        if remat:
            blk = jax.tree.map(
                lambda t, s: constrain_cotangent(t, s.logical),
                blk, blk_specs,
            )
        return inner_fn(blk, x, caches=caches)

    if remat:
        body_fn = jax.checkpoint(
            body_fn, policy=jax.checkpoint_policies.nothing_saveable,
            static_argnums=(),
        )

    if caches is None:
        def body(carry, blk):
            x, aux = carry
            x, _, a = body_fn(blk, x, caches=None)
            return (x, aux + a), None

        (x, aux), _ = jax.lax.scan(
            body, (x, jnp.zeros((), jnp.float32)), params["blocks"]
        )
        return x, None, aux

    def body(carry, inp):
        x, aux = carry
        blk, ch = inp
        x, nch, a = body_fn(blk, x, caches=ch)
        return (x, aux + a), nch

    (x, aux), new_caches = jax.lax.scan(
        body, (x, jnp.zeros((), jnp.float32)), (params["blocks"], caches)
    )
    return x, new_caches, aux


def encode(
    params, cfg: ModelConfig, enc_embeds: jnp.ndarray,
    *, policy: Optional[ApproxPolicy] = None, remat: bool = True,
) -> jnp.ndarray:
    """Encoder stack (enc-dec models): full attention over embeddings."""
    inv_freq = jnp.asarray(make_rope(cfg.resolved_head_dim, cfg.rope_theta))
    enc = params["encoder"]
    x = enc_embeds.astype(jnp.bfloat16)

    def body(x, blk):
        def blk_fn(blk, x):
            y, _ = self_attention(blk["attn"], x, cfg, inv_freq,
                                  policy=policy, causal=False)
            x = x + y
            x = x + dense_mlp(blk["mlp"], x, cfg, policy=policy)
            return x
        if remat:
            blk_fn = jax.checkpoint(
                blk_fn, policy=jax.checkpoint_policies.nothing_saveable
            )
        return blk_fn(blk, x), None

    x, _ = jax.lax.scan(body, x, enc["blocks"])
    return rms_norm(x, enc["final_norm"], cfg.rms_eps)


def forward(
    params,
    cfg: ModelConfig,
    tokens: Optional[jnp.ndarray] = None,      # (b, s_text)
    *,
    embeds: Optional[jnp.ndarray] = None,      # frontend embeddings (b,f,d)
    enc_embeds: Optional[jnp.ndarray] = None,  # enc-dec source features
    policy: Optional[ApproxPolicy] = None,
    caches: Optional[Dict[str, Any]] = None,   # prefill: filled, returned
    remat: bool = True,
    attn_chunk: int = 1024,
    scan_chunk: int = 128,
) -> Tuple[jnp.ndarray, Optional[Dict[str, Any]], jnp.ndarray]:
    """Teacher-forcing / prefill forward.

    Returns (logits (b, s, padded_vocab), caches|None, aux_loss)."""
    inv_freq = jnp.asarray(
        make_rope(cfg.resolved_head_dim, cfg.rope_theta,
                  fraction=0.5 if cfg.rope_style == "half" else 1.0)
    )
    parts = []
    if embeds is not None:
        parts.append(embeds.astype(jnp.bfloat16))
    if tokens is not None:
        parts.append(_embed(params, cfg, tokens))
    x = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)
    if cfg.name.startswith("gemma"):
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    x = constrain(x, ("batch", "seq", "act_embed"))

    enc_out = None
    if cfg.is_encoder_decoder:
        assert enc_embeds is not None
        enc_out = encode(params, cfg, enc_embeds, policy=policy, remat=remat)

    x, new_caches, aux = _scan_blocks(
        params, cfg, x, inv_freq, policy=policy, causal=True,
        caches=caches, pos=None, enc_out=enc_out, remat=remat,
        attn_chunk=attn_chunk, scan_chunk=scan_chunk,
    )
    return _logits(params, cfg, x), new_caches, aux


def decode_step(
    params,
    cfg: ModelConfig,
    caches: Dict[str, Any],
    tokens: jnp.ndarray,          # (b, 1)
    pos: jnp.ndarray,             # scalar int32 — current write position
    *,
    policy: Optional[ApproxPolicy] = None,
    enc_out: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, Dict[str, Any]]:
    """One autoregressive step against a pre-allocated cache."""
    inv_freq = jnp.asarray(
        make_rope(cfg.resolved_head_dim, cfg.rope_theta,
                  fraction=0.5 if cfg.rope_style == "half" else 1.0)
    )
    x = _embed(params, cfg, tokens)
    if cfg.name.startswith("gemma"):
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    x, new_caches, _ = _scan_blocks(
        params, cfg, x, inv_freq, policy=policy, causal=True,
        caches=caches, pos=pos, enc_out=enc_out, remat=False,
        attn_chunk=4096, scan_chunk=1,
    )
    return _logits(params, cfg, x), new_caches
