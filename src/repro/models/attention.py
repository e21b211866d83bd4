"""Attention layer: GQA/MQA self-attention (causal or full), cross
attention, RoPE variants, KV-cache decode.  Projections route through
``approx_linear.linear`` so the DSE policy applies."""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..dist.sharding import constrain
from ..kernels.flash_attention import attention as attn_op
from .approx_linear import ApproxPolicy, linear
from .common import ParamSpec, apply_rope, rms_norm
from .config import ModelConfig

__all__ = [
    "attn_param_specs",
    "self_attention",
    "cross_attention",
    "init_kv_cache_spec",
]


def attn_param_specs(cfg: ModelConfig, cross: bool = False) -> Dict[str, ParamSpec]:
    d = cfg.d_model
    hd = cfg.resolved_head_dim
    dt = cfg.compute_dtype  # read only through ``linear``
    p = {
        "norm": ParamSpec((d,), ("norm",), init="zeros"),
        "wq": ParamSpec((d, cfg.n_heads * hd), ("embed", "heads"), dt),
        "wk": ParamSpec((d, cfg.n_kv_heads * hd), ("embed", "kv_heads"), dt),
        "wv": ParamSpec((d, cfg.n_kv_heads * hd), ("embed", "kv_heads"), dt),
        "wo": ParamSpec((cfg.n_heads * hd, d), ("heads", "embed"), dt),
    }
    return p


def gqa_decode_attention(
    q: jnp.ndarray,    # (b, h, 1, d)
    ck: jnp.ndarray,   # (b, kvh, S, d) — kv_seq sharded on "model"
    cv: jnp.ndarray,
    pos: jnp.ndarray,  # scalar: current position (attend to kpos <= pos)
) -> jnp.ndarray:
    """Single-token decode attention, sharding-aware:

    * KV stays seq-sharded (constrained); the query (one token) is
      replicated across the model axis — replicating q is free, gathering
      a 32k-deep KV cache is not.
    * GQA via grouped einsum — no repeat_kv materialization.
    * softmax over the sharded seq axis lowers to partial reductions +
      a tiny all-reduce (the flash-decode pattern).
    """
    b, h, _, d = q.shape
    kvh, s = ck.shape[1], ck.shape[2]
    rep = h // kvh
    ck = constrain(ck, ("batch", "kv_heads", "kv_seq", None))
    cv = constrain(cv, ("batch", "kv_heads", "kv_seq", None))
    qg = constrain(
        q.reshape(b, kvh, rep, d), ("batch", "kv_heads", None, None)
    )
    scale = d ** -0.5
    scores = jnp.einsum(
        "bgrd,bgsd->bgrs", (qg * scale).astype(jnp.float32),
        ck.astype(jnp.float32),
    )
    scores = constrain(scores, ("batch", "kv_heads", None, "kv_seq"))
    mask = jnp.arange(s) <= pos
    scores = jnp.where(mask[None, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bgrs,bgsd->bgrd", probs, cv.astype(jnp.float32))
    return out.reshape(b, h, 1, d).astype(q.dtype)


def _split_heads(x: jnp.ndarray, n_heads: int) -> jnp.ndarray:
    b, s, _ = x.shape
    return x.reshape(b, s, n_heads, -1).transpose(0, 2, 1, 3)  # (b, h, s, d)


def _merge_heads(x: jnp.ndarray) -> jnp.ndarray:
    b, h, s, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, s, h * d)


def self_attention(
    p: Dict[str, jnp.ndarray],
    x: jnp.ndarray,                      # (b, s, d)
    cfg: ModelConfig,
    inv_freq: jnp.ndarray,
    *,
    policy: Optional[ApproxPolicy] = None,
    causal: bool = True,
    positions: Optional[jnp.ndarray] = None,
    cache: Optional[Dict[str, jnp.ndarray]] = None,
    pos: Optional[jnp.ndarray] = None,   # scalar decode position
    attn_chunk: int = 1024,
) -> Tuple[jnp.ndarray, Optional[Dict[str, jnp.ndarray]]]:
    """Returns (out, new_cache).  Modes:
       * train/prefill: cache=None (new_cache=None) or cache given with
         pos=0 -> cache filled with this sequence's K/V.
       * decode: x is (b, 1, d), cache holds S_max positions, pos = index.
    """
    b, s, d = x.shape
    hd = cfg.resolved_head_dim
    h = rms_norm(x, p["norm"], cfg.rms_eps)
    q = _split_heads(linear(h, p["wq"], "qkv", policy), cfg.n_heads)
    k = _split_heads(linear(h, p["wk"], "qkv", policy), cfg.n_kv_heads)
    v = _split_heads(linear(h, p["wv"], "qkv", policy), cfg.n_kv_heads)
    q = constrain(q, ("batch", "act_heads", "seq", None))
    k = constrain(k, ("batch", "kv_heads", "seq", None))

    if pos is not None:
        positions = jnp.zeros((s,), jnp.int32) + pos  # decode: (1,)
    q = apply_rope(q, inv_freq, positions)
    k = apply_rope(k, inv_freq, positions)

    new_cache = None
    if cache is not None:
        ck, cv = cache["k"], cache["v"]
        start = 0 if pos is None else pos
        ck = jax.lax.dynamic_update_slice(
            ck, k.astype(ck.dtype), (0, 0, start, 0)
        )
        cv = jax.lax.dynamic_update_slice(
            cv, v.astype(cv.dtype), (0, 0, start, 0)
        )
        new_cache = {"k": ck, "v": cv}
        if pos is not None:
            k, v = ck, cv
        # prefill (pos None): attend over the locally-computed k/v, NOT
        # the cache copy — re-reading the seq-sharded cache would force
        # SPMD to replicate it (the chunk reshape splits the sharded dim)

    if pos is not None:
        # decode: dedicated sharding-aware single-token attention
        out = gqa_decode_attention(q, k, v, pos)
    else:
        out = attn_op(
            q, k, v, causal=causal, impl="chunked", chunk=attn_chunk,
        )
    out = constrain(out, ("batch", "act_heads", "seq", None))
    y = linear(_merge_heads(out), p["wo"], "attn_out", policy)
    return y, new_cache


def cross_attention(
    p: Dict[str, jnp.ndarray],
    x: jnp.ndarray,                    # (b, s_dec, d)
    enc_out: jnp.ndarray,              # (b, s_enc, d)  (or cached k/v)
    cfg: ModelConfig,
    *,
    policy: Optional[ApproxPolicy] = None,
    cached_kv: Optional[Dict[str, jnp.ndarray]] = None,
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    h = rms_norm(x, p["norm"], cfg.rms_eps)
    q = _split_heads(linear(h, p["wq"], "qkv", policy), cfg.n_heads)
    if cached_kv is None:
        k = _split_heads(linear(enc_out, p["wk"], "qkv", policy), cfg.n_kv_heads)
        v = _split_heads(linear(enc_out, p["wv"], "qkv", policy), cfg.n_kv_heads)
        cached_kv = {"k": k, "v": v}
    else:
        k, v = cached_kv["k"], cached_kv["v"]
    out = attn_op(q, k, v, causal=False, impl="chunked", chunk=1024)
    y = linear(_merge_heads(out), p["wo"], "attn_out", policy)
    return y, cached_kv


def init_kv_cache_spec(cfg: ModelConfig, batch: int, max_len: int):
    """ParamSpec-style declaration of one layer's KV cache (bf16)."""
    hd = cfg.resolved_head_dim
    shape = (batch, cfg.n_kv_heads, max_len, hd)
    logical = ("batch", "kv_heads", "kv_seq", None)
    return {
        "k": ParamSpec(shape, logical, dtype="bfloat16", init="zeros"),
        "v": ParamSpec(shape, logical, dtype="bfloat16", init="zeros"),
    }
