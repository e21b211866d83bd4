"""Public attention op: dispatches between the naive reference, the
chunked scan (production path on any backend) and the Pallas flash kernel
(TPU target; interpret mode by default on the CPU backend only)."""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from ..backend import interpret_mode
from .kernel import flash_attention_fwd
from .ref import chunked_attention, mha_reference, repeat_kv

__all__ = ["attention"]


def attention(
    q: jnp.ndarray,          # (b, h, sq, d)
    k: jnp.ndarray,          # (b, kvh, sk, d)
    v: jnp.ndarray,          # (b, kvh, sk, d)
    *,
    causal: bool = True,
    impl: str = "chunked",   # "chunked" | "naive" | "pallas"
    chunk: int = 1024,
    q_offset: int = 0,
    interpret: Optional[bool] = None,
    scale: Optional[float] = None,   # None -> d ** -0.5
) -> jnp.ndarray:
    if impl == "naive":
        return mha_reference(q, k, v, causal=causal, scale=scale,
                             q_offset=q_offset)
    if impl == "chunked":
        return chunked_attention(
            q, k, v, causal=causal, scale=scale,
            chunk=min(chunk, k.shape[2]), q_offset=q_offset,
        )
    if scale is not None:
        raise ValueError("the Pallas kernel scales by d ** -0.5 only")
    if impl == "pallas":
        b, h, sq, d = q.shape
        kvh = k.shape[1]
        kr = repeat_kv(k, h // kvh)
        vr = repeat_kv(v, h // kvh)
        out = flash_attention_fwd(
            q.reshape(b * h, sq, d),
            kr.reshape(b * h, -1, d),
            vr.reshape(b * h, -1, d),
            causal=causal,
            q_offset=q_offset,
            interpret=interpret_mode(interpret),
        )
        return out.reshape(b, h, sq, d)
    raise ValueError(f"unknown attention impl {impl!r}")
