"""Public op: dispatches between the chunked associative-scan (XLA
composed — differentiable, used by training) and the fused Pallas kernel
(TPU serving/forward path; interpret mode by default on the CPU
backend only)."""

from __future__ import annotations

from typing import Optional, Tuple

import jax.numpy as jnp

from ..backend import interpret_mode
from .kernel import selective_scan_pallas
from .ref import selective_scan_reference

__all__ = ["selective_scan"]


def selective_scan(
    x, dt, A, B, C, h0=None, *, impl: str = "reference",
    interpret: Optional[bool] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    if h0 is None:
        h0 = jnp.zeros((x.shape[0], x.shape[2], A.shape[1]), jnp.float32)
    if impl == "pallas":
        return selective_scan_pallas(
            x, dt, A, B, C, h0, interpret=interpret_mode(interpret))
    return selective_scan_reference(x, dt, A, B, C, h0)
