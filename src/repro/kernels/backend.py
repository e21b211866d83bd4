"""Which way a Pallas kernel runs: compiled for the device, or under the
Pallas interpreter on the CPU backend."""

from __future__ import annotations

from typing import Optional

import jax

__all__ = ["interpret_mode"]


def interpret_mode(interpret: Optional[bool]) -> bool:
    """``interpret`` as given, or None: interpret only when JAX's default
    backend is the CPU.  A TPU caller that leaves it unset gets the
    compiled kernel (or the compiler's error), never the interpreter."""
    if interpret is None:
        return jax.default_backend() == "cpu"
    return bool(interpret)
