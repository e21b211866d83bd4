"""JAX's persistent compilation cache, turned on the same way by every
entry point (the service, the launch CLIs, the fleet worker and the chip
smoke script).

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module sets nothing.  Otherwise the cache lives at ``<checkout>/.jax_cache``:
a fixed path, because the directory is part of what a later run must find
again, so it never comes from a temporary name, a pid or the time."""

from __future__ import annotations

import os

__all__ = ["CACHE_DIR", "enable"]

# src/repro/compile_cache.py -> <checkout>/.jax_cache
_SRC = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CACHE_DIR = os.path.join(os.path.dirname(_SRC), ".jax_cache")


def enable() -> str:
    """Turn the persistent cache on before the first compile; returns the
    directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
