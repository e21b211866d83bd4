"""Dispatch layer for the population LUT gather.

``gather_xla`` is the traceable building block the fused engine inlines
into its per-accelerator XLA programs on every backend.
``population_lut_gather`` is the standalone op with backend selection:
the XLA gather by default, the numpy reference, or the Pallas kernel
when asked for by name.  The Pallas kernel only runs in interpret mode:
the TPU compiler refuses its 3-D gather ("Only 2D gather is
supported").
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .kernel import population_lut_gather_pallas
from .ref import population_lut_gather_ref

__all__ = ["gather_xla", "population_lut_gather"]


def gather_xla(
    flat_lut: jnp.ndarray,   # (C*S*256,) flattened (C, S, 256) stack
    genes: jnp.ndarray,      # (G, S) int32
    cols: jnp.ndarray,       # (M, S) or (G, M, S) int32 table indices
    *,
    nslots: int,
    per_genome: bool = False,
) -> jnp.ndarray:
    """Traceable ``out[g, m, s] = lut[genes[g, s], s, cols[.., m, s]]``;
    fuses into the surrounding jit.

    Two small gathers: each genome's per-slot tables ``(G, S, 256)``,
    then a row lookup of the columns in them.  One flat gather with an
    index per output element computes the same, but the TPU compiler
    takes minutes over it at G=64 where this form takes about a second.
    """
    lut = flat_lut.reshape(-1, nslots, 256)
    tables = lut[genes, jnp.arange(nslots)[None, :]]          # (G, S, 256)
    lookup = jax.vmap(lambda t, c: jnp.take(t, c, axis=0))
    if per_genome:
        G, M = cols.shape[0], cols.shape[1]
        rows = lookup(tables.reshape(G * nslots, 256),
                      cols.transpose(0, 2, 1).reshape(G * nslots, M))
        return rows.reshape(G, nslots, M).transpose(0, 2, 1)
    rows = lookup(tables.transpose(1, 2, 0), cols.T)          # (S, M, G)
    return rows.transpose(2, 1, 0)


def population_lut_gather(
    lut: np.ndarray,
    genes: np.ndarray,
    cols: np.ndarray,
    *,
    per_genome: bool = False,
    backend: str = "xla",
) -> np.ndarray:
    """(G, M, S) gathered products; ``backend``: "xla", "ref",
    "pallas" or "pallas_interpret"."""
    if backend == "ref":
        return population_lut_gather_ref(lut, genes, cols, per_genome=per_genome)
    lut32 = np.asarray(lut, dtype=np.int32)
    genes32 = np.asarray(genes, dtype=np.int32)
    cols32 = np.asarray(cols, dtype=np.int32)
    if backend in ("pallas", "pallas_interpret"):
        G, S = genes32.shape
        M = cols32.shape[-2]
        bg = _block(G, 8)
        bm = _block(M, 256)
        out = population_lut_gather_pallas(
            jnp.asarray(lut32), jnp.asarray(genes32), jnp.asarray(cols32),
            per_genome=per_genome, bg=bg, bm=bm,
            interpret=(backend == "pallas_interpret"),
        )
        return np.asarray(out)
    if backend == "xla":
        out = jax.jit(gather_xla, static_argnames=("nslots", "per_genome"))(
            jnp.asarray(lut32).reshape(-1), jnp.asarray(genes32),
            jnp.asarray(cols32), nslots=lut32.shape[1],
            per_genome=per_genome,
        )
        return np.asarray(out)
    raise ValueError(f"unknown backend {backend!r}")


def _block(n: int, target: int) -> int:
    """Largest divisor of ``n`` that is <= target (tile size picker)."""
    b = min(n, target)
    while n % b:
        b -= 1
    return b
