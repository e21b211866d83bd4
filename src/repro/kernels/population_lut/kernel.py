"""Pallas TPU kernel for the population LUT gather.

Tiling: grid over (genome blocks, element blocks).  The full LUT stack
(C, S, 256) rides along in VMEM — for the repo's libraries that is at
most ~19 x 28 x 256 int32 ≈ 0.5 MB, well under the VMEM budget — and
every (bg, bm) tile performs one flat gather:

    out[g, m, s] = lut[genes[g, s], s, cols[m, s]]

The kernel runs only under ``interpret=True``: the TPU compiler (Mosaic)
refuses the flat gather over a 3-D index ("Only 2D gather is
supported").  The fused engine's hot path on every backend is the plain
XLA gather in ``ops.gather_xla``, which fuses into the surrounding
program.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["population_lut_gather_pallas"]


def _pop_lut_kernel(genes_ref, cols_ref, lut_ref, out_ref, *, nslots):
    genes = genes_ref[...]                      # (bg, S) int32
    cols = cols_ref[...]                        # (bm, S) or (bg, bm, S)
    flat = lut_ref[...].reshape(-1)             # (C*S*256,)
    sidx = jax.lax.broadcasted_iota(jnp.int32, (1, 1, nslots), 2)
    if cols.ndim == 2:
        idx = (genes[:, None, :] * nslots + sidx) * 256 + cols[None, :, :]
    else:
        idx = (genes[:, None, :] * nslots + sidx) * 256 + cols
    out_ref[...] = jnp.take(flat, idx.reshape(-1), axis=0).reshape(idx.shape)


@functools.partial(
    jax.jit, static_argnames=("per_genome", "bg", "bm", "interpret")
)
def population_lut_gather_pallas(
    lut: jnp.ndarray,     # (C, S, 256) int32
    genes: jnp.ndarray,   # (G, S) int32
    cols: jnp.ndarray,    # (M, S) or (G, M, S) int32 table indices
    *,
    per_genome: bool = False,
    bg: int = 8,
    bm: int = 256,
    interpret: bool = False,
) -> jnp.ndarray:
    C, S, _ = lut.shape
    G = genes.shape[0]
    M = cols.shape[-2]
    assert G % bg == 0 and M % bm == 0, (G, M, bg, bm)
    grid = (G // bg, M // bm)
    if per_genome:
        cols_spec = pl.BlockSpec((bg, bm, S), lambda i, j: (i, j, 0))
    else:
        cols_spec = pl.BlockSpec((bm, S), lambda i, j: (j, 0))
    kernel = functools.partial(_pop_lut_kernel, nslots=S)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bg, S), lambda i, j: (i, 0)),
            cols_spec,
            pl.BlockSpec((C, S, 256), lambda i, j: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((bg, bm, S), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((G, M, S), lut.dtype),
        interpret=interpret,
    )(genes.astype(jnp.int32), cols.astype(jnp.int32), lut)
