"""jit'd public ops for approximate matmul deployment.

``ApproxSpec`` packages everything a deployment site needs about one
circuit choice: the rank-k factors (MXU path), the exhaustive table
(behavioral path) and the signedness.  ``grouped_matmul`` implements the
per-slot assignment semantics of the DSE: the K (contraction) axis is
partitioned into slot groups, each with its own circuit — cost is
sum_c (1 + rank_c) MXU matmuls over that group's columns (DESIGN.md §2).

Also provides symmetric int8 quantization helpers used by
``models/approx_linear.py`` to put bf16 tensors into the 8-bit circuit
domain.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..backend import interpret_mode
from . import ref
from .kernel import lut_matmul_pallas, rank_k_mxu

__all__ = [
    "ApproxSpec",
    "from_circuit",
    "approx_matmul",
    "grouped_matmul",
    "quantize_sym",
    "dequantize",
]


@dataclass(frozen=True)
class ApproxSpec:
    """Deployment data of one circuit at one chosen rank.

    Truncation-family circuits carry ``trunc_bits`` > 0 and rank 0: they
    deploy NATIVELY as a reduced-width integer matmul (operands masked to
    8 - trunc_bits bits) — the MXU-cheap family.  Everything else deploys
    as an int8 base matmul + ``rank`` bf16 correction matmuls."""

    name: str
    signed: bool
    rank: int
    u: np.ndarray          # (256, rank) f32
    v: np.ndarray          # (256, rank) f32
    table: Optional[np.ndarray] = None   # (256,256) i32, behavioral path
    trunc_bits: int = 0    # native reduced-width deployment

    @property
    def width(self) -> int:
        return 8 - self.trunc_bits

    @property
    def is_exact(self) -> bool:
        return self.rank == 0 and self.name.endswith("_exact")


def from_circuit(circuit, rank: Optional[int] = None) -> ApproxSpec:
    """Build an ApproxSpec from an acl.library.Circuit.

    rank=None uses the circuit's faithful deployment rank (0 for exact
    and natively-truncating circuits, the 99%-energy effective rank
    otherwise); an explicit rank is the beyond-paper DSE axis.
    """
    if circuit.kind == "add16":
        raise ValueError("adders do not deploy as matmul corrections")
    native = circuit.native_width is not None
    r = circuit.deploy_rank if rank is None else (0 if native else int(rank))
    if circuit.is_exact or native or r == 0:
        u = np.zeros((256, 0), np.float32)
        v = np.zeros((256, 0), np.float32)
    else:
        f = circuit.factors(r)
        u, v = f.u, f.v
    return ApproxSpec(
        name=circuit.name,
        signed=circuit.signed,
        rank=u.shape[1],
        u=u,
        v=v,
        table=circuit.table.astype(np.int32),
        trunc_bits=circuit.trunc_bits if native else 0,
    )


def _approx_matmul_impl(x, w, u, v, table, *, signed, path, trunc=0):
    if path == "lut":
        return ref.lut_matmul(x, w, table, signed=signed).astype(jnp.float32)
    if trunc:
        # native reduced-width deployment: the truncation IS the circuit.
        # Sign-magnitude masking matches the behavioral mul8s wrapper.
        def _mask(v):
            v = v.astype(jnp.int32)
            return jnp.sign(v) * ((jnp.abs(v) >> trunc) << trunc)
        x, w = _mask(x), _mask(w)
    return ref.rank_k_matmul(x, w, u, v, signed=signed)


# inline=True: deployment graphs call this once PER MUL SLOT inside an
# outer synthesis jit; inlining drops the per-call pjit frames from the
# trace.  XLA flattens the calls during optimization anyway, so the
# optimized HLO — and the cost-analysis labels read off it — are
# unchanged; only lowering gets cheaper.  The non-inlined variant is the
# seed engine's trace, kept for the legacy baseline (below).
_STATIC = ("signed", "path", "trunc")
_approx_matmul_jit = functools.partial(
    jax.jit, static_argnames=_STATIC, inline=True
)(_approx_matmul_impl)
_approx_matmul_jit_outlined = functools.partial(
    jax.jit, static_argnames=_STATIC
)(_approx_matmul_impl)


# The original deployment trace materialized each spec's exhaustive
# (256,256) behavioral table as a graph constant even on the MXU path,
# where it is dead (the static ``path`` branch never reads it), and
# emitted every per-slot call as an outlined pjit.  XLA removes the dead
# constants and flattens the calls before cost analysis — flops /
# bytes-accessed labels are identical either way — but lowering and
# hashing ~256KB of dead literal PER MUL SLOT dominated synthesis time
# on multi-slot accelerators.  The lean trace passes a 1x1 dummy and
# inlines the per-slot calls; flipping this switch restores the seed
# trace exactly (benchmarks use it to measure the old engine as the
# per-genome baseline).
LEGACY_EMBED_TABLES = False

_DUMMY_TABLE = np.zeros((1, 1), np.int32)


def approx_matmul(
    x: jnp.ndarray,
    w: jnp.ndarray,
    spec: ApproxSpec,
    *,
    path: str = "mxu",     # "mxu" (rank-k deployment) | "lut" (behavioral)
    use_pallas: bool = False,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Approximate x @ w under one circuit spec.

    path="mxu": deployment semantics, f32 out.  path="lut": behavioral
    bit-exact semantics.  use_pallas selects the tiled TPU kernels;
    ``interpret`` defaults to interpret mode on the CPU backend only.
    """
    if use_pallas:
        interpret = interpret_mode(interpret)
        if path == "lut":
            return lut_matmul_pallas(
                x, w, jnp.asarray(spec.table), signed=spec.signed,
                interpret=interpret,
            ).astype(jnp.float32)
        return rank_k_mxu(
            x, w, jnp.asarray(spec.u), jnp.asarray(spec.v),
            signed=spec.signed, interpret=interpret,
        )
    if path == "lut" or LEGACY_EMBED_TABLES:
        table = spec.table if spec.table is not None else np.zeros((256, 256), np.int32)
    else:
        table = _DUMMY_TABLE
    fn = _approx_matmul_jit_outlined if LEGACY_EMBED_TABLES else _approx_matmul_jit
    return fn(
        x, w, jnp.asarray(spec.u), jnp.asarray(spec.v), jnp.asarray(table),
        signed=spec.signed, path=path, trunc=spec.trunc_bits,
    )


def grouped_matmul(
    x: jnp.ndarray,                      # (m, k)
    w: jnp.ndarray,                      # (k, n)
    specs: Sequence[ApproxSpec],
    groups: Sequence[Tuple[int, int]],   # [start, stop) K-ranges per spec
    *,
    path: str = "mxu",
) -> jnp.ndarray:
    """Per-slot-group approximate matmul: contraction columns [s, e) of
    group g use circuit specs[g].  This is the deployment form of a DSE
    genome over a matmul accelerator; its compiled cost is
    sum_g (1 + rank_g) partial matmuls — the TPU cost model the surrogates
    learn."""
    assert len(specs) == len(groups)
    out = None
    for spec, (s, e) in zip(specs, groups):
        part = approx_matmul(x[:, s:e], w[s:e, :], spec, path=path)
        out = part if out is None else out + part
    return out


def quantize_sym(
    t: jnp.ndarray, *, axis: Optional[int] = None, bits: int = 8
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Symmetric linear quantization to signed `bits` integers.

    Returns (q, scale) with t ~= q * scale; q in [-(2^(b-1)-1), 2^(b-1)-1].
    axis=None: per-tensor scale; otherwise per-slice along `axis`.
    """
    qmax = float(2 ** (bits - 1) - 1)
    if axis is None:
        amax = jnp.max(jnp.abs(t))
    else:
        amax = jnp.max(jnp.abs(t), axis=axis, keepdims=True)
    scale = jnp.maximum(amax, 1e-12) / qmax
    q = jnp.clip(jnp.round(t / scale), -qmax, qmax).astype(jnp.int32)
    return q, scale


def dequantize(q: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    return q.astype(jnp.float32) * scale
