"""HEVC 4x4 integer DCT — the paper's evaluation application (§IV).

The forward transform matrix (HEVC core transform, [25]):

    C = [[64,  64,  64,  64],
         [83,  36, -36, -83],
         [64, -64, -64,  64],
         [36, -83,  83, -36]]

Each output row i is one multiple-constant-multiplication block MCM_i:
four signed 8-bit multipliers (|constants| <= 83) + a 3-adder tree.  The
2-D transform applies the four MCMs column-wise, renormalizes (>>8, the
HEVC first-stage shift adapted to keep the 8-bit circuit domain), then
row-wise.  QoR = PSNR of the exact-IDCT reconstruction from approximate
coefficients vs the reconstruction from exact coefficients, over 4x4
blocks of the synthetic image set.

Adders run on 16-bit two's-complement patterns via ``signed16``.
"""

from __future__ import annotations

from typing import Callable, List, Optional, Sequence, Tuple

import numpy as np

from ..core.acl.library import Circuit, Library
from . import fused
from ._batchsim import grouped_apply, lut_gather, mul_lut
from .base import Accelerator, Slot
from .images import sample_images

__all__ = ["HEVC_C", "MCMAccelerator", "HEVCDct", "signed16"]

HEVC_C = np.array(
    [
        [64, 64, 64, 64],
        [83, 36, -36, -83],
        [64, -64, -64, 64],
        [36, -83, 83, -36],
    ],
    dtype=np.int64,
)

_SHIFT1 = 8  # stage-1 renormalization to stay in the signed 8-bit domain

# C's rows are orthogonal, so C^-1 = C^T diag(1 / |row|^2): each entry is
# one correctly rounded division, the same bits on every host
_ROW_NORMS = (HEVC_C * HEVC_C).sum(axis=1)
assert np.array_equal(HEVC_C @ HEVC_C.T, np.diag(_ROW_NORMS))
_CINV = HEVC_C.T.astype(np.float64) / _ROW_NORMS


def signed16(fn: Callable) -> Callable:
    """Lift an unsigned 16-bit adder model to signed two's complement:
    wrap to 16 bits, apply, sign-extend."""

    def wrapped(a, b):
        a16 = np.asarray(a, dtype=np.int64) & 0xFFFF
        b16 = np.asarray(b, dtype=np.int64) & 0xFFFF
        s = np.asarray(fn(a16, b16), dtype=np.int64) & 0xFFFF
        return np.where(s >= 0x8000, s - 0x10000, s)

    return wrapped


def _blocks(images: np.ndarray) -> np.ndarray:
    """(..., n, H, W) uint8 -> (..., m, 4, 4) signed residual blocks
    (pixel - 128); leading axes (e.g. a genome batch) pass through."""
    lead, (n, h, w) = images.shape[:-3], images.shape[-3:]
    h4, w4 = h - h % 4, w - w % 4
    x = images[..., :h4, :w4].reshape(lead + (n, h4 // 4, 4, w4 // 4, 4))
    x = np.moveaxis(x, -2, -3).reshape(lead + (-1, 4, 4))
    return x.astype(np.int64) - 128


def _mcm_apply(row: int, x: np.ndarray, muls, adds) -> np.ndarray:
    """y = sum_j C[row, j] * x[..., j] with per-slot circuits.

    x: (..., 4) signed 8-bit domain values."""
    coeffs = HEVC_C[row]
    # mul8s behavioral models are sign-magnitude wrapped: f(x, -c) = -f(x, c)
    prods = [muls[j](x[..., j], int(coeffs[j])) for j in range(4)]
    s0 = adds[0](prods[0], prods[1])
    s1 = adds[1](prods[2], prods[3])
    return adds[2](s0, s1)


def _rshift_round(v: np.ndarray, k: int) -> np.ndarray:
    return (v + (1 << (k - 1))) >> k


def _mcm_apply_batch(
    row: int,
    x: np.ndarray,
    mul_genes: np.ndarray,
    add_genes: np.ndarray,
    library: Library,
    *,
    per_genome: bool,
) -> np.ndarray:
    """Population MCM: products via one signed LUT gather (index = value
    + 128), adder tree grouped by distinct circuit.  ``x``: (..., 4)
    shared or (G, ..., 4) per-genome; returns (G, ...)."""
    lut = mul_lut(library, "mul8s", HEVC_C[row], tag=f"mcm{row}")
    prods = lut_gather(lut, mul_genes, x + 128, per_genome=per_genome)
    add_fns = [signed16(c.fn) for c in library.kind("add16")]
    s0 = grouped_apply(add_fns, add_genes[:, 0], prods[..., 0], prods[..., 1])
    s1 = grouped_apply(add_fns, add_genes[:, 1], prods[..., 2], prods[..., 3])
    return grouped_apply(add_fns, add_genes[:, 2], s0, s1)


class MCMAccelerator(Accelerator):
    """One MCM block (paper: MCM1..MCM4 of the HEVC use-case)."""

    batched_sim = True

    def __init__(self, row: int):
        assert 0 <= row < 4
        self.row = row
        self.name = f"mcm{row + 1}"
        self.slots = [Slot(f"mul{j}", "mul8s", 1.0) for j in range(4)] + [
            Slot(f"add{j}", "add16", 1.0) for j in range(3)
        ]

    def sample_inputs(self, n: int, seed: int = 0) -> np.ndarray:
        imgs = sample_images(n, size=32, seed=seed)
        return _blocks(imgs).reshape(-1, 4)  # row vectors of residuals

    def _decode(self, circuits: Sequence[Circuit]):
        muls = [c.fn for c in circuits[:4]]
        adds = [signed16(c.fn) for c in circuits[4:]]
        return muls, adds

    def simulate(self, circuits: Sequence[Circuit], inputs: np.ndarray) -> np.ndarray:
        muls, adds = self._decode(circuits)
        return _mcm_apply(self.row, inputs, muls, adds)

    def exact_output(self, inputs: np.ndarray) -> np.ndarray:
        return inputs @ HEVC_C[self.row]

    def simulate_batch(
        self,
        genomes: np.ndarray,
        library: Library,
        inputs: np.ndarray,
        *,
        rank_genes: bool = False,
        per_genome_inputs: bool = False,
    ) -> np.ndarray:
        fused_out = fused.try_simulate_batch(
            self, genomes, library, inputs,
            rank_genes=rank_genes, per_genome_inputs=per_genome_inputs,
        )
        if fused_out is not None:
            return fused_out
        genomes = np.atleast_2d(np.asarray(genomes, dtype=np.int64))
        return _mcm_apply_batch(
            self.row, np.asarray(inputs), genomes[:, :4], genomes[:, 4:7],
            library, per_genome=per_genome_inputs,
        )

    def matmul_shape(self) -> Tuple[int, int, int]:
        return (1024, 4, 1)

    def slot_groups(self) -> List[Tuple[int, int]]:
        return [(j, j + 1) for j in range(4)]

    def mul_slot_constants(self):
        return [int(c) for c in HEVC_C[self.row]]

    def deploy_signature(self, specs):
        from .base import grouped_deploy_signature

        return grouped_deploy_signature(self, specs)

    def build_deploy(self, specs: Sequence, inputs: Optional[np.ndarray] = None):
        import jax.numpy as jnp

        from ..kernels.approx_matmul import grouped_matmul

        if inputs is None:
            inputs = self.sample_inputs(1, seed=1)
        x = jnp.asarray(inputs)                              # (m, 4)
        w = jnp.asarray(HEVC_C[self.row].reshape(4, 1))      # signed constants
        groups = self.slot_groups()

        def fn(x, w):
            return grouped_matmul(x, w, specs, groups)

        return fn, (x, w)


class HEVCDct(Accelerator):
    """Full 2-D 4x4 approximate DCT: 16 mul8s + 12 add16 slots (four MCM
    blocks), applied column-wise then row-wise with a >>8 renorm."""

    name = "hevc_dct4x4"
    batched_sim = True
    deploy_passes = 2  # column stage + row stage

    def __init__(self):
        self.mcms = [MCMAccelerator(r) for r in range(4)]
        self.slots = []
        for m in self.mcms:
            self.slots += [
                Slot(f"{m.name}_{s.name}", s.kind, s.weight) for s in m.slots
            ]

    def sample_inputs(self, n: int, seed: int = 0) -> np.ndarray:
        return sample_images(n, size=32, seed=seed)

    def _split(self, circuits: Sequence[Circuit]):
        per = []
        for r in range(4):
            sub = circuits[r * 7 : (r + 1) * 7]
            muls = [c.fn for c in sub[:4]]
            adds = [signed16(c.fn) for c in sub[4:]]
            per.append((muls, adds))
        return per

    def _transform(self, blocks: np.ndarray, per) -> np.ndarray:
        """blocks: (..., m, 4, 4) -> coefficients (..., m, 4, 4)."""
        # stage 1: columns.  T[i, c] = MCM_i(X[:, c])
        t = np.stack(
            [
                _mcm_apply(r, np.swapaxes(blocks, -1, -2), per[r][0], per[r][1])
                for r in range(4)
            ],
            axis=-2,
        )  # (..., m, 4(row), 4(col))
        t = np.clip(_rshift_round(t, _SHIFT1), -128, 127)
        # stage 2: rows.  Y[i, k] = MCM_k(T[i, :])  (transform the rows)
        y = np.stack(
            [_mcm_apply(r, t, per[r][0], per[r][1]) for r in range(4)],
            axis=-1,
        )  # (..., m, 4, 4)
        return y

    def _reconstruct(self, coeffs: np.ndarray) -> np.ndarray:
        """Exact float inverse of the renormalized forward transform.

        The forward was ``Y ~= (C X C^T) / 2^8`` (stage-1 shift), so
        ``X = C^-1 (Y 2^8) C^-T``, summed term by term in a fixed order
        with elementwise ops.  A BLAS matmul is not used: its rounding
        may change from call to call on one host (the kernel it picks
        can follow the buffers' alignment), and the fused engine's
        output is verified bit for bit against this tail."""
        y = coeffs.astype(np.float64) * (1 << _SHIFT1)
        # t[..., i, l] = sum_k C^-1[i, k] y[..., k, l]
        t = _CINV[:, 0, None] * y[..., None, 0, :]
        for k in range(1, 4):
            t = t + _CINV[:, k, None] * y[..., None, k, :]
        # x[..., i, j] = sum_l t[..., i, l] C^-1[j, l]
        x = t[..., :, 0, None] * _CINV[:, 0]
        for l in range(1, 4):
            x = x + t[..., :, l, None] * _CINV[:, l]
        return x

    def simulate(self, circuits: Sequence[Circuit], inputs: np.ndarray) -> np.ndarray:
        per = self._split(circuits)
        return self._reconstruct(self._transform(_blocks(inputs), per))

    def exact_output(self, inputs: np.ndarray) -> np.ndarray:
        exact = [
            ([lambda a, b: a * b] * 4, [lambda a, b: a + b] * 3) for _ in range(4)
        ]
        return self._reconstruct(self._transform(_blocks(inputs), exact))

    def _transform_batch(
        self,
        blocks: np.ndarray,
        genomes: np.ndarray,
        library: Library,
        *,
        per_genome: bool,
    ) -> np.ndarray:
        """Population transform: gene column 7r+j is MCM r's multiplier
        j, 7r+4+j its adder j (slot concatenation order)."""

        def mcm(r, x, per_g):
            return _mcm_apply_batch(
                r, x,
                genomes[:, 7 * r : 7 * r + 4],
                genomes[:, 7 * r + 4 : 7 * r + 7],
                library, per_genome=per_g,
            )

        xt = np.swapaxes(blocks, -1, -2)
        t = np.stack([mcm(r, xt, per_genome) for r in range(4)], axis=-2)
        t = np.clip(_rshift_round(t, _SHIFT1), -128, 127)
        # stage 2 sees the PER-GENOME intermediate t regardless of how
        # the population's input was shared
        y = np.stack([mcm(r, t, True) for r in range(4)], axis=-1)
        return y

    def simulate_batch(
        self,
        genomes: np.ndarray,
        library: Library,
        inputs: np.ndarray,
        *,
        rank_genes: bool = False,
        per_genome_inputs: bool = False,
    ) -> np.ndarray:
        fused_out = fused.try_simulate_batch(
            self, genomes, library, inputs,
            rank_genes=rank_genes, per_genome_inputs=per_genome_inputs,
        )
        if fused_out is not None:
            return fused_out
        genomes = np.atleast_2d(np.asarray(genomes, dtype=np.int64))
        coeffs = self._transform_batch(
            _blocks(np.asarray(inputs)), genomes, library,
            per_genome=per_genome_inputs,
        )
        return self._reconstruct(coeffs)

    def matmul_shape(self) -> Tuple[int, int, int]:
        return (1024, 4, 4)

    def slot_groups(self) -> List[Tuple[int, int]]:
        # mul slot j of MCM r contracts column j; groups returned MCM-major
        return [(j, j + 1) for _ in range(4) for j in range(4)]

    def mul_slot_constants(self):
        return [int(HEVC_C[r, j]) for r in range(4) for j in range(4)]

    def deploy_signature(self, specs):
        """The 2-D DCT deploys each spec as a (m,1)@(1,1) product in BOTH
        passes; the 16 slots are shape-interchangeable, so classes are
        the sorted multiset.  Its builder is not plain grouped_matmul —
        the family carries the class name (no cross-accelerator sharing)
        plus the canonical deploy input shape, which differs when the
        DCT runs in situ inside a pipeline (smaller intermediate images
        re-block to a different m)."""
        shape = getattr(self, "_native_input_shape", None)
        if shape is None:
            shape = np.shape(self.sample_inputs(1, seed=1))
            self._native_input_shape = shape
        family = ("hevc_dct4x4_2pass", shape,
                  tuple(int(v) for v in self.matmul_shape()))
        classes = tuple(sorted(
            (int(sp.rank), int(sp.trunc_bits), bool(sp.signed))
            for sp in specs
        ))
        return family, classes

    def build_deploy(self, specs: Sequence, inputs: Optional[np.ndarray] = None):
        """Deployment: two grouped matmuls (m,4)@(4,4) with per-(row, j)
        circuit specs, renorm between stages."""
        import jax.numpy as jnp

        from ..kernels.approx_matmul import approx_matmul

        if inputs is None:
            inputs = self.sample_inputs(1, seed=1)
        x = jnp.asarray(_blocks(inputs).reshape(-1, 4))  # (m*4, 4) rows
        w = jnp.asarray(HEVC_C.T)                        # (4, 4): col r = MCM_r

        def fn(x, w):
            outs = []
            for r in range(4):
                cols = []
                for j in range(4):
                    spec = specs[r * 4 + j]
                    cols.append(
                        approx_matmul(x[:, j : j + 1], w[j : j + 1, r : r + 1], spec)
                    )
                outs.append(sum(cols))
            y = jnp.concatenate(outs, axis=1)  # (m*4, 4) stage-1
            y = jnp.clip(jnp.round(y / (1 << _SHIFT1)), -128, 127)
            # stage 2 on the transposed intermediate (same circuit set)
            outs2 = []
            for r in range(4):
                cols = []
                for j in range(4):
                    spec = specs[r * 4 + j]
                    cols.append(
                        approx_matmul(
                            y[:, j : j + 1].astype(jnp.int32),
                            w[j : j + 1, r : r + 1],
                            spec,
                        )
                    )
                outs2.append(sum(cols))
            return jnp.concatenate(outs2, axis=1)

        return fn, (x, w)


# --- fused engine plans ----------------------------------------------------

def _mcm_fused_apply(eng, lut, x, mul_genes, add_genes, per_genome):
    """Traceable twin of ``_mcm_apply_batch``: x (..., 4) residuals
    (leading genome axis iff per_genome), returns (G, ...)."""
    G = mul_genes.shape[0]
    mid = x.shape[1:-1] if per_genome else x.shape[:-1]
    cols = x + 128
    cols = cols.reshape((G, -1, 4)) if per_genome else cols.reshape((-1, 4))
    prods = eng.gather(lut, mul_genes, cols, per_genome=per_genome)
    s0 = eng.select_add(add_genes[:, 0], prods[..., 0], prods[..., 1], signed=True)
    s1 = eng.select_add(add_genes[:, 1], prods[..., 2], prods[..., 3], signed=True)
    out = eng.select_add(add_genes[:, 2], s0, s1, signed=True)
    return out.reshape((G,) + mid)


def _blocks_fused(images):
    """Traceable twin of ``_blocks`` (int32 domain)."""
    import jax.numpy as jnp

    lead, (n, h, w) = images.shape[:-3], images.shape[-3:]
    h4, w4 = h - h % 4, w - w % 4
    x = images[..., :h4, :w4].reshape(lead + (n, h4 // 4, 4, w4 // 4, 4))
    x = jnp.moveaxis(x, -2, -3).reshape(lead + (-1, 4, 4))
    return x - 128


def _prep_i32(inputs):
    return np.ascontiguousarray(np.asarray(inputs), dtype=np.int32)


@fused.register_fused(MCMAccelerator)
def _mcm_fused_plan(accel, library, eng):
    """Single-MCM XLA program; integer outputs, so QoR reduces on-device
    against the exact ``inputs @ C[row]``."""
    lut = eng.lut("mul8s", HEVC_C[accel.row], tag=f"mcm{accel.row}")

    def stage_fn(genes, x, per_genome):
        return _mcm_fused_apply(
            eng, lut, x, genes[:, :4], genes[:, 4:7], per_genome
        )

    return fused.FusedPlan(
        key=(),
        stage_fn=stage_fn,
        prep=_prep_i32,
        post=lambda raw, inputs, per_genome: raw.astype(np.int64),
        qor_ref=lambda a, inputs: np.asarray(a.exact_output(inputs)),
    )


@fused.register_fused(HEVCDct)
def _hevc_fused_plan(accel, library, eng):
    """Full 2-D DCT as one XLA program: in-jit blocking, both MCM
    passes, renorm/clip between.  The device returns the INTEGER
    coefficients; the float64 inverse-transform tail stays on the host
    (``_reconstruct``) because float64 matmul contraction order — and
    hence bits — is XLA-implementation-defined, while the host tail
    (fixed-order elementwise sums, no BLAS) is shared with the numpy
    engine verbatim."""
    import jax.numpy as jnp

    luts = [eng.lut("mul8s", HEVC_C[r], tag=f"mcm{r}") for r in range(4)]

    def stage_fn(genes, x, per_genome):
        blocks = _blocks_fused(x)

        def mcm(r, v, per_g):
            return _mcm_fused_apply(
                eng, luts[r], v,
                genes[:, 7 * r : 7 * r + 4],
                genes[:, 7 * r + 4 : 7 * r + 7],
                per_g,
            )

        xt = jnp.swapaxes(blocks, -1, -2)
        t = jnp.stack([mcm(r, xt, per_genome) for r in range(4)], axis=-2)
        t = jnp.clip((t + (1 << (_SHIFT1 - 1))) >> _SHIFT1, -128, 127)
        y = jnp.stack([mcm(r, t, True) for r in range(4)], axis=-1)
        return y  # integer coefficients (G, ..., m, 4, 4)

    return fused.FusedPlan(
        key=(),
        stage_fn=stage_fn,
        prep=_prep_i32,
        post=lambda raw, inputs, per_genome: accel._reconstruct(
            raw.astype(np.int64)
        ),
        qor_ref=None,
        device_natural=False,
    )
