"""The paper's accelerators: behavioral correctness, QoR ordering,
deployment-vs-behavioral consistency, genome plumbing."""

import numpy as np
import pytest

from repro.accel import GaussianFilter, HEVCDct, MCMAccelerator
from repro.accel.hevc_dct import HEVC_C, _blocks
from repro.accel.approxfpgas import circuit_level_front, restricted_library
from repro.core.acl.library import default_library

LIB = default_library()


@pytest.fixture(scope="module")
def gauss():
    return GaussianFilter()


@pytest.fixture(scope="module")
def images(gauss):
    return gauss.sample_inputs(2, seed=0)


def test_gaussian_exact_matches_reference(gauss, images):
    circuits, _ = gauss.decode(gauss.exact_genome(LIB), LIB)
    out = gauss.simulate(circuits, images)
    ref = gauss.exact_output(images)
    assert np.array_equal(out, ref)
    assert gauss.qor(circuits, images) == 100.0


def test_gaussian_exact_output_is_smoothing(gauss, images):
    ref = gauss.exact_output(images)
    inner = images[:, 1:-1, 1:-1]
    assert ref.shape == inner.shape
    assert ref.var() < inner.var()  # a Gaussian filter smooths
    assert ref.min() >= 0 and ref.max() <= 255


def test_gaussian_degrades_with_coarser_truncation(gauss, images):
    # k <= 3: beyond that the small coefficients (1,2,4) truncate to zero
    # and PSNR saturates
    psnrs = []
    for k in (1, 2, 3):
        g = gauss.exact_genome(LIB).copy()
        for i in range(9):
            g[i] = LIB.index("mul8u", f"mul8u_trunc{k}")
        circuits, _ = gauss.decode(g, LIB)
        psnrs.append(gauss.qor(circuits, images))
    assert psnrs[0] > psnrs[1] > psnrs[2]


def test_mcm_exact_and_signs():
    for row in range(4):
        m = MCMAccelerator(row)
        inp = m.sample_inputs(1, seed=1)
        circuits, _ = m.decode(m.exact_genome(LIB), LIB)
        out = m.simulate(circuits, inp)
        assert np.array_equal(out, m.exact_output(inp))


def test_hevc_exact_roundtrip():
    h = HEVCDct()
    inp = h.sample_inputs(1, seed=2)
    circuits, _ = h.decode(h.exact_genome(LIB), LIB)
    assert h.qor(circuits, inp) >= 40.0  # renorm shift loses some precision


def test_hevc_reconstruct_same_bits_for_any_buffer_layout():
    """The float inverse-transform tail gives the same bits whatever the
    coefficient buffer's alignment or order (the fused engine is checked
    bit for bit against it), and inverts the forward transform."""
    h = HEVCDct()
    x = _blocks(h.sample_inputs(2, seed=3))
    exact = [([lambda a, b: a * b] * 4, [lambda a, b: a + b] * 3)] * 4
    coeffs = h._transform(x, exact)
    want = h._reconstruct(coeffs)
    raw = np.empty(coeffs.nbytes + 8, np.uint8)  # 8 bytes off alignment
    shifted = np.frombuffer(raw[8:].data, np.int64).reshape(coeffs.shape)
    shifted[...] = coeffs
    for c in (shifted, np.asfortranarray(coeffs), coeffs.copy()):
        assert np.array_equal(h._reconstruct(c), want)
    cinv = np.linalg.inv(HEVC_C.astype(np.float64))
    assert np.allclose(want, cinv @ (coeffs * 256.0) @ cinv.T,
                       rtol=0, atol=1e-12)
    assert np.max(np.abs(want - x)) < 2.0  # the >>8 renorm's rounding


def test_hevc_genome_has_28_slots():
    h = HEVCDct()
    assert len(h.slots) == 28
    assert len(h.mul_slot_indices()) == 16
    assert len(h.mul_slot_constants()) == 16


def test_deployment_cost_scales_with_rank(gauss):
    """XLA synthesis: higher correction rank -> more FLOPs (the cost model
    the DSE exploits)."""
    from repro.core.features.synth import synthesize_variant

    circuits, _ = gauss.decode(gauss.exact_genome(LIB), LIB)
    mit = LIB["mul8u_mitchell"]
    circuits_hi = [mit] * 9 + circuits[9:]
    lo = synthesize_variant(gauss, circuits, [0] * 9)
    hi = synthesize_variant(gauss, circuits_hi, [8] * 9)
    assert hi["flops"] > lo["flops"]
    assert hi["energy"] > lo["energy"]


def test_restricted_library_is_subset_and_pareto():
    rlib = restricted_library(LIB)
    assert len(rlib) < len(LIB)
    for kind in ("mul8u", "mul8s", "add16"):
        front = circuit_level_front(LIB, kind)
        assert any(c.is_exact for c in front)
        assert {c.name for c in rlib.kind(kind)} == {c.name for c in front}


def test_exact_genome_roundtrip(gauss):
    g = gauss.exact_genome(LIB, rank_genes=True)
    circuits, ranks = gauss.decode(g, LIB, rank_genes=True)
    assert all(c.is_exact for c in circuits)
    assert all(r == 0 for r in ranks)
    sizes = gauss.gene_sizes(LIB, rank_genes=True)
    assert len(sizes) == len(gauss.slots) + 9
    assert (g < sizes).all()
