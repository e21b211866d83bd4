"""The controls, at a size a test run can hold: the reference put in the
program's place in the next lower precision must come out not correct
where the program comes out correct.  On the chip the same readings are
taken at the cells' own sizes with ``bench/calibrate.py``."""

import numpy as np
import pytest

import rehearsal
from benchkit import harness
from benchkit.refs import gaussian as ref


def test_gaussian_bfloat16_control_fails_the_exact_comparison():
    from repro.core.acl.library import default_library
    from repro.service.campaigns import make_accelerator

    c = harness.load_json(f"{harness.ROOT}/bench/configs/gaussian3x3.json")
    q = c["qor"]
    accel = make_accelerator("gaussian3x3")
    images = ref.sample_images(q["samples"], q["image"], q["seed"])
    assert np.array_equal(images, accel.sample_inputs(q["samples"], seed=q["seed"]))
    rng = np.random.default_rng(5)
    genomes = np.stack([rng.integers(0, 23, 8) for _ in range(9)]
                       + [rng.integers(0, 15, 8) for _ in range(8)], axis=1)
    genomes[0] = ref.exact_genome()
    served = accel.qor_batch(genomes, default_library(), images)
    limit = c["limits"]["qor_gap_db"]
    for g, s in zip(genomes, served):
        exact = ref.qor(images, g)
        assert abs(s - exact) <= limit
        assert abs(ref.qor(images, g, precision="bfloat16") - exact) > limit


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 17, 40])
def test_lm_float8_control_fails_where_the_program_passes(seed):
    r = rehearsal.tiny("lm.granite-8b.chat")
    run = harness.Run("lm.granite-8b.chat", seed, 0, 0, r, harness.ROOT)
    driver = harness.driver_for(run.traffic).Driver(run)
    try:
        driver.setup()
        ((_, program, control),) = driver.calibrate([seed])
    finally:
        driver.close()
    limit = r["config"]["limits"]["lm_logit_gap"]
    assert program <= limit < control


def test_gaussian_deploy_ranks_follow_from_the_circuit_models():
    """The configuration's deploy widths and ranks, from the reference's
    own circuit models: the fewest singular values of each error table
    that hold 99% of its energy; truncating circuits deploy natively."""
    c = harness.load_json(f"{harness.ROOT}/bench/configs/gaussian3x3.json")
    a = np.arange(256)[:, None]
    b = np.arange(256)[None, :]
    exact = ref.mul_exact(a, b)
    for name, model in zip(c["circuits"]["mul8u"], ref.MUL8U):
        width, rank = c["deploy"]["mul8u"][name]
        if "trunc" in name:
            assert (width, rank) == (8 - model.keywords["k"], 0), name
            continue
        s = np.linalg.svd((model(a, b) - exact).astype(np.float64),
                          compute_uv=False)
        energy = np.cumsum(s ** 2) / max(float((s ** 2).sum()), 1e-300)
        want = 0 if not s.any() else int(np.searchsorted(energy, 0.99) + 1)
        assert (width, rank) == (8, want), name


def test_synthesis_reference_gives_the_programs_labels():
    """Each genome compiled alone by the program, with no cache to serve
    it, against the reference's own deploy graph: every circuit in every
    slot, and slots that share circuits."""
    from repro.core.acl.library import default_library
    from repro.core.features import synth
    from repro.service.campaigns import make_accelerator

    from benchkit.refs import synth as synth_ref

    c = harness.load_json(f"{harness.ROOT}/bench/configs/gaussian3x3.json")
    accel = make_accelerator("gaussian3x3")
    lib = default_library()
    rng = np.random.default_rng(8)
    genomes = [[i] * 9 + [0] * 8 for i in range(23)]
    genomes += [list(rng.integers(0, 23, 9)) + [0] * 8 for _ in range(4)]
    genomes += [[7, 8, 7, 8, 7, 8, 7, 8, 22] + [0] * 8]
    for g in genomes:
        got = synth.synthesize_batch(accel, [accel.decode(np.array(g), lib)],
                                     synth_cache=synth.SynthCache())[0]
        want = synth_ref.labels(c, g)
        assert {k: got[k] for k in want} == want, g
