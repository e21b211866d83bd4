"""Batched labeling engine: population sim bit-exactness vs the
per-genome loop (all registered accelerators incl. staged pipelines and
stage views), vectorized PSNR, guarded fast codegen label-invariance,
process-backend label identity, put_many, and the scheduler's
single-campaign admission-window skip."""

import os
import time

import numpy as np
import pytest

from repro.accel import GaussianFilter, HEVCDct, MCMAccelerator
from repro.accel.smoothed_dct import SmoothedDct
from repro.core import qor as qor_mod
from repro.core.acl.library import default_library
from repro.service import (
    EvalContext,
    EvalScheduler,
    InMemoryLabelStore,
    JsonlLabelStore,
)
from repro.service.store import LABEL_KEYS

LIB = default_library()

# label keys that are a pure function of (context, genome) — timing keys
# (synth_time / sim_time) legitimately differ between runs/backends
DET_KEYS = ("qor", "latency", "energy", "flops", "hbm_bytes")


def _accelerators():
    return [
        GaussianFilter(),
        MCMAccelerator(0),
        MCMAccelerator(2),
        HEVCDct(),
        SmoothedDct(),
    ] + SmoothedDct().stage_views()


def _random_genomes(accel, rng, n, rank_genes):
    sizes = accel.gene_sizes(LIB, rank_genes=rank_genes)
    g = rng.integers(0, sizes[None, :], size=(n, len(sizes)))
    g[0] = accel.exact_genome(LIB, rank_genes=rank_genes)
    return g


# ---------------------------------------------------------------------------
# population simulation == per-genome loop (bit-exact)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("rank_genes", [False, True])
def test_simulate_batch_bit_exact_all_accelerators(rank_genes):
    """Property: the vectorized population path (LUT gathers + grouped
    adders + chained per-genome intermediates) is BIT-EXACT versus
    decoding and simulating each genome independently."""
    for seed, accel in enumerate(_accelerators()):
        rng = np.random.default_rng(100 + seed)
        inputs = accel.sample_inputs(2, seed=seed)
        genomes = _random_genomes(accel, rng, 5, rank_genes)
        batch = accel.simulate_batch(
            genomes, LIB, inputs, rank_genes=rank_genes
        )
        for t, g in enumerate(genomes):
            circuits, _ = accel.decode(g, LIB, rank_genes=rank_genes)
            ref = accel.simulate(circuits, inputs)
            assert np.array_equal(batch[t], ref), (accel.name, t)


@pytest.mark.parametrize("rank_genes", [False, True])
def test_qor_batch_bit_exact_all_accelerators(rank_genes):
    for seed, accel in enumerate(_accelerators()):
        rng = np.random.default_rng(200 + seed)
        inputs = accel.sample_inputs(2, seed=seed)
        genomes = _random_genomes(accel, rng, 4, rank_genes)
        qb = accel.qor_batch(genomes, LIB, inputs, rank_genes=rank_genes)
        for t, g in enumerate(genomes):
            circuits, _ = accel.decode(g, LIB, rank_genes=rank_genes)
            assert qb[t] == accel.qor(circuits, inputs), (accel.name, t)
        # the exact anchor saturates at the PSNR cap
        assert qb[0] == qor_mod.PSNR_CAP


def test_simulate_batch_per_genome_inputs():
    """A per-genome input stack (what staged pipelines feed forward)
    matches simulating each genome on its own input."""
    accel = HEVCDct()
    rng = np.random.default_rng(7)
    genomes = _random_genomes(accel, rng, 3, False)
    stack = np.stack([accel.sample_inputs(2, seed=s) for s in range(3)])
    batch = accel.simulate_batch(
        genomes, LIB, stack, per_genome_inputs=True
    )
    for t, g in enumerate(genomes):
        circuits, _ = accel.decode(g, LIB)
        assert np.array_equal(batch[t], accel.simulate(circuits, stack[t]))


def test_split_genome_batch_matches_split_genome():
    pipe = SmoothedDct()
    rng = np.random.default_rng(3)
    for rank_genes in (False, True):
        genomes = _random_genomes(pipe, rng, 4, rank_genes)
        parts = pipe.split_genome_batch(genomes, rank_genes=rank_genes)
        for t, g in enumerate(genomes):
            for part, ref in zip(parts, pipe.split_genome(
                    g, rank_genes=rank_genes)):
                assert np.array_equal(part[t], ref)


def test_psnr_batch_matches_psnr():
    rng = np.random.default_rng(11)
    ref = rng.normal(size=(3, 16, 16)) * 40
    outs = ref[None] + rng.normal(size=(6, 3, 16, 16))
    outs[0] = ref  # exact row saturates at the cap
    vals = qor_mod.psnr_batch(ref, outs)
    assert vals[0] == qor_mod.PSNR_CAP
    for t in range(len(outs)):
        assert vals[t] == qor_mod.psnr(ref, outs[t])
    # explicit peak forwards too
    vals_p = qor_mod.psnr_batch(ref, outs, peak=100.0)
    for t in range(len(outs)):
        assert vals_p[t] == qor_mod.psnr(ref, outs[t], peak=100.0)


def test_im2col_cache_returns_same_windows():
    from repro.accel.gaussian import _IM2COL_CACHE, _im2col, _im2col_cached

    imgs = GaussianFilter().sample_inputs(2, seed=5)
    a = _im2col_cached(imgs)
    b = _im2col_cached(imgs.copy())   # same content -> cache hit
    assert a is b
    assert np.array_equal(a, _im2col(imgs))
    assert not a.flags.writeable     # cached windows are frozen
    assert len(_IM2COL_CACHE) >= 1


# ---------------------------------------------------------------------------
# label_variants rides the batched path; engine knobs stay label-invariant
# ---------------------------------------------------------------------------

def test_label_variants_qor_matches_per_genome():
    from repro.core.features import synth

    accel = MCMAccelerator(1)
    rng = np.random.default_rng(17)
    genomes = _random_genomes(accel, rng, 3, False)
    inputs = accel.sample_inputs(2, seed=synth.DEFAULT_QOR_SEED)
    labels = synth.label_variants(
        accel, genomes, LIB, qor_inputs=inputs, cache={}
    )
    for t, g in enumerate(genomes):
        circuits, _ = accel.decode(g, LIB)
        assert labels["qor"][t] == accel.qor(circuits, inputs)


def test_fast_codegen_and_lean_trace_are_label_invariant():
    """The engine's compile-side knobs (guarded fast codegen, lean
    deployment trace) must not move a single deterministic label."""
    import repro.kernels.approx_matmul.ops as ops
    from repro.core.features import synth

    accel = MCMAccelerator(2)
    rng = np.random.default_rng(23)
    genomes = _random_genomes(accel, rng, 3, False)
    fast0 = synth.FAST_CODEGEN
    try:
        synth.FAST_CODEGEN = False
        ops.LEGACY_EMBED_TABLES = True
        seed_labels = synth.label_variants(accel, genomes, LIB, cache={})
        ops.LEGACY_EMBED_TABLES = False
        synth.FAST_CODEGEN = True
        # cold engine for the second run: the shared compile cache would
        # otherwise answer from the seed run's compiles and nothing new
        # would compile (exactly the leak reset_fast_codegen exists for)
        synth.reset_fast_codegen()
        new_labels = synth.label_variants(accel, genomes, LIB, cache={})
    finally:
        synth.FAST_CODEGEN = fast0
        ops.LEGACY_EMBED_TABLES = False
    for k in DET_KEYS:
        assert np.array_equal(seed_labels[k], new_labels[k]), k
    assert synth._FAST_VERDICT.get(f"accel:{accel.name}") is not None


# ---------------------------------------------------------------------------
# stores: put_many
# ---------------------------------------------------------------------------

def test_put_many_inmemory_and_jsonl(tmp_path):
    rec = lambda v: {k: float(v) for k in LABEL_KEYS}
    mem = InMemoryLabelStore()
    mem.put_many([("a", rec(1)), ("b", rec(2))])
    assert mem.get("a") == rec(1) and mem.get("b") == rec(2)

    path = str(tmp_path / "labels.jsonl")
    store = JsonlLabelStore(path)
    store.put("a", rec(1))
    # batch: one new, one duplicate (index update only, no new line)
    store.put_many([("a", rec(1)), ("b", rec(2)), ("c", rec(3))])
    s = store.stats()
    assert s["lines"] == 3 and s["entries"] == 3
    store.close()
    again = JsonlLabelStore(path)
    assert again.get("b") == rec(2) and again.get("c") == rec(3)
    assert again.stats()["lines"] == 3
    again.close()

    empty = JsonlLabelStore(str(tmp_path / "empty.jsonl"))
    empty.put_many([])                     # no-op, no file churn
    assert empty.stats()["lines"] == 0
    empty.close()


# ---------------------------------------------------------------------------
# scheduler: single-campaign latency + process backend
# ---------------------------------------------------------------------------

class _InstantCtx:
    fingerprint = "instant"
    accel = None

    def key(self, genome):
        return "g" + "-".join(str(int(v)) for v in np.atleast_1d(genome))

    def ground_truth(self, genomes):
        genomes = np.atleast_2d(genomes)
        v = genomes.sum(axis=1).astype(float)
        return {k: v.copy() for k in LABEL_KEYS}


def test_single_campaign_skips_admission_window():
    """With one campaign pending, a batch must dispatch without eating
    the (deliberately huge) admission window."""
    sched = EvalScheduler(InMemoryLabelStore(), n_workers=1,
                          max_batch=8, max_wait_s=5.0)
    t0 = time.perf_counter()
    out = sched.label(_InstantCtx(), np.arange(8).reshape(4, 2),
                      campaign="solo")
    elapsed = time.perf_counter() - t0
    assert out["qor"].tolist() == [1.0, 5.0, 9.0, 13.0]
    assert elapsed < 2.0, f"single campaign waited {elapsed:.2f}s"
    sched.shutdown()


def test_scheduler_rejects_unknown_backend():
    with pytest.raises(ValueError, match="backend"):
        EvalScheduler(InMemoryLabelStore(), backend="gpu")


def test_process_backend_labels_identical_to_thread():
    """Process-pool labels must be byte-identical to in-process labels,
    and non-resolvable contexts must fall back transparently."""
    accel = MCMAccelerator(1)
    ctx_t = EvalContext(accel, LIB, n_qor_samples=2)
    rng = np.random.default_rng(31)
    genomes = _random_genomes(accel, rng, 3, False)

    sched_t = EvalScheduler(InMemoryLabelStore(), n_workers=1,
                            max_wait_s=0.01)
    out_t = sched_t.label(ctx_t, genomes)
    sched_t.shutdown()

    sched_p = EvalScheduler(InMemoryLabelStore(), n_workers=1,
                            max_wait_s=0.01, backend="process",
                            process_workers=1)
    out_p = sched_p.label(
        EvalContext(MCMAccelerator(1), LIB, n_qor_samples=2), genomes
    )
    for k in DET_KEYS:
        assert np.array_equal(out_t[k], out_p[k]), k
    s = sched_p.stats()
    assert s["backend"] == "process" and s["process_batches"] == 1

    # a context the worker cannot rebuild by name falls back in-process
    out_f = sched_p.label(_InstantCtx(), np.arange(4).reshape(2, 2))
    assert out_f["qor"].tolist() == [1.0, 5.0]
    assert sched_p.stats()["process_fallbacks"] == 1
    sched_p.shutdown()


def test_process_pool_can_label_gates_contexts():
    from repro.service.workers import ProcessPoolLabeler

    pool = ProcessPoolLabeler.__new__(ProcessPoolLabeler)  # no processes
    pool._lock = __import__("threading").Lock()
    pool._safe_fps = {}
    # builtin accelerator with the default library: safe
    assert pool.can_label(EvalContext(MCMAccelerator(1), LIB,
                                      n_qor_samples=2))
    # subset library changes the fingerprint: NOT safe
    sub = LIB.subset([c.name for c in LIB.circuits[:40]])
    assert not pool.can_label(EvalContext(MCMAccelerator(1), sub,
                                          n_qor_samples=2))
    # verdicts are cached per fingerprint
    assert len(pool._safe_fps) == 2


def test_process_pool_refuses_an_accelerator_backend(monkeypatch):
    """On a chip the parent already holds the device, so the process
    backend must refuse at construction (naming the thread backend)
    instead of spawning children that hang on the device lock."""
    import jax

    from repro.service.workers import ProcessPoolLabeler

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="--eval-backend thread"):
        ProcessPoolLabeler(1)
