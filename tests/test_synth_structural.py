"""Structural synthesis engine (core/features/synth.py): signature-keyed
compile reuse is LABEL-EXACT — structurally-equal specs from different
named circuits compile to identical cost numbers, ``synthesize_batch``
reproduces the serial per-genome loop byte-identically across every
registered accelerator (incl. staged pipelines and stage views), the
persistent JSONL compile cache does ZERO compiles on a warm rerun, the
first-K verification scheme and its kill switch behave, and a stage
view shares the standalone accelerator's compiles."""

import numpy as np
import pytest

from repro.accel import GaussianFilter, HEVCDct, MCMAccelerator
from repro.accel.smoothed_dct import SmoothedDct
from repro.core.acl.library import default_library
from repro.core.features import synth

LIB = default_library()

# compile-derived label keys (deterministic); latency/energy are
# recomputed per variant from circuits/ranks on top of these
HW_KEYS = ("flops", "hbm_bytes", "latency", "energy")


def _variant(kind, names, n_adds=4):
    by = {c.name: c for c in LIB.kind(kind)}
    adds = list(LIB.kind("add16"))[:n_adds]
    circuits = [by[n] for n in names] + adds
    return circuits, [None] * len(names)


def _random_variants(accel, n, seed, rank_genes=False):
    rng = np.random.default_rng(seed)
    sizes = accel.gene_sizes(LIB, rank_genes=rank_genes)
    genomes = rng.integers(0, sizes[None, :], size=(n, len(sizes)))
    genomes[-1] = genomes[0]     # an exact duplicate rides the batch
    return genomes


def _serial_reference(accel, genomes, rank_genes=False):
    """The PR-4 engine: per-genome synthesize_variant, identity-keyed
    per-context dict cache, structural tier off."""
    synth.reset_fast_codegen()
    keep = synth.STRUCTURAL_KEYS
    synth.STRUCTURAL_KEYS = False
    try:
        cache = {}
        out = []
        for g in genomes:
            circuits, ranks = accel.decode(g, LIB, rank_genes=rank_genes)
            out.append(synth.synthesize_variant(
                accel, circuits, ranks, cache=cache,
            ))
        return out
    finally:
        synth.STRUCTURAL_KEYS = keep
        synth.reset_fast_codegen()


# ---------------------------------------------------------------------------
# (a) structural equality property
# ---------------------------------------------------------------------------

def test_structurally_equal_specs_compile_to_identical_cost_numbers():
    """Different named circuits of one deployment class (same rank /
    trunc bits / signedness), and slot PERMUTATIONS of them, produce
    identical compiled flops and bytes — the invariant the structural
    cache is keyed on.  Compiled with the structural tier OFF so every
    variant really goes through XLA."""
    accel = GaussianFilter()
    variants = [
        _variant("mul8u", ["mul8u_perf1"] * 3 + ["mul8u_drum3"] * 3
                 + ["mul8u_trunc2"] * 3),
        # same classes, different circuits
        _variant("mul8u", ["mul8u_perf4"] * 3 + ["mul8u_drum6"] * 3
                 + ["mul8u_trunc2"] * 3),
        # same classes, permuted slots
        _variant("mul8u", ["mul8u_trunc2"] * 3 + ["mul8u_perf2"] * 3
                 + ["mul8u_drum5"] * 3),
    ]
    keep = synth.STRUCTURAL_KEYS
    synth.STRUCTURAL_KEYS = False
    try:
        recs = [synth.synthesize_variant(accel, c, r) for c, r in variants]
    finally:
        synth.STRUCTURAL_KEYS = keep
    assert len({r["flops"] for r in recs}) == 1
    assert len({r["hbm_bytes"] for r in recs}) == 1
    # and the signature agrees that they are one structure
    from repro.kernels.approx_matmul import from_circuit

    sigs = {
        accel.deploy_signature(
            [from_circuit(c, r) for c, r in zip(cs[:9], rs)]
        )
        for cs, rs in variants
    }
    assert len(sigs) == 1


def test_deploy_signature_distinguishes_real_structure():
    """Rank and truncated width changes MUST re-key: different classes,
    different signature (and genuinely different compiled numbers)."""
    accel = GaussianFilter()
    from repro.kernels.approx_matmul import from_circuit

    def sig(names):
        circuits, ranks = _variant("mul8u", names)
        specs = [from_circuit(c, r)
                 for c, r in zip(circuits[:9], ranks)]
        return accel.deploy_signature(specs)

    base = sig(["mul8u_perf1"] * 9)
    assert sig(["mul8u_perf4"] * 9) == base            # same class
    assert sig(["mul8u_drum3"] * 9) != base            # rank 1 -> 2
    assert sig(["mul8u_trunc2"] * 9) != sig(["mul8u_trunc4"] * 9)


# ---------------------------------------------------------------------------
# (b) synthesize_batch == the serial per-genome loop, everywhere
# ---------------------------------------------------------------------------

def _accelerators():
    return [
        GaussianFilter(),
        MCMAccelerator(0),
        HEVCDct(),
        SmoothedDct(),
    ] + SmoothedDct().stage_views()


@pytest.mark.parametrize("rank_genes", [False, True])
def test_synthesize_batch_matches_serial_loop_all_accelerators(rank_genes):
    for seed, accel in enumerate(_accelerators()):
        genomes = _random_variants(accel, 4, 300 + seed, rank_genes)
        ref = _serial_reference(accel, genomes, rank_genes)
        synth.reset_fast_codegen()
        variants = [accel.decode(g, LIB, rank_genes=rank_genes)
                    for g in genomes]
        recs = synth.synthesize_batch(accel, variants)
        for t, (a, b) in enumerate(zip(ref, recs)):
            for k in HW_KEYS:
                assert a[k] == b[k], (accel.name, t, k)


def test_label_variants_rides_batch_and_matches(tmp_path):
    accel = MCMAccelerator(1)
    genomes = _random_variants(accel, 5, 17)
    inputs = accel.sample_inputs(2, seed=5)
    synth.reset_fast_codegen()
    keep = synth.STRUCTURAL_KEYS
    synth.STRUCTURAL_KEYS = False
    try:
        ref = synth.label_variants(accel, genomes, LIB, qor_inputs=inputs,
                                   cache={})
    finally:
        synth.STRUCTURAL_KEYS = keep
    synth.reset_fast_codegen()
    new = synth.label_variants(accel, genomes, LIB, qor_inputs=inputs,
                               cache={})
    for k in ("qor",) + HW_KEYS:
        assert np.array_equal(ref[k], new[k]), k


# ---------------------------------------------------------------------------
# (c) persistent cache: cold-then-warm does zero compiles, labels exact
# ---------------------------------------------------------------------------

def test_persistent_cache_cold_then_warm_zero_compiles(tmp_path):
    accel = GaussianFilter()
    genomes = _random_variants(accel, 4, 23)
    inputs = accel.sample_inputs(2, seed=2)
    path = str(tmp_path / "synth.jsonl")

    cold = synth.JsonlSynthCache(path)
    ref = synth.label_variants(accel, genomes, LIB, qor_inputs=inputs,
                               synth_cache=cold)
    assert cold.stats()["compiles"] > 0
    cold.close()

    # 'restart': cold module state, fresh cache object on the same file
    synth.reset_fast_codegen()
    warm = synth.JsonlSynthCache(path)
    new = synth.label_variants(accel, genomes, LIB, qor_inputs=inputs,
                               synth_cache=warm)
    assert warm.stats()["compiles"] == 0, warm.stats()
    assert warm.stats()["identity_hits"] > 0
    for k in ("qor",) + HW_KEYS:
        assert np.array_equal(ref[k], new[k]), k


def test_jax_compile_cache_never_serves_a_synthesis_compile(
        tmp_path, monkeypatch):
    """With JAX's persistent compile cache on and keeping every compile,
    a synthesis compile is neither written there nor read back, so
    wall_time always times a compile; the compile-derived labels of a
    second pass (cold in-memory caches) equal the first's."""
    import os

    import jax
    from jax import monitoring
    from jax.experimental.compilation_cache import compilation_cache as cc

    accel = GaussianFilter()
    genomes = _random_variants(accel, 3, 29)
    inputs = accel.sample_inputs(2, seed=2)
    cache_dir = str(tmp_path / "jax_cache")
    seen = {"written": [], "hits": 0}
    inner = synth._compile_cost_uncached
    in_synth = []

    def watched(fn, args, **kw):
        before = set(os.listdir(cache_dir)) if os.path.isdir(cache_dir) \
            else set()
        in_synth.append(True)
        try:
            return inner(fn, args, **kw)
        finally:
            in_synth.pop()
            after = set(os.listdir(cache_dir)) if os.path.isdir(cache_dir) \
                else set()
            seen["written"] += sorted(after - before)

    control_hits = []

    def on_event(event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            if in_synth:
                seen["hits"] += 1
            else:
                control_hits.append(event)

    monkeypatch.setattr(synth, "_compile_cost_uncached", watched)
    prev = (jax.config.jax_compilation_cache_dir,
            jax.config.jax_persistent_cache_min_compile_time_secs)
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    cc.reset_cache()
    monitoring.register_event_listener(on_event)
    try:
        passes = []
        for _ in range(2):
            synth.reset_fast_codegen()
            jax.clear_caches()
            passes.append(synth.label_variants(
                accel, genomes, LIB, qor_inputs=inputs,
                synth_cache=synth.SynthCache()))
        # control: an ordinary compile does land in the cache, and the
        # executable read back from it carries the same cost analysis
        x = jax.ShapeDtypeStruct((64, 64), np.float32)
        f = jax.jit(lambda a: a @ a + 1.0)
        fresh = f.lower(x).compile().cost_analysis()
        jax.clear_caches()
        control_hits.clear()
        hit = f.lower(x).compile().cost_analysis()
        assert control_hits, "the control's second compile was a cache hit"
    finally:
        monitoring.unregister_event_listener(on_event)
        jax.config.update("jax_compilation_cache_dir", prev[0])
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          prev[1])
        cc.reset_cache()
    assert seen == {"written": [], "hits": 0}, seen
    assert os.listdir(cache_dir), "the control compile was cached"
    assert hit["flops"] > 0 and hit["bytes accessed"] > 0, hit
    assert {k: hit[k] for k in synth._COST_KEYS} == \
        {k: fresh[k] for k in synth._COST_KEYS}
    for k in HW_KEYS:
        assert np.array_equal(passes[0][k], passes[1][k]), k
        assert (np.asarray(passes[0][k]) > 0).all(), k


def test_persistent_cache_verification_state_survives_restart(tmp_path):
    """A family verified cold stays verified warm: a NEVER-seen identity
    of a known structure is served with zero compiles after a restart."""
    accel = GaussianFilter()
    path = str(tmp_path / "synth.jsonl")
    same_class = [
        ["mul8u_perf1"] * 9, ["mul8u_perf2"] * 9, ["mul8u_perf3"] * 9,
        ["mul8u_perf4"] * 9,
    ]
    cold = synth.JsonlSynthCache(path)
    synth.synthesize_batch(
        accel, [_variant("mul8u", n) for n in same_class],
        synth_cache=cold,
    )
    s = cold.stats()
    assert s["compiles"] == 3 and s["verify_compiles"] == 2   # 1 fresh + K
    assert s["structural_hits"] == 1
    cold.close()

    synth.reset_fast_codegen()
    warm = synth.JsonlSynthCache(path)
    synth.synthesize_batch(
        accel, [_variant("mul8u", ["mul8u_perf5"] * 9)], synth_cache=warm,
    )
    assert warm.stats()["compiles"] == 0, warm.stats()
    assert warm.stats()["structural_hits"] == 1


# ---------------------------------------------------------------------------
# verification scheme + kill switch
# ---------------------------------------------------------------------------

def test_structural_kill_switch_pins_to_identity_keys():
    accel = MCMAccelerator(2)
    v1 = _variant("mul8s", ["mul8s_perf1"] * 4, n_adds=3)
    v2 = _variant("mul8s", ["mul8s_perf2"] * 4, n_adds=3)
    keep = synth.STRUCTURAL_KEYS
    try:
        synth.STRUCTURAL_KEYS = False
        cache = synth.SynthCache()
        synth.synthesize_batch(accel, [v1, v2], synth_cache=cache)
        s = cache.stats()
        assert s["compiles"] == 2 and s["structural_hits"] == 0
    finally:
        synth.STRUCTURAL_KEYS = keep


def test_pinned_family_stops_structural_serving():
    """A family whose verification diverged must compile every identity
    exactly (structural records stop serving)."""
    accel = MCMAccelerator(3)
    cache = synth.SynthCache()
    v1 = _variant("mul8s", ["mul8s_perf1"] * 4, n_adds=3)
    synth.synthesize_batch(accel, [v1], synth_cache=cache)
    from repro.kernels.approx_matmul import from_circuit

    specs = [from_circuit(c, r) for c, r in zip(v1[0][:4], v1[1])]
    family, _ = accel.deploy_signature(specs)
    fam = synth._digest("fam", tuple(family))
    cache.verdict_pin(fam)
    assert cache.verdict(fam) is False
    v2 = _variant("mul8s", ["mul8s_perf3"] * 4, n_adds=3)
    synth.synthesize_batch(accel, [v2], synth_cache=cache)
    s = cache.stats()
    assert s["compiles"] == 2 and s["structural_hits"] == 0
    assert s["pinned_families"] == 1


def test_pin_after_verified_persists_across_restart(tmp_path):
    """``False == 0`` in Python: a pin landing AFTER the countdown
    reached 0 (verified) must still be appended to the cache file — a
    warm replay that resurrects the family as 'verified' would serve
    structural records for a family proven divergent."""
    path = str(tmp_path / "synth.jsonl")
    cache = synth.JsonlSynthCache(path)
    fam = "famX"
    for _ in range(synth._STRUCT_VERIFY_SAMPLES):
        cache.verdict_pass(fam)
    assert cache.verdict(fam) == 0 and cache.verdict(fam) is not False
    cache.verdict_pin(fam)       # concurrent verifier saw a divergence
    assert cache.verdict(fam) is False
    assert cache.stats()["verified_families"] == 0
    cache.close()
    warm = synth.JsonlSynthCache(path)
    assert warm.verdict(fam) is False, "pin lost across restart"
    warm.close()


def test_reset_fast_codegen_clears_all_verification_state():
    synth._FAST_VERDICT["accel:test"] = False
    shared = synth.shared_synth_cache()
    shared.store({"k": "x", "s": "y", "fam": "z",
                  "flops": 1.0, "hbm_bytes": 2.0})
    synth.reset_fast_codegen()
    assert synth._FAST_VERDICT == {}
    assert len(synth.shared_synth_cache()) == 0
    assert synth.shared_synth_cache() is not shared


# ---------------------------------------------------------------------------
# cross-accelerator sharing: stage view == standalone accelerator
# ---------------------------------------------------------------------------

def test_stage0_view_shares_standalone_gaussian_compiles():
    """smoothed_dct/stage0 deploys the very graphs gaussian3x3 deploys
    (same shapes, same in-situ input): their structural signatures are
    EQUAL, so labeling the view after the standalone accelerator costs
    only the family's first-K verification compiles — after which every
    further view identity is served without touching XLA."""
    pipe = SmoothedDct()
    stage0 = pipe.stage_views()[0]
    gauss = GaussianFilter()
    rng = np.random.default_rng(41)
    sizes = gauss.gene_sizes(LIB)
    genomes = rng.integers(0, sizes[None, :], size=(4, len(sizes)))

    cache = synth.SynthCache()
    synth.synthesize_batch(
        gauss, [gauss.decode(g, LIB) for g in genomes], synth_cache=cache,
    )
    n0 = cache.stats()["compiles"]
    recs = synth.synthesize_batch(
        stage0, [stage0.decode(g, LIB) for g in genomes], synth_cache=cache,
    )
    s = cache.stats()
    # the view's identities are new (different accel name) but its
    # structures are gaussian3x3's: only verification compiles are paid
    assert s["compiles"] == n0 + synth._STRUCT_VERIFY_SAMPLES, s
    assert s["verify_compiles"] == synth._STRUCT_VERIFY_SAMPLES
    assert s["structural_hits"] >= 2
    assert all(r["flops"] > 0 for r in recs)
    # family now verified: NEW view identities of KNOWN structures
    # (multiplier genes rotated -> same sorted class multiset) are free
    more = np.array(genomes[:2])
    more[:, :9] = np.roll(more[:, :9], 1, axis=1)
    synth.synthesize_batch(
        stage0, [stage0.decode(g, LIB) for g in more], synth_cache=cache,
    )
    assert cache.stats()["compiles"] == n0 + synth._STRUCT_VERIFY_SAMPLES


# ---------------------------------------------------------------------------
# process-pool labeler surfaces synth counters
# ---------------------------------------------------------------------------

def test_process_pool_stats_surface_synth_counters(tmp_path):
    from repro.service import EvalContext
    from repro.service.workers import ProcessPoolLabeler

    path = str(tmp_path / "synth.jsonl")
    pool = ProcessPoolLabeler(1, synth_cache_path=path)
    try:
        ctx = EvalContext(MCMAccelerator(0), LIB, n_qor_samples=2)
        assert pool.can_label(ctx)
        genomes = _random_variants(ctx.accel, 3, 7)
        pool.label(ctx, genomes)
        s = pool.stats()
        assert s["synth"]["workers_reporting"] == 1
        assert s["synth"]["compiles"] > 0
        assert s["synth_cache_path"] == path
        import os

        assert os.path.exists(path)
    finally:
        pool.shutdown()
