"""Campaign service: label store persistence (incl. cross-process),
scheduler hit/miss accounting + in-flight dedup, campaign concurrency
with seed-identical results, and the run_dse labeler injection seam."""

import os
import subprocess
import sys
import textwrap
import threading
import time

import numpy as np
import pytest

from repro.accel import MCMAccelerator
from repro.core.acl.library import default_library
from repro.core.dse import DSEConfig, label_unique, run_dse
from repro.core.nsga2 import NSGA2Config
from repro.service import (
    CampaignManager,
    CampaignSpec,
    EvalContext,
    EvalScheduler,
    InMemoryLabelStore,
    JsonlLabelStore,
)
from repro.service.store import LABEL_KEYS

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

SMALL = dict(n_train=10, n_qor_samples=2, pop_size=8, n_parents=4,
             n_generations=2)


def small_cfg(seed=0):
    return DSEConfig(
        n_train=SMALL["n_train"], n_qor_samples=SMALL["n_qor_samples"],
        nsga=NSGA2Config(pop_size=SMALL["pop_size"],
                         n_parents=SMALL["n_parents"],
                         n_generations=SMALL["n_generations"], seed=seed),
        seed=seed,
    )


@pytest.fixture
def ctx():
    return EvalContext(MCMAccelerator(1), default_library(), n_qor_samples=2)


# ---------------------------------------------------------------------------
# store
# ---------------------------------------------------------------------------

def test_store_roundtrip_and_hit_accounting(tmp_path):
    store = JsonlLabelStore(str(tmp_path / "labels.jsonl"))
    rec = {k: float(i) for i, k in enumerate(LABEL_KEYS)}
    assert store.get("k1") is None            # miss
    store.put("k1", rec)
    assert store.get("k1") == rec             # hit
    s = store.stats()
    assert s["hits"] == 1 and s["misses"] == 1 and s["entries"] == 1
    store.close()

    # a fresh instance (same path) replays the file: persistence
    again = JsonlLabelStore(str(tmp_path / "labels.jsonl"))
    assert again.get("k1") == rec
    assert len(again) == 1


def test_store_persists_across_processes(tmp_path, ctx):
    """A child process writes labels; the parent store reads them."""
    path = str(tmp_path / "labels.jsonl")
    code = textwrap.dedent(f"""
        from repro.accel import MCMAccelerator
        from repro.core.acl.library import default_library
        from repro.service import EvalContext, JsonlLabelStore
        import numpy as np
        ctx = EvalContext(MCMAccelerator(1), default_library(), n_qor_samples=2)
        store = JsonlLabelStore({path!r})
        g = ctx.accel.exact_genome(ctx.library)
        labels = ctx.ground_truth(g[None, :])
        store.put(ctx.key(g), {{k: labels[k][0] for k in labels}})
        store.close()
        print("WROTE", ctx.key(g))
    """)
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env={**os.environ, "PYTHONPATH": SRC}, timeout=300,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    key = out.stdout.split("WROTE ")[1].strip()

    store = JsonlLabelStore(path)
    # same context in this process derives the same key (content address)
    g = ctx.accel.exact_genome(ctx.library)
    assert ctx.key(g) == key
    rec = store.get(key)
    assert rec is not None and rec["qor"] > 0


def test_store_compaction(tmp_path):
    """compact() rewrites the log one line per unique key; replay of the
    compacted file is O(unique labels)."""
    import json as _json

    path = str(tmp_path / "labels.jsonl")
    rec = {k: float(i) for i, k in enumerate(LABEL_KEYS)}
    store = JsonlLabelStore(path)
    store.put("k1", rec)
    store.put("k2", rec)
    store.close()
    # duplicates, as left by concurrent writers in other processes
    with open(path, "a") as f:
        for _ in range(3):
            f.write(_json.dumps({"k": "k1", "l": rec, "t": 0.0}) + "\n")
        f.write("not json\n")

    s2 = JsonlLabelStore(path)
    st = s2.stats()
    assert st["lines"] == 6 and st["entries"] == 2
    assert s2.compact() == 4                 # 3 dups + 1 malformed dropped
    assert s2.stats()["lines"] == 2
    assert s2.get("k1") == rec and s2.get("k2") == rec
    with open(path) as f:
        assert len(f.readlines()) == 2
    # appends still work after the rewrite, and a fresh replay sees all
    s2.put("k3", rec)
    s2.close()
    s3 = JsonlLabelStore(path)
    assert len(s3) == 3 and s3.stats()["lines"] == 3
    s3.close()


def test_store_refresh_does_not_recount_own_writes(tmp_path):
    """A store's own appends must not be re-replayed (and re-counted) by
    refresh(), or auto-compaction would fire on duplicate-free files."""
    import json as _json

    path = str(tmp_path / "labels.jsonl")
    rec = {k: 1.0 for k in LABEL_KEYS}
    store = JsonlLabelStore(path, auto_compact_ratio=2.0)
    for i in range(5):
        store.put(f"k{i}", rec)
    store.refresh()
    s = store.stats()
    assert s["lines"] == 5 and s["entries"] == 5
    assert store.compactions == 0       # no spurious auto-compaction
    assert store.compact() == 0         # nothing to drop
    # a foreign append (another process) is still picked up
    with open(path, "a") as f:
        f.write(_json.dumps({"k": "kx", "l": rec, "t": 0.0}) + "\n")
    store.refresh()
    assert store.get("kx") == rec and store.stats()["lines"] == 6
    store.close()


def test_store_auto_compact(tmp_path):
    """Opt-in threshold: replaying a file with > ratio x duplicate lines
    triggers compaction automatically."""
    import json as _json

    path = str(tmp_path / "labels.jsonl")
    rec = {k: 1.0 for k in LABEL_KEYS}
    with open(path, "w") as f:
        for _ in range(10):
            f.write(_json.dumps({"k": "k1", "l": rec, "t": 0.0}) + "\n")

    store = JsonlLabelStore(path, auto_compact_ratio=2.0)
    assert store.compactions == 1
    assert store.stats()["lines"] == 1 and len(store) == 1
    with open(path) as f:
        assert len(f.readlines()) == 1
    store.close()

    # without the opt-in, the file is left as-is
    with open(path, "a") as f:
        for _ in range(10):
            f.write(_json.dumps({"k": "k1", "l": rec, "t": 0.0}) + "\n")
    plain = JsonlLabelStore(path)
    assert plain.compactions == 0 and plain.stats()["lines"] == 11
    plain.close()

    with pytest.raises(ValueError):
        JsonlLabelStore(path, auto_compact_ratio=0.5)


def test_store_compact_races_concurrent_writer_processes(tmp_path):
    """Regression (fleet satellite): compact() racing concurrent
    appender PROCESSES must not drop records.  Before the cross-process
    write lock, the compaction's read-rewrite-rename could miss a torn
    tail another writer was mid-append on (or strand its next appends in
    the dropped inode).  Two subprocess writers append disjoint key
    ranges while the parent compacts in a loop; every key must survive
    in the final file."""
    path = str(tmp_path / "labels.jsonl")
    rec = {k: 1.0 for k in LABEL_KEYS}
    n_keys, n_writers = 120, 2

    writer = textwrap.dedent("""
        import sys
        from repro.service import JsonlLabelStore
        from repro.service.store import LABEL_KEYS
        path, wid, n = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])
        rec = {k: 1.0 for k in LABEL_KEYS}
        store = JsonlLabelStore(path)
        for i in range(n):
            store.put(f"w{wid}-k{i}", rec)
        store.close()
        print("DONE", wid)
    """)
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", writer, path, str(w), str(n_keys)],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env={**os.environ, "PYTHONPATH": SRC},
        )
        for w in range(n_writers)
    ]
    compactor = JsonlLabelStore(path)
    deadline = time.time() + 300
    while any(p.poll() is None for p in procs) and time.time() < deadline:
        compactor.compact()
        time.sleep(0.002)
    for p in procs:
        out, err = p.communicate(timeout=60)
        assert p.returncode == 0, err[-2000:]
        assert "DONE" in out
    compactor.refresh()
    expected = {f"w{w}-k{i}" for w in range(n_writers) for i in range(n_keys)}
    assert {k for k in expected if compactor.get(k) is not None} == expected
    # a final compaction leaves exactly one line per key on disk
    compactor.compact()
    with open(path) as f:
        lines = f.readlines()
    assert len(lines) == len(expected)
    compactor.close()

    fresh = JsonlLabelStore(path)
    assert len(fresh) == len(expected)
    fresh.close()


def test_context_fingerprint_sensitivity(ctx):
    lib = default_library()
    base = ctx.fingerprint
    assert EvalContext(MCMAccelerator(1), lib, n_qor_samples=2).fingerprint == base
    # different accel / rank_genes / qor signature / library all re-key
    assert EvalContext(MCMAccelerator(0), lib, n_qor_samples=2).fingerprint != base
    assert EvalContext(MCMAccelerator(1), lib, rank_genes=True,
                       n_qor_samples=2).fingerprint != base
    assert EvalContext(MCMAccelerator(1), lib, n_qor_samples=3).fingerprint != base
    sub = lib.subset([c.name for c in lib.circuits[:40]])
    assert EvalContext(MCMAccelerator(1), sub, n_qor_samples=2).fingerprint != base


def test_backend_rekeys_labels_and_compile_cache(monkeypatch):
    """A store or compile cache warmed on one backend must miss on
    another: the backend and device kind are part of both keys."""
    from repro.core.features import synth

    lib = default_library()
    genome = np.zeros(len(MCMAccelerator(1).slots), dtype=np.int64)
    cpu = EvalContext(MCMAccelerator(1), lib, n_qor_samples=2)
    cpu_key, cpu_digest = cpu.key(genome), synth._digest("t", "payload")
    assert synth.backend_tag().startswith("cpu:")
    monkeypatch.setattr(synth, "backend_tag", lambda: "tpu:TPU v5 lite")
    tpu = EvalContext(MCMAccelerator(1), lib, n_qor_samples=2)
    assert tpu.fingerprint != cpu.fingerprint
    assert tpu.key(genome) != cpu_key
    assert synth._digest("t", "payload") != cpu_digest


def test_compile_cache_dir_from_env_else_fixed_in_checkout(monkeypatch):
    """Entry points leave a set JAX_COMPILATION_CACHE_DIR to JAX and
    otherwise use one fixed directory in the checkout."""
    import jax

    from repro import compile_cache

    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "some/cache/dir")
        assert compile_cache.enable() == "some/cache/dir"
        assert jax.config.jax_compilation_cache_dir == prev
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        root = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
        want = os.path.join(root, ".jax_cache")
        assert compile_cache.enable() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


# ---------------------------------------------------------------------------
# scheduler
# ---------------------------------------------------------------------------

class _CountingCtx:
    """EvalContext stand-in with an observable, slowable ground truth."""

    def __init__(self, delay=0.0):
        self.fingerprint = "testctx"
        self.calls = []
        self.delay = delay
        self._lock = threading.Lock()

    def key(self, genome):
        return "g" + "-".join(str(int(v)) for v in np.atleast_1d(genome))

    def ground_truth(self, genomes):
        genomes = np.atleast_2d(genomes)
        with self._lock:
            self.calls.append(len(genomes))
        if self.delay:
            time.sleep(self.delay)
        n = len(genomes)
        val = genomes.sum(axis=1).astype(float)
        return {k: val.copy() for k in LABEL_KEYS}


def test_scheduler_store_hits_and_batching():
    store = InMemoryLabelStore()
    sched = EvalScheduler(store, n_workers=2, max_batch=8, max_wait_s=0.01)
    ctx = _CountingCtx()
    genomes = np.arange(12).reshape(6, 2)
    out = sched.label(ctx, genomes, campaign="a")
    assert np.array_equal(out["qor"], genomes.sum(axis=1).astype(float))
    assert sum(ctx.calls) == 6

    # identical batch again: all store hits, no new ground truth
    out2 = sched.label(ctx, genomes, campaign="b")
    assert np.array_equal(out2["qor"], out["qor"])
    assert sum(ctx.calls) == 6
    s = sched.stats()
    assert s["store_hits"] == 6 and s["labeled"] == 6
    assert s["per_campaign"]["b"]["store_hits"] == 6
    assert s["per_campaign"]["b"]["labeled"] == 0
    sched.shutdown()


def test_scheduler_inflight_dedup():
    """Two concurrent requests for one genome -> one ground-truth call."""
    store = InMemoryLabelStore()
    sched = EvalScheduler(store, n_workers=2, max_batch=8, max_wait_s=0.05)
    ctx = _CountingCtx(delay=0.2)
    genomes = np.array([[7, 7], [8, 8]])

    results = {}

    def ask(tag):
        results[tag] = sched.label(ctx, genomes, campaign=tag)

    t1 = threading.Thread(target=ask, args=("a",))
    t2 = threading.Thread(target=ask, args=("b",))
    t1.start(); t2.start(); t1.join(); t2.join()

    assert np.array_equal(results["a"]["qor"], results["b"]["qor"])
    # each unique genome synthesized exactly once across both campaigns
    assert sum(ctx.calls) == 2
    s = sched.stats()
    assert s["labeled"] == 2
    assert s["inflight_dedup_hits"] + s["store_hits"] == 2
    sched.shutdown()


def test_scheduler_duplicate_rows_one_call():
    """Duplicates WITHIN one submit dedupe in flight too."""
    store = InMemoryLabelStore()
    sched = EvalScheduler(store, n_workers=1, max_batch=8, max_wait_s=0.01)
    ctx = _CountingCtx()
    genomes = np.array([[1, 2], [1, 2], [1, 2], [3, 4]])
    out = sched.label(ctx, genomes)
    assert sum(ctx.calls) == 2
    assert out["qor"].tolist() == [3.0, 3.0, 3.0, 7.0]
    assert sched.stats()["inflight_dedup_hits"] == 2
    sched.shutdown()


# ---------------------------------------------------------------------------
# run_dse integration
# ---------------------------------------------------------------------------

def test_run_dse_injected_labeler_matches_default(ctx):
    accel, lib = ctx.accel, ctx.library
    cfg = small_cfg()
    ref = run_dse(accel, lib, cfg)

    store = InMemoryLabelStore()
    sched = EvalScheduler(store, n_workers=2, max_wait_s=0.005)
    res = run_dse(accel, lib, cfg,
                  labeler=lambda g: sched.label(ctx, g))
    assert np.array_equal(ref.front_genomes, res.front_genomes)
    assert np.allclose(ref.front_objectives, res.front_objectives)
    sched.shutdown()


def test_label_unique_scatters_back():
    calls = []

    def labeler(genomes):
        calls.append(len(genomes))
        v = genomes.sum(axis=1).astype(float)
        return {k: v for k in LABEL_KEYS}

    g = np.array([[3, 1], [0, 2], [3, 1], [0, 2], [0, 2]])
    out = label_unique(labeler, g)
    assert calls == [2]                      # only unique rows labeled
    assert out["qor"].tolist() == [4.0, 2.0, 4.0, 2.0, 2.0]


# ---------------------------------------------------------------------------
# campaigns
# ---------------------------------------------------------------------------

def test_two_concurrent_campaigns_share_labels(tmp_path):
    """Acceptance: two concurrent campaigns produce seed-identical
    fronts, every unique genome is synthesized once (in-flight dedup),
    and batches are coalesced across campaigns."""
    spec = CampaignSpec(accel="mcm2", **SMALL)
    ref = run_dse(MCMAccelerator(1), default_library(), spec.dse_config())

    mgr = CampaignManager(eval_workers=2, campaign_workers=2,
                          max_wait_s=0.02)
    c1, c2 = mgr.submit(spec), mgr.submit(spec)
    assert mgr.wait(c1, timeout=600) == "done"
    assert mgr.wait(c2, timeout=600) == "done"
    r1, r2 = mgr.result(c1), mgr.result(c2)

    assert np.array_equal(r1.front_genomes, r2.front_genomes)
    assert np.allclose(r1.front_objectives, ref.front_objectives)

    s = mgr.scheduler.stats()
    # both campaigns requested the same genomes; each was labeled once
    assert s["labeled"] < s["requests"]
    assert s["inflight_dedup_hits"] + s["store_hits"] > 0
    per = s["per_campaign"]
    total_saved = sum(v["store_hits"] + v["inflight_hits"] for v in per.values())
    assert total_saved >= s["labeled"]  # second campaign rode the first
    mgr.shutdown()


def test_second_campaign_cold_store_warm_rerun(tmp_path):
    """Acceptance: a rerun against a warm store performs zero
    ground-truth labeling (stage 1 AND stage 3 served from the store)."""
    path = str(tmp_path / "labels.jsonl")
    spec = CampaignSpec(accel="mcm2", **SMALL)

    store = JsonlLabelStore(path)
    mgr = CampaignManager(store, eval_workers=2, campaign_workers=1)
    cid = mgr.submit(spec)
    assert mgr.wait(cid, timeout=600) == "done"
    cold_front = mgr.result(cid).front_objectives
    cold_labeled = mgr.scheduler.stats()["labeled"]
    assert cold_labeled > 0
    mgr.shutdown()
    store.close()

    # fresh manager + fresh store instance on the same file (new "process")
    store2 = JsonlLabelStore(path)
    mgr2 = CampaignManager(store2, eval_workers=2, campaign_workers=1)
    cid2 = mgr2.submit(spec)
    assert mgr2.wait(cid2, timeout=600) == "done"
    s = mgr2.scheduler.stats()
    assert s["labeled"] == 0, "warm rerun paid ground truth"
    assert s["store_hits"] == s["requests"]
    assert np.allclose(mgr2.result(cid2).front_objectives, cold_front)
    mgr2.shutdown()
    store2.close()


def test_campaign_status_and_fronts():
    mgr = CampaignManager(eval_workers=2, campaign_workers=1)
    spec = CampaignSpec(accel="mcm2", **SMALL)
    cid = mgr.submit(spec)
    assert mgr.wait(cid, timeout=600) == "done"
    st = mgr.status(cid)
    assert st["state"] == "done" and st["front_size"] > 0
    assert st["labeling"]["requests"] > 0

    fr = mgr.front(cid)
    assert len(fr["front"]) == st["front_size"]
    gf = mgr.global_front("mcm2")
    assert 0 < len(gf["front"]) <= st["front_size"]
    assert gf["campaigns"] == [cid]
    assert mgr.global_front("mcm3")["front"] == []
    mgr.shutdown()


def test_global_front_skips_incompatible_contexts():
    """rank_genes changes the genome width, so campaigns with different
    eval contexts must not be merged into one front (and must not crash
    np.concatenate); the most recent context wins."""
    mgr = CampaignManager(eval_workers=2, campaign_workers=1)
    c1 = mgr.submit(CampaignSpec(accel="mcm2", **SMALL))
    assert mgr.wait(c1, timeout=600) == "done"
    c2 = mgr.submit(CampaignSpec(accel="mcm2", rank_genes=True, **SMALL))
    assert mgr.wait(c2, timeout=600) == "done"
    gf = mgr.global_front("mcm2")
    assert gf["campaigns"] == [c2]
    assert len(gf["front"]) > 0
    mgr.shutdown()


def test_campaign_retention_compacts_and_drops():
    """Old finished campaigns compact to their fronts, the very oldest
    are dropped entirely (incl. scheduler per-campaign accounting)."""
    from repro.service.campaigns import _CompactResult

    mgr = CampaignManager(eval_workers=2, campaign_workers=1,
                          keep_results=1, keep_campaigns=2)
    spec = CampaignSpec(accel="mcm2", **SMALL)
    # submit sequentially: retention evicts by FINISH order, which under
    # concurrent stepping is not necessarily submit order
    cids = []
    for _ in range(3):
        cid = mgr.submit(spec)
        assert mgr.wait(cid, timeout=600) == "done"
        cids.append(cid)

    with pytest.raises(KeyError):
        mgr.status(cids[0])                       # dropped
    assert cids[0] not in mgr.scheduler.stats()["per_campaign"]
    assert isinstance(mgr.result(cids[1]), _CompactResult)  # compacted
    assert len(mgr.front(cids[1])["front"]) > 0   # front still queryable
    assert not isinstance(mgr.result(cids[2]), _CompactResult)  # newest full
    assert len(mgr.global_front("mcm2")["front"]) > 0
    mgr.shutdown()


def test_submit_validates_spec_upfront():
    """Unknown accelerators / malformed sizes are rejected at submit time
    with a ValueError (-> HTTP 400) instead of failing asynchronously in
    a worker thread."""
    mgr = CampaignManager(eval_workers=1, campaign_workers=1)
    with pytest.raises(ValueError, match="unknown accelerator"):
        mgr.submit(CampaignSpec(accel="nope-such-accel", **SMALL))
    with pytest.raises(ValueError, match="n_train"):
        mgr.submit(CampaignSpec(accel="mcm2", **{**SMALL, "n_train": 0}))
    with pytest.raises(ValueError, match="n_parents"):
        mgr.submit(CampaignSpec(
            accel="mcm2", **{**SMALL, "pop_size": 4, "n_parents": 8}))
    with pytest.raises(ValueError, match="objectives"):
        mgr.submit(CampaignSpec(accel="mcm2",
                                objectives=("qor", "nope"), **SMALL))
    assert mgr.list_campaigns() == []    # nothing was admitted
    mgr.shutdown()


def test_campaign_failure_is_isolated():
    """A campaign that fails at RUN time (valid spec) is isolated: it
    lands in 'failed' with its error, without hurting the manager."""
    from repro.accel.base import Accelerator, Slot
    from repro.service import register_accelerator, unregister_accelerator

    class _Boom(Accelerator):
        name = "boom-accel"
        slots = [Slot("m0", "mul8u", 1.0)]

        def sample_inputs(self, n, seed=0):
            raise RuntimeError("boom at labeling time")

    register_accelerator("boom-accel", _Boom)
    mgr = CampaignManager(eval_workers=1, campaign_workers=1)
    try:
        cid = mgr.submit(CampaignSpec(accel="boom-accel", **SMALL))
        assert mgr.wait(cid, timeout=60) == "failed"
        assert "boom" in mgr.status(cid)["error"]
        with pytest.raises(RuntimeError):
            mgr.result(cid)
    finally:
        unregister_accelerator("boom-accel")
        mgr.shutdown()


def test_http_api_roundtrip():
    from repro.service.api import Client, make_server

    mgr = CampaignManager(eval_workers=2, campaign_workers=1)
    srv = make_server(mgr, port=0)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    try:
        cli = Client(f"http://127.0.0.1:{srv.server_address[1]}")
        assert cli._req("/healthz")["ok"]
        cid = cli.submit(accel="mcm2", **SMALL)
        st = cli.wait(cid, timeout=600)
        assert st["state"] == "done"
        assert len(cli.front(cid)["front"]) == st["front_size"]
        assert cli.global_front("mcm2")["campaigns"] == [cid]
        assert cli.stats()["scheduler"]["requests"] > 0
    finally:
        srv.shutdown()
        mgr.shutdown()
