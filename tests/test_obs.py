"""Flight recorder: span nesting + wire propagation (thread, process,
fleet-HTTP boundaries), race-free metrics under a hammered ``stats()``,
Prometheus rendering, Chrome-trace export, and the per-campaign
telemetry timeline."""

import json
import os
import re
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from repro import obs
from repro.accel import MCMAccelerator
from repro.core.acl.library import default_library
from repro.obs.export import load_jsonl, main as export_main, to_chrome_trace
from repro.obs.metrics import Registry
from repro.service import (
    CampaignManager,
    CampaignSpec,
    EvalContext,
    EvalScheduler,
    InMemoryLabelStore,
)
from repro.service.store import LABEL_KEYS

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

SMALL = dict(n_train=10, n_qor_samples=2, pop_size=8, n_parents=4,
             n_generations=2)


@pytest.fixture(autouse=True)
def _obs_state():
    """Tracing is process-global: restore it whatever a test does."""
    yield
    obs.set_enabled(True)
    obs.set_sink(None)


def _wait_for(pred, timeout=60.0, every=0.01, what="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return
        time.sleep(every)
    raise AssertionError(f"timed out waiting for {what}")


# ---------------------------------------------------------------------------
# trace core
# ---------------------------------------------------------------------------

def test_span_nesting_parents_and_baggage():
    rec = obs.recorder()
    rec.clear()
    with obs.context(campaign="c-unit", trace_id="c-unit", stage="train"):
        with obs.span("outer.op", n=3) as outer:
            with obs.span("inner.op"):
                pass
            outer_id = outer.span_id
    spans = {s["name"]: s for s in rec.snapshot()}
    assert spans["inner.op"]["parent"] == outer_id
    assert spans["inner.op"]["trace"] == "c-unit"
    assert spans["outer.op"]["trace"] == "c-unit"
    # baggage lands in every span's attrs
    assert spans["outer.op"]["attrs"]["campaign"] == "c-unit"
    assert spans["inner.op"]["attrs"]["stage"] == "train"
    assert spans["outer.op"]["attrs"]["n"] == 3
    assert spans["outer.op"]["dur"] >= 0.0


def test_wire_context_roundtrips_through_json():
    """The wire codec is what rides fleet lease responses: it must
    survive a JSON round trip and re-parent spans on the far side."""
    rec = obs.recorder()
    rec.clear()
    with obs.context(campaign="c-wire", trace_id="c-wire"):
        with obs.span("parent.op") as parent:
            wire = obs.wire_context()
            parent_id = parent.span_id
    wire = json.loads(json.dumps(wire))  # over the wire and back
    with obs.attach(wire, worker="w9", lease="L1"):
        with obs.span("remote.op"):
            pass
    remote = [s for s in rec.snapshot() if s["name"] == "remote.op"][0]
    assert remote["trace"] == "c-wire"
    assert remote["parent"] == parent_id
    assert remote["attrs"]["campaign"] == "c-wire"
    assert remote["attrs"]["worker"] == "w9"
    assert remote["attrs"]["lease"] == "L1"
    # garbage wire still labels worker-local spans
    with obs.attach(None, worker="w9"):
        with obs.span("orphan.op"):
            pass
    orphan = [s for s in rec.snapshot() if s["name"] == "orphan.op"][0]
    assert orphan["attrs"]["worker"] == "w9"


def test_disabled_tracing_noops():
    rec = obs.recorder()
    rec.clear()
    obs.set_enabled(False)
    assert obs.wire_context() is None
    with obs.context(campaign="nope"):
        with obs.span("invisible.op") as sp:
            sp.set(k=1)  # null span: must not raise
    assert rec.snapshot() == []
    obs.set_enabled(True)


def test_recorder_ring_bound_and_ingest():
    rec = obs.Recorder(ring=4)
    for i in range(10):
        rec.emit({"name": f"s{i}", "t0": 0.0, "dur": 0.0})
    assert len(rec.snapshot()) == 4
    assert rec.stats()["spans"] == 10
    assert rec.stats()["evicted"] == 6
    rec.ingest([{"name": "far", "t0": 0.0}, {"bogus": 1}, "junk"])
    assert rec.stats()["ingested"] == 1
    assert rec.stats()["evicted"] == 7
    assert rec.snapshot()[-1]["name"] == "far"
    rec.clear()                 # a clear is not an eviction
    rec.emit({"name": "after", "t0": 0.0, "dur": 0.0})
    assert rec.stats()["evicted"] == 7


def test_jit_compile_records_parent_to_the_open_span():
    import jax
    import jax.numpy as jnp

    x = jnp.ones((4, 4))
    with obs.span("compile.warm"):
        pass                    # jax is imported: the bridge is installed
    compiles = obs.REGISTRY.get("repro_jit_compiles_total")
    before = compiles.value
    rec = obs.recorder()
    rec.clear()

    def f(x):
        return jnp.sin(x) @ x.T

    with obs.context(campaign="c-jit"):
        with obs.span("compile.op") as sp:
            jax.jit(f).lower(x).compile()
    recs = [r for r in rec.snapshot() if r["name"] == "jit.compile"]
    by_phase = {r["attrs"]["phase"]: r for r in recs}
    assert sorted(r["attrs"]["phase"] for r in recs) == ["backend", "lower", "trace"]
    assert all(r["parent"] == sp.span_id for r in recs)
    assert all(r["attrs"]["campaign"] == "c-jit" for r in recs)
    # the sub-traces of sin and matmul fold into f's own trace
    assert by_phase["trace"]["attrs"]["fun"] == "f"
    assert by_phase["trace"]["attrs"]["nested"] >= 2
    assert by_phase["lower"]["attrs"]["fun"] == "jit(f)"
    assert by_phase["backend"]["attrs"]["fun"] == "jit(f)"
    op = [r for r in rec.snapshot() if r["name"] == "compile.op"][0]
    t = by_phase["trace"]
    assert op["t0"] - 1e-3 <= t["t0"] <= by_phase["lower"]["t0"]
    assert by_phase["backend"]["t0"] + by_phase["backend"]["dur"] <= (
        op["t0"] + op["dur"] + 1e-3)
    assert compiles.value >= before + 1
    text = obs.render_prometheus()
    assert "# TYPE repro_jit_compiles_total counter" in text


def test_fused_counters_count_across_calls():
    from repro.accel import fused

    name = "repro_sim_fused_obs_probe_total"
    try:
        fused._bump("obs_probe")
        fused._bump("obs_probe")
        assert _parse_prometheus(obs.render_prometheus())[name] == 2
        fused._bump("obs_probe", 3)
        assert obs.REGISTRY.get(name).value == 5
    finally:
        with fused._STATS_LOCK:
            fused._STATS.pop("obs_probe", None)


def test_scheduler_batch_span_carries_wait_s():
    """Labels that wait behind a busy worker show it in ``wait_s``."""
    rec = obs.recorder()
    rec.clear()
    sched = EvalScheduler(InMemoryLabelStore(), n_workers=1,
                          max_batch=4, max_wait_s=0.0)
    try:
        sched.label(_SlowCtx(delay=0.1), np.arange(16).reshape(8, 2))
    finally:
        sched.shutdown()
    batches = [s for s in rec.snapshot() if s["name"] == "sched.batch"]
    assert sum(s["attrs"]["n"] for s in batches) == 8
    waits = sorted(s["attrs"]["wait_s"] for s in batches)
    assert waits[0] >= 0.0
    # the later batch waited for the earlier one's 0.1 s ground truth
    assert waits[-1] >= 0.09


def test_lm_engine_spans_queue_and_model_step():
    """The LM serving path on a tiny model: each request's admission
    wait, and the model step's prefill and decode as children of its
    group."""
    from repro.accel.lm import LMAccelerator
    from repro.configs import get_config
    from repro.models import reduced
    from repro.serving import FrontCatalog, ServingEngine

    cfg = reduced(get_config("granite-8b"), n_layers=1, d_model=32)
    accel = LMAccelerator(cfg, use_reduced=False)
    lib = default_library()
    catalog = FrontCatalog.from_front(
        accel.name, [accel.exact_genome(lib).tolist()], [[-100.0, 1.0]])
    rec = obs.recorder()
    rec.clear()
    eng = ServingEngine(accel, lib, catalog=catalog, max_batch=4,
                        max_wait_s=0.2)
    try:
        prompts = np.arange(16, dtype=np.int32).reshape(2, 8) % cfg.vocab_size
        futs = [eng.submit(p, tier="exact", gen=3) for p in prompts]
        for f in futs:
            assert len(f.result(timeout=300)["tokens"]) == 3
    finally:
        eng.close()
    spans = rec.snapshot()
    reqs = [s for s in spans if s["name"] == "serving.request"]
    assert len(reqs) == 2
    # two requests of a possible four: the batch loop lingered for more
    assert all(0.1 <= s["attrs"]["queue_s"] < 60 for s in reqs)
    group, = [s for s in spans if s["name"] == "serving.group"]
    steps = {s["name"]: s for s in spans if s["parent"] == group["span"]
             and s["name"].startswith("lm.")}
    assert set(steps) == {"lm.prefill", "lm.decode"}
    assert steps["lm.decode"]["attrs"]["steps"] == 2
    assert steps["lm.prefill"]["dur"] + steps["lm.decode"]["dur"] <= group["dur"]


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------

_SAMPLE_RE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? [^ ]+$"
)


def _parse_prometheus(text):
    """Tiny exposition-format checker: every non-comment line must be a
    valid sample; returns {name_with_labels: float}."""
    out = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        assert _SAMPLE_RE.match(line), f"bad prometheus line: {line!r}"
        name, _, value = line.rpartition(" ")
        out[name] = float(value)
    return out


def test_prometheus_render_parses():
    reg = Registry()
    c = reg.counter("t_requests_total", "requests")
    g = reg.gauge("t_depth", "queue depth")
    h = reg.histogram("t_seconds", "latency", buckets=(0.1, 1.0))
    c.inc()
    c.inc(2)
    g.set(5)
    h.observe(0.05)
    h.observe(0.5)
    h.observe(99.0)
    text = reg.render()
    assert "# HELP t_requests_total requests" in text
    assert "# TYPE t_seconds histogram" in text
    samples = _parse_prometheus(text)
    assert samples["t_requests_total"] == 3.0
    assert samples["t_depth"] == 5.0
    assert samples['t_seconds_bucket{le="0.1"}'] == 1.0
    assert samples['t_seconds_bucket{le="1"}'] == 2.0
    assert samples['t_seconds_bucket{le="+Inf"}'] == 3.0
    assert samples["t_seconds_count"] == 3.0
    assert samples["t_seconds_sum"] == pytest.approx(99.55)


def test_counter_concurrent_increments_exact():
    """Per-thread shards: N threads incrementing concurrently must lose
    nothing (the old dict counters could)."""
    reg = Registry()
    c = reg.counter("t_conc_total", "x")
    h = reg.histogram("t_conc_seconds", "x", buckets=(1.0,))
    N, K = 8, 5000

    def work():
        for _ in range(K):
            c.inc()
            h.observe(0.5)

    threads = [threading.Thread(target=work) for _ in range(N)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == N * K
    assert h.count == N * K
    assert h.sum == pytest.approx(0.5 * N * K)


class _SlowCtx:
    """EvalContext stand-in with a slow, observable ground truth."""

    def __init__(self, delay=0.003):
        self.fingerprint = "obs-testctx"
        self.delay = delay

    def key(self, genome):
        return "g" + "-".join(str(int(v)) for v in np.atleast_1d(genome))

    def ground_truth(self, genomes):
        genomes = np.atleast_2d(genomes)
        time.sleep(self.delay)
        val = genomes.sum(axis=1).astype(float)
        return {k: val.copy() for k in LABEL_KEYS}


def test_scheduler_stats_race_regression():
    """Hammer ``stats()`` from several threads while batches run on the
    thread backend: reads must never raise, never go backwards, and end
    exactly consistent with the submitted work."""
    sched = EvalScheduler(InMemoryLabelStore(), n_workers=2,
                          max_batch=8, max_wait_s=0.002)
    ctx = _SlowCtx()
    stop = threading.Event()
    errors = []

    def hammer():
        # monotonicity is a per-reader property: each thread tracks the
        # highest values IT has seen
        req = lab = 0
        try:
            while not stop.is_set():
                s = sched.stats()
                assert s["requests"] >= req
                assert s["labeled"] >= lab
                req, lab = s["requests"], s["labeled"]
        except Exception as exc:  # noqa: BLE001 - reported below
            errors.append(exc)

    hammers = [threading.Thread(target=hammer) for _ in range(4)]
    for t in hammers:
        t.start()
    try:
        total = 0
        for rnd in range(6):
            genomes = np.arange(rnd * 32, rnd * 32 + 16).reshape(8, 2)
            sched.label(ctx, genomes, campaign=f"c{rnd % 2}")
            total += 8
    finally:
        stop.set()
        for t in hammers:
            t.join()
        sched.shutdown()
    assert not errors, errors
    s = sched.stats()
    assert s["requests"] == total
    assert (s["labeled"] + s["store_hits"]
            + s["inflight_dedup_hits"]) == total


# ---------------------------------------------------------------------------
# chrome trace export
# ---------------------------------------------------------------------------

def test_chrome_trace_export_valid_and_nested(tmp_path):
    sink = str(tmp_path / "dse.trace.jsonl")
    obs.set_sink(sink)
    try:
        with obs.context(campaign="c-exp", trace_id="c-exp"):
            with obs.span("sched.batch", n=4) as outer:
                outer_id = outer.span_id
                with obs.span("synth.compile", kind="structural"):
                    time.sleep(0.002)
    finally:
        obs.set_sink(None)
    # a torn tail must be skipped, not fatal
    with open(sink, "a") as f:
        f.write('{"name": "torn.span", "t0": 1.0, "dur"')
    assert export_main([sink, "--chrome-trace"]) == 0
    out = tmp_path / "dse.trace.json"
    doc = json.loads(out.read_text())
    events = doc["traceEvents"]
    slices = {e["name"]: e for e in events if e["ph"] == "X"}
    assert "torn.span" not in slices
    assert slices["synth.compile"]["args"]["parent"] == outer_id
    assert slices["synth.compile"]["args"]["trace"] == "c-exp"
    assert slices["synth.compile"]["cat"] == "synth"
    assert slices["sched.batch"]["args"]["campaign"] == "c-exp"
    # complete events with µs timestamps and a nonzero floor
    for e in slices.values():
        assert e["ts"] > 1e15 and e["dur"] >= 1.0
    assert any(e["ph"] == "M" and e["name"] == "process_name"
               for e in events)
    spans, skipped = load_jsonl(sink)
    assert len(spans) == 2 and skipped == 1


def test_export_labels_fleet_worker_processes():
    doc = to_chrome_trace([
        {"name": "worker.serve", "t0": 1.0, "dur": 0.1, "pid": 41,
         "tid": 1, "attrs": {"worker": "w0"}},
        {"name": "sched.batch", "t0": 1.0, "dur": 0.2, "pid": 42, "tid": 1},
    ])
    meta = {e["pid"]: e["args"]["name"] for e in doc["traceEvents"]
            if e["ph"] == "M"}
    assert meta[41] == "fleet worker w0 (pid 41)"
    assert meta[42] == "pid 42"


# ---------------------------------------------------------------------------
# timeline
# ---------------------------------------------------------------------------

def test_timeline_hypervolume_monotone_and_frozen_ref():
    tl = obs.Timeline(maxlen=8)
    r1 = tl.sample("c", objectives=np.array([[1.0, 1.0], [0.8, 1.2]]),
                   stage="explore", labels_requested=10)
    ref = tl.reference("c")
    assert ref is not None
    # a strictly better front against the FROZEN reference grows volume
    r2 = tl.sample("c", objectives=np.array([[0.5, 0.5], [0.4, 0.9]]))
    assert tl.reference("c") == ref
    assert r2["hypervolume"] > r1["hypervolume"]
    assert r1["front_size"] == 2
    assert r1["stage"] == "explore" and r1["labels_requested"] == 10.0
    series = tl.series("c")
    assert [s["rel_s"] for s in series] == sorted(s["rel_s"] for s in series)
    # non-finite rows are dropped; a non-2D front adds no hv fields
    r3 = tl.sample("c", objectives=np.array([[np.nan, 1.0]]))
    assert "hypervolume" not in r3
    # ring is bounded
    for _ in range(20):
        tl.sample("c", labels_requested=1)
    assert len(tl.series("c")) == 8
    tl.forget("c")
    assert tl.series("c") == [] and tl.reference("c") is None


# ---------------------------------------------------------------------------
# fleet: trace context across the worker subprocess boundary
# ---------------------------------------------------------------------------

def _spawn_worker(base, wid):
    return subprocess.Popen(
        [sys.executable, "-m", "repro.fleet.worker",
         "--orchestrator", base, "--id", wid, "--no-warm",
         "--max-idle-s", "120"],
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
        env={**os.environ, "PYTHONPATH": SRC},
    )


def test_fleet_spans_survive_worker_subprocess_roundtrip():
    """The satellite acceptance check: a fleet batch's spans — recorded
    inside a real ``python -m repro.fleet.worker`` subprocess — come
    back on the result payload with the campaign trace id and lease id
    intact, and the lease lifecycle span closes with outcome=ok."""
    from repro.fleet import FleetCoordinator, serve_fleet

    lib = default_library()
    coord = FleetCoordinator(lease_ttl_s=60.0, heartbeat_ttl_s=30.0)
    srv = serve_fleet(coord, port=0)
    base = f"http://127.0.0.1:{srv.server_address[1]}"
    rec = obs.recorder()
    proc = _spawn_worker(base, "obs-w0")
    try:
        _wait_for(lambda: coord.stats()["live"] >= 1, timeout=300,
                  what="fleet worker to register")
        rec.clear()
        ctx = EvalContext(MCMAccelerator(1), lib, n_qor_samples=2)
        rng = np.random.default_rng(7)
        sizes = ctx.accel.gene_sizes(lib)
        genomes = rng.integers(0, sizes[None, :], size=(6, len(sizes)))
        with obs.context(campaign="c-fleet", trace_id="c-fleet"):
            labels = coord.label(ctx, genomes)
        assert set(LABEL_KEYS) <= set(labels)

        spans = rec.snapshot()
        serve = [s for s in spans if s["name"] == "worker.serve"]
        assert serve, sorted({s["name"] for s in spans})
        for s in serve:
            assert s["trace"] == "c-fleet"          # across HTTP + process
            assert s["attrs"]["campaign"] == "c-fleet"
            assert s["attrs"]["worker"] == "obs-w0"
            assert s["attrs"]["lease"]
            assert s["pid"] != os.getpid()          # recorded on the far side
        leases = [s for s in spans if s["name"] == "fleet.lease"]
        assert leases and all(s["trace"] == "c-fleet" for s in leases)
        assert any(s["attrs"].get("outcome") == "ok" for s in leases)
        batch = [s for s in spans if s["name"] == "fleet.batch"]
        assert len(batch) == 1 and batch[0]["trace"] == "c-fleet"
        # worker spans were ingested, not recorded locally
        assert rec.stats()["ingested"] >= len(serve)
    finally:
        if proc.poll() is None:
            proc.kill()
        coord.shutdown()
        srv.shutdown()


# ---------------------------------------------------------------------------
# service end to end: tick spans, /metrics, /campaigns/<id>/timeline
# ---------------------------------------------------------------------------

def test_campaign_timeline_and_metrics_endpoints(tmp_path):
    import urllib.request

    from repro.service.api import make_server

    sink = str(tmp_path / "svc.trace.jsonl")
    obs.set_sink(sink)
    mgr = CampaignManager(eval_workers=2, campaign_workers=2)
    srv = make_server(mgr, port=0)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    base = f"http://127.0.0.1:{srv.server_address[1]}"
    try:
        cid = mgr.submit(CampaignSpec(accel="mcm2", **SMALL))
        assert mgr.wait(cid, timeout=600) == "done"

        tl = json.load(urllib.request.urlopen(
            f"{base}/campaigns/{cid}/timeline"))
        assert tl["id"] == cid and tl["state"] == "done"
        samples = tl["samples"]
        assert len(samples) >= 3
        stages = [s.get("stage") for s in samples]
        assert "train" in stages and "done" in stages
        assert any("hypervolume" in s for s in samples)
        assert samples[-1]["labels_requested"] > 0
        assert "hv_reference" in tl

        text = urllib.request.urlopen(f"{base}/metrics").read().decode()
        samples_m = _parse_prometheus(text)
        assert samples_m["repro_sched_requests_total"] > 0
        assert samples_m["repro_sched_batches_total"] > 0
        assert any(k.startswith("repro_synth_") for k in samples_m)
        assert samples_m["repro_jit_compiles_total"] > 0

        stats = json.load(urllib.request.urlopen(f"{base}/stats"))
        assert stats["obs"]["recorder"]["spans"] > 0
        assert stats["obs"]["timeline_campaigns"] >= 1

        # unknown campaign -> 404, same contract as the other GETs
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(f"{base}/campaigns/nope/timeline")
        assert ei.value.code == 404
    finally:
        obs.set_sink(None)
        srv.shutdown()
        mgr.shutdown()

    # the sink holds the correlated spans of the whole campaign
    spans, skipped = load_jsonl(sink)
    assert skipped == 0
    by_name = {}
    for s in spans:
        by_name.setdefault(s["name"], []).append(s)
    for required in ("campaign.tick", "campaign.deliver", "sched.batch"):
        assert required in by_name, sorted(by_name)
    assert {s["trace"] for s in by_name["campaign.tick"]} == {cid}
    assert all(s["attrs"].get("campaign") == cid
               for s in by_name["sched.batch"])
