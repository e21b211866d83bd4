"""Chaos drill -> BENCH_chaos.json.

A REAL DSE campaign (service ``CampaignManager``, fleet eval backend,
``python -m repro.fleet.worker`` subprocesses over HTTP) runs to
completion under a seeded fault storm while a fault-free twin runs the
same spec first.  The acceptance bar is the robustness north star:

  * byte-identical Pareto front vs the fault-free twin,
  * labels-lost = 0 (every label the storm campaign paid for is still
    readable from a FRESH store opened on the post-storm files),
  * the segmented store warm-starts without replaying sealed segments
    and quarantines a deliberately corrupted segment while continuing
    to serve (and accept) everything else.

The storm is deterministic under ``--seed`` (``repro.faults`` keys its
coin flips on seed x injection-point x occurrence, never on wall
clock):

  parent plan   store.append torn writes under the store's own writer,
                fleet.lease grant drops (TTL-expiry requeue),
                fleet.result drop + duplicate (requeue / dedup)
  worker plan   injected 503 bursts on every outbound HTTP call,
                heartbeat drops, slow synthesis (synth.compile
                latency) — shipped via the ``REPRO_FAULTS`` env var
  plus          kill -9 of a worker while it holds a lease

Recovery latencies (kill -> dead-worker detection, kill -> campaign
done) are recorded alongside fleet/storm counters.

Run:  PYTHONPATH=src python benchmarks/chaos_drill.py [--smoke]
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import threading
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from common import emit, section  # noqa: E402

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


def _wait_until(pred, timeout, what):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if pred():
            return
        time.sleep(0.05)
    raise TimeoutError(f"timed out waiting for {what}")


def _spawn_worker(base, wid, plan_path, log_path):
    return subprocess.Popen(
        [sys.executable, "-m", "repro.fleet.worker",
         "--orchestrator", base, "--id", wid, "--max-idle-s", "600",
         "--log-level", "info"],
        env={**os.environ, "PYTHONPATH": SRC, "REPRO_FAULTS": plan_path},
        stdout=subprocess.DEVNULL, stderr=open(log_path, "w"),
    )


def _spec(args):
    from repro.service import CampaignSpec

    if args.smoke:
        size = dict(n_train=8, n_qor_samples=2, pop_size=8, n_parents=4,
                    n_generations=2)
    else:
        size = dict(n_train=16, n_qor_samples=3, pop_size=12, n_parents=6,
                    n_generations=3)
    return CampaignSpec(accel="gaussian3x3", seed=args.seed, **size)


def _run_twin(args, root):
    """Fault-free twin: same spec, thread backend, clean store."""
    from repro.service import CampaignManager
    from repro.service.store import open_label_store

    store = open_label_store(os.path.join(root, "twin.segd"),
                             segment_records=8)
    mgr = CampaignManager(store, eval_workers=2, campaign_workers=1)
    try:
        t0 = time.perf_counter()
        cid = mgr.submit(_spec(args))
        assert mgr.wait(cid, timeout=1200) == "done", "twin failed"
        wall = time.perf_counter() - t0
        front = mgr.result(cid).front_objectives.copy()
        keys = set(store._data)
    finally:
        mgr.shutdown()
        store.close()
    return front, keys, wall


def _worker_plan(args, root):
    from repro.faults import FaultPlan

    plan = (
        FaultPlan(seed=args.seed, name="chaos-worker")
        # 503 burst early (registration/first leases retry through it),
        # then a sprinkle for the rest of the campaign
        .add("http.request", "error", status=503, after=2, times=4)
        .add("http.request", "error", status=503, p=0.05)
        .add("fleet.heartbeat", "drop", p=0.10)
        .add("synth.compile", "latency", delay_s=0.05, times=20)
    )
    return plan.save(os.path.join(root, "worker_plan.json"))


def _parent_plan(args):
    from repro.faults import FaultPlan

    return (
        FaultPlan(seed=args.seed + 1, name="chaos-parent")
        .add("store.append", "torn_write", times=3, fraction=0.5)
        .add("fleet.lease", "drop", times=2)
        .add("fleet.result", "drop", times=1)
        .add("fleet.result", "duplicate", times=1)
    )


def _run_storm(args, root):
    from repro import faults
    from repro.service import CampaignManager
    from repro.service.api import make_server
    from repro.service.store import open_label_store

    store = open_label_store(os.path.join(root, "storm.segd"),
                             segment_records=8)
    mgr = CampaignManager(
        store, eval_workers=2, campaign_workers=1,
        eval_backend="fleet", fleet_fallback="thread",
        lease_ttl_s=8.0, heartbeat_ttl_s=5.0,
    )
    srv = make_server(mgr, port=0)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    base = f"http://127.0.0.1:{srv.server_address[1]}"
    coord = mgr.scheduler.fleet
    plan_path = _worker_plan(args, root)
    procs = {}
    report = {}
    try:
        section("storm: 2 workers joining through an injected 503 burst")
        for wid in ("chaos-w0", "chaos-w1"):
            procs[wid] = _spawn_worker(
                base, wid, plan_path, os.path.join(root, f"{wid}.log"))
        _wait_until(lambda: coord.stats()["live"] >= 2, 600,
                    "both workers to register")

        section("storm: campaign under parent + worker fault plans")
        faults.install(_parent_plan(args))
        t0 = time.perf_counter()
        cid = mgr.submit(_spec(args))

        def _victim():
            with coord._cv:
                for lease in coord._leases.values():
                    if lease.worker in procs:
                        return lease.worker
            return None

        # kill -9 a worker the moment it holds a lease mid-campaign
        victim = None
        kill_deadline = time.time() + 600
        while victim is None and time.time() < kill_deadline:
            if mgr.status(cid)["state"] in ("done", "failed"):
                break
            victim = _victim()
            time.sleep(0.02)
        t_kill = time.perf_counter()
        if victim is not None:
            section(f"storm: kill -9 {victim} (holding a lease)")
            procs[victim].send_signal(signal.SIGKILL)
            dead0 = coord.stats()["dead_workers"]
            _wait_until(lambda: coord.stats()["dead_workers"] > dead0,
                        120, "dead-worker detection")
            report["kill_to_dead_s"] = time.perf_counter() - t_kill

        state = mgr.wait(cid, timeout=1800)
        wall = time.perf_counter() - t0
        assert state == "done", f"storm campaign ended {state!r}"
        report.update(
            wall_s=wall,
            kill_to_done_s=(time.perf_counter() - t_kill
                            if victim is not None else None),
            victim=victim,
            parent_faults=faults.stats(),
            fleet={k: v for k, v in coord.stats().items()
                   if k != "workers"},
            store=store.stats(),
        )
        front = mgr.result(cid).front_objectives.copy()
        keys = set(store._data)
    finally:
        faults.uninstall()
        for p in procs.values():
            if p.poll() is None:
                p.kill()
        srv.shutdown()
        mgr.shutdown()
        store.close()
    # worker-side proof the storm reached the subprocesses: every
    # firing logs "injected <kind> at <point>" in the worker's stderr
    report["worker_injections"] = sum(
        open(os.path.join(root, f"{wid}.log")).read().count("injected")
        for wid in procs)
    return front, keys, report


def _durability(root, storm_keys):
    """Crash-consistency view: everything the storm campaign paid for
    must be readable from a FRESH store on the post-storm files, the
    open must not replay sealed segments, and a corrupted segment must
    quarantine without taking the store down."""
    from repro.service.store import LABEL_KEYS, open_label_store

    path = os.path.join(root, "storm.segd")

    t0 = time.perf_counter()
    fresh = open_label_store(path, segment_records=8)
    open_s = time.perf_counter() - t0
    lazy = fresh.stats()["segments_loaded"] == 0
    lost = [k for k in storm_keys if fresh.get(k) is None]
    n_total = len(fresh)
    fresh.close()

    # bit-rot one sealed segment -> quarantine-and-continue
    segs = sorted(f for f in os.listdir(path)
                  if f.startswith("seg-") and f.endswith(".jsonl"))
    quarantine = {"checked": False}
    if segs:
        seg = os.path.join(path, segs[0])
        data = bytearray(open(seg, "rb").read())
        data[len(data) // 2] ^= 0xFF
        open(seg, "wb").write(data)
        q = open_label_store(path, segment_records=8)
        survivors = sum(1 for k in storm_keys if q.get(k) is not None)
        st = q.stats()
        q.put("chaos:drill:probe", {k: 1.0 for k in LABEL_KEYS})
        still_writes = q.get("chaos:drill:probe") is not None
        q.close()
        quarantine = {
            "checked": True,
            "quarantined_segments": int(st["quarantined_segments"]),
            "records_dropped": n_total - survivors,
            "survivors": survivors,
            "still_writable": bool(still_writes),
        }
        assert st["quarantined_segments"] >= 1, "corruption not detected"
        assert still_writes, "store stopped accepting writes"
    return {
        "reopen_s": open_s,
        "lazy_warm_start": bool(lazy),
        "labels_lost": len(lost),
        "entries": n_total,
        "quarantine": quarantine,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny campaign (CI: exercise every fault path, "
                         "don't trust the latencies)")
    ap.add_argument("--seed", type=int, default=0,
                    help="storm seed (fault plans + campaign)")
    ap.add_argument("--out", default=None)
    ap.add_argument("--keep", action="store_true",
                    help="keep the scratch dir (worker logs, stores)")
    args = ap.parse_args()
    from repro.service.workers import require_cpu_backend

    require_cpu_backend(
        "benchmarks/chaos_drill.py", "run it with JAX_PLATFORMS=cpu")
    out_path = os.path.abspath(args.out or os.path.join(
        os.path.dirname(__file__), "..", "BENCH_chaos.json"))
    root = tempfile.mkdtemp(prefix="chaos_drill_")

    from repro.service.workers import warm_library  # noqa: E402
    from repro.core.acl.library import default_library  # noqa: E402

    warm_library(default_library())
    try:
        section("fault-free twin")
        twin_front, twin_keys, twin_wall = _run_twin(args, root)
        emit("chaos.twin", twin_wall * 1e6, f"{len(twin_keys)} labels")

        section("seeded storm")
        storm_front, storm_keys, storm = _run_storm(args, root)
        emit("chaos.storm", storm["wall_s"] * 1e6,
             f"{len(storm_keys)} labels")

        front_identical = bool(np.array_equal(twin_front, storm_front))
        emit("chaos.front_identical", 0.0, front_identical)
        if storm.get("kill_to_dead_s") is not None:
            emit("chaos.kill_to_dead", storm["kill_to_dead_s"] * 1e6,
                 storm["victim"])

        section("durability: fresh reopen + corrupted-segment drill")
        dur = _durability(root, storm_keys)
        emit("chaos.labels_lost", 0.0, dur["labels_lost"])
        emit("chaos.quarantine_continue", 0.0,
             dur["quarantine"].get("still_writable", "n/a"))

        report = {
            "mode": "chaos", "smoke": bool(args.smoke), "seed": args.seed,
            "front_identical": front_identical,
            "twin": {"wall_s": twin_wall, "n_labels": len(twin_keys)},
            "storm": storm,
            "durability": dur,
        }
        assert front_identical, "storm front diverged from twin"
        assert dur["labels_lost"] == 0, (
            f"{dur['labels_lost']} labels lost in the storm")
        with open(out_path, "w") as f:
            json.dump(report, f, indent=1)
        print(f"wrote {out_path}", file=sys.stderr)
    finally:
        if args.keep:
            print(f"scratch kept at {root}", file=sys.stderr)
        else:
            shutil.rmtree(root, ignore_errors=True)


if __name__ == "__main__":
    main()
