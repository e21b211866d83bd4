"""Labeling-engine throughput benchmark -> BENCH_labeler.json.

Ground-truth labeling (XLA synthesis + behavioral simulation) is the
hot path of every DSE campaign.  This benchmark measures labels/sec of
three engine configurations on the same random populations:

  * ``per_genome_thread`` — the SEED engine as the baseline: one
    ground-truth call per genome fanned out to 2 worker threads, with
    the original deployment trace (dead behavioral tables embedded,
    outlined per-slot pjits) and default XLA codegen.  Threads buy
    nothing: the sim is GIL-bound and XLA tracing holds the GIL, so
    this backend can never use more than ~1 core.
  * ``batched_thread``   — the batched engine in-process: ONE
    ground-truth call for the population (vectorized ``qor_batch`` LUT
    simulation, lean inlined deployment trace, guarded label-invariant
    fast codegen).
  * ``batched_process``  — the batched engine fanned out in chunks to a
    warm spawn-safe process pool (``repro.service.workers``), the only
    backend whose throughput scales with real cores.

Labels (and the Pareto fronts induced by them) must be byte-identical
across all three — the engines differ in speed only.

Methodology: backends are measured INTERLEAVED over several rounds
(fresh genomes per round, so no synthesis-cache hits) and the median
per-label wall is reported — shared hosts drift by +-40% between runs.
Aggregate CPU-seconds per label (parent + workers, /proc-based) and a
measured machine parallelism ceiling are recorded alongside, so the
wall-clock ratios can be read against what the host actually provides:
on a full 2-core machine the process backend's projected throughput is
``n_cores / cpu_s_per_label``.

``--obs`` measures the flight recorder's overhead guardrail instead and
writes ``BENCH_obs.json``: span-machinery cost on vs off (microbench),
then labels/sec through the real scheduler path with tracing enabled vs
disabled (sink off, interleaved + order-alternated rounds).  Target:
tracing costs <3% labels/sec — a warning, not an assert, because shared
hosts drift more than that between runs.

``--fused`` measures the steady-state labeling regime instead — warm
synthesis caches across generations, where behavioral simulation
dominates the label — comparing the numpy batched engine (fused kill
switch thrown), the fused XLA engine, and the warm process pool on the
same per-round populations.  Labels and fronts must be byte-identical
across all three, and the fused engine must add ZERO XLA recompiles
across the timed generations (population bucketing).  Results merge
into BENCH_labeler.json under the ``fused`` key.

``--fleet`` benchmarks the multi-host labeling fleet instead and writes
``BENCH_fleet.json``: labels/sec of one vs two local fleet workers on
gaussian3x3 (measured, plus a CPU-seconds projection onto a machine
that actually provides 2 cores), then a kill -9 drill — one worker is
killed while holding a lease mid-batch and the batch must still
complete with labels byte-identical to the in-process engine.

Run:  PYTHONPATH=src python benchmarks/labeler_throughput.py [--smoke]
      PYTHONPATH=src python benchmarks/labeler_throughput.py --fused [--smoke]
      PYTHONPATH=src python benchmarks/labeler_throughput.py --fleet [--smoke]
      PYTHONPATH=src python benchmarks/labeler_throughput.py --obs [--smoke]
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from common import emit, section  # noqa: E402

WORKERS = 2
DET_KEYS = ("qor", "latency", "energy", "flops", "hbm_bytes")


# --------------------------------------------------------------------------
# cpu accounting: parent + live worker processes (RUSAGE_CHILDREN only
# counts reaped children, so read /proc/<pid>/stat directly)
def _proc_cpu_s(pid: int) -> float:
    try:
        with open(f"/proc/{pid}/stat") as f:
            parts = f.read().rsplit(") ", 1)[1].split()
        utime, stime = int(parts[11]), int(parts[12])
        return (utime + stime) / os.sysconf("SC_CLK_TCK")
    except Exception:  # noqa: BLE001 - non-linux or reaped pid
        return 0.0


def _cpu_snapshot(worker_pids) -> float:
    return _proc_cpu_s(os.getpid()) + sum(_proc_cpu_s(p) for p in worker_pids)


def _parallel_ceiling() -> float:
    """Measured aggregate speedup of 2 CPU-bound processes vs 1 (shared
    hosts often deliver far less than os.cpu_count() cores)."""
    import multiprocessing as mp
    from concurrent.futures import ProcessPoolExecutor

    n = 8_000_000
    t0 = time.perf_counter()
    _burn(n)
    t1 = time.perf_counter() - t0
    with ProcessPoolExecutor(2, mp_context=mp.get_context("spawn")) as pool:
        list(pool.map(_burn, [n // 8, n // 8]))           # spawn warmup
        t0 = time.perf_counter()
        list(pool.map(_burn, [n, n]))
        t2 = time.perf_counter() - t0
    return 2.0 * t1 / t2


def _burn(n):
    s = 0
    for i in range(n):
        s += i * i
    return s


# --------------------------------------------------------------------------
def _population(accel, library, n, seed):
    rng = np.random.default_rng(seed)
    sizes = accel.gene_sizes(library)
    return rng.integers(0, sizes[None, :], size=(n, len(sizes)))


def _front(labels):
    from repro.core.dse import _objective_matrix
    from repro.core.pareto import non_dominated_mask

    obj = _objective_matrix(labels, ("qor", "energy"))
    return obj[non_dominated_mask(obj)]


def _fresh_ctx(name, n_qor):
    from repro.core.acl.library import default_library
    from repro.service import EvalContext, make_accelerator

    return EvalContext(
        make_accelerator(name), default_library(), n_qor_samples=n_qor
    )


def bench_per_genome_thread(name, genomes, n_qor):
    """Seed-engine baseline: per-genome ground truth on thread workers.
    Structural compile keying and the shared compile cache are disabled
    (and the engine reset) so the baseline pays exactly what the seed
    engine paid — without this, the new engine's process-wide cache
    would answer for compiles another backend already did."""
    import repro.core.features.synth as synth
    import repro.kernels.approx_matmul.ops as ops

    ctx = _fresh_ctx(name, n_qor)
    ops.LEGACY_EMBED_TABLES, fast = True, synth.FAST_CODEGEN
    struct = synth.STRUCTURAL_KEYS
    synth.FAST_CODEGEN = False
    synth.STRUCTURAL_KEYS = False
    synth.reset_fast_codegen()
    try:
        with ThreadPoolExecutor(WORKERS) as pool:
            t0 = time.perf_counter()
            outs = list(pool.map(lambda g: ctx.ground_truth(g[None]), genomes))
            wall = time.perf_counter() - t0
    finally:
        ops.LEGACY_EMBED_TABLES = False
        synth.FAST_CODEGEN = fast
        synth.STRUCTURAL_KEYS = struct
    labels = {k: np.concatenate([o[k] for o in outs]) for k in DET_KEYS}
    return labels, wall


def bench_batched_thread(name, genomes, n_qor):
    """Batched engine, in-process: one ground-truth call for the batch
    (cold shared compile cache — backends must not feed each other)."""
    import repro.core.features.synth as synth

    synth.reset_fast_codegen()
    ctx = _fresh_ctx(name, n_qor)
    t0 = time.perf_counter()
    labels = ctx.ground_truth(genomes)
    return labels, time.perf_counter() - t0


def bench_batched_process(name, genomes, n_qor, pool):
    """Batched engine on the warm process pool (chunked fan-out)."""
    ctx = _fresh_ctx(name, n_qor)
    assert pool.can_label(ctx), f"{name} should be process-safe"
    t0 = time.perf_counter()
    labels = pool.label(ctx, genomes)
    return labels, time.perf_counter() - t0


# --------------------------------------------------------------------------
# fleet mode
def _wait_until(pred, timeout, what):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if pred():
            return
        time.sleep(0.05)
    raise TimeoutError(f"timed out waiting for {what}")


def _spawn_fleet_worker(base, wid):
    src = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    return subprocess.Popen(
        [sys.executable, "-m", "repro.fleet.worker",
         "--orchestrator", base, "--id", wid, "--max-idle-s", "600"],
        env={**os.environ, "PYTHONPATH": src},
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
    )


def run_fleet_bench(args):
    """1 vs 2 local fleet workers on gaussian3x3 + a kill -9 drill.

    Real ``python -m repro.fleet.worker`` subprocesses join an in-parent
    ``FleetCoordinator`` over HTTP.  Each phase gets a warmup batch first
    so both phases measure the steady state of long-lived workers
    (per-circuit tables and structural compile caches warm); rounds use
    fresh genomes so the label store never answers.  The drill kills one
    of two workers with SIGKILL while it holds a lease mid-batch: the
    batch must still complete (heartbeat expiry requeues the dead
    worker's chunks) with labels byte-identical to the in-process
    engine on the same genomes.
    """
    from repro.core.acl.library import default_library
    from repro.fleet import FleetCoordinator, serve_fleet
    from repro.service.workers import warm_library

    name = "gaussian3x3"
    G = args.n or (4 if args.smoke else 24)
    rounds = args.rounds or (1 if args.smoke else 3)
    n_qor = 2 if args.smoke else 4
    library = default_library()
    warm_library(library)

    section("machine parallelism probe")
    ceiling = _parallel_ceiling()
    emit("fleet.parallel_ceiling", 0.0, f"{ceiling:.2f}x")

    coord = FleetCoordinator(lease_ttl_s=60.0, heartbeat_ttl_s=10.0)
    srv = serve_fleet(coord, port=0)
    base = f"http://127.0.0.1:{srv.server_address[1]}"
    ctx0 = _fresh_ctx(name, n_qor)
    procs = {}

    def phase(pids, seed0):
        walls, cpus = [], []
        for rnd in range(rounds):
            genomes = _population(ctx0.accel, library, G, seed=seed0 + rnd)
            c0 = _cpu_snapshot(pids)
            t0 = time.perf_counter()
            coord.label(_fresh_ctx(name, n_qor), genomes)
            walls.append((time.perf_counter() - t0) / G)
            cpus.append((_cpu_snapshot(pids) - c0) / G)
        wall = float(np.median(walls))
        return {"s_per_label": wall, "labels_per_sec": 1.0 / wall,
                "cpu_s_per_label": float(np.median(cpus))}

    try:
        section("fleet: worker bench-w0 joining (register + warm)")
        procs["bench-w0"] = _spawn_fleet_worker(base, "bench-w0")
        _wait_until(lambda: coord.stats()["live"] >= 1, 300,
                    "bench-w0 to register")

        # warmup batch doubles as the byte-identity check against the
        # in-process engine
        genomes = _population(ctx0.accel, library, G, seed=999)
        ref = _fresh_ctx(name, n_qor).ground_truth(genomes)
        lab = coord.label(_fresh_ctx(name, n_qor), genomes)
        identical = all(np.array_equal(np.asarray(ref[k]),
                                       np.asarray(lab[k]))
                        for k in DET_KEYS)
        front_identical = bool(np.array_equal(_front(ref), _front(lab)))
        emit("fleet.labels_identical", 0.0, identical)

        section(f"fleet 1 worker: {rounds} rounds x {G} genomes")
        one = phase([procs["bench-w0"].pid], seed0=100)
        emit("fleet.gaussian3x3.1_worker", one["s_per_label"] * 1e6,
             f"{one['labels_per_sec']:.2f}/s")

        section("fleet: worker bench-w1 joining (elastic, mid-campaign ok)")
        procs["bench-w1"] = _spawn_fleet_worker(base, "bench-w1")
        _wait_until(lambda: coord.stats()["live"] >= 2, 300,
                    "bench-w1 to register")
        coord.label(_fresh_ctx(name, n_qor),
                    _population(ctx0.accel, library, G, seed=998))  # warm w1

        section(f"fleet 2 workers: {rounds} rounds x {G} genomes")
        pids = [p.pid for p in procs.values()]
        two = phase(pids, seed0=200)
        emit("fleet.gaussian3x3.2_workers", two["s_per_label"] * 1e6,
             f"{two['labels_per_sec']:.2f}/s")

        measured = two["labels_per_sec"] / one["labels_per_sec"]
        # one worker is one process; projected onto a machine that
        # actually provides 2 cores the fleet runs both workers at
        # full speed:
        proj_1 = 1.0 / one["cpu_s_per_label"]
        proj_2 = 2.0 / two["cpu_s_per_label"]
        projected = proj_2 / proj_1
        emit("fleet.gaussian3x3.scaling", 0.0, f"{measured:.2f}x")
        emit("fleet.gaussian3x3.scaling_projected_2core", 0.0,
             f"{projected:.2f}x")

        section("fleet: kill -9 drill (bench-w0 dies holding a lease)")
        kd_genomes = _population(ctx0.accel, library, max(2 * G, 8),
                                 seed=4242)
        kd_ref = _fresh_ctx(name, n_qor).ground_truth(kd_genomes)
        out = {}
        th = threading.Thread(
            target=lambda: out.update(
                labels=coord.label(_fresh_ctx(name, n_qor), kd_genomes)),
            daemon=True)
        th.start()

        def _victim_leased():
            with coord._cv:
                return any(l.worker == "bench-w0"
                           for l in coord._leases.values())

        _wait_until(_victim_leased, 120, "bench-w0 to hold a lease")
        procs["bench-w0"].send_signal(signal.SIGKILL)
        th.join(timeout=600)
        assert "labels" in out, "kill drill batch never completed"
        kd = out["labels"]
        kd_identical = all(np.array_equal(np.asarray(kd_ref[k]),
                                          np.asarray(kd[k]))
                           for k in DET_KEYS)
        kd_front = bool(np.array_equal(_front(kd_ref), _front(kd)))
        stats = coord.stats()
        emit("fleet.kill_drill.labels_identical", 0.0, kd_identical)
        emit("fleet.kill_drill.requeues", 0.0, stats["requeues"])
    finally:
        for p in procs.values():
            if p.poll() is None:
                p.kill()
        coord.shutdown()
        srv.shutdown()

    report = {
        "mode": "fleet", "workload": name,
        "population": G, "rounds": rounds, "n_qor_samples": n_qor,
        "smoke": bool(args.smoke),
        "machine": {"os_cpu_count": os.cpu_count(),
                    "measured_parallel_ceiling_x": ceiling},
        "labels_identical": bool(identical),
        "front_identical": front_identical,
        "backends": {"fleet_1_worker": one, "fleet_2_workers": two},
        "scaling": {
            "measured_x": measured,
            "projected_2core_x": projected,
            "projected_1_worker_labels_per_sec": proj_1,
            "projected_2_worker_labels_per_sec": proj_2,
        },
        "kill_drill": {
            "completed": True,
            "labels_identical": bool(kd_identical),
            "front_identical": kd_front,
            "requeues": stats["requeues"],
            "expired_leases": stats["expired_leases"],
            "dead_workers": stats["dead_workers"],
            "duplicate_results": stats["duplicate_results"],
            "local_fallback_chunks": stats["local_fallback_chunks"],
            "remote_labels": stats["remote_labels"],
            "local_labels": stats["local_labels"],
        },
    }
    assert identical, "fleet labels diverged from in-process engine"
    assert kd_identical, "kill drill labels diverged"
    if not args.smoke and measured < 1.5 and projected < 1.5:
        print(f"WARNING: fleet 2-worker scaling {measured:.2f}x measured "
              f"/ {projected:.2f}x projected < 1.5x", file=sys.stderr)

    out_path = os.path.abspath(args.out)
    with open(out_path, "w") as f:
        json.dump(report, f, indent=1)
    print(f"wrote {out_path}", file=sys.stderr)


def run_fused_bench(args):
    """Steady-state (warm-synth-cache) engine comparison -> the
    ``fused`` section of BENCH_labeler.json.

    A long-lived campaign's label stream runs with warm per-circuit
    tables and a warm structural synthesis cache — the regime where the
    numpy behavioral sim is the dominant cost.  Each round draws fresh
    genomes, pre-pays their synthesis once (untimed warm pass, so every
    arm sees the same cached-synthesis work), then times three engines
    in alternating order:

      * ``numpy_batched_thread`` — the batched numpy engine, fused
        dispatch disabled via the REPRO_SIM_FUSED=0 kill switch
      * ``fused_thread``         — the fused XLA engine in-process
      * ``batched_process``      — the warm spawn pool (production
        default; its workers fuse too, the delta is IPC + chunking)

    Asserted: labels and Pareto fronts byte-identical across all three
    engines every round, and zero fused-engine recompiles across the
    timed generations (population bucketing holds)."""
    from repro.accel import fused
    from repro.core.acl.library import default_library
    from repro.service.workers import ProcessPoolLabeler, warm_library

    G = args.n or (4 if args.smoke else 16)
    rounds = args.rounds or (1 if args.smoke else 5)
    n_qor = 2 if args.smoke else 4
    library = default_library()
    warm_library(library)
    fused.warm(library)

    section(f"warming process pool ({WORKERS} spawn workers)")
    pool = ProcessPoolLabeler(WORKERS)
    for name in ("gaussian3x3", "smoothed_dct"):
        wctx = _fresh_ctx(name, n_qor)
        pool.label(wctx, _population(wctx.accel, library, G, seed=777))
    worker_pids = list(getattr(pool._pool, "_processes", {}) or [])

    backends = ("numpy_batched_thread", "fused_thread", "batched_process")
    fused_report = {
        "population": G, "rounds": rounds, "n_qor_samples": n_qor,
        "workers": WORKERS, "smoke": bool(args.smoke),
        "workloads": {},
    }

    def run_numpy(ctx, genomes):
        os.environ["REPRO_SIM_FUSED"] = "0"
        try:
            t0 = time.perf_counter()
            labels = ctx.ground_truth(genomes)
            return labels, time.perf_counter() - t0
        finally:
            del os.environ["REPRO_SIM_FUSED"]

    def run_fused(ctx, genomes):
        t0 = time.perf_counter()
        labels = ctx.ground_truth(genomes)
        return labels, time.perf_counter() - t0

    def run_process(ctx, genomes):
        t0 = time.perf_counter()
        labels = pool.label(ctx, genomes)
        return labels, time.perf_counter() - t0

    for name in ("gaussian3x3", "smoothed_dct"):
        section(f"{name} steady-state: {rounds} rounds x {G} genomes "
                f"x 3 engines")
        ctx = _fresh_ctx(name, n_qor)
        # engine warmup: exhausts the fused verification budget and
        # compiles the population bucket; 2 calls per switch state so
        # both arms start steady
        for seed in (888, 889):
            warm_genomes = _population(ctx.accel, library, G, seed=seed)
            run_fused(ctx, warm_genomes)
            run_numpy(ctx, warm_genomes)
            run_process(ctx, warm_genomes)
        compiles_baseline = fused.stats()["compiles"]
        assert fused.stats()["pins"] == 0, "fused engine pinned during warmup"

        walls = {b: [] for b in backends}
        cpus = {b: [] for b in backends}
        identical = front_identical = True
        for rnd in range(rounds):
            genomes = _population(ctx.accel, library, G, seed=1000 + rnd)
            # pre-pay this round's synthesis once IN EVERY ARM'S CACHE
            # DOMAIN (parent and worker processes) so every arm measures
            # the warm-cache regime, not who-went-first
            run_fused(ctx, genomes)
            run_process(ctx, genomes)
            arms = [("numpy_batched_thread", run_numpy),
                    ("fused_thread", run_fused),
                    ("batched_process", run_process)]
            if rnd % 2:
                arms.reverse()
            labels = {}
            for backend, fn in arms:
                c0 = _cpu_snapshot(worker_pids)
                lab, wall = fn(ctx, genomes)
                cpus[backend].append((_cpu_snapshot(worker_pids) - c0) / G)
                walls[backend].append(wall / G)
                labels[backend] = {k: np.asarray(lab[k]) for k in DET_KEYS}
            base = labels["numpy_batched_thread"]
            identical &= all(
                np.array_equal(base[k], labels[b][k])
                for b in backends[1:] for k in DET_KEYS
            )
            fronts = {b: _front(labels[b]) for b in backends}
            front_identical &= all(
                np.array_equal(fronts[backends[0]], fronts[b])
                for b in backends[1:]
            )
        recompiles = fused.stats()["compiles"] - compiles_baseline

        results = {}
        for b in backends:
            wall = float(np.median(walls[b]))
            results[b] = {
                "s_per_label": wall,
                "labels_per_sec": 1.0 / wall,
                "cpu_s_per_label": float(np.median(cpus[b])),
            }
            emit(f"labeler.fused.{name}.{b}", wall * 1e6,
                 f"{1.0 / wall:.2f}/s")
        speed_vs_numpy = (results["fused_thread"]["labels_per_sec"]
                          / results["numpy_batched_thread"]["labels_per_sec"])
        speed_vs_process = (results["fused_thread"]["labels_per_sec"]
                            / results["batched_process"]["labels_per_sec"])
        emit(f"labeler.fused.{name}.speedup_vs_numpy", 0.0,
             f"{speed_vs_numpy:.2f}x")
        emit(f"labeler.fused.{name}.speedup_vs_process", 0.0,
             f"{speed_vs_process:.2f}x")
        emit(f"labeler.fused.{name}.steady_state_recompiles", 0.0,
             recompiles)
        fused_report["workloads"][name] = {
            "backends": results,
            "fused_speedup_vs_numpy_batched": speed_vs_numpy,
            "fused_speedup_vs_batched_process": speed_vs_process,
            "labels_identical": bool(identical),
            "front_identical": bool(front_identical),
            "steady_state_recompiles": int(recompiles),
            "engine_stats": fused.stats(),
        }
        assert identical, f"{name}: engine labels diverged"
        assert front_identical, f"{name}: engine fronts diverged"
        assert recompiles == 0, (
            f"{name}: {recompiles} steady-state recompiles (bucketing "
            f"failed to absorb the generations)"
        )

    pool.shutdown()
    best = max(
        wl["fused_speedup_vs_batched_process"]
        for wl in fused_report["workloads"].values()
    )
    if not args.smoke and best < 1.5:
        print(f"WARNING: best fused-vs-process speedup {best:.2f}x < 1.5x",
              file=sys.stderr)

    out_path = os.path.abspath(args.out)
    if args.smoke:
        print(f"smoke mode: not writing {out_path}", file=sys.stderr)
        return
    # merge into the existing default-mode report instead of clobbering it
    report = {}
    if os.path.exists(out_path):
        with open(out_path) as f:
            report = json.load(f)
    report["fused"] = fused_report
    with open(out_path, "w") as f:
        json.dump(report, f, indent=1)
    print(f"wrote {out_path}", file=sys.stderr)


def run_obs_bench(args):
    """Flight-recorder overhead guardrail -> BENCH_obs.json.

    Two measurements, both with the JSONL sink DISABLED (the sink is
    opt-in and pays I/O by design; the guardrail is about the always-on
    span machinery):

      * span microbench — enter/exit cost of one instrumented region
        with tracing on (ring append) vs off (null span), isolated from
        the workload.
      * labels/sec — the real scheduler path (submit -> coalesce ->
        batched ground truth -> resolve) with tracing enabled vs
        disabled, interleaved rounds with alternating order and fresh
        genomes per arm (no store/synth-cache cross-feeding), median
        per-label wall.

    Target: <3% labels/sec overhead.  Reported, and warned about when
    exceeded — not asserted, because shared-host wall clocks drift by
    more than 3% between back-to-back identical runs."""
    from repro import obs
    from repro.core.acl.library import default_library
    from repro.service import EvalScheduler, InMemoryLabelStore
    from repro.service.workers import warm_library

    name = "gaussian3x3"
    G = args.n or (4 if args.smoke else 16)
    rounds = args.rounds or (2 if args.smoke else 5)
    n_qor = 2 if args.smoke else 4
    library = default_library()
    warm_library(library)
    obs.set_sink(None)

    section("span machinery microbench (sink disabled)")
    N = 5_000 if args.smoke else 50_000
    span_cost = {}
    for arm, enabled in (("on", True), ("off", False)):
        obs.set_enabled(enabled)
        t0 = time.perf_counter()
        for _ in range(N):
            with obs.span("bench.noop", k=1):
                pass
        span_cost[arm] = (time.perf_counter() - t0) / N
        emit(f"obs.span_{arm}", span_cost[arm] * 1e6,
             f"{span_cost[arm] * 1e9:.0f}ns")
    obs.set_enabled(True)

    section(f"scheduler labels/sec, tracing on vs off: "
            f"{rounds} rounds x {G} genomes x 2 arms")
    walls = {"on": [], "off": []}
    seed = 0
    # warm the per-circuit caches once so both arms measure steady state
    wctx = _fresh_ctx(name, n_qor)
    wctx.ground_truth(_population(wctx.accel, library, 2, seed=777))
    for rnd in range(rounds):
        order = ("on", "off") if rnd % 2 == 0 else ("off", "on")
        for arm in order:
            obs.set_enabled(arm == "on")
            sched = EvalScheduler(InMemoryLabelStore(), n_workers=1,
                                  max_batch=G, max_wait_s=0.001)
            ctx = _fresh_ctx(name, n_qor)
            genomes = _population(ctx.accel, library, G, seed=seed)
            seed += 1
            t0 = time.perf_counter()
            for fut in sched.submit(ctx, genomes):
                fut.result(timeout=600)
            walls[arm].append((time.perf_counter() - t0) / G)
            sched.shutdown()
    obs.set_enabled(True)

    on = float(np.median(walls["on"]))
    off = float(np.median(walls["off"]))
    overhead_pct = (on - off) / off * 100.0
    emit("obs.labels_per_sec.on", on * 1e6, f"{1.0 / on:.2f}/s")
    emit("obs.labels_per_sec.off", off * 1e6, f"{1.0 / off:.2f}/s")
    emit("obs.overhead_pct", 0.0, f"{overhead_pct:+.2f}%")
    if overhead_pct > 3.0:
        print(f"WARNING: tracing overhead {overhead_pct:+.2f}% > 3% "
              f"target (shared-host noise is +-40%; rerun before "
              f"trusting)", file=sys.stderr)

    report = {
        "mode": "obs", "workload": name,
        "population": G, "rounds": rounds, "n_qor_samples": n_qor,
        "smoke": bool(args.smoke),
        "machine": {"os_cpu_count": os.cpu_count()},
        "span_cost_s": {"on": span_cost["on"], "off": span_cost["off"]},
        "labels": {
            "on_s_per_label": on, "off_s_per_label": off,
            "on_labels_per_sec": 1.0 / on,
            "off_labels_per_sec": 1.0 / off,
            "overhead_pct": overhead_pct,
        },
        "target_overhead_pct": 3.0,
        "within_target": bool(overhead_pct <= 3.0),
    }
    out_path = os.path.abspath(args.out)
    with open(out_path, "w") as f:
        json.dump(report, f, indent=1)
    print(f"wrote {out_path}", file=sys.stderr)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny population, one round (CI: exercise all "
                         "three backends, don't trust the ratios)")
    ap.add_argument("--fused", action="store_true",
                    help="steady-state engine comparison (numpy batched "
                         "vs fused XLA vs process pool, warm synth "
                         "caches) merged into BENCH_labeler.json under "
                         "the 'fused' key")
    ap.add_argument("--fleet", action="store_true",
                    help="benchmark the multi-host labeling fleet "
                         "(1 vs 2 local workers + kill -9 drill) and "
                         "write BENCH_fleet.json instead")
    ap.add_argument("--obs", action="store_true",
                    help="measure flight-recorder overhead (tracing on "
                         "vs off, sink disabled) and write "
                         "BENCH_obs.json instead")
    ap.add_argument("-n", type=int, default=None,
                    help="population size per round")
    ap.add_argument("--rounds", type=int, default=None)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    from repro.service.workers import require_cpu_backend

    require_cpu_backend(
        "benchmarks/labeler_throughput.py", "run it with JAX_PLATFORMS=cpu")
    root = os.path.join(os.path.dirname(__file__), "..")
    args.out = args.out or os.path.join(
        root, "BENCH_obs.json" if args.obs
        else "BENCH_fleet.json" if args.fleet else "BENCH_labeler.json")
    if args.obs:
        return run_obs_bench(args)
    if args.fleet:
        return run_fleet_bench(args)
    if args.fused:
        return run_fused_bench(args)

    from repro.core.acl.library import default_library
    from repro.service.workers import ProcessPoolLabeler, warm_library

    G = args.n or (4 if args.smoke else 8)
    rounds = args.rounds or (1 if args.smoke else 3)
    n_qor = 2 if args.smoke else 4
    library = default_library()
    # steady-state measurement for EVERY backend: per-circuit caches
    # (tables, error SVDs) are warm, as in a long-lived service
    warm_library(library)

    section("machine parallelism probe")
    ceiling = _parallel_ceiling()
    emit("labeler.parallel_ceiling", 0.0, f"{ceiling:.2f}x")

    section(f"warming process pool ({WORKERS} spawn workers)")
    pool = ProcessPoolLabeler(WORKERS)
    t0 = time.perf_counter()
    for name in ("gaussian3x3", "smoothed_dct"):
        wctx = _fresh_ctx(name, n_qor)
        pool.label(wctx, _population(wctx.accel, library, 2 * WORKERS,
                                     seed=777))
    emit("labeler.pool_warmup", (time.perf_counter() - t0) * 1e6, WORKERS)
    worker_pids = list(getattr(pool._pool, "_processes", {}) or [])

    backends = ("per_genome_thread", "batched_thread", "batched_process")
    report = {
        "population": G, "rounds": rounds, "n_qor_samples": n_qor,
        "workers": WORKERS, "smoke": bool(args.smoke),
        "machine": {"os_cpu_count": os.cpu_count(),
                    "measured_parallel_ceiling_x": ceiling},
        "workloads": {},
    }
    for name in ("gaussian3x3", "smoothed_dct"):
        section(f"{name}: {rounds} rounds x {G} genomes x 3 backends")
        ctx0 = _fresh_ctx(name, n_qor)
        walls = {b: [] for b in backends}
        cpus = {b: [] for b in backends}
        identical = front_identical = True
        front_size = 0
        for rnd in range(rounds):
            genomes = _population(ctx0.accel, library, G, seed=rnd)
            labels = {}
            for backend, fn in (
                ("per_genome_thread",
                 lambda: bench_per_genome_thread(name, genomes, n_qor)),
                ("batched_thread",
                 lambda: bench_batched_thread(name, genomes, n_qor)),
                ("batched_process",
                 lambda: bench_batched_process(name, genomes, n_qor, pool)),
            ):
                c0 = _cpu_snapshot(worker_pids)
                lab, wall = fn()
                cpus[backend].append((_cpu_snapshot(worker_pids) - c0) / G)
                walls[backend].append(wall / G)
                labels[backend] = {k: np.asarray(lab[k]) for k in DET_KEYS}
            base = labels["per_genome_thread"]
            identical &= all(
                np.array_equal(base[k], labels[b][k])
                for b in backends[1:] for k in DET_KEYS
            )
            fronts = {b: _front(labels[b]) for b in backends}
            front_identical &= all(
                np.array_equal(fronts[backends[0]], fronts[b])
                for b in backends[1:]
            )
            front_size = int(len(fronts[backends[0]]))

        results = {}
        for b in backends:
            wall = float(np.median(walls[b]))
            results[b] = {
                "s_per_label": wall,
                "labels_per_sec": 1.0 / wall,
                "cpu_s_per_label": float(np.median(cpus[b])),
            }
            emit(f"labeler.{name}.{b}", wall * 1e6,
                 f"{1.0 / wall:.2f}/s")
        speedups = {
            b: (results[b]["labels_per_sec"]
                / results["per_genome_thread"]["labels_per_sec"])
            for b in backends[1:]
        }
        # the process backend parallelizes across real cores; the seed
        # per-genome thread backend cannot (GIL).  Project both onto a
        # machine that actually provides WORKERS cores:
        proj = {
            "per_genome_thread":
                1.0 / results["per_genome_thread"]["cpu_s_per_label"],
            "batched_process":
                WORKERS / results["batched_process"]["cpu_s_per_label"],
        }
        proj["speedup"] = proj["batched_process"] / proj["per_genome_thread"]
        emit(f"labeler.{name}.process_speedup", 0.0,
             f"{speedups['batched_process']:.2f}x")
        emit(f"labeler.{name}.process_speedup_projected_{WORKERS}core", 0.0,
             f"{proj['speedup']:.2f}x")
        report["workloads"][name] = {
            "backends": results,
            "speedup_vs_per_genome_thread": speedups,
            "projected_full_parallel": proj,
            "labels_identical": bool(identical),
            "front_identical": bool(front_identical),
            "front_size": front_size,
        }
        assert identical, f"{name}: backend labels diverged"
        assert front_identical, f"{name}: backend fronts diverged"

    pool.shutdown()
    wl = report["workloads"]["smoothed_dct"]
    measured = wl["speedup_vs_per_genome_thread"]["batched_process"]
    projected = wl["projected_full_parallel"]["speedup"]
    if not args.smoke and measured < 3.0 and projected < 3.0:
        print(f"WARNING: smoothed_dct batched-process speedup "
              f"{measured:.2f}x measured / {projected:.2f}x projected < 3x",
              file=sys.stderr)

    out_path = os.path.abspath(args.out)
    if args.smoke:
        print(f"smoke mode: not writing {out_path}", file=sys.stderr)
        return
    with open(out_path, "w") as f:
        json.dump(report, f, indent=1)
    print(f"wrote {out_path}", file=sys.stderr)


if __name__ == "__main__":
    main()
