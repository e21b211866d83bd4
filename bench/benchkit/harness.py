"""The benchmark's one command, driven by data.

``BENCHMARK.json`` names each cell's configuration and traffic mix.  The
configuration is ``bench/configs/<config>.json`` (through the entry's
``file``), the traffic mix is ``bench/traffic/<traffic>.json``, and the
mix's ``driver`` names the general generator in ``benchkit.drivers``
that reads it.  Each per-layer metric is ``bench/metrics/<name>.py``, a
reader with ``read(ctx)`` that returns a number or None.  A cell, a mix
or a metric is added with files and entries; nothing here changes.

A run: set-up (the driver builds the system, makes its inputs and
weights from the seed and warms every shape its traffic uses), a window
of ``--seconds`` (traced with ``--trace 1``), the drain of what was sent
in the window, the peak memory, the release of the program's state, and
the comparison with the plain reference that decides ``correct``.  The
last line of standard output is the result.
"""

from __future__ import annotations

import argparse
import gc
import importlib
import importlib.util
import json
import math
import os
import shutil
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


class Refused(SystemExit):
    """The run cannot be made here: exit non-zero, print no result."""

    def __init__(self, msg: str):
        super().__init__(f"bench: {msg}")


def load_json(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def load_spec(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def cells_reporting(spec: dict, metric: dict) -> list:
    """The cells in which ``metric`` is reported."""
    names = [w["name"] for w in spec["workloads"]]
    if "workloads" in metric:
        return [n for n in names if n in metric["workloads"]]
    moved = next((m for m in spec["end_to_end"]
                  if m["name"] == metric.get("moves")), None)
    return cells_reporting(spec, moved) if moved is not None else names


def resolve(spec: dict, workload: str, root: str = ROOT) -> dict:
    """Everything one cell needs, found by name."""
    cell = next((w for w in spec["workloads"] if w["name"] == workload), None)
    if cell is None:
        raise Refused(f"no workload {workload!r} in BENCHMARK.json")
    entry = next(c for c in spec["configs"] if c["name"] == cell["config"])
    return {
        "cell": cell,
        "config": load_json(os.path.join(root, entry["file"])),
        "traffic": load_json(os.path.join(root, "bench", "traffic",
                                          cell["traffic"] + ".json")),
        "end_to_end": [m for m in spec["end_to_end"]
                       if workload in cells_reporting(spec, m)],
        "per_layer": [m for m in spec["per_layer"]
                      if workload in cells_reporting(spec, m)],
    }


def load_reader(name: str, root: str = ROOT):
    path = os.path.join(root, "bench", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def driver_for(traffic: dict):
    return importlib.import_module("benchkit.drivers." + traffic["driver"])


class Run:
    """What a driver is given: the cell, its data, the seed and a fixed
    working directory inside the checkout."""

    def __init__(self, workload, seed, seconds, trace, resolved, root):
        self.workload = workload
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.cell = resolved["cell"]
        self.config = resolved["config"]
        self.traffic = resolved["traffic"]
        self.root = root
        self.workdir = os.path.join(root, "bench_runs", workload)

    def log(self, msg: str) -> None:
        print(f"[bench {self.workload}] {msg}", file=sys.stderr, flush=True)


def _device_info(jax, chips: int) -> dict:
    devs = jax.devices()[:chips]
    peaks = []
    for d in devs:
        try:
            st = d.memory_stats() or {}
        except Exception:  # noqa: BLE001 - backends without memory stats
            st = {}
        peaks.append(int(st.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": max(peaks)}


def _finite(v):
    return v is not None and isinstance(v, (int, float)) and math.isfinite(v)


def _spans_between(t0_wall: float, t1_wall: float) -> list:
    from repro import obs

    return [s for s in obs.recorder().snapshot()
            if s.get("t0", 0) >= t0_wall and s.get("t0", 0) <= t1_wall]


def _gap_labeler(spans, tracer, reduced):
    """Name an idle gap by the innermost program span open at its middle.
    Spans are on the host's wall clock and the trace on its own; the two
    are aligned by taking the trace's first device operation to fall when
    the profiler started, which is exact only where the device was busy
    from the start (PERF.md, Open questions: the tracing issue)."""
    origin = reduced.get("origin_ns")

    def label(start_ns, seconds):
        if origin is None:
            return "idle"
        mid = tracer.wall0 + (start_ns - origin) / 1e9 + seconds / 2
        open_ = [s for s in spans if s["t0"] <= mid <= s["t0"] + s["dur"]]
        if not open_:
            return "no program span"
        return min(open_, key=lambda s: s["dur"])["name"]

    return label


def parse_args(argv):
    ap = argparse.ArgumentParser(prog="bench/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, *, t_start=None, root=ROOT, require_accelerator=True,
         resolved=None, compile_cache=True, control=False, workdir=None) -> int:
    """One run.  ``require_accelerator=False``, a ``resolved`` cell,
    ``compile_cache=False`` and a ``workdir`` of their own are for
    rehearsals on the CPU at a small size;
    ``control=True`` also reads the control on the answers the check
    compared (``bench/calibrate.py``; never in a benchmark run)."""
    t_start = time.perf_counter() if t_start is None else t_start
    args = parse_args(argv)
    if not os.path.isdir(os.path.join(root, "src", "repro")):
        raise Refused("src/repro is not beside bench/: run from a checkout "
                      "of the repository")
    spec = load_spec(root)
    if resolved is None:
        resolved = resolve(spec, args.workload, root)
    chips = int(resolved["cell"]["chips"])

    import jax

    if compile_cache:
        # JAX's persistent cache: where the environment says, else at a
        # fixed path inside the checkout; every program is kept
        cache = os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                                      os.path.join(root, ".jax_cache"))
        jax.config.update("jax_compilation_cache_dir", cache)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devs = jax.devices()
    if require_accelerator:
        if devs[0].platform == "cpu":
            raise Refused("JAX found no accelerator")
        if len(devs) < chips:
            raise Refused(f"the cell needs {chips} chips; JAX found {len(devs)}")
    src = os.path.join(root, "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    from repro import obs

    obs.setup_logging("warning")
    run = Run(args.workload, args.seed, args.seconds, args.trace,
              resolved, root)
    if workdir is not None:
        run.workdir = workdir
    shutil.rmtree(run.workdir, ignore_errors=True)
    os.makedirs(run.workdir, exist_ok=True)
    driver = driver_for(run.traffic).Driver(run)
    try:
        driver.setup()
        setup_s = time.perf_counter() - t_start
        run.log(f"set-up {setup_s:.3f} s")

        from .devtrace import Tracer

        tracer = Tracer(os.path.join(run.workdir, "trace")) if run.trace else None
        if tracer:
            tracer.start()
        t0_wall = time.time()
        t0 = time.perf_counter()
        driver.window(t0, t0 + run.seconds)
        t1_wall = time.time()
        if tracer:
            tracer.stop()
        driver.finish()
        device = _device_info(jax, chips)

        metrics = {}
        if run.trace:
            from . import devtrace
            from .peaks import peaks_for

            reduced = tracer.reduce()
            top = sorted(reduced["modules"].items(), key=lambda kv: -kv[1][1])[:12]
            run.log(f"traced {reduced['window_s']:.3f} s, device busy "
                    f"{reduced['busy_s']} s; modules {top}")
            spans = _spans_between(t0_wall, t1_wall)
            ctx = {"layer": driver.layer_inputs(), "spans": spans,
                   "trace": reduced if reduced["busy_s"] else None,
                   "peaks": (None if device["platform"] == "cpu"
                             else peaks_for(device["kind"])),
                   "config": run.config,
                   "window_s": reduced["window_s"]}
            for m in resolved["per_layer"]:
                value = load_reader(m["name"], root)(ctx)
                if _finite(value):
                    metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
            device["busy_s"] = reduced["busy_s"] or 0.0
            device["window_s"] = reduced["window_s"]
            bd = devtrace.breakdown(reduced, _gap_labeler(spans, tracer, reduced))
            shutil.rmtree(tracer.dir, ignore_errors=True)
        else:
            e2e = driver.e2e()
            for m in resolved["end_to_end"]:
                value = setup_s if m["name"] == "setup_s" else e2e.get(m["name"])
                if _finite(value):
                    metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

        driver.release()
        gc.collect()
        checks = driver.check()
        controls = driver.control() if control and checks else None
    finally:
        driver.close()
    correct = (driver.failed == 0 and bool(checks)
               and all(c["value"] <= c["limit"] for c in checks))
    for c in checks:
        print(f"check {c['name']}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(f"check failed_requests: {driver.failed} (limit 0)", file=sys.stderr,
          flush=True)
    line = {"correct": correct, "attempted": driver.attempted,
            "failed": driver.failed, "metrics": metrics, "device": device}
    if run.trace:
        line["breakdown"] = bd
    if controls is not None:
        line["control"] = controls
    line["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                      for c in checks}
    line["checks"]["failed_requests"] = {"value": driver.failed, "limit": 0}
    print(json.dumps(line), flush=True)
    return 0
