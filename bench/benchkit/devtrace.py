"""The profiler's trace of a measured window, reduced to numbers.

``Tracer`` records a window with ``jax.profiler`` into a fixed directory
of the checkout.  ``reduce`` turns the trace into what the per-layer
readers and the result line need: device busy time (the union of the
intervals in which an operation ran, averaged over the devices that ran
any), the time and count of each XLA module (one entry per jitted
program, named ``jit_<function>``), the time of each operation, and the
idle gaps.  It takes any object shaped like ``jax.profiler.ProfileData``
(planes with lines with events carrying ``name``, ``start_ns`` and
``duration_ns``), so it is tested on a trace built by hand.
"""

from __future__ import annotations

import glob
import os
import re
import shutil
import time
from collections import defaultdict

_ID = re.compile(r"\(\d+\)$")


def module_name(name: str) -> str:
    """'jit_serve_step(42)' -> 'jit_serve_step'."""
    return _ID.sub("", name.strip())


def _union(intervals):
    """Sorted disjoint union of (start, end) pairs -> (total, merged)."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), merged


def _device_planes(profile):
    return [p for p in profile.planes
            if p.name.startswith("/device:") and "CPU" not in p.name]


def reduce(profile, window_s: float, *, max_gaps: int = 10) -> dict:
    """-> {busy_s, window_s, devices, modules: {name: [count, seconds]},
    ops: {name: seconds}, gaps: [(start_s, seconds)], origin_ns}.

    ``busy_s`` is None where the trace holds no device operation."""
    modules = defaultdict(lambda: [0, 0.0])
    ops = defaultdict(float)
    busy, gaps, devices, origin = [], [], 0, None
    for plane in _device_planes(profile):
        lines = {ln.name: ln for ln in plane.lines}
        op_line = lines.get("XLA Ops")
        mod_line = lines.get("XLA Modules")
        intervals = []
        if op_line is not None:
            for ev in op_line.events:
                # '%fusion.3 = bf16[...] fusion(...)' -> '%fusion.3'
                ops[ev.name.split(" = ", 1)[0]] += ev.duration_ns / 1e9
                intervals.append((ev.start_ns, ev.start_ns + ev.duration_ns))
        if mod_line is not None:
            for ev in mod_line.events:
                m = modules[module_name(ev.name)]
                m[0] += 1
                m[1] += ev.duration_ns / 1e9
                if op_line is None:
                    intervals.append((ev.start_ns, ev.start_ns + ev.duration_ns))
        if not intervals:
            continue
        devices += 1
        total, merged = _union(intervals)
        busy.append(total / 1e9)
        first = merged[0][0]
        origin = first if origin is None else min(origin, first)
        for (_, e0), (s1, _) in zip(merged, merged[1:]):
            gaps.append((e0, (s1 - e0) / 1e9))
    gaps.sort(key=lambda g: -g[1])
    return {
        "busy_s": (sum(busy) / len(busy)) if busy else None,
        "window_s": float(window_s),
        "devices": devices,
        "modules": {k: [v[0], v[1]] for k, v in modules.items()},
        "ops": dict(ops),
        "gaps_ns": gaps[:max_gaps],
        "origin_ns": origin,
    }


def module_time(reduced: dict, name: str):
    """(count, seconds) of one jitted program, or None if it never ran."""
    if not reduced:
        return None
    m = reduced["modules"].get(name)
    return None if not m or m[0] == 0 else (m[0], m[1])


def breakdown(reduced: dict, label_gap=None) -> dict:
    """The ten device operations that took most time, and the longest
    idle gaps named by ``label_gap(start_ns, seconds)``."""
    top = sorted(reduced["ops"].items(), key=lambda kv: -kv[1])[:10]
    if not top:
        top = sorted(((k, v[1]) for k, v in reduced["modules"].items()),
                     key=lambda kv: -kv[1])[:10]
    gaps = [[label_gap(s, d) if label_gap else "idle", d]
            for s, d in reduced["gaps_ns"]]
    return {"device_ops": [[k, v] for k, v in top], "idle_gaps": gaps}


class Tracer:
    """One profiler trace into a fixed directory, cleared first."""

    def __init__(self, directory: str):
        self.dir = directory
        self.t0 = self.t1 = None
        self.wall0 = None

    def start(self):
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.wall0 = time.time()
        self.t0 = time.perf_counter()

    def stop(self):
        import jax

        self.t1 = time.perf_counter()
        jax.profiler.stop_trace()

    def load(self):
        from jax.profiler import ProfileData

        files = sorted(glob.glob(os.path.join(
            self.dir, "plugins", "profile", "*", "*.xplane.pb")))
        if not files:
            return None
        return ProfileData.from_file(files[-1])

    def reduce(self) -> dict:
        prof = self.load()
        window = self.t1 - self.t0
        if prof is None:
            return {"busy_s": None, "window_s": window, "devices": 0,
                    "modules": {}, "ops": {}, "gaps_ns": [], "origin_ns": None}
        return reduce(prof, window)
