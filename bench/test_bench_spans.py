"""The program's spans as the benchmark sees them: on the profiler's host
plane under the harness's own profile options, and read by the
``program_span`` metrics from hand-built span lists."""

import os
import subprocess
import sys

import pytest

from benchkit import harness
from benchkit.devtrace import Tracer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _host_annotations(profile):
    """{name: [(start_ns, end_ns, span id)]} of the bridged annotations
    (events carrying a ``span`` stat) on the host plane."""
    out = {}
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                stats = dict(ev.stats)
                if "span" in stats:
                    out.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns, stats["span"]))
    return out


def test_nested_spans_are_annotations_on_the_host_plane(tmp_path):
    import jax.numpy as jnp

    from repro import obs

    tracer = Tracer(str(tmp_path / "trace"))
    tracer.start()
    with obs.span("bench.outer") as outer:
        with obs.span("bench.inner") as inner:
            jnp.arange(8).sum().block_until_ready()
    tracer.stop()
    ann = _host_annotations(tracer.load())
    (o0, o1, oid), = ann["bench.outer"]
    (i0, i1, iid), = ann["bench.inner"]
    assert (oid, iid) == (outer.span_id, inner.span_id)
    assert o0 <= i0 < i1 <= o1
    recs = {r["span"]: r for r in obs.recorder().snapshot()}
    assert recs[iid]["parent"] == oid
    # the record and its annotation time the same region
    assert (o1 - o0) / 1e9 == pytest.approx(recs[oid]["dur"], abs=2e-3)


def test_repro_obs_off_leaves_no_annotation_and_no_record(tmp_path):
    script = f"""
import jax, jax.numpy as jnp
from benchkit.devtrace import Tracer
from repro import obs
tracer = Tracer({str(tmp_path / "trace")!r})
x = jnp.ones(4)
tracer.start()
with obs.span("bench.off"):
    jax.jit(lambda x: x * 3).lower(x).compile()
tracer.stop()
names = [ev.name for p in tracer.load().planes for ln in p.lines for ev in ln.events]
print(obs.enabled(), "bench.off" in names, len(obs.recorder().snapshot()),
      obs.REGISTRY.get("repro_jit_compiles_total").value)
"""
    env = dict(os.environ, REPRO_OBS="0", JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([os.path.join(ROOT, "src"),
                                           os.path.join(ROOT, "bench")]))
    out = subprocess.run([sys.executable, "-c", script], env=env, cwd=str(tmp_path),
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr
    # tracing off: no annotation, no record; the compile still counts
    assert out.stdout.split() == ["False", "False", "0", "1.0"]


# -- the readers, on hand-built spans ---------------------------------------

def rec(name, span, dur, parent=None, **attrs):
    return {"name": name, "span": span, "parent": parent, "t0": 0.0,
            "dur": dur, "attrs": attrs}


def campaign_spans():
    return [
        rec("synth.compile", "c1", 0.50),
        rec("jit.compile", "t1", 0.10, "c1", phase="trace", nested=30),
        rec("jit.compile", "t1b", 0.02, "c1", phase="trace"),   # an eager helper
        rec("jit.compile", "l1", 0.05, "c1", phase="lower"),
        rec("jit.compile", "b1", 0.30, "c1", phase="backend"),
        rec("synth.compile", "c2", 0.40),
        rec("jit.compile", "t2", 0.08, "c2", phase="trace"),
        rec("jit.compile", "l2", 0.04, "c2", phase="lower"),
        rec("jit.compile", "b2", 0.25, "c2", phase="backend"),
        # a compile outside synthesis (the fused engine's) is not counted
        rec("sim.fused", "f1", 0.9),
        rec("jit.compile", "b3", 0.70, "f1", phase="backend"),
        rec("sched.batch", "s1", 1.0, n=30, wait_s=0.2),
        rec("sched.batch", "s2", 1.0, n=10, wait_s=0.6),
    ]


def lm_spans():
    return [
        rec("serving.request", "r1", 5.0, queue_s=0.001),
        rec("serving.request", "r2", 5.0, queue_s=0.003),
        rec("serving.group", "g1", 4.72),
        rec("lm.prefill", "p1", 0.33, "g1"),
        rec("lm.decode", "d1", 4.37, "g1"),
        rec("serving.group", "g2", 4.70),
        rec("lm.prefill", "p2", 0.32, "g2"),
        rec("lm.decode", "d2", 4.36, "g2"),
        # the group in flight at the close: its decode began after it
        rec("serving.group", "g3", 4.70),
        rec("lm.prefill", "p3", 0.32, "g3"),
    ]


CAMPAIGN = {"kind": "campaign", "labels": 4}
LM = {"kind": "lm", "groups": 2, "responses": 16}


@pytest.mark.parametrize("name, layer, spans, want", [
    ("synth.trace_ms_per_label", CAMPAIGN, campaign_spans,
     (0.10 + 0.05 + 0.08 + 0.04) * 1e3 / 4),
    ("synth.backend_compile_ms_per_label", CAMPAIGN, campaign_spans,
     (0.30 + 0.25) * 1e3 / 4),
    ("sched.queue_ms", CAMPAIGN, campaign_spans, (0.2 * 30 + 0.6 * 10) * 1e3 / 40),
    ("serving.queue_ms.lm", LM, lm_spans, 2.0),
    ("serving.host_ms_per_group.lm", LM, lm_spans, 20.0),
])
def test_span_reader(name, layer, spans, want):
    read = harness.load_reader(name)
    assert read({"layer": layer, "spans": spans()}) == pytest.approx(want)
    # without its spans (the program before these spans existed): nothing
    assert read({"layer": layer, "spans": []}) is None
    bare = [s for s in spans() if s["name"] not in
            ("jit.compile", "lm.prefill", "lm.decode")]
    for s in bare:
        s["attrs"].pop("wait_s", None)
        s["attrs"].pop("queue_s", None)
    assert read({"layer": layer, "spans": bare}) is None
