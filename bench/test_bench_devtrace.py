"""The reduction from a profiler trace to the per-layer numbers, on a
trace built by hand: busy and idle share, time by XLA module and by
operation, the idle gaps, and the roofline and MFU arithmetic."""

from types import SimpleNamespace as NS

import pytest

from benchkit import devtrace, harness, lmcost
from benchkit.peaks import PEAKS, peaks_for

MS = 1_000_000  # nanoseconds


def ev(name, start_ms, dur_ms):
    return NS(name=name, start_ns=start_ms * MS, duration_ns=dur_ms * MS, stats=[])


def trace():
    """One TPU plane: a prefill (ops 0-100 ms), two decode steps (30 ms
    each, at 150 and 200 ms), an op that overlaps another; plus a host
    plane whose events must not count as device time."""
    tpu = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[
            ev("jit_prefill(7)", 0, 100),
            ev("jit_serve_step(9)", 150, 30),
            ev("jit_serve_step(9)", 200, 30),
        ]),
        NS(name="XLA Ops", events=[
            ev("fusion.1 = s32[8] fusion(s32[8] %p)", 0, 60),
            ev("dot.2", 50, 50),                                # overlap 50-60
            ev("dot.3", 150, 30), ev("dot.3", 200, 30),
        ]),
    ])
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[ev("x", 0, 400)])])
    return NS(planes=[host, tpu])


def test_busy_idle_modules_ops_and_gaps():
    r = devtrace.reduce(trace(), window_s=0.4)
    assert r["devices"] == 1
    assert r["busy_s"] == pytest.approx(0.160)         # 0-100, 150-180, 200-230
    assert r["window_s"] == 0.4
    assert r["modules"]["jit_serve_step"] == [2, pytest.approx(0.060)]
    assert r["modules"]["jit_prefill"] == [1, pytest.approx(0.100)]
    assert r["ops"]["dot.3"] == pytest.approx(0.060)
    assert r["ops"]["fusion.1"] == pytest.approx(0.060)
    assert [round(g[1], 6) for g in r["gaps_ns"]] == [0.05, 0.02]
    idle = harness.load_reader("device.idle.lm")(
        {"trace": r, "layer": {"kind": "lm"}})
    assert idle == pytest.approx(60.0)
    bd = devtrace.breakdown(r, lambda s, d: "gap")
    assert bd["device_ops"][0][0] in ("fusion.1", "dot.3")
    assert bd["idle_gaps"] == [["gap", pytest.approx(0.05)], ["gap", pytest.approx(0.02)]]


def test_no_device_plane_gives_no_device_numbers():
    r = devtrace.reduce(NS(planes=[NS(name="/host:CPU", lines=[])]), 1.0)
    assert r["busy_s"] is None and r["devices"] == 0
    ctx = {"trace": None, "layer": {"kind": "lm", "labels": 5},
           "peaks": PEAKS["TPU v5 lite"], "config": {}, "window_s": 1.0}
    for name in ("device.idle.lm", "lm.decode_step_ms", "lm.decode_roofline",
                 "lm.decode_mfu", "lm.prefill_ms", "sim.device_us_per_label"):
        assert harness.load_reader(name)(ctx) is None, name


GRANITE8 = {"hidden_size": 4096, "num_attention_heads": 32, "num_key_value_heads": 8,
            "intermediate_size": 14336, "vocab_size": 49152, "num_hidden_layers": 8}


def test_decode_cost_counts_bf16_weights_and_the_kv_read():
    flops, nbytes = lmcost.decode_step(GRANITE8, batch=8, position=1087)
    per_layer = 4096 * 4096 * 2 + 4096 * 1024 * 2 + 3 * 4096 * 14336
    weights = 8 * per_layer + 4096 * 49152
    assert per_layer == 218_103_808
    kv = 8 * 8 * 8 * 128 * 2 * 2 * 1089
    assert nbytes == pytest.approx(2 * weights + kv)
    assert flops == pytest.approx(2 * 8 * weights + 8 * 4 * 8 * 32 * 128 * 1088)
    assert 4.0e9 < nbytes < 4.4e9                      # ~5.1 ms at 819 GB/s


def test_roofline_and_mfu_arithmetic():
    r = devtrace.reduce(trace(), window_s=0.4)
    lay = {"kind": "lm", "batch": 8, "decode_position": 1087.0,
           "decode_cost": lmcost.decode_step}
    ctx = {"trace": r, "layer": lay, "peaks": peaks_for("TPU v5 lite"),
           "config": GRANITE8, "window_s": 0.4}
    flops, nbytes = lmcost.decode_step(GRANITE8, 8, 1087.0)
    least = max(flops / 197e12, nbytes / 819e9)        # bytes bound it
    assert least == nbytes / 819e9
    step = 0.030
    roof = harness.load_reader("lm.decode_roofline")(ctx)
    assert roof == pytest.approx(100 * least / step)
    mfu = harness.load_reader("lm.decode_mfu")(ctx)
    assert mfu == pytest.approx(100 * flops * 2 / 0.4 / 197e12)
    assert harness.load_reader("lm.decode_step_ms")(ctx) == pytest.approx(30.0)
    assert harness.load_reader("lm.prefill_ms")(ctx) == pytest.approx(100.0)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        peaks_for("TPU v9 imaginary")
