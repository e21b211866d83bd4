"""Each cell's harness rehearsed on the CPU at a small size, and the real
command's refusals: without an accelerator, and outside a checkout."""

import json
import os
import shutil
import subprocess
import sys

import pytest

import rehearsal
from benchkit import harness

SPEC = harness.load_spec()
CELLS = [w["name"] for w in SPEC["workloads"]]
DEVICE_METRICS = {m["name"] for m in SPEC["per_layer"] + SPEC["end_to_end"]
                  if m["source"] == "device_trace"}


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_cell_rehearsal_prints_the_result_line(cell, trace):
    line = rehearsal.run(cell, trace=trace)
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(line)[-1] == "checks"
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    r = harness.resolve(SPEC, cell)
    wanted = {m["name"]: m["unit"] for m in
              (r["per_layer"] if trace else r["end_to_end"])}
    assert set(line["metrics"]) <= set(wanted)
    for name, m in line["metrics"].items():
        assert m["unit"] == wanted[name]
    # a CPU run never writes a number under a device metric
    assert not DEVICE_METRICS & set(line["metrics"])
    dev = line["device"]
    assert dev["platform"] == "cpu" and dev["count"] == 1
    assert "memory_peak_bytes" in dev
    if trace:
        assert {"busy_s", "window_s"} <= set(dev) and "breakdown" in line
        assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    else:
        assert "setup_s" in line["metrics"] and len(line["metrics"]) >= 2
    for c in line["checks"].values():
        assert c["value"] <= c["limit"]


def _command(cwd, env):
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         str(2 ** 31 + 3), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_command_refuses_without_an_accelerator():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = _command(harness.ROOT, env)
    assert p.returncode != 0
    assert "no accelerator" in p.stderr
    assert not p.stdout.strip()


def test_command_refuses_in_a_directory_of_only_the_benchmark(tmp_path):
    shutil.copytree(os.path.join(harness.ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = _command(tmp_path, env)
    assert p.returncode != 0 and not p.stdout.strip()
    assert not any(line.startswith("{") for line in p.stdout.splitlines())
    json.dumps(p.stderr)
