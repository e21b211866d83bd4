"""The chip smoke script off the chip: it refuses a CPU-only JAX before
any phase, and its LM phase (the served Generator's logits against the
float32 reference, with a planted fault that must land outside the
tolerance) passes at reduced widths on the CPU."""

import dataclasses
import importlib.util
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_cpu_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, SCRIPT], env=env, cwd=ROOT,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert "found no TPU" in r.stderr
    for line in r.stdout.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)


def test_refuses_outside_a_checkout(tmp_path):
    lone = tmp_path / "chip_smoke.py"
    lone.write_text(open(SCRIPT).read())
    r = subprocess.run([sys.executable, str(lone)], cwd=tmp_path,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert "src/repro not found" in r.stderr
    assert r.stdout == ""


def test_lm_phase_at_reduced_width(chip_smoke, capsys):
    from repro.configs import get_config

    full = get_config("granite-8b")
    cfg = dataclasses.replace(full, n_layers=2, d_model=1024, n_heads=8,
                              n_kv_heads=2, head_dim=128, d_ff=3584,
                              vocab_size=4096)
    out = chip_smoke.lm_phase(cfg, batch=2, prompt_len=32, gen=4,
                              approx=chip_smoke.APPROX,
                              rtol=chip_smoke.LOGITS_RTOL, hbm_bytes=1 << 40)
    log = capsys.readouterr().out
    assert "planted fault" in log
    for err in out["logits_rel_err"].values():
        assert err <= chip_smoke.LOGITS_RTOL
