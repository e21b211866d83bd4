"""BENCHMARK.json against the benchmark's contract, and every name in it
resolved to its files: configuration, traffic mix, driver and per-layer
reader.  Also shows that a cell, a mix and a metric are added with files
and entries alone."""

import importlib
import json
import os
import re
import shutil

import pytest

from benchkit import harness

SPEC = harness.load_spec()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
TEXT = re.compile(r"^[^\t\r\n]{1,200}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")


def _cells():
    return [w["name"] for w in SPEC["workloads"]]


def test_top_level_keys_and_command():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert 1 <= len(SPEC["paths"]) <= 16
    for p in SPEC["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
    for word in SPEC["command"]:
        assert TEXT.match(word) and not word.startswith("/") and ".." not in word
    assert isinstance(SPEC["run_seconds"], int) and 1 <= SPEC["run_seconds"] <= 51
    runs = 2 + 14 * 24
    assert runs * (SPEC["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200
    assert len(json.dumps(SPEC)) <= 64 * 1024


def test_names_units_and_text_fields():
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in SPEC[group]:
            assert NAME.match(e["name"]), e["name"]
            names.append((group, e["name"]))
            if "unit" in e:
                assert UNIT.match(e["unit"]), e["unit"]
                assert e["better"] in ("lower", "higher")
            for key in ("why", "layer", "source"):
                if key in e:
                    assert TEXT.match(e[key]), (e["name"], key)
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        got = [n for g, n in names if g == group]
        assert len(got) == len(set(got)), group
    metrics = [n for g, n in names if g in ("end_to_end", "per_layer")]
    assert len(metrics) == len(set(metrics))


def test_entry_keys():
    allowed = {
        "configs": {"name", "source", "file", "reduced", "why"},
        "workloads": {"name", "config", "traffic", "chips", "why"},
        "end_to_end": {"name", "unit", "better", "bound", "source", "workloads"},
        "per_layer": {"name", "unit", "better", "source", "layer", "moves",
                      "workloads"},
    }
    for group, keys in allowed.items():
        assert 1 <= len(SPEC[group]) <= (128 if group == "per_layer" else
                                         16 if group == "end_to_end" else 24)
        for e in SPEC[group]:
            assert set(e) <= keys, (group, set(e) - keys)
            assert set(e) >= keys - {"workloads"}, (group, keys - set(e))


@pytest.mark.parametrize("entry", SPEC["configs"], ids=lambda e: e["name"])
def test_config_resolves(entry):
    path = os.path.join(harness.ROOT, entry["file"])
    assert any(entry["file"].startswith(p + "/") for p in SPEC["paths"])
    cfg = harness.load_json(path)
    assert cfg["name"] == entry["name"] and cfg["source"] == entry["source"]
    assert cfg["reduced"] == entry["reduced"]
    assert len(entry["reduced"]) <= 16
    width = re.compile(r"(_dim$|_rank$|expan|experts_per_tok|"
                       r"(hidden|intermediate|latent|state|proj|head).*size)")
    for key in entry["reduced"]:
        assert NAME.match(key) and not width.search(key), key
        assert key in cfg["published"] and cfg["published"][key] != cfg[key]
    assert [c["file"] for c in SPEC["configs"]].count(entry["file"]) == 1
    assert any(w["config"] == entry["name"] for w in SPEC["workloads"])


@pytest.mark.parametrize("cell", _cells())
def test_cell_resolves_and_reports_what_it_must(cell):
    r = harness.resolve(SPEC, cell)
    assert r["cell"]["chips"] in (1, 4)
    mod = harness.driver_for(r["traffic"])
    assert hasattr(mod, "Driver")
    e2e = {m["name"] for m in r["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert r["per_layer"], "every cell reports a per-layer metric"
    for m in r["per_layer"]:
        assert m["moves"] in e2e, (m["name"], m["moves"])
        assert callable(harness.load_reader(m["name"]))


def test_cells_pairs_and_chips():
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = sum(w["chips"] == 4 for w in SPEC["workloads"])
    assert four <= max(1, len(pairs) // 2)


def test_metrics_sources_bounds_and_moves():
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in SPEC["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert m["moves"] in e2e
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
        for cell in harness.cells_reporting(SPEC, m):
            assert cell in harness.cells_reporting(SPEC, e2e[m["moves"]])


def test_layers_are_named_in_perf_md():
    with open(os.path.join(harness.ROOT, "PERF.md"), encoding="utf-8") as f:
        perf = f.read()
    for layer in {m["layer"] for m in SPEC["per_layer"]}:
        assert f"**{layer}**" in perf, layer


def test_model_cells_report_a_whole_step_mfu_beside_rooflines():
    for m in SPEC["per_layer"]:
        if m["name"].endswith("_roofline"):
            cells = set(harness.cells_reporting(SPEC, m))
            assert any("mfu" in o["name"] and o["moves"] == m["moves"]
                       and cells <= set(harness.cells_reporting(SPEC, o))
                       for o in SPEC["per_layer"])


def test_a_cell_mix_and_metric_are_added_with_files_and_entries(tmp_path):
    """A later change adds a traffic mix, a reader and entries; the
    harness finds them by name with no edit to any file already there."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(harness.ROOT, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = json.loads(json.dumps(SPEC))
    mix = harness.load_json(os.path.join(harness.ROOT, "bench", "traffic",
                                         "lm_chat.json"))
    mix["prompt_tokens"] = mix["prompt_tokens"] // 4
    (root / "bench" / "traffic" / "lm_short.json").write_text(json.dumps(mix))
    (root / "bench" / "metrics" / "serving.responses.py").write_text(
        "def read(ctx):\n    return ctx['layer'].get('responses')\n")
    spec["workloads"].append({"name": "lm.granite-8b.short", "config": "granite-8b",
                              "traffic": "lm_short", "chips": 1,
                              "why": "prompts a quarter as long"})
    spec["end_to_end"].append({"name": "lm_short_p95_ms", "unit": "ms", "better": "lower",
                               "bound": 0.25, "source": "host_clock",
                               "workloads": ["lm.granite-8b.short"]})
    spec["per_layer"].append({"name": "serving.responses", "unit": "requests",
                              "better": "higher", "source": "program_counter",
                              "layer": "serving engine", "moves": "lm_short_p95_ms"})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    r = harness.resolve(spec, "lm.granite-8b.short", str(root))
    assert r["traffic"]["prompt_tokens"] == mix["prompt_tokens"]
    assert r["config"]["name"] == "granite-8b"
    assert "lm_short_p95_ms" in {m["name"] for m in r["end_to_end"]}
    assert "serving.responses" in {m["name"] for m in r["per_layer"]}
    read = harness.load_reader("serving.responses", str(root))
    assert read({"layer": {"responses": 7}}) == 7
    assert importlib.import_module("benchkit.drivers." + r["traffic"]["driver"])
