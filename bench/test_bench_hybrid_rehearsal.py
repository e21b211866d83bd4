"""lm.granite-4.0-h-small.batch32 rehearsed through the harness on the CPU
at the configuration's rehearsal widths: the result line, the routing
counter, planted faults that must come out not correct, and the float8
control that must fail where the program passes."""

import copy

import pytest

import rehearsal
from benchkit import harness

CELL = "lm.granite-4.0-h-small.batch32"


def tiny() -> dict:
    """The cell resolved from the real files, with the rehearsal sizes of
    the configuration and the traffic mix put in."""
    r = copy.deepcopy(harness.resolve(harness.load_spec(), CELL))
    r["config"].update(r["config"]["rehearsal"])
    r["traffic"].update(r["traffic"]["rehearsal"])
    return r


def test_rehearsal_counts_the_picks_of_the_held_experts():
    r = tiny()
    line = rehearsal.run(CELL, trace=1, resolved=r)
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    got = line["metrics"]["moe.tokens_per_held_expert.lm"]["value"]
    c, t = r["config"], r["traffic"]
    # picks fall on each expert with probability k / routed under random
    # routing: clients * k / routed tokens per held expert and step
    expected = t["clients"] * c["num_experts_per_tok"] / c["routed_experts"]
    assert 0.5 * expected < got < 1.5 * expected
    assert line["metrics"]["serving.group_size.lm"]["value"] == t["clients"]


def test_program_skipping_d_x_is_not_correct(monkeypatch):
    from repro.models import transformer

    real = transformer.mamba2_layer

    def no_skip(p, x, cfg, **kw):
        return real({**p, "D": p["D"] * 0}, x, cfg, **kw)

    monkeypatch.setattr(transformer, "mamba2_layer", no_skip)
    line = rehearsal.run(CELL, resolved=tiny())
    assert line["correct"] is False
    gap = line["checks"]["lm_logit_gap"]
    assert gap["value"] > gap["limit"]


def test_program_skipping_the_shared_expert_is_not_correct(monkeypatch):
    import jax.numpy as jnp

    from repro.models import moe

    monkeypatch.setattr(moe, "_swiglu",
                        lambda p, h, cfg, policy: jnp.zeros_like(h))
    line = rehearsal.run(CELL, resolved=tiny())
    assert line["correct"] is False
    gap = line["checks"]["lm_logit_gap"]
    assert gap["value"] > gap["limit"]


def test_float8_control_fails_where_the_program_passes():
    r = tiny()
    seeds = [3, 2 ** 31 + 17, 40]
    run = harness.Run(CELL, seeds[0], 0, 0, r, harness.ROOT)
    driver = harness.driver_for(run.traffic).Driver(run)
    try:
        driver.setup()
        rows = driver.calibrate(seeds)
    finally:
        driver.close()
    limit = r["config"]["limits"]["lm_logit_gap"]
    for seed, program, fp8, bf16_state in rows:
        assert program <= limit < fp8, (seed, program, fp8)
        assert bf16_state >= 0.0
