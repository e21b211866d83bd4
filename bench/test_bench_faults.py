"""Each cell's run, rehearsed on the CPU with its timed path broken
underneath, must come out not correct: once for each fault the cell can
have (a QoR label, a synthesized cost or a token altered where it is
produced; synthesis labels served from another variant's compile; for
the LM also a decode step that returns its cache unchanged)."""

import numpy as np
import pytest

import rehearsal


@pytest.fixture
def fresh_fused():
    from repro.accel import fused

    fused.reset()
    yield fused
    fused.reset()


def test_campaign_label_altered_where_produced(monkeypatch, fresh_fused):
    real = fresh_fused.try_qor_batch

    def altered(*a, **kw):
        vals = real(*a, **kw)
        return None if vals is None else vals - 0.25

    monkeypatch.setattr(fresh_fused, "try_qor_batch", altered)
    line = rehearsal.run("campaign.gaussian3x3")
    assert line["correct"] is False
    assert line["checks"]["qor_gap_db"]["value"] == pytest.approx(0.25)


def test_campaign_synthesis_cost_altered_where_produced(monkeypatch, fresh_fused):
    from repro.core.features import synth

    real = synth._compile_identity

    def altered(accel, specs):
        cost, wall = real(accel, specs)
        return dict(cost, hbm_bytes=cost["hbm_bytes"] + 1024.0), wall

    monkeypatch.setattr(synth, "_compile_identity", altered)
    line = rehearsal.run("campaign.gaussian3x3")
    assert line["correct"] is False
    assert line["checks"]["synth_bytes_gap"]["value"] > 0
    assert line["checks"]["latency_gap"]["value"] > 0


def test_campaign_synthesis_served_from_another_genomes_compile(monkeypatch,
                                                                fresh_fused):
    """Every variant taken for one circuit assignment: one compile
    labels them all, with its own costs."""
    from repro.core.features import synth

    monkeypatch.setattr(synth, "_identity_signature",
                        lambda accel, specs: (accel.name,))
    line = rehearsal.run("campaign.gaussian3x3")
    assert line["correct"] is False
    assert line["checks"]["synth_compiles_short"]["value"] > 0
    assert line["checks"]["synth_flops_gap"]["value"] > 0


def test_lm_token_altered_where_produced(monkeypatch):
    from repro.train import serve

    real = serve.Generator.generate

    def altered(self, params, prompts, gen, **kw):
        toks, tps = real(self, params, prompts, gen, **kw)
        return toks.at[:, -1].set((toks[:, -1] + 1) % self.cfg.vocab_size), tps

    monkeypatch.setattr(serve.Generator, "generate", altered)
    line = rehearsal.run("lm.granite-8b.chat")
    assert line["correct"] is False


def test_lm_decode_step_returns_its_cache_unchanged(monkeypatch):
    from repro.models import transformer

    real = transformer.decode_step

    def stale(params, cfg, caches, *a, **kw):
        logits, _ = real(params, cfg, caches, *a, **kw)
        return logits, caches

    monkeypatch.setattr(transformer, "decode_step", stale)
    line = rehearsal.run("lm.granite-8b.chat")
    assert line["correct"] is False
    assert np.isfinite(line["checks"]["lm_logit_gap"]["value"])
