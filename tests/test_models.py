"""Per-architecture smoke tests (assignment deliverable f): every assigned
arch instantiates at reduced scale, runs a forward/train step on CPU,
asserts output shapes + no NaNs.  Plus decode-path consistency and the
approximate-projection (paper technique) integration."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_config
from repro.models import (
    ApproxPolicy,
    cache_specs,
    decode_step,
    forward,
    param_specs,
    reduced,
)
from repro.models.common import init_tree
from repro.train.serve import make_prefill_step
from repro.train.step import make_loss_fn

KEY = jax.random.PRNGKey(0)
B, S = 2, 32


def _batch(cfg):
    tokens = jax.random.randint(KEY, (B, S), 0, cfg.vocab_size)
    kwargs = {}
    if cfg.is_encoder_decoder:
        kwargs["enc_embeds"] = (
            jax.random.normal(KEY, (B, 16, cfg.d_model), jnp.float32) * 0.1
        )
    if cfg.frontend == "vision":
        kwargs["embeds"] = (
            jax.random.normal(KEY, (B, cfg.frontend_len, cfg.d_model),
                              jnp.float32) * 0.1
        )
    return tokens, kwargs


@pytest.mark.parametrize("arch", ARCHS)
def test_arch_smoke_forward_and_grad(arch):
    cfg = reduced(get_config(arch))
    params = init_tree(param_specs(cfg), KEY)
    tokens, kwargs = _batch(cfg)
    s_total = S + (cfg.frontend_len if cfg.frontend == "vision" else 0)
    logits, _, aux = forward(params, cfg, tokens, remat=False,
                             attn_chunk=16, scan_chunk=8, **kwargs)
    assert logits.shape == (B, s_total, cfg.padded_vocab)
    assert not bool(jnp.isnan(logits).any())
    # one train-style grad step
    loss_fn = make_loss_fn(cfg, attn_chunk=16, scan_chunk=8)
    batch = {"tokens": tokens, "labels": tokens, **{
        k: v for k, v in kwargs.items()}}
    (loss, parts), grads = jax.value_and_grad(loss_fn, has_aux=True)(
        params, batch)
    assert np.isfinite(float(loss))
    gnorm = sum(float(jnp.abs(g).sum()) for g in jax.tree.leaves(grads))
    assert np.isfinite(gnorm) and gnorm > 0


@pytest.mark.parametrize("arch", ["granite-8b", "gemma-2b", "chatglm3-6b",
                                  "falcon-mamba-7b", "phi3.5-moe-42b-a6.6b",
                                  "jamba-1.5-large-398b"])
def test_prefill_decode_matches_forward(arch):
    """serve path == teacher-forcing path at the same positions."""
    cfg = reduced(get_config(arch))
    params = init_tree(param_specs(cfg), KEY)
    tokens, _ = _batch(cfg)
    caches = init_tree(cache_specs(cfg, B, S), KEY)
    prefill = make_prefill_step(cfg, attn_chunk=16, scan_chunk=8)
    lg_last, c2 = prefill(params, {"tokens": tokens[:, : S - 1]}, caches)
    lg_dec, _ = decode_step(params, cfg, c2, tokens[:, S - 1 : S],
                            jnp.int32(S - 1))
    full, _, _ = forward(params, cfg, tokens, remat=False,
                         attn_chunk=16, scan_chunk=8)
    tol = 0.12  # bf16 logits
    assert float(jnp.abs(lg_last[:, 0] - full[:, S - 2]).max()) < tol
    assert float(jnp.abs(lg_dec[:, 0] - full[:, S - 1]).max()) < tol


def test_decode_steps_chain(rng):
    """Multi-step decode: each step's logits match teacher forcing."""
    cfg = reduced(get_config("granite-8b"))
    params = init_tree(param_specs(cfg), KEY)
    tokens = jax.random.randint(KEY, (B, 8), 0, cfg.vocab_size)
    caches = init_tree(cache_specs(cfg, B, 8), KEY)
    prefill = make_prefill_step(cfg, attn_chunk=8, scan_chunk=8)
    _, caches = prefill(params, {"tokens": tokens[:, :4]}, caches)
    full, _, _ = forward(params, cfg, tokens, remat=False, attn_chunk=8,
                         scan_chunk=8)
    for t in range(4, 8):
        lg, caches = decode_step(params, cfg, caches, tokens[:, t : t + 1],
                                 jnp.int32(t))
        err = float(jnp.abs(lg[:, 0] - full[:, t]).max())
        assert err < 0.12, (t, err)


def test_approx_policy_reconstructs_circuit_error():
    """Deployment semantics (DESIGN.md §2): rank 0 = plain int8 (smallest
    deviation from exact); growing the correction rank reproduces the
    approximate circuit's own error more faithfully, so the deviation
    from the exact model GROWS toward the behavioral error and
    saturates."""
    cfg = reduced(get_config("granite-8b"))
    params = init_tree(param_specs(cfg), KEY)
    tokens = jax.random.randint(KEY, (B, S), 0, cfg.vocab_size)
    exact, _, _ = forward(params, cfg, tokens, remat=False, attn_chunk=16)
    errs = {}
    for rank in (0, 2, 16):
        pol = ApproxPolicy({"ffn_in": ("mul8s_mitchell", rank),
                            "ffn_out": ("mul8s_mitchell", rank)})
        out, _, _ = forward(params, cfg, tokens, policy=pol, remat=False,
                            attn_chunk=16)
        errs[rank] = float(jnp.abs(out.astype(jnp.float32)
                                   - exact.astype(jnp.float32)).mean())
    assert errs[2] > errs[0]                  # circuit error applied
    assert abs(errs[16] - errs[2]) < errs[2]  # saturates near behavioral


def test_native_truncation_policy_perturbs():
    """Truncation circuits deploy natively (reduced-width ints): the
    coarser the truncation, the larger the deviation."""
    cfg = reduced(get_config("granite-8b"))
    params = init_tree(param_specs(cfg), KEY)
    tokens = jax.random.randint(KEY, (B, S), 0, cfg.vocab_size)
    exact, _, _ = forward(params, cfg, tokens, remat=False, attn_chunk=16)
    errs = []
    for name in ("mul8s_trunc1", "mul8s_trunc4"):
        pol = ApproxPolicy({"ffn_in": (name, None)})
        out, _, _ = forward(params, cfg, tokens, policy=pol, remat=False,
                            attn_chunk=16)
        errs.append(float(jnp.abs(out.astype(jnp.float32)
                                  - exact.astype(jnp.float32)).mean()))
    assert errs[1] > errs[0] > 0


def test_exact_policy_close_to_no_policy():
    """int8-quantized exact multiplier ~ the bf16 exact path (quantization
    noise only)."""
    cfg = reduced(get_config("gemma-2b"))
    params = init_tree(param_specs(cfg), KEY)
    tokens = jax.random.randint(KEY, (B, S), 0, cfg.vocab_size)
    exact, _, _ = forward(params, cfg, tokens, remat=False, attn_chunk=16)
    pol = ApproxPolicy({"ffn_in": ("mul8s_exact", None)})
    out, _, _ = forward(params, cfg, tokens, policy=pol, remat=False,
                        attn_chunk=16)
    rel = float(jnp.abs(out.astype(jnp.float32) - exact.astype(jnp.float32)).mean()
                / (jnp.abs(exact.astype(jnp.float32)).mean() + 1e-9))
    assert rel < 0.25


@pytest.mark.parametrize("arch", ARCHS)
def test_param_specs_logical_axes_well_formed(arch):
    from repro.models.common import ParamSpec

    cfg = get_config(arch)  # FULL config: shapes only, no allocation
    specs = param_specs(cfg)
    for leaf in jax.tree.leaves(
        specs, is_leaf=lambda s: isinstance(s, ParamSpec)
    ):
        assert isinstance(leaf, ParamSpec)
        assert len(leaf.shape) == len(leaf.logical)
        assert all(d > 0 for d in leaf.shape)


def test_moe_grouping_equivalence():
    """Sequence grouping (§Perf: bounds GShard dispatch capacity) must not
    change the MoE layer's output when capacity is not binding."""
    import dataclasses

    from repro.models import moe as moe_mod
    from repro.models.moe import moe_layer, moe_param_specs

    cfg = dataclasses.replace(
        reduced(get_config("phi3.5-moe-42b-a6.6b")),
        capacity_factor=8.0,  # capacity never binds -> outputs identical
    )
    p = init_tree(moe_param_specs(cfg), KEY)
    x = jax.random.normal(KEY, (2, 32, cfg.d_model), jnp.float32)
    prev = moe_mod.MOE_GROUP
    try:
        moe_mod.set_moe_group(0)
        y0, a0, _ = moe_layer(p, x, cfg)
        moe_mod.set_moe_group(8)   # 4 groups of 8 tokens
        y1, a1, _ = moe_layer(p, x, cfg)
    finally:
        moe_mod.set_moe_group(prev)
    np.testing.assert_allclose(np.asarray(y0, np.float32),
                               np.asarray(y1, np.float32),
                               rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("overrides", [None, {}, {"d_model": 32}],
                         ids=["published", "reduced", "reduced-d32"])
def test_gemma_embedding_scale_is_sqrt_d_model(overrides):
    """gemma scales its embeddings by sqrt(d_model) in bfloat16, at the
    published width and at every cut width ``reduced`` gives it."""
    from repro.models.transformer import scale_embeddings

    cfg = get_config("gemma-2b")
    if overrides is not None:
        cfg = reduced(cfg, **overrides)
    x = jax.random.normal(KEY, (2, 3, cfg.d_model)).astype(jnp.bfloat16)
    want = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    np.testing.assert_array_equal(np.asarray(scale_embeddings(cfg, x)),
                                  np.asarray(want))


def test_reduced_keeps_a_fixed_embedding_multiplier():
    assert reduced(get_config("granite-4.0-h-small")).embedding_multiplier == 12.0
