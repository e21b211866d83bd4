"""Mamba-2 (SSD): the chunked scan the program runs in prefill against the
sequential per-token recurrence it stands for, and the one-step decode
against the chunked prefill of the same sequence."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import reduced
from repro.models.common import init_tree
from repro.models.ssm import (
    mamba2_cache_spec,
    mamba2_layer,
    mamba2_param_specs,
    ssd_chunked,
)


def _sequential(x, dt, A, B, C, h0):
    """h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T, y_t = h_t C_t, head by
    head, in float64."""
    b, s, hh, _ = x.shape
    r = hh // B.shape[2]
    h = h0.astype(np.float64).copy()
    ys = np.zeros(x.shape)
    for t in range(s):
        for i in range(hh):
            g = i // r
            h[:, i] = (np.exp(dt[:, t, i] * A[i])[:, None, None] * h[:, i]
                       + (dt[:, t, i, None] * x[:, t, i])[:, :, None]
                       * B[:, t, g][:, None, :])
            ys[:, t, i] = np.einsum("bpn,bn->bp", h[:, i], C[:, t, g])
    return ys, h


@pytest.mark.parametrize("s, chunk", [(32, 8), (37, 8), (5, 8), (24, 24)],
                         ids=["multiple", "ragged", "shorter", "one-chunk"])
def test_chunked_ssd_matches_the_sequential_recurrence(s, chunk):
    rng = np.random.default_rng(s)
    b, hh, p, g, n = 2, 4, 3, 2, 5
    x = rng.standard_normal((b, s, hh, p)).astype(np.float32)
    dt = np.log1p(np.exp(rng.standard_normal((b, s, hh)))).astype(np.float32)
    A = -rng.uniform(0.5, 3.0, hh).astype(np.float32)
    B = rng.standard_normal((b, s, g, n)).astype(np.float32)
    C = rng.standard_normal((b, s, g, n)).astype(np.float32)
    h0 = rng.standard_normal((b, hh, p, n)).astype(np.float32)
    want_y, want_h = _sequential(x, dt, A, B, C, h0)
    with jax.default_matmul_precision("highest"):
        y, h = ssd_chunked(*map(jnp.asarray, (x, dt, A, B, C)), chunk,
                           jnp.asarray(h0))
    # float32 against float64: the chunk's decays and sums in float32
    scale = np.abs(want_y).max()
    np.testing.assert_allclose(np.asarray(y), want_y, atol=1e-5 * scale)
    np.testing.assert_allclose(np.asarray(h), want_h,
                               atol=1e-5 * np.abs(want_h).max())


def test_decode_steps_continue_the_chunked_prefill():
    """Prefill over the first tokens, then one-step decode through the
    (conv, state) cache, gives the mixer's outputs of one prefill over the
    whole sequence."""
    cfg = dataclasses.replace(reduced(get_config("granite-4.0-h-small")),
                              ssm_chunk=4)
    key = jax.random.PRNGKey(3)
    p = init_tree(mamba2_param_specs(cfg), key)
    x = jax.random.normal(key, (2, 11, cfg.d_model), jnp.float32)
    with jax.default_matmul_precision("highest"):
        full, _ = mamba2_layer(p, x, cfg, cache=init_tree(
            mamba2_cache_spec(cfg, 2), key))
        out, cache = mamba2_layer(p, x[:, :7], cfg, cache=init_tree(
            mamba2_cache_spec(cfg, 2), key))
        steps = []
        for t in range(7, 11):
            y, cache = mamba2_layer(p, x[:, t:t + 1], cfg, cache=cache,
                                    decode=True)
            steps.append(y)
    got = jnp.concatenate([out] + steps, axis=1).astype(jnp.float32)
    # both paths round the projections' inputs and outputs to bfloat16
    np.testing.assert_allclose(np.asarray(got), np.asarray(full, np.float32),
                               atol=2e-2 * float(jnp.abs(full).max()))


@pytest.mark.parametrize("arch, donated", [("granite-4.0-h-small", True),
                                           ("granite-8b", False)])
def test_generator_donates_the_caches_only_where_a_state_recurs(arch, donated):
    """A model with a recurrent state hands its caches to prefill and to
    each decode step donated, so a step updates them in place; an
    attention-only model keeps the programs it had.  Caches come zeroed,
    as their declaration says."""
    from repro.models.transformer import cache_specs, param_specs
    from repro.train.serve import Generator

    cfg = reduced(get_config(arch))
    gen = Generator(cfg, attn_chunk=8, scan_chunk=4)
    key = jax.random.PRNGKey(0)
    params = init_tree(param_specs(cfg), key)
    caches = gen.new_caches(2, 12, 0, key)
    want = init_tree(cache_specs(cfg, 2, 12), key)
    assert jax.tree.structure(caches) == jax.tree.structure(want)
    for got, w in zip(jax.tree.leaves(caches), jax.tree.leaves(want)):
        assert got.dtype == w.dtype
        np.testing.assert_array_equal(np.asarray(got), np.asarray(w))
    _, filled = gen.prefill(params, {"tokens": jnp.ones((2, 8), jnp.int32)},
                            caches)
    _, _, stepped = gen.decode(params, filled, jnp.ones((2, 1), jnp.int32),
                               jnp.int32(8))
    # prefill never reads the incoming conv windows (it starts from zero),
    # and jit drops an argument it does not read, so those stay
    assert any(a.is_deleted() for a in jax.tree.leaves(caches)) == donated
    assert [a.is_deleted() for a in jax.tree.leaves(filled)] == \
        [donated] * len(jax.tree.leaves(filled))
    assert not any(a.is_deleted() for a in jax.tree.leaves(stepped))
