"""Storage types of the parameter tree.

Leaves that every reader casts to the compute type are declared in it, so
serving holds them once in bfloat16 instead of casting float32 weights on
every step.  The exact tier must compute the same bits either way, the
approximate tier must quantize a weight as it did in float32, and
training must keep its master weights in ``cfg.param_dtype``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs import get_config
from repro.models import ApproxPolicy, cache_specs, param_specs, reduced
from repro.models.approx_linear import _approx_matmul_nd
from repro.models.common import ParamSpec, init_tree
from repro.train.serve import Generator

KEY = jax.random.PRNGKey(0)

# leaves read only through a cast to the compute type (``linear``, the MoE
# experts' einsums, the embedding gather, the LM head)
COMPUTE_TYPED = {"wq", "wk", "wv", "wo", "wi", "wg", "in_proj", "x_proj",
                 "dt_proj", "out_proj", "embed", "lm_head"}
# leaves read in float32 (norm gains, the router's logits, the SSM's
# convolution and dynamics)
FLOAT32 = {"norm", "final_norm", "router", "conv_w", "conv_b", "dt_bias",
           "A_log", "D"}


def _leaves(tree, is_leaf=None):
    flat = jax.tree_util.tree_flatten_with_path(tree, is_leaf=is_leaf)[0]
    return [(path[-1].key, leaf) for path, leaf in flat]


def _upcast(params):
    return jax.tree.map(lambda t: t.astype(jnp.float32), params)


@pytest.mark.parametrize("arch", ["granite-8b", "granite-moe-3b-a800m"])
def test_exact_tier_bit_identical_across_storage(arch):
    """The bfloat16 tree and the same values stored in float32 give the
    exact tier's prefill logits and generated tokens bit for bit."""
    cfg = reduced(get_config(arch), n_layers=2)
    params = init_tree(param_specs(cfg), KEY)
    assert params["blocks"]["layer0"]["attn"]["wq"].dtype == jnp.bfloat16
    wide = _upcast(params)
    prompts = jax.random.randint(KEY, (2, 8), 0, cfg.vocab_size)
    gen = Generator(cfg, attn_chunk=8, scan_chunk=8)

    def prefill_logits(p):
        caches = init_tree(cache_specs(cfg, 2, 8), KEY)
        return np.asarray(gen.prefill(p, {"tokens": prompts}, caches)[0])

    a, b = prefill_logits(params), prefill_logits(wide)
    assert a.dtype == b.dtype and np.array_equal(a, b)
    toks_a, _ = gen.generate(params, prompts, 5)
    toks_b, _ = gen.generate(wide, prompts, 5)
    np.testing.assert_array_equal(np.asarray(toks_a), np.asarray(toks_b))


@pytest.mark.parametrize("arch", ["granite-8b", "phi3.5-moe-42b-a6.6b",
                                  "falcon-mamba-7b", "jamba-1.5-large-398b"])
def test_leaves_declared_in_the_type_their_readers_use(arch):
    """Dense, MoE, SSM and hybrid: every leaf read through a cast to the
    compute type is declared in it, every leaf read in float32 stays
    float32, and no leaf is left undecided."""
    cfg = get_config(arch)  # full widths: shapes only, nothing allocated
    leaves = _leaves(param_specs(cfg), lambda s: isinstance(s, ParamSpec))
    names = {name for name, _ in leaves}
    assert names <= COMPUTE_TYPED | FLOAT32, names - COMPUTE_TYPED - FLOAT32
    for name, spec in leaves:
        want = cfg.compute_dtype if name in COMPUTE_TYPED else "float32"
        assert spec.dtype == want, (name, spec.dtype)


@pytest.mark.parametrize("arch,dtype", [("granite-8b", jnp.float32),
                                        ("jamba-1.5-large-398b",
                                         jnp.bfloat16)])
def test_training_init_keeps_param_dtype(arch, dtype):
    """``train_loop`` builds its master weights in ``cfg.param_dtype``:
    float32 for granite, bfloat16 for jamba."""
    from repro.launch.train import train_loop

    cfg = reduced(get_config(arch))
    assert jnp.dtype(cfg.param_dtype) == dtype
    state, losses = train_loop(cfg, steps=0, batch=2, seq=8)
    assert losses == []
    dtypes = {leaf.dtype for _, leaf in _leaves(state["params"])}
    assert dtypes == {jnp.dtype(dtype)}


@pytest.mark.parametrize("circuit", [("mul8s_drum4", 2),
                                     ("mul8s_trunc2", None)])
def test_approx_tier_quantizes_a_bfloat16_weight_as_float32(circuit):
    spec = ApproxPolicy({"ffn_in": circuit}).spec("ffn_in")
    kx, kw = jax.random.split(KEY)
    x = jax.random.normal(kx, (2, 4, 64), jnp.float32).astype(jnp.bfloat16)
    w = (jax.random.normal(kw, (64, 48), jnp.float32) * 0.02).astype(
        jnp.bfloat16)
    got = np.asarray(_approx_matmul_nd(x, w, spec))
    want = np.asarray(_approx_matmul_nd(x, w.astype(jnp.float32), spec))
    assert got.dtype == np.float32
    np.testing.assert_array_equal(got, want)


def test_prefill_span_records_the_weight_bytes_that_served():
    cfg = reduced(get_config("granite-8b"), n_layers=1, d_model=32)
    params = init_tree(param_specs(cfg), KEY)
    wide = _upcast(params)
    gen = Generator(cfg, attn_chunk=8, scan_chunk=8)
    prompts = jax.random.randint(KEY, (1, 8), 0, cfg.vocab_size)
    rec = obs.recorder()
    rec.clear()
    for p in (params, wide, params):
        gen.generate(p, prompts, 2)
    got = [s["attrs"]["weight_bytes"] for s in rec.snapshot()
           if s["name"] == "lm.prefill"]
    narrow = sum(leaf.nbytes for leaf in jax.tree.leaves(params))
    assert got == [narrow, sum(leaf.nbytes for leaf in jax.tree.leaves(wide)),
                   narrow]
    assert got[1] > got[0]
