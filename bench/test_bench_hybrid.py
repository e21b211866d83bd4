"""granite-4.0-h-small's program against the plain reference
(``benchkit.refs.granite_hybrid``) at the configuration's rehearsal
widths, on seeded random weights: the serving path's logits through both
kinds of cache, and the held-experts cut of the MoE."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchkit import harness
from benchkit.drivers import lm_hybrid_closed as drv
from benchkit.refs import granite_hybrid as ref

CONFIG = harness.load_json(f"{harness.ROOT}/bench/configs/granite-4.0-h-small.json")
TINY = {**CONFIG, **CONFIG["rehearsal"]}


def test_reference_imports_nothing_of_the_program_and_uses_highest():
    import inspect

    src = inspect.getsource(ref)
    assert "repro" not in src.replace("``granitemoehybrid``", "")
    assert 'default_matmul_precision("highest")' in src


@pytest.mark.parametrize("seed", [5, 2 ** 31 + 9])
def test_generator_prefill_and_decode_match_the_reference(seed):
    """Prefill a ragged prompt (19 tokens, chunks of 8) through the
    program's jitted ``Generator`` steps, decode 5 tokens through the KV
    cache and the Mamba-2 (conv, state) caches, and compare the logits at
    every generated position with the reference's full forward of the
    same sequence."""
    from repro.models.common import init_tree
    from repro.models.transformer import cache_specs
    from repro.train.serve import Generator

    cfg = drv.program_config(TINY)
    params = ref.make_weights(TINY, seed, drv.program_dtypes(TINY, cfg))
    gen = Generator(cfg, attn_chunk=8)
    P, G = 19, 6
    prompts = np.random.default_rng(seed).integers(
        0, TINY["vocab_size"], (2, P)).astype(np.int32)
    caches = init_tree(cache_specs(cfg, 2, P + G), jax.random.PRNGKey(0))
    logits, caches = gen.prefill(params, {"tokens": jnp.asarray(prompts)}, caches)
    nxt = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]
    outs, toks = [logits[:, -1]], [nxt]
    for i in range(G - 1):
        nxt, logits, caches = gen.decode(params, caches, nxt, jnp.int32(P + i))
        outs.append(logits[:, -1])
        toks.append(nxt)
    got = np.asarray(jnp.stack(outs, 1), np.float32)
    served = np.asarray(jnp.concatenate(toks, 1))
    weights = ref.make_weights(TINY, seed)
    for b in range(2):
        want = ref.logits(weights, TINY, np.concatenate([prompts[b], served[b, :-1]]),
                          start=P - 1)
        # the program computes in bfloat16 through ten layers, holds the
        # Mamba-2 state in bfloat16 and rounds its logits to bfloat16:
        # about 5% of the logits' spread at these widths (seeds 5 and
        # 2**31 + 9 read 4.5-5.2%)
        assert np.abs(got[b] - want).max() <= 0.1 * want.std()


def test_held_expert_shares_add_up_to_the_uncut_layer():
    """Three chips hold experts [0, 4), [4, 8), [8, 12) of 12: the routed
    parts they compute, with the shared expert (which every chip computes
    alike) counted once, add up to the reference's layer with all twelve
    held."""
    import dataclasses

    from repro.models import moe

    full = {**TINY, "held_experts": [0, 12], "num_local_experts": 12}
    cfg = drv.program_config(full)
    params = ref.make_weights(full, 11, drv.program_dtypes(full, cfg))
    p = jax.tree.map(lambda a: a[0], params["blocks"]["layer0"]["moe"])
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 16, TINY["hidden_size"]))
    h = moe.rms_norm(x, p["norm"], cfg.rms_eps)
    shared = moe._swiglu(p["shared"], h, cfg, None).astype(jnp.float32)
    total = jnp.zeros_like(shared)
    for lo, hi in ((0, 4), (4, 8), (8, 12)):
        share = dataclasses.replace(cfg, held_experts=(lo, hi))
        part = {**p, **{k: p[k][lo:hi] for k in ("wi", "wg", "wo")}}
        out, _, picks = moe.moe_layer(part, x, share)
        total = total + out.astype(jnp.float32) - shared
        assert 0 < int(picks) < 16 * cfg.n_experts_active
    total = total + shared
    want = ref.sublayer("moe", jax.tree.map(lambda a: a.astype(jnp.float32), p),
                        x[0], full)
    # bfloat16 products in the program, float32 in the reference
    np.testing.assert_allclose(np.asarray(total[0]), np.asarray(want),
                               atol=2e-2 * float(jnp.abs(want).max()))
