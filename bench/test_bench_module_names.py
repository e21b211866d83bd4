"""The XLA module names the device-trace readers key on: lm.decode_step_ms,
lm.prefill_ms, lm.decode_roofline, lm.decode_mfu and sim.device_us_per_label
find their programs by these names, so a rename would turn them to null
without failing anything else."""

import jax
import jax.numpy as jnp
import numpy as np


def _module(lowered) -> str:
    """The XLA module name a lowered program shows in the profiler's
    'XLA Modules' line ('module @jit_prefill attributes ...')."""
    head = lowered.as_text().split("\n", 1)[0]
    return head.split("@", 1)[1].split()[0]


def test_generator_modules_are_jit_prefill_and_jit_serve_step():
    from repro.configs import get_config
    from repro.models import reduced
    from repro.models.common import init_tree
    from repro.models.transformer import cache_specs, param_specs
    from repro.train.serve import Generator

    cfg = reduced(get_config("granite-8b"), n_layers=1, d_model=32)
    gen = Generator(cfg, attn_chunk=32, scan_chunk=8)
    key = jax.random.PRNGKey(0)
    params = init_tree(param_specs(cfg), key)
    caches = init_tree(cache_specs(cfg, 2, 12), key)
    tokens = jnp.zeros((2, 8), jnp.int32)
    assert _module(gen.prefill.lower(params, {"tokens": tokens}, caches)) == "jit_prefill"
    assert _module(gen.decode.lower(params, caches, tokens[:, :1],
                                    jnp.int32(8))) == "jit_serve_step"


def test_fused_engine_module_is_jit_run():
    from repro.accel import fused
    from repro.core.acl.library import default_library
    from repro.service.campaigns import make_accelerator

    accel, lib = make_accelerator("gaussian3x3"), default_library()
    plan = fused._plan_for(accel, lib)
    genomes = np.stack([accel.exact_genome(lib)] * 4).astype(np.int32)
    x = plan.prep(accel.sample_inputs(2, seed=0))
    run = fused._compiled(plan, bucket=4, per_genome=False,
                          x_sig=(x.shape, x.dtype.str), want_sse=False,
                          n_genes=genomes.shape[1])
    with jax.enable_x64(True):
        lowered = run.lower(jnp.asarray(genomes), jnp.asarray(x))
    assert _module(lowered) == "jit_run"
