"""Compile-only checks for one TPU v5e chip, described and not attached.

The TPU compiler is installed even where no chip is, and it compiles for
a described topology: what it refuses here (an op Mosaic does not lower,
a program that does not fit) would be refused on the chip.  Nothing runs.
Covered: the fused population-sim programs of the image families (sim,
and the int64 SSE tail where the plan has one) at two population
buckets, the approximate projection at granite-8b FFN width, and one
granite-8b decode step at published widths.  Each case prints its
compile seconds and ``memory_analysis`` (``pytest -s`` shows them).

The topology is described inside a module fixture, never at import: only
one process may load the TPU library at a time, and every test worker
imports this file.
"""

import dataclasses
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.accel import GaussianFilter, HEVCDct, fused
from repro.accel.smoothed_dct import SmoothedDct
from repro.core.acl.library import default_library
from repro.core.features import synth

LIB = default_library()


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "no TPU compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    # a compile for a described device is written to the persistent
    # cache but cannot be read back without the chip
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)


def _bytes(mem) -> str:
    return (f"arguments {mem.argument_size_in_bytes} B, outputs "
            f"{mem.output_size_in_bytes} B, temporaries "
            f"{mem.temp_size_in_bytes} B")


def _sds(arr, sharding):
    return jax.ShapeDtypeStruct(arr.shape, arr.dtype, sharding=sharding)


@pytest.mark.parametrize("bucket", [16, 64])
@pytest.mark.parametrize("make", [GaussianFilter, HEVCDct, SmoothedDct],
                         ids=["gaussian3x3", "hevc_dct", "smoothed_dct"])
def test_fused_programs_compile_for_v5e(make, bucket, one_chip):
    accel = make()
    plan = fused._plan_for(accel, LIB)
    assert plan is not None, accel.name
    x = plan.prep(accel.sample_inputs(4, seed=0))
    n_genes = len(accel.slots)
    genes = np.zeros((bucket, n_genes), np.int32)
    x_sig = (x.shape, x.dtype.str)
    tails = [False] + ([True] if plan.qor_ref is not None else [])
    with jax.enable_x64(True):
        for want_sse in tails:
            fn = fused._compiled(plan, bucket=bucket, per_genome=False,
                                 x_sig=x_sig, want_sse=want_sse,
                                 n_genes=n_genes)
            args = [_sds(genes, one_chip), _sds(x, one_chip)]
            if want_sse:
                ref = plan.qor_ref(accel, accel.sample_inputs(4, seed=0))
                args.append(_sds(np.asarray(ref), one_chip))
            t0 = time.perf_counter()
            compiled = fn.lower(*args).compile()
            mem = compiled.memory_analysis()
            print(f"{accel.name} bucket {bucket} "
                  f"{'sse' if want_sse else 'sim'}: compiled in "
                  f"{time.perf_counter() - t0:.3f} s; {_bytes(mem)}")
            assert mem is not None


def test_approx_linear_granite_ffn_width_compiles_for_v5e(one_chip):
    from repro.models import ApproxPolicy
    from repro.models.approx_linear import linear

    policy = ApproxPolicy({"ffn_in": ("mul8s_drum4", 2)})
    assert policy.spec("ffn_in").rank == 2
    x = jax.ShapeDtypeStruct((8, 128, 4096), jnp.bfloat16, sharding=one_chip)
    w = jax.ShapeDtypeStruct((4096, 14336), jnp.float32, sharding=one_chip)
    t0 = time.perf_counter()
    compiled = jax.jit(
        lambda x, w: linear(x, w, "ffn_in", policy)).lower(x, w).compile()
    print(f"approx linear 8x128x4096 @ 4096x14336, rank 2: compiled in "
          f"{time.perf_counter() - t0:.3f} s; "
          f"{_bytes(compiled.memory_analysis())}")
    assert "gather" in compiled.as_text()  # the rank-k lookup is there
    # the cost numbers synthesis labels read are there on the TPU too
    cost = synth._cost_numbers(compiled)
    assert cost["flops"] > 0 and cost["bytes accessed"] > 0, cost


def test_granite_8b_decode_step_compiles_for_v5e(one_chip):
    from repro.configs import get_config
    from repro.models.common import init_tree
    from repro.models.transformer import cache_specs, param_specs
    from repro.train.serve import make_decode_step

    cfg = dataclasses.replace(get_config("granite-8b"), n_layers=1)
    assert (cfg.d_model, cfg.d_ff, cfg.vocab_size) == (4096, 14336, 49152)
    key = jax.random.PRNGKey(0)

    def on_chip(tree):
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                           sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(lambda: init_tree(param_specs(cfg), key)))
    caches = on_chip(jax.eval_shape(
        lambda: init_tree(cache_specs(cfg, 8, 256), key)))
    tokens = jax.ShapeDtypeStruct((8, 1), jnp.int32, sharding=one_chip)
    pos = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    t0 = time.perf_counter()
    compiled = jax.jit(make_decode_step(cfg)).lower(
        params, caches, tokens, pos).compile()
    mem = compiled.memory_analysis()
    print(f"granite-8b decode step, 1 layer: compiled in "
          f"{time.perf_counter() - t0:.3f} s; {_bytes(mem)}")
    assert mem.argument_size_in_bytes > cfg.padded_vocab * cfg.d_model * 4
