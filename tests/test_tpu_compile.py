"""Compile-only checks for one TPU v5e chip, described and not attached.

The TPU compiler is installed even where no chip is, and it compiles for
a described topology: what it refuses here (an op Mosaic does not lower,
a program that does not fit) would be refused on the chip.  Nothing runs.
Covered: the fused population-sim programs of the image families (sim,
and the int64 SSE tail where the plan has one) at two population
buckets, the approximate projection at granite-8b FFN width, one
granite-8b decode step at published widths, granite-8b's serving
programs against recorded digests (op metadata aside), granite-8b's
layer scan at its benchmark cell's shapes (no weight copied out of the
stack, temporaries bounded, exact and approximate), and
granite-4.0-h-small's prefill and decode step at its benchmark cell's
shapes.  Each case prints its compile seconds and
``memory_analysis`` (``pytest -s`` shows them).

The topology is described inside a module fixture, never at import: only
one process may load the TPU library at a time, and every test worker
imports this file.
"""

import dataclasses
import hashlib
import json
import os
import re
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


def _on_chip(tree, one_chip):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        tree)


def _canonical_hlo(text: str) -> str:
    """Optimized HLO without what names and locates ops: the debug tables
    of file and function names, each op's ``metadata`` and the numbers
    in instruction names (renamed in order of first use)."""
    out, skip = [], False
    for ln in text.splitlines():
        if ln in ("FileNames", "FunctionNames", "FileLocations", "StackFrames"):
            skip = True
            continue
        if skip and (ln.startswith("%") or ln.startswith("ENTRY")):
            skip = False
        if not skip:
            out.append(ln)
    text = re.sub(r",? metadata=\{[^}]*\}", "", "\n".join(out))
    names = {}
    return re.sub(r"%[\w.\-]+",
                  lambda m: names.setdefault(m.group(0), f"%v{len(names)}"), text)


def _granite_8b_programs(one_chip, n_layers, policy=None):
    """granite-8b's serving programs as the LM cell builds them, at
    published widths and ``n_layers`` layers: name -> (jitted, args) of
    the prefill of a group of 8 1024-token prompts and one decode step
    against caches of 1152."""
    from repro.configs import get_config
    from repro.models.common import init_tree
    from repro.models.transformer import cache_specs, param_specs
    from repro.train.serve import Generator

    cfg = dataclasses.replace(get_config("granite-8b"), n_layers=n_layers)
    key = jax.random.PRNGKey(0)
    params = _on_chip(jax.eval_shape(lambda: init_tree(param_specs(cfg), key)),
                      one_chip)
    caches = _on_chip(jax.eval_shape(
        lambda: init_tree(cache_specs(cfg, 8, 1152), key)), one_chip)
    prompt = jax.ShapeDtypeStruct((8, 1024), jnp.int32, sharding=one_chip)
    token = jax.ShapeDtypeStruct((8, 1), jnp.int32, sharding=one_chip)
    pos = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    gen = Generator(cfg, policy=policy, attn_chunk=32, scan_chunk=8)  # as LMBackend's
    return {
        "jit_prefill": (gen.prefill, (params, {"tokens": prompt}, caches)),
        "jit_serve_step": (gen.decode, (params, caches, token, pos)),
    }


# sha256 of ``_canonical_hlo`` of granite-8b's serving programs (1 layer at
# published widths, the LM cell's shapes) as compiled once the layer scan
# read each layer's weights straight from the stack (no barrier on them),
# with this JAX (0.9.0) and its TPU compiler
GRANITE_8B_HLO = {
    "jit_prefill": "8ee7c46855a74f4f1925a2b9d4d813a94cfeb4e5426f729636815bd847cf213b",
    "jit_serve_step": "dc3c565f2a3b3aeff527c5b54e8cfc57c73913bd7f41fcf00d5baae06e3e06d0",
}


def test_granite_8b_serving_programs_are_unchanged(one_chip):
    """granite-8b compiles to the recorded optimized prefill and decode
    step: a change to what the model step computes for the dense LM cell
    shows here before it shows on the chip."""
    if jax.__version__ != "0.9.0":
        pytest.skip(f"digests were recorded with JAX 0.9.0, not {jax.__version__}")
    for name, (fn, args) in _granite_8b_programs(one_chip, 1).items():
        text = fn.lower(*args).compile().as_text()
        digest = hashlib.sha256(_canonical_hlo(text).encode()).hexdigest()
        print(f"granite-8b {name}: {digest}")
        assert digest == GRANITE_8B_HLO[name], name


def _computations(text: str) -> dict:
    """Optimized HLO text -> {computation name: its instruction lines}."""
    comps, name = {}, None
    for ln in text.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) .*\{$", ln)
        if head:
            name = head.group(1)
            comps[name] = []
        elif ln == "}":
            name = None
        elif name is not None:
            comps[name].append(ln)
    return comps


def _weight_slice_copies(text: str, shapes) -> list:
    """Scheduled fusions (not ones inlined into another fusion) that
    dynamic-slice a result of one of ``shapes`` into a buffer of its own."""
    comps = _computations(text)
    fused = set(re.findall(r"calls=%([\w.\-]+)", text))
    out = []
    for comp, lines in comps.items():
        if comp in fused:
            continue
        for ln in lines:
            m = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = (\w+\[[\d,]*\])\S* "
                         r"fusion\(.*calls=%([\w.\-]+)", ln)
            if (m and m.group(2) in shapes
                    and any(" dynamic-slice(" in c for c in comps[m.group(3)])):
                out.append(f"{m.group(1)} {m.group(2)}")
    return out


GRANITE_8B_WEIGHTS = ("bf16[4096,14336]", "bf16[14336,4096]", "bf16[4096,4096]")


@pytest.mark.parametrize("tier", ["exact", "approx"])
def test_granite_8b_layer_scan_reads_weights_in_place(tier, one_chip):
    """The layer scan of granite-8b at the LM cell's shapes (8 layers at
    published widths, batch 8, caches 1152) reads each layer's weights
    straight from the stacked tree: the decode step copies no weight
    matrix out of the stack (a barrier on the layer's slice did, 353 MB
    of temporaries and 0.83 GB more read a layer), and prefill's
    temporaries stay under 600 MB (816 MB with the copies).  Under an
    approximate policy, which quantizes ``w`` on every call, no transform
    of the whole stack is hoisted out of the loop either: the decode
    step's temporaries stay under 600 MB (824 MB with the barrier; the
    ``ffn_in`` stack alone is 1.9 GB in bfloat16)."""
    from repro.models import ApproxPolicy

    policy = (None if tier == "exact"
              else ApproxPolicy({"ffn_in": ("mul8s_drum4", 2)}))
    programs = _granite_8b_programs(one_chip, 8, policy=policy)
    limits = {"exact": {"jit_serve_step": 64e6, "jit_prefill": 600e6},
              "approx": {"jit_serve_step": 600e6}}[tier]
    for name, limit in limits.items():
        fn, args = programs[name]
        t0 = time.perf_counter()
        compiled = fn.lower(*args).compile()
        mem = compiled.memory_analysis()
        print(f"granite-8b {tier} {name}, 8 layers: compiled in "
              f"{time.perf_counter() - t0:.3f} s; {_bytes(mem)}")
        if name == "jit_serve_step":
            copies = _weight_slice_copies(compiled.as_text(), GRANITE_8B_WEIGHTS)
            assert not copies, copies
        assert mem.temp_size_in_bytes < limit, (name, mem.temp_size_in_bytes)


def test_granite_4_h_small_cell_programs_fit_one_v5e(one_chip):
    """granite-4.0-h-small as its benchmark cell serves it (10 layers at
    published widths, 9 held experts, groups of 32, 1024-token prompts and
    128 generated): prefill and decode step compile for one v5e, and each
    one's arguments, outputs and temporaries fit its 16 GB.  Both take
    the caches donated; the decode step's outputs alias every cache
    (prefill never reads the incoming conv windows, so jit drops them)."""
    import sys

    from repro.models.common import init_tree
    from repro.models.transformer import cache_specs, param_specs
    from repro.train.serve import Generator

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.join(root, "bench"))
    from benchkit.drivers.lm_hybrid_closed import program_config

    with open(os.path.join(root, "bench", "configs",
                           "granite-4.0-h-small.json")) as f:
        cfg = program_config(json.load(f))
    key = jax.random.PRNGKey(0)
    params = _on_chip(jax.eval_shape(lambda: init_tree(param_specs(cfg), key)),
                      one_chip)
    caches = _on_chip(jax.eval_shape(
        lambda: init_tree(cache_specs(cfg, 32, 1152), key)), one_chip)
    prompt = jax.ShapeDtypeStruct((32, 1024), jnp.int32, sharding=one_chip)
    token = jax.ShapeDtypeStruct((32, 1), jnp.int32, sharding=one_chip)
    pos = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    gen = Generator(cfg, attn_chunk=32, scan_chunk=8)  # as LMBackend's
    cache_bytes = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                      for a in jax.tree.leaves(caches))
    programs = {
        "jit_serve_step": (gen.decode, (params, caches, token, pos)),
        "jit_prefill": (gen.prefill, (params, {"tokens": prompt}, caches)),
    }
    for name, (fn, args) in programs.items():
        t0 = time.perf_counter()
        mem = fn.lower(*args).compile().memory_analysis()
        total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                 + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
        print(f"granite-4.0-h-small {name}, batch 32: compiled in "
              f"{time.perf_counter() - t0:.3f} s; {_bytes(mem)}; "
              f"aliased {mem.alias_size_in_bytes} B; {total} B in all")
        assert mem.argument_size_in_bytes > 4.8e9  # the bfloat16 weights
        if name == "jit_serve_step":
            assert mem.alias_size_in_bytes >= cache_bytes  # padded tiles
        assert total < 16e9, name
