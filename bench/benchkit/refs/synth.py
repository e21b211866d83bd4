"""Plain reference of the gaussian3x3 configuration's synthesis labels,
independent of the program under test.

A variant deploys on the matrix unit as one im2col matmul, (m, 9) image
windows times the (9, 1) coefficients, split by contraction column into
one part per multiplier slot.  A slot whose circuit has deploy width
``w`` < 8 masks both operands to their top ``w`` bits; a slot of rank
``r`` > 0 adds ``r`` correction matmuls of per-value lookup factors.
The configuration's ``deploy`` entry states each circuit's width and
rank, the matmul shape and the cost model's constants.

The labels, as the configuration defines them:

* ``flops`` and ``hbm_bytes``: the compiler's cost analysis of that
  graph, compiled here from this module's own copy of it;
* ``latency``: the serial roofline of the dtype-adjusted matrix work and
  ``hbm_bytes``;
* ``energy``: the adjusted matrix work at the energy per FLOP plus the
  correction factors' table bytes at the energy per HBM byte.

Only shapes, and which slots share one circuit's factors, enter the
cost: the correction factors are arbitrary values of the stated rank,
one pair per circuit (not all equal, which a compiler could fold), and
slots that select the same circuit read the same pair, as they do in the
deployed graph, where the compiler keeps one copy of it.
"""

from __future__ import annotations

import functools
import zlib

import numpy as np


def circuits(c: dict, genome) -> tuple:
    """The multiplier circuits the genome selects, slot by slot."""
    names = c["circuits"]["mul8u"]
    return tuple(names[int(g)] for g in list(genome)[:c["slots"]["mul8u"]])


def classes(c: dict, genome) -> tuple:
    """Per multiplier slot, ``(deploy width, deploy rank)`` of the
    circuit the genome selects."""
    return tuple(tuple(c["deploy"]["mul8u"][n]) for n in circuits(c, genome))


def structures(c: dict, genomes) -> int:
    """Distinct deploy graphs up to the order of the slots (all slots are
    one column wide): the fewest compiles that can label ``genomes``."""
    return len({tuple(sorted(classes(c, g))) for g in genomes})


def _dtype_factor(c: dict, width: int) -> float:
    k = c["deploy"]["cost_model"]
    if width <= 4:
        return k["peak_bf16_flops"] / k["peak_int4_ops"]
    if width <= 8:
        return k["peak_bf16_flops"] / k["peak_int8_ops"]
    return 1.0


def adjusted_flops(c: dict, cls) -> float:
    """bf16-equivalent matrix work: per slot, 2 m n (dtype factor + rank)."""
    m, _, n = c["deploy"]["matmul_shape"]
    total = 0.0
    for width, rank in cls:
        total += 2.0 * m * n * (_dtype_factor(c, width) + rank)
    return total


def energy(c: dict, cls) -> float:
    k = c["deploy"]["cost_model"]
    table_bytes = sum(256.0 * 4 * 2 * rank for _, rank in cls)
    return adjusted_flops(c, cls) * k["e_flop"] + table_bytes * k["e_hbm_byte"]


def latency(c: dict, cls, hbm_bytes: float) -> float:
    k = c["deploy"]["cost_model"]
    return (adjusted_flops(c, cls) / k["peak_bf16_flops"]
            + hbm_bytes / k["hbm_bytes_per_s"])


@functools.lru_cache(maxsize=256)
def compiled_cost(c_key: tuple, slots: tuple) -> tuple:
    """``(flops, bytes accessed)`` of the deploy graph of one variant,
    given as ``(circuit, width, rank)`` per slot, compiled on the default
    backend."""
    import jax
    import jax.numpy as jnp

    m, k, n = dict(c_key)["matmul_shape"]
    coeffs = np.asarray(dict(c_key)["coefficients"], dtype=np.int64)
    pairs = {}
    for name, _, r in slots:
        if name not in pairs:
            rng = np.random.default_rng(zlib.crc32(name.encode()))
            pairs[name] = (rng.standard_normal((256, r)).astype(np.float32),
                           rng.standard_normal((256, r)).astype(np.float32))
    cls = [(w, r) for _, w, r in slots]
    factors = [pairs[name] for name, _, _ in slots]

    def mask(v, t):
        v = v.astype(jnp.int32)
        return jnp.sign(v) * ((jnp.abs(v) >> t) << t)

    def graph(x, w):
        out = None
        for i, ((width, rank), (u, v)) in enumerate(zip(cls, factors)):
            xs, ws = x[:, i:i + 1], w[i:i + 1, :]
            if width < 8:
                xs, ws = mask(xs, 8 - width), mask(ws, 8 - width)
            part = xs.astype(jnp.float32) @ ws.astype(jnp.float32)
            if rank:
                ux = jnp.take(jnp.asarray(u), xs.astype(jnp.int32), axis=0)
                vw = jnp.take(jnp.asarray(v), ws.astype(jnp.int32), axis=0)
                part = part + jnp.einsum("mkr,knr->mn", ux, vw)
            out = part if out is None else out + part
        return out

    x = jnp.asarray(np.zeros((m, k), dtype=np.int64))
    w = jnp.asarray(coeffs.reshape(k, n))
    cost = jax.jit(graph).lower(x, w).compile().cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    return float(cost["flops"]), float(cost["bytes accessed"])


def cost_key(c: dict) -> tuple:
    d = c["deploy"]
    return (("matmul_shape", tuple(d["matmul_shape"])),
            ("coefficients", tuple(d["coefficients"])))


def labels(c: dict, genome) -> dict:
    """The synthesis labels of one genome."""
    cls = classes(c, genome)
    slots = tuple((n,) + wr for n, wr in zip(circuits(c, genome), cls))
    flops, nbytes = compiled_cost(cost_key(c), slots)
    return {"flops": flops, "hbm_bytes": nbytes,
            "latency": latency(c, cls, nbytes), "energy": energy(c, cls)}
