"""Fused population kernels: the batched behavioral hot path as jitted
XLA programs.

The numpy batched engine (`_batchsim`) spends its time in
``grouped_apply``: one Python-level call per distinct adder circuit per
slot, each allocating int64 temporaries over boolean-masked
sub-populations.  This module compiles the whole ``(genomes, inputs) →
outputs`` pipeline per accelerator into ONE XLA program — LUT gather,
adder-tree reduction, normalization, and (where the outputs are
integral) the QoR reduction itself — with no ``(G, M, S)`` intermediate
ever materialized in host memory.

Design constraints, in priority order:

* **Bit-exactness.**  Three engines coexist (per-genome loop, numpy
  batched, fused) and must be provably identical.  Genomes are traced,
  so the adder choice per slot cannot branch: the engine evaluates every
  adder circuit's closed-form int32 twin on the full operand stack and
  per-genome-selects the result (the twins are O(log) bit-trick forms —
  e.g. the speculative adder's carry is ``c_exact & ~window-AND(p)`` —
  verified against the numpy models at build time; an unknown or
  divergent circuit unfuses the library).  LUT widening is verified
  (int64 tables must fit int32), adders operate on 16-bit-masked
  operands so int32 intermediates match the int64 semantics, and the
  device QoR tail returns an exact integer SSE (`core.qor.sse_batch_jax`).
  On top of the static proofs, the PR-5 verification scheme applies
  dynamically: each plan's first calls ALSO run the numpy engine and
  compare; a divergent accelerator family is pinned back to numpy for
  the process lifetime.  An exception raised while compiling or running
  a fused program is not a divergence: it propagates to the caller.

* **Zero steady-state recompiles.**  Population sizes are bucketed (pad
  G up to a power of two with repeats of the first genome, slice the
  results); the jit cache is keyed on (plan structural key, bucket,
  input signature) where the structural key rides the PR-5
  ``deploy_signature`` family, so campaigns over structurally identical
  accelerators share compiles and process workers warm-start the same
  way the synth cache does.

* **Observability + kill switch.**  ``REPRO_SIM_FUSED=0`` falls back to
  the numpy engine wholesale; compiles / bucket hits / verify calls /
  pins / fallbacks (a fusible family that ran numpy) are counted
  (``stats()``, mirrored into ``repro.obs`` counters) and every device
  execution runs under a ``sim.fused`` span.
"""

from __future__ import annotations

import functools
import logging
import os
import threading
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from ..core.acl import adders as _adders
from ..core.acl.library import Library, library_fingerprint

log = logging.getLogger("repro.sim.fused")

__all__ = [
    "enabled", "try_simulate_batch", "try_qor_batch", "register_fused",
    "register_coupling", "stats", "reset", "warm", "FusedPlan",
]

_M16 = (1 << 16) - 1

# ---------------------------------------------------------------------------
# knobs / module state
# ---------------------------------------------------------------------------

_STATS_LOCK = threading.Lock()
_STATS: Dict[str, int] = {}
_COUNTERS: Dict[str, object] = {}       # key -> repro_sim_fused_<key>_total
_JIT_CACHE: Dict[tuple, Callable] = {}
_PLAN_CACHE: Dict[tuple, Optional["FusedPlan"]] = {}
_ENGINES: Dict[str, "_Engine"] = {}
_PINNED: Dict[tuple, str] = {}          # plan key -> reason
_VERIFY_LEFT: Dict[tuple, int] = {}
_BUILDERS: Dict[type, Callable] = {}
_COUPLINGS: Dict[str, Optional[Callable]] = {"identity": None}
_GUARD = threading.local()              # re-entrancy guard (verification)


def enabled() -> bool:
    return os.environ.get("REPRO_SIM_FUSED", "1") != "0"


def _verify_budget() -> int:
    try:
        return int(os.environ.get("REPRO_SIM_FUSED_VERIFY", "2"))
    except ValueError:
        return 2


def _bump(key: str, n: int = 1) -> None:
    with _STATS_LOCK:
        _STATS[key] = _STATS.get(key, 0) + n
        counter = _COUNTERS.get(key)
        if counter is None:
            from .. import obs

            # registered once: the registry replaces a name on every
            # registration, which would drop the count so far
            counter = _COUNTERS[key] = obs.REGISTRY.counter(
                f"repro_sim_fused_{key}_total", f"fused sim engine: {key}")
    counter.inc(n)


def stats() -> Dict[str, int]:
    """Snapshot of the engine counters (plus pin/cache gauges)."""
    with _STATS_LOCK:
        out = dict(_STATS)
    for k in ("fused_calls", "fused_qor_calls", "compiles", "bucket_hits",
              "verify_calls", "pins", "fallback_calls"):
        out.setdefault(k, 0)
    out["pinned_plans"] = len(_PINNED)
    out["compiled_programs"] = len(_JIT_CACHE)
    return out


def reset() -> None:
    """Cold-start the engine (tests): drop compiled programs, plans,
    pins, verification history and counters."""
    with _STATS_LOCK:
        _STATS.clear()
    _JIT_CACHE.clear()
    _PLAN_CACHE.clear()
    _ENGINES.clear()
    _PINNED.clear()
    _VERIFY_LEFT.clear()


# ---------------------------------------------------------------------------
# closed-form adder twins
# ---------------------------------------------------------------------------
# Each twin is written with plain operators so the SAME code runs under
# numpy (build-time verification against the library's int64 models) and
# under jit tracing (int32 device math).  Operands arrive 16-bit masked;
# results may carry bit 16 (the adders' carry-out), exactly like the
# numpy models.

def _shared(a, b):
    """Subexpressions shared across all adder circuit twins."""
    a = a & _M16
    b = b & _M16
    s = a + b
    p = a ^ b
    return {"a": a, "b": b, "s": s, "p": p, "ab": a & b, "c": s ^ p}


def _tw_exact(sh):
    return sh["s"]


def _tw_loa(sh, k):
    # LOA: high sum + OR of low bits == s - (a AND b AND lowmask)
    return sh["s"] - (sh["ab"] & ((1 << k) - 1))


def _tw_trunc(sh, k):
    m = (1 << k) - 1
    return sh["s"] - (sh["a"] & m) - (sh["b"] & m)


def _tw_seg(sh, seg):
    # independent per-segment sums; only the top segment keeps its carry
    a, b = sh["a"], sh["b"]
    out = None
    nseg = 16 // seg
    for i in range(nseg):
        lo = i * seg
        m = (1 << seg) - 1
        ssum = ((a >> lo) & m) + ((b >> lo) & m)
        if i < nseg - 1:
            ssum = ssum & m
        part = ssum << lo
        out = part if out is None else out + part
    return out


def _tw_eta1(sh, k):
    # ETA1 low part: OR of the operands, flooded to ones strictly below
    # the highest generate position (downward smear of a AND b)
    lowm = (1 << k) - 1
    g = sh["ab"] & lowm
    g = g | (g >> 1)
    g = g | (g >> 2)
    g = g | (g >> 4)  # k <= 8
    low = ((sh["p"] | sh["ab"]) & lowm) | (g >> 1)
    return (((sh["a"] >> k) + (sh["b"] >> k)) << k) + low


def _tw_aca(sh, la):
    # ACA(la): carry into bit i is the exact carry unless ALL la
    # propagate bits below i are set (a carry chain longer than the
    # window); window-AND of p computes in log2(la) shift-ANDs.
    r = sh["p"]
    shift = 1
    while shift < la:
        r = r & (r >> shift)
        shift <<= 1
    c_aca = sh["c"] & ~(r << la)
    return sh["p"] ^ c_aca


_TWIN_FAMILIES = {
    "add_exact": lambda kw: _tw_exact,
    "add_loa": lambda kw: functools.partial(_tw_loa, k=kw["k"]),
    "add_trunc": lambda kw: functools.partial(_tw_trunc, k=kw["k"]),
    "add_segmented": lambda kw: functools.partial(_tw_seg, seg=kw["seg"]),
    "add_eta1": lambda kw: functools.partial(_tw_eta1, k=kw["k"]),
    "add_speculative": lambda kw: functools.partial(_tw_aca, la=kw["la"]),
}


def _resolve_twin(fn) -> Optional[Callable]:
    """Map a library adder model to its closed-form twin by introspecting
    the ``functools.partial`` over the ``core.acl.adders`` module."""
    base, kw = fn, {}
    if isinstance(fn, functools.partial):
        base, kw = fn.func, dict(fn.keywords)
    if getattr(_adders, getattr(base, "__name__", ""), None) is not base:
        return None  # not a stock adder model: unfusible
    maker = _TWIN_FAMILIES.get(base.__name__)
    return None if maker is None else maker(kw)


def _probe_operands() -> Tuple[np.ndarray, np.ndarray]:
    """Dense verification probe: random 16-bit pairs + a corner grid of
    carry-chain patterns (all-ones runs, alternating bits, boundaries)."""
    rng = np.random.default_rng(0xF05ED)
    a = rng.integers(0, 1 << 16, size=1 << 15, dtype=np.int64)
    b = rng.integers(0, 1 << 16, size=1 << 15, dtype=np.int64)
    corners = np.array(
        [0, 1, 2, 3, 0x000F, 0x00FF, 0x0FFF, 0x7FFF, 0x8000, 0x8001,
         0xAAAA, 0x5555, 0xFF00, 0xF0F0, 0xFFFE, 0xFFFF],
        dtype=np.int64,
    )
    ca, cb = np.meshgrid(corners, corners)
    return (np.concatenate([a, ca.ravel()]),
            np.concatenate([b, cb.ravel()]))


class _Engine:
    """Per-library fused-engine state: verified adder twins + device LUTs."""

    def __init__(self, library: Library):
        self.library = library
        self.fingerprint = library_fingerprint(library)
        self.twins: Optional[List[Callable]] = self._build_twins(library)
        self._luts: Dict[tuple, object] = {}

    @staticmethod
    def _build_twins(library: Library) -> Optional[List[Callable]]:
        pa, pb = _probe_operands()
        ref_shared = _shared(pa, pb)
        twins: List[Callable] = []
        for c in library.kind("add16"):
            twin = _resolve_twin(c.fn)
            if twin is None:
                log.warning("fused sim: no twin for adder %r — unfusible", c.name)
                return None
            want = np.asarray(c.fn(pa, pb), dtype=np.int64)
            got = np.asarray(twin(ref_shared), dtype=np.int64)
            if not np.array_equal(want, got):
                log.warning(
                    "fused sim: twin for %r diverges on probe — unfusible",
                    c.name,
                )
                return None
            twins.append(twin)
        return twins

    def lut(self, kind: str, constants, tag: str):
        """Device (C, S, 256) int32 LUT stack with verified widening."""
        key = (kind, tag, tuple(int(c) for c in constants))
        dev = self._luts.get(key)
        if dev is None:
            import jax.numpy as jnp

            from ._batchsim import mul_lut

            lut64 = mul_lut(self.library, kind, constants, tag=tag)
            info = np.iinfo(np.int32)
            if lut64.max() > info.max or lut64.min() < info.min:
                raise OverflowError(
                    f"LUT for {kind}/{tag} exceeds int32 — unfusible"
                )
            dev = jnp.asarray(lut64.astype(np.int32))
            self._luts[key] = dev
        return dev

    def gather(self, lut_dev, genes, cols, *, per_genome: bool):
        """Traceable population LUT gather, the XLA form on every
        backend: it fuses into the surrounding program, and the TPU
        compiler refuses the Pallas kernel in ``kernels.population_lut``
        (Mosaic lowers only 2-D gathers)."""
        from ..kernels.population_lut import gather_xla

        return gather_xla(
            lut_dev.reshape(-1), genes, cols, nslots=lut_dev.shape[1],
            per_genome=per_genome,
        )

    def select_add(self, gene_col, a, b, *, signed: bool):
        """All-circuits adder stack + per-genome selection.  ``a``/``b``:
        (G, ...) operand stacks; ``gene_col``: (G,) circuit indices."""
        import jax.numpy as jnp

        sh = _shared(a, b)
        allr = jnp.stack([tw(sh) for tw in self.twins])  # (A, G, ...)
        idx = gene_col.reshape((1, -1) + (1,) * (a.ndim - 1))
        r = jnp.take_along_axis(allr, idx, axis=0)[0]
        if signed:
            # signed16 semantics: wrap to 16 bits, sign-extend
            r = r & _M16
            r = (r ^ 0x8000) - 0x8000
        return r


def _engine_for(library: Library) -> Optional[_Engine]:
    fp = library_fingerprint(library)
    eng = _ENGINES.get(fp)
    if eng is None:
        eng = _Engine(library)
        _ENGINES[fp] = eng
    return eng if eng.twins is not None else None


def warm(library: Library) -> bool:
    """Pre-build (and probe-verify) the library's adder twins so the
    first labeled batch doesn't pay them; True iff the library fuses."""
    if not enabled():
        return False
    return _engine_for(library) is not None


# ---------------------------------------------------------------------------
# plans
# ---------------------------------------------------------------------------

@dataclass
class FusedPlan:
    """One accelerator's fused pipeline.

    ``stage_fn(genes, x, per_genome)`` is the traceable core: slot genes
    in, natural-layout (numpy-``simulate_batch``-shaped) outputs out, so
    plans chain through ``StagedPipeline`` couplings inside one program.
    ``prep``/``post`` are the host-side dtype shims; ``qor_ref`` (when
    set) provides the integer exact reference that lets the QoR reduce
    on-device (``sse_batch_jax``)."""

    key: tuple
    stage_fn: Callable
    prep: Callable
    post: Callable
    qor_ref: Optional[Callable] = None
    # True iff stage_fn's device output IS the numpy simulate_batch
    # output (modulo dtype).  Plans with a host-side tail (e.g. the
    # DCT's float64 reconstruction) set False and can only terminate a
    # fused pipeline, not feed a later stage.
    device_natural: bool = True


def register_fused(cls):
    """Decorator: ``@register_fused(Accel)`` marks ``builder(accel,
    library, engine) -> Optional[FusedPlan pieces]`` as the fused-plan
    builder for ``cls`` (and, via MRO lookup, its subclasses)."""

    def deco(builder):
        _BUILDERS[cls] = builder
        return builder

    return deco


def register_unfused(cls) -> None:
    """Pin an accelerator type to the numpy path (e.g. non-LUT
    workloads like the LM, whose custom qor path isn't table-driven)."""
    _BUILDERS[cls] = None


def register_coupling(name: str, fn: Callable) -> None:
    """Traceable twin of a ``Coupling.sim`` map, by coupling name.
    Pipelines fuse end-to-end only when every coupling has a twin."""
    _COUPLINGS[name] = fn


def _builder_for(accel):
    for cls in type(accel).__mro__:
        if cls in _BUILDERS:
            return _BUILDERS[cls]
    return None


def _family_key(accel) -> tuple:
    """The PR-5 structural-signature family of this accelerator's
    deployment graph: plans/compiles are shared exactly where the synth
    cache shares compiles.  Name and slot constants ride along — two
    accelerators may share a deployment family (e.g. MCM rows) while
    simulating different constants."""
    try:
        sig = accel.deploy_signature([])
        fam = tuple(sig[0]) if sig else ()
    except Exception:
        fam = ()
    try:
        consts = tuple(
            int(c) if c is not None else None
            for c in accel.mul_slot_constants()
        )
    except Exception:
        consts = ()
    return (type(accel).__qualname__, accel.name, fam, consts)


def _plan_for(accel, library: Library) -> Optional[FusedPlan]:
    key = _family_key(accel) + (library_fingerprint(library),)
    if key in _PLAN_CACHE:
        return _PLAN_CACHE[key]
    plan: Optional[FusedPlan] = None
    builder = _builder_for(accel)
    if builder is not None:
        eng = _engine_for(library)
        if eng is not None:
            try:
                plan = builder(accel, library, eng)
            except OverflowError:  # a LUT exceeds int32: unfusible
                log.exception("fused sim: plan build failed for %s", accel.name)
                plan = None
    if plan is not None:
        plan.key = key
    _PLAN_CACHE[key] = plan
    return plan


# generic StagedPipeline chaining: fuse the whole chain into ONE program
# when every stage has a plan and every coupling has a registered twin
def _staged_builder(pipe, library: Library, eng: _Engine) -> Optional[FusedPlan]:
    stage_plans = []
    for i, st in enumerate(pipe.stages):
        p = _plan_for(st, library)
        if p is None or p.key in _PINNED:
            return None
        if not p.device_natural and i < len(pipe.stages) - 1:
            return None  # host-tailed plan can't feed a later stage
        stage_plans.append(p)
    twins = []
    for c in pipe.couplings:
        name = "identity" if c.sim is None else c.name
        if name not in _COUPLINGS:
            return None
        twins.append(_COUPLINGS[name])
    counts = pipe.stage_slot_counts()
    last = len(stage_plans) - 1

    def stage_fn(genes, x, per_genome):
        per = per_genome
        off = 0
        for i, (sp, ns) in enumerate(zip(stage_plans, counts)):
            y = sp.stage_fn(genes[:, off:off + ns], x, per)
            off += ns
            per = True  # stage outputs always carry the genome axis
            x = twins[i](y) if (i < last and twins[i] is not None) else y
        return x

    tail = stage_plans[last]
    return FusedPlan(
        key=(), stage_fn=stage_fn, prep=stage_plans[0].prep,
        post=tail.post, qor_ref=tail.qor_ref,
        device_natural=tail.device_natural,
    )


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def _bucket(G: int) -> int:
    """Pad-to-bucket population size: next power of two (min 4), so
    steady-state searches with drifting survivor counts never retrace."""
    return max(4, 1 << (int(G) - 1).bit_length())


def _pad_rows(arr: np.ndarray, B: int) -> np.ndarray:
    G = len(arr)
    if G == B:
        return arr
    reps = np.repeat(arr[:1], B - G, axis=0)
    return np.concatenate([arr, reps], axis=0)


def _compiled(plan: FusedPlan, *, bucket: int, per_genome: bool,
              x_sig: tuple, want_sse: bool, n_genes: int) -> Callable:
    """Jit-cache lookup keyed on (plan structural key, bucket, input
    signature); a miss compiles (counted), a hit is a bucket hit."""
    key = (plan.key, bucket, per_genome, x_sig, want_sse, n_genes)
    fn = _JIT_CACHE.get(key)
    if fn is not None:
        _bump("bucket_hits")
        return fn
    import jax

    if want_sse:
        from ..core.qor import sse_batch_jax

        def run(genes, x, ref):
            out = plan.stage_fn(genes, x, per_genome)
            return sse_batch_jax(ref, out)
    else:
        def run(genes, x):
            return plan.stage_fn(genes, x, per_genome)

    fn = jax.jit(run)
    _JIT_CACHE[key] = fn
    _bump("compiles")
    return fn


def _execute(plan: FusedPlan, genomes: np.ndarray, x: np.ndarray,
             *, per_genome: bool, ref: Optional[np.ndarray] = None):
    """Bucket, pad, run the compiled program, slice back to G."""
    import jax
    import jax.numpy as jnp

    from .. import obs

    G = len(genomes)
    B = _bucket(G)
    g_pad = _pad_rows(np.ascontiguousarray(genomes, dtype=np.int32), B)
    x_np = plan.prep(x)
    if per_genome:
        x_np = _pad_rows(x_np, B)
    x_sig = (x_np.shape[1:] if per_genome else x_np.shape, x_np.dtype.str)
    want_sse = ref is not None
    fn = _compiled(plan, bucket=B, per_genome=per_genome, x_sig=x_sig,
                   want_sse=want_sse, n_genes=g_pad.shape[1])
    with obs.span("sim.fused", g=G, bucket=B, sse=bool(want_sse)):
        # x64 at trace AND call time: the jax jit cache keys on the flag,
        # and the SSE tail accumulates exact int64
        with jax.enable_x64(True):
            args = [jnp.asarray(g_pad), jnp.asarray(x_np)]
            if want_sse:
                args.append(jnp.asarray(ref))
            out = np.asarray(fn(*args))
    return out[:G]


def _numpy_reference(kind: str, accel, genomes, library, inputs, *,
                     rank_genes: bool, per_genome_inputs: bool = False,
                     peak=None):
    """Run the numpy engine with fused dispatch disabled (re-entrancy
    guard), for verification and for pinned fallbacks."""
    _GUARD.active = True
    try:
        if kind == "sim":
            return accel.simulate_batch(
                genomes, library, inputs,
                rank_genes=rank_genes, per_genome_inputs=per_genome_inputs,
            )
        return accel.qor_batch(
            genomes, library, inputs, rank_genes=rank_genes, peak=peak,
        )
    finally:
        _GUARD.active = False


def _verify_or_pin(plan: FusedPlan, got: np.ndarray, want: np.ndarray,
                   what: str) -> bool:
    """True iff the fused result is byte-identical to the numpy engine;
    divergence pins the plan's whole family back to numpy."""
    _bump("verify_calls")
    ok = (
        got.shape == want.shape
        and got.dtype == want.dtype
        and np.array_equal(got, want)
    )
    if not ok:
        _PINNED[plan.key] = what
        _bump("pins")
        log.warning(
            "fused sim: %s diverged from numpy engine for %s — pinning "
            "family to the numpy path", what, plan.key[:2],
        )
    return ok


def _gate(accel, library) -> Optional[FusedPlan]:
    if not enabled() or getattr(_GUARD, "active", False):
        return None
    plan = _plan_for(accel, library)
    if plan is None or plan.key in _PINNED:
        if _builder_for(accel) is not None:
            # a fusible family runs the numpy engine: count it, so a
            # run that expects the device path can check it got it
            _bump("fallback_calls")
        return None
    return plan


def try_simulate_batch(
    accel, genomes, library, inputs, *,
    rank_genes: bool = False, per_genome_inputs: bool = False,
) -> Optional[np.ndarray]:
    """Fused ``simulate_batch``; None routes the caller to its numpy
    body (kill switch, re-entrant verification, unfused or pinned
    accelerator)."""
    plan = _gate(accel, library)
    if plan is None:
        return None
    genomes = np.atleast_2d(np.asarray(genomes))
    raw = _execute(plan, genomes, inputs, per_genome=per_genome_inputs)
    out = plan.post(raw, inputs, per_genome_inputs)
    left = _VERIFY_LEFT.get(plan.key, _verify_budget())
    if left > 0:
        want = _numpy_reference(
            "sim", accel, genomes, library, inputs,
            rank_genes=rank_genes, per_genome_inputs=per_genome_inputs,
        )
        if not _verify_or_pin(plan, out, want, "simulate_batch"):
            return want
        _VERIFY_LEFT[plan.key] = left - 1
    _bump("fused_calls")
    return out


def try_qor_batch(
    accel, genomes, library, inputs, *,
    rank_genes: bool = False, peak=None,
) -> Optional[np.ndarray]:
    """Fully fused ``(genomes, inputs) → QoR``: device-side integer SSE
    against the exact reference, host-side PSNR finish.  Only plans with
    an integer exact reference (``qor_ref``) qualify — float tails (the
    DCT's float64 reconstruction) return None here and instead run the
    generic qor path over the fused ``simulate_batch``."""
    plan = _gate(accel, library)
    if plan is None or plan.qor_ref is None:
        return None
    genomes = np.atleast_2d(np.asarray(genomes))
    ref = plan.qor_ref(accel, inputs)
    pk = (float(np.max(np.abs(ref))) or 1.0) if peak is None else float(peak)
    sse = _execute(plan, genomes, inputs, per_genome=False, ref=ref)
    from ..core.qor import psnr_from_sse

    vals = psnr_from_sse(sse, ref.size, pk)
    left = _VERIFY_LEFT.get(plan.key, _verify_budget())
    if left > 0:
        want = _numpy_reference(
            "qor", accel, genomes, library, inputs,
            rank_genes=rank_genes, peak=peak,
        )
        if not _verify_or_pin(plan, vals, want, "qor_batch"):
            return want
        _VERIFY_LEFT[plan.key] = left - 1
    _bump("fused_qor_calls")
    return vals


def _register_staged() -> None:
    # registered lazily to dodge the accel <-> hierarchy import cycle
    from ..hierarchy.staged import StagedPipeline

    if StagedPipeline not in _BUILDERS:
        _BUILDERS[StagedPipeline] = _staged_builder
