"""Process-pool ground-truth labeler.

Behavioral simulation is numpy (GIL-bound) and XLA synthesis holds the
GIL through tracing — thread workers give ZERO labeling parallelism (the
scheduler's thread pool only overlaps I/O).  This module fans whole
coalesced label batches out to a pool of **spawned worker processes**,
each of which initializes once (library + exhaustive product tables
warmed at startup, accelerators and evaluation contexts cached per
fingerprint) and then labels genome chunks with the same batched
``EvalContext.ground_truth`` path the thread backend uses.

Labels are a pure function of the evaluation context fingerprint and the
genome, so process-backend labels are byte-identical to thread-backend
labels (tests pin this).

Nothing heavyweight is pickled: workers rebuild the accelerator from its
NAME via ``make_accelerator`` and the default library from scratch.  A
context is process-safe exactly when a fresh process would derive the
SAME context fingerprint from the name — ``can_label`` checks that in
the parent (resolving the name with the registry bypassed, since
``register_accelerator`` entries don't exist in a spawned child) and the
scheduler falls back to in-process labeling when it fails (ad-hoc
registered pipelines, subset libraries, parameterized accelerators).
"""

from __future__ import annotations

import math
import multiprocessing as mp
import os
import threading
import time
from concurrent.futures import ProcessPoolExecutor
from typing import Dict, Optional

import numpy as np

from .. import obs
from .store import LABEL_KEYS, EvalContext

__all__ = [
    "ProcessPoolLabeler", "WORKER_XLA_FLAGS", "require_cpu_backend",
    "warm_library",
]

# Appended to XLA_FLAGS in each worker BEFORE jax loads: one compile's
# parallel LLVM codegen would fight the other workers for cores, so each
# worker compiles single-threaded and the pool supplies the parallelism.
# Codegen splitting only parallelizes backend code emission — HLO-level
# cost analysis (the labels) is unaffected.
WORKER_XLA_FLAGS = "--xla_cpu_parallel_codegen_split_count=1"

# per-worker-process state: the warm library and the contexts built so far
_WORKER_STATE: Dict = {}


def require_cpu_backend(what: str, remedy: str) -> None:
    """Refuse to start JAX worker processes next to this one unless JAX
    runs on the CPU here.  An accelerator belongs to one process at a
    time: once this process has touched JAX it holds the chip, and a
    child that needs it fails or hangs on the lock."""
    import jax

    backend = jax.default_backend()
    if backend != "cpu":
        raise RuntimeError(
            f"{what} starts worker processes that each load JAX, but this "
            f"process holds the {backend} backend and a chip belongs to "
            f"one process at a time; {remedy}"
        )


def warm_library(lib) -> None:
    """Build every multiplier circuit's labeling-side caches: the
    exhaustive product table (the batched sim's LUT source), the error
    table, its effective rank and the deployment-rank SVD factors.  A
    cold labeler pays these lazily INSIDE its first batches (one
    256x256 SVD per circuit); warming them once up front keeps them out
    of the steady-state label stream."""
    for kind in ("mul8u", "mul8s"):
        for c in lib.kind(kind):
            c.table
            c.etab
            r = c.deploy_rank
            if r > 0:
                c.factors(r)


def _init_worker(xla_flags: str = "", synth_cache_path: str = "") -> None:
    """Run once per spawned process: pin down XLA's threading before jax
    is imported, then build the library and warm the per-circuit
    labeling caches so the first labeled chunk doesn't pay them.  With a
    ``synth_cache_path`` the worker joins the pool-wide persistent
    compile cache (one JSONL file appended by every worker AND the
    parent), so no structure ever compiles twice across the pool."""
    if xla_flags:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") + " " + xla_flags
        ).strip()
    from ..core.acl.library import default_library
    from ..core.features import synth

    if synth_cache_path:
        # non-migrating open: the parent already owns (and may have
        # migrated) this path; replicas must never rename it
        synth.set_shared_synth_cache(
            synth.open_synth_cache(synth_cache_path))
    lib = default_library()
    warm_library(lib)
    # pre-build (and probe-verify) the fused sim engine's adder twins so
    # the first labeled chunk only pays its own shape's XLA compile —
    # structurally identical contexts then land in warm jit buckets
    from ..accel import fused

    fused.warm(lib)
    _WORKER_STATE["library"] = lib
    _WORKER_STATE["ctxs"] = {}


def _worker_label(
    accel_name: str,
    rank_genes: bool,
    n_qor_samples: int,
    qor_seed: int,
    expected_fp: str,
    genomes: np.ndarray,
    wire: Optional[Dict] = None,
) -> Dict[str, np.ndarray]:
    """Label one genome chunk inside a worker process."""
    if "library" not in _WORKER_STATE:  # fork-start or initializer skipped
        _init_worker()
    from ..core.features import synth
    from .campaigns import make_accelerator

    key = (accel_name, bool(rank_genes), int(n_qor_samples), int(qor_seed))
    ctx = _WORKER_STATE["ctxs"].get(key)
    if ctx is None:
        ctx = EvalContext(
            make_accelerator(accel_name, builtin_only=True),
            _WORKER_STATE["library"],
            rank_genes=rank_genes,
            n_qor_samples=n_qor_samples,
            qor_seed=qor_seed,
        )
        _WORKER_STATE["ctxs"][key] = ctx
    if ctx.fingerprint != expected_fp:
        # the parent's safety check should make this unreachable; guard
        # anyway so a drifted worker can never poison the store
        raise RuntimeError(
            f"worker context fingerprint {ctx.fingerprint} != parent "
            f"{expected_fp} for {accel_name!r}"
        )
    scache = synth.shared_synth_cache()
    if hasattr(scache, "refresh"):
        # pick up compiles that sibling workers / the parent appended
        scache.refresh()
    # adopt the parent's trace context so this chunk's spans (and the
    # synth.compile spans under it) link to the submitting campaign;
    # the worker handles one chunk at a time, so the ring holds exactly
    # this chunk's spans between clear() and snapshot()
    rec = obs.recorder()
    rec.clear()
    with obs.attach(wire, worker=f"pool-{os.getpid()}"):
        with obs.span("labeler.chunk", n=int(len(genomes)),
                      accel=accel_name):
            labels = ctx.ground_truth(np.asarray(genomes, dtype=np.int64))
    out = {k: np.asarray(labels[k]) for k in LABEL_KEYS}
    # piggyback this worker's cumulative synth counters AND the chunk's
    # finished spans on the result so the parent can aggregate/ingest
    # them without an extra round trip
    out["_synth_stats"] = {"pid": os.getpid(), **scache.stats()}
    from ..accel import fused

    out["_sim_stats"] = {"pid": os.getpid(), **fused.stats()}
    out["_spans"] = rec.snapshot()
    rec.clear()
    return out


class ProcessPoolLabeler:
    """Chunked batch fan-out to spawn-safe worker processes.

    ``label`` splits a genome batch into ~``2 x n_workers`` chunks (or
    fixed ``chunk_size`` rows) and reassembles the per-chunk label dicts
    in order.  ``can_label`` gates which contexts may cross the process
    boundary; callers fall back to in-process labeling otherwise."""

    def __init__(
        self,
        n_workers: int = 2,
        *,
        chunk_size: Optional[int] = None,
        mp_context: str = "spawn",
        xla_flags: str = WORKER_XLA_FLAGS,
        synth_cache_path: Optional[str] = None,
    ):
        require_cpu_backend(
            "the process labeler",
            "label in-process with --eval-backend thread",
        )
        self.n_workers = max(1, int(n_workers))
        self.chunk_size = None if chunk_size is None else max(1, int(chunk_size))
        self.synth_cache_path = synth_cache_path
        self._pool = ProcessPoolExecutor(
            self.n_workers,
            mp_context=mp.get_context(mp_context),
            initializer=_init_worker,
            initargs=(xla_flags, synth_cache_path or ""),
        )
        self._lock = threading.Lock()
        self._safe_fps: Dict[str, bool] = {}   # ctx fingerprint -> verdict
        self._worker_synth: Dict[int, Dict] = {}  # pid -> latest counters
        self._worker_sim: Dict[int, Dict] = {}    # pid -> latest fused-sim counters
        self.n_chunks = obs.REGISTRY.counter(
            "repro_labeler_chunks_total", "chunks sent to worker processes")
        self.n_labeled = obs.REGISTRY.counter(
            "repro_labeler_labeled_total",
            "genomes labeled by the process pool")
        self.batch_seconds = obs.REGISTRY.histogram(
            "repro_labeler_batch_seconds",
            "wall seconds per process-pool batch fan-out")

    # ------------------------------------------------------------------
    def can_label(self, ctx: EvalContext) -> bool:
        """True iff a fresh process, given only ``ctx.accel.name``, would
        rebuild a context with the SAME fingerprint (identical labels and
        store keys).  Cached per fingerprint.  The check itself is the
        fleet's portability gate — one rule decides what may cross a
        process OR host boundary."""
        fp = ctx.fingerprint
        with self._lock:
            if fp in self._safe_fps:
                return self._safe_fps[fp]
        from ..fleet.protocol import context_is_portable

        verdict = context_is_portable(ctx)
        with self._lock:
            self._safe_fps[fp] = verdict
        return verdict

    def _chunks(self, n: int) -> int:
        if self.chunk_size is not None:
            return max(1, math.ceil(n / self.chunk_size))
        # ~2 chunks per worker: keeps the pool busy when chunk costs are
        # uneven without shredding the batched-sim vectorization
        return min(n, 2 * self.n_workers)

    def label(self, ctx: EvalContext, genomes: np.ndarray) -> Dict[str, np.ndarray]:
        """Label a genome batch across the pool (caller must have
        checked ``can_label``)."""
        genomes = np.atleast_2d(np.asarray(genomes, dtype=np.int64))
        parts = [
            c for c in np.array_split(genomes, self._chunks(len(genomes)))
            if len(c)
        ]
        t0 = time.perf_counter()
        with obs.span("labeler.batch", n=int(len(genomes)),
                      chunks=len(parts)):
            wire = obs.wire_context()
            futures = [
                self._pool.submit(
                    _worker_label,
                    ctx.accel.name, ctx.rank_genes, ctx.n_qor_samples,
                    ctx.qor_seed, ctx.fingerprint, chunk, wire,
                )
                for chunk in parts
            ]
            results = [f.result() for f in futures]
        self.batch_seconds.observe(time.perf_counter() - t0)
        self.n_chunks.inc(len(parts))
        self.n_labeled.inc(len(genomes))
        rec = obs.recorder()
        with self._lock:
            for r in results:
                ws = r.get("_synth_stats")
                if ws:   # counters are cumulative: latest-per-pid wins
                    self._worker_synth[ws["pid"]] = ws
                sim = r.get("_sim_stats")
                if sim:
                    self._worker_sim[sim["pid"]] = sim
        for r in results:
            rec.ingest(r.get("_spans") or ())
        return {
            k: np.concatenate([r[k] for r in results]) for k in LABEL_KEYS
        }

    def stats(self) -> Dict[str, int]:
        """Pool counters + the aggregated synthesis-engine counters of
        every worker process (compiles, identity/structural cache hits,
        verification compiles, pinned families)."""
        with self._lock:
            per_worker = list(self._worker_synth.values())
        synth_agg = {k: sum(int(w.get(k, 0)) for w in per_worker)
                     for k in ("compiles", "verify_compiles",
                               "identity_hits", "structural_hits",
                               "pinned_families")}
        # cache sizes are shared state when the pool rides one cache
        # file: report the widest view, not the (double-counting) sum
        for k in ("entries", "structures"):
            synth_agg[k] = max((int(w.get(k, 0)) for w in per_worker),
                               default=0)
        served = synth_agg["identity_hits"] + synth_agg["structural_hits"]
        total = served + synth_agg["compiles"]
        synth_agg["hit_rate"] = (served / total) if total else 0.0
        synth_agg["workers_reporting"] = len(per_worker)
        with self._lock:
            per_worker_sim = list(self._worker_sim.values())
        sim_agg = {k: sum(int(w.get(k, 0)) for w in per_worker_sim)
                   for k in ("fused_calls", "fused_qor_calls", "compiles",
                             "bucket_hits", "verify_calls", "pins",
                             "fallback_calls")}
        sim_agg["workers_reporting"] = len(per_worker_sim)
        return {
            "workers": self.n_workers,
            "chunks": int(self.n_chunks.value),
            "labeled": int(self.n_labeled.value),
            "synth_cache_path": self.synth_cache_path,
            "synth": synth_agg,
            "sim": sim_agg,
        }

    def shutdown(self, *, wait: bool = True) -> None:
        self._pool.shutdown(wait=wait)
