"""XLA 'synthesis' — our Vivado tool-chain analogue (ground-truth labels).

The paper's ground truth for one accelerator variant is a full Vivado
synthesis run (minutes/design): LUTs, power, delay.  Ours is a full XLA
lower+compile of the variant's rank-k MXU deployment (seconds/design):
``cost_analysis()`` FLOPs and bytes, turned into roofline latency and
energy on TPU v5e constants (core/hw.py).  The QoR ground truth is the
bit-exact behavioral simulation (accel.simulate).

Both are deliberately the *slow* path; the whole point of the paper is to
call them O(n_train + n_final) times instead of O(|space|).
"""

from __future__ import annotations

import hashlib
import json
import os
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from typing import TYPE_CHECKING

if TYPE_CHECKING:  # avoid circular import
    from ...accel.base import Accelerator
from ... import faults, obs
from ...core.acl.library import Circuit, Library
from ...segments import SegmentedLog
from .. import hw

__all__ = [
    "SynthResult",
    "SynthCache",
    "JsonlSynthCache",
    "SegmentedSynthCache",
    "open_synth_cache",
    "synthesize_variant",
    "synthesize_batch",
    "circuit_features_synth",
    "label_variants",
    "shared_synth_cache",
    "set_shared_synth_cache",
    "synth_stats",
    "reset_fast_codegen",
    "backend_tag",
    "LABEL_KEYS",
    "DEFAULT_QOR_SEED",
    "SYNTH_AC_DIM",
]

SYNTH_AC_DIM = 6

# the per-genome record label_variants produces (the service label
# store persists exactly these keys — keep the two in sync by import)
LABEL_KEYS = ("qor", "latency", "energy", "flops", "hbm_bytes",
              "synth_time", "sim_time")

# default seed for the QoR evaluation inputs: shared by the in-process
# default labeler (core/dse.py) and the service EvalContext so both
# paths label identically (and derive identical store keys)
DEFAULT_QOR_SEED = 1234


class SynthResult(dict):
    """{'flops', 'hbm_bytes', 'latency', 'energy', 'wall_time'}"""


# --- guarded fast codegen ---------------------------------------------------
# Ground-truth labels read HLO-level quantities (flops, bytes accessed)
# off compiled_cost_analysis; most of the compile wall is backend code
# GENERATION, which does not enter them.  FAST_CODEGEN compiles
# synthesis probes at LLVM opt level 0, without expensive LLVM passes,
# on the non-thunk runtime (~2x faster on multi-slot deploys) — but the
# options are only trusted per GRAPH FAMILY after verification: the
# first compile of each ``fast_key`` runs BOTH ways and compares the
# cost-analysis keys the labels read.  Families where any option leaks
# into HLO-level cost (e.g. the LM forward under the non-thunk runtime)
# are pinned to default codegen, keeping labels byte-identical to the
# seed engine by construction.  REPRO_SYNTH_FAST=0 disables the whole
# mechanism; unknown options degrade to a default compile.  The options
# are CPU-LLVM flags, so the mechanism runs only on the CPU backend;
# elsewhere each graph compiles once with default options.
FAST_CODEGEN = os.environ.get("REPRO_SYNTH_FAST", "1") != "0"
_FAST_COMPILER_OPTIONS = {
    "xla_backend_optimization_level": 0,
    "xla_llvm_disable_expensive_passes": True,
    "xla_cpu_use_thunk_runtime": False,
    "xla_cpu_copy_insertion_use_region_analysis": False,
}
_COST_KEYS = ("flops", "bytes accessed")
# The verdict is per graph FAMILY (one accelerator's build_deploy /
# one circuit kind's canonical probe), verified on the family's first
# few distinct graphs rather than every graph — per-graph verification
# would double-compile everything and erase the speedup.  Family-level
# sampling is sound because option leakage into HLO-level cost is
# driven by op-type coverage (e.g. the thunk runtime rewrites
# control-flow ops, which is why the LM forward diverges and is pinned
# to default codegen on its very first compile), and graphs within one
# family share op types, differing only in per-slot rank/width counts.
# Residual risk is bounded by REPRO_SYNTH_FAST=0.
_FAST_VERIFY_SAMPLES = 2
# fast_key -> remaining verifications (int countdown) | False (diverged)
_FAST_VERDICT: Dict[str, object] = {}


# --- structural compile keying ---------------------------------------------
# The compiled cost numbers the labels read (HLO-level flops / bytes
# accessed) are determined by the deployment graph's STRUCTURE — matmul
# shapes, slot-group widths, per-slot deployment class (rank, truncated
# width, signedness), pass count — not by which named circuit fills a
# slot (the rank-1 family alone holds 7 interchangeable circuits, and
# slot PERMUTATIONS of equal-width groups compile to isomorphic graphs).
# Keying compiles on ``Accelerator.deploy_signature`` therefore collapses
# distinct compiles from O(|library|^slots) circuit identities to
# O(distinct structures), and makes the cache survive context changes
# (QoR sample count / seed) and accelerator renames (a pipeline's stage
# view shares the standalone accelerator's compiles).
#
# The invariance is VERIFIED, not assumed, with the proven _FAST_VERDICT
# scheme: the first ``_STRUCT_VERIFY_SAMPLES`` structural collisions of
# each graph FAMILY (one accelerator's builder; classes vary within it)
# compile the colliding identity anyway and compare the cost keys the
# labels read.  A family whose numbers ever diverge is pinned to exact
# identity keys.  REPRO_SYNTH_STRUCTURAL=0 kills structural sharing
# entirely (identity-keyed caching, the seed engine's semantics).
STRUCTURAL_KEYS = os.environ.get("REPRO_SYNTH_STRUCTURAL", "1") != "0"
_STRUCT_VERIFY_SAMPLES = 2

# REPRO_SYNTH_COMPILE_WORKERS>1 compiles a batch's unique structures on a
# thread pool.  Default is serial: jaxlib 0.4.x's CPU client serializes
# compilation internally (measured 0.73-0.89x with 2 threads), so the
# knob only pays off on jaxlibs whose compile path truly releases the
# GIL; batch-level parallelism normally comes from the process pool
# (service/workers.py) instead.
COMPILE_WORKERS = int(os.environ.get("REPRO_SYNTH_COMPILE_WORKERS", "1") or 1)

# cache-key salt: a jax/jaxlib upgrade, another backend or a label-
# semantics change must MISS a persisted compile cache instead of
# serving stale cost numbers
SYNTH_CACHE_SCHEMA_VERSION = 1


def backend_tag() -> str:
    """The backend a label is computed on: JAX's default backend and its
    first device's kind (e.g. ``tpu:TPU v5 lite``).  Compile costs come
    from that backend's compiler, so labels and compile caches key on it:
    a store warmed on one backend misses on another."""
    import jax

    return f"{jax.default_backend()}:{jax.devices()[0].device_kind}"


def _cache_salt() -> str:
    import jax

    return (f"v{SYNTH_CACHE_SCHEMA_VERSION}|jax{jax.__version__}|"
            f"{backend_tag()}")


def _digest(tag: str, payload: object) -> str:
    h = hashlib.sha256(f"{tag}|{_cache_salt()}|{payload!r}".encode())
    return h.hexdigest()[:24]


def _identity_signature(accel, specs) -> tuple:
    """Exact per-slot circuit identity (the seed engine's cache key)."""
    return (accel.name,) + tuple(
        (s.name, s.rank, s.trunc_bits) for s in specs
    )


def _structural_signature(accel, specs) -> Optional[Tuple[tuple, tuple]]:
    """``(family, classes)`` from the accelerator's signature hook, or
    None when the accelerator opts out (no hook / hook returns None)."""
    hook = getattr(accel, "deploy_signature", None)
    if hook is None:
        return None
    try:
        sig = hook(specs)
    except NotImplementedError:
        return None
    if sig is None:
        return None
    family, classes = sig
    return tuple(family), tuple(classes)


class SynthCache:
    """Shared compile-cost cache with two tiers.

    * identity tier — keyed on the exact per-slot circuit assignment;
      hits are safe unconditionally (same graph, deterministic compile).
    * structural tier — keyed on ``deploy_signature``; a hit recorded by
      a DIFFERENT identity is only served after the graph family passed
      its first-K verification compiles (see module comment).

    One instance is shared process-wide by default (``shared_synth_
    cache``) so every evaluation context, campaign and scheduler worker
    reuses one compile pool; ``JsonlSynthCache`` adds persistence.
    Thread-safe; records hold only the compile-derived numbers
    ({'flops', 'hbm_bytes'}) — everything else in a label is recomputed
    per variant from its circuits and ranks."""

    def __init__(self):
        self._lock = threading.RLock()
        self._by_id: Dict[str, dict] = {}
        self._by_struct: Dict[str, dict] = {}
        # family digest -> remaining verifications | False (pinned)
        self._verdicts: Dict[str, object] = {}
        # registry instruments (idempotent-replace: the live process-
        # shared cache is the one a /metrics scrape sees); increments
        # stay under the cache lock they always ran under
        reg = obs.REGISTRY
        self.hits_identity = reg.counter(
            "repro_synth_identity_hits_total",
            "compiles served from the identity tier")
        self.hits_structural = reg.counter(
            "repro_synth_structural_hits_total",
            "compiles served from the verified structural tier")
        self.compiles = reg.counter(
            "repro_synth_compiles_total", "XLA compiles paid")
        self.verify_compiles = reg.counter(
            "repro_synth_verify_compiles_total",
            "compiles spent verifying a structural family")
        self.pinned_families = reg.counter(
            "repro_synth_pinned_families_total",
            "graph families pinned to identity-only caching")
        self.compile_seconds = reg.histogram(
            "repro_synth_compile_seconds", "wall seconds per XLA compile")

    # -- lookups -------------------------------------------------------
    def get_identity(self, idd: str) -> Optional[dict]:
        with self._lock:
            rec = self._by_id.get(idd)
            if rec is not None:
                self.hits_identity.inc()
            return rec

    def get_structural(self, sdd: str) -> Optional[dict]:
        with self._lock:
            return self._by_struct.get(sdd)

    # -- stores --------------------------------------------------------
    def store(self, rec: dict, *, verify: bool = False) -> None:
        """Record one compile: ``rec`` carries k (identity digest),
        flops, hbm_bytes and optionally s (structural digest) + fam."""
        with self._lock:
            self.compiles.inc()
            if verify:
                self.verify_compiles.inc()
            self._store_locked(dict(rec))

    def store_alias(self, rec: dict) -> None:
        """Record a STRUCTURAL SERVE: the identity now maps to numbers
        another identity compiled.  Counted as a hit, not a compile (and
        persisted, so a warm run answers it from the identity tier)."""
        with self._lock:
            self.hits_structural.inc()
            self._store_locked(dict(rec))

    def _store_locked(self, rec: dict) -> None:
        self._by_id[rec["k"]] = rec
        sdd = rec.get("s")
        if sdd is not None and sdd not in self._by_struct:
            self._by_struct[sdd] = rec

    # -- family verdicts -----------------------------------------------
    def verdict(self, fam: str):
        """Remaining verification compiles for a family (int countdown)
        or False once the family diverged and is identity-pinned."""
        with self._lock:
            return self._verdicts.get(fam, _STRUCT_VERIFY_SAMPLES)

    def verdict_pass(self, fam: str) -> None:
        with self._lock:
            v = self._verdicts.get(fam, _STRUCT_VERIFY_SAMPLES)
            if v is not False and v > 0:
                self._set_verdict_locked(fam, v - 1)

    def verdict_pin(self, fam: str) -> None:
        with self._lock:
            if self._verdicts.get(fam) is not False:
                self.pinned_families.inc()
            self._set_verdict_locked(fam, False)
            # structural records of a pinned family must never serve
            # other identities again
            self._by_struct = {
                s: r for s, r in self._by_struct.items()
                if r.get("fam") != fam
            }

    def _set_verdict_locked(self, fam: str, v) -> None:
        self._verdicts[fam] = v

    # ------------------------------------------------------------------
    def __len__(self) -> int:
        with self._lock:
            return len(self._by_id)

    def stats(self) -> Dict[str, float]:
        compiles = int(self.compiles.value)
        served = int(self.hits_identity.value) + int(
            self.hits_structural.value)
        total = served + compiles
        with self._lock:
            return {
                "entries": len(self._by_id),
                "structures": len(self._by_struct),
                "compiles": compiles,
                "verify_compiles": int(self.verify_compiles.value),
                "identity_hits": int(self.hits_identity.value),
                "structural_hits": int(self.hits_structural.value),
                "hit_rate": (served / total) if total else 0.0,
                "pinned_families": int(self.pinned_families.value),
                # v is False means PINNED, not verified — and False == 0
                # in Python, so the identity check is load-bearing
                "verified_families": sum(
                    1 for v in self._verdicts.values()
                    if v is not False and v == 0
                ),
            }


class JsonlSynthCache(SynthCache):
    """Persistent ``SynthCache``: an append-only JSON-lines sidecar next
    to the service's ``JsonlLabelStore``.

    One record per compile: ``{"k": <identity digest>, "s": <structural
    digest|null>, "fam": <family digest|null>, "c": {"flops", "hbm_
    bytes"}}``; family verification progress persists as ``{"fam": ...,
    "v": <countdown|"pinned">}`` lines so a warm process continues where
    the cold one stopped (a fully verified family does ZERO verification
    compiles after a restart).  Concurrent writers (scheduler threads,
    process-pool labeler workers) append under the same torn-tail replay
    discipline as ``JsonlLabelStore``: the tail is re-read before every
    append, so one cache file is safely shared by many processes."""

    def __init__(self, path: str):
        super().__init__()
        self.path = str(path)
        self._offset = 0
        self._fh = None
        self.quarantined = 0  # malformed/torn records dropped, counted
        d = os.path.dirname(os.path.abspath(self.path))
        os.makedirs(d, exist_ok=True)
        with self._lock:
            self._replay_locked()

    def _replay_locked(self) -> None:
        if not os.path.exists(self.path):
            return
        # errors="replace": undecodable bit-rot must fail a line's CRC,
        # not crash the replay
        with open(self.path, errors="replace") as f:
            f.seek(self._offset)
            while True:
                pos = f.tell()
                line = f.readline()
                if not line or not line.endswith("\n"):
                    self._offset = pos   # torn tail: re-read next time
                    return
                try:
                    rec = json.loads(line)
                except json.JSONDecodeError:
                    # malformed complete line: dropped, but counted and
                    # logged — never a silent swallow
                    self.quarantined += 1
                    obs.get_logger("synth.cache").warning(
                        "quarantined malformed record in %s @%d",
                        self.path, pos)
                    continue
                if "k" in rec and "c" in rec:
                    # base-class store: replayed records must not be
                    # re-appended to the file they came from
                    SynthCache._store_locked(self, {
                        "k": rec["k"], "s": rec.get("s"),
                        "fam": rec.get("fam"),
                        "flops": float(rec["c"]["flops"]),
                        "hbm_bytes": float(rec["c"]["hbm_bytes"]),
                    })
                elif "fam" in rec and "v" in rec:
                    v = rec["v"]
                    SynthCache._set_verdict_locked(
                        self, rec["fam"], False if v == "pinned" else int(v)
                    )

    def refresh(self) -> int:
        """Pick up records other processes appended since the last read."""
        with self._lock:
            self._replay_locked()
            return len(self._by_id)

    def _append_locked(self, obj: dict) -> None:
        if self._fh is None:
            self._fh = open(self.path, "a")
        # consume any foreign tail BEFORE appending so advancing the
        # offset can never skip another process's records
        self._replay_locked()
        # a torn tail from a dead writer would merge with our record and
        # destroy both; newline-terminate it so it quarantines alone
        try:
            size = os.path.getsize(self.path)
        except OSError:
            size = 0
        if size > self._offset:
            torn = size - self._offset
            self._fh.write("\n")
            self._fh.flush()
            self._offset = self._fh.tell()
            self.quarantined += 1
            obs.get_logger("synth.cache").warning(
                "repaired torn tail in %s (%d bytes quarantined)",
                self.path, torn)
        self._fh.write(json.dumps(obj, sort_keys=True) + "\n")
        self._fh.flush()
        self._offset = self._fh.tell()

    def _store_locked(self, rec: dict) -> None:
        fresh = rec["k"] not in self._by_id
        super()._store_locked(rec)
        if fresh:
            self._append_locked({
                "k": rec["k"], "s": rec.get("s"), "fam": rec.get("fam"),
                "c": {"flops": rec["flops"], "hbm_bytes": rec["hbm_bytes"]},
            })

    def _set_verdict_locked(self, fam: str, v) -> None:
        cur = self._verdicts.get(fam, _STRUCT_VERIFY_SAMPLES)
        # False (pinned) and 0 (verified) compare equal in Python; a pin
        # arriving after the countdown reached 0 MUST still persist, or
        # a warm replay would serve a family proven divergent
        changed = (cur is False) != (v is False) or (
            v is not False and cur != v
        )
        super()._set_verdict_locked(fam, v)
        if changed:
            self._append_locked(
                {"fam": fam, "v": "pinned" if v is False else int(v)}
            )

    def stats(self) -> Dict[str, float]:
        s = super().stats()
        s["path"] = self.path
        s["quarantined"] = self.quarantined
        return s

    def close(self) -> None:
        with self._lock:
            if self._fh is not None:
                self._fh.close()
                self._fh = None

    def __del__(self):  # best-effort
        try:
            self.close()
        except Exception:
            pass


class SegmentedSynthCache(SynthCache):
    """Persistent ``SynthCache`` on the segmented CRC-framed log
    (:mod:`repro.segments`) — the fleet-grade replacement for one big
    ``JsonlSynthCache`` sidecar.

    Record shapes are identical to ``JsonlSynthCache``'s (compiles and
    family-verdict lines), but they live in fixed-size sealed segments
    with per-record CRCs and a manifest: a damaged record or segment is
    quarantined and counted (the lost compiles simply re-compile)
    instead of poisoning a warm replay, and all appends/seals run under
    one cross-process ``flock``.  Replay is eager — the compile cache is
    small next to the label store and every record is needed to answer
    lookups — but it is CRC-verified end to end."""

    def __init__(self, path: str, *, segment_records: int = 4096):
        super().__init__()
        self.path = str(path)
        self._seglog = SegmentedLog(self.path,
                                    segment_records=segment_records,
                                    name="synth")
        self._known_segs = set()
        with self._lock:
            with self._seglog.lock():
                self._sync_cache_locked()

    # -- replay ---------------------------------------------------------
    def _ingest_locked(self, rec) -> None:
        if not isinstance(rec, dict):
            return
        if "k" in rec and "c" in rec:
            SynthCache._store_locked(self, {
                "k": rec["k"], "s": rec.get("s"),
                "fam": rec.get("fam"),
                "flops": float(rec["c"]["flops"]),
                "hbm_bytes": float(rec["c"]["hbm_bytes"]),
            })
        elif "fam" in rec and "v" in rec:
            v = rec["v"]
            SynthCache._set_verdict_locked(
                self, rec["fam"], False if v == "pinned" else int(v))

    def _sync_cache_locked(self) -> None:
        m, tail = self._seglog.sync_locked()
        for e in m["sealed"]:
            name = e["name"]
            if name in self._known_segs:
                continue
            self._known_segs.add(name)
            try:
                recs, bad = self._seglog.read_segment(name)
            except OSError as err:
                recs, bad, reason = [], -1, f"unreadable: {err}"
            else:
                reason = f"{bad} damaged records"
            if bad:
                if bad > 0:
                    self._seglog.quarantined_records += bad
                self._seglog.quarantine_locked(name, reason)
                self._known_segs.discard(name)
                # salvaged records still serve; the rest re-compile
            for rec in recs:
                self._ingest_locked(rec)
        for rec in tail:
            self._ingest_locked(rec)

    def refresh(self) -> int:
        """Pick up records other processes appended/sealed."""
        with self._lock:
            with self._seglog.lock():
                self._sync_cache_locked()
            return len(self._by_id)

    # -- writes ---------------------------------------------------------
    def _append(self, obj: dict) -> None:
        with self._seglog.lock():
            self._sync_cache_locked()
            self._seglog.append_locked([obj])

    def _store_locked(self, rec: dict) -> None:
        fresh = rec["k"] not in self._by_id
        super()._store_locked(rec)
        if fresh:
            self._append({
                "k": rec["k"], "s": rec.get("s"), "fam": rec.get("fam"),
                "c": {"flops": rec["flops"],
                      "hbm_bytes": rec["hbm_bytes"]},
            })

    def _set_verdict_locked(self, fam: str, v) -> None:
        cur = self._verdicts.get(fam, _STRUCT_VERIFY_SAMPLES)
        changed = (cur is False) != (v is False) or (
            v is not False and cur != v
        )
        super()._set_verdict_locked(fam, v)
        if changed:
            self._append(
                {"fam": fam, "v": "pinned" if v is False else int(v)}
            )

    def stats(self) -> Dict[str, float]:
        s = super().stats()
        s["path"] = self.path
        seg = self._seglog.stats()
        s["quarantined"] = seg.pop("quarantined")
        s.update(seg)
        return s

    def close(self) -> None:
        with self._lock:
            self._seglog.close()

    def __del__(self):  # best-effort
        try:
            self.close()
        except Exception:
            pass


def open_synth_cache(path: str, *, migrate: bool = False,
                     **kw) -> SynthCache:
    """Open the right persistent compile cache for ``path``: a legacy
    single-file ``<name>.jsonl`` with ``migrate=True`` auto-migrates
    *warm* into a segmented root at ``<name>.segd`` (old file kept as
    ``.migrated``); without ``migrate`` a ``.jsonl`` path opens the
    already-migrated root when one exists, else the plain
    :class:`JsonlSynthCache` — replicas never rename a file another
    process may still be appending to.  Any other path is a segmented
    root directly."""
    p = str(path)
    if not p.endswith(".jsonl"):
        return SegmentedSynthCache(p, **kw)
    root = p[:-len(".jsonl")] + ".segd"
    if not migrate:
        if os.path.isdir(root) and not os.path.isfile(p):
            return SegmentedSynthCache(root, **kw)
        return JsonlSynthCache(p, **kw)
    cache = SegmentedSynthCache(root, **kw)
    if os.path.isfile(p):
        legacy = []
        with open(p) as f:
            for line in f:
                if not line.endswith("\n"):
                    continue  # torn legacy tail
                try:
                    rec = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if isinstance(rec, dict) and (
                        ("k" in rec and "c" in rec)
                        or ("fam" in rec and "v" in rec)):
                    legacy.append(rec)
        if legacy:
            with cache._lock:
                for rec in legacy:
                    cache._ingest_locked(rec)
                with cache._seglog.lock():
                    cache._seglog.sync_locked()
                    cache._seglog.append_locked(legacy)
        try:
            os.replace(p, p + ".migrated")
        except OSError:  # a concurrent migrator won the rename
            pass
        obs.get_logger("synth.cache").info(
            "migrated %d records from %s into %s", len(legacy), p, root)
    return cache


# the process-wide default cache: every label_variants call that does
# not inject its own cache shares this one, so distinct evaluation
# contexts (different QoR sampling, stage views vs their standalone
# accelerator) stop recompiling each other's structures
_SHARED_CACHE = SynthCache()


def shared_synth_cache() -> SynthCache:
    return _SHARED_CACHE


def set_shared_synth_cache(cache: SynthCache) -> SynthCache:
    """Swap the process-default compile cache (e.g. for a persistent
    ``JsonlSynthCache``); returns the previous one."""
    global _SHARED_CACHE
    prev, _SHARED_CACHE = _SHARED_CACHE, cache
    return prev


def synth_stats() -> Dict[str, object]:
    """Process-wide synthesis engine counters (for ``GET /stats``)."""
    return {
        "backend": backend_tag(),
        "structural_keys": STRUCTURAL_KEYS,
        "fast_codegen": FAST_CODEGEN,
        "compile_workers": COMPILE_WORKERS,
        "cache": _SHARED_CACHE.stats(),
    }


def reset_fast_codegen() -> None:
    """Reset every module-global verification/caching state: the fast-
    codegen verdicts AND the structural verdicts + shared compile cache.
    Test fixtures and pool workers call this so one test's (or one
    context's) verification history can never leak into another."""
    global _SHARED_CACHE
    _FAST_VERDICT.clear()
    _SHARED_CACHE = SynthCache()


def _cost_numbers(compiled) -> Dict[str, float]:
    from ...dist.compat import compiled_cost_analysis

    ca = compiled_cost_analysis(compiled)
    missing = [k for k in _COST_KEYS if k not in ca]
    if missing:
        raise RuntimeError(f"compiled cost analysis lacks {missing}: "
                           f"{sorted(ca)}")
    return {k: float(ca[k]) for k in _COST_KEYS}


def _compile_cost(fn, args, *, fast_key: Optional[str] = None) -> Dict[str, float]:
    # wall_time is a compile measurement, and an executable loaded from
    # JAX's persistent compile cache would time the load instead: no
    # synthesis graph is written there, so none is read back
    from jax._src import config as jax_config

    with jax_config.persistent_cache_min_compile_time_secs(float("inf")):
        return _compile_cost_uncached(fn, args, fast_key=fast_key)


def _compile_cost_uncached(fn, args, *,
                           fast_key: Optional[str]) -> Dict[str, float]:
    import jax

    t0 = time.perf_counter()
    lowered = jax.jit(fn).lower(*args)
    compiled = None
    # the fast options are CPU-LLVM flags: on any other backend the
    # graph compiles once, plainly, and a compile error propagates
    cpu = jax.default_backend() == "cpu"
    if FAST_CODEGEN and fast_key is not None and cpu:
        verdict = _FAST_VERDICT.get(fast_key, _FAST_VERIFY_SAMPLES)
        if verdict is not False and verdict > 0:
            # verification compile: both ways, compare what labels read
            ref = lowered.compile()
            try:
                fast = lowered.compile(dict(_FAST_COMPILER_OPTIONS))
                ok = _cost_numbers(fast) == _cost_numbers(ref)
            except Exception:  # noqa: BLE001 - unknown option / old jax
                ok = False
            _FAST_VERDICT[fast_key] = (verdict - 1) if ok else False
            compiled = ref
        elif verdict is not False:
            try:
                compiled = lowered.compile(dict(_FAST_COMPILER_OPTIONS))
            except Exception:  # noqa: BLE001
                compiled = None
    if compiled is None:
        compiled = lowered.compile()
    wall = time.perf_counter() - t0
    ca = _cost_numbers(compiled)
    flops = ca["flops"]
    byts = ca["bytes accessed"]
    rt = hw.roofline(flops, byts, 0.0)
    return {
        "flops": flops,
        "hbm_bytes": byts,
        "latency": rt.t_serial,
        "energy": rt.energy,
        "wall_time": wall,
    }


def _adjusted_compute(accel, circuits, ranks) -> float:
    """Dtype-aware MXU cost (bf16-MAC equivalents) of the variant's
    faithful deployment: per slot, 2*m*width*n * (dtype_factor +
    rank) — truncation circuits deploy natively at narrow width (cheap),
    exotic circuits pay int8 base + bf16 corrections (DESIGN.md §2)."""
    if hasattr(accel, "adjusted_compute"):
        return accel.adjusted_compute(circuits, ranks)
    mul_idx = accel.mul_slot_indices()
    m, ktot, n = accel.matmul_shape()
    groups = accel.slot_groups()
    passes = getattr(accel, "deploy_passes", 1)
    total = 0.0
    for (s0, e0), i, r in zip(groups, mul_idx, ranks):
        c = circuits[i]
        base = hw.V5E.dtype_cost_factor(c.deploy_width)
        rank = c.deploy_rank if r is None else (
            0 if c.native_width is not None else int(r)
        )
        total += 2.0 * m * (e0 - s0) * n * (base + rank)
    return total * passes


def _finish_record(accel, circuits, ranks, specs, compiled: dict,
                   wall: float, cache_hit: bool) -> SynthResult:
    """Full per-variant label record from the compile-derived numbers.

    Only {'flops', 'hbm_bytes'} come from the (cached) compile; latency
    and energy are recomputed per variant from its circuits/ranks, so a
    structural cache hit can never leak another variant's dtype mix."""
    out = SynthResult()
    out["flops"] = compiled["flops"]
    out["hbm_bytes"] = compiled["hbm_bytes"]
    out["wall_time"] = wall
    adj = _adjusted_compute(accel, circuits, ranks)
    out["mxu_flops_adjusted"] = adj
    rt = hw.roofline(adj, out["hbm_bytes"], 0.0)
    out["latency"] = rt.t_serial
    # energy = the MARGINAL arithmetic energy of the variant (MXU MACs at
    # their dtype rate + the rank-k lookup-table traffic).  Input/output
    # streaming bytes are identical across variants of one accelerator
    # (board-level cost in the paper's terms) and would flatten the
    # objective to a ~0.2% spread on the small MCM matmuls.
    lut_bytes = sum(256.0 * 4 * 2 * sp.rank for sp in specs)
    out["energy"] = adj * hw.V5E.e_flop + lut_bytes * hw.V5E.e_hbm_byte
    out["cache_hit"] = cache_hit
    return out


class _Variant:
    """Per-genome bookkeeping inside synthesize_batch."""

    __slots__ = ("index", "circuits", "ranks", "specs", "ikey", "idd")

    def __init__(self, index, circuits, ranks, specs, ikey, idd):
        self.index = index
        self.circuits = circuits
        self.ranks = ranks
        self.specs = specs
        self.ikey = ikey
        self.idd = idd


def _compile_identity(accel, specs) -> Tuple[dict, float]:
    """One deployment compile; returns (cost numbers, wall seconds)."""
    fn, args = accel.build_deploy(specs)
    cost = _compile_cost(fn, args, fast_key=f"accel:{accel.name}")
    return ({"flops": cost["flops"], "hbm_bytes": cost["hbm_bytes"]},
            cost["wall_time"])


def synthesize_batch(
    accel: Accelerator,
    variants: Sequence[Tuple[Sequence[Circuit], Sequence[Optional[int]]]],
    *,
    cache: Optional[dict] = None,
    synth_cache: Optional[SynthCache] = None,
    compile_workers: Optional[int] = None,
    progress: Optional[callable] = None,
) -> List[SynthResult]:
    """Population-scale synthesis: one call for a whole genome batch.

    ``variants`` is a list of decoded ``(circuits, ranks)`` pairs.  The
    batch is deduplicated at two levels before anything compiles —
    exact circuit identity, then the structural ``deploy_signature``
    (first-K-verified per graph family; see the module comment) — and
    the surviving unique compiles run serially or, with
    ``compile_workers > 1`` (default ``REPRO_SYNTH_COMPILE_WORKERS``),
    on a thread pool.  Results scatter back per genome with the same
    values the serial per-genome loop would produce; the genome that
    paid a compile carries its wall time, riders carry 0.0 (the seed
    cache-hit convention).

    ``cache`` keeps the legacy per-context dict contract (full records
    keyed on circuit identity); ``synth_cache`` is the shared/persistent
    compile tier (default: the process-wide ``shared_synth_cache()``).
    """
    from ...kernels.approx_matmul import from_circuit

    scache = synth_cache if synth_cache is not None else _SHARED_CACHE
    workers = COMPILE_WORKERS if compile_workers is None else compile_workers
    mul_idx = accel.mul_slot_indices()
    n = len(variants)
    results: List[Optional[SynthResult]] = [None] * n

    # -- pass 1: decode specs, serve legacy-dict hits, group identities --
    order: List[str] = []                 # unique identity digests, FIFO
    groups: Dict[str, List[_Variant]] = {}
    for t, (circuits, ranks) in enumerate(variants):
        specs = [from_circuit(circuits[i], r)
                 for i, r in zip(mul_idx, ranks)]
        ikey = _identity_signature(accel, specs)
        if cache is not None and ikey in cache:
            out = SynthResult(cache[ikey])
            out["wall_time"] = 0.0
            out["cache_hit"] = True
            results[t] = out
            continue
        idd = _digest("id", ikey)
        v = _Variant(t, list(circuits), list(ranks), specs, ikey, idd)
        if idd not in groups:
            order.append(idd)
            groups[idd] = []
        groups[idd].append(v)

    structural = STRUCTURAL_KEYS
    sigs: Dict[str, Optional[Tuple[str, str]]] = {}  # idd -> (sdd, fam)
    if structural:
        for idd in order:
            sig = _structural_signature(accel, groups[idd][0].specs)
            if sig is None:
                sigs[idd] = None
            else:
                family, classes = sig
                fam = _digest("fam", family)
                sigs[idd] = (_digest("st", (family, classes)), fam)

    # -- pass 2: resolve each unique identity against the cache tiers --
    # compiled[idd] = (cost numbers, wall paid here)
    compiled: Dict[str, Tuple[dict, float]] = {}

    def _needs_compile(idd: str):
        """None if served from a cache tier, else the compile plan
        ('fresh' stores structurally, 'verify' compares against the
        colliding record, 'pinned' stores identity-only)."""
        rec = scache.get_identity(idd)
        if rec is not None:
            compiled[idd] = ({"flops": rec["flops"],
                              "hbm_bytes": rec["hbm_bytes"]}, 0.0)
            return None
        sd = sigs.get(idd) if structural else None
        if sd is None:
            return ("pinned", None, None)
        sdd, fam = sd
        verdict = scache.verdict(fam)
        if verdict is False:
            return ("pinned", None, None)
        srec = scache.get_structural(sdd)
        if srec is None:
            return ("fresh", sdd, fam)
        if verdict == 0:
            scache.store_alias({"k": idd, "s": sdd, "fam": fam,
                                "flops": srec["flops"],
                                "hbm_bytes": srec["hbm_bytes"]})
            compiled[idd] = ({"flops": srec["flops"],
                              "hbm_bytes": srec["hbm_bytes"]}, 0.0)
            return None
        return ("verify", sdd, fam)

    def _run_compile(idd: str, plan) -> None:
        kind, sdd, fam = plan
        specs = groups[idd][0].specs
        faults.hit("synth.compile", kind=kind, identity=idd[:12])
        with obs.span("synth.compile", kind=kind, identity=idd[:12]):
            cost, wall = _compile_identity(accel, specs)
        cs = getattr(scache, "compile_seconds", None)
        if cs is not None:
            cs.observe(wall)
        if kind == "verify":
            srec = scache.get_structural(sdd)
            same = (srec is not None
                    and cost["flops"] == srec["flops"]
                    and cost["hbm_bytes"] == srec["hbm_bytes"])
            if srec is None:
                pass          # record vanished (pin race): treat as fresh
            elif same:
                scache.verdict_pass(fam)
            else:
                scache.verdict_pin(fam)
            scache.store({"k": idd, "s": sdd if srec is None or same
                          else None,
                          "fam": fam, **cost}, verify=srec is not None)
        else:
            scache.store({"k": idd,
                          "s": sdd if kind == "fresh" else None,
                          "fam": fam, **cost})
        compiled[idd] = (cost, wall)

    # Structural dedup WITHIN the batch needs the first compile of a
    # structure to land before its siblings resolve, so resolution runs
    # in waves: every identity that must compile under the current cache
    # state compiles (possibly in parallel), then the remainder re-
    # resolves against the now-warmer cache.
    batch_span = (
        obs.start_span("synth.batch", n=n, unique=len(order))
        if order else None
    )
    n_waves = n_compiled = 0
    pending = list(order)
    while pending:
        plans = []
        deferred = []
        seen_struct: set = set()
        verify_used: Dict[str, int] = {}
        for idd in pending:
            plan = _needs_compile(idd)
            if plan is None:
                continue
            kind, sdd, fam = plan
            if kind == "fresh" and sdd in seen_struct:
                deferred.append(idd)     # a sibling compiles it this wave
                continue
            if kind == "verify":
                # spend at most the family's REMAINING countdown on
                # verification this wave; the rest re-resolves next wave
                # (and serves structurally once the family is verified)
                used = verify_used.get(fam, 0)
                verdict = scache.verdict(fam)
                if verdict is False or used >= verdict:
                    deferred.append(idd)
                    continue
                verify_used[fam] = used + 1
            if sdd is not None:
                seen_struct.add(sdd)
            plans.append((idd, plan))
        n_waves += 1
        n_compiled += len(plans)
        if plans:
            if workers > 1 and len(plans) > 1:
                from concurrent.futures import ThreadPoolExecutor

                with ThreadPoolExecutor(workers) as pool:
                    list(pool.map(lambda p: _run_compile(*p), plans))
            else:
                for p in plans:
                    _run_compile(*p)
        if not deferred:
            break
        pending = deferred
    if batch_span is not None:
        batch_span.end(waves=n_waves, compiled=n_compiled)

    # -- pass 3: assemble + scatter ------------------------------------
    done = 0
    total = sum(len(g) for g in groups.values())
    for idd in order:
        cost, wall = compiled[idd]
        for j, v in enumerate(groups[idd]):
            out = _finish_record(
                accel, v.circuits, v.ranks, v.specs, cost,
                wall if j == 0 else 0.0,
                cache_hit=(wall == 0.0 or j > 0),
            )
            if cache is not None and v.ikey not in cache:
                cache[v.ikey] = dict(out)
            results[v.index] = out
            done += 1
            if progress is not None:
                progress(done, total)
    return results


def synthesize_variant(
    accel: Accelerator,
    circuits: Sequence[Circuit],
    ranks: Sequence[Optional[int]],
    *,
    cache: Optional[dict] = None,
    synth_cache: Optional[SynthCache] = None,
) -> SynthResult:
    """Ground-truth hardware labels for one variant (XLA compile of its
    deployment).  Cost is shape-determined, so compiles are reused via
    ``cache`` (exact circuit identity, the seed contract) and the shared
    structural ``synth_cache`` (see ``synthesize_batch``).

    The compute term is dtype-adjusted (the CPU compile runs everything
    in f32; the v5e MXU runs int4/int8/bf16 at different rates)."""
    return synthesize_batch(
        accel, [(circuits, ranks)], cache=cache, synth_cache=synth_cache,
    )[0]


def circuit_features_synth(
    c: Circuit, *, rank: Optional[int] = None, m: int = 256, n: int = 128
) -> np.ndarray:
    """Per-AC synthesis features — XLA-compile a canonical (m,256)@(256,n)
    deployment of this single circuit (Vivado-on-AC analogue, pipeline
    B/E).  Returns [flops, log10 bytes, latency, energy, rank, wall_time].
    Adders deploy as an elementwise segmented add (cost-flat by design)."""
    import jax.numpy as jnp

    from ...kernels.approx_matmul import approx_matmul, from_circuit

    if c.kind == "add16":
        # elementwise behavioral map: fixed small cost; use error stats row
        return np.array([256.0 * n, np.log10(256.0 * n * 8), 0.0, 0.0, 0.0, 0.0])
    spec = from_circuit(c, rank)
    rng = np.random.default_rng(0)
    lo, hi = (-128, 128) if c.signed else (0, 256)
    x = jnp.asarray(rng.integers(lo, hi, (m, 256)))
    w = jnp.asarray(rng.integers(lo, hi, (256, n)))

    def fn(x, w):
        return approx_matmul(x, w, spec)

    cost = _compile_cost(fn, (x, w), fast_key=f"circuit:{c.kind}")
    # dtype-aware adjustment (see synthesize_variant)
    adj = 2.0 * m * 256 * n * c.deploy_cost_factor()
    rt = hw.roofline(adj, cost["hbm_bytes"], 0.0)
    cost["flops"] = adj
    cost["latency"] = rt.t_serial
    cost["energy"] = adj * hw.V5E.e_flop         + 256.0 * 4 * 2 * c.deploy_rank * hw.V5E.e_hbm_byte
    return np.array(
        [
            cost["flops"],
            np.log10(1.0 + cost["hbm_bytes"]),
            cost["latency"],
            cost["energy"],
            float(spec.rank),
            cost["wall_time"],
        ]
    )


def label_variants(
    accel: Accelerator,
    genomes: np.ndarray,
    library: Library,
    *,
    rank_genes: bool = False,
    qor_inputs: Optional[np.ndarray] = None,
    cache: Optional[dict] = None,
    synth_cache: Optional[SynthCache] = None,
    progress: Optional[callable] = None,
) -> Dict[str, np.ndarray]:
    """Ground-truth labels for a genome batch: hardware via BATCHED XLA
    synthesis (``synthesize_batch``: identity + structural dedup across
    the whole batch, shared/persistent compile cache), QoR via BATCHED
    behavioral simulation (one vectorized ``qor_batch`` call instead of
    a sim per genome) — values bit-exact versus the per-genome loop.
    Returns arrays keyed
    {'qor','latency','energy','flops','hbm_bytes','synth_time','sim_time'}.
    ``sim_time`` is the batch's wall clock amortized evenly per genome."""
    genomes = np.atleast_2d(genomes)
    n = len(genomes)
    if qor_inputs is None:
        qor_inputs = accel.sample_inputs(4, seed=DEFAULT_QOR_SEED)
    out = {k: np.zeros(n) for k in LABEL_KEYS}
    t0 = time.perf_counter()
    out["qor"][:] = accel.qor_batch(
        genomes, library, qor_inputs, rank_genes=rank_genes
    )
    out["sim_time"][:] = (time.perf_counter() - t0) / max(n, 1)
    variants = [accel.decode(g, library, rank_genes=rank_genes)
                for g in genomes]
    records = synthesize_batch(
        accel, variants, cache=cache, synth_cache=synth_cache,
        progress=progress,
    )
    for t, sr in enumerate(records):
        out["latency"][t] = sr["latency"]
        out["energy"][t] = sr["energy"]
        out["flops"][t] = sr["flops"]
        out["hbm_bytes"][t] = sr["hbm_bytes"]
        out["synth_time"][t] = sr["wall_time"]
    return out
