"""Model-execution backends for the serving engine.

A backend executes ONE batch group — requests that resolved to the same
operating point (genome) and compatible input shapes — in a single
batched call:

  * ``SimBackend`` — table-driven accelerators (gaussian3x3, the HEVC
    DCTs, staged pipelines): one ``simulate_batch(..., per_genome_
    inputs=True)`` over the stacked request inputs, which dispatches to
    the fused ``(genomes, inputs)`` XLA engine where a plan exists
    (repro.accel.fused), plus the exact reference batch — each request
    gets its output and its *measured* QoR (PSNR vs exact on ITS
    inputs, bit-identical for identical genome+inputs, which is what
    the hot-swap pinning drill asserts).
  * ``LMBackend``  — ``lm:<arch>`` accelerators: the genome decodes to
    an ``ApproxPolicy`` and the group runs batched greedy decoding
    through the jitted prefill/decode pair (``repro.train.serve.
    Generator`` — the resurrected seed serving steps), with generators
    cached per genome so steady-state requests never re-jit.

Backends are pure executors: selection, batching and hot-swap live in
``engine.py``.
"""

from __future__ import annotations

import threading
from collections import OrderedDict
from typing import Dict, List, Sequence, Tuple

import numpy as np

from ..core import qor as qor_mod
from .catalog import OperatingPoint

__all__ = ["SimBackend", "LMBackend", "make_backend"]


class SimBackend:
    """Batched behavioral execution + per-request measured QoR.

    A request's ``inputs`` is a BATCH of accelerator inputs — the shape
    ``accel.sample_inputs(n)`` returns (``(n, H, W)`` images for
    gaussian3x3 / the DCTs, ``(n, 4)`` operand rows for the MCM blocks)
    — so the stacked group forms the ``(G, n, ...)`` per-genome stack
    ``simulate_batch(..., per_genome_inputs=True)`` consumes.  Inputs
    arriving over the wire (JSON) are coerced to the accelerator's
    native dtype: integral floats cast silently, non-integral values
    for an integer-operand accelerator are a ``ValueError`` (HTTP
    400)."""

    kind = "sim"

    def __init__(self, accel, library, *, rank_genes: bool = False):
        self.accel = accel
        self.library = library
        self.rank_genes = bool(rank_genes)
        self._in_dtype = None

    def group_key(self, req) -> Tuple:
        return (tuple(np.shape(req.inputs)),)

    def _coerce(self, inputs) -> np.ndarray:
        arr = np.asarray(inputs)
        if self._in_dtype is None:
            self._in_dtype = np.asarray(
                self.accel.sample_inputs(1, 0)).dtype
        dt = self._in_dtype
        if arr.dtype == dt:
            return arr
        if np.issubdtype(dt, np.integer) and \
                not np.issubdtype(arr.dtype, np.integer):
            if arr.size and (not np.all(np.isfinite(arr))
                             or np.any(np.mod(arr, 1) != 0)):
                raise ValueError(
                    f"{self.accel.name} takes integer operands; got "
                    f"non-integral inputs (dtype {arr.dtype})")
        return arr.astype(dt)

    def run(self, point: OperatingPoint, reqs: Sequence) -> List[Dict]:
        X = np.stack([self._coerce(r.inputs) for r in reqs])
        G = np.tile(point.genome_array()[None, :], (len(reqs), 1))
        outs = self.accel.simulate_batch(
            G, self.library, X,
            rank_genes=self.rank_genes, per_genome_inputs=True,
        )
        refs = self.accel.exact_output_batch(X, per_genome_inputs=True)
        results = []
        for i, r in enumerate(reqs):
            res = {"qor": qor_mod.psnr(refs[i], outs[i])}
            if r.return_outputs:
                res["outputs"] = np.asarray(outs[i]).tolist()
            results.append(res)
        return results


class LMBackend:
    """Continuous-batching greedy decode through an ApproxPolicy'd
    model: one jitted prefill + per-token decode per batch group."""

    kind = "lm"

    def __init__(self, accel, library, *, rank_genes: bool = False,
                 max_generators: int = 8):
        self.accel = accel
        self.library = library
        self.rank_genes = bool(rank_genes)
        self.max_generators = int(max_generators)
        self._gens: "OrderedDict[bytes, object]" = OrderedDict()
        self._lock = threading.Lock()
        self._routing = {"held_picks": 0, "decode_steps": 0}

    def group_key(self, req) -> Tuple:
        return (tuple(np.shape(req.inputs)), int(req.gen or 0))

    def stats(self) -> Dict:
        """Routing counters over every group run (dropless MoE configs):
        ``held_picks``, the (token, expert) picks of decode steps that
        landed on the experts held here, summed over the MoE layers;
        ``decode_steps``, the decode steps of the groups that counted
        them; and the shapes to read them against."""
        cfg = self.accel.cfg
        moe_layers = sum(k.mlp == "moe" for k in cfg.block_pattern) \
            * cfg.n_superblocks
        with self._lock:
            return {**self._routing, "moe_layers": moe_layers,
                    "held_experts": cfg.n_held if cfg.n_experts else 0}

    def _generator(self, point: OperatingPoint):
        from ..train.serve import Generator

        key = point.genome_array().tobytes()
        with self._lock:
            gen = self._gens.get(key)
            if gen is not None:
                self._gens.move_to_end(key)
                return gen
        policy = self.accel.policy_for_genome(
            point.genome_array(), self.library, rank_genes=self.rank_genes
        )
        gen = Generator(self.accel.cfg, policy=policy,
                        attn_chunk=32, scan_chunk=8)
        with self._lock:
            self._gens[key] = gen
            while len(self._gens) > self.max_generators:
                self._gens.popitem(last=False)
        return gen

    def run(self, point: OperatingPoint, reqs: Sequence) -> List[Dict]:
        prompts = np.stack(
            [np.asarray(r.inputs, dtype=np.int32) for r in reqs]
        )
        if prompts.ndim != 2:
            raise ValueError(
                f"LM requests carry 1-D prompt token arrays; got batch "
                f"shape {prompts.shape}"
            )
        n_gen = int(reqs[0].gen or 16)
        gen = self._generator(point)
        params = self.accel._ensure_params()
        tokens, tps = gen.generate(params, prompts, n_gen)
        tokens = np.asarray(tokens)  # one read for the whole group
        with self._lock:
            for k in self._routing:
                self._routing[k] += gen.last_routing[k]
        results = []
        for i, r in enumerate(reqs):
            res = {
                # per-request QoR is the genome's catalog label (logits
                # PSNR of the policy'd model vs exact); a per-request
                # exact forward would double every group's cost
                "qor": float(point.labels.get("qor", float("nan"))),
                "tokens_per_s": tps,
                "n_generated": n_gen,
            }
            if r.return_outputs:
                res["tokens"] = tokens[i].tolist()
            else:
                res["tokens"] = tokens[i, -n_gen:].tolist()
            results.append(res)
        return results


def make_backend(accel, library, *, rank_genes: bool = False):
    """SimBackend for table-driven accelerators, LMBackend for
    ``lm:<arch>`` (anything exposing ``policy_for_genome``)."""
    if hasattr(accel, "policy_for_genome"):
        return LMBackend(accel, library, rank_genes=rank_genes)
    return SimBackend(accel, library, rank_genes=rank_genes)
