"""LMAccelerator — the paper's DSE applied to the transformer stack.

The 'accelerator' is a (reduced-config) language model; the *slots* are
its projection classes (qkv / attn_out / ffn_in / ffn_out / experts / ssm
/ lm_head), each deployable as an int8 rank-k-corrected approximate
matmul (models/approx_linear).  The genome assigns one mul8s circuit per
class — exactly the accelerator-variant semantics of the paper, with

  QoR        = logits-PSNR of the approximate model vs the exact model
               (behavioral simulation at reduced scale),
  hw labels  = XLA-compile of the policy'd forward step -> roofline
               energy/latency (synthesis at reduced scale; relative cost
               transfers to the full config since every class's FLOP
               share is architecture-determined).

This makes run_dse / the surrogates / NSGA-II / the Figs. 5-9 benchmarks
reusable verbatim on LM architectures (DESIGN.md §4).
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..core.acl.library import Circuit
from ..models import ApproxPolicy
from ..models.config import ModelConfig, reduced
from .base import Accelerator, Slot

__all__ = ["LMAccelerator", "proj_classes_for"]


def proj_classes_for(cfg: ModelConfig) -> List[Tuple[str, float]]:
    """[(projection class, relative FLOP share)] for this family."""
    d, ff, hd = cfg.d_model, max(cfg.d_ff, 1), cfg.resolved_head_dim
    qkv = d * hd * (cfg.n_heads + 2 * cfg.n_kv_heads)
    attn_out = d * hd * cfg.n_heads
    head = d * cfg.padded_vocab / max(cfg.n_layers, 1)
    out: List[Tuple[str, float]] = []
    has_attn = any(k.mixer == "attn" for k in cfg.block_pattern)
    if has_attn:
        out += [("qkv", qkv), ("attn_out", attn_out)]
    # the dense MLP and the MoE's shared expert both run through "ffn_*"
    ffn = ff if any(k.mlp == "dense" for k in cfg.block_pattern) else 0
    ffn += cfg.shared_ff
    if ffn:
        out += [("ffn_in", 2.0 * d * ffn), ("ffn_out", d * ffn)]
    if cfg.n_experts:
        act = cfg.n_experts_active
        out += [("expert_in", 2.0 * d * ff * act), ("expert_out", d * ff * act)]
    if any(k.mixer == "mamba" for k in cfg.block_pattern):
        di = cfg.d_inner
        if cfg.ssm_kind == "mamba2":  # in_proj -> z, xBC, dt
            ssm_in = d * (2 * di + 2 * cfg.ssm_groups * cfg.ssm_state
                          + cfg.ssm_heads)
        else:
            ssm_in = 2.0 * d * di
        out += [("ssm_in", ssm_in), ("ssm_out", di * d)]
    out += [("lm_head", head)]
    total = sum(w for _, w in out)
    return [(c, w / total) for c, w in out]


class LMAccelerator(Accelerator):
    def __init__(
        self,
        cfg: ModelConfig,
        *,
        use_reduced: bool = True,
        batch: int = 2,
        seq: int = 32,
        seed: int = 0,
    ):
        self.full_cfg = cfg
        self.cfg = reduced(cfg) if use_reduced else cfg
        self.name = f"lm:{cfg.name}"
        self.classes = proj_classes_for(self.cfg)
        self.slots = [Slot(c, "mul8s", w) for c, w in self.classes]
        self.batch, self.seq, self.seed = batch, seq, seed
        self._params = None
        self._logits_cache: Dict[bytes, np.ndarray] = {}

    # -- lazy shared weights -------------------------------------------------
    def _ensure_params(self):
        if self._params is None:
            import jax

            from ..models.common import init_tree
            from ..models.transformer import param_specs

            self._params = init_tree(
                param_specs(self.cfg), jax.random.PRNGKey(self.seed)
            )
        return self._params

    def sample_inputs(self, n: int, seed: int = 0) -> np.ndarray:
        rng = np.random.default_rng(seed)
        return rng.integers(
            0, self.cfg.vocab_size, size=(n, self.batch, self.seq)
        ).astype(np.int32)

    # -- policy plumbing ------------------------------------------------------
    def _policy(self, circuits: Sequence[Circuit],
                ranks: Optional[Sequence[Optional[int]]] = None) -> ApproxPolicy:
        ranks = ranks or [None] * len(circuits)
        assignments = {}
        for slot, c, r in zip(self.slots, circuits, ranks):
            if not c.is_exact:
                assignments[slot.name] = (c.name, r)
        return ApproxPolicy(assignments)

    def policy_for_genome(
        self,
        genome,
        library=None,
        *,
        rank_genes: bool = False,
    ) -> ApproxPolicy:
        """Decode one front genome to the ``ApproxPolicy`` the serving
        tier (and ``launch.serve --front``) feeds into the jitted
        prefill/decode steps.  This is the bridge from a stored Pareto
        point to a runnable model configuration."""
        if library is None:
            from ..core.acl.library import default_library

            library = default_library()
        genome = np.asarray(genome, dtype=np.int64).reshape(-1)
        width = len(self.slots) + (
            len(self.mul_slot_indices()) if rank_genes else 0
        )
        if len(genome) != width:
            raise ValueError(
                f"genome has {len(genome)} genes; {self.name} expects "
                f"{width} (rank_genes={rank_genes})"
            )
        circuits, ranks = self.decode(genome, library, rank_genes=rank_genes)
        return self._policy(circuits, ranks)

    def _forward(self, policy: Optional[ApproxPolicy], inputs: np.ndarray) -> np.ndarray:
        import jax.numpy as jnp

        from ..models.transformer import forward

        params = self._ensure_params()
        outs = []
        for tok in inputs:
            kwargs = {}
            if self.cfg.is_encoder_decoder:
                rng = np.random.default_rng(self.seed)
                kwargs["enc_embeds"] = jnp.asarray(
                    rng.standard_normal((self.batch, 16, self.cfg.d_model))
                    .astype(np.float32) * 0.1)
            logits, _, _ = forward(
                params, self.cfg, jnp.asarray(tok), policy=policy,
                remat=False, attn_chunk=self.seq, scan_chunk=8, **kwargs,
            )
            outs.append(np.asarray(logits.astype(jnp.float32)))
        return np.stack(outs)

    # -- Accelerator interface ------------------------------------------------
    def simulate(self, circuits: Sequence[Circuit], inputs: np.ndarray) -> np.ndarray:
        return self._forward(self._policy(circuits), inputs)

    def exact_output(self, inputs: np.ndarray) -> np.ndarray:
        key = inputs.tobytes()
        if key not in self._logits_cache:
            self._logits_cache[key] = self._forward(None, inputs)
        return self._logits_cache[key]

    def qor_batch(
        self,
        genomes: np.ndarray,
        library,
        inputs: np.ndarray,
        *,
        rank_genes: bool = False,
        peak: float | None = None,
    ) -> np.ndarray:
        """Population path for the LM: the exact forward runs once for
        the whole batch (cached logits), distinct policies run once each
        (NSGA-II survivor sets repeat genomes heavily), and the per-
        genome logits are scored immediately instead of stacking the
        whole population's logits in memory."""
        from ..core import qor as qor_mod

        genomes = np.atleast_2d(np.asarray(genomes))
        ref = self.exact_output(inputs)
        uniq, inverse = np.unique(genomes, axis=0, return_inverse=True)
        vals = np.empty(len(uniq), dtype=np.float64)
        for i, g in enumerate(uniq):
            circuits, _ = self.decode(g, library, rank_genes=rank_genes)
            vals[i] = qor_mod.psnr(ref, self.simulate(circuits, inputs), peak)
        return vals[inverse]

    def build_deploy(self, specs: Sequence, inputs: Optional[np.ndarray] = None):
        """Deployment = the policy'd forward step of the reduced config;
        the compile's cost_analysis carries the (1 + rank)-matmul cost
        model for every approximated class."""
        import jax.numpy as jnp

        from ..models.transformer import forward

        policy = ApproxPolicy({
            slot.name: (spec.name, spec.rank)
            for slot, spec in zip(self.slots, specs)
            if not spec.is_exact
        })
        params = self._ensure_params()
        tok = jnp.asarray(self.sample_inputs(1, seed=1)[0])

        def fn(params, tok):
            kwargs = {}
            if self.cfg.is_encoder_decoder:
                kwargs["enc_embeds"] = jnp.zeros(
                    (self.batch, 16, self.cfg.d_model), jnp.bfloat16)
            logits, _, _ = forward(params, self.cfg, tok, policy=policy,
                                   remat=False, attn_chunk=self.seq,
                                   scan_chunk=8, **kwargs)
            return logits

        return fn, (params, tok)

    def mul_slot_constants(self):
        return [None] * len(self.slots)

    def adjusted_compute(self, circuits, ranks) -> float:
        """Dtype-aware MXU cost of one forward step of the reduced model:
        per projection class, (2 * N_class * tokens) MACs scaled by the
        circuit's deployment cost factor (unapproximated work — attention
        cores, norms — rides along at bf16 cost 1.0)."""
        from ..core import hw

        tokens = self.batch * self.seq
        n_active = self.cfg.active_param_count()
        total = 0.0
        for (cls, share), c, r in zip(self.classes, circuits, ranks):
            base = hw.V5E.dtype_cost_factor(c.deploy_width)
            rank = c.deploy_rank if r is None else (
                0 if c.native_width is not None else int(r)
            )
            total += 2.0 * n_active * share * tokens * (base + rank)
        return total


# The LM is not a LUT workload: its qor path is a deduped bf16 forward
# per distinct genome, not a table-driven population sim.  Opt it out of
# the fused population engine explicitly (counted as a pin-by-design).
from . import fused as _fused  # noqa: E402

_fused.register_unfused(LMAccelerator)
