"""Closed-loop LM serving of a granite-4.0-h hybrid (Mamba-2 and attention
layers, each with a routed MoE and a shared expert): ``lm_closed``'s
callers, engine and window, with the hybrid's program configuration,
weights, reference, cost model and routing counter.

Traffic parameters as ``lm_closed``.  The configuration file names its
held experts (``held_experts``, [first, stop) of ``routed_experts``) and
its ``limits``.  On a CPU, which only a rehearsal runs on (the
benchmark's command refuses it), the driver takes the sizes under the
``rehearsal`` keys of the configuration and of the traffic mix, so that
the rehearsal of every cell holds this one too.

The check re-runs ``check_requests`` finished requests through the plain
reference (``refs.granite_hybrid``); ``control`` reads two controls on
the same requests: the reference with float8 weights (``lm_logit_gap``)
and the reference with its Mamba-2 state held in bfloat16
(``lm_logit_gap.bf16_state``).
"""

from __future__ import annotations

import dataclasses
import gc

import numpy as np

from .. import hybridcost
from ..refs import granite_hybrid as ref
from ..stats import sample
from . import lm_closed

# configuration key -> the program's ModelConfig field, taken as they are
_PROGRAM_KEYS = {
    "hidden_size": "d_model", "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads", "intermediate_size": "d_ff",
    "vocab_size": "vocab_size", "num_hidden_layers": "n_layers",
    "rms_norm_eps": "rms_eps", "attention_multiplier": "attn_scale",
    "embedding_multiplier": "embedding_multiplier",
    "residual_multiplier": "residual_multiplier",
    "logits_scaling": "logits_scaling", "routed_experts": "n_experts",
    "num_experts_per_tok": "n_experts_active",
    "shared_intermediate_size": "shared_ff", "mamba_d_state": "ssm_state",
    "mamba_d_conv": "ssm_conv", "mamba_expand": "ssm_expand",
    "mamba_n_heads": "ssm_heads", "mamba_d_head": "ssm_head_dim",
    "mamba_n_groups": "ssm_groups", "mamba_chunk_size": "ssm_chunk",
}
# what the program computes and the configuration has to state
_FIXED = {"hidden_act": "silu", "position_embedding_type": "nope",
          "tie_word_embeddings": True, "mamba_conv_bias": True,
          "mamba_proj_bias": False, "attention_bias": False,
          "normalization_function": "rmsnorm"}


def program_config(c: dict):
    """The program's ModelConfig for configuration file ``c``: the named
    model with every size the file states, its held experts and its one
    period of layers."""
    from repro.configs import get_config

    wrong = {k: c.get(k) for k, v in _FIXED.items() if c.get(k) != v}
    if wrong:
        raise ValueError(f"the program computes {_FIXED}; the configuration "
                         f"states {wrong}")
    kinds = c["layer_types"]
    if kinds.count("attention") != 1:
        raise ValueError("the program's hybrid period holds one attention layer")
    cfg = dataclasses.replace(
        get_config(c["model"]),
        **{f: c[k] for k, f in _PROGRAM_KEYS.items()},
        head_dim=c["hidden_size"] // c["num_attention_heads"],
        held_experts=tuple(c["held_experts"]),
        ssm_state_dtype=c["precision"]["ssm_state"],
        attn_period=len(kinds), attn_offset=kinds.index("attention"))
    got = ["attention" if k.mixer == "attn" else "mamba" for k in cfg.block_pattern]
    if got != kinds or any(k.mlp != "moe" for k in cfg.block_pattern):
        raise ValueError(f"the program's layer pattern {got} is not {kinds}")
    return cfg


def program_dtypes(c: dict, cfg) -> dict:
    """{path: dtype} of the program's parameter tree, which has to hold
    the configuration's weights leaf for leaf (``lm_closed.program_dtypes``
    against this reference)."""
    import jax

    from repro.models.common import ParamSpec
    from repro.models.transformer import param_specs

    leaves = jax.tree_util.tree_flatten_with_path(
        param_specs(cfg), is_leaf=lambda x: isinstance(x, ParamSpec))[0]
    got = {"/".join(str(k.key) for k in path): spec for path, spec in leaves}
    want = ref.weight_shapes(c)
    diff = sorted(p for p in set(got) | set(want)
                  if p not in got or p not in want
                  or tuple(got[p].shape) != tuple(want[p][0]))
    if diff:
        raise ValueError("the program's parameter tree differs from the "
                         f"configuration's weights at {diff}")
    return {p: spec.dtype for p, spec in got.items()}


class Driver(lm_closed.Driver):
    def __init__(self, run):
        super().__init__(run)
        import jax

        if jax.devices()[0].platform == "cpu":
            self.c = {**self.c, **self.c.get("rehearsal", {})}
            self.t = {**self.t, **self.t.get("rehearsal", {})}

    def setup(self):
        from repro.accel.lm import LMAccelerator
        from repro.core.acl.library import default_library
        from repro.serving import FrontCatalog, ServingEngine

        cfg = program_config(self.c)
        self.accel = LMAccelerator(cfg, use_reduced=False)
        self.dtypes = program_dtypes(self.c, cfg)
        self.accel._params = self.program_weights(self.run.seed)
        lib = default_library()
        genome = self.accel.exact_genome(lib)
        catalog = FrontCatalog.from_front(self.accel.name, [genome.tolist()],
                                          [[-100.0, 1.0]])
        self.engine = ServingEngine(self.accel, lib, catalog=catalog,
                                    **self.t.get("engine", {}))
        # one group of the window's shape: compiles prefill and decode
        warm = [self.engine.submit(self.prompt(i, 1 << 30), tier=self.t["tier"],
                                   gen=self.t["generated_tokens"])
                for i in range(self.t["clients"])]
        for f in warm:
            f.result(timeout=900)
        self.base = self.engine.stats()
        self.base_routing = self.engine.backend.stats()

    def program_weights(self, seed: int):
        import jax

        return jax.block_until_ready(ref.make_weights(self.c, seed, self.dtypes))

    def finish(self):
        super().finish()
        self.routing = self.engine.backend.stats()

    def layer_inputs(self) -> dict:
        out = super().layer_inputs()
        out.update(
            decode_cost=hybridcost.decode_step, prefill_cost=hybridcost.prefill,
            held_picks=self.routing["held_picks"] - self.base_routing["held_picks"],
            decode_steps=(self.routing["decode_steps"]
                          - self.base_routing["decode_steps"]),
            moe_layers=self.routing["moe_layers"],
            held_experts=self.routing["held_experts"])
        return out

    # -- the check and its controls -----------------------------------------
    def _widest(self, quant, weights=None) -> float:
        if weights is None:
            weights = ref.make_weights(self.c, self.run.seed)
        widest = 0.0
        for key in self.picked:
            gaps = request_gaps(weights, self.c, self.prompt(*key),
                                self.served[key], quant=quant)
            widest = max(widest, float(gaps.max()))
        return widest

    def control(self) -> dict:
        weights = ref.make_weights(self.c, self.run.seed)
        return {"lm_logit_gap": self._widest("fp8", weights),
                "lm_logit_gap.bf16_state": self._widest("bf16_state", weights)}

    def calibrate(self, seeds) -> list:
        """[(seed, program's reading, float8 control's, bfloat16-state
        control's)]: each seed's weights and first prompts through the
        set-up engine, one group, in this one process."""
        out = []
        for seed in seeds:
            self.run.seed = int(seed)
            self.accel._params = None
            gc.collect()
            self.accel._params = self.program_weights(seed)
            keys = [(i, 0) for i in range(self.t["clients"])]
            futs = [self.engine.submit(self.prompt(*k), tier=self.t["tier"],
                                       gen=self.t["generated_tokens"]) for k in keys]
            for k, f in zip(keys, futs):
                self.served[k] = np.asarray(f.result(timeout=900)["tokens"])
            rng = np.random.default_rng([seed, 7])
            self.picked = [keys[j] for j in
                           sample(rng, len(keys), self.t["check_requests"])]
            # the program's weights go before the reference's are made
            self.accel._params = None
            gc.collect()
            weights = ref.make_weights(self.c, seed)
            out.append((int(seed), self._widest(None, weights),
                        self._widest("fp8", weights),
                        self._widest("bf16_state", weights)))
            weights = None
            self.run.log(f"calibrate seed {seed}: program {out[-1][1]!r}, "
                         f"float8 control {out[-1][2]!r}, bfloat16-state "
                         f"control {out[-1][3]!r}")
        return out


def request_gaps(weights, c, prompt, served, *, quant=None):
    """Gaps of one request's served tokens under the reference; with
    ``quant`` the gaps of the tokens the control puts first at the same
    positions."""
    seq = np.concatenate([np.asarray(prompt), np.asarray(served)[:-1]])
    start = len(prompt) - 1
    exact = ref.logits(weights, c, seq, start=start)
    if quant is None:
        return ref.token_gaps(exact, served)
    low = ref.logits(weights, c, seq, start=start, quant=quant)
    return ref.token_gaps(exact, low.argmax(axis=-1))
