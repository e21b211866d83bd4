"""The assigned input-shape cells and their abstract input specs.

Four shapes per architecture (40 cells):
    train_4k     seq 4,096   global batch 256   -> train_step
    prefill_32k  seq 32,768  global batch 32    -> prefill_step
    decode_32k   seq 32,768  global batch 128   -> serve_step (1 token,
                                                  KV cache of seq_len)
    long_500k    seq 524,288 global batch 1     -> serve_step; SSM/hybrid
                                                  only (sub-quadratic);
                                                  SKIP for full-attention
                                                  archs per the brief.

``input_specs`` returns weak-type-correct ShapeDtypeStructs with resolved
NamedShardings — no device allocation — plus per-cell sharding-rule
overrides (decode cells shard the KV sequence on "model"; long-context
also on "data").
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..dist.sharding import AxisRules, sharding_for
from ..models.common import abstract_tree, with_dtype
from ..models.config import ModelConfig
from ..models.transformer import cache_specs, param_specs

__all__ = ["ShapeCell", "SHAPES", "cell_rules", "input_specs", "runnable",
           "n_microbatches", "ENC_CONTEXT"]

ENC_CONTEXT = 4096  # encoder context length for enc-dec decode cells


@dataclass(frozen=True)
class ShapeCell:
    name: str
    seq_len: int
    global_batch: int
    kind: str          # "train" | "prefill" | "decode"


SHAPES: Dict[str, ShapeCell] = {
    "train_4k": ShapeCell("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeCell("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeCell("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeCell("long_500k", 524288, 1, "decode"),
}


def runnable(cfg: ModelConfig, shape: str) -> Tuple[bool, str]:
    """(runnable?, reason-if-skip) for one (arch, shape) cell."""
    cell = SHAPES[shape]
    if cell.name == "long_500k" and not cfg.supports_long_context:
        return False, "SKIP(full-attn): 512k dense-KV decode out of scope"
    return True, ""


def cell_rules(cfg: ModelConfig, shape: str, mesh=None) -> AxisRules:
    """Per-cell sharding-rule overrides (see module docstring)."""
    cell = SHAPES[shape]
    n_pods = mesh.shape.get("pod", 1) if mesh is not None else 1
    # kv_seq -> "model" is the global default (dist.sharding); it must
    # match the constraint the model applies internally.  Arch-level
    # overrides (e.g. jamba's cross-pod FSDP) come from the config; perf
    # experiments pass rules_override explicitly on top.
    rules: AxisRules = dict(cfg.sharding_rules)
    # §Perf-validated defaults for archs whose head count cannot shard on
    # the 16-way model axis (gemma 8, granite-moe 24): attention would
    # REPLICATE across TP, so
    #   * prefill: context-parallel queries (seq -> model): 8.6-13.8x
    #   * train:   batch over (pod, data, model): 10.8x on gemma
    # (no-ops for shardable-head archs: the heads rule wins the axis)
    if cfg.n_heads % 16 != 0 and not cfg.is_attention_free:
        if cell.kind == "prefill" and not (cfg.n_experts and n_pods > 1):
            # (exception: on the multi-pod mesh the MoE routing-group
            # reshape crosses seq shards and regresses — §Perf)
            rules.setdefault("seq", "model")
        if cell.kind == "train":
            rules.setdefault("batch", ("pod", "data", "model"))
    return rules


def n_microbatches(cfg: ModelConfig, mesh) -> int:
    """Gradient-accumulation depth for train_4k: enough that a per-device
    microbatch is 1-2 rows (activation memory), shard-aligned to the
    cell's batch sharding (cell_rules)."""
    from ..dist.sharding import DEFAULT_RULES

    rules = {**DEFAULT_RULES, **cell_rules(cfg, "train_4k", mesh)}
    axes = rules.get("batch") or ()
    if isinstance(axes, str):
        axes = (axes,)
    b = SHAPES["train_4k"].global_batch
    batch_shards = 1
    for a in axes:
        n = mesh.shape.get(a, 1)
        if b % (batch_shards * n) == 0:
            batch_shards *= n
    per_dev = b // batch_shards
    rows = 1 if cfg.d_model >= 4096 else 2
    return max(per_dev // rows, 1)


def _tok_sds(shape, mesh, rules, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(
        shape, dtype,
        sharding=sharding_for(("batch",) + (None,) * (len(shape) - 1),
                              shape, mesh, rules),
    )


def _embed_sds(b, s, d, mesh, rules):
    return jax.ShapeDtypeStruct(
        (b, s, d), jnp.bfloat16,
        sharding=sharding_for(("batch", None, None), (b, s, d), mesh, rules),
    )


def input_specs(
    cfg: ModelConfig,
    shape: str,
    mesh,
    *,
    serve_dtype: str = "bfloat16",
    rules_override: Optional[AxisRules] = None,
) -> Dict[str, Any]:
    """Abstract inputs for one cell.

    Returns {"kind", "rules", "batch"| ("caches","tokens","pos"),
    "params" (spec tree), ...} — everything dryrun/launch needs.
    ``rules_override`` lets perf experiments re-shard a cell."""
    cell = SHAPES[shape]
    rules = {**cell_rules(cfg, shape, mesh), **(rules_override or {})}
    d = cfg.d_model
    out: Dict[str, Any] = {"kind": cell.kind, "rules": rules, "cell": cell}

    pspecs = param_specs(cfg)
    # train: master-weight dtype from the config (jamba: bf16 to fit HBM);
    # serving: bf16 weights
    dtype = cfg.param_dtype if cell.kind == "train" else serve_dtype
    pspecs = with_dtype(pspecs, dtype)
    out["param_specs"] = pspecs
    out["params"] = abstract_tree(pspecs, mesh, rules)

    b, s = cell.global_batch, cell.seq_len
    if cell.kind == "train":
        batch: Dict[str, Any] = {}
        if cfg.is_encoder_decoder:
            batch["enc_embeds"] = _embed_sds(b, s, d, mesh, rules)
            batch["tokens"] = _tok_sds((b, s), mesh, rules)
        elif cfg.frontend == "vision":
            batch["embeds"] = _embed_sds(b, cfg.frontend_len, d, mesh, rules)
            batch["tokens"] = _tok_sds((b, s - cfg.frontend_len), mesh, rules)
        else:
            batch["tokens"] = _tok_sds((b, s), mesh, rules)
        batch["labels"] = _tok_sds(batch["tokens"].shape, mesh, rules)
        out["batch"] = batch
        return out

    if cell.kind == "prefill":
        batch = {}
        cache_len = s
        if cfg.is_encoder_decoder:
            # long source (the 32k audio), short decoder prime
            batch["enc_embeds"] = _embed_sds(b, s, d, mesh, rules)
            batch["tokens"] = _tok_sds((b, 128), mesh, rules)
            cache_len = 128
        elif cfg.frontend == "vision":
            batch["embeds"] = _embed_sds(b, cfg.frontend_len, d, mesh, rules)
            batch["tokens"] = _tok_sds((b, s - cfg.frontend_len), mesh, rules)
        else:
            batch["tokens"] = _tok_sds((b, s), mesh, rules)
        out["batch"] = batch
        cspecs = cache_specs(cfg, b, max_len=cache_len, enc_len=s)
        out["caches"] = abstract_tree(cspecs, mesh, rules)
        return out

    # decode
    enc_len = ENC_CONTEXT if cfg.is_encoder_decoder else 0
    cspecs = cache_specs(cfg, b, max_len=s, enc_len=enc_len)
    out["caches"] = abstract_tree(cspecs, mesh, rules)
    out["tokens"] = _tok_sds((b, 1), mesh, rules)
    out["pos"] = jax.ShapeDtypeStruct((), jnp.int32)
    if cfg.is_encoder_decoder:
        out["enc_out"] = _embed_sds(b, enc_len, d, mesh, rules)
    return out
