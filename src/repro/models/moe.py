"""Mixture-of-experts layer: top-k token-choice routing with GShard-style
capacity dispatch (einsum form — expert-parallel shardable: the experts
dimension lives on the "model" mesh axis, XLA inserts the all-to-alls).

Returns the load-balance auxiliary loss (Switch/GShard form) so the train
loop can add it to the objective.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..dist.sharding import constrain
from .approx_linear import ApproxPolicy, linear
from .common import ParamSpec, act_fn, rms_norm
from .config import ModelConfig

__all__ = ["moe_param_specs", "moe_layer", "dense_mlp_param_specs", "dense_mlp"]


def dense_mlp_param_specs(cfg: ModelConfig) -> Dict[str, ParamSpec]:
    d, f, dt = cfg.d_model, cfg.d_ff, cfg.compute_dtype
    return {
        "norm": ParamSpec((d,), ("norm",), init="zeros"),
        "wi": ParamSpec((d, f), ("embed", "mlp"), dt),
        "wg": ParamSpec((d, f), ("embed", "mlp"), dt),
        "wo": ParamSpec((f, d), ("mlp", "embed"), dt),
    }


def dense_mlp(
    p: Dict[str, jnp.ndarray],
    x: jnp.ndarray,
    cfg: ModelConfig,
    *,
    policy: Optional[ApproxPolicy] = None,
) -> jnp.ndarray:
    h = rms_norm(x, p["norm"], cfg.rms_eps)
    up = linear(h, p["wi"], "ffn_in", policy)
    gate = act_fn(cfg.mlp_act)(linear(h, p["wg"], "ffn_in", policy))
    up = constrain(up * gate, ("batch", "seq", "act_mlp"))
    return linear(up, p["wo"], "ffn_out", policy)


def moe_param_specs(cfg: ModelConfig) -> Dict[str, ParamSpec]:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.padded_experts
    dt = cfg.compute_dtype  # the experts are read cast to bfloat16
    return {
        "norm": ParamSpec((d,), ("norm",), init="zeros"),
        "router": ParamSpec((d, e), ("embed", None)),
        "wi": ParamSpec((e, d, f), ("experts", "embed", "expert_mlp"), dt),
        "wg": ParamSpec((e, d, f), ("experts", "embed", "expert_mlp"), dt),
        "wo": ParamSpec((e, f, d), ("experts", "expert_mlp", "embed"), dt),
    }


MOE_GROUP = 4096  # max tokens per routing group; see moe_layer docstring


def set_moe_group(n: int) -> None:
    """Perf knob (§Perf): GShard dispatch capacity scales with the
    routing-group length, so the (b, s, e, cap) one-hots grow
    QUADRATICALLY with sequence length if the whole sequence is one
    group.  Grouping bounds cap at group*k/e*cf regardless of s."""
    global MOE_GROUP
    MOE_GROUP = int(n)


def moe_layer(
    p: Dict[str, jnp.ndarray],
    x: jnp.ndarray,               # (b, s, d)
    cfg: ModelConfig,
    *,
    policy: Optional[ApproxPolicy] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (out, aux_loss)."""
    b0, s0, d = x.shape
    if MOE_GROUP and s0 > MOE_GROUP and s0 % MOE_GROUP == 0:
        # sequence grouping: route/dispatch in fixed-size groups
        g = s0 // MOE_GROUP
        x = x.reshape(b0 * g, MOE_GROUP, d)
    b, s, d = x.shape
    e = cfg.padded_experts
    k = cfg.n_experts_active
    cap = max(int(s * k / e * cfg.capacity_factor), 1)
    h = rms_norm(x, p["norm"], cfg.rms_eps)

    logits = jnp.einsum(
        "bsd,de->bse", h.astype(jnp.float32), p["router"].astype(jnp.float32)
    )
    # mask padded experts out of routing
    if e > cfg.n_experts:
        pad_mask = jnp.arange(e) >= cfg.n_experts
        logits = jnp.where(pad_mask[None, None, :], -1e30, logits)
    probs = jax.nn.softmax(logits, axis=-1)                   # (b, s, e)
    gate_vals, gate_idx = jax.lax.top_k(probs, k)             # (b, s, k)
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9
    )

    # GShard capacity dispatch: rank of each (token, expert) assignment
    sel = jax.nn.one_hot(gate_idx, e, dtype=jnp.float32)      # (b, s, k, e)
    # priority: k-th choices ranked after all (k-1)-th choices
    flat = sel.transpose(0, 2, 1, 3).reshape(b, k * s, e)
    rank_in_expert = jnp.cumsum(flat, axis=1) - flat          # (b, k*s, e)
    rank = rank_in_expert.reshape(b, k, s, e).transpose(0, 2, 1, 3)
    keep = (rank < cap) * sel                                 # (b, s, k, e)
    # an expert is selected at most once per token, so the k axis can be
    # summed BEFORE the capacity one-hot — avoids a (b,s,k,e,cap) 5-D
    # intermediate (memory hog at scale)
    pos_e = (rank * keep).sum(axis=2).astype(jnp.int32)       # (b, s, e)
    keep_e = keep.sum(axis=2)                                 # (b, s, e)
    gate_e = (gate_vals[..., None] * sel).sum(axis=2)         # (b, s, e)

    cap_oh = jax.nn.one_hot(pos_e, cap, dtype=jnp.float32) * keep_e[..., None]
    dispatch = cap_oh                                          # (b, s, e, cap)
    combine = cap_oh * gate_e[..., None]

    dispatch = constrain(dispatch, ("batch", "seq", "act_experts", None))
    xin = jnp.einsum(
        "bsec,bsd->ebcd", dispatch.astype(x.dtype), h
    )                                                          # (e, b, cap, d)
    xin = constrain(xin, ("act_experts", "batch", None, None))

    def expert_ffn(xin):
        up = jnp.einsum(
            "ebcd,edf->ebcf", xin.astype(jnp.bfloat16), p["wi"].astype(jnp.bfloat16)
        )
        gate = act_fn(cfg.mlp_act)(
            jnp.einsum(
                "ebcd,edf->ebcf",
                xin.astype(jnp.bfloat16),
                p["wg"].astype(jnp.bfloat16),
            )
        )
        return jnp.einsum(
            "ebcf,efd->ebcd", up * gate, p["wo"].astype(jnp.bfloat16)
        )

    hout = expert_ffn(xin)                                     # (e, b, cap, d)
    hout = constrain(hout, ("act_experts", "batch", None, None))
    out = jnp.einsum("bsec,ebcd->bsd", combine.astype(x.dtype), hout)

    # Switch-style load-balance aux loss over the real experts
    me = probs[..., : cfg.n_experts].mean(axis=(0, 1))
    ce = (
        sel[..., : cfg.n_experts].sum(axis=2).mean(axis=(0, 1))
        * cfg.n_experts / k
    )
    aux = cfg.n_experts * jnp.sum(me * ce)
    if (b, s) != (b0, s0):
        out = out.reshape(b0, s0, d)
    return out, aux.astype(jnp.float32)
