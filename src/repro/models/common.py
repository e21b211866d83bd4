"""Shared model building blocks: parameter specs, RMSNorm, RoPE variants,
activations.

Parameters are plain nested dicts of jnp arrays.  Their shapes/logical
axes are declared once via ``ParamSpec``; ``init_tree`` materializes real
arrays (smoke tests / examples) and ``abstract_tree`` materializes
ShapeDtypeStructs with NamedShardings (dry-run) from the same declaration.

A leaf that every reader casts to the compute type before use (the
projections, embedding and LM head) is declared in ``cfg.compute_dtype``,
so serving stores it once in that type instead of casting it on every
step; leaves read in float32 (norm scales, router, SSM dynamics) keep
float32.  Training maps the whole tree to ``cfg.param_dtype`` with
``with_dtype`` for its master weights.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "ParamSpec",
    "init_tree",
    "abstract_tree",
    "with_dtype",
    "rms_norm",
    "make_rope",
    "apply_rope",
    "act_fn",
]


@dataclass(frozen=True)
class ParamSpec:
    shape: Tuple[int, ...]
    logical: Tuple[Optional[str], ...]
    dtype: str = "float32"
    init: str = "normal"      # normal | zeros | ones | conv
    scale: float = 0.02

    def __post_init__(self):
        assert len(self.shape) == len(self.logical), (self.shape, self.logical)


def _is_spec(x) -> bool:
    return isinstance(x, ParamSpec)


def init_tree(specs, key: jax.Array):
    """Materialize a ParamSpec tree into real arrays (deterministic)."""
    leaves, treedef = jax.tree.flatten(specs, is_leaf=_is_spec)
    keys = jax.random.split(key, len(leaves))
    out = []
    for spec, k in zip(leaves, keys):
        if spec.init == "zeros":
            arr = jnp.zeros(spec.shape, spec.dtype)
        elif spec.init == "ones":
            arr = jnp.ones(spec.shape, spec.dtype)
        else:
            arr = (
                jax.random.normal(k, spec.shape, jnp.float32) * spec.scale
            ).astype(spec.dtype)
        out.append(arr)
    return jax.tree.unflatten(treedef, out)


def with_dtype(specs, dtype: str):
    """The same ParamSpec tree with every leaf declared in ``dtype``."""
    return jax.tree.map(
        lambda s: ParamSpec(s.shape, s.logical, dtype, s.init, s.scale),
        specs,
        is_leaf=_is_spec,
    )


def abstract_tree(specs, mesh, rules=None):
    """ParamSpec tree -> ShapeDtypeStruct tree with resolved shardings."""
    from ..dist.sharding import sharding_for

    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=sharding_for(s.logical, s.shape, mesh, rules)
        ),
        specs,
        is_leaf=_is_spec,
    )


def rms_norm(x: jnp.ndarray, scale: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * (1.0 + scale.astype(jnp.float32))).astype(dtype)


def act_fn(name: str):
    return {"silu": jax.nn.silu, "gelu": jax.nn.gelu, "relu": jax.nn.relu}[name]


def make_rope(head_dim: int, theta: float = 10000.0,
              fraction: float = 1.0) -> np.ndarray:
    """Inverse-frequency vector (rot_dim//2,).  cos/sin are computed on
    the fly from positions (no O(max_len) table — a 512k-position table
    would be a 268 MB baked constant).

    fraction < 1 rotates only the first ``fraction*head_dim`` dims
    (ChatGLM-style 2d/partial RoPE)."""
    rot = int(head_dim * fraction)
    rot -= rot % 2
    return (1.0 / (theta ** (np.arange(0, rot, 2) / rot))).astype(np.float32)


def apply_rope(
    x: jnp.ndarray,                            # (b, h, s, d)
    inv_freq: jnp.ndarray,                     # (rot//2,)
    positions: Optional[jnp.ndarray] = None,   # (s,) or (b, s); None=arange
) -> jnp.ndarray:
    b, h, s, d = x.shape
    rot2 = inv_freq.shape[0]
    if positions is None:
        positions = jnp.arange(s)
    ang = positions[..., :, None].astype(jnp.float32) * inv_freq  # (s|b,s, r2)
    c, sn = jnp.cos(ang), jnp.sin(ang)
    if c.ndim == 2:
        c, sn = c[None, None], sn[None, None]
    else:
        c, sn = c[:, None], sn[:, None]
    xr = x[..., : 2 * rot2].astype(jnp.float32).reshape(b, h, s, rot2, 2)
    x1, x2 = xr[..., 0], xr[..., 1]
    rotated = jnp.stack([x1 * c - x2 * sn, x1 * sn + x2 * c], axis=-1)
    rotated = rotated.reshape(b, h, s, 2 * rot2).astype(x.dtype)
    return jnp.concatenate([rotated, x[..., 2 * rot2 :]], axis=-1)
