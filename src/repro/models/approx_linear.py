"""The DSE-selectable approximate projection — the paper's technique as a
first-class feature of the LM stack.

Every heavy projection in the model calls ``linear(x, w, cls, policy)``
with a *projection class* name ("qkv", "attn_out", "ffn_in", "ffn_out",
"expert_in", "expert_out", "ssm_in", "ssm_out", "lm_head").  An
``ApproxPolicy`` (decoded from a DSE genome) maps classes to (circuit,
rank): such projections run as int8-quantized rank-k-corrected MXU
matmuls (kernels/approx_matmul); unmapped classes run exact bf16.

The compiled HLO of an approximated projection contains (1 + rank) MXU
matmuls plus two 256-entry gathers per rank — exactly the cost model the
paper's surrogates learn (DESIGN.md §2).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, Mapping, Optional, Tuple

import jax.numpy as jnp
import numpy as np

__all__ = ["ApproxPolicy", "linear", "PROJ_CLASSES"]

PROJ_CLASSES = (
    "qkv",
    "attn_out",
    "ffn_in",
    "ffn_out",
    "expert_in",
    "expert_out",
    "ssm_in",
    "ssm_out",
    "lm_head",
)


@dataclass(frozen=True)
class ApproxPolicy:
    """class name -> (circuit_name, rank|None).  Specs are resolved once
    at construction (cached SVD factors from the ACL)."""

    assignments: Mapping[str, Tuple[str, Optional[int]]] = field(
        default_factory=dict
    )
    _specs: Dict[str, object] = field(default_factory=dict, compare=False)

    def __post_init__(self):
        from ..core.acl.library import default_library
        from ..kernels.approx_matmul import from_circuit

        lib = default_library()
        for cls, (name, rank) in self.assignments.items():
            c = lib[name]
            assert c.kind == "mul8s", (
                f"LM projections quantize to signed int8; {name} is {c.kind}"
            )
            object.__setattr__(
                self, "_specs", {**self._specs, cls: from_circuit(c, rank)}
            )

    def spec(self, cls: str):
        return self._specs.get(cls)

    @staticmethod
    def exact() -> "ApproxPolicy":
        return ApproxPolicy({})


def _approx_matmul_nd(x: jnp.ndarray, w: jnp.ndarray, spec) -> jnp.ndarray:
    """x (..., k) @ w (k, n) under an ApproxSpec, with dynamic per-tensor
    symmetric int8 quantization."""
    from ..kernels.approx_matmul import quantize_sym

    lead = x.shape[:-1]
    k = x.shape[-1]
    x2 = x.reshape(-1, k)
    qx, sx = quantize_sym(x2)
    qw, sw = quantize_sym(w)
    if spec.trunc_bits:
        # natively-truncating circuit: reduced-width integer operands
        t = spec.trunc_bits
        qx = jnp.sign(qx) * ((jnp.abs(qx) >> t) << t)
        qw = jnp.sign(qw) * ((jnp.abs(qw) >> t) << t)
    xi = qx + 128
    wi = qw + 128
    out = qx.astype(jnp.float32) @ qw.astype(jnp.float32)
    # one 256-entry table per rank: a gathered (k, n, r) stack with the
    # rank as its minor axis is padded to the TPU's 128 lanes (at rank 2
    # on a 4096x14336 projection, 14 GB instead of 0.22 GB)
    u = jnp.asarray(spec.u)
    v = jnp.asarray(spec.v)
    for j in range(spec.rank):
        ux = jnp.take(u[:, j], xi, axis=0)    # (m, k)
        vw = jnp.take(v[:, j], wi, axis=0)    # (k, n)
        out = out + jnp.matmul(ux, vw, preferred_element_type=jnp.float32)
    out = out * (sx * sw)
    return out.reshape(*lead, w.shape[1])


def linear(
    x: jnp.ndarray,
    w: jnp.ndarray,
    cls: str,
    policy: Optional[ApproxPolicy] = None,
    *,
    compute_dtype=jnp.bfloat16,
) -> jnp.ndarray:
    """Projection with optional DSE-assigned approximation."""
    spec = policy.spec(cls) if policy is not None else None
    if spec is None:
        return jnp.einsum(
            "...k,kn->...n", x.astype(compute_dtype), w.astype(compute_dtype)
        )
    return _approx_matmul_nd(x, w, spec).astype(compute_dtype)
