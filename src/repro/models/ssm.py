"""Mamba-1 selective-SSM layer, TPU-adapted.

The CUDA selective-scan kernel fuses the (B, S, d_inner, N) state update
in SRAM.  The TPU-native rethink (DESIGN.md §2): a *chunked* scan —
``lax.associative_scan`` (parallel prefix, stable (a, b) combine) inside
fixed-size chunks that fit VMEM-scale working sets, with a sequential
``lax.scan`` carrying the (B, d_inner, N) state across chunks.  Decode is
the O(1) single-step recurrence with a (conv, ssm) cache.

Parameterization follows Mamba-1 (falcon-mamba): in_proj -> (x, z),
depthwise causal conv (k=4), x_proj -> (dt, B, C), dt via softplus,
A = -exp(A_log), y = C.h + D*x, out = out_proj(y * silu(z)).
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..dist.sharding import constrain
from .approx_linear import ApproxPolicy, linear
from .common import ParamSpec, rms_norm
from .config import ModelConfig

__all__ = ["mamba_param_specs", "mamba_layer", "mamba_cache_spec",
           "set_scan_dtype"]

# §Perf knob: dtype of the (b, L, d_inner, N) selective-scan streams.
# f32 is the reference; bf16 halves the dominant SSM HBM traffic at a
# bounded precision cost (the cross-chunk carry stays f32).
SCAN_DTYPE = "float32"


def set_scan_dtype(dt: str) -> None:
    global SCAN_DTYPE
    SCAN_DTYPE = dt


def mamba_param_specs(cfg: ModelConfig) -> Dict[str, ParamSpec]:
    d, di = cfg.d_model, cfg.d_inner
    n, dtr, ck = cfg.ssm_state, cfg.resolved_dt_rank, cfg.ssm_conv
    dt = cfg.compute_dtype  # the projections are read only through ``linear``
    return {
        "norm": ParamSpec((d,), ("norm",), init="zeros"),
        "in_proj": ParamSpec((d, 2 * di), ("embed", "mlp"), dt),
        "conv_w": ParamSpec((ck, di), ("conv", "mlp"), scale=0.1),
        "conv_b": ParamSpec((di,), ("mlp",), init="zeros"),
        "x_proj": ParamSpec((di, dtr + 2 * n), ("mlp", None), dt),
        "dt_proj": ParamSpec((dtr, di), ("dt", "mlp"), dt),
        "dt_bias": ParamSpec((di,), ("mlp",), init="ones", scale=1.0),
        "A_log": ParamSpec((di, n), ("mlp", "state"), init="ones"),
        "D": ParamSpec((di,), ("mlp",), init="ones"),
        "out_proj": ParamSpec((di, d), ("mlp", "embed"), dt),
    }


def _causal_conv(x: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Depthwise causal conv via shifted adds (kernel k is tiny).
    x: (b, s, di), w: (k, di)."""
    k = w.shape[0]
    out = x * w[-1]
    for i in range(1, k):
        shifted = jnp.pad(x, ((0, 0), (i, 0), (0, 0)))[:, : x.shape[1]]
        out = out + shifted * w[-1 - i]
    return out + b


def _scan_combine(left, right):
    a1, b1 = left
    a2, b2 = right
    return a1 * a2, b2 + a2 * b1


def _selective_scan_chunked(
    xc: jnp.ndarray,     # (b, s, di)  conv'd, silu'd input
    dt: jnp.ndarray,     # (b, s, di)
    A: jnp.ndarray,      # (di, n)  (negative)
    Bc: jnp.ndarray,     # (b, s, n)
    Cc: jnp.ndarray,     # (b, s, n)
    chunk: int,
    h0: Optional[jnp.ndarray] = None,   # (b, di, n)
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (y (b, s, di), h_final (b, di, n))."""
    b, s, di = xc.shape
    n = A.shape[1]
    chunk = min(chunk, s)
    s_orig = s
    if s % chunk:
        # pad with dt=0 steps: a=exp(0)=1, bx=0 — identity state updates,
        # so h_final is still the state at the last valid position
        pad = chunk - s % chunk
        xc = jnp.pad(xc, ((0, 0), (0, pad), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bc = jnp.pad(Bc, ((0, 0), (0, pad), (0, 0)))
        Cc = jnp.pad(Cc, ((0, 0), (0, pad), (0, 0)))
        s = s + pad
    nchunks = s // chunk
    if h0 is None:
        h0 = jnp.zeros((b, di, n), jnp.float32)

    xcs = xc.reshape(b, nchunks, chunk, di).transpose(1, 0, 2, 3)
    dts = dt.reshape(b, nchunks, chunk, di).transpose(1, 0, 2, 3)
    Bs = Bc.reshape(b, nchunks, chunk, n).transpose(1, 0, 2, 3)
    Cs = Cc.reshape(b, nchunks, chunk, n).transpose(1, 0, 2, 3)

    sdt = jnp.dtype(SCAN_DTYPE)

    def chunk_body(h, inp):
        xci, dti, Bi, Ci = inp                      # (b, L, ...)
        dtA = dti[..., None] * A[None, None]        # (b, L, di, n)
        a = jnp.exp(dtA).astype(sdt)
        bx = ((dti * xci)[..., None] * Bi[:, :, None, :]).astype(sdt)
        aa, hh = jax.lax.associative_scan(_scan_combine, (a, bx), axis=1)
        hh = hh.astype(jnp.float32) + aa.astype(jnp.float32) * h[:, None]
        y = jnp.einsum("blin,bln->bli", hh, Ci)     # (b, L, di)
        return hh[:, -1], y

    # remat each chunk: without this, backward saves every chunk's
    # (b, L, d_inner, N) residuals — tens of GB for the 16k-wide configs
    chunk_body = jax.checkpoint(chunk_body)

    h_final, ys = jax.lax.scan(chunk_body, h0, (xcs, dts, Bs, Cs))
    y = ys.transpose(1, 0, 2, 3).reshape(b, s, di)[:, :s_orig]
    return y, h_final


def mamba_layer(
    p: Dict[str, jnp.ndarray],
    x: jnp.ndarray,                     # (b, s, d)
    cfg: ModelConfig,
    *,
    policy: Optional[ApproxPolicy] = None,
    cache: Optional[Dict[str, jnp.ndarray]] = None,
    decode: bool = False,
    scan_chunk: int = 128,
) -> Tuple[jnp.ndarray, Optional[Dict[str, jnp.ndarray]]]:
    """cache: {"conv": (b, k-1, di), "ssm": (b, di, n)}.

    Modes: cache=None -> training; cache + decode=False -> prefill (runs
    the chunked scan and returns the post-prompt state); cache +
    decode=True -> single-step recurrence (s == 1)."""
    b, s, d = x.shape
    di, n = cfg.d_inner, cfg.ssm_state
    dtr = cfg.resolved_dt_rank
    h = rms_norm(x, p["norm"], cfg.rms_eps)
    xz = linear(h, p["in_proj"], "ssm_in", policy)
    x_in, z = jnp.split(xz, 2, axis=-1)
    x_in = constrain(x_in, ("batch", "seq", "act_mlp"))

    new_cache = None
    if not decode:
        xc = _causal_conv(
            x_in.astype(jnp.float32), p["conv_w"].astype(jnp.float32),
            p["conv_b"].astype(jnp.float32),
        )
    else:
        # decode: s == 1; conv over (cached k-1 inputs, current)
        window = jnp.concatenate(
            [cache["conv"], x_in.astype(jnp.float32)], axis=1
        )  # (b, k, di)
        xc = (
            jnp.einsum("bki,ki->bi", window, p["conv_w"].astype(jnp.float32))
            + p["conv_b"]
        )[:, None]
        new_conv = window[:, 1:]
    xc = jax.nn.silu(xc)

    proj = linear(xc.astype(x.dtype), p["x_proj"], "ssm_out", policy)
    dt_raw = proj[..., :dtr]
    Bc = proj[..., dtr : dtr + n].astype(jnp.float32)
    Cc = proj[..., dtr + n :].astype(jnp.float32)
    dt = jax.nn.softplus(
        linear(dt_raw, p["dt_proj"], "ssm_out", policy).astype(jnp.float32)
        + p["dt_bias"]
    )
    A = -jnp.exp(p["A_log"].astype(jnp.float32))

    if not decode:
        h0 = cache["ssm"] if cache is not None else None
        y, h_final = _selective_scan_chunked(xc, dt, A, Bc, Cc, scan_chunk, h0)
        if cache is not None:  # prefill: persist post-prompt state
            k = cfg.ssm_conv
            tail = x_in.astype(jnp.float32)[:, -(k - 1):, :]
            new_cache = {"conv": tail, "ssm": h_final}
    else:
        a = jnp.exp(dt[:, 0, :, None] * A[None])            # (b, di, n)
        bx = (dt[:, 0] * xc[:, 0])[..., None] * Bc[:, 0, None, :]
        hnew = a * cache["ssm"] + bx
        y = jnp.einsum("bin,bn->bi", hnew, Cc[:, 0])[:, None]
        new_cache = {"conv": new_conv, "ssm": hnew}

    y = y + xc * p["D"].astype(jnp.float32)
    y = (y * jax.nn.silu(z.astype(jnp.float32))).astype(x.dtype)
    y = constrain(y, ("batch", "seq", "act_mlp"))
    return linear(y, p["out_proj"], "ssm_out", policy), new_cache


def mamba_cache_spec(cfg: ModelConfig, batch: int) -> Dict[str, ParamSpec]:
    di, n, ck = cfg.d_inner, cfg.ssm_state, cfg.ssm_conv
    return {
        "conv": ParamSpec((batch, ck - 1, di), ("batch", None, "mlp"),
                          dtype="float32", init="zeros"),
        "ssm": ParamSpec((batch, di, n), ("batch", "mlp", "state"),
                         dtype="float32", init="zeros"),
    }
