"""Plain reference of a dense llama-style decoder (granite-8b), and the
weights the benchmark serves it with.

The forward pass is straightforward ``jax.numpy`` in float32 at HIGHEST
matmul precision: RMSNorm with a (1 + scale) gain, interleaved-pair RoPE,
grouped-query causal attention, a SwiGLU MLP and an untied LM head.  It
imports nothing of the program.  ``quant="fp8"`` is the control: every
weight matrix rounded to float8 (e4m3, one scale per output column) and
the activations carried in bfloat16, the step below the bfloat16 the
configuration computes in.

The weights are made here from the seed, on the device, in one jitted
call: bfloat16 values, the precision the configuration states, stored in
the type each leaf of the program's parameter tree declares (a wider
type holds them exactly).  The program is handed them, and the
reference makes them again from the same seed, in bfloat16, once the
program's state is gone, and computes over them in float32.
"""

from __future__ import annotations

import functools

import numpy as np

INIT_SCALE = 0.02


def dims(c: dict) -> dict:
    d, h, kv = c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"]
    hd = c.get("head_dim") or d // h
    v = -(-c["vocab_size"] // 256) * 256  # the program pads rows to 256
    return dict(d=d, h=h, kv=kv, hd=hd, ff=c["intermediate_size"], v=v,
                L=c["num_hidden_layers"], eps=c["rms_norm_eps"],
                theta=c["rope_theta"], vocab=c["vocab_size"])


def weight_shapes(c: dict) -> dict:
    """The served parameter tree as {path: (shape, init)}; layers are
    stacked on a leading axis."""
    m = dims(c)
    d, hd, L = m["d"], m["hd"], m["L"]
    blk = "blocks/layer0/"
    return {
        "embed": ((m["v"], d), "normal"),
        blk + "attn/norm": ((L, d), "zeros"),
        blk + "attn/wq": ((L, d, m["h"] * hd), "normal"),
        blk + "attn/wk": ((L, d, m["kv"] * hd), "normal"),
        blk + "attn/wv": ((L, d, m["kv"] * hd), "normal"),
        blk + "attn/wo": ((L, m["h"] * hd, d), "normal"),
        blk + "mlp/norm": ((L, d), "zeros"),
        blk + "mlp/wi": ((L, d, m["ff"]), "normal"),
        blk + "mlp/wg": ((L, d, m["ff"]), "normal"),
        blk + "mlp/wo": ((L, m["ff"], d), "normal"),
        "final_norm": ((d,), "zeros"),
        "lm_head": ((d, m["v"]), "normal"),
    }


def _nest(flat: dict) -> dict:
    out: dict = {}
    for path, val in flat.items():
        node = out
        *head, leaf = path.split("/")
        for k in head:
            node = node.setdefault(k, {})
        node[leaf] = val
    return out


def seed32(seed: int) -> int:
    """Any whole number -> a 31-bit PRNG seed, deterministically."""
    return int(np.random.SeedSequence(int(seed)).generate_state(1)[0] >> 1)


@functools.lru_cache(maxsize=4)
def _maker(leaves_key):
    import jax
    import jax.numpy as jnp

    def make(key):
        flat = {}
        for i, (path, shape, init, dtype) in enumerate(leaves_key):
            if init == "zeros":
                flat[path] = jnp.zeros(shape, dtype)
            else:
                w = INIT_SCALE * jax.random.normal(
                    jax.random.fold_in(key, i), shape, jnp.float32)
                flat[path] = w.astype(jnp.bfloat16).astype(dtype)
        return _nest(flat)

    return jax.jit(make)


def make_weights(c: dict, seed: int, dtypes=None):
    """The model's bfloat16 weights from ``seed``, on the default device,
    each leaf stored in ``dtypes[path]`` (bfloat16 where none is given)."""
    import jax

    dtypes = dtypes or {}
    key = tuple((p, tuple(s), i, str(dtypes.get(p, "bfloat16")))
                for p, (s, i) in sorted(weight_shapes(c).items()))
    return _maker(key)(jax.random.PRNGKey(seed32(seed)))


def _fp8(w):
    import jax.numpy as jnp

    scale = jnp.max(jnp.abs(w), axis=-2, keepdims=True) / 448.0
    scale = jnp.where(scale == 0, 1.0, scale)
    q = (w / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return (q * scale).astype(jnp.bfloat16)


@functools.lru_cache(maxsize=8)
def _layer_fn(h, kv, hd, eps, theta, quant):
    import jax
    import jax.numpy as jnp

    act = jnp.float32 if quant is None else jnp.bfloat16

    def norm(x, scale):
        x32 = x.astype(jnp.float32)
        var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
        return (x32 * jax.lax.rsqrt(var + eps) * (1.0 + scale)).astype(act)

    def mm(x, w):
        if quant is None:
            return x @ w
        return jnp.matmul(x, _fp8(w), preferred_element_type=jnp.float32).astype(act)

    def layer(x, a, m):
        s = x.shape[0]
        inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
        ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
        cos, sin = jnp.cos(ang), jnp.sin(ang)

        def rot(t):  # (n, s, hd), interleaved pairs
            t1, t2 = t[..., 0::2], t[..., 1::2]
            return jnp.stack([t1 * cos - t2 * sin, t1 * sin + t2 * cos],
                             axis=-1).reshape(t.shape).astype(act)

        def heads(t, n):
            return t.reshape(s, n, hd).transpose(1, 0, 2)

        y = norm(x, a["norm"])
        q = rot(heads(mm(y, a["wq"]), h))
        k = jnp.repeat(rot(heads(mm(y, a["wk"]), kv)), h // kv, axis=0)
        v = jnp.repeat(heads(mm(y, a["wv"]), kv), h // kv, axis=0)
        sc = jnp.einsum("hqd,hkd->hqk", q, k,
                        preferred_element_type=jnp.float32) * hd ** -0.5
        sc = jnp.where(jnp.tril(jnp.ones((s, s), bool)), sc, -jnp.inf)
        o = jnp.einsum("hqk,hkd->hqd", jax.nn.softmax(sc, -1).astype(act), v,
                       preferred_element_type=jnp.float32).astype(act)
        x = x + mm(o.transpose(1, 0, 2).reshape(s, h * hd), a["wo"])
        y = norm(x, m["norm"])
        g = mm(y, m["wg"]).astype(jnp.float32)
        u = mm(y, m["wi"]).astype(jnp.float32)
        return x + mm((jax.nn.silu(g) * u).astype(act), m["wo"])

    return jax.jit(layer)


@functools.lru_cache(maxsize=4)
def _head_fn(eps, start, quant):
    import jax
    import jax.numpy as jnp

    def head(x, scale, w):
        x = x[start:].astype(jnp.float32)
        var = jnp.mean(x * x, axis=-1, keepdims=True)
        y = x * jax.lax.rsqrt(var + eps) * (1.0 + scale)
        if quant is None:
            return y @ w
        return jnp.matmul(y.astype(jnp.bfloat16), _fp8(w),
                          preferred_element_type=jnp.float32)

    return jax.jit(head)


def logits(weights, c: dict, tokens, *, start: int, quant=None) -> np.ndarray:
    """Logits (float32, over the padded vocabulary) of positions
    ``start``.. of one sequence ``tokens`` (1-D), computed layer by layer
    so that only one layer's temporaries live at a time."""
    import jax
    import jax.numpy as jnp

    m = dims(c)
    f32 = jnp.float32
    with jax.default_matmul_precision("highest"):
        x = jnp.take(weights["embed"], jnp.asarray(tokens, jnp.int32),
                     axis=0).astype(f32 if quant is None else jnp.bfloat16)
        layer = _layer_fn(m["h"], m["kv"], m["hd"], m["eps"], m["theta"], quant)
        blocks = weights["blocks"]["layer0"]
        for i in range(m["L"]):
            a = {k: v[i].astype(f32) for k, v in blocks["attn"].items()}
            mm = {k: v[i].astype(f32) for k, v in blocks["mlp"].items()}
            x = layer(x, a, mm)
        out = _head_fn(m["eps"], int(start), quant)(
            x, weights["final_norm"].astype(f32), weights["lm_head"].astype(f32))
        return np.asarray(out, dtype=np.float32)


def token_gaps(ref_logits: np.ndarray, tokens) -> np.ndarray:
    """For each position, how far the reference's logit of the chosen
    token lies below the reference's best logit (0 where they agree)."""
    tokens = np.asarray(tokens, dtype=np.int64)
    best = ref_logits.max(axis=-1)
    return best - ref_logits[np.arange(len(tokens)), tokens]
