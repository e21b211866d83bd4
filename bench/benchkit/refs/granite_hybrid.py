"""Plain reference of granite-4.0-h (``granitemoehybrid``): Mamba-2 layers
and NoPE grouped-query attention layers, each followed by a routed MoE
with a shared SwiGLU expert beside it; and the weights the benchmark
serves it with.

The forward pass is straightforward ``jax.numpy`` in float32 at HIGHEST
matmul precision, layer by layer over one sequence:

- x = embed[tokens] * embedding_multiplier;
- each layer: x = x + r * mixer(norm(x)), then
  x = x + r * (moe(norm(x)) + shared(norm(x))), r the residual multiplier;
- Mamba-2 as the sequential per-token recurrence: in_proj -> z, xBC, dt;
  a depthwise causal conv over xBC with bias, SiLU; dt = softplus(dt +
  dt_bias), A = -exp(A_log) per head; h_t = exp(dt_t A) h_{t-1} + dt_t
  x_t B_t^T per head, y_t = h_t C_t + D x_t; RMSNorm(y * silu(z)) over
  d_inner; out_proj;
- attention without rotary positions, scores scaled by the attention
  multiplier, causal, grouped-query;
- the MoE's gates are a softmax over the top-k of all the router's
  logits; only the held experts' part is computed (below);
- logits = RMSNorm(x) @ embed^T / logits_scaling (tied embeddings).

It imports nothing of the program.  Departures from the published
model, each shared with the program: the depth is one period of the
layer pattern; the MoE computes the part of the held experts only (this
chip's share of an expert-parallel deployment: what the experts held
elsewhere add is left out); every norm's gain is written as (1 + scale)
with scale made zero, which is the published gain of one.

``quant`` selects a control, put in the program's place by
``bench/calibrate.py``: ``"fp8"`` rounds every weight matrix to float8
(e4m3, one scale per output column) and carries the activations in
bfloat16; ``"bf16_state"`` holds the Mamba-2 state in bfloat16 between
steps and leaves the rest in float32.

The weights are made here from the seed, on the device, in one jitted
call: bfloat16 values, stored in the type each leaf of the program's
parameter tree declares.  Matrices are normal with std 0.02; the tied
embedding has std 0.02 / embedding_multiplier, so that the embedded input
has std 0.02 (at std 0.02 the x12 input would dominate the tied head's
logits and every served token would repeat the one before it, which no
comparison of tokens could look past); A_log, dt_bias and D take
Mamba-2's usual init and the conv its usual (PyTorch) uniform init.
"""

from __future__ import annotations

import functools

import numpy as np

INIT_SCALE = 0.02


def dims(c: dict) -> dict:
    d, h, kv = c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"]
    period = c["layer_types"]
    m = dict(
        d=d, h=h, kv=kv, hd=c.get("head_dim") or d // h,
        v=-(-c["vocab_size"] // 256) * 256, vocab=c["vocab_size"],
        L=c["num_hidden_layers"], period=tuple(period),
        eps=c["rms_norm_eps"], emb=float(c["embedding_multiplier"]),
        res=float(c["residual_multiplier"]), logit_div=float(c["logits_scaling"]),
        attn_scale=float(c["attention_multiplier"]),
        H=c["mamba_n_heads"], P=c["mamba_d_head"], N=c["mamba_d_state"],
        G=c["mamba_n_groups"], K=c["mamba_d_conv"],
        di=c["mamba_expand"] * d,
        ff=c["intermediate_size"], sff=c["shared_intermediate_size"],
        k=c["num_experts_per_tok"], routed=c["routed_experts"],
        held=tuple(c["held_experts"]),
    )
    assert m["H"] * m["P"] == m["di"], "mamba_n_heads * mamba_d_head != d_inner"
    assert m["held"][1] - m["held"][0] == c["num_local_experts"]
    assert m["L"] % len(period) == 0
    m["conv"] = m["di"] + 2 * m["G"] * m["N"]
    return m


def weight_shapes(c: dict) -> dict:
    """The served parameter tree as {path: (shape, init)}; each layer of
    the period is stacked over the periods on a leading axis."""
    m = dims(c)
    d, n_sb = m["d"], m["L"] // len(m["period"])
    out = {"embed": ((m["v"], d), "embed"), "final_norm": ((d,), "zeros")}
    for i, kind in enumerate(m["period"]):
        blk = f"blocks/layer{i}/"
        leaves = {}
        if kind == "mamba":
            leaves.update({
                "mamba/norm": ((d,), "zeros"),
                "mamba/in_proj": ((d, m["di"] + m["conv"] + m["H"]), "normal"),
                "mamba/conv_w": ((m["K"], m["conv"]), "conv"),
                "mamba/conv_b": ((m["conv"],), "conv"),
                "mamba/dt_bias": ((m["H"],), "dt_bias"),
                "mamba/A_log": ((m["H"],), "a_log"),
                "mamba/D": ((m["H"],), "ones"),
                "mamba/gate_norm": ((m["di"],), "zeros"),
                "mamba/out_proj": ((m["di"], d), "normal"),
            })
        elif kind == "attention":
            leaves.update({
                "attn/norm": ((d,), "zeros"),
                "attn/wq": ((d, m["h"] * m["hd"]), "normal"),
                "attn/wk": ((d, m["kv"] * m["hd"]), "normal"),
                "attn/wv": ((d, m["kv"] * m["hd"]), "normal"),
                "attn/wo": ((m["h"] * m["hd"], d), "normal"),
            })
        else:
            raise ValueError(f"unknown layer type {kind!r}")
        e = m["held"][1] - m["held"][0]
        leaves.update({
            "moe/norm": ((d,), "zeros"),
            "moe/router": ((d, m["routed"]), "normal"),
            "moe/wi": ((e, d, m["ff"]), "normal"),
            "moe/wg": ((e, d, m["ff"]), "normal"),
            "moe/wo": ((e, m["ff"], d), "normal"),
            "moe/shared/wi": ((d, m["sff"]), "normal"),
            "moe/shared/wg": ((d, m["sff"]), "normal"),
            "moe/shared/wo": ((m["sff"], d), "normal"),
        })
        for path, (shape, init) in leaves.items():
            out[blk + path] = ((n_sb,) + shape, init)
    return out


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


def _init(key, shape, init, kconv, emb):
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    if init == "embed":  # std 0.02 once multiplied by the embedding multiplier
        return (INIT_SCALE / emb) * jax.random.normal(key, shape, f32)
    if init == "zeros":
        return jnp.zeros(shape, f32)
    if init == "ones":
        return jnp.ones(shape, f32)
    if init == "conv":  # PyTorch's Conv1d default: U(+-1/sqrt(fan_in)), fan_in = k
        b = kconv ** -0.5
        return jax.random.uniform(key, shape, f32, -b, b)
    if init == "a_log":  # A ~ U(1, 16)
        return jnp.log(jax.random.uniform(key, shape, f32, 1.0, 16.0))
    if init == "dt_bias":  # dt log-uniform on [1e-3, 0.1], floor 1e-4
        u = jax.random.uniform(key, shape, f32)
        dt = jnp.maximum(jnp.exp(u * (np.log(0.1) - np.log(1e-3))
                                 + np.log(1e-3)), 1e-4)
        return dt + jnp.log(-jnp.expm1(-dt))
    return INIT_SCALE * jax.random.normal(key, shape, f32)


@functools.lru_cache(maxsize=4)
def _maker(leaves_key, kconv, emb):
    import jax
    import jax.numpy as jnp

    def make(key):
        flat = {}
        for i, (path, shape, init, dtype) in enumerate(leaves_key):
            w = _init(jax.random.fold_in(key, i), shape, init, kconv, emb)
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
    return _maker(key, c["mamba_d_conv"], float(c["embedding_multiplier"]))(
        jax.random.PRNGKey(seed32(seed)))


def _fp8(w):
    import jax.numpy as jnp

    scale = jnp.max(jnp.abs(w), axis=-2, keepdims=True) / 448.0
    scale = jnp.where(scale == 0, 1.0, scale)
    q = (w / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return (q * scale).astype(jnp.bfloat16)


def _ops(eps, quant):
    import jax
    import jax.numpy as jnp

    act = jnp.bfloat16 if quant == "fp8" else jnp.float32

    def norm(x, scale):
        x32 = x.astype(jnp.float32)
        var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
        return (x32 * jax.lax.rsqrt(var + eps) * (1.0 + scale)).astype(act)

    def mm(x, w):
        if quant != "fp8":
            return x @ w
        return jnp.matmul(x.astype(act), _fp8(w),
                          preferred_element_type=jnp.float32).astype(act)

    return act, norm, mm


@functools.lru_cache(maxsize=8)
def _mamba_fn(m_key, quant):
    import jax
    import jax.numpy as jnp

    m = dict(m_key)
    act, norm, mm = _ops(m["eps"], quant)
    di, H, P, N, G, K = m["di"], m["H"], m["P"], m["N"], m["G"], m["K"]
    f32 = jnp.float32

    def mixer(x, p):
        s = x.shape[0]
        zxbcdt = mm(norm(x, p["norm"]), p["in_proj"]).astype(f32)
        z, xbc, dt = (zxbcdt[:, :di], zxbcdt[:, di:di + m["conv"]],
                      zxbcdt[:, di + m["conv"]:])
        padded = jnp.concatenate([jnp.zeros((K - 1, m["conv"]), f32), xbc])
        conv = sum(padded[j:j + s] * p["conv_w"][j] for j in range(K))
        xbc = jax.nn.silu(conv + p["conv_b"]).astype(act).astype(f32)
        xs = xbc[:, :di].reshape(s, H, P)
        B = xbc[:, di:di + G * N].reshape(s, G, N)
        C = xbc[:, di + G * N:].reshape(s, G, N)
        dt = jax.nn.softplus(dt + p["dt_bias"])                 # (s, H)
        A = -jnp.exp(p["A_log"])
        grp = jnp.arange(H) // (H // G)                         # head -> group

        def step(h, t):
            x_t, dt_t, B_t, C_t = t
            h = (jnp.exp(dt_t * A)[:, None, None] * h
                 + (dt_t[:, None] * x_t)[:, :, None] * B_t[grp][:, None, :])
            if quant == "bf16_state":
                h = h.astype(jnp.bfloat16).astype(f32)
            return h, jnp.einsum("hpn,hn->hp", h, C_t[grp])

        _, y = jax.lax.scan(step, jnp.zeros((H, P, N), f32), (xs, dt, B, C))
        y = y + xs * p["D"][:, None]
        y = y.reshape(s, di) * jax.nn.silu(z)
        return mm(norm(y, p["gate_norm"]), p["out_proj"])

    return jax.jit(mixer)


@functools.lru_cache(maxsize=8)
def _attn_fn(m_key, quant):
    import jax
    import jax.numpy as jnp

    m = dict(m_key)
    act, norm, mm = _ops(m["eps"], quant)
    h, kv, hd = m["h"], m["kv"], m["hd"]

    def mixer(x, a):
        s = x.shape[0]

        def heads(t, n):
            return t.reshape(s, n, hd).transpose(1, 0, 2)

        y = norm(x, a["norm"])
        q = heads(mm(y, a["wq"]), h)
        k = jnp.repeat(heads(mm(y, a["wk"]), kv), h // kv, axis=0)
        v = jnp.repeat(heads(mm(y, a["wv"]), kv), h // kv, axis=0)
        sc = jnp.einsum("hqd,hkd->hqk", q, k,
                        preferred_element_type=jnp.float32) * m["attn_scale"]
        sc = jnp.where(jnp.tril(jnp.ones((s, s), bool)), sc, -jnp.inf)
        o = jnp.einsum("hqk,hkd->hqd", jax.nn.softmax(sc, -1).astype(act), v,
                       preferred_element_type=jnp.float32).astype(act)
        return mm(o.transpose(1, 0, 2).reshape(s, h * hd), a["wo"])

    return jax.jit(mixer)


@functools.lru_cache(maxsize=8)
def _moe_fn(m_key, quant):
    import jax
    import jax.numpy as jnp

    m = dict(m_key)
    act, norm, mm = _ops(m["eps"], quant)
    lo, hi = m["held"]

    def swiglu(y, wi, wg, wo):
        g = mm(y, wg).astype(jnp.float32)
        u = mm(y, wi).astype(jnp.float32)
        return mm((jax.nn.silu(g) * u).astype(act), wo)

    def moe(x, p):
        y = norm(x, p["norm"])
        logits = y.astype(jnp.float32) @ p["router"]
        top, idx = jax.lax.top_k(logits, m["k"])
        gates = jax.nn.softmax(top, axis=-1)                     # (s, k)
        out = swiglu(y, p["shared"]["wi"], p["shared"]["wg"],
                     p["shared"]["wo"]).astype(jnp.float32)
        for j in range(hi - lo):
            gate = jnp.sum(jnp.where(idx == lo + j, gates, 0.0), axis=-1)
            out = out + gate[:, None] * swiglu(
                y, p["wi"][j], p["wg"][j], p["wo"][j]).astype(jnp.float32)
        return out.astype(act)

    return jax.jit(moe)


@functools.lru_cache(maxsize=4)
def _head_fn(eps, logit_div, start, quant):
    import jax
    import jax.numpy as jnp

    def head(x, scale, embed):
        x = x[start:].astype(jnp.float32)
        var = jnp.mean(x * x, axis=-1, keepdims=True)
        y = x * jax.lax.rsqrt(var + eps) * (1.0 + scale)
        if quant != "fp8":
            return (y @ embed.T) / logit_div
        return jnp.matmul(y.astype(jnp.bfloat16), _fp8(embed.T),
                          preferred_element_type=jnp.float32) / logit_div

    return jax.jit(head)


def sublayer(kind: str, p, x, c: dict, *, quant=None):
    """One sub-layer's output for the sequence ``x`` (s, d): ``"mamba"``,
    ``"attention"`` (the mixers, norm included) or ``"moe"`` (the held
    experts' part plus the shared expert), at HIGHEST precision."""
    import jax

    m = dims(c)
    key = tuple(sorted(m.items()))
    fn = {"mamba": _mamba_fn, "attention": _attn_fn, "moe": _moe_fn}[kind]
    with jax.default_matmul_precision("highest"):
        return fn(key, quant)(x, p)


def _layer(tree, i, j):
    """Layer ``j`` of the period, in its ``i``-th stacked copy, in float32."""
    import jax
    import jax.numpy as jnp

    return jax.tree.map(lambda a: a[i].astype(jnp.float32), tree[f"layer{j}"])


def logits(weights, c: dict, tokens, *, start: int, quant=None) -> np.ndarray:
    """Logits (float32, over the padded vocabulary) of positions
    ``start``.. of one sequence ``tokens`` (1-D), computed layer by layer
    so that only one layer's temporaries live at a time."""
    import jax
    import jax.numpy as jnp

    m = dims(c)
    key = tuple(sorted(m.items()))
    act = jnp.bfloat16 if quant == "fp8" else jnp.float32
    with jax.default_matmul_precision("highest"):
        x = (jnp.take(weights["embed"], jnp.asarray(tokens, jnp.int32), axis=0)
             .astype(jnp.float32) * m["emb"]).astype(act)
        blocks, period = weights["blocks"], m["period"]
        for i in range(m["L"] // len(period)):
            for j, kind in enumerate(period):
                p = _layer(blocks, i, j)
                if kind == "mamba":
                    y = _mamba_fn(key, quant)(x, p["mamba"])
                else:
                    y = _attn_fn(key, quant)(x, p["attn"])
                x = x + m["res"] * y
                x = x + m["res"] * _moe_fn(key, quant)(x, p["moe"])
        out = _head_fn(m["eps"], m["logit_div"], int(start), quant)(
            x, weights["final_norm"].astype(jnp.float32),
            weights["embed"].astype(jnp.float32))
        return np.asarray(out, dtype=np.float32)


def token_gaps(ref_logits: np.ndarray, tokens) -> np.ndarray:
    """For each position, how far the reference's logit of the chosen
    token lies below the reference's best logit (0 where they agree)."""
    tokens = np.asarray(tokens, dtype=np.int64)
    best = ref_logits.max(axis=-1)
    return best - ref_logits[np.arange(len(tokens)), tokens]
