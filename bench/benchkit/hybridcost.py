"""Operations and bytes of granite-4.0-h's prefill and decode step (Mamba-2
and attention layers, each with a routed MoE and a shared expert), from
the configuration's shapes alone.

Counted at the precision the configuration states: bfloat16 weights read
once per step, the Mamba-2 state and the float32 conv window read and
written once per decode step, the bfloat16 key/value cache of the
attention layers read up to the current position and written at it, and
the tied LM head.  The routed experts held here count as the work their
picks need: a token picks each expert with probability k / routed
(uniform routing, which random weights give), so a token does
k * held / routed experts' products here, and a decode step reads the
held experts that at least one of its tokens picked.  What the program
computes beyond that (it multiplies every token with every held expert)
is its choice, not the step's need.  Norms, the conv, gates, softmax and
the embedding lookup are left out (well under 1% of either count).
"""

from __future__ import annotations

BF16, F32 = 2, 4


def _m(c: dict) -> dict:
    d, h, kv = c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"]
    di = c["mamba_expand"] * d
    G, N, H = c["mamba_n_groups"], c["mamba_d_state"], c["mamba_n_heads"]
    kinds = c["layer_types"] * (c["num_hidden_layers"] // len(c["layer_types"]))
    return dict(
        d=d, h=h, kv=kv, hd=c.get("head_dim") or d // h, di=di, G=G, N=N, H=H,
        P=c["mamba_d_head"], K=c["mamba_d_conv"], Q=c["mamba_chunk_size"],
        conv=di + 2 * G * N, v=-(-c["vocab_size"] // 256) * 256,
        ff=c["intermediate_size"], sff=c["shared_intermediate_size"],
        k=c["num_experts_per_tok"], routed=c["routed_experts"],
        held=c["held_experts"][1] - c["held_experts"][0],
        n_mamba=kinds.count("mamba"), n_attn=kinds.count("attention"),
        L=len(kinds))


def _token_weights(m: dict) -> dict:
    """Weights each token multiplies with, per kind of layer (expected
    held-expert share included), and the LM head."""
    d = m["d"]
    expert = 3 * d * m["ff"]
    moe = d * m["routed"] + 3 * d * m["sff"] + expert * m["k"] * m["held"] / m["routed"]
    return {
        "mamba": d * (m["di"] + m["conv"] + m["H"]) + m["di"] * d + moe,
        "attn": d * m["hd"] * (2 * m["h"] + 2 * m["kv"]) + moe,
        "head": d * m["v"],
    }


def step_weight_bytes(c: dict, batch: int) -> float:
    """bfloat16 bytes of the weights one decode step of ``batch`` reads."""
    m = _m(c)
    d = m["d"]
    expert = 3 * d * m["ff"]
    touched = m["held"] * (1.0 - (1.0 - m["k"] / m["routed"]) ** batch)
    moe = d * m["routed"] + 3 * d * m["sff"] + expert * touched
    mamba = d * (m["di"] + m["conv"] + m["H"]) + m["di"] * d + m["K"] * m["conv"]
    attn = d * m["hd"] * (2 * m["h"] + 2 * m["kv"])
    w = m["n_mamba"] * mamba + m["n_attn"] * attn + m["L"] * moe + d * m["v"]
    return float(w * BF16)


def state_bytes(c: dict, batch: int) -> float:
    """Bytes of the Mamba-2 state (in the configuration's precision) and
    the float32 conv windows of ``batch``."""
    m = _m(c)
    state = BF16 if c["precision"]["ssm_state"] == "bfloat16" else F32
    per = m["H"] * m["P"] * m["N"] * state + (m["K"] - 1) * m["conv"] * F32
    return float(m["n_mamba"] * batch * per)


def decode_step(c: dict, batch: int, position: float) -> tuple:
    """(flops, bytes) of one decode step of ``batch`` sequences whose new
    token sits at ``position`` (it attends to position + 1 keys)."""
    m = _m(c)
    w = _token_weights(m)
    ctx = position + 1
    flops = 2 * batch * (m["n_mamba"] * w["mamba"] + m["n_attn"] * w["attn"]
                         + w["head"])
    # state update (decay, outer product, add) and read-out per head
    flops += m["n_mamba"] * batch * 5 * m["H"] * m["P"] * m["N"]
    flops += m["n_attn"] * 4 * batch * m["h"] * m["hd"] * ctx
    kv_bytes = m["n_attn"] * batch * m["kv"] * m["hd"] * 2 * BF16 * (ctx + 1)
    nbytes = step_weight_bytes(c, batch) + 2 * state_bytes(c, batch) + kv_bytes
    return float(flops), float(nbytes)


def prefill(c: dict, batch: int, prompt: int) -> float:
    """Operations of one prefill of ``batch`` prompts of ``prompt`` tokens
    through the last token's logits: the projections (expected held-expert
    share included), the chunked SSD terms of each Mamba-2 layer (C B^T,
    the masked (Q, Q) products, the read-out of the incoming state and the
    state's update, chunk by chunk) and causal attention."""
    m = _m(c)
    w = _token_weights(m)
    tokens = batch * prompt
    flops = 2 * tokens * (m["n_mamba"] * w["mamba"] + m["n_attn"] * w["attn"])
    flops += 2 * batch * w["head"]
    Q, H, P, N, G = m["Q"], m["H"], m["P"], m["N"], m["G"]
    chunks = -(-prompt // Q)
    per_chunk = 2 * Q * Q * N * G + 2 * H * Q * Q * P + 2 * 2 * Q * H * P * N
    flops += m["n_mamba"] * batch * chunks * per_chunk
    flops += m["n_attn"] * batch * 2 * m["h"] * m["hd"] * prompt * (prompt + 1)
    return float(flops)
