"""Operations and bytes a dense decoder's decode step needs, from the
configuration's shapes alone.

Counted at the precision the model computes in: bfloat16 weights read
once per step, the bfloat16 key/value cache read up to the current
position and written at it, and the LM head.  Float32 copies the program
may keep are not counted: they are the program's choice, not the step's
need.  Norms, RoPE, softmax and the embedding lookup are left out (well
under 1% of either count at these widths).
"""

from __future__ import annotations

BF16 = 2


def _m(c: dict):
    d, h, kv = c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"]
    hd = c.get("head_dim") or d // h
    v = -(-c["vocab_size"] // 256) * 256
    return d, h, kv, hd, c["intermediate_size"], v, c["num_hidden_layers"]


def layer_weight_count(c: dict) -> int:
    d, h, kv, hd, ff, _, _ = _m(c)
    return d * h * hd * 2 + d * kv * hd * 2 + 3 * d * ff


def decode_step(c: dict, batch: int, position: float) -> tuple:
    """(flops, bytes) of one decode step of ``batch`` sequences whose new
    token sits at ``position`` (it attends to position + 1 keys)."""
    d, h, kv, hd, ff, v, L = _m(c)
    ctx = position + 1
    w = L * layer_weight_count(c) + d * v
    flops = 2 * batch * w + L * 4 * batch * h * hd * ctx
    kv_bytes = L * batch * kv * hd * 2 * BF16 * (ctx + 1)
    return float(flops), float(w * BF16 + kv_bytes)

