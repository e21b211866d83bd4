"""Tokens each held expert got per MoE layer and decode step in the
window: the (token, expert) routing picks of decode steps that landed on
the experts held here (the LM backend's ``stats()``, counted inside the
model step) over held experts x MoE layers x decode steps."""


def read(ctx):
    lay = ctx["layer"]
    if lay.get("kind") != "lm" or not lay.get("decode_steps") \
            or not lay.get("held_experts"):
        return None
    return lay["held_picks"] / (lay["held_experts"] * lay["moe_layers"]
                                * lay["decode_steps"])
