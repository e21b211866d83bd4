"""FLOP/s utilization of a prefill, in percent: the operations one
prefill of the window's group needs (``benchkit.hybridcost.prefill``:
projections with the held experts' expected share, the chunked SSD terms
and causal attention) over the device time per prefill (XLA module
``jit_prefill``) and the chip's bf16 peak.  Cells whose driver gives no
prefill cost report nothing."""

from benchkit.devtrace import module_time


def read(ctx):
    lay, peaks = ctx["layer"], ctx["peaks"]
    cost = lay.get("prefill_cost")
    m = module_time(ctx["trace"], "jit_prefill")
    if m is None or peaks is None or cost is None or lay.get("kind") != "lm":
        return None
    flops = cost(ctx["config"], lay["batch"], lay["prompt_tokens"])
    return 100.0 * flops / (m[1] / m[0]) / peaks["bf16_flops"]
