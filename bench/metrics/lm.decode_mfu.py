"""Model FLOP/s utilization of decoding, in percent: the operations a
decode step needs (``benchkit.lmcost``) times the decode steps in the
traced window, over the window's seconds and the chip's bf16 peak."""

from benchkit.devtrace import module_time


def read(ctx):
    lay, peaks = ctx["layer"], ctx["peaks"]
    m = module_time(ctx["trace"], "jit_serve_step")
    if m is None or peaks is None or lay.get("kind") != "lm":
        return None
    flops, _ = lay["decode_cost"](ctx["config"], lay["batch"],
                                  lay["decode_position"])
    return 100.0 * flops * m[0] / ctx["window_s"] / peaks["bf16_flops"]
