"""Share of its roofline that a decode step reaches, in percent: the
least time the chip could take (the larger of the step's operations
over peak bf16 FLOP/s and its bytes over HBM bandwidth, counted from the
shapes by ``benchkit.lmcost``) over the measured device time per step
(XLA module ``jit_serve_step``)."""

from benchkit.devtrace import module_time


def read(ctx):
    lay, peaks = ctx["layer"], ctx["peaks"]
    m = module_time(ctx["trace"], "jit_serve_step")
    if m is None or peaks is None or lay.get("kind") != "lm":
        return None
    flops, nbytes = lay["decode_cost"](ctx["config"], lay["batch"],
                                       lay["decode_position"])
    least = max(flops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least / (m[1] / m[0])
