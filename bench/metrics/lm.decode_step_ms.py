"""Device milliseconds per decode step: time of the XLA module
``jit_serve_step`` in the traced window over its executions."""

from benchkit.devtrace import module_time


def read(ctx):
    m = module_time(ctx["trace"], "jit_serve_step")
    return None if m is None else m[1] * 1e3 / m[0]
