"""Device microseconds of the fused simulation programs (XLA module
``jit_run``) in the traced window per label completed in it."""

from benchkit.devtrace import module_time


def read(ctx):
    labels = ctx["layer"].get("labels")
    m = module_time(ctx["trace"], "jit_run")
    if not labels or m is None:
        return None
    return m[1] * 1e6 / labels
