"""Milliseconds of JAX tracing and lowering to StableHLO inside the
program's ``synth.compile`` spans per label completed in the window.
Per span: its longest ``jit.compile`` child of phase ``trace`` (the
deploy function's own trace; the sub-traces nested in it lie inside it)
plus its children of phase ``lower``.  This part holds the GIL."""


def read(ctx):
    labels = ctx["layer"].get("labels")
    compiles = {s["span"] for s in ctx["spans"] if s["name"] == "synth.compile"}
    if not labels or not compiles:
        return None
    trace, lower, seen = {}, 0.0, False
    for s in ctx["spans"]:
        if s["name"] != "jit.compile" or s.get("parent") not in compiles:
            continue
        phase = s["attrs"].get("phase")
        if phase == "trace":
            trace[s["parent"]] = max(trace.get(s["parent"], 0.0), s["dur"])
            seen = True
        elif phase == "lower":
            lower += s["dur"]
            seen = True
    if not seen:
        return None
    return (sum(trace.values()) + lower) * 1e3 / labels
