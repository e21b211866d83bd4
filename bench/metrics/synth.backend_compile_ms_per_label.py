"""Milliseconds of XLA's backend compile (``jit.compile`` records of
phase ``backend``) inside the program's ``synth.compile`` spans per
label completed in the window.  This part releases the GIL."""


def read(ctx):
    labels = ctx["layer"].get("labels")
    compiles = {s["span"] for s in ctx["spans"] if s["name"] == "synth.compile"}
    if not labels or not compiles:
        return None
    durs = [s["dur"] for s in ctx["spans"]
            if s["name"] == "jit.compile" and s.get("parent") in compiles
            and s["attrs"].get("phase") == "backend"]
    return sum(durs) * 1e3 / labels if durs else None
