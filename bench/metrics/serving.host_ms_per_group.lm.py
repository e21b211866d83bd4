"""Milliseconds of each LM group spent outside the model step: mean over
the program's ``serving.group`` spans of their duration less that of
their ``lm.prefill`` and ``lm.decode`` children.  A group whose children
are not both in the window is left out."""


def read(ctx):
    if ctx["layer"].get("kind") != "lm":
        return None
    steps = {}
    for s in ctx["spans"]:
        if s["name"] in ("lm.prefill", "lm.decode"):
            steps.setdefault(s.get("parent"), {})[s["name"]] = s["dur"]
    host = [s["dur"] - sum(steps[s["span"]].values()) for s in ctx["spans"]
            if s["name"] == "serving.group" and len(steps.get(s["span"], ())) == 2]
    return sum(host) * 1e3 / len(host) if host else None
