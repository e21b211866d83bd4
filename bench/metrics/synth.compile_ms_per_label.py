"""Milliseconds of synthesis compiles (the program's ``synth.compile``
spans that started in the window) per label completed in the window."""


def read(ctx):
    labels = ctx["layer"].get("labels")
    if not labels:
        return None
    total = sum(s["dur"] for s in ctx["spans"] if s["name"] == "synth.compile")
    return total * 1e3 / labels
