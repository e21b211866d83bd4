"""Milliseconds an LM request waited in the serving engine's admission
queue: mean ``queue_s`` (submit until the batch loop popped it) of the
program's ``serving.request`` spans."""


def read(ctx):
    if ctx["layer"].get("kind") != "lm":
        return None
    waits = [s["attrs"]["queue_s"] for s in ctx["spans"]
             if s["name"] == "serving.request" and "queue_s" in s["attrs"]]
    return sum(waits) * 1e3 / len(waits) if waits else None
