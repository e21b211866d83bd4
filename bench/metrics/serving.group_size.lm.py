"""Requests per batch group run by the serving engine in the window
(``ServingEngine.stats()``: responses over groups), LM serving."""


def read(ctx):
    lay = ctx["layer"]
    if lay.get("kind") != "lm" or not lay.get("groups"):
        return None
    return lay["responses"] / lay["groups"]
