"""Share of synthesis requests in the window that the synth cache served
without a compile (``SynthCache.stats()`` deltas), in percent."""


def read(ctx):
    lay = ctx["layer"]
    hits, compiles = lay.get("synth_hits"), lay.get("synth_compiles")
    if hits is None or hits + compiles == 0:
        return None
    return 100.0 * hits / (hits + compiles)
