"""Share of the traced window in which no operation ran on the device,
in percent (1 - busy / window), campaign cells."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None or ctx["layer"].get("kind") != "campaign":
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
