"""Labels completed per scheduler batch in the window
(``EvalScheduler.stats()``: ``labeled`` over ``batches``)."""


def read(ctx):
    lay = ctx["layer"]
    if lay.get("sched_batches"):
        return lay["sched_labeled"] / lay["sched_batches"]
    return None
