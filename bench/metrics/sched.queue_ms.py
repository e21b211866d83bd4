"""Milliseconds a label waited in the scheduler between submit and the
start of its batch: ``wait_s`` of the program's ``sched.batch`` spans
(each batch's mean over its labels), weighted by the batch's labels."""


def read(ctx):
    batches = [s for s in ctx["spans"]
               if s["name"] == "sched.batch" and "wait_s" in s["attrs"]]
    n = sum(s["attrs"]["n"] for s in batches)
    if not n:
        return None
    return sum(s["attrs"]["wait_s"] * s["attrs"]["n"] for s in batches) * 1e3 / n
