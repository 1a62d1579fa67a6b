"""Scheduler: the share of the window's decode groups that were the long
ones. The scheduler dispatches a longer group once enough rows are live (its
"busy" mode), and a request's first token waits some two and a half groups:
so a first-token tail that moved with nothing changed in the code moved
because the order of arrivals took more, or fewer, of the window over that
edge - and this is the number that shows it. Read from the loop track's
``sched.dispatch`` spans that began inside the window (after-drain export):
those whose ``chunks`` x ``k`` reaches the cell's ``long_group_steps``
(``cells/<cell>.json``), as a share of all. No spans, or a cell that names no
long group: nothing."""

from benchmark.lib import spans


def read(ctx):
    long_steps = (ctx.get("cell") or {}).get("params", {}).get("long_group_steps")
    w = ctx.get("window")
    if not long_steps or not w:
        return None
    steps = [
        s["chunks"] * s["k"]
        for s in spans.loop_spans(ctx.get("flight"), "sched.dispatch")
        if w["w0"] <= s["t0"] <= w["w1"]
    ]
    if not steps:
        return None
    return 100.0 * sum(1 for k in steps if k >= long_steps) / len(steps)
