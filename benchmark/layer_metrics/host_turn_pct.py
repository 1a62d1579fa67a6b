"""Scheduler: the share of the worker loop's time that is the host's own
turn - ``loop`` seconds less ``loop.idle`` (blocked on an empty queue) and
``sched.fetch_wait`` (blocked on the device: a group's results or an
admission's first tokens), over ``loop`` less ``loop.idle``, from the
``loop`` block of /metrics at the two ends of the window. Near 0 the device
bounds the loop; near 100 the host does."""

from benchmark.lib import spans


def read(ctx):
    d = spans.loop_delta(ctx)
    if d is None:
        return None
    s = d["seconds"]
    busy = s["loop"] - s.get("loop.idle", 0.0)
    if busy <= 0:
        return None
    return 100.0 * (busy - s.get("sched.fetch_wait", 0.0)) / busy
