"""Scheduler: host time of the worker loop per decode step - the seconds of
the ``loop`` spans (every ``run_once``) less ``sched.fetch_wait`` (blocked on
the device) and ``loop.idle`` (blocked on an empty queue), over the decode
steps dispatched, all from the ``loop`` block of /metrics at the two ends of
the window. What ``host_ms_per_group`` sees of it is the dispatch and the
callback; this holds housekeeping, the broker drain, planning, admission and
the metrics publish too."""

from benchmark.lib import spans


def read(ctx):
    d = spans.loop_delta(ctx)
    if d is None or not d.get("decode_steps"):
        return None
    s = d["seconds"]
    host = s["loop"] - s.get("sched.fetch_wait", 0.0) - s.get("loop.idle", 0.0)
    return host / d["decode_steps"] * 1e3
