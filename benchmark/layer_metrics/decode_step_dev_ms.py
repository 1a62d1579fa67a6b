"""Step programs: device time for one decode step, with the steps counted by
the program's loop track. The time is ``decode_step_ms``'s: the share of the
traced window the device spent in the engine's step programs, times the span
from the first to the last group dispatched inside the trace. The steps are
``chunks`` x ``k`` of the ``sched.dispatch`` spans in that span - one span a
group, however short the group, where ``decode_step_ms`` counts throttled
per-request events and reads nothing once groups come faster than 50 ms."""

from benchmark.lib import reduce, spans


def read(ctx):
    trace = ctx.get("trace")
    if not trace or not trace.get("devices") or not trace.get("window_s"):
        return None
    seconds, _n = reduce.program_seconds(trace, *reduce.STEP_PROGRAMS)
    # A dispatch is placed where ``group_dispatch`` events are: at its end.
    inside = [
        (s["t0"] + s["dur"], s["chunks"] * s["k"])
        for s in spans.loop_spans(ctx.get("flight_trace"), "sched.dispatch")
        if trace["t_start"] <= s["t0"] + s["dur"] <= trace["t_stop"]
    ]
    if len(inside) < 3 or not seconds:
        return None
    steps = sum(k for _t, k in inside[:-1])
    span = inside[-1][0] - inside[0][0]
    return seconds / trace["window_s"] * span / steps * 1e3
