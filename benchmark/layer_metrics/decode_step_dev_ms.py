"""Step programs: device time for one decode step, with the steps counted by
the program's loop track (``benchmark/lib/reduce.py: step_seconds_in_trace``).
The time is the device's: the share of the traced window spent in the engine's
step programs (XLA module ``jit__unknown``: the decode groups and the
admission prefills between them, which carry one name), times the span from
the first to the last group dispatched inside the trace. The steps are
``chunks`` x ``k`` of the ``sched.dispatch`` spans in that span - one span a
group, however short the group."""

from benchmark.lib import reduce


def read(ctx):
    s = reduce.step_seconds_in_trace(ctx)
    return None if s is None else s * 1e3
