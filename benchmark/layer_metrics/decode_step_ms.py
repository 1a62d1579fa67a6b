"""Step programs: device time for one decode step - the share of the traced
window the device spent in the engine's step programs (XLA module
``jit__unknown``: the decode groups and the admission prefills between them,
which carry one name), times the span from the first to the last group the
scheduler dispatched inside the trace, over the steps of those groups as the
scheduler's own flight recorder counts them (benchmark/lib/reduce.py). The
time is the device's; the count is the program's."""

from benchmark.lib import reduce


def read(ctx):
    s = reduce.decode_step_seconds(ctx)
    return None if s is None else s * 1e3
