"""Front end and broker: median time a request lies in the broker's queue -
from its ``enqueue`` event to its ``lease`` event, which the broker stamps
when the worker loop pops it (once an iteration, in ``loop.drain``). The
first of the three waits inside ``queue_wait_p50_ms``."""

from benchmark.lib import spans


def read(ctx):
    return spans.wait_p50_ms(ctx, "enqueue", "lease")
