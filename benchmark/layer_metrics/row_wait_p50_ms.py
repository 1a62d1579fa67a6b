"""Scheduler: median time a submitted request waits for a row - from
``sched_submit`` to ``prefill_dispatch``, which the scheduler stamps when it
puts the prompt's prefill on the device queue (``sched.admit``, at the end
of the iteration that popped the request, or later if no row is free). The
second of the three waits inside ``queue_wait_p50_ms``."""

from benchmark.lib import spans


def read(ctx):
    return spans.wait_p50_ms(ctx, "sched_submit", "prefill_dispatch")
