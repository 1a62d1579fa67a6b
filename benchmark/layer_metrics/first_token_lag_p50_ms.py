"""Scheduler: median time from ``prefill_dispatch`` to ``admit`` - the
prompt's prefill on the device, behind the group that was running, and the
one-group lag of the pipelined fetch (the first token is resolved in the
next iteration's ``sched.resolve``). The third of the three waits inside
``queue_wait_p50_ms``."""

from benchmark.lib import spans


def read(ctx):
    return spans.wait_p50_ms(ctx, "prefill_dispatch", "admit")
