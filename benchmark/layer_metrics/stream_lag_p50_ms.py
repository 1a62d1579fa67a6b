"""Front end and broker: median time from ``admit`` (the scheduler resolved
the first token and pushed it on the broker's stream channel) to
``first_write`` (the producer's handler thread has written the first SSE
token event): the stream channel and the handler's wake-up."""

from benchmark.lib import spans


def read(ctx):
    return spans.wait_p50_ms(ctx, "admit", "first_write")
