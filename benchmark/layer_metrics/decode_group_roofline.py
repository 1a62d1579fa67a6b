"""Step programs: the decode program's share of its roofline. The least time
a step could take - every parameter byte and the cached keys and values of
the tokens in flight read once at the chip's bandwidth, or the batch's
operations at its peak, whichever is longer (benchmark/lib/costs.py, shapes
only; batch and context from the harness's own request log) - over the
measured device time for a step (``decode_step_ms``, which holds the admission
prefills between the groups too). Bandwidth bounds it at these batch sizes. Sampling, bucketed over-read and padded rows
are the program's, not the floor's."""

from benchmark.lib import reduce


def read(ctx):
    step = reduce.decode_step_seconds(ctx)
    if step is None or ctx.get("peaks") is None:
        return None
    trace, cell = ctx["trace"], ctx["cell"]
    batch = reduce.batch_between(ctx["records"], trace["t_start"], trace["t_stop"])
    if not batch["rows"]:
        return None
    floor = ctx["costs"].decode_step_floor_s(
        ctx["dims"], cell["config"]["dtype"], ctx["peaks"],
        rows=batch["rows"], context=batch["context"],
        chips=cell["entry"]["chips"],
    )
    return 100.0 * floor["floor_s"] / step
