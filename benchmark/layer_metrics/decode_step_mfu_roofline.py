"""Step programs: the whole decode step's share of the chip's peak - the one
share of the whole step, so ``mfu`` is in its name; on these cells memory
bounds it, not operations, and the reader takes whichever of the two is the
longer. The least time a step could take - every parameter byte, the cached
keys and values of the tokens in flight and twice every live row's recurrent
state at the chip's bandwidth, or the batch's operations at its peak
(``benchmark/lib/costs.py: decode_step_floor_s``; shapes only; rows and
context from the harness's own request log) - over the measured device time
for a step (``decode_step_dev_ms``'s: ``reduce.step_seconds_in_trace``, which
holds the admission prefills between the groups too, and counts one
``sched.dispatch`` span a group, so it reads at any step time). Shapes and
traffic alone make the floor, so it prices the same work whatever implements
it, a Pallas kernel or the compiler's own code. The floor is a DECODE
step's: the prompt tokens a mixed step also carries are not counted, which
can only read low. Sampling, bucketed over-read and padded rows are the
program's, not the floor's."""

from benchmark.lib import reduce


def read(ctx):
    step = reduce.step_seconds_in_trace(ctx)
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
