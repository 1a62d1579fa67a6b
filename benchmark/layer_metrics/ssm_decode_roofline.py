"""Kernels: the mixer's decode update as a share of its roofline. The least
time a step's update could take - the recurrent state of every row the
program updates read and written once and the mixer's weights read once, at
the chip's bandwidth (``benchmark/lib/ssm.py``) - over the measured time of
the mixer's decode ops a step: their self time in the traced window (told by
shape: a floor, so this share is a ceiling) over the steps dispatched
meanwhile (the loop track's ``sched.dispatch`` spans where the flight recorder
has them, else ``loop.decode_steps`` of /metrics scaled from the window to the
traced part of it). The program updates the state of all the cell's rows,
live or done, so the floor counts them all: the share follows the kernel and
not how many rows the traffic happened to fill. ``None`` without a mixer, a
state pool (``cache.state_bytes`` of /metrics), a trace, or a counted step."""

from benchmark.lib import reduce, spans, ssm


def decode_steps_in_trace(ctx):
    """Steps dispatched inside the traced interval: the loop track's
    ``sched.dispatch`` spans (``chunks`` x ``k``), else the window's
    ``loop.decode_steps`` scaled by the traced share of the window."""
    trace = ctx["trace"]
    steps = sum(k for _t, k in reduce.dispatches_in_trace(ctx))
    if steps:
        return steps
    d, w = spans.loop_delta(ctx), ctx.get("window") or {}
    if d is None or not d.get("decode_steps") or not w.get("w1", 0) > w.get("w0", 0):
        return None
    return d["decode_steps"] * (trace["t_stop"] - trace["t_start"]) / (w["w1"] - w["w0"])


def read(ctx):
    seconds = ssm.mixer_seconds(ctx)
    if seconds is None or not seconds["decode"] or ctx.get("peaks") is None:
        return None
    if "state_bytes" not in ((ctx.get("metrics_after") or {}).get("cache") or {}):
        return None  # the program holds no state pool: it has no mixer
    steps = decode_steps_in_trace(ctx)
    if not steps:
        return None
    cell = ctx["cell"]
    floor = ssm.decode_update_floor_s(
        ssm.sizes(cell["model"]), cell["config"]["dtype"], ctx["peaks"],
        rows=cell["serve"]["rows"],
    )
    return 100.0 * floor / (seconds["decode"] / steps)
