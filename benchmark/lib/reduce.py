"""Reductions that more than one per-layer metric shares: the decode groups
the scheduler dispatched while the trace was taken (the loop track's
``sched.dispatch`` spans), what the request log says the batch looked like
meanwhile, and the device time of one decode step."""

from __future__ import annotations

from benchmark.lib import spans


def _increments(rec: dict) -> list[tuple[float, int]]:
    return rec.get("increments") or []


def dispatches_in_trace(ctx: dict) -> list[tuple[float, int]]:
    """``(time, decode steps)`` of every group the scheduler dispatched
    inside the traced interval, in order: one ``sched.dispatch`` span of the
    loop track a group, however short the group, placed at its end, with
    ``chunks`` x ``k`` steps (what every row advances). Times are the
    server's monotonic clock. Empty where tracing was off."""
    trace = ctx.get("trace") or {}
    if "t_start" not in trace:
        return []
    return [
        (s["t0"] + s["dur"], s["chunks"] * s["k"])
        for s in spans.loop_spans(ctx.get("flight_trace"), "sched.dispatch")
        if trace["t_start"] <= s["t0"] + s["dur"] <= trace["t_stop"]
    ]


def batch_between(records: list[dict], a: float, b: float, n: int = 16) -> dict:
    """Mean number of requests decoding, and their mean context (prompt plus
    tokens received so far), sampled at ``n`` instants in [a, b]."""
    rows, ctx = [], []
    for i in range(n):
        t = a + (b - a) * (i + 0.5) / n
        live = [r for r in records
                if r.get("first") is not None and r["first"] <= t
                and (r.get("done") or float("inf")) > t]
        rows.append(len(live))
        for r in live:
            got = sum(k for ts, k in _increments(r) if ts <= t)
            ctx.append(len(r["body"]["token_ids"]) + got)
    return {
        "rows": sum(rows) / len(rows) if rows else 0.0,
        "context": sum(ctx) / len(ctx) if ctx else 0.0,
    }


def program_seconds(trace: dict, *needles: str) -> tuple[float, float]:
    """Device seconds (a device) and executions of the programs whose name
    holds one of ``needles``."""
    s = n = 0.0
    for name, p in (trace or {}).get("programs", {}).items():
        if any(x in name for x in needles):
            s += p["s"]
            n += p["n"]
    return s, n


# The engine's step programs are jitted ``functools.partial`` objects, which
# JAX names ``<unknown>``: the admission prefill, the decode group and the
# ragged group all run as XLA module ``jit__unknown`` and cannot be told apart
# by name until the program names them (PERF.md section 7). The small
# programs between them (``jit__admit_merge_impl``, ``jit__lambda``) have
# names of their own.
STEP_PROGRAMS = ("jit__unknown",)


def step_seconds_in_trace(ctx: dict) -> float | None:
    """Device time of the engine's step programs per decode step, while the
    trace was taken: what the device spends for one token of every row, the
    admission prefills between the decode groups included (they run under
    the same module name). The time is the device trace's: the share of the
    traced window spent in step programs. The steps are the program's own
    count: the groups it dispatched inside the traced interval, from the
    first to the last of them so that no group is cut at an edge, each with
    the steps the scheduler gave it. A group runs on the device one group
    after its dispatch; if the groups at the two edges differ in steps, the
    count is off by that difference. Nothing is read without a device in the
    trace or with fewer than three dispatches inside it."""
    trace = ctx.get("trace")
    if not trace or not trace.get("devices") or not trace.get("window_s"):
        return None
    seconds, _n = program_seconds(trace, *STEP_PROGRAMS)
    inside = dispatches_in_trace(ctx)
    if len(inside) < 3 or not seconds:
        return None
    steps = sum(k for _t, k in inside[:-1])
    span = inside[-1][0] - inside[0][0]
    return seconds / trace["window_s"] * span / steps
