"""Reductions that more than one per-layer metric shares: the decode groups
the scheduler dispatched while the trace was taken (the program's own flight
recorder), what the request log says the batch looked like meanwhile, and the
device time of one decode step."""

from __future__ import annotations

# Events of one dispatch are recorded in one loop over the batch's rows, well
# under a millisecond apart; two dispatches are at least a group apart.
MERGE_S = 0.005
# The recorder throttles ``group_dispatch`` to one event per request in 50 ms:
# groups dispatched faster than that are not all recorded, so not countable.
THROTTLE_S = 0.05


def _increments(rec: dict) -> list[tuple[float, int]]:
    return rec.get("increments") or []


def group_dispatches(flight: dict) -> list[tuple[float, int]]:
    """``(time, decode steps)`` of every group the scheduler dispatched, in
    order: the flight recorder's ``group_dispatch`` events (the scheduler
    stamps one per request in the batch, with the group's ``chunks`` and
    ``k``, whose product is the steps every row advances), merged into one
    entry per dispatch. Times are the server's monotonic clock."""
    events = sorted(
        (ev["t"], ev["attrs"]["chunks"] * ev["attrs"]["k"])
        for req in (flight or {}).get("requests", {}).values()
        for ev in req["events"] if ev["name"] == "group_dispatch"
    )
    out: list[tuple[float, int]] = []
    for t, steps in events:
        if not out or t - out[-1][0] >= MERGE_S:
            out.append((t, steps))
    return out


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


def decode_step_seconds(ctx: dict) -> float | None:
    """Device time of the engine's step programs per decode step, while the
    trace was taken: what the device spends for one token of every row, the
    admission prefills between the decode groups included (they run under
    the same module name). The time is the device trace's: the share of the
    traced window spent in step programs. The steps are the program's own
    count: the groups it dispatched inside the traced interval, from the
    first to the last of them so that no group is cut at an edge, each with
    the steps the scheduler gave it. A group runs on the device one group
    after its dispatch; if the groups at the two edges differ in steps, the
    count is off by that difference. Nothing is read when fewer than three
    dispatches fall inside the trace or when groups come faster than the
    recorder's throttle lets it record them."""
    trace = ctx.get("trace")
    if not trace or not trace.get("devices") or not trace.get("window_s"):
        return None
    seconds, _n = program_seconds(trace, *STEP_PROGRAMS)
    inside = [d for d in group_dispatches(ctx.get("flight_trace"))
              if trace["t_start"] <= d[0] <= trace["t_stop"]]
    if len(inside) < 3 or not seconds:
        return None
    gaps = [b[0] - a[0] for a, b in zip(inside, inside[1:])]
    if min(gaps) < THROTTLE_S:
        return None
    steps = sum(k for _t, k in inside[:-1])
    span = inside[-1][0] - inside[0][0]
    return seconds / trace["window_s"] * span / steps
