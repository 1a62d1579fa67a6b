"""What the per-layer metrics that read the program's own spans share: the
seams of one request's way to its first token (flight-recorder events, all on
the server's monotonic clock), the loop track's spans, and the difference of
the ``loop`` block of two /metrics reads. Each gives nothing (an empty list,
``None``) where the program recorded nothing: tracing off, or a program
without these spans."""

from __future__ import annotations

# The events between a request's arrival at the broker and its first token on
# the wire, in order. Consecutive pairs are the waits the metrics report;
# ``lease`` -> ``sched_submit`` (validate, encode, submit: host work inside
# one ``loop.drain``) is the one pair without a metric of its own.
SEAMS = ("enqueue", "lease", "sched_submit", "prefill_dispatch", "admit",
         "first_write")


def first_times(req: dict) -> dict[str, float]:
    """Time of the first event of each name in one request's timeline."""
    t: dict[str, float] = {}
    for ev in req["events"]:
        t.setdefault(ev["name"], ev["t"])
    return t


def waits_ms(flight: dict | None, a: str, b: str) -> list[float]:
    """Milliseconds from event ``a`` to event ``b``, for every request of the
    export that has both."""
    out = []
    for req in (flight or {}).get("requests", {}).values():
        t = first_times(req)
        if a in t and b in t:
            out.append((t[b] - t[a]) * 1e3)
    return out


def wait_p50_ms(ctx: dict, a: str, b: str) -> float | None:
    """The median of ``waits_ms`` over the export taken after the drain (the
    one ``queue_wait_p50_ms`` reads)."""
    waits = waits_ms(ctx.get("flight"), a, b)
    return ctx["stats"].percentile(waits, 50) if waits else None


def loop_spans(flight: dict | None, name: str) -> list[dict]:
    """The loop track's spans of one name, oldest first: ``seq``, ``parent``,
    ``t0``, ``dur`` and the span's own attributes."""
    spans = ((flight or {}).get("loop") or {}).get("spans", ())
    return sorted(
        ({**(attrs or {}), "seq": seq, "parent": parent, "t0": t0, "dur": dur}
         for seq, parent, n, t0, dur, attrs in spans if n == name),
        key=lambda s: s["t0"],
    )


def loop_delta(ctx: dict) -> dict | None:
    """``loop`` block of /metrics at the window's end less the same at its
    start: every counter, and ``seconds`` a span name. ``None`` where the
    program has no such block or counted no ``loop`` span in between."""
    a = (ctx.get("metrics_before") or {}).get("loop")
    b = (ctx.get("metrics_after") or {}).get("loop")
    if not a or not b:
        return None
    out = {k: v - a.get(k, 0) for k, v in b.items() if k != "spans"}
    out["seconds"] = {
        name: s["seconds"] - a["spans"].get(name, {}).get("seconds", 0.0)
        for name, s in b["spans"].items()
    }
    return out if out["seconds"].get("loop") else None
