"""Front end and broker: median time from the broker's ``enqueue`` event to
the scheduler's ``admit`` event of a request (flight recorder, both ends on
the server's monotonic clock). ``admit`` is stamped when the request's first
token is resolved, so this spans the queue, the wait for a free row and the
prompt's prefill."""


def read(ctx):
    flight = ctx.get("flight") or {}
    waits = []
    for req in flight.get("requests", {}).values():
        t = {}
        for ev in req["events"]:
            t.setdefault(ev["name"], ev["t"])
        if "enqueue" in t and "admit" in t:
            waits.append((t["admit"] - t["enqueue"]) * 1e3)
    return ctx["stats"].percentile(waits, 50) if waits else None
