"""Scheduler: host time per dispatched group - median ``host_dispatch`` plus
median ``host_callback`` from /metrics (host clock around host work; the
blocking fetch, which waits for the device, is left out)."""


def read(ctx):
    ho = (ctx["metrics_after"] or {}).get("host_overhead") or {}
    d, c = ho.get("dispatch", {}).get("p50_ms"), ho.get("callback", {}).get("p50_ms")
    return None if d is None or c is None else d + c
