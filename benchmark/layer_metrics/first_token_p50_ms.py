"""Scheduler: the median first-token time of the window's requests - due time
to first token event at the client, on the load generator's clock (the same
requests and clock as ``ttft_p90_ms``). For a cell that reports no first-token
tail: where admission runs through the mixed step, the chunk budget trades
this against the step's cost, so it stands beside ``tpot_p90_ms`` there.
``None`` where the window answered nothing."""


def read(ctx):
    return ((ctx.get("info") or {}).get("ttft_ms") or {}).get("p50")
