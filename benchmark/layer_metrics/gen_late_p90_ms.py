"""How late the load generator ran: sent - due, 90th percentile over the
window's requests, on the generator's own clock. A starved generator must not
be read as a fast server."""


def read(ctx):
    late = ctx["info"].get("gen_late_ms")
    return late["p90"] if late else None
