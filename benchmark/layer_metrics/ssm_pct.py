"""Kernels: the Mamba-2 mixer's share of the device's busy time in the traced
window - the self time of the ops that ``benchmark/lib/ssm.py`` tells as the
mixer's (by the shapes only the mixer has, among the 40 ops with most self
time: a floor), decode update and admission scan together, over ``busy_s``.
``None`` for a program or a configuration without a mixer."""

from benchmark.lib import ssm


def read(ctx):
    seconds = ssm.mixer_seconds(ctx)
    busy = (ctx.get("trace") or {}).get("busy_s")
    if seconds is None or not busy or not sum(seconds.values()):
        return None
    return 100.0 * sum(seconds.values()) / busy
