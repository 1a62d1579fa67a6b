"""Percentile arithmetic that states its sample count."""

from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """The ``q``-th percentile (0..100) by linear interpolation between the
    two nearest order statistics. Raises on an empty sample: a metric with
    nothing behind it is left out, never reported as 0."""
    s = sorted(values)
    if not s:
        raise ValueError("percentile of an empty sample")
    pos = (len(s) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(s) - 1)
    return s[lo] + (s[hi] - s[lo]) * (pos - lo)


def summary(values, q: float) -> dict:
    """Median, the ``q``-th percentile, the sample count and how many
    samples lie beyond the percentile (ten is the least a tail can stand
    on: choosing-metrics guide, section 1)."""
    s = sorted(values)
    p = percentile(s, q)
    return {
        "n": len(s), "p50": percentile(s, 50.0), f"p{q:g}": p,
        "beyond": sum(1 for v in s if v > p),
    }
