"""Kernels: the share of the window's decode steps on which the sampler ran
its keep-set search (``ops/sampling.py``: a live row samples with ``top_k`` > 0
or ``top_p`` < 1) and not one of its two cheap branches - 100 x ``filter_steps``
over ``decode_steps``, both from the ``loop`` block of /metrics at the two
ends of the window. Nothing where the program has no such counter."""

from benchmark.lib import spans


def read(ctx):
    d = spans.loop_delta(ctx)
    if d is None or "filter_steps" not in d or not d.get("decode_steps"):
        return None
    return 100.0 * d["filter_steps"] / d["decode_steps"]
