"""What the per-layer metrics of set-up read: the ``setup.*`` sums of the
``loop.spans`` map of ``/metrics``, which the program keeps while tracing is
on (``docs/observability.md``, "Set-up"; ``utils/trace.py: SetupSpan``,
``utils/devtel.py: CompileObserver.on_monitoring_event``).

- ``setup.runtime``, ``setup.weights``, ``setup.engine``, ``setup.cache``,
  ``setup.prewarm``: spans of a replica's bring-up, in the order it runs
  them; each sum is the seconds of the spans of that name.
- ``setup.jax.trace``, ``setup.jax.lower``, ``setup.jax.compile``: counters,
  the seconds ``jax.monitoring`` reports for every outermost trace, lowering
  to MLIR and backend compile (on a warm compile cache: the fetch and the
  deserialisation it wraps) until prewarm is over: the WHOLE set-up's, so
  the weights' program and the pool's zeros are in them (half a second to
  three seconds warm) and two of them together can pass ``setup.prewarm``.

They are ABSOLUTE: set-up is over before the window begins, so a reader takes
``ctx["metrics_after"]`` alone and no difference. A program that records no
such span (tracing off, or any tree before PR 42) gives ``None`` everywhere.

The four readers at the end move ``setup_s`` (layer ``set-up``, unit ``s``,
lower is better) and are NOT per-layer metrics of the manifest yet, like the
readers of ``lib/moe.py`` and ``lib/gdn.py`` and for the same reason: the
accepted ``tests/benchmark/test_bench_sampler_search.py`` pins
``per_layer[-1]``, so an entry can only be put in the middle of the list,
which the driver reads as an edit of what was there. Declaring one is a file
``layer_metrics/<name>.py`` of one line (``from benchmark.lib.setup import
<name> as read``) and its entry.
"""

from __future__ import annotations

BEFORE_PREWARM = ("setup.runtime", "setup.weights", "setup.engine",
                  "setup.cache")


def seconds(ctx: dict, *names: str) -> float | None:
    """The summed seconds of ``names`` in ``/metrics`` ``loop.spans`` after
    the window; ``None`` unless the program recorded every one of them."""
    spans = ((ctx.get("metrics_after") or {}).get("loop") or {}).get("spans")
    if not spans or any(n not in spans for n in names):
        return None
    return sum(spans[n]["seconds"] for n in names)


def setup_before_prewarm_s(ctx: dict) -> float | None:
    """``program_span``: runtime and mesh, the weights (the host's part),
    the engine's jitted callables, the batcher's pools."""
    return seconds(ctx, *BEFORE_PREWARM)


def prewarm_s(ctx: dict) -> float | None:
    """``program_span``: ``ContinuousWorker.prewarm`` whole: the inside twin
    of the ``prewarm.seconds`` that ``server.py`` logs from outside."""
    return seconds(ctx, "setup.prewarm")


def prewarm_trace_lower_s(ctx: dict) -> float | None:
    """``program_counter``: Python tracing the step programs and lowering
    them to MLIR: the share of set-up no compile cache saves."""
    return seconds(ctx, "setup.jax.trace", "setup.jax.lower")


def prewarm_compile_s(ctx: dict) -> float | None:
    """``program_counter``: the compiler cold; warm, the fetch from the
    compile cache and the deserialisation."""
    return seconds(ctx, "setup.jax.compile")
