"""Device telemetry plane: compile forensics and counter tracks for the
Perfetto timeline, riding the existing trace/metrics transport.

**Compile forensics.** A process-wide :class:`CompileObserver` records
every XLA compilation as an event: the ``jax.monitoring`` duration hook
when available (gives real durations), plus ``_cache_size()`` deltas over
the engine's jitted callables sampled at group boundaries (gives the
executable NAME and the triggering ``req_id`` when one is in flight).
After :meth:`CompileObserver.mark_steady` (called at prewarm completion)
any further compile is a *steady-state recompile* — a multi-second stall
the serving path promised would never happen — counted separately and
flagged on ``/slo``. Events surface at ``GET /compiles`` and as flight-
recorder spans, so an attributed recompile shows up in the request's own
timeline.

**Counter tracks.** :func:`record_counters` buffers point-in-time samples
(KV blocks in use/free, pool fragmentation, rows by phase, queue depths
by class, device live bytes) with monotonic timestamps; the export blob
carries the same ``mono_anchor``/``wall_anchor`` pair as the flight
recorder so ``trace.to_chrome_trace`` can emit them as wall-aligned
Chrome ``C`` counter events next to the request spans.

**Set-up.** Until ``mark_steady()`` the same hook keeps what JAX says of
every program it traces, lowers, compiles or fetches from the compile cache
(``setup.jax.*`` sums on ``/metrics`` ``loop.spans``, and the seconds on
the set-up span they were spent in); :func:`setup_span` opens a phase of a
replica's bring-up and :func:`warm` one call of a step program by a prewarm
(``docs/observability.md``, "Set-up").

The whole plane is inert when tracing is off (``LLMSS_TRACE=0``). What a
step should cost and what share of the roofline it reaches is the
benchmark's to say (``benchmark/lib/costs.py``), from a device trace.
"""

from __future__ import annotations

import threading
import time
from collections import deque

from llmss_tpu.utils import trace

# How many compile events / counter samples one process retains.
MAX_COMPILE_EVENTS = 512
MAX_COUNTER_SAMPLES = 2048


def enabled() -> bool:
    """Devtel is active iff tracing is: LLMSS_TRACE governs the whole
    observability plane."""
    return trace.enabled()


# -- set-up spans -------------------------------------------------------------

# The jitted callables a prewarm calls, by the attribute names the engine and
# the scheduler give them: the closed set of ``setup.prewarm.<family>`` spans.
PREWARM_SPANS = {
    family: f"setup.prewarm.{family}"
    for family in (
        "prefill", "prefill_row", "decode", "decode_group", "ragged_group",
        "admit_merge", "merge_positions", "insert", "zero_state",
    )
}


def setup_span(name: str):
    """A phase of this replica's bring-up as a span on the loop track
    (``trace.setup_span``) that is also a ``jax.profiler.TraceAnnotation``,
    so a profile taken over a start shows it on the host plane. Tracing off:
    the one shared no-op span."""
    if not trace.enabled():
        return trace.NO_LOOP_SPAN
    import jax

    return trace.setup_span(name, jax.profiler.TraceAnnotation)


def tree_bytes(tree) -> int:
    """Bytes of the arrays of a pytree, from their shapes (no device sync)."""
    import jax

    return sum(x.nbytes for x in jax.tree.leaves(tree))


def warm(family: str, key: dict, fn, *args, **kwargs):
    """``fn(*args, **kwargs)``: one call of a step program by a prewarm,
    inside a span ``setup.prewarm.<family>`` that carries the program's
    ``key``, whether the call compiled (``fn._cache_size()`` grew) and, from
    the monitoring hook, the seconds JAX spent on it."""
    if not trace.enabled():
        return fn(*args, **kwargs)
    size = getattr(fn, "_cache_size", None)
    before = size() if size is not None else None
    with setup_span(PREWARM_SPANS[family]) as sp:
        out = fn(*args, **kwargs)
        if size is not None:
            key = {**key, "compiled": size() > before}
        sp.set(**key)
    return out


# -- compile forensics --------------------------------------------------------

# JAX's durations over one jitted call's first run -> the ``/metrics`` name
# of their sum until ``mark_steady()``, and the attribute they add to on the
# set-up span they were spent in.
_JAX_SECONDS = {
    "/jax/core/compile/jaxpr_trace_duration": ("setup.jax.trace", "trace_s"),
    "/jax/core/compile/jaxpr_to_mlir_module_duration": (
        "setup.jax.lower", "lower_s"),
    "/jax/core/compile/backend_compile_duration": (
        "setup.jax.compile", "compile_s"),
    "/jax/compilation_cache/cache_retrieval_time_sec": (
        "setup.jax.cache_fetch", "cache_fetch_s"),
}
# Those of them that JAX brackets (a scalar at the start, the duration at
# the end) and that can therefore lie inside one another on a thread.
_JAX_NESTED = frozenset(k for k in _JAX_SECONDS if "/core/compile/" in k)
_JAX_DEPTH = threading.local()


class CompileObserver:
    """Process-wide compile recorder.

    Two independent sources feed :meth:`_record`:

    - the ``jax.monitoring`` duration listener (installed once per
      process; fires for every backend compile with a real duration but
      no executable name);
    - ``_cache_size()`` deltas over watched jitted callables, sampled at
      group boundaries by the scheduler (names the executable and
      attributes the triggering ``req_id`` when one is in flight, but
      has no duration).

    ``mark_steady()`` (prewarm completion) splits the event stream:
    everything after it is a steady-state recompile — counted in
    ``steady_recompiles`` and flagged on ``/slo``.
    """

    # Minimum seconds between _cache_size() sweeps: recompiles are
    # multi-second events, so the group-boundary sampler only needs to
    # pay the sweep cost a couple of times a second.
    SAMPLE_INTERVAL_S = 0.5

    def __init__(self):
        self._lock = threading.Lock()
        self._fns: dict[str, object] = {}  # guarded_by: self._lock
        self._sizes: dict[str, int] = {}  # guarded_by: self._lock
        self._events: deque = deque(maxlen=MAX_COMPILE_EVENTS)  # guarded_by: self._lock
        self.steady = False  # guarded_by: self._lock
        self.steady_recompiles = 0  # guarded_by: self._lock
        self._last_sample = float("-inf")

    # -- registration ---------------------------------------------------

    def watch(self, name: str, fn) -> None:
        """Track one jitted callable's compile cache (skipped when the
        jax version hides ``_cache_size`` — degrades like CompileGuard)."""
        if not hasattr(fn, "_cache_size"):
            return
        with self._lock:
            self._fns[name] = fn
            self._sizes[name] = fn._cache_size()

    def watch_obj(self, obj, prefix: str = "") -> None:
        """Track every jitted callable hanging off ``obj`` (the
        CompileGuard discovery idiom)."""
        for name, fn in vars(obj).items():
            if hasattr(fn, "_cache_size"):
                self.watch(prefix + name, fn)

    def mark_steady(self) -> None:
        """Prewarm is done: refresh baselines; any growth from here on is
        a steady-state recompile."""
        with self._lock:
            for name, fn in self._fns.items():
                self._sizes[name] = fn._cache_size()
            self.steady = True

    # -- sources --------------------------------------------------------

    def on_monitoring_scalar(self, event: str, value: float, **kw):
        """jax.monitoring scalar listener: JAX reports the START of each
        trace / lowering / backend compile under the duration's own key. A
        jitted function traced inside another's trace (or lowering) reports
        a duration of its own inside the outer one, so only the outermost
        of a thread is counted: this keeps the depth."""
        if event in _JAX_NESTED:
            _JAX_DEPTH.n = getattr(_JAX_DEPTH, "n", 0) + 1

    def on_monitoring_event(self, event: str, duration: float, **kw):
        """jax.monitoring duration listener. Until ``mark_steady()``: the
        seconds of every outermost trace, lowering and backend compile, and
        of every fetch from the compile cache (a part of the backend
        compile that wraps it), go to the ``setup.jax.*`` sums and to the
        set-up span open on this thread. Always: one compile event per
        backend compile, real duration, no name/req attribution."""
        if event in _JAX_NESTED:
            depth = _JAX_DEPTH.n = max(0, getattr(_JAX_DEPTH, "n", 0) - 1)
            if depth:
                return
        if not enabled():
            return
        names = _JAX_SECONDS.get(event)
        if names is not None:
            with self._lock:
                steady = self.steady
            if not steady:
                trace.add_setup_seconds(*names, float(duration))
        # Only the backend compile is the multi-second stall we forensic.
        if "backend_compile" not in event:
            return
        self._record(
            name=event.rsplit("/", 1)[-1], dur_s=float(duration),
            source="monitoring", req_id=None,
        )

    def maybe_sample(self, req_id: str | None = None) -> int:
        """Group-boundary ``_cache_size()`` sweep (throttled). Returns
        how many watched callables grew. The sweep itself is host-only
        bookkeeping — it never touches a device buffer — which is why
        the jit-host-sync exemption below is sound: ``_cache_size`` reads
        a host-side cache counter, not an array.
        """
        if not enabled():
            return 0
        now = time.monotonic()
        if now - self._last_sample < self.SAMPLE_INTERVAL_S:
            return 0
        self._last_sample = now
        grew = 0
        with self._lock:
            items = list(self._fns.items())
        for name, fn in items:
            # lint: ignore[jit-host-sync] — deliberate: _cache_size() is a
            # host-side compile-cache counter read (no device sync); the
            # whole point of this sampler is to observe the jit cache.
            size = fn._cache_size()
            with self._lock:
                was = self._sizes.get(name, 0)
                self._sizes[name] = size
            if size > was:
                grew += size - was
                self._record(
                    name=name, dur_s=None, source="cache_size",
                    req_id=req_id, delta=size - was,
                )
        return grew

    def _record(self, *, name, dur_s, source, req_id, **extra) -> None:
        t = time.monotonic()
        with self._lock:
            steady = self.steady
            if steady:
                self.steady_recompiles += 1
            ev = {
                "t": t, "name": name, "source": source,
                "steady_state": steady,
                **({"dur_s": round(dur_s, 6)} if dur_s is not None else {}),
                **({"req_id": req_id} if req_id else {}),
                **extra,
            }
            self._events.append(ev)
        # Compile spans ride the flight recorder too: attributed ones in
        # the triggering request's own timeline, the rest under a
        # process-wide pseudo request so they still stitch/export.
        trace.record(
            req_id or "__compiles__", "compile", dur_s=dur_s,
            executable=name, source=source, steady_state=steady,
        )

    # -- readout --------------------------------------------------------

    def events(self) -> list[dict]:
        with self._lock:
            return [dict(e) for e in self._events]

    def export(self) -> dict:
        with self._lock:
            return {
                "steady": self.steady,
                "steady_recompiles": self.steady_recompiles,
                "events": [dict(e) for e in self._events],
            }

    def reset(self) -> None:
        with self._lock:
            self._fns.clear()
            self._sizes.clear()
            self._events.clear()
            self.steady = False
            self.steady_recompiles = 0
            self._last_sample = float("-inf")


_OBSERVER = CompileObserver()
_HOOK_INSTALLED = False


def observer() -> CompileObserver:
    return _OBSERVER


def install_monitoring_hook() -> bool:
    """Register the compile-duration listener once per process (jax has
    no deregistration API, so the singleton observer receives forever).
    Returns whether the hook is installed."""
    global _HOOK_INSTALLED
    if _HOOK_INSTALLED:
        return True
    try:
        from jax._src import monitoring as _jm

        _jm.register_event_duration_secs_listener(
            _OBSERVER.on_monitoring_event
        )
        _jm.register_scalar_listener(_OBSERVER.on_monitoring_scalar)
        _HOOK_INSTALLED = True
    except Exception:  # noqa: BLE001 — private-but-stable; degrade quietly
        pass
    return _HOOK_INSTALLED


# -- counter tracks -----------------------------------------------------------

_COUNTER_LOCK = threading.Lock()
_COUNTER_SAMPLES: deque = deque(maxlen=MAX_COUNTER_SAMPLES)  # guarded_by: _COUNTER_LOCK


def record_counters(tracks: dict, t: float | None = None) -> None:
    """Buffer one point-in-time counter sample.

    ``tracks`` maps track name -> {series: numeric value}; each track
    becomes one Chrome ``C`` counter row in the exported timeline (series
    stack within the row). Callers throttle; this just appends.
    """
    if not enabled():
        return
    with _COUNTER_LOCK:
        _COUNTER_SAMPLES.append({
            "t": t if t is not None else time.monotonic(),
            "tracks": tracks,
        })


def _counter_samples() -> list[dict]:
    with _COUNTER_LOCK:
        return [dict(s) for s in _COUNTER_SAMPLES]


def device_memory_stats() -> dict | None:
    """Live/peak device bytes for device 0, or None when the backend
    doesn't report (CPU). Host-side C++ counters — never a device sync."""
    try:
        import jax

        stats = jax.local_devices()[0].memory_stats()
    except Exception:  # noqa: BLE001 — backend-optional surface
        return None
    if not stats:
        return None
    out = {}
    for key in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit"):
        if key in stats:
            out[key] = int(stats[key])
    return out or None


def largest_run(sorted_ids: list[int]) -> int:
    """Longest contiguous run in an ascending id list — the pool
    fragmentation signal (largest_run == len means unfragmented)."""
    best = cur = 1 if sorted_ids else 0
    for a, b in zip(sorted_ids, sorted_ids[1:]):
        cur = cur + 1 if b == a + 1 else 1
        if cur > best:
            best = cur
    return best


# -- export -------------------------------------------------------------------


def export() -> dict:
    """This process's devtel blob: counter samples + compile events,
    wall-anchored exactly like a FlightRecorder export so the producer
    can stitch fleet-wide."""
    return {
        "proc": trace.recorder().proc,
        "mono_anchor": time.monotonic(),
        # The ONE wall-clock read per export (anchor discipline shared
        # with FlightRecorder.export).
        "wall_anchor": time.time(),
        "counters": _counter_samples(),
        "compiles": _OBSERVER.export(),
    }


def dedup_exports(exports) -> list[dict]:
    """One blob per process (in-process fleets surface the same module
    singleton through the local path AND several worker heartbeats)."""
    seen: set[str] = set()
    out = []
    for ex in exports:
        proc = ex.get("proc")
        if proc in seen:
            continue
        seen.add(proc)
        out.append(ex)
    return out


def compiles_payload(exports) -> dict:
    """GET /compiles body: fleet-wide compile events (wall-aligned,
    newest last) + the steady-state recompile rollup."""
    events = []
    steady_recompiles = 0
    for ex in dedup_exports(exports):
        base = ex.get("wall_anchor", 0.0) - ex.get("mono_anchor", 0.0)
        blob = ex.get("compiles") or {}
        steady_recompiles += int(blob.get("steady_recompiles", 0))
        for e in blob.get("events", ()):
            ev = dict(e)
            ev["ts_wall"] = base + ev.pop("t", 0.0)
            ev["proc"] = ex.get("proc", "?")
            events.append(ev)
    events.sort(key=lambda e: e["ts_wall"])
    return {
        "n_compiles": len(events),
        "steady_recompiles": steady_recompiles,
        "compiles": events,
    }


def recompile_flag(exports) -> dict:
    """The /slo block: did any process recompile after declaring steady
    state? ``flagged`` going true mid-serve means some request ate a
    multi-second XLA stall the SLO math didn't budget for."""
    n = 0
    for ex in dedup_exports(exports):
        n += int((ex.get("compiles") or {}).get("steady_recompiles", 0))
    return {"steady_state_recompiles": n, "flagged": n > 0}


def reset() -> None:
    """Test hook: clear every module-level accumulator (the monitoring
    hook stays installed — it re-feeds the singleton observer)."""
    _OBSERVER.reset()
    with _COUNTER_LOCK:
        _COUNTER_SAMPLES.clear()
