"""End-to-end request tracing: spans, a per-process flight recorder, and
Perfetto-loadable export.

One request now crosses a router, a broker lease, a prefill replica, a KV
handoff, and a decode replica; aggregate reservoirs (``utils/metrics.py``)
cannot answer "where did request X's p95 go". Every hop records events into
a bounded per-process :class:`FlightRecorder`; ``GET /trace/{req_id}`` on
the producer stitches the fleet-wide timeline back together.

Clock discipline (enforced by graftlint's ``wall-clock-timer`` rule): every
event timestamp and span duration is ``time.monotonic()``. Exactly ONE
wall-clock read happens per process — the ``wall_anchor`` captured at
:meth:`FlightRecorder.export` — so cross-process stitching survives clock
skew: within a process ordering is monotonic-exact, across processes events
are aligned by ``wall_anchor + (t_mono - mono_anchor)``.

Work that belongs to an ITERATION of the worker loop and not to one request
(plan, dispatch, the blocking fetch, the callback over the batch, admission,
housekeeping) goes on the recorder's second, request-less store: the **loop
track**, a bounded ring of spans that name their parent. Request events point
into it with ``attrs["loop"]`` (the ``seq`` of the span that caused them).

What a replica does BEFORE its first iteration (runtime, weights, engine,
pools, prewarm) is on the loop track too, as **set-up spans**
(:class:`SetupSpan`, ``setup.*``): they name the set-up span open on their
thread as parent, take the seconds JAX reports for the programs compiled
inside them, and their sums wait in the recorder until an ``EngineMetrics``
exists to adopt them (``docs/observability.md``, "Set-up").

Tracing is ON by default at event granularity. Disable with
``LLMSS_TRACE=0`` in the environment or :func:`set_enabled` at runtime;
the disabled fast path is a single attribute check per call site.
"""

from __future__ import annotations

import itertools
import json
import os
import threading
import time
from collections import OrderedDict, deque

# Event names a stitched timeline must end with exactly once: the broker's
# response channel is the delivery contract's terminal ack.
TERMINAL_EVENTS = frozenset({"respond"})

# High-frequency per-group / per-renewal events the recorder may shed when a
# request's ring fills; lifecycle events (enqueue/lease/respond/...) are
# never shed in their favor.
_SHEDDABLE_PREFIXES = ("group_",)
_SHEDDABLE_NAMES = frozenset({"lease_renew", "handoff_renew"})


def _sheddable(name: str) -> bool:
    return name in _SHEDDABLE_NAMES or name.startswith(_SHEDDABLE_PREFIXES)


class Span:
    """A monotonic-duration span over one phase of one request.

    ``end()`` is idempotent and safe on the disabled path (``rec=None``).
    Usable as a context manager; an exception inside the block is recorded
    as an ``error`` attribute before the span closes.
    """

    __slots__ = ("_rec", "req_id", "name", "_t0", "_attrs", "_ended")

    def __init__(self, rec, req_id, name, attrs):
        self._rec = rec
        self.req_id = req_id
        self.name = name
        self._attrs = attrs
        self._t0 = time.monotonic()
        self._ended = False

    def end(self, **attrs) -> None:
        if self._ended:
            return
        self._ended = True
        if self._rec is None:
            return
        if attrs:
            self._attrs.update(attrs)
        self._rec.record(
            self.req_id, self.name,
            dur_s=time.monotonic() - self._t0, **self._attrs,
        )

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        if exc_type is not None:
            self._attrs.setdefault("error", exc_type.__name__)
        self.end()
        return False


class LoopSpan:
    """One span of the loop track: a phase of one worker-loop iteration.

    ``seq`` is known from the start, so children and request events can
    name the span while it is still open; the span enters the ring when it
    ends. ``annotate`` (``jax.profiler.TraceAnnotation``, handed in by the
    modules that import JAX) puts the same name on the profiler's host plane
    for the span's lifetime; ``on_close(name, seconds)`` feeds the cumulative
    counters at the same boundary. ``end()`` is idempotent.
    """

    __slots__ = ("_rec", "seq", "parent", "name", "_t0", "_attrs",
                 "_on_close", "_ann")

    def __init__(self, rec, seq, parent, name, on_close, annotate):
        self._rec = rec
        self.seq = seq
        self.parent = parent
        self.name = name
        self._attrs = None
        self._on_close = on_close
        self._ann = annotate(name) if annotate is not None else None
        if self._ann is not None:
            self._ann.__enter__()
        self._t0 = time.monotonic()

    def set(self, **attrs) -> None:
        if self._attrs is None:
            self._attrs = attrs
        else:
            self._attrs.update(attrs)

    def add(self, key: str, value: float) -> None:
        """Add ``value`` to the numeric attribute ``key`` (0 when unset)."""
        if self._attrs is None:
            self._attrs = {key: value}
        else:
            self._attrs[key] = self._attrs.get(key, 0.0) + value

    def end(self, **attrs) -> None:
        rec = self._rec
        if rec is None:
            return
        self._rec = None
        dur = time.monotonic() - self._t0
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        if attrs:
            self.set(**attrs)
        rec._close_loop_span(
            (self.seq, self.parent, self.name, self._t0, dur, self._attrs)
        )
        if self._on_close is not None:
            self._on_close(self.name, dur)

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        if exc_type is not None:
            self.set(error=exc_type.__name__)
        self.end()
        return False


# The set-up spans open on each thread, innermost last.
_OPEN_SETUP = threading.local()


def _open_setup() -> list:
    try:
        return _OPEN_SETUP.spans
    except AttributeError:
        _OPEN_SETUP.spans = []
        return _OPEN_SETUP.spans


class SetupSpan(LoopSpan):
    """A loop span of a replica's bring-up (``setup.*``): what runs before
    the first loop iteration. Its parent is the set-up span already open on
    its thread, and while it is the innermost one it takes the seconds JAX
    reports for the programs traced, lowered and compiled inside it
    (:func:`add_setup_seconds`). It closes into the recorder's set-up sums
    (:meth:`FlightRecorder.add_setup`), which reach ``/metrics``
    ``loop.spans`` whether or not an ``EngineMetrics`` existed yet."""

    __slots__ = ()

    def __init__(self, rec, seq, name, annotate):
        stack = _open_setup()
        super().__init__(
            rec, seq, stack[-1].seq if stack else None, name,
            rec.add_setup, annotate,
        )
        stack.append(self)

    def end(self, **attrs) -> None:
        if self._rec is not None:
            stack = _open_setup()
            if self in stack:
                stack.remove(self)
        super().end(**attrs)


class _NoLoopSpan:
    """What every ``loop_span`` call site gets while tracing is off: ONE
    shared object, so the off path makes no span, takes no lock and reads
    no clock (a ``set(k=v)`` still builds its keyword dictionary).
    ``seq`` is None: it names no span."""

    __slots__ = ()
    seq = None

    def set(self, **attrs) -> None:
        pass

    def add(self, key: str, value: float) -> None:
        pass

    def end(self, **attrs) -> None:
        pass

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        return False


NO_LOOP_SPAN = _NoLoopSpan()

# The loop track's thread name in a Chrome trace (no request id is spelled so).
LOOP_LANE = "worker loop"

# Loop spans kept: a traced window at 20 ms groups is ~50 iterations a
# second of ~10 spans each, so 16 k spans hold half a minute of it.
LOOP_RING_SPANS = 16384


class FlightRecorder:
    """Bounded ring of per-request event histories for one process, and the
    loop track beside it.

    Retains the ``max_requests`` most recently active requests; each keeps
    up to ``max_events`` events (overflow sheds group/renewal spam first and
    counts what it dropped, so a postmortem can see the ring was lossy).
    The loop track keeps the ``max_loop_spans`` most recently ended loop
    spans as plain tuples and counts what fell off the ring.
    """

    def __init__(
        self,
        max_requests: int = 256,
        max_events: int = 512,
        proc: str | None = None,
        max_loop_spans: int = LOOP_RING_SPANS,
    ):
        self.max_requests = max_requests
        self.max_events = max_events
        self.proc = proc or f"proc-{os.getpid()}"
        self._lock = threading.Lock()
        # req_id -> {"trace_id", "events": [dict], "dropped", "last": {name: t}}
        self._reqs: OrderedDict[str, dict] = OrderedDict()  # guarded_by: self._lock
        # The loop track has a lock of its own: its one writer is the worker
        # loop, which must not queue behind the front end's request events.
        self._loop_lock = threading.Lock()
        # (seq, parent_seq, name, t0, dur, attrs) in order of ENDING
        self._loop: deque = deque(maxlen=max_loop_spans)  # guarded_by: self._loop_lock
        self._loop_dropped = 0  # guarded_by: self._loop_lock
        self._loop_seq = itertools.count(1)
        # Set-up sums ({name: [seconds, count]}) wait here until an
        # EngineMetrics adopts them (``adopt_setup``): the runtime and the
        # weights come up before any engine exists. Afterwards they go
        # straight to the adopter.
        self._setup_held: dict[str, list] = {}  # guarded_by: self._loop_lock
        self._setup_sink = None  # guarded_by: self._loop_lock

    # -- recording ----------------------------------------------------------

    def record(
        self,
        req_id: str,
        name: str,
        *,
        trace_id: str | None = None,
        dur_s: float | None = None,
        proc: str | None = None,
        throttle_s: float | None = None,
        **attrs,
    ) -> None:
        t = time.monotonic()
        with self._lock:
            e = self._reqs.get(req_id)
            if e is None:
                while len(self._reqs) >= self.max_requests:
                    self._reqs.popitem(last=False)
                e = {"trace_id": None, "events": [], "dropped": 0, "last": {}}
                self._reqs[req_id] = e
            else:
                self._reqs.move_to_end(req_id)
            if trace_id is not None:
                e["trace_id"] = trace_id
            if throttle_s is not None:
                prev = e["last"].get(name)
                if prev is not None and t - prev < throttle_s:
                    return
            e["last"][name] = t
            ev = {"req_id": req_id, "name": name, "t": t}
            if dur_s is not None:
                ev["dur"] = dur_s
            if proc is not None:
                ev["proc"] = proc
            if attrs:
                ev["attrs"] = attrs
            events = e["events"]
            if len(events) >= self.max_events:
                if _sheddable(name):
                    e["dropped"] += 1
                    return
                for i, old in enumerate(events):
                    if _sheddable(old["name"]):
                        del events[i]
                        e["dropped"] += 1
                        break
                else:
                    e["dropped"] += 1
                    return
            events.append(ev)

    def start_span(self, req_id: str, name: str, **attrs) -> Span:
        return Span(self, req_id, name, attrs)

    def start_loop_span(
        self, name: str, parent: int | None = None, on_close=None,
        annotate=None,
    ) -> LoopSpan:
        return LoopSpan(
            self, next(self._loop_seq), parent, name, on_close, annotate,
        )

    def start_setup_span(self, name: str, annotate=None) -> SetupSpan:
        return SetupSpan(self, next(self._loop_seq), name, annotate)

    def add_setup(self, name: str, seconds: float) -> None:
        """Seconds of bring-up under ``name`` (a closed ``setup.*`` span, or
        one of JAX's own durations): to the adopter's ``loop.spans`` sums,
        or held until there is one."""
        with self._loop_lock:
            sink = self._setup_sink
            if sink is None:
                acc = self._setup_held.setdefault(name, [0.0, 0])
                acc[0] += seconds
                acc[1] += 1
                return
        sink(name, seconds)

    def adopt_setup(self, sink) -> None:
        """``sink(name, seconds, count=1)`` (``EngineMetrics.add_loop_span``)
        takes over the sums held so far and every later one: a process is
        one replica, and its set-up is on the ``/metrics`` of the engine
        built last."""
        with self._loop_lock:
            held, self._setup_held = self._setup_held, {}
            self._setup_sink = sink
        for name, (seconds, count) in held.items():
            sink(name, seconds, count)

    def _close_loop_span(self, span: tuple) -> None:
        with self._loop_lock:
            if len(self._loop) == self._loop.maxlen:
                self._loop_dropped += 1
            self._loop.append(span)

    def loop_spans(self) -> list[tuple]:
        with self._loop_lock:
            return list(self._loop)

    # -- readout ------------------------------------------------------------

    def events_for(self, req_id: str) -> list[dict]:
        with self._lock:
            e = self._reqs.get(req_id)
            return [dict(ev) for ev in e["events"]] if e else []

    def _events_view(self, req_id: str) -> list[dict]:
        """Shallow read-only snapshot (the list is copied, the event dicts
        are not — they are append-only and never mutated after insert).
        Hot-path twin of :meth:`events_for` for the respond-time cost
        derivation; callers must not modify the dicts."""
        with self._lock:
            e = self._reqs.get(req_id)
            return list(e["events"]) if e else []

    def req_ids(self) -> list[str]:
        with self._lock:
            return list(self._reqs)

    def clear(self) -> None:
        with self._lock:
            self._reqs.clear()
        with self._loop_lock:
            self._loop.clear()
            self._loop_dropped = 0
            self._setup_held.clear()
            self._setup_sink = None

    def export(
        self,
        req_ids=None,
        max_events: int | None = None,
    ) -> dict:
        """Snapshot this process's retained timelines for stitching.

        ``max_events`` bounds the total event count (most recent kept) so
        registry heartbeats stay small. The returned blob is JSON-safe.
        """
        with self._lock:
            reqs = {}
            budget = max_events if max_events is not None else None
            for rid in reversed(self._reqs):
                if req_ids is not None and rid not in req_ids:
                    continue
                e = self._reqs[rid]
                evs = [dict(ev) for ev in e["events"]]
                if budget is not None:
                    if budget <= 0:
                        break
                    evs = evs[-budget:]
                    budget -= len(evs)
                reqs[rid] = {
                    "trace_id": e["trace_id"],
                    "dropped": e["dropped"],
                    "events": evs,
                }
        out = {
            "proc": self.proc,
            "mono_anchor": time.monotonic(),
            # The ONE wall-clock read per process, taken only at export so
            # recorded timestamps stay monotonic (see module docstring).
            "wall_anchor": time.time(),
            "requests": reqs,
        }
        if req_ids is None and max_events is None:
            # The whole-recorder export carries the loop track too; the
            # bounded ones (registry heartbeats, one request's timeline)
            # stay as small as they were.
            with self._loop_lock:
                spans, dropped = list(self._loop), self._loop_dropped
            out["loop"] = {
                "spans": [list(sp) for sp in spans], "dropped": dropped,
            }
        return out


# -- module-level recorder (one per process) --------------------------------

_ENABLED = os.environ.get("LLMSS_TRACE", "1").lower() not in (
    "0", "false", "off",
)
_RECORDER = FlightRecorder()


def recorder() -> FlightRecorder:
    return _RECORDER


def enabled() -> bool:
    return _ENABLED


def set_enabled(on: bool) -> None:
    global _ENABLED
    _ENABLED = bool(on)


def record(req_id: str | None, name: str, **kw) -> None:
    """Record one event for ``req_id``; no-op when tracing is disabled."""
    if not _ENABLED or req_id is None:
        return
    _RECORDER.record(req_id, name, **kw)


def span(req_id: str | None, name: str, **attrs) -> Span:
    """A context-managed monotonic span; inert when tracing is disabled."""
    if not _ENABLED or req_id is None:
        return Span(None, req_id, name, attrs)
    return _RECORDER.start_span(req_id, name, **attrs)


def loop_span(
    name: str, parent: int | None = None, on_close=None, annotate=None,
):
    """Open a span on the loop track; its ``seq`` names it to children
    (``parent=``) and to request events (``loop=``). While tracing is off
    every call returns the one shared :data:`NO_LOOP_SPAN`."""
    if not _ENABLED:
        return NO_LOOP_SPAN
    return _RECORDER.start_loop_span(name, parent, on_close, annotate)


def setup_span(name: str, annotate=None):
    """Open a span of this replica's bring-up on the loop track
    (:class:`SetupSpan`); the shared :data:`NO_LOOP_SPAN` while tracing is
    off."""
    if not _ENABLED:
        return NO_LOOP_SPAN
    return _RECORDER.start_setup_span(name, annotate)


def add_setup_seconds(name: str, attr: str, seconds: float) -> None:
    """Seconds JAX spent tracing, lowering, compiling or fetching a program
    during bring-up: to the cumulative sum ``name`` and to attribute
    ``attr`` of the innermost set-up span open on the calling thread."""
    if not _ENABLED:
        return
    _RECORDER.add_setup(name, seconds)
    stack = _open_setup()
    if stack:
        stack[-1].add(attr, seconds)


def ensure_context(req) -> None:
    """Stamp a ``trace_id`` on a GenerateRequest-shaped object if missing.

    The trace id is the request id at first admission and survives
    re-prefill (only ``trace_attempt`` bumps), so one timeline covers every
    delivery attempt.
    """
    if getattr(req, "trace_id", None) is None:
        req.trace_id = req.id


# -- stitching --------------------------------------------------------------


def normalize(export: dict) -> list[dict]:
    """Flatten one process export to events with fleet-comparable
    ``ts_wall`` timestamps (wall = wall_anchor + (t - mono_anchor))."""
    base = export["wall_anchor"] - export["mono_anchor"]
    out = []
    for rid, blob in export.get("requests", {}).items():
        for ev in blob["events"]:
            e = dict(ev)
            e.setdefault("proc", export.get("proc", "?"))
            e["ts_wall"] = base + e["t"]
            e["trace_id"] = blob.get("trace_id")
            out.append(e)
    return out


def stitch(exports, req_id: str | None = None) -> list[dict]:
    """Merge process exports into one wall-aligned timeline, deduplicating
    events that reach the producer via more than one path (local recorder
    AND a registry heartbeat from a worker in the same process)."""
    seen = set()
    evs = []
    for ex in exports:
        for e in normalize(ex):
            if req_id is not None and e["req_id"] != req_id:
                continue
            key = (e["req_id"], e["name"], e["proc"], round(e["t"] * 1e6))
            if key in seen:
                continue
            seen.add(key)
            evs.append(e)
    evs.sort(key=lambda e: e["ts_wall"])
    return evs


def phase_breakdown(events) -> dict[str, float]:
    """Seconds attributed per phase: span durations summed by name, plus a
    synthesized ``queue_wait`` (first enqueue → first lease gap)."""
    tot: dict[str, float] = {}
    for e in events:
        d = e.get("dur")
        if d:
            tot[e["name"]] = tot.get(e["name"], 0.0) + d
    enq = next((e for e in events if e["name"] == "enqueue"), None)
    lease = next((e for e in events if e["name"] == "lease"), None)
    if enq and lease and lease["ts_wall"] > enq["ts_wall"]:
        tot["queue_wait"] = lease["ts_wall"] - enq["ts_wall"]
    return tot


def dominant_phase(events) -> str | None:
    tot = phase_breakdown(events)
    if not tot:
        return None
    return max(tot.items(), key=lambda kv: kv[1])[0]


def timeline(exports, req_id: str) -> dict | None:
    """The ``GET /trace/{req_id}`` body: stitched events + attribution."""
    evs = stitch(exports, req_id)
    if not evs:
        return None
    phases = phase_breakdown(evs)
    return {
        "req_id": req_id,
        "trace_id": next(
            (e["trace_id"] for e in evs if e.get("trace_id")), None,
        ),
        "total_s": round(evs[-1]["ts_wall"] - evs[0]["ts_wall"], 6),
        "dominant_phase": dominant_phase(evs),
        "phases": {k: round(v, 6) for k, v in sorted(phases.items())},
        "events": evs,
    }


def slowest(exports, n: int = 10, phase: str | None = None) -> list[dict]:
    """Tail-latency attribution: the ``n`` slowest retained requests by
    first-to-last event span, each with its dominant phase.

    ``phase`` reranks by time attributed to that phase alone (e.g.
    ``phase="kv_export"`` answers "which requests were slowest in
    handoff"), dropping requests that never entered it.
    """
    by_req: dict[str, list[dict]] = {}
    for e in stitch(exports):
        by_req.setdefault(e["req_id"], []).append(e)
    rows = []
    for rid, evs in by_req.items():
        phases = phase_breakdown(evs)
        rows.append({
            "req_id": rid,
            "trace_id": next(
                (e["trace_id"] for e in evs if e.get("trace_id")), None,
            ),
            "total_s": round(evs[-1]["ts_wall"] - evs[0]["ts_wall"], 6),
            "dominant_phase": dominant_phase(evs),
            "phases": {k: round(v, 6) for k, v in sorted(phases.items())},
            "n_events": len(evs),
        })
    if phase is not None:
        rows = [r for r in rows if r["phases"].get(phase)]
        for r in rows:
            r["rank_phase"] = phase
            r["phase_s"] = r["phases"][phase]
        rows.sort(key=lambda r: r["phase_s"], reverse=True)
    else:
        rows.sort(key=lambda r: r["total_s"], reverse=True)
    return rows[:max(0, int(n))]


# -- per-request cost attribution -------------------------------------------


def _ts(e: dict) -> float:
    # Stitched events carry fleet-aligned ``ts_wall``; raw local events
    # only ``t`` (monotonic). Either is internally consistent for deltas.
    return e.get("ts_wall", e["t"])


def _span_sum(evs, name: str) -> float:
    return sum(e.get("dur") or 0.0 for e in evs if e["name"] == name)


def _round6(v):
    return round(v, 6) if v is not None else None


def request_cost(events, assume_sorted: bool = False) -> dict | None:
    """Derive the compact ``RequestCost`` record from one request's events
    (stitched fleet-wide or raw from one recorder).

    Returns None unless the events contain a terminal ``respond`` — cost
    records exist only for settled requests, which is what makes the
    attribution exactly-once: a chaos-killed replica's partial timeline
    yields nothing; the surviving path that answers the request yields
    the one record, with every delivery attempt's prefill/handoff time
    already merged into the same req_id timeline.
    """
    # Single pass over time-sorted events (a hot-path constraint: brokers
    # derive this at every respond, so no per-phase rescans). One recorder's
    # events are appended in monotonic order (``assume_sorted``); only
    # stitched multi-process timelines pay for the sort.
    evs = list(events)
    if not assume_sorted:
        evs.sort(key=_ts)
    term_t = None
    t_attrs: dict = {}
    enq_t = lease_t = first_tok_t = None
    prefill = decode = wire = kv_span = kv_block_s = 0.0
    pending_push: list[float] = []  # handoff_push awaiting its next lease
    handoff_bytes = 0
    fin_tokens = 0
    attempts = 0
    reprefills = 0
    preemptions = 0
    slo_class = None
    trace_id = None
    for e in evs:
        name = e["name"]
        t = e.get("ts_wall", e["t"])
        a = e.get("attrs")
        if trace_id is None and e.get("trace_id"):
            trace_id = e["trace_id"]
        if a and "attempt" in a and a["attempt"] > attempts:
            attempts = a["attempt"]
        if name == "enqueue":
            if enq_t is None:
                enq_t = t
            if slo_class is None and a:
                slo_class = a.get("slo_class")
        elif name == "lease":
            if lease_t is None:
                lease_t = t
        elif name in ("admit", "adopt"):
            if first_tok_t is None:
                first_tok_t = t
        elif name == "prefill":
            prefill += e.get("dur") or 0.0
        elif name == "decode":
            decode += e.get("dur") or 0.0
        elif name == "handoff_push":
            pending_push.append(t)
            if a:
                handoff_bytes += a.get("bytes", 0)
        elif name == "handoff_lease":
            # Wire time: each push pairs with the FIRST lease at/after it
            # (sorted order ⇒ every pending push precedes this lease).
            for pt in pending_push:
                wire += t - pt
            pending_push.clear()
        elif name in ("kv_export", "kv_adopt"):
            kv_span += e.get("dur") or 0.0
        elif name == "finish":
            if a:
                fin_tokens += a.get("tokens", 0)
                kv_block_s += a.get("kv_block_s", 0.0)
        elif name == "reprefill":
            reprefills += 1
        elif name == "preempt":
            # Broker-side refund events only — the scheduler's paired
            # "evict" is deliberately not counted (one preemption, two
            # vantage points).
            preemptions += 1
        elif name in TERMINAL_EVENTS:
            term_t = t
            t_attrs = a or {}
    if term_t is None:
        return None

    queue_wait = None
    if enq_t is not None and lease_t is not None and lease_t >= enq_t:
        queue_wait = lease_t - enq_t
    # TTFT: arrival -> the scheduler's first-token resolution (``admit``
    # carries dur_s = submit->first-token; ``adopt`` marks a handoff row's
    # first decode-side token).
    ttft = None
    if enq_t is not None and first_tok_t is not None and (
        first_tok_t >= enq_t
    ):
        ttft = first_tok_t - enq_t
    tokens = t_attrs.get("n_tokens")
    if tokens is None:
        tokens = fin_tokens or None
    err = t_attrs.get("error")
    _r = _round6
    return {
        "req_id": evs[0]["req_id"],
        "trace_id": trace_id,
        "ok": bool(t_attrs.get("ok", err is None)),
        "error": err,
        "total_s": _r(term_t - _ts(evs[0])),
        "queue_wait_s": _r(queue_wait),
        "ttft_s": _r(ttft),
        "prefill_s": _r(prefill) or None,
        "handoff_s": _r(wire + kv_span) or None,
        "handoff_bytes": handoff_bytes or None,
        "decode_s": _r(decode) or None,
        "tokens": tokens,
        "kv_block_s": _r(kv_block_s) or None,
        "attempts": attempts or 1,
        "reprefills": reprefills,
        "preemptions": preemptions,
        "slo_class": slo_class,
        "n_events": len(evs),
    }


def derive_costs(exports) -> list[dict]:
    """One RequestCost per settled request across the stitched exports
    (requests without a terminal event are still in flight — or died with
    their replica — and are skipped)."""
    by_req: dict[str, list[dict]] = {}
    for e in stitch(exports):
        by_req.setdefault(e["req_id"], []).append(e)
    out = []
    for evs in by_req.values():
        cost = request_cost(evs)
        if cost is not None:
            out.append(cost)
    return out


def local_cost(req_id: str, error: str | None = None) -> dict | None:
    """RequestCost from THIS process's recorder (the terminal-time hook:
    brokers call it right after recording ``respond``). ``error``
    overrides the ok/error fields for responses settled exceptionally."""
    evs = _RECORDER._events_view(req_id)
    if not evs:
        return None
    cost = request_cost(evs, assume_sorted=True)
    if cost is None:
        return None
    if error is not None:
        cost["ok"] = False
        cost["error"] = error
    return cost


# -- trace-to-workload export -----------------------------------------------

WORKLOAD_FORMAT = "llmss-workload/1"


def export_workload(exports) -> dict:
    """Convert stitched timelines into a replayable arrival process — the
    input the deterministic fleet simulator consumes (capture -> replay).

    Each retained request becomes one row keyed by its FIRST ``enqueue``
    (re-routes and re-prefills are delivery mechanics, not arrivals);
    ``arrival_s`` offsets are relative to the earliest arrival so replay
    is start-time independent. ``slo_class`` carries each arrival's
    scheduling class so a replay reproduces the priority mix.
    """
    by_req: dict[str, list[dict]] = {}
    for e in stitch(exports):
        by_req.setdefault(e["req_id"], []).append(e)
    rows = []
    for rid, evs in by_req.items():
        enq = next((e for e in evs if e["name"] == "enqueue"), None)
        if enq is None:
            continue
        a = enq.get("attrs") or {}
        row = {
            "req_id": rid,
            "_arrival_ts": _ts(enq),
            "prompt_len": a.get("plen"),
            "max_new_tokens": a.get("max_new"),
            "prefix_hash": a.get("prefix"),
            "slo_class": a.get("slo_class"),
        }
        # Optional keys (absent in captures that predate session ids /
        # turn ordinals) so legacy workload files stay byte-for-byte
        # reproducible.
        if a.get("session"):
            row["session_id"] = a["session"]
            if a.get("turn") is not None:
                row["turn"] = int(a["turn"])
        rows.append(row)
    rows.sort(key=lambda r: r["_arrival_ts"])
    t0 = rows[0]["_arrival_ts"] if rows else 0.0
    for r in rows:
        r["arrival_s"] = round(r.pop("_arrival_ts") - t0, 6)
    # Per-session think time: the gap between consecutive turns of one
    # session (arrival-to-arrival). Stamped per row so a replay — or a
    # workload synthesized from capture statistics — can reproduce
    # multi-turn cadence, not just marginal arrival rates.
    last_arrival: dict[str, float] = {}
    for r in rows:
        sid = r.get("session_id")
        if sid is None:
            continue
        if sid in last_arrival:
            r["think_s"] = round(r["arrival_s"] - last_arrival[sid], 6)
        last_arrival[sid] = r["arrival_s"]
    return {
        "format": WORKLOAD_FORMAT,
        "n_requests": len(rows),
        "span_s": rows[-1]["arrival_s"] if rows else 0.0,
        "requests": rows,
    }


def to_chrome_trace(
    exports, req_id: str | None = None, counters=None,
) -> dict:
    """Chrome trace-event JSON (loadable at ui.perfetto.dev): one pid per
    process label, one tid per request, ``X`` complete events for spans and
    ``i`` instants for point events, timestamps in microseconds.

    An export's loop track becomes ONE more thread lane of its process
    (``worker loop``): ``X`` events nested by their parents, each with its
    ``seq`` and ``parent`` in ``args``. With ``req_id`` only the spans that
    overlap that request's events are drawn.

    ``counters`` is an optional list of devtel export blobs (each carrying
    its own ``mono_anchor``/``wall_anchor`` pair plus ``counters`` samples
    of ``{"t": mono, "tracks": {name: {series: value}}}``); each track
    becomes a ``C`` counter row under its process, wall-aligned exactly
    like span events, so KV occupancy / queue depth / memory ride the
    same timeline as the requests that waited on them.
    """
    exports = list(exports)
    evs = stitch(exports, req_id)
    out: list[dict] = []
    pids: dict[str, int] = {}
    tids: dict[tuple, int] = {}
    # Wall-align counter samples up front so t0 covers them too: a trace
    # that opens with a counter sample must not produce negative ts.
    csamples: list[tuple[str, float, dict]] = []  # (proc, ts_wall, tracks)
    for ex in counters or ():
        base = ex.get("wall_anchor", 0.0) - ex.get("mono_anchor", 0.0)
        proc = ex.get("proc", "?")
        for s in ex.get("counters", ()):
            csamples.append((proc, base + s.get("t", 0.0), s.get("tracks") or {}))
    # (proc, ts_wall of the start, span tuple), once per (proc, seq)
    lspans: list[tuple[str, float, list]] = []
    seen_spans = set()
    for ex in exports:
        base = ex.get("wall_anchor", 0.0) - ex.get("mono_anchor", 0.0)
        proc = ex.get("proc", "?")
        for sp in (ex.get("loop") or {}).get("spans", ()):
            ts = base + sp[3]
            if (proc, sp[0]) in seen_spans or (req_id is not None and (
                not evs or ts > evs[-1]["ts_wall"]
                or ts + sp[4] < evs[0]["ts_wall"]
            )):
                continue
            seen_spans.add((proc, sp[0]))
            lspans.append((proc, ts, sp))
    starts = [evs[0]["ts_wall"]] if evs else []
    starts += [ts for _, ts, _ in csamples] + [ts for _, ts, _ in lspans]
    t0 = min(starts, default=0.0)
    for e in evs:
        pid = pids.setdefault(e["proc"], len(pids) + 1)
        tids.setdefault((e["proc"], e["req_id"]), len(tids) + 1)
    for proc, _ts_w, _sp in lspans:
        pids.setdefault(proc, len(pids) + 1)
        tids.setdefault((proc, LOOP_LANE), len(tids) + 1)
    for proc, _ts_w, _tracks in csamples:
        pids.setdefault(proc, len(pids) + 1)
    for proc, pid in pids.items():
        out.append({
            "ph": "M", "pid": pid, "tid": 0, "name": "process_name",
            "args": {"name": proc},
        })
    for (proc, rid), tid in tids.items():
        out.append({
            "ph": "M", "pid": pids[proc], "tid": tid, "name": "thread_name",
            "args": {"name": rid},
        })
    for e in evs:
        pid = pids[e["proc"]]
        tid = tids[(e["proc"], e["req_id"])]
        args = dict(e.get("attrs") or {})
        if e.get("trace_id"):
            args["trace_id"] = e["trace_id"]
        ts = (e["ts_wall"] - t0) * 1e6
        if e.get("dur") is not None:
            out.append({
                "ph": "X", "pid": pid, "tid": tid, "name": e["name"],
                "cat": "span", "ts": ts - e["dur"] * 1e6,
                "dur": e["dur"] * 1e6, "args": args,
            })
        else:
            out.append({
                "ph": "i", "pid": pid, "tid": tid, "name": e["name"],
                "cat": "event", "ts": ts, "s": "t", "args": args,
            })
    for proc, ts_wall, (seq, parent, name, _t, dur, attrs) in lspans:
        out.append({
            "ph": "X", "pid": pids[proc], "tid": tids[(proc, LOOP_LANE)],
            "name": name, "cat": "loop", "ts": (ts_wall - t0) * 1e6,
            "dur": dur * 1e6,
            "args": {**(attrs or {}), "seq": seq, "parent": parent},
        })
    for proc, ts_wall, tracks in csamples:
        pid = pids[proc]
        for track, values in tracks.items():
            out.append({
                "ph": "C", "pid": pid, "tid": 0, "name": track,
                "cat": "counter", "ts": (ts_wall - t0) * 1e6,
                "args": dict(values),
            })
    return {"traceEvents": out, "displayTimeUnit": "ms"}


def chrome_trace_json(
    exports, req_id: str | None = None, counters=None,
) -> str:
    return json.dumps(to_chrome_trace(exports, req_id, counters=counters))
