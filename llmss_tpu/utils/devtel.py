"""Device telemetry plane: roofline accounting, compile forensics, and
counter tracks for the Perfetto timeline.

Everything the observability stack reported before this module was
host-observed wall clock: a span can say *a group took 3.1 ms* but not
*whether the hardware was busy*. This plane adds device-side truth in
three layers, all riding the existing trace/metrics transport:

**Roofline accounting.** At prewarm time each compiled executable's FLOPs
and HBM bytes are derived from ``jit(...).lower(...).cost_analysis()``
(the unoptimized-HLO cost model — no second XLA compile) and cached in a
:class:`CostTable` keyed by executable signature. When the backend
returns nothing the cost falls back to an analytical model computed from
config shapes (:class:`EngineCostModel` — the same roofline arithmetic
``bench.py`` applies offline). At each group/ragged dispatch the
scheduler folds the measured fetch-to-fetch interval into achieved
MFU/MBU via :func:`fold`: windowed histograms (``mfu_<kernel>`` /
``mbu_<kernel>``) plus last-value gauges for ``/metrics``. Kernel classes
are a closed enum (:data:`KERNEL_CLASSES`) so the metric label set is
bounded by construction.

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

The whole plane is inert when tracing is off (``LLMSS_TRACE=0``) and can
be disabled independently with ``LLMSS_DEVTEL=0``; the enabled fast path
adds one attribute check per call site. MFU is computed against the
device peaks in :data:`DEVICE_PEAKS` (override with ``DEVTEL_PEAK_TFLOPS``
/ ``DEVTEL_HBM_GBPS``); on a CPU backend the analytical numbers are
roofline-shaped but priced by the table's "cpu" row (the v5e figures), so
absolute MFU/MBU values are only meaningful on real accelerators
(docs/observability.md).
"""

from __future__ import annotations

import os
import threading
import time
from collections import deque

from llmss_tpu.utils import metrics as metrics_mod
from llmss_tpu.utils import trace
from llmss_tpu.utils.signatures import METERED_CLASSES, signature_str

# Closed kernel-class enum: every MFU/MBU series name is ``mfu_<class>``/
# ``mbu_<class>`` with <class> drawn from here, so the graftlint
# unbounded-metric-label rule holds by construction. Shared with the
# shardcheck program registry via utils/signatures.py — one vocabulary
# for both planes, so a class added to one cannot silently miss the
# other.
KERNEL_CLASSES = METERED_CLASSES

# Utilization histogram bounds (MFU/MBU are fractions in [0, 1]).
UTIL_BOUNDS = (
    0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
    0.2, 0.3, 0.4, 0.5, 0.6, 0.7, 0.8, 0.9, 1.0,
)

# device_kind -> (peak dense TFLOP/s bf16, HBM GB/s), keyed by the string
# ``jax.devices()[0].device_kind`` reports (Google Cloud TPU documentation
# per generation; v5e is "TPU v5 lite", v5p plain "TPU v5", v6e "TPU v6
# lite" — checked against ``topologies.get_topology_desc``). A TPU that is
# not in the table is an error, not a default. The "cpu" row is the CPU
# backend's own: not a CPU's peaks but the v5e figures, so functional runs
# keep producing finite, roofline-SHAPED gauges (see module docstring
# caveat) — nothing priced by it is a device metric.
DEVICE_PEAKS = {
    "TPU v6 lite": (918.0, 1640.0),
    "TPU v5": (459.0, 2765.0),
    "TPU v5 lite": (197.0, 819.0),
    "TPU v4": (275.0, 1228.0),
    "cpu": (197.0, 819.0),
}

# How many compile events / counter samples one process retains.
MAX_COMPILE_EVENTS = 512
MAX_COUNTER_SAMPLES = 2048

_DEVTEL_ON = os.environ.get("LLMSS_DEVTEL", "1").lower() not in (
    "0", "false", "off",
)


def enabled() -> bool:
    """Devtel is active iff tracing is (LLMSS_TRACE governs the whole
    observability plane) and LLMSS_DEVTEL has not opted out."""
    return _DEVTEL_ON and trace.enabled()


def set_enabled(on: bool) -> None:
    global _DEVTEL_ON
    _DEVTEL_ON = bool(on)


_PEAKS: tuple[float, float] | None = None


def device_peaks() -> tuple[float, float]:
    """(peak FLOP/s, peak HBM bytes/s) for device 0, resolved once.

    Env overrides win (``DEVTEL_PEAK_TFLOPS`` / ``DEVTEL_HBM_GBPS`` —
    the latter intentionally shares units with bench.py's
    ``BENCH_HBM_GBPS``); otherwise the device_kind is looked up in
    :data:`DEVICE_PEAKS`, and an unknown device is an error.
    """
    global _PEAKS
    if _PEAKS is not None:
        return _PEAKS
    import jax

    kind = jax.devices()[0].device_kind
    if kind not in DEVICE_PEAKS:
        raise KeyError(
            f"no peaks for device_kind {kind!r} in devtel.DEVICE_PEAKS "
            f"(known: {sorted(DEVICE_PEAKS)}); add its row with a source"
        )
    tf, gb = DEVICE_PEAKS[kind]
    tf = float(os.environ.get("DEVTEL_PEAK_TFLOPS", tf))
    gb = float(os.environ.get(
        "DEVTEL_HBM_GBPS", os.environ.get("BENCH_HBM_GBPS", gb),
    ))
    _PEAKS = (tf * 1e12, gb * 1e9)
    return _PEAKS


def _reset_peaks() -> None:  # test hook
    global _PEAKS
    _PEAKS = None


# -- roofline cost table ------------------------------------------------------


class KernelCost:
    """FLOPs + HBM bytes for one compiled executable signature."""

    __slots__ = ("flops", "hbm_bytes", "source")

    def __init__(self, flops: float, hbm_bytes: float, source: str):
        self.flops = float(flops)
        self.hbm_bytes = float(hbm_bytes)
        self.source = source  # "cost_analysis" | "analytical"

    def to_dict(self) -> dict:
        return {
            "flops": self.flops, "hbm_bytes": self.hbm_bytes,
            "source": self.source,
        }


def _parse_cost_analysis(ca) -> tuple[float, float] | None:
    """(flops, bytes) out of a ``cost_analysis()`` result — a dict in
    recent jax, a list of per-computation dicts in older releases —
    or None when the backend returned nothing usable."""
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    if not isinstance(ca, dict):
        return None
    flops = ca.get("flops", 0.0) or 0.0
    nbytes = ca.get("bytes accessed", 0.0) or 0.0
    if flops <= 0.0 and nbytes <= 0.0:
        return None
    return float(flops), float(nbytes)


class CostTable:
    """Per-executable-signature cost cache.

    ``derive`` is the single entry point: a cache hit never invokes the
    (trace-cost) ``lower_thunk``; a miss tries the backend cost model and
    falls back to the analytical estimate. Read by the per-dispatch fold
    path, so lookups are one dict get under a lock.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._costs: dict[tuple, KernelCost] = {}  # guarded_by: self._lock

    def get(self, key: tuple) -> KernelCost | None:
        # Lockless by design: entries are write-once (``put`` under the
        # lock, never mutated after), and a CPython dict read is safe
        # against concurrent inserts — this is the per-dispatch hot path.
        return self._costs.get(key)

    def put(self, key: tuple, cost: KernelCost) -> KernelCost:
        with self._lock:
            self._costs[key] = cost
        return cost

    def derive(
        self, key: tuple, lower_thunk=None,
        fallback: tuple[float, float] | None = None,
    ) -> KernelCost | None:
        """Cost for ``key``: cached value, else ``lower_thunk()`` (a
        callable returning a ``jax.stages.Lowered``-shaped object) run
        through ``cost_analysis()``, else the analytical ``fallback``
        (flops, bytes). Returns None only when every source fails."""
        hit = self.get(key)
        if hit is not None:
            return hit
        if lower_thunk is not None:
            try:
                parsed = _parse_cost_analysis(lower_thunk().cost_analysis())
            except Exception:  # noqa: BLE001 — backend support is optional
                parsed = None
            if parsed is not None:
                return self.put(key, KernelCost(*parsed, "cost_analysis"))
        if fallback is not None:
            return self.put(key, KernelCost(*fallback, "analytical"))
        return None

    def export(self) -> dict:
        with self._lock:
            return {
                signature_str(key): c.to_dict()
                for key, c in self._costs.items()
            }

    def clear(self) -> None:
        with self._lock:
            self._costs.clear()


_COSTS = CostTable()


def costs() -> CostTable:
    """The module-level per-process cost table."""
    return _COSTS


class EngineCostModel:
    """Analytical FLOPs/bytes from config shapes — the fallback when the
    backend's ``cost_analysis`` returns nothing, and the lazy source for
    signatures first seen mid-serve (deriving via ``lower()`` there would
    re-trace on the hot path).

    Same roofline discipline as bench.py: a decode step streams every
    parameter byte plus each row's live (bucketed) KV prefix from HBM;
    matmul FLOPs are ``2 * params`` per token plus the attention
    contractions ``4 * n_layers * n_heads * head_dim`` per token per
    context position. Deliberately first-order — it prices the roofline,
    not the exact op mix.
    """

    __slots__ = ("param_count", "param_bytes", "_attn_flops_ctx",
                 "_kv_bytes_row_ctx", "max_seq_len")

    def __init__(
        self, cfg, param_count: int, param_bytes: int,
        kv_itemsize: int = 2, max_seq_len: int | None = None,
    ):
        self.param_count = int(param_count)
        self.param_bytes = int(param_bytes)
        # qk^T + attn@v: 2 contractions x 2 flops per MAC, per layer,
        # per head, per head_dim lane, per context position, per token.
        self._attn_flops_ctx = (
            4.0 * cfg.n_layers * cfg.n_heads * cfg.head_dim
        )
        # k + v read per context position per row per step.
        self._kv_bytes_row_ctx = (
            2.0 * cfg.n_layers * cfg.n_kv_heads * cfg.head_dim * kv_itemsize
        )
        self.max_seq_len = max_seq_len or cfg.max_position_embeddings

    def step_cost(
        self, batch: int, steps: int, kv_len: int | None,
        prefill_tokens: int = 0,
    ) -> tuple[float, float]:
        """(flops, bytes) for ``steps`` fused decode steps at ``batch``
        rows reading a ``kv_len``-bucketed context, plus optional ragged
        ``prefill_tokens`` streamed through the same dispatch."""
        ctx = kv_len if kv_len else self.max_seq_len
        tokens = batch * steps + prefill_tokens
        flops = (
            2.0 * self.param_count * tokens
            + self._attn_flops_ctx * ctx * tokens
        )
        nbytes = (
            float(self.param_bytes) * steps
            + self._kv_bytes_row_ctx * ctx * batch * steps
        )
        return flops, nbytes

    @property
    def kv_bytes_per_token(self) -> float:
        """KV bytes one context position costs one row (k + v across
        layers) — the unit the fleet simulator prices handoff wire
        transfers and paged-block budgets in."""
        return self._kv_bytes_row_ctx


def roofline_seconds(
    flops: float, hbm_bytes: float,
    peak_flops: float, peak_hbm_bps: float,
) -> float:
    """Roofline execution time: the kernel runs at whichever ceiling it
    hits first, so its duration is the max of compute time and memory
    time. Shared by the MFU/MBU plane's inverse (achieved/peak) and the
    fleet simulator's cost model, so sim seconds and telemetry
    utilization are two views of one model."""
    compute = flops / peak_flops if peak_flops > 0 else 0.0
    memory = hbm_bytes / peak_hbm_bps if peak_hbm_bps > 0 else 0.0
    return max(compute, memory)


def param_stats(params) -> tuple[int, int]:
    """(element count, bytes) over a params pytree — shape/dtype metadata
    only, never a device sync."""
    import jax
    import numpy as np

    count = nbytes = 0
    for leaf in jax.tree_util.tree_leaves(params):
        size = int(getattr(leaf, "size", 0) or 0)
        count += size
        dt = getattr(leaf, "dtype", None)
        nbytes += size * (np.dtype(dt).itemsize if dt is not None else 4)
    return count, nbytes


# -- MFU/MBU folding ----------------------------------------------------------

# kernel class -> (mfu hist, mbu hist, registry generation); rebuilt when
# the registry is cleared (tests) so folds never land in orphaned series.
_UTIL_SINKS: dict[str, tuple] = {}
_LAST_UTIL: dict[str, dict] = {}  # kernel class -> last gauge sample
# kernel class -> [n, dur_sum, flops_sum, bytes_sum, source, last_flush_t]
_FOLD_ACC: dict[str, list] = {}  # guarded_by: _UTIL_LOCK
_UTIL_LOCK = threading.Lock()

FOLD_FLUSH_S = 0.05  # accumulator -> histogram drain cadence


def fold(kind: str, dur_s: float, cost: KernelCost | None) -> None:
    """Fold one measured dispatch interval into achieved MFU/MBU.

    Hot path (once per group fetch): a dict get and five float adds into
    a per-kind accumulator — the <= 2 us/group budget (DEVTEL_BENCH.json)
    rules out touching the histogram locks per group. Every
    ``FOLD_FLUSH_S`` the accumulator drains into the windowed MFU/MBU
    histograms as one duration-weighted sample (``sum(flops) /
    (peak * sum(dur))``); readers (``last_util``/``export``) force a
    drain first, so nothing is ever stuck in the accumulator. No-op when
    the plane is off or the cost is unknown.
    """
    if cost is None or dur_s <= 0.0 or not enabled():
        return
    now = time.monotonic()
    with _UTIL_LOCK:
        acc = _FOLD_ACC.get(kind)
        if acc is None:
            acc = _FOLD_ACC[kind] = [0, 0.0, 0.0, 0.0, cost.source, now]
        acc[0] += 1
        acc[1] += dur_s
        acc[2] += cost.flops
        acc[3] += cost.hbm_bytes
        acc[4] = cost.source
        if now - acc[5] < FOLD_FLUSH_S:
            return
    _flush_kind(kind, now)


def _flush_kind(kind: str, now: float) -> None:
    """Drain one kind's fold accumulator into the histograms/gauges."""
    with _UTIL_LOCK:
        acc = _FOLD_ACC.get(kind)
        if acc is None or acc[0] == 0:
            return
        n, dur, fl, by, src = acc[0], acc[1], acc[2], acc[3], acc[4]
        acc[0] = 0
        acc[1] = acc[2] = acc[3] = 0.0
        acc[5] = now
    peak_f, peak_b = device_peaks()
    mfu = fl / (peak_f * dur)
    mbu = by / (peak_b * dur)
    # >1 means the cost model over-prices the kernel (or peaks are
    # misconfigured) — clamp so the gauges stay in [0, 1] by contract.
    if mfu > 1.0:
        mfu = 1.0
    if mbu > 1.0:
        mbu = 1.0
    reg = metrics_mod.series()
    sinks = _UTIL_SINKS.get(kind)
    if sinks is None or sinks[2] != reg.generation():
        sinks = _UTIL_SINKS[kind] = (
            reg.histogram(f"mfu_{kind}", UTIL_BOUNDS),
            reg.histogram(f"mbu_{kind}", UTIL_BOUNDS),
            reg.generation(),
        )
    epoch = int(now // metrics_mod.DEFAULT_WINDOW_BUCKET_S)
    i = epoch % metrics_mod.DEFAULT_WINDOW_BUCKETS
    sinks[0]._observe_at(i, epoch, mfu)
    sinks[1]._observe_at(i, epoch, mbu)
    # No rounding on the gauges: CPU functional runs produce MFU ~1e-9
    # (tiny model, v5e peaks) and the in-(0,1] contract must survive.
    with _UTIL_LOCK:
        _LAST_UTIL[kind] = {
            "mfu": mfu, "mbu": mbu,
            "dur_s": round(dur / n, 6), "source": src, "t": now,
        }


def flush_folds() -> None:
    """Drain every kind's accumulator (readers call this so gauges and
    histograms reflect folds newer than the last throttled drain)."""
    now = time.monotonic()
    with _UTIL_LOCK:
        kinds = [k for k, a in _FOLD_ACC.items() if a[0]]
    for kind in kinds:
        _flush_kind(kind, now)


def last_util() -> dict:
    """Last-value MFU/MBU gauges per kernel class (JSON-safe copy)."""
    flush_folds()
    with _UTIL_LOCK:
        return {k: dict(v) for k, v in _LAST_UTIL.items()}


def merged_gauges(exports) -> dict:
    """``{"mfu": {kernel: v}, "mbu": {kernel: v}}`` across devtel export
    blobs — per kernel class, the most recent sample wins (exports carry
    per-process monotonic anchors; recency is judged per blob)."""
    best: dict[str, tuple[float, dict]] = {}
    for ex in exports:
        for kind, g in (ex.get("util") or {}).items():
            age = ex.get("mono_anchor", 0.0) - g.get("t", 0.0)
            prev = best.get(kind)
            if prev is None or age < prev[0]:
                best[kind] = (age, g)
    out: dict = {"mfu": {}, "mbu": {}}
    for kind, (_age, g) in best.items():
        out["mfu"][kind] = g.get("mfu")
        out["mbu"][kind] = g.get("mbu")
    return out


def phase_utilization(exports=None) -> dict:
    """Per-phase utilization signal for the fleet controller.

    Prefill saturates FLOPs (MFU) while decode saturates HBM bandwidth
    (MBU) — the asymmetry that motivates P:D ratio tuning — so the
    controller steers prefill capacity on the hottest MFU gauge and
    decode capacity on the hottest MBU gauge. Reads the in-process
    gauges by default, or a list of devtel export blobs when aggregating
    across replicas. Missing gauges read 0.0 (no signal, not "idle" —
    the controller's hysteresis treats 0 as no pressure either way)."""
    if exports is not None:
        g = merged_gauges(exports)
        mfu = [v for v in g["mfu"].values() if v is not None]
        mbu = [v for v in g["mbu"].values() if v is not None]
    else:
        lu = last_util()
        mfu = [g["mfu"] for g in lu.values() if g.get("mfu") is not None]
        mbu = [g["mbu"] for g in lu.values() if g.get("mbu") is not None]
    return {
        "prefill": max(mfu) if mfu else 0.0,
        "decode": max(mbu) if mbu else 0.0,
    }


# -- compile forensics --------------------------------------------------------


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

    def on_monitoring_event(self, event: str, duration: float, **kw):
        """jax.monitoring duration listener: one event per backend
        compile, real duration, no name/req attribution."""
        if "compile" not in event or not enabled():
            return
        # Trace/lowering sub-phases also carry "compile" in their key;
        # only the backend compile is the multi-second stall we forensic.
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

    def record_compile(
        self, name: str, *, dur_s: float | None = None,
        req_id: str | None = None, arg_shapes=None,
    ) -> None:
        """Explicit compile event (callers that already know a compile
        happened — e.g. an engine path that just paid a cold bucket)."""
        if not enabled():
            return
        self._record(
            name=name, dur_s=dur_s, source="explicit", req_id=req_id,
            **({"arg_shapes": arg_shapes} if arg_shapes else {}),
        )

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
    """This process's devtel blob: counter samples + compile events +
    last-value gauges + the cost table, wall-anchored exactly like a
    FlightRecorder export so the producer can stitch fleet-wide."""
    return {
        "proc": trace.recorder().proc,
        "mono_anchor": time.monotonic(),
        # The ONE wall-clock read per export (anchor discipline shared
        # with FlightRecorder.export).
        "wall_anchor": time.time(),
        "counters": _counter_samples(),
        "compiles": _OBSERVER.export(),
        "util": last_util(),
        "costs": _COSTS.export(),
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
    global _PEAKS
    _OBSERVER.reset()
    _COSTS.clear()
    with _COUNTER_LOCK:
        _COUNTER_SAMPLES.clear()
    with _UTIL_LOCK:
        _LAST_UTIL.clear()
        _FOLD_ACC.clear()
    _UTIL_SINKS.clear()
    _PEAKS = None
