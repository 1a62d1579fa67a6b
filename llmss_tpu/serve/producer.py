"""Producer: the HTTP frontend.

≙ reference ``producer_server.py`` (FastAPI + uvicorn): one route,
``POST /generate``, same JSON schema. Implemented on the stdlib threading
HTTP server so the serving path has zero non-baked dependencies. Unlike the
reference — which busy-polls the shared response queue and can return another
caller's response (``producer_server.py:50-54``) — each handler waits on its
own request id.
"""

from __future__ import annotations

import collections
import json
import math
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from urllib.parse import parse_qs, urlsplit

from llmss_tpu.serve.broker import Broker
from llmss_tpu.serve.protocol import (
    SLO_CLASS_BATCH,
    STATE_DEAD,
    STATE_DRAINING,
    STATE_READY,
    GenerateRequest,
)
from llmss_tpu.utils import devtel
from llmss_tpu.utils import metrics as metrics_mod
from llmss_tpu.utils import trace
from llmss_tpu.utils.metrics import profile_trace, render_prometheus

# Prometheus text exposition version served for /metrics?format=prometheus.
_PROM_CONTENT_TYPE = "text/plain; version=0.0.4; charset=utf-8"

# jax.profiler keeps one global trace per process, so one in-flight
# POST /profile per process is the correct serialization unit. The lock
# guards the slot fields below; the slot itself expires at its deadline
# (duration + grace) so a crashed caller can never wedge profiling until
# restart — the next POST force-stops the orphaned profiler and takes
# over.
_PROFILE_LOCK = threading.Lock()
_PROFILE_ACTIVE = 0  # generation of the in-flight profile, 0 when idle; guarded_by: _PROFILE_LOCK
_PROFILE_GEN = 0  # guarded_by: _PROFILE_LOCK
_PROFILE_DEADLINE = 0.0  # monotonic expiry of the active slot; guarded_by: _PROFILE_LOCK
_PROFILE_GRACE_S = 5.0

# A stream handler waits on the request's stream channel and, each time that
# wait runs out, looks for the terminal response. While it looks it is deaf
# to tokens: one that comes meanwhile is written when the look ends. So the
# look is a check, far shorter than the time between two tokens; at tens of
# milliseconds a client's first-token times bunch at multiples of the cycle.
_DONE_CHECK_S = 0.001

# Class-aware admission: the fraction of max_queue_depth each class may
# fill before shedding. Batch saturates at half the backlog so a batch
# burst leaves queue room for latency-sensitive traffic even before the
# brownout ladder engages; interactive and standard keep the full depth
# (standard's behavior — the default class — is unchanged from the
# pre-class stack).
CLASS_DEPTH_FRACTION = {SLO_CLASS_BATCH: 0.5}


class QueueDrainEstimator:
    """Windowed queue service-rate tracker behind honest Retry-After.

    Both frontends used to stamp a hardcoded ``Retry-After: 1`` on
    queue-depth 429s — a lie whenever the backlog needs more than a
    second to drain, and a thundering-herd invitation since every shed
    client retries in lockstep. This keeps a short window of
    ``(t, admitted_total, depth)`` samples (one per admitted request);
    the service rate over the window is what left the queue —
    ``(admitted Δ − depth Δ) / Δt`` — and the suggested retry is the
    current depth divided by that rate, clamped to [min_s, max_s].
    Fewer than two samples, or a rate estimate ≤ 0 (queue growing or
    stalled), degrade conservatively: the legacy 1s, or the max clamp.
    """

    def __init__(self, *, window_s: float = 10.0, min_s: int = 1,
                 max_s: int = 30):
        self.window_s = window_s
        self.min_s = min_s
        self.max_s = max_s
        self._lock = threading.Lock()
        self._admitted = 0  # guarded_by: self._lock
        self._samples: collections.deque = collections.deque()  # guarded_by: self._lock

    def note_admitted(self, depth: int, now: float | None = None) -> None:
        """Record one admission with the queue depth observed AFTER it."""
        now = time.monotonic() if now is None else now
        with self._lock:
            self._admitted += 1
            self._samples.append((now, self._admitted, depth))
            cutoff = now - self.window_s
            while len(self._samples) > 2 and self._samples[0][0] < cutoff:
                self._samples.popleft()

    def retry_after_s(self, depth: int, now: float | None = None) -> int:
        now = time.monotonic() if now is None else now
        with self._lock:
            if len(self._samples) < 2:
                return self.min_s  # no signal: legacy behavior
            t0, adm0, d0 = self._samples[0]
            t1, adm1, d1 = self._samples[-1]
        dt = t1 - t0
        if dt <= 0:
            return self.min_s
        served = (adm1 - adm0) - (d1 - d0)
        rate = served / dt
        if rate <= 0:
            return self.max_s  # draining nothing: back way off
        return max(self.min_s, min(self.max_s, math.ceil(depth / rate)))


def admission_verdict(
    req: GenerateRequest, broker: Broker, max_queue_depth: int,
    brownout=None, drain: QueueDrainEstimator | None = None,
) -> tuple[int, dict, dict] | None:
    """Class-aware shed decision shared by both producer frontends:
    ``None`` admits (a brownout rung may have capped a batch request's
    ``max_new_tokens`` in place); otherwise ``(status, body, headers)``
    for the 429. Checked in ladder-first order so a browned-out class
    reads the brownout reason, not a coincidental queue-depth one.
    Brownout sheds carry the ladder's dwell-derived Retry-After;
    queue-depth sheds derive theirs from the windowed drain rate when a
    ``QueueDrainEstimator`` is wired in."""
    if brownout is not None:
        ok, retry_after = brownout.admit(req)
        if not ok:
            return 429, {
                "error": f"brownout: shedding {req.slo_class}",
                "id": req.id,
                "brownout_state": brownout.state()["state"],
            }, {"Retry-After": str(retry_after)}
    if max_queue_depth:
        frac = CLASS_DEPTH_FRACTION.get(req.slo_class, 1.0)
        limit = max(1, int(max_queue_depth * frac))
        depth = broker.queue_depth()
        if depth >= limit:
            retry = drain.retry_after_s(depth) if drain is not None else 1
            return 429, {
                "error": "queue full", "id": req.id, "queue_depth": depth,
                "slo_class": req.slo_class,
            }, {"Retry-After": str(retry)}
    return None


def collect_trace_exports(broker: Broker) -> list[dict]:
    """Every flight-recorder export visible from this producer: the local
    process recorder plus the per-worker snapshots riding the registry
    heartbeats (``load_snapshot`` embeds ``trace``). ``trace.stitch``
    dedups events that arrive through both paths."""
    exports: list[dict] = []
    if trace.enabled():
        exports.append(trace.recorder().export())
    for _wid, info in sorted(broker.read_workers().items()):
        blob = info.get("trace")
        if isinstance(blob, dict):
            exports.append(blob)
    return exports


def collect_series_exports(broker: Broker) -> tuple[list[dict], dict]:
    """Every windowed-series export visible from this producer: the local
    registry plus the per-worker blobs riding the registry heartbeats
    (``load_snapshot`` embeds ``series``). Returns ``(exports, sources)``
    — each export tagged with a ``source`` label, plus per-source role
    metadata for ``/fleet/timeseries``. In-process fleets surface the
    same registry through several heartbeats;
    ``metrics.dedup_series_exports`` (applied by every consumer of these
    exports) keeps one blob per process."""
    exports: list[dict] = []
    sources: dict[str, dict] = {}
    if trace.enabled():
        local = dict(metrics_mod.series().export())
        local["source"] = "producer"
        exports.append(local)
        sources["producer"] = {"role": "producer"}
    for wid, info in sorted(broker.read_workers().items()):
        blob = info.get("series")
        if isinstance(blob, dict):
            tagged = dict(blob)
            tagged["source"] = wid
            exports.append(tagged)
            sources[wid] = {"role": info.get("role", "unified")}
    return exports, sources


def collect_devtel_exports(broker: Broker) -> list[dict]:
    """Every device-telemetry export visible from this producer: the
    local process blob plus the per-worker blobs riding the registry
    heartbeats (``load_snapshot`` embeds ``devtel``), deduped to one per
    process (in-process fleets surface the same module singleton through
    both paths)."""
    exports: list[dict] = []
    if devtel.enabled():
        exports.append(devtel.export())
    for _wid, info in sorted(broker.read_workers().items()):
        blob = info.get("devtel")
        if isinstance(blob, dict):
            exports.append(blob)
    return devtel.dedup_exports(exports)


def trace_timeline_response(
    broker: Broker, req_id: str, fmt: str = "",
) -> tuple[int, dict]:
    """GET /trace/{req_id}: the stitched fleet-wide timeline (404 when no
    process recorded the id). ``fmt == "chrome"`` returns Chrome
    trace-event JSON loadable in Perfetto instead — with the fleet's
    devtel counter tracks (KV occupancy, queue depth, memory)
    alongside the request's spans, so the timeline shows *why* it waited."""
    exports = collect_trace_exports(broker)
    if fmt == "chrome":
        if not trace.stitch(exports, req_id=req_id):
            return 404, {"error": f"no trace for {req_id}"}
        return 200, trace.to_chrome_trace(
            exports, req_id=req_id,
            counters=collect_devtel_exports(broker),
        )
    tl = trace.timeline(exports, req_id)
    if tl is None:
        return 404, {"error": f"no trace for {req_id}"}
    return 200, tl


def start_profile(
    log_dir: str | None = None, duration_s: float = 3.0,
) -> tuple[int, dict]:
    """POST /profile: capture an on-demand ``jax.profiler`` trace for
    ``duration_s`` seconds in a background thread (the serving loop keeps
    running — the profiler observes it). 409 while one is in flight; 501
    when jax is not importable (the producer itself never needs it).

    The in-flight slot carries a hard expiry (``duration_s`` + grace): a
    caller whose capture thread died or hung past its own cap no longer
    wedges profiling until process restart — the next POST force-stops
    the orphaned profiler session and takes the slot over."""
    global _PROFILE_ACTIVE, _PROFILE_GEN, _PROFILE_DEADLINE
    import tempfile
    import time as _time

    try:
        duration_s = min(max(float(duration_s), 0.1), 60.0)
    except (TypeError, ValueError):
        return 400, {"error": "duration_s must be a number"}
    try:
        import jax
    except Exception as e:  # noqa: BLE001 — report, don't crash the route
        return 501, {"error": f"jax unavailable: {e}"}
    with _PROFILE_LOCK:
        now = _time.monotonic()
        if _PROFILE_ACTIVE and now < _PROFILE_DEADLINE:
            return 409, {
                "error": "profile already in progress",
                "retry_after_s": round(_PROFILE_DEADLINE - now, 3),
            }
        stolen = bool(_PROFILE_ACTIVE)
        _PROFILE_GEN += 1
        gen = _PROFILE_ACTIVE = _PROFILE_GEN
        _PROFILE_DEADLINE = now + duration_s + _PROFILE_GRACE_S
    if stolen:
        # The previous holder blew through its own duration cap: its
        # capture thread is hung or dead, but jax's one-global-trace may
        # still be recording. Stop it so our start_trace doesn't fail.
        try:
            jax.profiler.stop_trace()
        except Exception:  # noqa: BLE001 — already stopped is fine
            pass
    if log_dir is None:
        log_dir = tempfile.mkdtemp(prefix="llmss-profile-")

    def run():
        global _PROFILE_ACTIVE
        try:
            with profile_trace(log_dir):
                _time.sleep(duration_s)
        except Exception:  # noqa: BLE001 — background capture best-effort
            pass
        finally:
            with _PROFILE_LOCK:
                # Only the still-current generation frees the slot — a
                # stolen-from thread waking up late must not release the
                # thief's in-flight profile.
                if _PROFILE_ACTIVE == gen:
                    _PROFILE_ACTIVE = 0

    threading.Thread(target=run, daemon=True).start()
    return 202, {
        "profiling": True, "log_dir": log_dir, "duration_s": duration_s,
        **({"stole_wedged_slot": True} if stolen else {}),
    }


def evaluate_worker_health(
    sup, saw_supervisor: bool, stale_factor: float = 3.0,
) -> tuple[int, dict, bool]:
    """Shared /health policy over the published supervisor block (both
    producer frontends use it). Returns (status_code, body,
    saw_supervisor'). 503 statuses, in precedence order:

    - ``no-heartbeat-data``: a supervisor block was seen before but the
      metrics channel no longer has one (Redis TTL expired — a hung
      worker must not read as recovered);
    - ``draining`` / ``dead``: lifecycle says stop sending traffic —
      draining workers finish their active rows but lease nothing new,
      dead workers are gone for good;
    - ``unhealthy``: the supervisor reports the worker not alive
      (crash-backoff window, watchdog stall);
    - ``stale-heartbeat``: no demonstrable worker progress for
      ``stale_factor × heartbeat_s`` — the progress-stamped
      ``heartbeat_ts`` goes stale even while the supervisor thread is
      blocked inside a hung ``run_once``."""
    import time as _time

    if not isinstance(sup, dict) or "heartbeat_ts" not in sup:
        if saw_supervisor:
            return 503, {
                "status": "no-heartbeat-data",
                "detail": "supervisor block seen before but gone "
                          "(metrics expired — worker presumed hung)",
            }, saw_supervisor
        return 200, {"status": "ok", "worker": "unsupervised"}, saw_supervisor
    # heartbeat_ts is a wall-clock stamp published by *another process*
    # (the supervisor converts its monotonic progress stamp at the edge);
    # monotonic epochs don't line up across processes, so wall clock is
    # the only clock both sides share.
    age = _time.time() - float(sup["heartbeat_ts"])  # lint: ignore[wall-clock-timer]
    stale_after = float(sup.get("heartbeat_s", 5.0)) * stale_factor
    state = sup.get("state")
    body = {
        "heartbeat_age_s": round(age, 3),
        "stale_after_s": stale_after,
        "state": state,
        "restarts": sup.get("restarts"),
        "watchdog_stalls": sup.get("watchdog_stalls"),
        "last_error": sup.get("last_error"),
    }
    if state in (STATE_DRAINING, STATE_DEAD):
        return 503, {"status": state, **body}, True
    if not sup.get("alive", True):
        return 503, {"status": "unhealthy", **body}, True
    if age > stale_after:
        return 503, {"status": "stale-heartbeat", **body}, True
    return 200, {"status": "ok", **body}, True


def evaluate_fleet_health(
    workers: dict, stale_factor: float = 3.0,
) -> tuple[int, dict]:
    """Aggregate /health over the worker registry: the fleet is healthy
    iff at least one replica is routable (per-worker policy 200 AND
    lifecycle ``ready``). One draining or crashed replica no longer flips
    the whole frontend to 503 the way the single-supervisor-block logic
    did — the survivors keep taking traffic. Per-worker detail rides
    along for operators (same bodies as ``GET /fleet``)."""
    per = {}
    ready = 0
    for wid, info in sorted(workers.items()):
        code, body, _ = evaluate_worker_health(info, True, stale_factor)
        routable = (
            code == 200 and info.get("state", STATE_READY) == STATE_READY
        )
        ready += int(routable)
        per[wid] = {"routable": routable, **body}
    if ready:
        return 200, {
            "status": "ok", "ready": ready, "workers": per,
        }
    return 503, {
        "status": "no-ready-workers", "ready": 0, "workers": per,
    }


class ProducerServer:
    # A worker is unhealthy after this many missed heartbeat intervals.
    HEARTBEAT_STALE_FACTOR = 3.0
    # How long one worker-state read is trusted for admission decisions —
    # keeps /generate from paying a broker metrics read per request.
    STATE_MEMO_S = 0.5

    def __init__(self, broker: Broker, host: str = "0.0.0.0",
                 port: int = 8000, timeout_s: float = 300.0,
                 max_queue_depth: int = 1024, router=None,
                 slo_objectives=None, brownout=None, controller=None):
        self.broker = broker
        # Optional serve.controller.FleetController: surfaced on /fleet
        # so operators see the reconciler's epoch / counters / last
        # action next to the registry it acts on. The producer never
        # ticks it — whoever owns the control loop does.
        self.controller = controller
        # Windowed queue drain rate behind queue-depth 429 Retry-After.
        self.drain_estimator = QueueDrainEstimator()
        # Burn-rate-driven brownout ladder: None builds the default
        # controller fed by this server's own /slo view of interactive
        # TTFT burn. With no traffic the burn reads 0.0, so the default
        # controller sits at rung 0 (admit-all) and costs nothing.
        if brownout is None:
            from llmss_tpu.serve.fleet import (
                BrownoutController, interactive_burn,
            )

            brownout = BrownoutController(
                lambda: interactive_burn(self.slo()),
            )
        self.brownout = brownout
        # SLO objectives served by GET /slo (attainment + burn rates over
        # the windowed fleet series); None = metrics.DEFAULT_SLO_OBJECTIVES.
        self.slo_objectives = slo_objectives
        # Optional serve.fleet.Router: when set, /generate places each
        # request on a replica's routed queue (policy-driven) instead of
        # the shared queue; without one, behavior is exactly the
        # single-worker shared-queue stack.
        self.router = router
        self.timeout_s = timeout_s
        # Admission control: when the broker backlog reaches this depth,
        # /generate sheds with 429 + Retry-After instead of queueing work
        # that will blow its deadline anyway (0 disables).
        self.max_queue_depth = max_queue_depth
        self._saw_supervisor = False
        self._state_memo: str | None = None
        self._state_memo_until = 0.0
        outer = self

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, *a):  # quiet
                pass

            def _reply(self, code: int, payload: dict):
                body = json.dumps(payload).encode()
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def _reply_text(self, code: int, text: str, ctype: str):
                body = text.encode()
                self.send_response(code)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):
                parts = urlsplit(self.path)
                path, q = parts.path, parse_qs(parts.query)
                if path == "/health":
                    code, body = outer.health()
                    self._reply(code, body)
                elif path == "/fleet/timeseries":
                    self._reply(200, outer.timeseries())
                elif path == "/fleet":
                    self._reply(200, outer.fleet())
                elif path == "/slo":
                    self._reply(200, outer.slo())
                elif path == "/compiles":
                    self._reply(200, outer.compiles())
                elif path == "/metrics":
                    payload = outer.metrics_payload()
                    if q.get("format", [""])[0] == "prometheus":
                        exports, _src = collect_series_exports(
                            outer.broker,
                        )
                        self._reply_text(
                            200, render_prometheus(
                                payload,
                                series=metrics_mod.cumulative_summary(
                                    exports,
                                ),
                            ),
                            _PROM_CONTENT_TYPE,
                        )
                    else:
                        # JSON stays the default and byte-identical to the
                        # pre-Prometheus payload.
                        self._reply(200, payload)
                elif path == "/dlq":
                    # Admin surface for quarantined poison requests: depth
                    # plus the most recent dead-lettered payloads.
                    self._reply(200, {
                        "depth": outer.broker.dlq_depth(),
                        "requests": outer.broker.read_dlq(),
                    })
                elif path == "/trace/slowest":
                    try:
                        n = int(q.get("n", ["10"])[0])
                    except ValueError:
                        self._reply(400, {"error": "n must be an integer"})
                        return
                    phase = q.get("phase", [None])[0] or None
                    self._reply(
                        200, {"slowest": outer.trace_slowest(n, phase)},
                    )
                elif path == "/trace/export_workload":
                    self._reply(200, outer.workload())
                elif path.startswith("/trace/"):
                    rid = path[len("/trace/"):]
                    code, body = trace_timeline_response(
                        outer.broker, rid, q.get("format", [""])[0],
                    )
                    self._reply(code, body)
                else:
                    self._reply(404, {"error": "not found"})

            def _admit(self, req) -> bool:
                """Admission control + deadline stamping. Returns False
                (with the 429/503 already sent) when the backlog is full
                or the worker lifecycle says stop sending traffic."""
                trace.ensure_context(req)
                state = outer.worker_unavailable()
                if state is not None:
                    # Draining/dead worker: queueing would only strand the
                    # request past its deadline (draining workers lease
                    # nothing new). Shed like a load balancer would.
                    trace.record(
                        req.id, "reject", trace_id=req.trace_id,
                        reason=f"worker {state}",
                    )
                    body = json.dumps({
                        "error": f"worker {state}", "id": req.id,
                    }).encode()
                    self.send_response(503)
                    self.send_header("Content-Type", "application/json")
                    self.send_header("Retry-After", "1")
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)
                    return False
                outer.brownout.tick()
                verdict = admission_verdict(
                    req, outer.broker, outer.max_queue_depth,
                    outer.brownout, drain=outer.drain_estimator,
                )
                if verdict is not None:
                    code, payload, headers = verdict
                    trace.record(
                        req.id, "reject", trace_id=req.trace_id,
                        reason=payload.get("error", "shed"),
                        slo_class=req.slo_class,
                    )
                    body = json.dumps(payload).encode()
                    self.send_response(code)
                    self.send_header("Content-Type", "application/json")
                    for k, v in headers.items():
                        self.send_header(k, v)
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)
                    return False
                if req.deadline_ts is None:
                    # Every request carries an end-to-end deadline so
                    # workers can shed expired work before prefill instead
                    # of decoding into the void.
                    import time as _time

                    req.deadline_ts = _time.time() + outer.timeout_s
                trace.record(
                    req.id, "accept", trace_id=req.trace_id,
                    timeout_s=outer.timeout_s, stream=req.stream,
                )
                return True

            def _stream_response(self, req):
                """SSE delivery for ``stream: true`` requests: one
                ``data:`` event per token increment as the worker decodes
                (granularity = its chunk), then a ``done`` event carrying
                the terminal response. HTTP/1.0 close-delimited body — no
                chunked-encoding bookkeeping. The reference can only
                deliver whole continuations."""
                import socket as _socket
                import time as _time

                outer.submit(req)
                self.send_response(200)
                self.send_header("Content-Type", "text/event-stream")
                self.send_header("Cache-Control", "no-cache")
                self.end_headers()
                # A stalled reader must not pin this handler thread: once
                # the socket send buffer fills, an untimed write would
                # block forever and the deadline/cancel logic below could
                # never run. A write timeout makes a stalled client look
                # like a disconnect.
                self.connection.settimeout(30.0)

                wrote_first = False

                def write_data(inc):
                    nonlocal wrote_first
                    self.wfile.write(
                        b"data: " + json.dumps(
                            {"token_ids": inc}
                        ).encode() + b"\n\n"
                    )
                    self.wfile.flush()
                    if not wrote_first:
                        # The end of the first-token path on the server:
                        # ``admit`` -> here is the stream channel plus
                        # this thread's wake-up.
                        wrote_first = True
                        trace.record(req.id, "first_write")

                deadline = _time.monotonic() + outer.timeout_s
                try:
                    while _time.monotonic() < deadline:
                        inc = outer.broker.pop_stream(req.id, timeout=0.1)
                        if inc is not None:
                            write_data(inc)
                            continue
                        resp = outer.broker.wait_response(
                            req.id, timeout=_DONE_CHECK_S
                        )
                        if resp is not None:
                            # Drain increments that raced the response.
                            while True:
                                inc = outer.broker.pop_stream(req.id)
                                if inc is None:
                                    break
                                write_data(inc)
                            self.wfile.write(
                                b"event: done\ndata: "
                                + resp.to_json().encode() + b"\n\n"
                            )
                            self.wfile.flush()
                            return
                    outer.broker.cancel_request(req.id)
                    self.wfile.write(
                        b'event: error\ndata: {"error": "timed out"}\n\n'
                    )
                except (
                    BrokenPipeError, ConnectionResetError,
                    TimeoutError, _socket.timeout,
                ):
                    # Client went away (or stopped reading) mid-stream:
                    # stop decoding for it.
                    outer.broker.cancel_request(req.id)
                finally:
                    outer.broker.drop_stream(req.id)

            def do_POST(self):
                if self.path == "/profile":
                    try:
                        n = int(self.headers.get("Content-Length", 0))
                        body = json.loads(self.rfile.read(n)) if n else {}
                    except Exception as e:  # noqa: BLE001 — client error
                        self._reply(400, {"error": str(e)})
                        return
                    code, out = start_profile(
                        body.get("log_dir"),
                        body.get("duration_s", 3.0),
                    )
                    self._reply(code, out)
                    return
                if self.path == "/cancel":
                    try:
                        n = int(self.headers.get("Content-Length", 0))
                        rid = json.loads(self.rfile.read(n))["id"]
                    except Exception as e:  # noqa: BLE001 — client error
                        self._reply(400, {"error": str(e)})
                        return
                    outer.broker.cancel_request(rid)
                    self._reply(200, {"cancelled": rid})
                    return
                if self.path != "/generate":
                    self._reply(404, {"error": "not found"})
                    return
                try:
                    n = int(self.headers.get("Content-Length", 0))
                    req = GenerateRequest.from_json(self.rfile.read(n))
                    req.validate()
                except Exception as e:  # noqa: BLE001 — client error surface
                    self._reply(400, {"error": str(e)})
                    return
                if not self._admit(req):
                    return
                if req.stream:
                    self._stream_response(req)
                    return
                outer.submit(req)
                resp = outer.broker.wait_response(req.id, outer.timeout_s)
                if resp is None:
                    # The client is gone; stop the worker spending decode
                    # steps on this id (the reference keeps decoding to
                    # max_new_tokens — wasted chip time + slow-client DoS).
                    outer.broker.cancel_request(req.id)
                    self._reply(504, {"error": "timed out", "id": req.id})
                elif resp.error:
                    self._reply(500, {"error": resp.error, "id": req.id})
                else:
                    self._reply(200, json.loads(resp.to_json()))

        self._server = ThreadingHTTPServer((host, port), Handler)
        self._thread: threading.Thread | None = None

    def submit(self, req: GenerateRequest) -> None:
        """Place an admitted request: through the router's policy when
        one is configured, else the shared queue (pre-fleet behavior)."""
        if self.router is not None:
            self.router.submit(req)
        else:
            self.broker.push_request(req)
        self.drain_estimator.note_admitted(self.broker.queue_depth())

    def health(self) -> tuple[int, dict]:
        """Worker-health-aware /health. With a populated worker registry
        the fleet aggregate applies: healthy iff >= 1 ``ready`` replica
        (``evaluate_fleet_health``) — one draining/crashed replica no
        longer 503s the whole frontend. With no registry (single-worker
        deployments that never register), the original single-supervisor
        path is used unchanged: a supervised worker publishes lifecycle
        state and a progress-stamped ``heartbeat_ts`` through the broker
        metrics channel, and draining/dead/stalled workers flip this to
        503. Policy in ``evaluate_worker_health``."""
        workers = self.broker.read_workers()
        if workers:
            return evaluate_fleet_health(
                workers, self.HEARTBEAT_STALE_FACTOR,
            )
        sup = self.broker.read_metrics().get("supervisor")
        code, body, self._saw_supervisor = evaluate_worker_health(
            sup, self._saw_supervisor, self.HEARTBEAT_STALE_FACTOR,
        )
        return code, body

    def fleet(self) -> dict:
        """GET /fleet: per-worker registry detail + routed queue depths +
        router stats + brownout ladder position."""
        from llmss_tpu.serve.fleet import fleet_status

        out = fleet_status(
            self.broker, self.router, self.HEARTBEAT_STALE_FACTOR,
        )
        out["brownout"] = self.brownout.state()
        if self.controller is not None:
            out["controller"] = self.controller.state()
        return out

    def metrics_payload(self) -> dict:
        """The GET /metrics JSON payload (also the input to the
        Prometheus rendering — one payload, two encodings)."""
        payload = {
            **self.broker.read_metrics(),
            "delivery": self.broker.delivery_stats(),
            # Closed enum (interactive/standard/batch) — the metric label
            # set is bounded by construction.
            "queue_depths_by_class": self.broker.queue_depths_by_class(),
            "brownout": self.brownout.state(),
        }
        fleet = self.fleet_metrics()
        if fleet is not None:
            payload["fleet"] = fleet
        dt = collect_devtel_exports(self.broker)
        if dt:
            # Only present when the plane is on somewhere in the fleet —
            # the pre-devtel payload stays byte-identical otherwise.
            payload["devtel"] = {"compiles": devtel.recompile_flag(dt)}
        return payload

    def trace_slowest(
        self, n: int = 10, phase: str | None = None,
    ) -> list[dict]:
        """GET /trace/slowest: the n slowest requests visible fleet-wide,
        each with its dominant phase (where the time actually went).
        ``?phase=`` reranks by time spent in that phase alone."""
        return trace.slowest(
            collect_trace_exports(self.broker), n=n, phase=phase,
        )

    def slo(self) -> dict:
        """GET /slo: per-objective attainment and multi-window burn rates
        from the windowed fleet-aggregated series — the signal the
        autoscaler and priority scheduler consume. When the devtel plane
        is on, a ``compile`` block flags steady-state recompiles: an
        unbudgeted multi-second XLA stall some request just ate."""
        exports, _src = collect_series_exports(self.broker)
        out = metrics_mod.evaluate_slos(exports, self.slo_objectives)
        dt = collect_devtel_exports(self.broker)
        if dt:
            out["compile"] = devtel.recompile_flag(dt)
        return out

    def compiles(self) -> dict:
        """GET /compiles: fleet-wide compile forensics — every recorded
        compilation (name, duration when known, triggering req_id when
        attributable) wall-aligned and newest-last, plus the steady-state
        recompile rollup."""
        return devtel.compiles_payload(collect_devtel_exports(self.broker))

    def timeseries(self) -> dict:
        """GET /fleet/timeseries: per-worker/per-series windowed points on
        a wall-aligned time base."""
        exports, sources = collect_series_exports(self.broker)
        return metrics_mod.timeseries_payload(exports, sources)

    def workload(self) -> dict:
        """GET /trace/export_workload: the retained timelines as a
        replayable arrival process (tools/trace_workload.py replays it;
        the fleet simulator consumes it)."""
        return trace.export_workload(collect_trace_exports(self.broker))

    def fleet_metrics(self) -> dict | None:
        """Fleet block for GET /metrics: per-worker load/queue-depth
        labels plus routing counters (routed per policy/worker, failover
        re-routes, prefix-affinity hit rate). None when no fleet exists —
        the pre-fleet /metrics payload stays byte-identical."""
        workers = self.broker.read_workers()
        if not workers and self.router is None:
            return None
        keys = (
            "role", "state", "inflight_rows", "queue_depth",
            "free_kv_blocks", "free_slots", "kv_blocks_total",
        )
        out: dict = {
            "workers": {
                wid: {k: info.get(k) for k in keys}
                for wid, info in sorted(workers.items())
            },
            "routed_depths": self.broker.routed_depths(),
            # Disaggregated prefill/decode: records waiting between a
            # prefill export and a decode adopt (shared + per-replica).
            "handoff_depth": self.broker.handoff_depth(),
            "handoff_depths": self.broker.handoff_depths(),
        }
        if self.router is not None:
            out["router"] = self.router.stats()
        from llmss_tpu.serve.fleet import aggregate_kv_tiers

        tiers = aggregate_kv_tiers(
            info.get("kv_tiers") for info in workers.values()
        )
        if tiers:
            # KV tiering rollup: only present when a worker runs a tiered
            # store — the pre-tiering payload stays byte-identical.
            out["kv_tiers"] = tiers
        return out

    def worker_unavailable(self) -> str | None:
        """A shed reason when the published worker state says new work
        must not be admitted, else None. Memoized for ``STATE_MEMO_S`` so
        per-request admission doesn't pay a broker read. With a populated
        registry this is the fleet aggregate (shed only when NO replica
        is routable); otherwise the legacy single-supervisor-block logic
        (draining/dead sheds fleet-wide, since one metrics channel is all
        there is)."""
        import time as _time

        now = _time.monotonic()
        if now < self._state_memo_until:
            return self._state_memo
        workers = self.broker.read_workers()
        if workers:
            code, _body = evaluate_fleet_health(
                workers, self.HEARTBEAT_STALE_FACTOR,
            )
            self._state_memo = (
                None if code == 200 else "unavailable (no ready replica)"
            )
        else:
            sup = self.broker.read_metrics().get("supervisor")
            state = sup.get("state") if isinstance(sup, dict) else None
            self._state_memo = (
                state if state in (STATE_DRAINING, STATE_DEAD) else None
            )
        self._state_memo_until = now + self.STATE_MEMO_S
        return self._state_memo

    @property
    def port(self) -> int:
        return self._server.server_address[1]

    def start(self) -> None:
        self._thread = threading.Thread(
            target=self._server.serve_forever, daemon=True
        )
        self._thread.start()

    def stop(self) -> None:
        self._server.shutdown()
        self._server.server_close()

    def serve_forever(self) -> None:
        self._server.serve_forever()


def main(argv=None):
    import argparse

    parser = argparse.ArgumentParser("llmss-producer")
    parser.add_argument("--host", default="0.0.0.0")
    parser.add_argument("--port", type=int, default=8000)
    parser.add_argument("--redis_host", default="localhost")
    parser.add_argument("--redis_port", type=int, default=6379)
    parser.add_argument("--timeout_s", type=float, default=300.0,
                        help="end-to-end request deadline (stamped into "
                             "deadline_ts at admission)")
    parser.add_argument("--max_queue_depth", type=int, default=1024,
                        help="shed with 429 once the broker backlog reaches "
                             "this depth (0 disables)")
    parser.add_argument("--policy", default=None,
                        choices=[None, "round_robin", "least_loaded",
                                 "prefix_affinity"],
                        help="fleet routing policy: place requests on "
                             "per-worker routed queues via the worker "
                             "registry (workers must run with --worker_id); "
                             "omit for the shared queue")
    parser.add_argument("--slo_config", default=None,
                        help="path to a JSON list of SLO objectives "
                             "served by GET /slo (see "
                             "metrics.DEFAULT_SLO_OBJECTIVES for the "
                             "schema); omit for the defaults")
    args = parser.parse_args(argv)

    slo_objectives = None
    if args.slo_config:
        with open(args.slo_config) as f:
            slo_objectives = json.load(f)

    from llmss_tpu.serve.broker import RedisBroker

    broker = RedisBroker(args.redis_host, args.redis_port)
    router = None
    if args.policy:
        from llmss_tpu.serve.fleet import Router

        router = Router(broker, args.policy)
    server = ProducerServer(broker, args.host, args.port,
                            timeout_s=args.timeout_s,
                            max_queue_depth=args.max_queue_depth,
                            router=router,
                            slo_objectives=slo_objectives)
    print(f"producer listening on {args.host}:{server.port}")
    server.serve_forever()


if __name__ == "__main__":
    main()
