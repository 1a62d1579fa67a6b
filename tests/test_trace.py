"""End-to-end request tracing (``utils/trace.py``).

The tentpole claims pinned here:

- trace context (``trace_id`` + ``trace_attempt``) survives the wire and
  the LKVH handoff on BOTH brokers, so ``GET /trace/{req_id}`` can
  reconstruct the full producer → prefill → handoff → decode timeline;
- a decode replica hard-killed mid-handoff leaves a complete flight
  recorder timeline: the re-prefill keeps the SAME trace id with a bumped
  attempt index, and the timeline ends in exactly one terminal event;
- the Chrome trace export is valid JSON with per-process monotonically
  consistent timestamps even under (simulated) cross-process clock skew —
  the one-wall-anchor-per-export discipline is what makes that true;
- tracing off records nothing, and tracing on adds zero steady-state
  recompiles (the instrumentation is host-side only);
- the loop track: a bounded ring of request-less spans that name their
  parent, one ``sched.dispatch`` a decode group whatever the group's length,
  the same steps in ``EngineMetrics``, and the seams of a request's way to
  its first token (``prefill_dispatch``, ``first_write``) in order.
"""

import json
import threading
import time

import httpx
import pytest

from llmss_tpu.serve.broker import InProcBroker, RedisBroker
from llmss_tpu.serve.chaos import (
    ChaosWorkerHost,
    FakeRedis,
    HardKill,
    ScriptedEngine,
)
from llmss_tpu.serve.handoff import DecodeWorker, PrefillWorker
from llmss_tpu.serve.producer import ProducerServer
from llmss_tpu.serve.protocol import GenerateRequest, GenerateResponse
from llmss_tpu.utils import trace
from llmss_tpu.utils.trace import FlightRecorder

BROKER_KINDS = ("inproc", "fakeredis")


def make_brokers(kind, **kw):
    """(producer-side broker, make_worker_broker(worker_id)) — the same
    two deployment shapes tests/test_handoff.py exercises."""
    if kind == "inproc":
        b = InProcBroker(**kw)
        return b, (lambda wid: b)
    server = FakeRedis()

    def mk(wid):
        return RedisBroker(client=server, worker_id=wid, **kw)

    return mk("producer"), mk


@pytest.fixture(autouse=True)
def clean_recorder():
    """Each test starts from an empty process recorder with tracing on."""
    trace.set_enabled(True)
    trace.recorder().clear()
    yield
    trace.set_enabled(True)
    trace.recorder().clear()


# -- flight recorder unit behavior ------------------------------------------


def test_recorder_ring_evicts_oldest_request():
    rec = FlightRecorder(max_requests=2, proc="p")
    rec.record("a", "enqueue")
    rec.record("b", "enqueue")
    rec.record("c", "enqueue")  # ring full: "a" (oldest) is evicted
    assert rec.req_ids() == ["b", "c"]
    rec.record("b", "lease")  # touching "b" makes "c" the eviction victim
    rec.record("d", "enqueue")
    assert rec.req_ids() == ["b", "d"]


def test_recorder_sheds_group_spam_before_lifecycle_events():
    rec = FlightRecorder(max_events=4, proc="p")
    rec.record("r", "enqueue")
    for _ in range(3):
        rec.record("r", "group_dispatch")
    # At capacity a lifecycle event evicts a sheddable one, never the
    # other way around...
    rec.record("r", "respond")
    names = [e["name"] for e in rec.events_for("r")]
    assert names.count("group_dispatch") == 2
    assert names[0] == "enqueue" and names[-1] == "respond"
    # ...and new sheddable events at capacity are simply dropped.
    rec.record("r", "group_dispatch")
    assert len(rec.events_for("r")) == 4
    assert rec.export()["requests"]["r"]["dropped"] == 2


def test_recorder_throttles_renewals():
    rec = FlightRecorder(proc="p")
    rec.record("r", "lease_renew", throttle_s=10.0)
    rec.record("r", "lease_renew", throttle_s=10.0)
    rec.record("r", "lease_renew", throttle_s=10.0)
    assert len(rec.events_for("r")) == 1


def test_span_records_duration_error_and_is_idempotent():
    rec = FlightRecorder(proc="p")
    with rec.start_span("r", "prefill", worker="w0"):
        pass
    ev = rec.events_for("r")[0]
    assert ev["name"] == "prefill" and ev["dur"] >= 0.0
    assert ev["attrs"]["worker"] == "w0"
    with pytest.raises(RuntimeError):
        with rec.start_span("r", "decode"):
            raise RuntimeError("boom")
    assert rec.events_for("r")[1]["attrs"]["error"] == "RuntimeError"
    s = rec.start_span("r", "adopt")
    s.end()
    s.end()  # idempotent: one event, not two
    assert len(rec.events_for("r")) == 3


# -- the loop track -----------------------------------------------------------


def test_loop_ring_is_bounded_and_counts_what_it_dropped():
    rec = FlightRecorder(proc="p", max_loop_spans=4)
    for i in range(6):
        rec.start_loop_span("loop").end(iteration=i)
    loop = rec.export()["loop"]
    assert loop["dropped"] == 2
    assert [sp[5]["iteration"] for sp in loop["spans"]] == [2, 3, 4, 5]
    assert [sp[0] for sp in loop["spans"]] == [3, 4, 5, 6]  # seq from 1
    rec.clear()
    assert rec.export()["loop"] == {"spans": [], "dropped": 0}


def test_loop_ring_loses_no_span_under_contention():
    """Several loops (in-process replicas share the recorder) end spans while
    an exporter reads the ring: every span is either in it or counted."""
    import sys

    rec = FlightRecorder(proc="p", max_loop_spans=64)
    n_threads, per_thread = 16, 400
    stop = threading.Event()

    def work():
        for _ in range(per_thread):
            rec.start_loop_span("loop").end()

    def read():
        while not stop.is_set():
            rec.export()

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        reader = threading.Thread(target=read)
        reader.start()
        workers = [threading.Thread(target=work) for _ in range(n_threads)]
        for t in workers:
            t.start()
        for t in workers:
            t.join(timeout=60)
        stop.set()
        reader.join(timeout=10)
    finally:
        sys.setswitchinterval(old)
    assert not reader.is_alive() and not any(t.is_alive() for t in workers)
    loop = rec.export()["loop"]
    assert len(loop["spans"]) + loop["dropped"] == n_threads * per_thread
    assert len({sp[0] for sp in loop["spans"]}) == 64  # every seq once


def test_loop_spans_name_their_parent_and_feed_their_hooks():
    rec = FlightRecorder(proc="p")
    closed, entered = [], []

    class Ann:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            entered.append(("in", self.name))

        def __exit__(self, *exc):
            entered.append(("out", self.name))

    def on_close(name, seconds):
        closed.append((name, seconds))

    with rec.start_loop_span("loop", on_close=on_close, annotate=Ann) as it:
        with rec.start_loop_span("sched.dispatch", it.seq, on_close, Ann) as d:
            d.set(group=7)
            d.set(chunks=1, k=4)
        with pytest.raises(RuntimeError):
            with rec.start_loop_span("sched.callback", it.seq):
                raise RuntimeError("boom")
        it.end(iteration=0)
        it.end()  # idempotent: one span, one hook call
    spans = {sp[2]: sp for sp in rec.loop_spans()}
    assert len(rec.loop_spans()) == 3
    seq, parent, _name, t0, dur, attrs = spans["sched.dispatch"]
    assert parent == spans["loop"][0] and spans["loop"][1] is None
    assert attrs == {"group": 7, "chunks": 1, "k": 4}
    assert spans["sched.callback"][5] == {"error": "RuntimeError"}
    # children end first and lie inside the parent, on one clock
    assert [sp[2] for sp in rec.loop_spans()][-1] == "loop"
    lt0, ldur = spans["loop"][3], spans["loop"][4]
    assert lt0 <= t0 and t0 + dur <= lt0 + ldur
    assert [n for n, _s in closed] == ["sched.dispatch", "loop"]
    assert closed[0][1] == dur
    assert entered == [("in", "loop"), ("in", "sched.dispatch"),
                       ("out", "sched.dispatch"), ("out", "loop")]


def test_loop_track_off_is_one_shared_noop():
    trace.set_enabled(False)
    a = trace.loop_span("loop")
    b = trace.loop_span("sched.plan", 3, on_close=lambda *_: 1 / 0)
    assert a is b is trace.NO_LOOP_SPAN and a.seq is None
    with b as sp:
        sp.set(x=1)
    b.end(y=2)
    assert trace.recorder().loop_spans() == []
    assert trace.recorder().export()["loop"] == {"spans": [], "dropped": 0}


def test_export_keeps_its_keys_beside_the_loop_track():
    rec = FlightRecorder(proc="p")
    rec.record("r", "enqueue")
    rec.start_loop_span("loop").end()
    full = rec.export()
    assert {"proc", "mono_anchor", "wall_anchor", "requests"} < set(full)
    assert set(full) - {"proc", "mono_anchor", "wall_anchor", "requests"} == {
        "loop"}
    assert list(full["requests"]["r"]) == ["trace_id", "dropped", "events"]
    json.dumps(full)  # JSON-safe: spans are lists, attrs plain
    # the bounded exports (heartbeats, one request) stay as small as before
    assert "loop" not in rec.export(max_events=8)
    assert "loop" not in rec.export(req_ids={"r"})


def test_chrome_trace_draws_the_loop_as_one_lane():
    rec = FlightRecorder(proc="w0")
    rec.record("r1", "enqueue")
    with rec.start_loop_span("loop") as it:
        with rec.start_loop_span("sched.dispatch", it.seq) as d:
            d.set(group=1)
            rec.record("r1", "group_dispatch", loop=d.seq)
    time.sleep(0.002)
    rec.record("r1", "respond")
    time.sleep(0.002)
    rec.start_loop_span("loop").end()  # after r1's last event
    ct = trace.to_chrome_trace([rec.export()])
    lanes = [e for e in ct["traceEvents"]
             if e["ph"] == "M" and e["args"]["name"] == trace.LOOP_LANE]
    assert len(lanes) == 1
    drawn = [e for e in ct["traceEvents"] if e.get("cat") == "loop"]
    assert [e["name"] for e in drawn] == ["sched.dispatch", "loop", "loop"]
    assert {e["tid"] for e in drawn} == {lanes[0]["tid"]}
    assert all(e["ph"] == "X" for e in drawn)
    assert all(e["ts"] >= 0 for e in ct["traceEvents"] if e["ph"] != "M")
    disp, it0 = drawn[0], drawn[1]
    assert disp["args"]["parent"] == it0["args"]["seq"]
    assert disp["args"]["group"] == 1
    assert it0["ts"] <= disp["ts"]
    assert disp["ts"] + disp["dur"] <= it0["ts"] + it0["dur"] + 1e-3
    # one request's timeline draws only the spans that overlap it
    one = trace.to_chrome_trace([rec.export()], req_id="r1")
    assert len([e for e in one["traceEvents"] if e.get("cat") == "loop"]) == 2


def test_export_budget_keeps_most_recent():
    rec = FlightRecorder(proc="p")
    for i in range(5):
        rec.record(f"r{i}", "enqueue")
    ex = rec.export(max_events=2)
    assert set(ex["requests"]) == {"r4", "r3"}
    assert "wall_anchor" in ex and "mono_anchor" in ex and ex["proc"] == "p"


# -- trace context on the wire ----------------------------------------------


def test_trace_context_survives_wire_roundtrip():
    req = GenerateRequest(id="w1", token_ids=[1, 2])
    trace.ensure_context(req)
    assert req.trace_id == "w1"
    rt = GenerateRequest.from_json(req.to_json())
    assert rt.trace_id == "w1" and rt.trace_attempt == 0
    # Pre-tracing payloads (no trace fields) still parse: wire-compatible.
    d = json.loads(req.to_json())
    d.pop("trace_id")
    d.pop("trace_attempt")
    old = GenerateRequest.from_json(json.dumps(d))
    assert old.trace_id is None and old.trace_attempt == 0


# -- end-to-end propagation across the handoff ------------------------------


def _run_to_completion(b, workers, reqs, timeout_s=20.0):
    got = {}
    deadline = time.monotonic() + timeout_s
    while len(got) < len(reqs) and time.monotonic() < deadline:
        for w in workers:
            w.run_once()
        for r in reqs:
            if r.id not in got:
                resp = b.wait_response(r.id, timeout=0.01)
                if resp is not None:
                    got[r.id] = resp
    return got


@pytest.mark.parametrize("kind", BROKER_KINDS)
def test_trace_propagates_producer_to_decode(kind):
    b, mk = make_brokers(kind, lease_s=2.0)
    pre = PrefillWorker(ScriptedEngine(), mk("p0"), worker_id="p0")
    dec = DecodeWorker(ScriptedEngine(), mk("d0"), worker_id="d0")
    reqs = [
        GenerateRequest(id=f"t{i}", token_ids=[5 + i, 3], max_new_tokens=4)
        for i in range(2)
    ]
    for r in reqs:
        b.push_request(r)
    got = _run_to_completion(b, [pre, dec], reqs)
    assert len(got) == len(reqs)

    exports = [trace.recorder().export()]
    for r in reqs:
        tl = trace.timeline(exports, r.id)
        assert tl is not None and tl["trace_id"] == r.id
        names = [e["name"] for e in tl["events"]]
        # The full disaggregated path, in one stitched timeline.
        for expected in (
            "enqueue", "lease", "prefill", "handoff_push",
            "handoff_lease", "decode", "respond",
        ):
            assert expected in names, (r.id, expected, names)
        assert names.count("respond") == 1
        assert names[-1] == "respond"
        assert {e["trace_id"] for e in tl["events"]} == {r.id}
        assert tl["phases"].get("queue_wait", 0.0) >= 0.0
        assert tl["dominant_phase"] is not None


# -- the acceptance chaos case ----------------------------------------------


class _KillOnAdopt(ScriptedEngine):
    """Decode-engine stand-in whose first N adoptions are machine death:
    HardKill escapes mid-adopt with the handoff lease still open."""

    def __init__(self, kills: int):
        super().__init__()
        self._kills_left = kills
        self._klock = threading.Lock()

    def adopt_generate(self, *a, **kw):
        with self._klock:
            if self._kills_left > 0:
                self._kills_left -= 1
                raise HardKill("chaos: decode replica died mid-adopt")
        return super().adopt_generate(*a, **kw)


@pytest.mark.parametrize("kind", BROKER_KINDS)
def test_chaos_kill_decode_mid_handoff_timeline(kind):
    """A decode replica hard-dies after leasing a handoff record. The
    lease expires, the broker re-prefills the request — same trace_id,
    bumped attempt index — and the flight recorder shows the complete
    story ending in exactly one terminal event."""
    b, mk = make_brokers(kind, lease_s=0.25, max_delivery_attempts=6)
    eng = _KillOnAdopt(2)  # shared across respawns: exactly 2 deaths
    pre = ChaosWorkerHost(
        lambda: PrefillWorker(
            ScriptedEngine(), mk("p0"), worker_id="p0",
            poll_timeout_s=0.02,
        ),
        respawn_delay_s=0.02,
    )
    dec = ChaosWorkerHost(
        lambda: DecodeWorker(
            eng, mk("d0"), worker_id="d0", poll_timeout_s=0.02,
        ),
        respawn_delay_s=0.02,
    )
    reqs = [
        GenerateRequest(
            id=f"c{i}", token_ids=[i + 2, 9], max_new_tokens=4,
            deadline_ts=time.time() + 30.0,
        )
        for i in range(4)
    ]
    pre.start()
    dec.start()
    try:
        for r in reqs:
            b.push_request(r)
        for r in reqs:
            resp = b.wait_response(r.id, timeout=20.0)
            assert resp is not None, f"lost {r.id}"
            assert resp.error is None, (r.id, resp.error)
            assert resp.token_ids == ScriptedEngine.expected_tokens(
                list(r.token_ids), r.max_new_tokens,
            )
            assert b.wait_response(r.id, timeout=0.05) is None, (
                f"duplicate terminal response for {r.id}"
            )
    finally:
        pre.stop()
        dec.stop()
    assert pre.error is None and dec.error is None
    assert dec.kills == 2

    exports = [trace.recorder().export()]
    n_reprefills = 0
    for r in reqs:
        tl = trace.timeline(exports, r.id)
        assert tl is not None and tl["trace_id"] == r.id
        names = [e["name"] for e in tl["events"]]
        terminals = [n for n in names if n in trace.TERMINAL_EVENTS]
        assert terminals == ["respond"], (r.id, names)
        assert names[-1] == "respond"
        reps = [e for e in tl["events"] if e["name"] == "reprefill"]
        for i, e in enumerate(reps, start=1):
            # Re-prefill stays inside the ORIGINAL request's timeline:
            # same trace id, attempt index bumped per re-prefill.
            assert e["trace_id"] == r.id
            assert e["attrs"]["attempt"] == i
        n_reprefills += len(reps)
    assert n_reprefills == 2
    assert b.delivery_stats()["reprefills"] == 2


# -- cross-process stitching under clock skew --------------------------------


def _skewed_exports():
    """Two process exports whose monotonic epochs are wildly different
    (1000s vs 50s) and whose wall anchors disagree by 200 ms — the
    stitcher must align them purely through the per-export anchors."""
    ex_a = {
        "proc": "pA", "mono_anchor": 1000.0, "wall_anchor": 5000.0,
        "requests": {"r": {"trace_id": "r", "dropped": 0, "events": [
            {"req_id": "r", "name": "enqueue", "t": 999.0},
            {"req_id": "r", "name": "lease", "t": 999.5},
        ]}},
    }
    ex_b = {
        "proc": "pB", "mono_anchor": 50.0, "wall_anchor": 5000.2,
        "requests": {"r": {"trace_id": "r", "dropped": 0, "events": [
            {"req_id": "r", "name": "prefill", "t": 49.9, "dur": 0.4},
            {"req_id": "r", "name": "respond", "t": 49.95},
        ]}},
    }
    return [ex_a, ex_b]


def test_stitch_aligns_across_clock_skew():
    evs = trace.stitch(_skewed_exports())
    assert [e["name"] for e in evs] == [
        "enqueue", "lease", "prefill", "respond",
    ]
    phases = trace.phase_breakdown(evs)
    assert abs(phases["queue_wait"] - 0.5) < 1e-9
    assert abs(phases["prefill"] - 0.4) < 1e-9
    assert trace.dominant_phase(evs) == "queue_wait"
    tl = trace.timeline(_skewed_exports(), "r")
    assert abs(tl["total_s"] - 1.15) < 1e-6
    rows = trace.slowest(_skewed_exports(), n=3)
    assert rows[0]["req_id"] == "r"
    assert rows[0]["dominant_phase"] == "queue_wait"


def test_stitch_dedups_double_delivered_events():
    # The same export arriving twice (local recorder + registry
    # heartbeat) must not duplicate the timeline.
    ex = _skewed_exports()[0]
    assert len(trace.stitch([ex, ex])) == 2


def test_chrome_trace_export_valid():
    exports = _skewed_exports()
    doc = json.loads(trace.chrome_trace_json(exports))  # valid JSON
    assert doc["displayTimeUnit"] == "ms"
    evs = doc["traceEvents"]
    # "C" = devtel counter tracks (KV blocks, MFU/MBU, queue depths).
    assert {e["ph"] for e in evs} <= {"M", "X", "i", "C"}
    procs = {
        e["args"]["name"] for e in evs
        if e["ph"] == "M" and e["name"] == "process_name"
    }
    assert procs == {"pA", "pB"}
    xs = [e for e in evs if e["ph"] == "X"]
    assert len(xs) == 1 and abs(xs[0]["dur"] - 0.4e6) < 1.0
    assert all(e["ts"] >= 0 for e in evs if e["ph"] in ("X", "i"))
    assert all(e["s"] == "t" for e in evs if e["ph"] == "i")

    # Per-process consistency: within one process the wall-aligned order
    # must equal the monotonic order (the anchor is a pure offset).
    by_proc: dict = {}
    for e in trace.stitch(exports):
        by_proc.setdefault(e["proc"], []).append(e)
    for proc_evs in by_proc.values():
        ts = [e["ts_wall"] for e in proc_evs]
        mono = [e["t"] for e in proc_evs]
        assert ts == sorted(ts) and mono == sorted(mono)


# -- tracing off -------------------------------------------------------------


def test_tracing_off_records_nothing():
    trace.set_enabled(False)
    b, mk = make_brokers("inproc", lease_s=2.0)
    pre = PrefillWorker(ScriptedEngine(), mk("p0"), worker_id="p0")
    dec = DecodeWorker(ScriptedEngine(), mk("d0"), worker_id="d0")
    r = GenerateRequest(id="off", token_ids=[3, 4], max_new_tokens=3)
    b.push_request(r)
    got = _run_to_completion(b, [pre, dec], [r], timeout_s=10.0)
    assert got and got["off"].token_ids
    assert trace.recorder().req_ids() == []
    with trace.span("off", "phase"):
        pass
    assert trace.recorder().req_ids() == []
    # Heartbeat snapshots omit the trace blob entirely on the off path.
    assert all("trace" not in info for info in b.read_workers().values())


# -- producer endpoints ------------------------------------------------------


def _seed_recorder():
    trace.record("rq1", "enqueue", trace_id="rq1", queue="shared")
    with trace.span("rq1", "prefill", trace_id="rq1", worker="w0"):
        time.sleep(0.01)
    trace.record("rq1", "respond", ok=True)


def test_producer_trace_and_prometheus_endpoints():
    b = InProcBroker()
    srv = ProducerServer(b, host="127.0.0.1", port=0, timeout_s=5.0)
    srv.start()
    try:
        _seed_recorder()
        base = f"http://127.0.0.1:{srv.port}"
        tl = httpx.get(f"{base}/trace/rq1").json()
        assert tl["req_id"] == "rq1" and tl["trace_id"] == "rq1"
        assert [e["name"] for e in tl["events"]][-1] == "respond"
        assert "prefill" in tl["phases"]

        sl = httpx.get(f"{base}/trace/slowest?n=5").json()["slowest"]
        assert sl and sl[0]["req_id"] == "rq1"

        ch = httpx.get(f"{base}/trace/rq1?format=chrome").json()
        assert any(e.get("ph") == "X" for e in ch["traceEvents"])

        assert httpx.get(f"{base}/trace/nope").status_code == 404

        r = httpx.get(f"{base}/metrics")  # JSON stays the default
        assert r.headers["content-type"].startswith("application/json")
        assert "delivery" in r.json()

        r = httpx.get(f"{base}/metrics?format=prometheus")
        assert r.status_code == 200
        assert r.headers["content-type"].startswith("text/plain")
        assert "# TYPE" in r.text and "llmss_delivery_" in r.text
    finally:
        srv.stop()


def test_profile_endpoint_serializes_captures(tmp_path):
    from llmss_tpu.serve import producer as producer_mod

    b = InProcBroker()
    srv = ProducerServer(b, host="127.0.0.1", port=0)
    srv.start()
    try:
        base = f"http://127.0.0.1:{srv.port}"
        r = httpx.post(f"{base}/profile", json={
            "log_dir": str(tmp_path / "prof"), "duration_s": 0.3,
        })
        assert r.status_code == 202
        body = r.json()
        assert body["profiling"] is True and body["duration_s"] == 0.3
        # One capture per process: an overlapping request is refused.
        r2 = httpx.post(f"{base}/profile", json={"duration_s": 0.1})
        assert r2.status_code == 409
        deadline = time.monotonic() + 10.0
        # The slot (not the lock — that's only held for bookkeeping) is
        # what the capture thread frees on completion.
        while producer_mod._PROFILE_ACTIVE:
            assert time.monotonic() < deadline, "profile never finished"
            time.sleep(0.05)
    finally:
        srv.stop()


# -- tracing on adds zero steady-state recompiles ----------------------------

from llmss_tpu.engine import DecodeEngine, GenerationParams  # noqa: E402
from llmss_tpu.engine.scheduler import ContinuousBatcher  # noqa: E402
from llmss_tpu.serve.consumer import ContinuousWorker  # noqa: E402


def test_tracing_adds_no_steady_state_recompiles(toy_engine):
    """The instrumentation is host-side only: with tracing ON and traced
    req_ids flowing through the scheduler, a warmed batcher must hit the
    jit caches exactly as before — zero new compiles."""
    from llmss_tpu.analysis import CompileGuard

    engine = toy_engine
    batcher = ContinuousBatcher(
        engine, rows=2, chunk_steps=2, group_chunks=2,
    )
    batcher.prewarm()
    gen = GenerationParams(max_new_tokens=4, is_greedy=True)

    guard = CompileGuard.for_engine(engine)
    assert guard._fns, "engine exposes no jitted callables to guard"
    got = {}
    with guard.steady_state():
        for i, p in enumerate([[5, 9], [3, 14, 15]]):
            batcher.submit(
                p, gen, lambda t, i=i: got.__setitem__(i, t),
                req_id=f"g{i}",
            )
        batcher.run_until_idle()
    assert len(got) == 2
    names = {e["name"] for e in trace.recorder().events_for("g0")}
    assert {"sched_submit", "admit", "finish"} <= names


def _loop_spans(name):
    return [sp for sp in trace.recorder().loop_spans() if sp[2] == name]


@pytest.mark.parametrize("tracing", [True, False])
def test_one_step_is_one_dispatch_span_and_steps_are_counted(
    toy_engine, tracing,
):
    """Every ``step()`` that dispatches a group leaves exactly one
    ``sched.dispatch`` span with that group's ``chunks`` x ``k``, however
    short the group, and NO per-row request event (the ``group_dispatch``
    events went with PR 42: a request's place in a group follows from
    ``prefill_dispatch.loop`` and ``admit.loop``), and ``EngineMetrics``
    counts the same steps with tracing on or off."""
    trace.set_enabled(tracing)
    batcher = ContinuousBatcher(
        toy_engine, rows=2, chunk_steps=2, group_chunks=2,
    )
    m = toy_engine.metrics
    before = m.to_dict()["loop"]
    gen = GenerationParams(max_new_tokens=24, is_greedy=True)
    for i, p in enumerate([[5, 9], [3, 14, 15]]):
        batcher.submit(p, gen, lambda t: None, req_id=f"s{i}")
    groups = []
    while not batcher.idle:
        n_disp = len(_loop_spans("sched.dispatch"))
        g0 = m.groups_dispatched
        batcher.step()
        if m.groups_dispatched > g0:
            groups.append(batcher._inflight)
            assert len(_loop_spans("sched.dispatch")) - n_disp == int(tracing)
    after = m.to_dict()["loop"]
    steps = sum(g.n_chunks * g.k for g in groups)
    assert len(groups) >= 6 and steps > 0
    assert after["decode_steps"] - before["decode_steps"] == steps
    disp = _loop_spans("sched.dispatch")
    if not tracing:
        assert disp == [] and after["spans"] == before["spans"]
        return
    assert [(sp[5]["group"], sp[5]["chunks"] * sp[5]["k"]) for sp in disp] == [
        (g.no, g.n_chunks * g.k) for g in groups]
    assert all(sp[5]["kind"] == "decode_group" and sp[5]["rows_live"] >= 1
               for sp in disp)
    # a group is on the loop track alone: no request carries an event a
    # group, and what a request does carry points into the track
    for rid in ("s0", "s1"):
        evs = trace.recorder().events_for(rid)
        assert not [e for e in evs if e["name"].startswith("group_")]
        assert len(evs) < len(groups)
        seqs = {sp[0] for sp in trace.recorder().loop_spans()}
        assert {e["attrs"]["loop"] for e in evs
                if "loop" in e.get("attrs", {})} <= seqs
    # a group's fetch and callback carry its number, one step later; the
    # wait for an admission's first tokens is a fetch_wait of its own
    waits = [sp[5] for sp in _loop_spans("sched.fetch_wait")]
    assert [a["group"] for a in waits if "group" in a] == [
        g.no for g in groups]
    assert sum(a.get("admission", 0) for a in waits) == 2
    assert [sp[5]["group"] for sp in _loop_spans("sched.callback")] == [
        g.no for g in groups]
    counted = after["spans"]["sched.dispatch"]["count"] - (
        before["spans"].get("sched.dispatch", {}).get("count", 0))
    assert counted == len(groups)


@pytest.mark.parametrize("chunked", [None, 4])
def test_first_token_seams_lie_in_order_for_every_request(toy_engine, chunked):
    """Over HTTP, streamed: ``enqueue`` <= ``lease`` <= ``sched_submit`` <=
    ``prefill_dispatch`` <= ``admit`` <= ``first_write`` for every request,
    on the bucketed and on the chunked admission path, and every loop span
    of the worker hangs under one ``loop`` span an iteration."""
    eng = toy_engine if chunked is None else DecodeEngine(
        toy_engine.cfg, toy_engine.params, toy_engine.mesh, max_seq_len=64,
        kv_layout="paged",
    )
    broker = InProcBroker()
    worker = ContinuousWorker(
        eng, broker, rows=2, poll_timeout_s=0.01, chunk_steps=2,
        chunked_prefill=chunked,
    )
    stop = threading.Event()
    t = threading.Thread(target=worker.run_forever, args=(stop,), daemon=True)
    t.start()
    server = ProducerServer(broker, host="127.0.0.1", port=0, timeout_s=60)
    server.start()
    ids = [f"f{chunked}-{i}" for i in range(4)]
    try:
        def one(rid, n):
            with httpx.stream(
                "POST", f"http://127.0.0.1:{server.port}/generate",
                json={"id": rid, "token_ids": list(range(3, 3 + n)),
                      "max_new_tokens": 6, "is_greedy": True, "stream": True},
                timeout=60,
            ) as r:
                assert r.status_code == 200
                for _line in r.iter_lines():
                    pass

        threads = [threading.Thread(target=one, args=(rid, 5 + 3 * i))
                   for i, rid in enumerate(ids)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=120)
    finally:
        stop.set()
        t.join(timeout=30)
        server.stop()
    seams = ["enqueue", "lease", "sched_submit", "prefill_dispatch", "admit",
             "first_write"]
    spans = {sp[0]: sp for sp in trace.recorder().loop_spans()}
    for i, rid in enumerate(ids):
        first = {}
        for e in trace.recorder().events_for(rid):
            first.setdefault(e["name"], e)
        assert set(seams) <= set(first), (rid, sorted(first))
        times = [first[n]["t"] for n in seams]
        assert times == sorted(times), (rid, dict(zip(seams, times)))
        pd = first["prefill_dispatch"]["attrs"]
        assert pd["tokens"] == 5 + 3 * i
        cause = "sched.plan" if chunked else "sched.admit"
        assert spans[pd["loop"]][2] == cause
        assert spans[first["admit"]["attrs"]["loop"]][2] in (
            "sched.resolve", "sched.callback")
    loops = {seq for seq, sp in spans.items() if sp[2] == "loop"}
    children = [sp for sp in spans.values() if sp[2] != "loop"]
    assert children and all(
        sp[1] in loops or spans[sp[1]][2] == "loop.drain" for sp in children
        if sp[1] in spans)
    assert all(spans[sp[1]][2] == "loop.drain" for sp in children
               if sp[2] == "loop.idle" and sp[1] in spans)
    per_loop = toy_engine.metrics.to_dict()["loop"]["spans"]
    assert per_loop["loop"]["count"] >= per_loop["sched.dispatch"]["count"] > 0
    assert {"loop.housekeep", "loop.drain", "sched.plan", "sched.fetch_wait",
            "sched.callback", "loop.publish"} <= set(per_loop)


def test_stream_handler_writes_a_token_whenever_it_comes():
    """``admit`` -> ``first_write`` is the stream channel and the handler's
    wake-up, not a poll: a first token pushed 105-145 ms after the request
    (where the handler used to sit 50 ms in its look for the terminal
    response) is written at once."""
    broker = InProcBroker()
    server = ProducerServer(broker, host="127.0.0.1", port=0, timeout_s=30)
    server.start()
    lags = []
    try:
        for i, wait_s in enumerate((0.105, 0.115, 0.125, 0.135, 0.145)):
            rid = f"lag-{i}"

            def work(rid=rid, wait_s=wait_s):
                req = broker.pop_request(timeout=10)
                time.sleep(wait_s)
                pushed = time.monotonic()
                broker.push_stream(req.id, [7])
                while not any(
                    e["name"] == "first_write"
                    for e in trace.recorder().events_for(rid)
                ) and time.monotonic() < pushed + 5:
                    time.sleep(0.001)
                broker.push_response(GenerateResponse(id=rid, token_ids=[7]))
                lags.append(pushed)

            th = threading.Thread(target=work, daemon=True)
            th.start()
            with httpx.stream(
                "POST", f"http://127.0.0.1:{server.port}/generate",
                json={"id": rid, "token_ids": [3, 4], "max_new_tokens": 1,
                      "is_greedy": True, "stream": True},
                timeout=30,
            ) as r:
                assert r.status_code == 200
                for _line in r.iter_lines():
                    pass
            th.join(timeout=30)
            wrote = next(
                e["t"] for e in trace.recorder().events_for(rid)
                if e["name"] == "first_write")
            lags[-1] = wrote - lags[-1]
    finally:
        server.stop()
    assert len(lags) == 5 and sorted(lags)[2] < 0.015, lags
