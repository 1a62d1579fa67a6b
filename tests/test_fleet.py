"""Fleet routing: worker registry, routing policies, failover, and
multi-replica chaos.

The fleet layer (``serve/fleet.py`` over the broker registry/routed-queue
substrate) must keep the delivery contract the single-worker stack
already guarantees — every accepted request gets exactly one terminal
response — while adding replica placement and failover. Every
broker-level behavior here is exercised on both ``InProcBroker`` and
``RedisBroker``-over-``FakeRedis`` (the real Redis code paths: JSON
registry keys, routed lists, per-worker lease keys, SCAN-based
failover).
"""

import threading
import time
from types import SimpleNamespace
from typing import NamedTuple

import pytest

from llmss_tpu.serve.broker import InProcBroker, RedisBroker
from llmss_tpu.serve.chaos import FakeRedis, ScriptedEngine
from llmss_tpu.serve.consumer import Worker
from llmss_tpu.serve.fleet import (
    FleetHarness,
    Router,
    fleet_status,
    routable_workers,
)
from llmss_tpu.serve.handoff import HandoffRecord
from llmss_tpu.serve.producer import ProducerServer, evaluate_fleet_health
from llmss_tpu.sim.invariants import audit_exactly_once, collect_responses
from llmss_tpu.serve.protocol import (
    STATE_DEAD,
    STATE_READY,
    GenerateRequest,
    prefix_hash,
)

BROKER_KINDS = ("inproc", "fakeredis")


def make_brokers(kind, **kw):
    """(producer-side broker, make_worker_broker(worker_id)) pair.

    InProc: one shared object (worker identity is per-pop). Redis: one
    client instance per participant over a shared FakeRedis server, the
    real deployment shape.
    """
    if kind == "inproc":
        b = InProcBroker(**kw)
        return b, (lambda wid: b)
    server = FakeRedis()

    def mk(wid):
        return RedisBroker(client=server, worker_id=wid, **kw)

    return mk("producer"), mk


def snap(**over):
    """A fresh ready-worker load snapshot (what consumers publish)."""
    s = {
        "state": STATE_READY,
        "alive": True,
        "rows": 4,
        "inflight_rows": 0,
        "queue_depth": 0,
        "free_slots": 4,
        "free_kv_blocks": None,
        "kv_blocks_total": None,
        "prefix_hashes": [],
        "heartbeat_s": 5.0,
        "heartbeat_ts": time.time(),
    }
    s.update(over)
    return s


def req(i=0, **kw):
    kw.setdefault("deadline_ts", time.time() + 60.0)
    # token_ids must extend prefix_token_ids (protocol.validate contract).
    toks = list(kw.get("prefix_token_ids") or []) + [i + 1]
    r = GenerateRequest(token_ids=toks, max_new_tokens=4, **kw)
    r.validate()
    return r


# -- registry ---------------------------------------------------------------


@pytest.mark.parametrize("kind", BROKER_KINDS)
def test_registry_register_publish_read(kind):
    b, _ = make_brokers(kind)
    b.register_worker({"worker_id": "w0", "model": "gpt2", "kv_blocks": 64})
    b.publish_worker_load("w0", snap(inflight_rows=2))
    workers = b.read_workers()
    assert set(workers) == {"w0"}
    info = workers["w0"]
    # Capabilities and load snapshot merge into one entry.
    assert info["model"] == "gpt2" and info["kv_blocks"] == 64
    assert info["inflight_rows"] == 2 and info["state"] == STATE_READY
    # Internal expiry bookkeeping never leaks to readers.
    assert "_expires_at" not in info
    b.deregister_worker("w0")
    assert b.read_workers() == {}


@pytest.mark.parametrize("kind", BROKER_KINDS)
def test_registry_expiry_and_reregistration(kind):
    b, _ = make_brokers(kind, worker_ttl_s=0.1)
    b.register_worker({"worker_id": "w0", "model": "gpt2"})
    assert "w0" in b.read_workers()
    time.sleep(0.15)
    # Entry ages out when the worker stops publishing entirely.
    assert b.read_workers() == {}
    # A worker may simply re-register (consumer.register is re-callable);
    # a load publish alone also resurrects + refreshes the entry.
    b.register_worker({"worker_id": "w0", "model": "gpt2"})
    assert "w0" in b.read_workers()
    time.sleep(0.06)
    b.publish_worker_load("w0", snap())
    time.sleep(0.06)  # past the original stamp, within the refreshed one
    assert "w0" in b.read_workers()


@pytest.mark.parametrize("kind", BROKER_KINDS)
def test_routed_pop_priority_and_depths(kind):
    b, _ = make_brokers(kind)
    shared = req(0, id="shared")
    routed = req(1, id="routed")
    b.push_request(shared)
    b.push_request_to("w0", routed)
    assert b.routed_depths() == {"w0": 1}
    # Routed backlog counts toward admission control.
    assert b.queue_depth() == 2
    # A worker popping with its id drains its routed queue before the
    # shared one; a plain (anonymous) pop never sees routed work.
    got = b.pop_request(worker_id="w0")
    assert got.id == "routed"
    assert b.lease_holders() == {"w0": 1}
    got2 = b.pop_request(worker_id="w0")
    assert got2.id == "shared"
    assert b.routed_depths() == {}


# -- routing policies -------------------------------------------------------


def fleet_of(b, *wids, **snap_over):
    for wid in wids:
        b.register_worker({"worker_id": wid, "model": "gpt2"})
        b.publish_worker_load(wid, snap(**snap_over.get(wid, {})))


@pytest.mark.parametrize("kind", BROKER_KINDS)
def test_round_robin_rotation(kind):
    b, _ = make_brokers(kind)
    fleet_of(b, "w0", "w1", "w2", w1={}, w0={}, w2={})
    r = Router(b, "round_robin")
    picks = [r.submit(req(i)) for i in range(6)]
    assert picks == ["w0", "w1", "w2", "w0", "w1", "w2"]
    assert b.routed_depths() == {"w0": 2, "w1": 2, "w2": 2}
    stats = r.stats()
    assert stats["routed_total"] == 6 and stats["shared_fallback"] == 0
    assert stats["routed_by_worker"] == {"w0": 2, "w1": 2, "w2": 2}


@pytest.mark.parametrize("kind", BROKER_KINDS)
def test_least_loaded_prefers_idle_then_kv_headroom(kind):
    b, _ = make_brokers(kind)
    fleet_of(
        b, "w0", "w1", "w2",
        w0={"inflight_rows": 3, "free_slots": 1},
        w1={"free_kv_blocks": 8, "kv_blocks_total": 16},
        w2={"free_kv_blocks": 2, "kv_blocks_total": 16},
    )
    r = Router(b, "least_loaded")
    # Both idle workers beat the busy one; KV headroom breaks the tie.
    assert r.submit(req(0)) == "w1"
    # The live routed depth (not just the lagging snapshot) feeds back:
    # w1 now has backlog 1, so the truly idle w2 wins next.
    assert r.submit(req(1)) == "w2"
    # Tie again at backlog 1 each — headroom prefers w1.
    assert r.submit(req(2)) == "w1"
    assert "w0" not in r.stats()["routed_by_worker"]


@pytest.mark.parametrize("kind", BROKER_KINDS)
def test_least_loaded_skips_unroutable_states(kind):
    b, _ = make_brokers(kind)
    fleet_of(
        b, "w0", "w1", "w2",
        w0={"state": STATE_DEAD},
        w1={"state": "draining"},
        w2={"inflight_rows": 4, "free_slots": 0},
    )
    r = Router(b, "least_loaded")
    # Dead and draining replicas take nothing, however loaded the
    # survivor is.
    assert r.submit(req(0)) == "w2"
    assert set(routable_workers(b)) == {"w2"}


@pytest.mark.parametrize("kind", BROKER_KINDS)
def test_prefix_affinity_snapshot_sticky_and_fallback(kind):
    b, _ = make_brokers(kind)
    pfx = [7, 7, 7, 7]
    h = prefix_hash(pfx)
    fleet_of(
        b, "w0", "w1",
        w0={"free_kv_blocks": 64},  # the headroom favorite
        w1={"prefix_hashes": [h]},  # already holds the prefix
    )
    r = Router(b, "prefix_affinity")
    # Resident prefix wins over headroom: the request rides to w1.
    assert r.submit(req(0, prefix_token_ids=pfx)) == "w1"
    # Sticky thereafter, even as w1's backlog grows.
    assert r.submit(req(1, prefix_token_ids=pfx)) == "w1"
    assert r.submit(req(2, prefix_token_ids=pfx)) == "w1"
    # Unknown prefix: least-loaded fallback (w0 — all of w1's backlog),
    # and the chosen worker becomes the sticky owner.
    new_pfx = [9, 9]
    assert r.submit(req(3, prefix_token_ids=new_pfx)) == "w0"
    assert r.submit(req(4, prefix_token_ids=new_pfx)) == "w0"
    # No prefix → plain least-loaded, no affinity accounting.
    stats_before = r.stats()
    r.submit(req(5))
    stats = r.stats()
    assert stats["affinity_hits"] == stats_before["affinity_hits"]
    assert stats["affinity_misses"] == stats_before["affinity_misses"]
    # 4 hits (3 resident/sticky + 1 new-prefix sticky), 1 miss.
    assert stats["affinity_hits"] == 4 and stats["affinity_misses"] == 1
    assert stats["affinity_hit_rate"] == pytest.approx(0.8)


@pytest.mark.parametrize("kind", BROKER_KINDS)
def test_shared_fallback_when_no_replicas(kind):
    b, _ = make_brokers(kind)
    r = Router(b, "least_loaded")
    fallback = req(0)
    assert r.submit(fallback) is None
    assert r.stats()["shared_fallback"] == 1
    assert b.routed_depths() == {}
    # The request landed on the shared queue — any worker that appears
    # later serves it.
    got = b.pop_request(worker_id="late-joiner")
    assert got is not None and got.id == fallback.id


def test_router_rejects_unknown_policy():
    b = InProcBroker()
    with pytest.raises(ValueError, match="unknown policy"):
        Router(b, "fastest")


# -- failover ---------------------------------------------------------------


@pytest.mark.parametrize("kind", BROKER_KINDS)
def test_failover_moves_routed_and_leased_to_survivor(kind):
    b, mk = make_brokers(kind)
    # w0 heartbeats on a 0.05s cadence, so it reads stale 0.15s after its
    # last publish; w1 heartbeats slowly (stays fresh for the whole test).
    fleet_of(b, "w0", "w1", w0={"heartbeat_s": 0.05}, w1={})
    r = Router(b, "round_robin", failover_check_s=0.01)
    wb = mk("w0")
    r1, r2 = req(0), req(1)
    assert r.submit(r1) == "w0"
    assert r.submit(r2) == "w1"
    # Re-route r2's twin onto w0 so it holds routed AND leased work.
    r3 = req(2)
    b.push_request_to("w0", r3)
    leased = wb.pop_request(worker_id="w0")  # r1: now in-flight on w0
    assert leased.id == r1.id and leased.delivery_attempts == 1
    time.sleep(0.2)  # w0's heartbeat is now stale; w1 still fresh
    assert set(routable_workers(b)) == {"w1"}

    moved = r.check_failover(force=True)
    assert moved == 2  # r3 (routed) + r1 (force-expired lease)
    # Everything w0 held is now on the survivor's routed queue.
    assert b.routed_depths() == {"w1": 3}
    assert b.lease_holders() == {}
    got = {b.pop_request(worker_id="w1").id for _ in range(3)}
    assert got == {r1.id, r2.id, r3.id}
    # The never-delivered r3 spent no attempt; the leased r1 spent one.
    assert r.stats()["failover_reroutes"] == 2
    assert b.delivery_stats()["failover_rerouted"] == 2


@pytest.mark.parametrize("kind", BROKER_KINDS)
def test_failover_orphan_routed_queue(kind):
    """A routed queue whose worker has vanished from the registry
    entirely (TTL expiry) is still evacuated."""
    b, _ = make_brokers(kind, worker_ttl_s=0.05)
    b.register_worker({"worker_id": "ghost", "model": "gpt2"})
    fleet_of(b, "live")
    orphan = req(0)
    b.push_request_to("ghost", orphan)
    time.sleep(0.1)  # ghost's registry entry ages out; queue remains
    assert "ghost" not in b.read_workers()
    # "live" was registered with the same short TTL — keep it fresh.
    b.publish_worker_load("live", snap())
    r = Router(b, "least_loaded")
    assert r.check_failover(force=True) == 1
    assert b.routed_depths() == {"live": 1}
    assert b.pop_request(worker_id="live").id == orphan.id


@pytest.mark.parametrize("kind", BROKER_KINDS)
def test_failover_applies_terminal_dispositions(kind):
    """Force-expired leases go through the standard at-least-once
    disposition: attempts exhausted → DLQ + terminal error; deadline
    passed → terminal deadline error. Neither is re-routed."""
    b, mk = make_brokers(kind, max_delivery_attempts=1)
    fleet_of(b, "w0", w0={"heartbeat_s": 0.05})
    wb = mk("w0")
    doomed = req(0)  # its 1st delivery attempt is also its last
    late = req(1, deadline_ts=time.time() + 0.1)
    b.push_request_to("w0", doomed)
    b.push_request_to("w0", late)
    assert wb.pop_request(worker_id="w0") is not None
    assert wb.pop_request(worker_id="w0") is not None
    time.sleep(0.2)  # w0 stale AND late's deadline passed
    r = Router(b, "least_loaded")
    assert r.check_failover(force=True) == 0  # both terminal, none moved
    assert b.dlq_depth() == 1
    dead = b.wait_response(doomed.id, timeout=1.0)
    assert dead is not None and "dead-lettered after 1" in dead.error
    shed = b.wait_response(late.id, timeout=1.0)
    assert shed is not None and "deadline" in shed.error
    assert r.stats()["failover_reroutes"] == 0


@pytest.mark.parametrize("kind", BROKER_KINDS)
def test_failover_leaves_healthy_and_draining_workers_alone(kind):
    b, _ = make_brokers(kind)
    fleet_of(
        b, "w0", "w1",
        w0={},  # healthy
        w1={"state": "draining"},  # finishing its leases on purpose
    )
    b.push_request_to("w0", req(0))
    b.push_request_to("w1", req(1))
    r = Router(b, "least_loaded")
    assert r.check_failover(force=True) == 0
    assert b.routed_depths() == {"w0": 1, "w1": 1}


# -- status surfaces --------------------------------------------------------


@pytest.mark.parametrize("kind", BROKER_KINDS)
def test_fleet_status_and_aggregate_health(kind):
    b, _ = make_brokers(kind)
    fleet_of(
        b, "w0", "w1", "w2",
        w0={},
        w1={"state": STATE_DEAD},
        w2={"heartbeat_ts": time.time() - 600.0},  # long-stale
    )
    b.push_request_to("w0", req(0))
    r = Router(b, "least_loaded")
    st = fleet_status(b, r)
    assert set(st["workers"]) == {"w0", "w1", "w2"}
    assert st["ready"] == 1
    assert st["workers"]["w0"]["routable"] is True
    assert st["workers"]["w0"]["routed_queue_depth"] == 1
    assert st["workers"]["w1"]["routable"] is False
    assert st["workers"]["w1"]["health"] == STATE_DEAD
    assert st["workers"]["w2"]["health"] == "stale-heartbeat"
    assert st["router"]["policy"] == "least_loaded"

    code, body = evaluate_fleet_health(b.read_workers())
    assert code == 200 and body["ready"] == 1
    # The last ready replica going stale flips the fleet to 503.
    b.publish_worker_load(
        "w0", snap(heartbeat_ts=time.time() - 600.0)
    )
    code, body = evaluate_fleet_health(b.read_workers())
    assert code == 503 and body["status"] == "no-ready-workers"


def test_producer_fleet_endpoints():
    import http.client
    import json

    b = InProcBroker()
    fleet_of(b, "w0", "w1", w0={}, w1={"state": STATE_DEAD})
    router = Router(b, "least_loaded")
    srv = ProducerServer(b, host="127.0.0.1", port=0, router=router)
    srv.start()
    try:
        conn = http.client.HTTPConnection("127.0.0.1", srv.port, timeout=10)
        # Aggregate health: one dead replica does not 503 the frontend.
        conn.request("GET", "/health")
        resp = conn.getresponse()
        body = json.loads(resp.read())
        assert resp.status == 200
        assert body["ready"] == 1
        assert body["workers"]["w1"]["routable"] is False
        # GET /fleet: per-worker registry detail + router stats.
        conn.request("GET", "/fleet")
        resp = conn.getresponse()
        body = json.loads(resp.read())
        assert resp.status == 200
        assert body["ready"] == 1 and set(body["workers"]) == {"w0", "w1"}
        assert body["router"]["policy"] == "least_loaded"
        # /metrics grows a fleet block with per-worker labels.
        conn.request("GET", "/metrics")
        resp = conn.getresponse()
        body = json.loads(resp.read())
        assert resp.status == 200
        fl = body["fleet"]
        assert set(fl["workers"]) == {"w0", "w1"}
        assert fl["workers"]["w0"]["state"] == STATE_READY
        assert fl["router"]["routed_total"] == 0
        # The whole fleet going dead flips /health to 503.
        b.publish_worker_load("w0", snap(state=STATE_DEAD))
        conn.request("GET", "/health")
        resp = conn.getresponse()
        body = json.loads(resp.read())
        assert resp.status == 503 and body["status"] == "no-ready-workers"
        conn.close()
    finally:
        srv.stop()


def test_producer_metrics_unchanged_without_fleet():
    """No registry, no router → the /metrics payload has no fleet block
    and /health takes the legacy single-supervisor path (bit-identical
    pre-fleet behavior)."""
    import http.client
    import json

    b = InProcBroker()
    srv = ProducerServer(b, host="127.0.0.1", port=0)
    srv.start()
    try:
        conn = http.client.HTTPConnection("127.0.0.1", srv.port, timeout=10)
        conn.request("GET", "/metrics")
        body = json.loads(conn.getresponse().read())
        assert "fleet" not in body
        conn.request("GET", "/health")
        resp = conn.getresponse()
        body = json.loads(resp.read())
        assert resp.status == 200 and body.get("worker") == "unsupervised"
        conn.close()
    finally:
        srv.stop()


# -- worker integration -----------------------------------------------------


def test_worker_registers_and_serves_routed_requests():
    b = InProcBroker()
    w = Worker(
        ScriptedEngine(), b, batch_size=2, poll_timeout_s=0.01,
        pad_batch=False, worker_id="w0", snapshot_interval_s=0.01,
    )
    info = b.read_workers()["w0"]
    assert info["model"] == "ScriptedEngine"
    assert info["state"] == STATE_READY and "heartbeat_ts" in info
    first_ts = info["heartbeat_ts"]
    r = req(0)
    b.push_request_to("w0", r)
    time.sleep(0.02)
    w.run_once()
    got = b.wait_response(r.id, timeout=5.0)
    assert got is not None and not got.error
    assert got.token_ids == ScriptedEngine.expected_tokens(
        list(r.token_ids), r.max_new_tokens
    )
    # run_once refreshed the heartbeat past the registration stamp.
    assert b.read_workers()["w0"]["heartbeat_ts"] >= first_ts


def test_anonymous_worker_stays_out_of_registry():
    b = InProcBroker()
    w = Worker(
        ScriptedEngine(), b, batch_size=2, poll_timeout_s=0.01,
        pad_batch=False,
    )
    assert b.read_workers() == {}
    r = req(0)
    b.push_request(r)
    w.run_once()
    assert b.wait_response(r.id, timeout=5.0) is not None
    assert b.read_workers() == {}


def test_scheduler_load_snapshot_is_host_only():
    """ContinuousBatcher.load_snapshot: host counters + resident prefix
    hashes, no device arrays touched."""
    from llmss_tpu.engine import GenerationParams
    from llmss_tpu.engine.scheduler import ContinuousBatcher

    class _Eng:
        kv_layout = "dense"
        max_seq_len = 64
        cfg = None
        mesh = None

        def canon_vec(self, x):
            return x

        def new_cache(self, rows):
            return None

        def check_capacity(self, prompt_len, max_new_tokens):
            pass

    b = ContinuousBatcher(_Eng(), rows=4)
    gen = GenerationParams(max_new_tokens=4, is_greedy=True)
    b.submit([1, 2], gen, lambda *_: None)
    b.submit([3, 4], gen, lambda *_: None)
    s = b.load_snapshot()
    assert s["rows"] == 4 and s["pending"] == 2
    assert s["inflight_rows"] == 0 and s["free_slots"] == 4
    assert s["free_kv_blocks"] is None and s["prefix_hashes"] == []

    # Paged bookkeeping surfaces pool headroom + prefix content hashes.
    class _Pfx(NamedTuple):
        tokens: tuple

    b._paged = True
    b.allocator = SimpleNamespace(free_blocks=5, num_blocks=8)
    b._paged_prefixes = {1: (_Pfx((1, 2, 3)), [0, 1])}
    s = b.load_snapshot()
    assert s["free_kv_blocks"] == 5 and s["kv_blocks_total"] == 8
    assert s["prefix_hashes"] == [prefix_hash((1, 2, 3))]


# -- multi-replica chaos ----------------------------------------------------


# Shared with the fleet simulator's invariant catalog (sim/invariants):
# wall-clock chaos and virtual-clock storms audit the same contract.
_collect = collect_responses


@pytest.mark.parametrize("kind", BROKER_KINDS)
def test_fleet_chaos_kill_mid_decode(kind):
    """3 replicas, one hard-killed mid-decode while holding routed and
    leased work; the machine never comes back. Failover + lease
    redelivery must get every request exactly one terminal response with
    an uncorrupted payload — zero lost, zero double-answered."""
    # A lease of 1 s: a LIVE replica that stalls past its lease (six xdist
    # workers compiling beside this test: 0.25 s was twice not enough in PR
    # 49's full runs) has its work redelivered and answers twice, which is
    # the at-least-once contract and not what this test is about.
    producer, mk = make_brokers(
        kind, lease_s=1.0, max_delivery_attempts=6,
    )
    wids = ["w0", "w1", "w2"]
    switches = {wid: threading.Event() for wid in wids}

    def make_worker(wid):
        return Worker(
            ScriptedEngine(kill_switch=switches[wid], chunk_delay_s=0.002),
            mk(wid), batch_size=2, poll_timeout_s=0.02, pad_batch=False,
            worker_id=wid, snapshot_interval_s=0.04,
        )

    # stale_factor 25 × 0.04s heartbeats: a live replica would have to
    # stall 1s to be misjudged (heartbeats refresh every decode chunk),
    # while the killed one reads stale well inside the test budget.
    router = Router(
        producer, "least_loaded", stale_factor=25.0, failover_check_s=0.05,
    )
    reqs = [req(i) for i in range(18)]
    stop_pump = threading.Event()

    def pump():
        while not stop_pump.is_set():
            router.check_failover(force=True)
            time.sleep(0.05)

    harness = FleetHarness(make_worker, wids, respawn=False)
    # w0 dies at its first decode chunk — mid-decode, leases held.
    switches["w0"].set()
    pump_t = threading.Thread(target=pump, daemon=True)
    with harness:
        deadline = time.monotonic() + 10.0
        while len(router.routable_workers()) < 3:
            assert time.monotonic() < deadline, "fleet never became ready"
            time.sleep(0.01)
        for r in reqs[:12]:
            router.submit(r)
        deadline = time.monotonic() + 10.0
        while harness.hosts["w0"].kills < 1:
            assert time.monotonic() < deadline, "kill switch never fired"
            time.sleep(0.01)
        # Strand work on the corpse: routed directly to w0, never leased.
        stranded = reqs[12:15]
        for r in stranded:
            producer.push_request_to("w0", r)
        for r in reqs[15:]:
            router.submit(r)
        pump_t.start()
        try:
            results = _collect(producer, reqs, timeout_s=60.0)
        finally:
            stop_pump.set()
            pump_t.join(timeout=5)

    assert not [h.error for h in harness.hosts.values() if h.error]
    assert harness.hosts["w0"].kills == 1
    assert harness.hosts["w0"].spawns == 1  # the machine stayed dead
    # == len(reqs): exactly-once AND zero terminal errors — a kill with
    # failover may not cost any request its clean payload.
    assert audit_exactly_once(reqs, results) == len(reqs)
    # The stranded routed work was rescued by failover, not luck.
    assert router.stats()["failover_reroutes"] >= len(stranded)
    assert producer.delivery_stats()["failover_rerouted"] >= len(stranded)
    assert "w0" not in router.routable_workers()


# -- disaggregated roles ----------------------------------------------------


def hrec(i=0, **kw):
    r = req(i, **kw)
    return HandoffRecord(
        req=r, first_token=1, n_tokens=len(r.token_ids), payload=b"kv" * 8,
    )


@pytest.mark.parametrize("kind", BROKER_KINDS)
def test_router_excludes_decode_replicas_from_raw_requests(kind):
    b, _ = make_brokers(kind)
    fleet_of(
        b, "w0", "d0",
        w0={"inflight_rows": 3, "free_slots": 1},  # busy unified replica
        d0={"role": "decode"},  # idle decode replica
    )
    r = Router(b, "least_loaded")
    # The idle decode replica NEVER takes a raw request — it only speaks
    # the handoff channel; a request routed there would strand.
    assert r.submit(req(0)) == "w0"
    assert r.submit(req(1)) == "w0"
    assert "d0" not in r.stats()["routed_by_worker"]

    # A decode-only fleet has no raw-request target at all: shared-queue
    # fallback (a prefill/unified replica appearing later serves it).
    b2, _ = make_brokers(kind)
    fleet_of(b2, "d0", d0={"role": "decode"})
    r2 = Router(b2, "least_loaded")
    assert r2.submit(req(2)) is None
    assert r2.stats()["shared_fallback"] == 1
    assert b2.routed_depths() == {}


@pytest.mark.parametrize("kind", BROKER_KINDS)
def test_fleet_status_shows_roles_and_handoff_depths(kind):
    b, mk = make_brokers(kind)
    fleet_of(
        b, "p0", "d0",
        p0={"role": "prefill"},
        d0={"role": "decode"},
    )
    routed, shared = hrec(0), hrec(1)
    b.push_handoff_to("d0", routed)
    b.push_handoff(shared)
    st = fleet_status(b, Router(b, "least_loaded"))
    assert st["workers"]["p0"]["role"] == "prefill"
    assert st["workers"]["d0"]["role"] == "decode"
    assert st["workers"]["d0"]["routed_handoff_depth"] == 1
    assert st["workers"]["d0"]["handoff_leases_held"] == 0
    assert st["handoff_depth"] == 2  # shared + routed

    # Adoption converts routed depth into a held lease (the routed queue
    # drains before the shared one, so d0 gets its targeted record).
    got = mk("d0").pop_handoff(timeout=0.5, worker_id="d0")
    assert got is not None and got.req.id == routed.req.id
    st = fleet_status(b, None)
    assert st["workers"]["d0"]["routed_handoff_depth"] == 0
    assert st["workers"]["d0"]["handoff_leases_held"] == 1
    assert st["handoff_depth"] == 1


@pytest.mark.parametrize("kind", BROKER_KINDS)
def test_failover_reroutes_handoffs_to_surviving_decode(kind):
    b, mk = make_brokers(kind)
    fleet_of(
        b, "p0", "d0", "d1",
        p0={"role": "prefill"},
        d0={"role": "decode", "heartbeat_s": 0.05},
        d1={"role": "decode"},
    )
    # d0 adopted one record (leased) and has one routed-but-unleased.
    b.push_handoff_to("d0", hrec(0, id="adopted"))
    db = mk("d0")
    got = db.pop_handoff(timeout=0.5, worker_id="d0")
    assert got is not None and got.req.id == "adopted"
    b.push_handoff_to("d0", hrec(1, id="routed"))
    time.sleep(0.2)  # d0's heartbeat goes stale; d1 stays fresh
    r = Router(b, "least_loaded", failover_check_s=0.01)
    assert r.check_failover(force=True) == 1  # the intact routed record
    # The routed record (KV payload intact) moved to the surviving
    # decode replica — no re-prefill for it...
    assert b.handoff_depths() == {"d1": 1}
    moved = mk("d1").pop_handoff(timeout=0.5, worker_id="d1")
    assert moved is not None and moved.req.id == "routed"
    # ...while the adopted one re-prefills: its device state died with
    # d0, so the embedded request returns to the shared queue.
    back = b.pop_request(timeout=0.5)
    assert back is not None and back.id == "adopted"
    assert b.delivery_stats()["reprefills"] == 1
    assert r.stats()["handoff_reroutes"] == 1


def test_producer_surfaces_roles_and_handoff_metrics():
    import http.client
    import json

    b = InProcBroker()
    fleet_of(
        b, "p0", "d0",
        p0={"role": "prefill"},
        d0={"role": "decode"},
    )
    b.push_handoff_to("d0", hrec(0))
    router = Router(b, "least_loaded")
    srv = ProducerServer(b, host="127.0.0.1", port=0, router=router)
    srv.start()
    try:
        conn = http.client.HTTPConnection("127.0.0.1", srv.port, timeout=10)
        # GET /fleet: per-worker role + handoff depth detail.
        conn.request("GET", "/fleet")
        resp = conn.getresponse()
        body = json.loads(resp.read())
        assert resp.status == 200
        assert body["workers"]["p0"]["role"] == "prefill"
        assert body["workers"]["d0"]["role"] == "decode"
        assert body["workers"]["d0"]["routed_handoff_depth"] == 1
        assert body["handoff_depth"] == 1
        # /metrics fleet block: role per worker + handoff queue depths.
        conn.request("GET", "/metrics")
        resp = conn.getresponse()
        body = json.loads(resp.read())
        assert resp.status == 200
        fl = body["fleet"]
        assert fl["workers"]["p0"]["role"] == "prefill"
        assert fl["workers"]["d0"]["role"] == "decode"
        assert fl["handoff_depth"] == 1
        assert fl["handoff_depths"] == {"d0": 1}
        # The delivery block carries the channel counters.
        assert body["delivery"]["handoffs"] == 1
        conn.close()
    finally:
        srv.stop()
