"""Deterministic fleet simulator (llmss_tpu/sim): virtual-clock storms
over the real serving stack, byte-identical replays, and the fleet-wide
invariant catalog.

Every test here runs the REAL broker / router / brownout / preemption
code under the sim's virtual clock — the sim never mocks them — so a
green run certifies the serving stack, not a model of it. Scenarios are
dicts (the JSON file format, inline); ``run_scenario`` raises
``InvariantViolation`` if any request is lost, double-answered, refunded
wrong, or dead-lettered without being poison.
"""

import copy
import json

import pytest

from llmss_tpu.serve.broker import InProcBroker, RedisBroker
from llmss_tpu.serve.chaos import ChaosBroker, FakeRedis
from llmss_tpu.serve.protocol import GenerateRequest
from llmss_tpu.sim import DeviceCostModel, FleetSim, run_scenario

FMT = "llmss-scenario/1"


def smoke_spec(**over):
    spec = {
        "format": FMT,
        "name": "smoke",
        "seed": 7,
        "duration_s": 120.0,
        "broker": {"kind": "inproc"},
        "fleet": {"replicas": [{"count": 2, "role": "unified"}]},
        "workload": {
            "kind": "synthetic", "requests": 200, "rate_rps": 40.0,
            "prompt_len": [8, 64], "max_new": [4, 24],
        },
    }
    spec.update(over)
    return spec


def gauntlet_spec(broker_kind, seed, requests=600):
    """Mixed unified+disagg fleet, all five fault kinds, poison."""
    return {
        "format": FMT,
        "name": f"gauntlet-{broker_kind}",
        "seed": seed,
        "duration_s": 120.0,
        "broker": {
            "kind": broker_kind, "lease_s": 2.0, "max_delivery_attempts": 8,
        },
        "fleet": {
            "replicas": [
                {"count": 4, "role": "unified"},
                {"count": 2, "role": "prefill"},
                {"count": 2, "role": "decode"},
            ],
            "router_policy": "least_loaded",
            "failover_check_s": 1.0,
        },
        "workload": {
            "kind": "synthetic", "requests": requests, "rate_rps": 120.0,
            "prompt_len": [8, 96], "max_new": [4, 32],
            "classes": {"interactive": 0.3, "standard": 0.5, "batch": 0.2},
            "deadline_s": {"interactive": 30.0},
            "poison_every": 200,
        },
        "faults": [
            {"kind": "kill_wave", "at_s": 4.0, "count": 2,
             "respawn_after_s": 1.0, "repeat_every_s": 5.0},
            {"kind": "partition", "at_s": 6.0, "duration_s": 2.5,
             "targets": 1},
            {"kind": "latency_spike", "at_s": 9.0, "duration_s": 3.0,
             "extra_s": 0.08, "targets": "*"},
            {"kind": "heartbeat_stall", "at_s": 11.0, "duration_s": 4.0,
             "count": 1},
            {"kind": "handoff_storm", "at_s": 7.5, "count": 1,
             "respawn_after_s": 0.8, "repeat_every_s": 7.0},
        ],
    }


def run_twice(spec):
    """Same seed twice; the whole report must be byte-identical."""
    a = json.dumps(run_scenario(copy.deepcopy(spec)), sort_keys=True)
    b = json.dumps(run_scenario(copy.deepcopy(spec)), sort_keys=True)
    assert a == b, "same-seed scenario replay diverged"
    return json.loads(a)


# -- determinism + smoke -----------------------------------------------------


def test_smoke_deterministic_and_clean():
    r = run_twice(smoke_spec())
    assert r["requests"]["submitted"] == 200
    assert r["requests"]["ok"] == 200
    assert r["invariants"]["violations"] == 0
    assert r["invariants"]["pending_at_drain"] == 0
    assert r["throughput"]["tokens_out"] > 0
    assert r["latency_ms"]["ttft_p95"] > 0


def test_different_seed_different_run():
    a = run_scenario(smoke_spec(seed=7))
    b = run_scenario(smoke_spec(seed=8))
    assert a["latency_ms"] != b["latency_ms"]


def test_bad_format_rejected():
    with pytest.raises(ValueError, match="format"):
        FleetSim({"format": "llmss-scenario/999"})


# -- fault gauntlets over both brokers ---------------------------------------


def test_gauntlet_inproc():
    r = run_twice(gauntlet_spec("inproc", seed=11))
    reqs = r["requests"]
    # Every non-poison request answered OK despite kills, partitions,
    # stalls, and handoff storms; only poison dead-letters.
    assert reqs["answered"] == reqs["submitted"]
    assert reqs["dead_lettered"] == 600 // 200
    assert reqs["ok"] == reqs["submitted"] - reqs["dead_lettered"]
    assert r["faults"]["kills"] > 0
    assert r["faults"]["partitions"] > 0
    assert r["delivery"]["redelivered"] > 0
    assert r["delivery"]["handoffs"] > 0


def test_gauntlet_fakeredis():
    """Same storm through the real RedisBroker code paths (per-worker
    lease keys, SCAN reaper, DLQ list) on the virtual-clock FakeRedis."""
    r = run_twice(gauntlet_spec("fakeredis", seed=3, requests=400))
    reqs = r["requests"]
    assert reqs["answered"] == reqs["submitted"]
    assert reqs["dead_lettered"] == 400 // 200
    assert r["faults"]["kills"] > 0


# -- targeted fault semantics ------------------------------------------------


def test_preemption_refund_keeps_exactly_once():
    """Batch rows evicted for interactive arrivals come back through the
    preemption-refund path (no delivery attempt consumed) and every
    request still completes cleanly."""
    spec = smoke_spec(
        name="preempt",
        fleet={"replicas": [{
            "count": 1, "role": "unified", "rows": 2, "preempt": True,
        }]},
        workload={
            "kind": "synthetic", "requests": 120, "rate_rps": 60.0,
            "prompt_len": [4, 16], "max_new": [8, 24],
            "classes": {"interactive": 0.5, "batch": 0.5},
        },
    )
    r = run_twice(spec)
    assert r["faults"]["preemptions"] > 0
    assert r["delivery"]["preempted"] > 0
    assert r["requests"]["ok"] == r["requests"]["submitted"]


def test_handoff_storm_reprefills():
    """Killing prefill replicas mid-handoff forces re-prefill via lease
    redelivery; nothing is lost and nothing lands in the DLQ."""
    spec = smoke_spec(
        name="handoff-storm",
        fleet={"replicas": [
            {"count": 2, "role": "prefill"},
            {"count": 2, "role": "decode"},
        ]},
        faults=[{"kind": "handoff_storm", "at_s": 1.0, "count": 1,
                 "respawn_after_s": 0.5, "repeat_every_s": 2.0}],
    )
    r = run_twice(spec)
    assert r["delivery"]["handoffs"] > 0
    assert r["faults"]["kills"] > 0
    assert r["requests"]["ok"] == r["requests"]["submitted"]
    assert r["delivery"]["dead_lettered"] == 0


# -- workload replay ---------------------------------------------------------


def test_workload_file_replay(tmp_path):
    """Native replay of an llmss-workload/1 capture: arrivals, lengths,
    classes, and session ids replay verbatim."""
    doc = {
        "format": "llmss-workload/1",
        "requests": [
            {
                "req_id": f"cap{i}", "arrival_s": i * 0.05,
                "prompt_len": 8 + i, "max_new_tokens": 6,
                "slo_class": "interactive" if i % 2 else "standard",
                "session_id": f"sess-{i % 3}" if i % 2 else None,
            }
            for i in range(40)
        ],
    }
    path = tmp_path / "capture.json"
    path.write_text(json.dumps(doc))
    spec = smoke_spec(
        name="replay",
        workload={"kind": "workload-file", "path": str(path)},
    )
    r = run_twice(spec)
    assert r["requests"]["submitted"] == 40
    assert r["requests"]["ok"] == 40


def test_trace_workload_inline_rows():
    spec = smoke_spec(
        name="trace",
        workload={"kind": "trace", "rows": [
            {"arrival_s": 0.0, "token_ids": [5, 6, 7], "max_new": 4,
             "slo_class": "interactive", "id": "t-a"},
            {"arrival_s": 0.2, "prompt_len": 12, "max_new": 8,
             "session_id": "s0"},
        ]},
    )
    r = run_twice(spec)
    assert r["requests"]["submitted"] == 2
    assert r["requests"]["ok"] == 2


# -- cost model --------------------------------------------------------------


def test_cost_model_table_overrides():
    m = DeviceCostModel.from_config(
        {"kind": "table", "decode_step_s": 0.02, "prefill_token_s": 1e-4}
    )
    assert m.decode_step_s == 0.02
    assert m.step_s(4, feeding_tokens=10) == pytest.approx(0.02 + 10e-4)
    assert m.kv_blocks(17, 16) == 3  # ceil(33 / 16)


# -- broker fault plumbing (satellites: retry + partition/latency) -----------


def test_redis_broker_retries_transient_connection_errors():
    """Two injected connection failures on the pop path are absorbed by
    the capped-backoff retry loop and surface in delivery_stats."""
    server = FakeRedis()
    fail = {"left": 2}

    def hook(op):
        if op == "rpop" and fail["left"] > 0:
            fail["left"] -= 1
            raise ConnectionError("injected blip")

    server.fault_hook = hook
    b = RedisBroker(client=server, worker_id="w0", retry_base_s=0.001)
    b.push_request(GenerateRequest(token_ids=[1], max_new_tokens=2))
    req = b.pop_request(timeout=0.0)
    assert req is not None
    assert b.delivery_stats()["broker_retries"] == 2


def test_redis_broker_retry_budget_exhausts():
    server = FakeRedis()
    server.fault_hook = lambda op: (_ for _ in ()).throw(
        ConnectionError("down hard")
    )
    b = RedisBroker(
        client=server, worker_id="w0", retry_attempts=2, retry_base_s=0.001,
    )
    with pytest.raises(ConnectionError):
        b.pop_request(timeout=0.0)


def test_chaos_broker_partition_window_and_latency():
    inner = InProcBroker()
    cb = ChaosBroker(inner, seed=1, op_latency_s=0.0)
    cb.partition_for(0.15)
    with pytest.raises(ConnectionError):
        cb.pop_request(timeout=0.0)
    assert cb.faults["partition_errors"] == 1
    cb._partition_until = 0.0  # close the window
    inner.push_request(GenerateRequest(token_ids=[1], max_new_tokens=2))
    cb.op_latency_s = 0.001
    assert cb.pop_request(timeout=0.0) is not None
    assert cb.faults["latency_injections"] >= 1
