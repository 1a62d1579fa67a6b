"""Metrics: stats math + engine/serving integration."""

import numpy as np
import pytest

from llmss_tpu.utils.metrics import EngineMetrics, LatencyStat


def test_latency_stat_percentiles():
    s = LatencyStat("x")
    for v in [0.01, 0.02, 0.03, 0.04, 0.1]:
        s.record(v)
    d = s.to_dict()
    assert d["count"] == 5
    assert d["p50_ms"] == 30.0
    assert d["p99_ms"] == 100.0
    assert abs(d["mean_ms"] - 40.0) < 1e-6


def test_engine_metrics_shape():
    m = EngineMetrics()
    m.add_request(2)
    m.add_tokens(10)
    m.ttft.record(0.05)
    d = m.to_dict()
    assert d["requests_served"] == 2
    assert d["tokens_generated"] == 10
    assert d["ttft"]["count"] == 1
    assert d["poisoned_rows"] == 0


def test_poisoned_row_counter():
    m = EngineMetrics()
    m.add_poisoned()
    m.add_poisoned(2)
    assert m.to_dict()["poisoned_rows"] == 3


def test_supervisor_lifecycle_fields_exported():
    """The health channel carries the lifecycle state machine: state,
    watchdog stall count, and the watchdog config ride every publish (the
    producer's /health and /metrics read them from here)."""
    from llmss_tpu.serve.broker import InProcBroker
    from llmss_tpu.serve.protocol import STATE_STARTING, WORKER_STATES
    from llmss_tpu.serve.supervisor import Supervisor

    b = InProcBroker()
    sup = Supervisor(
        lambda: None, b, heartbeat_s=0.0, step_timeout_s=12.5,
    )
    b.publish_metrics({})
    s = b.read_metrics()["supervisor"]
    assert s["state"] == STATE_STARTING
    assert s["state"] in WORKER_STATES
    assert s["watchdog_stalls"] == 0
    assert s["step_timeout_s"] == 12.5
    assert "heartbeat_ts" in s and "heartbeat_s" in s
    sup.watchdog_stalls += 1
    b.publish_metrics({})
    assert b.read_metrics()["supervisor"]["watchdog_stalls"] == 1


def test_engine_records_metrics(tmp_path, devices):
    import torch
    import transformers as tr

    from llmss_tpu.engine import DecodeEngine, GenerationParams
    from llmss_tpu.models import config_from_hf
    from llmss_tpu.models.registry import MODEL_REGISTRY
    from llmss_tpu.parallel import MeshPlan, make_mesh
    from llmss_tpu.weights import CheckpointShards, weight_files

    torch.manual_seed(1)
    cfg_hf = tr.GPT2Config(
        vocab_size=64, n_positions=64, n_embd=32, n_layer=2, n_head=4
    )
    d = tmp_path / "m"
    tr.GPT2LMHeadModel(cfg_hf).eval().save_pretrained(
        d, safe_serialization=True
    )
    from transformers import AutoConfig

    mesh = make_mesh(MeshPlan(dp=2, tp=4))
    cfg = config_from_hf(AutoConfig.from_pretrained(d), dtype="float32")
    ckpt = CheckpointShards(weight_files(str(d)), dtype=np.float32)
    params = MODEL_REGISTRY["gpt2"].load_params(ckpt, cfg, mesh)
    engine = DecodeEngine(cfg, params, mesh, max_seq_len=64)

    engine.generate([[1, 2, 3]], GenerationParams(max_new_tokens=5))
    m = engine.metrics.to_dict()
    assert m["requests_served"] == 1
    assert m["tokens_generated"] == 5
    assert m["ttft"]["count"] == 1
    assert m["decode_step"]["count"] == 4

def test_latency_stat_reservoir_spans_stream():
    """Algorithm-R sampling: once the reservoir is full, retained samples
    must span the whole stream rather than being a cyclic slice of the
    most recent ``max_samples`` values (the old deterministic-stride
    behavior). The rng is seeded from the stat name, so this is exact."""
    s = LatencyStat("resv", max_samples=50)
    for i in range(1000):
        s.record(float(i))
    assert len(s._samples) == 50
    early = sum(1 for v in s._samples if v < 500.0)
    # The stride sampler would keep only the tail (early == 0); a fair
    # reservoir keeps ~half from the first half of the stream.
    assert 10 <= early <= 40
    d = s.to_dict()
    assert set(d) == {"count", "mean_ms", "p50_ms", "p95_ms", "p99_ms"}
    assert d["count"] == 1000


def test_latency_stat_reservoir_deterministic():
    a, b = LatencyStat("same", max_samples=20), LatencyStat("same", max_samples=20)
    for i in range(300):
        a.record(float(i))
        b.record(float(i))
    assert a._samples == b._samples
    assert a.to_dict() == b.to_dict()


def test_render_prometheus():
    from llmss_tpu.utils.metrics import render_prometheus

    payload = {
        "requests_served": 3,
        "ttft": {
            "count": 2, "mean_ms": 5.0, "p50_ms": 4.0,
            "p95_ms": 6.0, "p99_ms": 6.5,
        },
        "delivery": {"redelivered": 1, "handoff_bytes": 64},
        "supervisor": {"state": "ready", "alive": True, "restarts": 0},
        "fleet": {
            "handoff_depth": 0,
            "workers": {
                "w0": {"queue_depth": 2, "free_slots": 4, "state": "ready"},
                "w1": {"queue_depth": 0, "free_slots": 8, "state": "ready"},
            },
        },
    }
    text = render_prometheus(payload)
    lines = text.splitlines()
    assert "llmss_requests_served 3" in lines
    # Latency dicts become a quantile family plus _count/_mean_ms.
    assert "# TYPE llmss_ttft_ms gauge" in lines
    assert 'llmss_ttft_ms{quantile="p50"} 4.0' in lines
    assert 'llmss_ttft_ms{quantile="p99"} 6.5' in lines
    assert "llmss_ttft_count 2" in lines
    assert "llmss_ttft_mean_ms 5.0" in lines
    assert "llmss_delivery_redelivered 1" in lines
    # Fleet workers get a worker label instead of per-worker names.
    assert 'llmss_fleet_worker_queue_depth{worker="w0"} 2' in lines
    assert 'llmss_fleet_worker_free_slots{worker="w1"} 8' in lines
    assert "llmss_fleet_handoff_depth 0" in lines
    # Strings and bools are not Prometheus samples.
    assert "ready" not in text and "alive" not in text
    assert "llmss_supervisor_restarts 0" in lines
    assert text.endswith("\n")


@pytest.mark.parametrize("gen_kw, filtered", [
    (dict(is_greedy=True, top_p=0.5, top_k=3), False),  # greedy: no draw
    (dict(is_greedy=False, temperature=0.8), False),  # plain categorical
    (dict(is_greedy=False, top_p=0.95), True),
    (dict(is_greedy=False, top_k=5), True),
])
def test_filter_steps_count_the_groups_with_a_filtered_row_live(
    toy_engine, gen_kw, filtered,
):
    """``loop.filter_steps`` rises by ``chunks x k`` for every group
    dispatched while a live row samples with an active top-k / top-p, and
    not otherwise; like ``decode_steps`` it is counted with tracing off."""
    from llmss_tpu.engine import GenerationParams
    from llmss_tpu.engine.scheduler import ContinuousBatcher
    from llmss_tpu.utils import trace

    was = trace.enabled()
    trace.set_enabled(False)
    try:
        batcher = ContinuousBatcher(
            toy_engine, rows=2, chunk_steps=2, group_chunks=2,
        )
        m = toy_engine.metrics
        before = m.to_dict()["loop"]
        # one greedy row beside the row under test: the count goes by ANY
        # live row, and the greedy one outlives the other
        batcher.submit(
            [5, 9], GenerationParams(max_new_tokens=24), lambda t: None,
        )
        batcher.submit(
            [3, 14, 15], GenerationParams(max_new_tokens=8, **gen_kw),
            lambda t: None,
        )
        want = 0
        while not batcher.idle:
            g0 = m.groups_dispatched
            # a group goes out with the rows live when step() is entered
            live = any(not r.gen.is_greedy for r in batcher.active.values())
            batcher.step()
            if m.groups_dispatched > g0:
                g = batcher._inflight
                want += g.n_chunks * g.k * int(filtered and live)
        after = m.to_dict()["loop"]
    finally:
        trace.set_enabled(was)
    steps = after["decode_steps"] - before["decode_steps"]
    assert after["spans"] == before["spans"]  # tracing was off
    assert after["filter_steps"] - before["filter_steps"] == want
    assert (0 < want < steps) if filtered else want == 0
