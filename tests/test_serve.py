"""Serving stack: HTTP round-trip, id correlation under concurrency,
error containment."""

import json
import threading
import time

import numpy as np
import pytest

import httpx

from llmss_tpu.engine import DecodeEngine, GenerationParams
from llmss_tpu.models import config_from_hf
from llmss_tpu.models.registry import MODEL_REGISTRY
from llmss_tpu.parallel import MeshPlan, make_mesh
from llmss_tpu.serve import GenerateRequest, InProcBroker
from llmss_tpu.serve.consumer import ContinuousWorker, Worker
from llmss_tpu.serve.producer import ProducerServer
from llmss_tpu.weights import CheckpointShards, weight_files


@pytest.fixture(scope="module")
def serving(tmp_path_factory, devices):
    import torch
    import transformers as tr

    torch.manual_seed(11)
    cfg_hf = tr.GPT2Config(
        vocab_size=64, n_positions=64, n_embd=32, n_layer=2, n_head=4
    )
    d = tmp_path_factory.mktemp("serve") / "m"
    tr.GPT2LMHeadModel(cfg_hf).eval().save_pretrained(
        d, safe_serialization=True
    )

    mesh = make_mesh(MeshPlan(dp=2, tp=4))
    from transformers import AutoConfig

    cfg = config_from_hf(AutoConfig.from_pretrained(d), dtype="float32")
    ckpt = CheckpointShards(weight_files(str(d)), dtype=np.float32)
    params = MODEL_REGISTRY["gpt2"].load_params(ckpt, cfg, mesh)
    engine = DecodeEngine(cfg, params, mesh, max_seq_len=64)

    broker = InProcBroker()
    worker = Worker(engine, broker, batch_size=4, poll_timeout_s=0.05)
    stop = threading.Event()
    t = threading.Thread(target=worker.run_forever, args=(stop,), daemon=True)
    t.start()

    server = ProducerServer(broker, host="127.0.0.1", port=0, timeout_s=120)
    server.start()

    yield server, engine
    stop.set()
    server.stop()


def _post(port, payload, timeout=120.0):
    return httpx.post(
        f"http://127.0.0.1:{port}/generate", json=payload, timeout=timeout
    )


def test_roundtrip(serving):
    server, _ = serving
    r = _post(server.port, {
        "token_ids": [1, 2, 3], "max_new_tokens": 4, "is_greedy": True,
    })
    assert r.status_code == 200, r.text
    body = r.json()
    assert len(body["token_ids"]) == 4
    assert body["id"]


def test_correlation_under_concurrency(serving):
    """Concurrent requests each get their own answer (the reference's
    producer can mix these up — SURVEY.md §2.10)."""
    server, engine = serving
    prompts = [[i + 1, i + 2, i + 3] for i in range(6)]
    expected = engine.generate(
        prompts, [GenerationParams(max_new_tokens=4, is_greedy=True)] * 6
    )

    results = {}

    def call(i):
        r = _post(server.port, {
            "token_ids": prompts[i], "max_new_tokens": 4, "is_greedy": True,
        })
        results[i] = r.json()["token_ids"]

    threads = [threading.Thread(target=call, args=(i,)) for i in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()

    for i in range(6):
        assert results[i] == expected[i], (i, results[i], expected[i])


def test_bad_request_and_health(serving):
    server, _ = serving
    r = _post(server.port, {"max_new_tokens": 4})
    assert r.status_code == 400
    r = _post(server.port, {
        "token_ids": [1], "is_greedy": False, "temperature": -1.0,
    })
    assert r.status_code == 400
    r = httpx.get(f"http://127.0.0.1:{server.port}/health", timeout=10)
    assert r.status_code == 200


def test_mixed_params_batch(serving):
    server, _ = serving
    greedy = _post(server.port, {
        "token_ids": [5, 6], "max_new_tokens": 3, "is_greedy": True,
    }).json()
    sampled = _post(server.port, {
        "token_ids": [5, 6], "max_new_tokens": 6, "is_greedy": False,
        "temperature": 0.7, "top_k": 5, "top_p": 0.9, "seed": 1,
    }).json()
    assert len(greedy["token_ids"]) == 3
    assert len(sampled["token_ids"]) == 6


def test_cancelled_pending_request_is_skipped(serving):
    """A request cancelled while still queued (e.g. producer timeout) must
    not reach the engine: the worker answers it with a 'cancelled' error."""
    _, engine = serving
    broker = InProcBroker()
    worker = Worker(engine, broker, batch_size=4, poll_timeout_s=0.01)
    broker.push_request(GenerateRequest(
        id="dead", token_ids=[1, 2], max_new_tokens=30, is_greedy=True,
    ))
    broker.cancel_request("dead")
    before = engine.metrics.cancelled
    worker.run_once()
    resp = broker.wait_response("dead", timeout=10)
    assert resp.error == "cancelled"
    assert engine.metrics.cancelled == before + 1


def test_cancel_http_route(serving):
    server, _ = serving
    r = httpx.post(
        f"http://127.0.0.1:{server.port}/cancel", json={"id": "xyz"},
        timeout=10,
    )
    assert r.status_code == 200 and r.json()["cancelled"] == "xyz"


def test_no_recompile_across_batch_sizes(serving):
    """Steady-state serving must reuse one executable per seq bucket no
    matter how many requests each queue drain yields: the worker pads the
    batch dim to its envelope (a fresh compile per live batch size would be
    a multi-second stall under bursty load)."""
    _, engine = serving
    broker = InProcBroker()
    worker = Worker(engine, broker, batch_size=4, poll_timeout_s=0.01)

    def push(n, start):
        ids = []
        for i in range(n):
            rid = f"r{start + i}"
            broker.push_request(GenerateRequest(
                id=rid, token_ids=[1 + i, 2, 3], max_new_tokens=3,
                is_greedy=True,
            ))
            ids.append(rid)
        return ids

    ids = push(4, 0)  # full batch: compiles (or reuses) the envelope shape
    worker.run_once()
    for rid in ids:
        assert broker.wait_response(rid, timeout=30).error is None
    base_prefill = engine._prefill._cache_size()
    base_decode = engine._decode._cache_size()

    for n, start in ((1, 10), (3, 20), (2, 30)):
        ids = push(n, start)
        worker.run_once()
        for rid in ids:
            assert broker.wait_response(rid, timeout=30).error is None

    assert engine._prefill._cache_size() == base_prefill
    assert engine._decode._cache_size() == base_decode


def test_prewarm_covers_all_shapes(serving):
    """After prewarm, no request shape inside the envelope may trigger a
    new compile: varied prompt-length buckets and admission batch sizes all
    hit prewarmed executables (first long-prompt request must not eat a
    multi-second XLA compile mid-serve)."""
    _, engine = serving
    broker = InProcBroker()
    worker = ContinuousWorker(
        engine, broker, rows=4, poll_timeout_s=0.01, chunk_steps=2
    )
    import gc

    frozen = gc.get_freeze_count()
    worker.prewarm()
    # what tracing the step programs left is out of the collector's reach
    assert gc.get_freeze_count() > frozen
    b = worker.batcher
    sizes = {
        "prefill_row": b._prefill_row._cache_size(),
        "insert": b._insert._cache_size(),
        "decode": engine._decode._cache_size(),
        "decode_group": engine._decode_group._cache_size(),
    }

    # Prompt lengths spanning every bucket (engine max_seq_len caps them),
    # admitted in drains of 1, 3, and 4 requests.
    rid = 0
    for n in (1, 3, 4):
        ids = []
        for _ in range(n):
            rid += 1
            L = [3, 20, 40, 7][rid % 4] % engine.max_seq_len or 3
            broker.push_request(GenerateRequest(
                id=f"p{rid}", token_ids=list(range(1, L + 1)),
                max_new_tokens=3, is_greedy=True,
            ))
            ids.append(f"p{rid}")
        deadline = time.time() + 60
        while ids and time.time() < deadline:
            worker.run_once()
            ids = [i for i in ids
                   if broker.wait_response(i, timeout=0.001) is None]
        assert not ids

    # The expensive executables (prefill buckets, fused decode) must be
    # airtight. _insert — a sub-second scatter compile — may pick up a
    # couple of late variants: the cache's PartitionSpec representation
    # alternates normalized forms as it cycles through differently-pinned
    # jit outputs, and insert sits downstream of all of them.
    assert b._prefill_row._cache_size() == sizes["prefill_row"]
    assert engine._decode._cache_size() == sizes["decode"]
    assert engine._decode_group._cache_size() == sizes["decode_group"]
    assert b._insert._cache_size() <= sizes["insert"] + 2


def test_cancel_race_orderings(serving):
    """The cancellation flag is TTL'd broker state, so both orderings land:
    (a) cancel after the request is queued, (b) cancel *before* the worker
    ever sees the request (the Redis no-cross-queue-ordering race). Both
    must answer error='cancelled', and a mid-decode cancel must not be
    disguised as a success response."""
    _, engine = serving
    broker = InProcBroker()
    worker = ContinuousWorker(
        engine, broker, rows=2, poll_timeout_s=0.01, chunk_steps=2
    )

    # (b) cancel races ahead of its request.
    broker.cancel_request("early")
    worker.run_once()  # drains nothing; flag must persist
    broker.push_request(GenerateRequest(
        id="early", token_ids=[1, 2, 3], max_new_tokens=30, is_greedy=True,
    ))
    deadline = time.time() + 60
    resp = None
    while resp is None and time.time() < deadline:
        worker.run_once()
        resp = broker.wait_response("early", timeout=0.001)
    assert resp is not None and resp.error == "cancelled"

    # (a) cancel mid-decode: honest error + partial tokens, not success.
    broker.push_request(GenerateRequest(
        id="mid", token_ids=[4, 5], max_new_tokens=40, is_greedy=True,
    ))
    for _ in range(4):
        worker.run_once()
    broker.cancel_request("mid")
    deadline = time.time() + 60
    resp = None
    while resp is None and time.time() < deadline:
        worker.run_once()
        resp = broker.wait_response("mid", timeout=0.001)
    assert resp is not None and resp.error == "cancelled"
    assert resp.token_ids is not None and 0 < len(resp.token_ids) < 40


def test_health_flips_on_stale_heartbeat(serving):
    """A hung supervised worker must not look healthy: /health serves 503
    once the published heartbeat goes stale (the reference at
    least dies visibly; a green light over a dead worker 504s clients)."""
    server, _ = serving
    broker = server.broker

    # Fresh heartbeat: healthy, with age surfaced.
    broker.publish_metrics({})
    broker.metrics_extra = lambda: {"supervisor": {
        "alive": True, "heartbeat_ts": time.time(), "heartbeat_s": 1.0,
        "restarts": 0, "last_error": None,
    }}
    broker.publish_metrics({})
    r = httpx.get(f"http://127.0.0.1:{server.port}/health", timeout=10)
    assert r.status_code == 200 and r.json()["status"] == "ok"

    # Stale heartbeat: 503.
    broker.metrics_extra = lambda: {"supervisor": {
        "alive": True, "heartbeat_ts": time.time() - 60.0,
        "heartbeat_s": 1.0, "restarts": 0, "last_error": None,
    }}
    broker.publish_metrics({})
    r = httpx.get(f"http://127.0.0.1:{server.port}/health", timeout=10)
    assert r.status_code == 503
    assert r.json()["status"] == "stale-heartbeat"

    # Dead worker: 503 regardless of age.
    broker.metrics_extra = lambda: {"supervisor": {
        "alive": False, "heartbeat_ts": time.time(), "heartbeat_s": 1.0,
        "restarts": 3, "last_error": "boom",
    }}
    broker.publish_metrics({})
    r = httpx.get(f"http://127.0.0.1:{server.port}/health", timeout=10)
    assert r.status_code == 503 and r.json()["status"] == "unhealthy"

    # Supervisor block vanishing after having been seen (metrics TTL
    # expiry over a hung worker) must NOT read as recovery.
    broker.metrics_extra = None
    broker.publish_metrics({})
    r = httpx.get(f"http://127.0.0.1:{server.port}/health", timeout=10)
    assert r.status_code == 503
    assert r.json()["status"] == "no-heartbeat-data"


def test_two_workers_share_one_broker(serving):
    """Multi-consumer topology (what RedisBroker exists for): two workers
    draining one queue must serve disjoint requests correctly, and a
    cancellation must reach the worker that owns the request — the TTL'd
    flag is readable by all workers, not competitively consumed by
    whichever polls first."""
    _, engine = serving
    broker = InProcBroker()
    w1 = ContinuousWorker(engine, broker, rows=2, poll_timeout_s=0.01,
                          chunk_steps=2)
    w2 = ContinuousWorker(engine, broker, rows=2, poll_timeout_s=0.01,
                          chunk_steps=2)

    ids = []
    for i in range(6):
        rid = f"mw{i}"
        broker.push_request(GenerateRequest(
            id=rid, token_ids=[1 + i, 2, 3], max_new_tokens=4,
            is_greedy=True,
        ))
        ids.append(rid)
    # A long request that will be cancelled mid-flight; either worker may
    # own it.
    broker.push_request(GenerateRequest(
        id="mw-long", token_ids=[9, 9], max_new_tokens=60, is_greedy=True,
    ))

    # Interleave the two workers; cancel the long request once it is
    # somewhere in the system.
    for step in range(6):
        w1.run_once()
        w2.run_once()
    broker.cancel_request("mw-long")

    deadline = time.time() + 120
    got = {}
    while len(got) < 7 and time.time() < deadline:
        w1.run_once()
        w2.run_once()
        for rid in ids + ["mw-long"]:
            if rid not in got:
                r = broker.wait_response(rid, timeout=0.001)
                if r is not None:
                    got[rid] = r
    assert set(got) == set(ids) | {"mw-long"}, sorted(got)
    for rid in ids:
        assert got[rid].error is None and len(got[rid].token_ids) == 4
    assert got["mw-long"].error == "cancelled"
    assert len(got["mw-long"].token_ids or []) < 60


def test_streaming_sse_roundtrip(serving):
    """stream: true delivers token increments as SSE events while the
    request decodes (continuous worker), then a done event with the full
    response; tokens concatenate to exactly the non-streamed result."""
    _, engine = serving
    broker = InProcBroker()
    worker = ContinuousWorker(engine, broker, rows=2, poll_timeout_s=0.01,
                              chunk_steps=2)
    stop = threading.Event()
    t = threading.Thread(target=worker.run_forever, args=(stop,),
                         daemon=True)
    t.start()
    server = ProducerServer(broker, host="127.0.0.1", port=0, timeout_s=60)
    server.start()
    try:
        ref = _post(server.port, {
            "token_ids": [5, 6, 7], "max_new_tokens": 12, "is_greedy": True,
        }).json()["token_ids"]

        events, done = [], None
        with httpx.stream(
            "POST", f"http://127.0.0.1:{server.port}/generate",
            json={"token_ids": [5, 6, 7], "max_new_tokens": 12,
                  "is_greedy": True, "stream": True},
            timeout=60,
        ) as r:
            assert r.status_code == 200
            assert "text/event-stream" in r.headers["content-type"]
            cur_event = None
            for line in r.iter_lines():
                if line.startswith("event:"):
                    cur_event = line.split(":", 1)[1].strip()
                elif line.startswith("data:"):
                    payload = json.loads(line.split(":", 1)[1])
                    if cur_event == "done":
                        done = payload
                    elif cur_event is None:
                        events.append(payload["token_ids"])
                    cur_event = None

        assert done is not None and done["error"] is None
        streamed = [t for inc in events for t in inc]
        assert len(events) >= 2  # actually incremental, not one blob
        assert streamed == ref == done["token_ids"]
    finally:
        stop.set()
        server.stop()


def test_streaming_from_batch_worker_is_incremental(serving):
    """The STATIC (batch-at-a-time) Worker streams too: stream:true must
    deliver >1 increment per request (round 3 degraded to one blob at
    completion), with engine-owned completion semantics — increments
    concatenate to exactly the final response tokens."""
    _, engine = serving
    broker = InProcBroker()
    worker = Worker(
        engine, broker, batch_size=2, poll_timeout_s=0.01, chunk_steps=2
    )
    broker.push_request(GenerateRequest(
        id="s1", token_ids=[5, 6, 7], max_new_tokens=10, is_greedy=True,
        stream=True,
    ))
    broker.push_request(GenerateRequest(
        id="p1", token_ids=[5, 6, 7], max_new_tokens=10, is_greedy=True,
    ))
    worker.run_once()

    done = broker.wait_response("s1", timeout=5)
    plain = broker.wait_response("p1", timeout=5)
    assert done is not None and done.error is None

    events = []
    while True:
        inc = broker.pop_stream("s1", timeout=0.05)
        if inc is None:
            break
        events.append(inc)
    assert len(events) >= 2, events  # actually incremental, not one blob
    streamed = [t for inc in events for t in inc]
    assert streamed == done.token_ids == plain.token_ids


@pytest.mark.parametrize("tracing", [True, False])
def test_metrics_devtel_block_is_the_compile_flag(tracing):
    """``GET /metrics`` of a live producer, both encodings: with tracing on
    the ``devtel`` block is the steady-state recompile flag and nothing
    else (the program prices no step: no ``mfu`` / ``mbu`` key or family);
    with tracing off there is no ``devtel`` key at all."""
    from llmss_tpu.utils import devtel, trace

    was = trace.enabled()
    trace.set_enabled(tracing)
    devtel.reset()  # whatever this process compiled before is not the subject
    server = ProducerServer(InProcBroker(), host="127.0.0.1", port=0)
    server.start()
    try:
        url = f"http://127.0.0.1:{server.port}/metrics"
        payload = httpx.get(url, timeout=10).json()
        text = httpx.get(url + "?format=prometheus", timeout=10).text
    finally:
        server.stop()
        trace.set_enabled(was)
    if tracing:
        assert set(payload["devtel"]) == {"compiles"}
        assert payload["devtel"]["compiles"]["flagged"] is False
        assert "llmss_devtel_compiles_steady_state_recompiles 0" in text
    else:
        assert "devtel" not in payload
        assert "devtel" not in text
    assert "mfu" not in text and "mbu" not in text
