"""chip_smoke.py's phases at a tiny size on the CPU mesh.

The script itself has no CPU mode — without a TPU its first check exits
non-zero — so its control flow (checkpoint synthesis, load, the two serve
windows over real sockets, the HF oracle and its negative control, the
tensor-parallel comparison) is checked here on every PR without a chip.
"""

import subprocess
import sys
from pathlib import Path

import jax
import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

import chip_smoke  # noqa: E402
from llmss_tpu.parallel import MeshPlan, make_mesh  # noqa: E402

TINY = dict(
    vocab_size=128, n_positions=128, n_embd=64, n_layer=2, n_head=4,
    n_inner=128, multi_query=True, activation_function="gelu_pytorch_tanh",
    layer_norm_epsilon=1e-5,
)
SERVE = chip_smoke.ServeConfig(
    max_seq_len=64, rows=4, chunk_steps=2, group_chunks=2, chunked_prefill=4,
    min_prompt=10, max_prompt=32, n_requests=6, max_new_tokens=8,
    stream_every=3, oracle_prompts=3,
)


@pytest.fixture(scope="module")
def counter():
    return chip_smoke.CompileCounter()


def test_phases_one_device(devices, tmp_path, counter):
    assert SERVE.buckets() == [16, 32]
    mesh = make_mesh(MeshPlan(tp=1), devices=devices[:1])
    out = chip_smoke.run_single(
        TINY, SERVE, mesh, seed=0, dtype="float32", ckpt_root=tmp_path,
        counter=counter,
    )
    for name in ("grouped_decode", "chunked_prefill"):
        w = out[name]
        assert w["requests"] == 6 and w["streamed"] == 2
        assert w["sse_events"] >= 2 * 2  # 8 tokens in groups of 4
        assert w["compilations_in_window"] == 0
    lg = out["logits"]
    assert max(lg["prefill"], lg["decode"]) < lg["tolerance"]
    assert lg["control_dropped_bias"] > lg["tolerance"]
    # Reused, not rewritten, the second time.
    ckpt = chip_smoke.synthesize_checkpoint(TINY, 0, tmp_path)
    assert (ckpt / ".complete").exists()


def test_phases_tensor_parallel(devices, tmp_path, counter):
    out = chip_smoke.run_tp(
        TINY, SERVE, devices[:4], seed=0, dtype="float32",
        ckpt_root=tmp_path, counter=counter,
    )
    assert out["window"]["requests"] == 6
    assert out["collectives"]["all-reduce"]["count"] >= 2
    assert len(set(out["shares"].values())) == 1


def test_a_wrong_answer_fails():
    """A response one token short makes the request raise — the window has
    no per-request except that records it and carries on. (A stub worker
    behind the real HTTP front end: no engine needed to be wrong.)"""
    import threading

    from llmss_tpu.serve.broker import InProcBroker
    from llmss_tpu.serve.producer import ProducerServer
    from llmss_tpu.serve.protocol import GenerateResponse

    broker = InProcBroker()
    server = ProducerServer(broker, host="127.0.0.1", port=0)
    server.start()

    def short_answer():
        req = broker.pop_request(timeout=30)
        broker.push_response(GenerateResponse(
            id=req.id, token_ids=[1] * (req.max_new_tokens - 1),
        ))

    worker = threading.Thread(target=short_answer, daemon=True)
    worker.start()
    body = {"id": "short", "token_ids": [1, 2, 3], "max_new_tokens": 8,
            "is_greedy": True, "stream": False}
    try:
        with pytest.raises(RuntimeError, match="7 tokens, expected 8"):
            chip_smoke._post_generate(
                f"http://127.0.0.1:{server.port}", body, min_events=1,
            )
    finally:
        worker.join(timeout=30)
        server.stop()


def test_no_tpu_exits_nonzero_and_prints_no_result():
    r = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py")],
        capture_output=True, text=True, timeout=300, cwd=REPO,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"},
    )
    assert r.returncode != 0
    assert "not 'tpu'" in r.stderr
    assert '"ok"' not in r.stdout
