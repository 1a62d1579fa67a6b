"""The read kernel of a pool of keys and values (``ops.pallas_kv``, interpret
mode on the CPU) against the XLA oracle
``ops.attention.ragged_paged_attention`` at the four head layouts the
benchmark's cells serve, and the rule that chooses it
(``models.decoder.attn_read``)."""

import dataclasses
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llmss_tpu.engine import DecodeEngine, GenerationParams
from llmss_tpu.engine.scheduler import ContinuousBatcher
from llmss_tpu.models import decoder
from llmss_tpu.models.common import DecoderConfig, IndexerConfig
from llmss_tpu.models.decoder import init_params
from llmss_tpu.ops import pallas_kv
from llmss_tpu.parallel import MeshPlan, make_mesh
from llmss_tpu.utils import trace

attn = importlib.import_module("llmss_tpu.ops.attention")

L, BS = 2, 16
B, MB = 4, 72
RING = MB * BS  # 1,152 slots: four 256-slot chunks of the walk and a half
N = 96
SENTINEL = N + 5

# (Hq, Hkv, D) of the benchmark's four cells that hold keys and values
GQA_2x256 = (16, 2, 256)  # qwen3-next: both KV heads in one sublane pair
GQA_4x128 = (20, 4, 128)  # falcon-h1: G = 5
MHA_32x128 = (32, 32, 128)  # olmo-hybrid: a pool head a query head
MQA_1x128 = (16, 1, 128)  # starcoderbase: one head, G = 16

# Each case: the heads, the chunk budget, the tokens each row has been fed
# so far (``ctx``; more than RING: the ring has wrapped) and its live queries.
CASES = {
    "gqa-hkv2-d256-chunk8": dict(
        heads=GQA_2x256, cb=8, ctx=[300, 45, 1040, 77], qlen=[0, 1, 8, 3]
    ),
    "gqa-hkv4-g5-chunk4": dict(
        heads=GQA_4x128, cb=4, ctx=[300, 45, 1040, 77], qlen=[4, 1, 0, 3]
    ),
    "mha-32x128-chunk4": dict(
        heads=MHA_32x128, cb=4, ctx=[300, 45, 140, 77], qlen=[1, 4, 0, 2]
    ),
    # the decode step: the same call at CB == 1
    "mqa-g16-decode": dict(
        heads=MQA_1x128, cb=1, ctx=[300, 0, 1025, 512], qlen=[1, 1, 1, 1]
    ),
    "mha-32x128-decode": dict(
        heads=MHA_32x128, cb=1, ctx=[130, 0, 260, 17], qlen=[1, 1, 1, 1]
    ),
    # wrapped rings: the pending slots hold older tokens, and slot order is
    # not position order
    "ring-wrapped": dict(
        heads=GQA_2x256, cb=8, ctx=[1300, 2303, 1148, 1152],
        qlen=[8, 1, 8, 3],
    ),
    # rows 0 and 1 share their first four blocks, rows 2 and 3 two
    "shared-prefix": dict(
        heads=GQA_4x128, cb=4, ctx=[100, 64, 200, 33], qlen=[3, 4, 1, 4],
        shared=True,
    ),
    # rows with q_len 0, 1 and the whole chunk side by side
    "qlen-0-1-chunk": dict(
        heads=MQA_1x128, cb=8, ctx=[600, 45, 1040, 77], qlen=[0, 1, 8, 3]
    ),
    # rows with nothing cached: the fresh keys alone; the table past a row's
    # blocks is the unmapped sentinel (>= N)
    "nothing-cached": dict(
        heads=GQA_2x256, cb=8, ctx=[0, 0, 5, 0], qlen=[8, 1, 2, 0],
        sentinel=True,
    ),
    # a bucketed read: kv_pos narrower than the table, not a whole chunk
    "bucketed-read": dict(
        heads=GQA_4x128, cb=1, ctx=[300, 45, 600, 77], qlen=[1, 1, 1, 1],
        t_bucket=608,
    ),
    # a sliding window narrower than what the rows hold, and than the chunk
    "sliding-window": dict(
        heads=GQA_4x128, cb=4, ctx=[300, 45, 1040, 1300], qlen=[4, 1, 3, 4],
        window=200,
    ),
    "sliding-window-3": dict(
        heads=MQA_1x128, cb=8, ctx=[300, 45, 2, 0], qlen=[8, 1, 6, 8],
        window=3,
    ),
    # what the chip serves in; eight bits of mantissa: the probabilities
    # and the output are each rounded once, to 2**-8 relative
    "bfloat16-hkv2": dict(
        heads=GQA_2x256, cb=8, ctx=[300, 45, 1040, 1300], qlen=[2, 1, 8, 8],
        dtype=jnp.bfloat16, tol=2e-2,
    ),
    "bfloat16-mha-decode": dict(
        heads=MHA_32x128, cb=1, ctx=[300, 0, 513, 1500], qlen=[1, 1, 1, 1],
        dtype=jnp.bfloat16, tol=2e-2,
    ),
}


def _inputs(case, seed=0):
    rng = np.random.default_rng(seed)
    (Hq, Hkv, D), cb = case["heads"], case["cb"]
    dtype = case.get("dtype", jnp.float32)
    ctx, qlen = np.asarray(case["ctx"]), np.asarray(case["qlen"])
    # every row its own blocks, interleaved so a row's are not contiguous
    bt = (np.arange(MB)[None, :] * 2 + np.arange(B)[:, None] * 3) % N
    bt = bt.astype(np.int32)
    if case.get("shared"):
        bt[1, :4] = bt[0, :4]
        bt[3, :2] = bt[2, :2]
    kv_pos = np.full((B, RING), -1, np.int32)
    for b in range(B):
        # slot s holds the newest position p < ctx with p % RING == s
        pos = np.arange(max(ctx[b] - RING, 0), ctx[b])
        kv_pos[b, pos % RING] = pos
    used = -(-np.minimum(ctx, RING) // BS)
    if case.get("sentinel"):
        for b in range(B):
            bt[b, used[b]:] = SENTINEL

    def draw(*shape):
        return jnp.asarray(rng.normal(size=shape), dtype)

    T = case.get("t_bucket", RING)
    return dict(
        q=draw(B, cb, Hq, D), k=draw(L, N, BS, Hkv, D),
        v=draw(L, N, BS, Hkv, D), kn=draw(B, cb, Hkv, D),
        vn=draw(B, cb, Hkv, D), q_pos=jnp.asarray(ctx, jnp.int32),
        q_len=jnp.asarray(qlen, jnp.int32),
        kv_pos=jnp.asarray(kv_pos[:, :T]), bt=jnp.asarray(bt),
        nblk=jnp.asarray(used, jnp.int32),
        slot0=jnp.asarray(ctx % RING, jnp.int32),
    )


@pytest.mark.parametrize("name", list(CASES))
def test_kernel_matches_the_oracle(name):
    case = CASES[name]
    x = _inputs(case)
    (Hq, Hkv, D), cb = case["heads"], case["cb"]
    window, scale = case.get("window"), D ** -0.5
    tol = case.get("tol", 2e-5)  # float32: that of tests/test_ragged.py
    T = x["kv_pos"].shape[1]
    nb = -(-T // BS) if T < RING else None
    assert pallas_kv.supports(BS, Hq, Hkv, D, cb, x["k"].dtype)
    for layer in range(L):
        got = pallas_kv.kv_paged_attention(
            x["q"], x["k"], x["v"], x["kn"], x["vn"], x["q_pos"], x["q_len"],
            x["kv_pos"], x["bt"], x["nblk"], x["slot0"], jnp.int32(layer),
            ring_len=RING, scale=scale, window=window, interpret=True,
        )
        want = attn.ragged_paged_attention(
            x["q"], x["k"], x["v"], x["kn"], x["vn"], x["q_pos"], x["q_len"],
            x["kv_pos"], x["bt"], x["slot0"], RING, scale=scale,
            window=window, n_blocks=nb, layer=layer,
        )
        got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
        assert got.shape == (B, cb, Hq, D)
        assert np.isfinite(got).all()  # padding rows too
        for b, n in enumerate(case["qlen"]):
            np.testing.assert_allclose(
                got[b, :n], want[b, :n], rtol=tol, atol=tol
            )
        if cb == 1:  # the decode step's own oracle says the same
            dec = attn.paged_decode_attention(
                x["q"], x["k"], x["v"], x["kn"], x["vn"],
                x["q_pos"][:, None], x["kv_pos"], x["bt"],
                x["slot0"][:, None], scale=scale, window=window,
                n_blocks=nb, layer=layer,
            )
            np.testing.assert_allclose(
                got, np.asarray(dec, np.float32), rtol=tol, atol=tol
            )


def test_the_chunk_width_follows_the_slot_bytes():
    """A slot is ``Hkv * D * itemsize`` bytes a pool: 256 slots fit four
    times for the pools of 1, 2 and 4 heads, 128 for 32 heads of 128."""
    bf16 = jnp.bfloat16
    assert pallas_kv.chunk_slots(16, 16, 1, 128, 1, bf16) == 256
    assert pallas_kv.chunk_slots(16, 16, 2, 256, 8, bf16) == 256
    assert pallas_kv.chunk_slots(16, 20, 4, 128, 4, bf16) == 256
    assert pallas_kv.chunk_slots(16, 32, 32, 128, 4, bf16) == 128


@pytest.mark.parametrize(
    "kwargs",
    [
        dict(head_dim=96),  # a head that is not whole lanes
        dict(block_size=8),  # bfloat16 tiles 16 sublanes
        dict(block_size=48),  # does not divide a chunk of the walk
        dict(chunk=32),  # more fresh keys than the merge holds
        dict(n_heads=128, n_kv_heads=128, head_dim=256),  # 64 KB a slot: VMEM
        dict(n_heads=16, n_kv_heads=3),  # heads that do not group
        dict(dtype=jnp.int8),
    ],
    ids=lambda kw: "-".join(f"{k}={v}" for k, v in kw.items()),
)
def test_supports_refuses(kwargs):
    ok = dict(block_size=16, n_heads=16, n_kv_heads=2, head_dim=256, chunk=8,
              dtype=jnp.bfloat16)
    assert pallas_kv.supports(**ok)
    assert not pallas_kv.supports(**{**ok, **kwargs})


# -- through the model: the rule that chooses the read, and the tokens -------

# a small GQA model inside the kernel's envelope: 8 query heads over 4 KV
# heads of 128
GQA = DecoderConfig(
    model_type="llama", vocab_size=128, hidden_size=256, n_layers=2,
    n_heads=8, n_kv_heads=4, head_dim=128, intermediate_size=128,
    max_position_embeddings=64, activation="silu", norm="rmsnorm",
    norm_eps=1e-5, mlp="swiglu", positions="rotary", rope_style="half",
    rotary_dim=128, attn_bias=False, mlp_bias=False,
    tie_word_embeddings=False, dtype="float32",
)


def _engine(mesh, **kw):
    params = init_params(GQA, mesh, jax.random.key(3))
    return DecodeEngine(
        GQA, params, mesh, max_seq_len=64, kv_layout="paged", block_size=8,
        **kw,
    )


@pytest.fixture(scope="module")
def one_device(devices):
    return make_mesh(MeshPlan(tp=1), devices=devices[:1])


def test_greedy_tokens_equal_under_the_kernel_and_the_gather(one_device):
    """Prompts streamed through the mixed step (4 tokens a row a step)
    beside rows that decode, then decode groups alone: the same greedy
    tokens whether the step programs read the pool through ``kv.kernel``
    (forced, interpreted) or the gather, and every group's ``sched.dispatch``
    span says which."""
    prompts = [list(range(2, 22)), [3, 14, 15, 9, 26, 5], [7] * 11]
    gen = GenerationParams(max_new_tokens=6, is_greedy=True)
    outs, reads = {}, {}
    trace.set_enabled(True)
    for impl in ("xla", "pallas"):
        trace.recorder().clear()
        with attn.force_impl(impl):
            eng = _engine(one_device)
            b = ContinuousBatcher(
                eng, rows=2, chunk_steps=2, group_chunks=2, chunked_prefill=4
            )
            res = {}
            for i, p in enumerate(prompts):
                b.submit(p, gen, lambda toks, i=i, **kw: res.__setitem__(i, toks))
            b.run_until_idle()
        outs[impl] = res
        spans = [sp[5] for sp in trace.recorder().loop_spans()
                 if sp[2] == "sched.dispatch"]
        assert {a["kind"] for a in spans} == {"ragged_group", "decode_group"}
        reads[impl] = {a["attn_read"] for a in spans}
        for a in spans:
            assert 0 <= a["blocks_read"] <= a["blocks_ring"]
    assert reads == {"xla": {"gather"}, "pallas": {"kv.kernel"}}
    assert outs["xla"] == outs["pallas"], outs


def test_attn_read_chooses_the_kernel_by_what_it_can_see(
    one_device, devices, monkeypatch,
):
    """On a TPU (here: ``pallas_interpret`` answering as one) a one-device
    pool of keys and values inside ``supports`` is read by ``kv.kernel``, in
    the decode and the mixed step; an int8 pool, a ``tp`` mesh, shapes
    outside ``supports`` and ``force == "xla"`` keep the gather. On the CPU
    nothing is chosen unless forced."""
    eng = _engine(one_device)
    cache = eng.new_paged_cache(2)
    for chunk in (1, 4):
        assert decoder.attn_read(GQA, cache, one_device, chunk) == "gather"
    monkeypatch.setattr(attn, "pallas_interpret", lambda: False)
    for chunk in (1, 4):
        assert decoder.attn_read(GQA, cache, one_device, chunk) == "kv.kernel"
        assert decoder.attn_read(GQA, cache, None, chunk) == "kv.kernel"
    assert decoder.attn_read(GQA, cache, one_device, 32) == "gather"
    with attn.force_impl("xla"):
        assert decoder.attn_read(GQA, cache, one_device, 1) == "gather"
    quantized = _engine(one_device, kv_dtype="int8").new_paged_cache(2)
    assert quantized.quantized
    assert decoder.attn_read(GQA, quantized, one_device, 1) == "gather"
    tp = make_mesh(MeshPlan(tp=2), devices=devices[:2])
    assert decoder.attn_read(GQA, cache, tp, 1) == "gather"
    # forced on a mesh the kernel does not serve: the block-at-a-time ones
    with attn.force_impl("pallas"):
        assert decoder.attn_read(GQA, cache, tp, 1) == "kernel"
        assert decoder.attn_read(GQA, cache, one_device, 1) == "kv.kernel"

    # a model that selects what attention reads (``cfg.indexer``): the same
    # rule names ``dsa.kernel`` (ops/pallas_dsa.py), up to 32 tokens a row a
    # step; what it cannot take keeps the XLA forms by their names
    picks = dataclasses.replace(
        GQA, indexer=IndexerConfig(n_heads=4, head_dim=64, topk=16)
    )
    xla = {1: "dsa.tokens", 4: "dsa.mask", 32: "dsa.mask"}
    for chunk, form in xla.items():
        assert decoder.attn_read(picks, cache, one_device, chunk) == "dsa.kernel"
        assert decoder.attn_read(picks, cache, None, chunk) == "dsa.kernel"
        assert decoder.attn_read(picks, quantized, one_device, chunk) == form
        assert decoder.attn_read(picks, cache, tp, chunk) == form
        with attn.force_impl("xla"):
            assert decoder.attn_read(picks, cache, one_device, chunk) == form
    assert decoder.attn_read(picks, cache, one_device, 33) == "dsa.mask"
    monkeypatch.undo()  # the CPU again: nothing is chosen unless forced
    for chunk, form in xla.items():
        assert decoder.attn_read(picks, cache, one_device, chunk) == form
        with attn.force_impl("pallas"):
            assert decoder.attn_read(
                picks, cache, one_device, chunk) == "dsa.kernel"
    with attn.force_impl("pallas"), pytest.warns(UserWarning, match="selected"):
        assert decoder.attn_read(picks, cache, one_device, 33) == "dsa.mask"
