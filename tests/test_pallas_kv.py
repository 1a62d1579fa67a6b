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

BS = 16
# 72 blocks a row unless a case says: 1,152 slots, four 256-slot chunks of
# the walk and a half; four rows and two layers unless a case says
N = 96
SENTINEL = N + 5

# (Hq, Hkv, D) of the benchmark's four cells that hold keys and values
GQA_2x256 = (16, 2, 256)  # qwen3-next: both KV heads in one sublane pair
GQA_4x128 = (20, 4, 128)  # falcon-h1: G = 5
MHA_32x128 = (32, 32, 128)  # olmo-hybrid: a pool head a query head
MQA_1x128 = (16, 1, 128)  # starcoderbase: one head, G = 16

# Each case: the heads, the chunk budget, the tokens each row has been fed
# so far (``ctx``; more than RING: the ring has wrapped) and its live queries.
# A case over the long ring reads the LAST layer of its stack (interpretation
# costs by the grid step); the cases over a short ring (``mb``) read every
# layer, and hold that each layer index reads its own layer.
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
    # a ring of two blocks (``mb``), shorter than one chunk of the walk: the
    # rows end inside a block (20, 30), at its edge (16) and past the ring
    # (40: wrapped); the same with one KV head, under a window of 8, and a
    # mixed step whose chunk crosses a block's edge and the ring's end
    "ring-of-32-mha-decode": dict(
        heads=(4, 4, 128), cb=1, mb=2, ctx=[20, 30, 40, 16], qlen=[1] * 4
    ),
    "ring-of-32-mqa-decode": dict(
        heads=(4, 1, 128), cb=1, mb=2, ctx=[20, 0, 40, 31], qlen=[1] * 4
    ),
    "ring-of-32-window-8": dict(
        heads=(4, 4, 128), cb=1, mb=2, ctx=[30, 5, 40, 8], qlen=[1] * 4,
        window=8,
    ),
    "ring-of-32-chunk4": dict(
        heads=(4, 2, 128), cb=4, mb=2, ctx=[13, 0, 27, 30], qlen=[3, 4, 1, 4]
    ),
    # one row alone, its ring of four blocks full: 8 query heads over 2
    "one-row-gqa-8-over-2": dict(
        heads=(8, 2, 128), cb=1, mb=4, ctx=[64], qlen=[1]
    ),
    # three rows of a stack of three layers (every case reads each layer)
    "three-layers-three-rows": dict(
        heads=(8, 2, 128), cb=1, mb=4, layers=3, ctx=[13, 5, 27], qlen=[1] * 3
    ),
    # All heads of a chunk in one product (``attn_form``'s ``heads``: a KV
    # head a query head, whole tiles of them), over a ring of ten blocks, two
    # chunks and a half of the walk in float32: wrapped, rows that share their
    # first blocks, nothing cached, a read bucket that ends inside a chunk,
    # windows narrower than the rows and than the chunk, what the chip
    # serves in, and two smaller pools that still take the form
    "heads-ring-wrapped": dict(
        heads=MHA_32x128, cb=4, mb=10, ctx=[170, 330, 95, 160],
        qlen=[4, 1, 4, 3],
    ),
    "heads-shared-prefix": dict(
        heads=MHA_32x128, cb=4, mb=10, ctx=[100, 64, 150, 33],
        qlen=[3, 4, 1, 4], shared=True,
    ),
    "heads-nothing-cached": dict(
        heads=MHA_32x128, cb=4, mb=10, ctx=[0, 0, 5, 0], qlen=[4, 1, 2, 0],
        sentinel=True,
    ),
    "heads-bucketed-read": dict(
        heads=MHA_32x128, cb=1, mb=10, ctx=[100, 45, 140, 77], qlen=[1] * 4,
        t_bucket=112,
    ),
    "heads-sliding-window": dict(
        heads=MHA_32x128, cb=4, mb=10, ctx=[150, 45, 300, 9],
        qlen=[4, 1, 3, 4], window=20,
    ),
    "heads-sliding-window-3": dict(
        heads=MHA_32x128, cb=4, mb=10, ctx=[150, 45, 2, 0], qlen=[4, 1, 3, 4],
        window=3,
    ),
    "heads-bfloat16-chunk4": dict(
        heads=MHA_32x128, cb=4, mb=10, ctx=[150, 0, 330, 77],
        qlen=[0, 1, 4, 2], dtype=jnp.bfloat16, tol=2e-2,
    ),
    "heads-8x8-chunk4": dict(
        heads=(8, 8, 128), cb=4, mb=10, ctx=[150, 45, 330, 77],
        qlen=[0, 1, 4, 2],
    ),
    "heads-8x8-decode": dict(
        heads=(8, 8, 128), cb=1, mb=10, ctx=[150, 0, 330, 16], qlen=[1] * 4
    ),
    "heads-bfloat16-16x16-d256-chunk2": dict(
        heads=(16, 16, 256), cb=2, mb=10, ctx=[150, 45, 330, 77],
        qlen=[2, 1, 0, 2], dtype=jnp.bfloat16, tol=2e-2,
    ),
}
# the cases above that take ``heads``; every other takes ``head``
ALL_HEADS = {n for n in CASES if n.startswith(("heads-", "mha-32x128"))} | {
    "bfloat16-mha-decode"
}


def _inputs(case, seed=0):
    rng = np.random.default_rng(seed)
    (Hq, Hkv, D), cb = case["heads"], case["cb"]
    dtype = case.get("dtype", jnp.float32)
    ctx, qlen = np.asarray(case["ctx"]), np.asarray(case["qlen"])
    B, MB, L = len(ctx), case.get("mb", 72), case.get("layers", 2)
    RING = MB * BS
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
    T, (L, B) = x["kv_pos"].shape[1], (x["k"].shape[0], x["q"].shape[0])
    RING = x["bt"].shape[1] * BS
    nb = -(-T // BS) if T < RING else None
    assert pallas_kv.supports(BS, Hq, Hkv, D, cb, x["k"].dtype)
    form = pallas_kv.attn_form(Hq, Hkv, cb, x["k"].dtype)
    assert form == ("heads" if name in ALL_HEADS else "head")
    outs = []
    for layer in (range(L) if "mb" in case else (L - 1,)):
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
        outs.append(got)
    if len(outs) > 1:  # each layer index read its own layer of the stack
        live = (np.asarray(case["qlen"]) > 0) & (np.asarray(case["ctx"]) > 0)
        assert not np.allclose(outs[0][live, 0], outs[-1][live, 0], atol=1e-3)


def test_the_chunk_width_follows_the_slot_bytes():
    """A slot is ``Hkv * D * itemsize`` bytes a pool: 256 slots fit four
    times for the pools of 1, 2 and 4 heads, 128 for 32 heads of 128."""
    bf16 = jnp.bfloat16
    assert pallas_kv.chunk_slots(16, 16, 1, 128, 1, bf16) == 256
    assert pallas_kv.chunk_slots(16, 16, 2, 256, 8, bf16) == 256
    assert pallas_kv.chunk_slots(16, 20, 4, 128, 4, bf16) == 256
    assert pallas_kv.chunk_slots(16, 32, 32, 128, 4, bf16) == 128


# (query heads, KV heads, head size) by (tokens a row a step, dtype): the
# form ``attn_form`` gives. The benchmark's cells by name, then the corners
# of the rule.
bf16, f32 = jnp.bfloat16, jnp.float32
FORMS = {
    "starcoderbase-1b": (MQA_1x128, {(1, bf16): "head", (4, bf16): "head"}),
    "falcon-h1-34b-1chip": (GQA_4x128, {(1, bf16): "head", (4, bf16): "head"}),
    "qwen3-next-80b-a3b-1chip": (
        GQA_2x256, {(1, bf16): "head", (8, bf16): "head"}),
    "olmo-hybrid-7b-1chip": (
        MHA_32x128, {(1, bf16): "heads", (4, bf16): "heads", (1, f32): "heads",
                     # eight query rows a head fill a float32 tile
                     (7, bf16): "head", (8, bf16): "head"}),
    "gpt-j-6b": ((16, 16, 256), {(1, bf16): "heads", (4, bf16): "heads"}),
    "mistral-7b": ((32, 8, 128), {(1, bf16): "head", (4, bf16): "head"}),
    # all heads' rows within 128: 64 x 2, not 64 x 4
    "mha-64": ((64, 64, 128), {(2, bf16): "heads", (4, bf16): "head"}),
    # two query heads a KV head: 16 x 2 x 2 rows, each head's 4 under a tile
    "gqa-32-over-16": ((32, 16, 128), {(2, bf16): "heads", (4, bf16): "head"}),
    # a slot's heads in whole tiles: 16 of bfloat16, 8 of float32
    "mha-8": ((8, 8, 128), {(1, bf16): "head", (1, f32): "heads"}),
    "mha-4": ((4, 4, 128), {(1, bf16): "head", (1, f32): "head"}),
}


@pytest.mark.parametrize("name", FORMS)
def test_the_form_follows_the_shapes(name):
    """``heads`` where a KV head's query rows are under a float32 tile's 8
    sublanes, all heads' rows within 128 and a slot's heads whole tiles of
    the pool's dtype; ``head`` everywhere else, the cells with grouped or
    single KV heads among them. Nothing but shapes goes in."""
    (Hq, Hkv, D), forms = FORMS[name]
    for (chunk, dtype), form in forms.items():
        assert pallas_kv.supports(BS, Hq, Hkv, D, chunk, dtype)
        assert pallas_kv.attn_form(Hq, Hkv, chunk, dtype) == form, (
            chunk, dtype)


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


# the same with one KV head, under a window shorter than the prompts, and
# with a KV head a query head (float32: eight heads are a slot's whole tile,
# and ``kv.kernel`` works all heads of a chunk in one product)
MHA = dataclasses.replace(GQA, n_kv_heads=8)
MODELS = {
    "gqa": GQA,
    "mqa": dataclasses.replace(GQA, n_kv_heads=1),
    "sliding-window": dataclasses.replace(GQA, sliding_window=8),
    "mha": MHA,
    "mha-sliding-window": dataclasses.replace(MHA, sliding_window=8),
}


def _engine(mesh, cfg=GQA, **kw):
    params = init_params(cfg, mesh, jax.random.key(3))
    return DecodeEngine(
        cfg, params, mesh, max_seq_len=64, kv_layout="paged", block_size=8,
        **kw,
    )


@pytest.fixture(scope="module")
def one_device(devices):
    return make_mesh(MeshPlan(tp=1), devices=devices[:1])


@pytest.mark.parametrize("model", MODELS)
def test_greedy_tokens_equal_under_the_kernel_and_the_gather(
    one_device, model,
):
    """Prompts streamed through the mixed step (4 tokens a row a step)
    beside rows that decode, then decode groups alone: the same greedy
    tokens whether the step programs read the pool through ``kv.kernel``
    (forced, interpreted) or the gather, and every group's ``sched.dispatch``
    span says which, and what the kernel does with a chunk (``attn_form``)."""
    prompts = [list(range(2, 22)), [3, 14, 15, 9, 26, 5], [7] * 11]
    gen = GenerationParams(max_new_tokens=6, is_greedy=True)
    outs, reads, forms = {}, {}, {}
    trace.set_enabled(True)
    for impl in ("xla", "pallas"):
        trace.recorder().clear()
        with attn.force_impl(impl):
            eng = _engine(one_device, MODELS[model])
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
        forms[impl] = {a["attn_form"] for a in spans}
        assert {a["index_read"] for a in spans} == {"none"}  # no indexer
        for a in spans:
            assert 0 <= a["blocks_read"] <= a["blocks_ring"]
    assert reads == {"xla": {"gather"}, "pallas": {"kv.kernel"}}
    form = "heads" if model.startswith("mha") else "head"
    assert forms == {"xla": {"none"}, "pallas": {form}}
    assert outs["xla"] == outs["pallas"], outs


@pytest.mark.filterwarnings("ignore:pallas forced")
@pytest.mark.parametrize("model", ["gqa", "mha"])
def test_a_mixed_step_of_one_token_a_row_is_the_decode_step(one_device, model):
    """Through ``kv.kernel`` (forced, interpreted) a group of mixed steps in
    which every row feeds nothing and decodes one token IS the decode group:
    the same tokens, and both pools equal BIT FOR BIT afterwards (every
    layer's fresh keys and values come out of the read below them), at a
    chunk budget of one token a row and of four; under either form of the
    kernel's work on a chunk."""
    cfg = MODELS[model]
    nB, nc = 4, 5
    prompts = [[5, 9, 23, 40], [3, 14, 15, 9], [7, 7, 7, 7], [1, 2, 3, 4]]
    gen = GenerationParams(max_new_tokens=8, is_greedy=True)
    eos = jnp.full(nB, -1, jnp.int32)

    def start(eng):
        lens = jnp.asarray([len(p) for p in prompts], jnp.int32)
        sa = eng._sample_args([gen] * nB, nB)
        cache = eng.new_paged_cache(nB, num_blocks=64, identity=True)
        tok, _, cache = eng._prefill(
            eng.params, jnp.asarray(prompts, jnp.int32), cache, lens, sa
        )
        # ``lens`` is donated into the group: a copy of its own each
        return tok, cache, lens + 0, sa, jnp.zeros(nB, bool), eos

    with attn.force_impl("pallas"):
        eng = _engine(one_device, cfg)
        tok, cache, cur, sa, done, _ = start(eng)
        assert decoder.attn_read(cfg, cache, one_device, 1) == "kv.kernel"
        assert {decoder.attn_form(cfg, cache, one_device, c) for c in (1, 4)} == {
            "heads" if model == "mha" else "head"}
        packed, _, want, cur_d, _ = eng._decode_group(
            eng.params, tok, cache, cur, sa, done, eos,
            n_chunks=nc, n_steps=1, t_bucket=None,
        )
        want_toks = np.asarray(packed)[: nc * nB]
        want_k, want_v = np.asarray(want.k), np.asarray(want.v)
        for cb in (1, 4):
            tok, cache, cur, sa, done, _ = start(eng)
            packed, _, got, cur_r, _ = eng._ragged_group(
                eng.params, tok, cache, cur, sa, done, eos,
                jnp.zeros((nc, nB, cb), jnp.int32),
                jnp.ones((nc, nB), jnp.int32), jnp.zeros((nc, nB), bool),
                jnp.ones((nc, nB), bool),
            )
            assert np.array_equal(np.asarray(packed)[: nc * nB], want_toks)
            assert np.array_equal(np.asarray(cur_r), np.asarray(cur_d))
            assert np.array_equal(np.asarray(got.k), want_k), cb
            assert np.array_equal(np.asarray(got.v), want_v), cb


def test_nothing_in_the_environment_chooses_a_read():
    """``LLMSS_ATTN_IMPL`` named an implementation until PR 49: a fresh
    interpreter with it set imports ``ops.attention`` with nothing pinned,
    and ``force_impl`` is the one way to pin, to ``xla`` or ``pallas``."""
    import os
    import subprocess
    import sys

    out = subprocess.run(
        [sys.executable, "-c",
         "import importlib; a = importlib.import_module("
         "'llmss_tpu.ops.attention'); print(a.IMPL_OVERRIDE)"],
        env={**os.environ, "LLMSS_ATTN_IMPL": "pallas", "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, check=True,
    )
    assert out.stdout.strip() == "None"
    assert attn.IMPL_OVERRIDE is None
    with pytest.raises(ValueError, match="xla"):
        attn.force_impl("ring")
    with attn.force_impl("pallas"):
        assert attn.IMPL_OVERRIDE == "pallas"
        with attn.force_impl(None):
            assert attn.IMPL_OVERRIDE is None
    assert attn.IMPL_OVERRIDE is None


@pytest.mark.parametrize("what", ["tp-mesh", "int8-pool", "chunk-of-32"])
def test_a_pinned_kernel_that_cannot_read_says_so(
    one_device, devices, monkeypatch, what,
):
    """Under ``force_impl("pallas")`` a ``tp`` mesh, an int8 pool and a
    chunk over the kernel's 16 tokens keep the gather: interpreted (here)
    with a warning that names the shapes, compiled (a TPU) as an error: a
    run under a kernel's name never measures another read."""
    mesh = one_device
    if what == "tp-mesh":
        mesh = make_mesh(MeshPlan(tp=2), devices=devices[:2])
    cache = _engine(
        one_device, kv_dtype="int8" if what == "int8-pool" else None
    ).new_paged_cache(2)
    chunk = 32 if what == "chunk-of-32" else 1
    with attn.force_impl("pallas"):
        with pytest.warns(UserWarning, match="pallas forced: .*pool read"):
            assert decoder.attn_read(GQA, cache, mesh, chunk) == "gather"
        monkeypatch.setattr(attn, "pallas_interpret", lambda: False)
        with pytest.raises(ValueError, match="pallas forced: .*pool read"):
            decoder.attn_read(GQA, cache, mesh, chunk)
    # nothing pinned: the program chooses, and says ``gather`` quietly
    assert decoder.attn_read(GQA, cache, mesh, chunk) == "gather"


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
    with attn.force_impl("pallas"):
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
