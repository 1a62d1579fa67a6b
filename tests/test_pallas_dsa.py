"""The selected read's kernel (``ops.pallas_dsa``, interpret mode on the CPU)
against both XLA oracles of ``ops.sparse_attention``: the mask form
(``sparse_chunk_attention``) on every live query of a mixed step and the
gather form (``sparse_decode_attention``) on a decode step, the selection
computed once (``chunk_selection`` / ``decode_selection``) and handed to the
kernel as bits."""

import importlib

import jax.numpy as jnp
import numpy as np
import pytest

from llmss_tpu.engine.cache import gather_block_view
from llmss_tpu.ops import pallas_dsa
from llmss_tpu.ops import sparse_attention as dsa

attn = importlib.import_module("llmss_tpu.ops.attention")

L, BS = 2, 16
B, MB = 4, 72
RING = MB * BS  # 1,152 slots: four 256-slot chunks of the walk and a half
N = 96
SENTINEL = N + 5
HI, DI, W = 4, 64, 128  # the indexer: heads, key width, the pool's row

GQA_4x2 = (8, 4, 128)  # G = 2: small enough to interpret at every case
KEYE = (32, 4, 128)  # the cell's heads: G = 8, 256 query rows at chunk 32

# Each case: the heads, the chunk, ``topk``, the tokens each row has been fed
# so far (``ctx``; more than RING: the ring has wrapped) and its live queries.
# A case reads the LAST layer of the stack (interpretation costs by the grid
# step, and a walk of 1,152 slots is 288 of them); ``layers`` says otherwise
# where every layer is read, so that the layer index is held too.
CASES = {
    # decoding, feeding, done and empty (padding) rows in one call
    "mixed-step": dict(cb=8, topk=128, ctx=[300, 1040, 77, 0],
                       qlen=[1, 8, 0, 0]),
    "mixed-step-short-last-chunk": dict(cb=8, topk=128, ctx=[300, 45, 1040, 600],
                                        qlen=[3, 1, 8, 5]),
    # a context under topk keeps all it sees; one over it drops most
    "under-topk": dict(cb=8, topk=128, ctx=[45, 100, 127, 129],
                       qlen=[8, 1, 8, 8], layers=range(L)),
    # every cached indexer key the same: their scores tie, the earliest win
    "tie": dict(cb=4, topk=64, ctx=[300, 200, 70, 64], qlen=[4, 1, 4, 2],
                tie=True),
    "ring-wrapped": dict(cb=8, topk=128, ctx=[1300, 2303, 1148, 1152],
                         qlen=[8, 1, 8, 3]),
    # topk under the chunk: later queries drop fresh tokens too (keep_w)
    "fresh-tokens-compete": dict(cb=8, topk=4, ctx=[300, 0, 2, 40],
                                 qlen=[8, 8, 6, 1]),
    "nothing-cached": dict(cb=8, topk=16, ctx=[0, 0, 5, 0], qlen=[8, 1, 2, 0],
                           sentinel=True),
    "decode-step": dict(cb=1, topk=128, ctx=[300, 45, 1025, 600],
                        qlen=[1, 1, 1, 1]),
    "decode-step-wrapped": dict(cb=1, topk=128, ctx=[1300, 0, 2303, 1152],
                                qlen=[1, 1, 1, 1], layers=range(L)),
    "decode-step-bucketed": dict(cb=1, topk=128, ctx=[300, 45, 600, 77],
                                 qlen=[1, 1, 1, 1], t_bucket=608),
    # what the chip serves: the cell's heads and chunk, bfloat16 pools
    "bfloat16-keye-chunk32": dict(heads=KEYE, cb=32, topk=128,
                                  ctx=[300, 1040, 77, 520], qlen=[1, 32, 0, 20],
                                  dtype=jnp.bfloat16, tol=2e-2),
    "bfloat16-keye-decode": dict(heads=KEYE, cb=1, topk=128,
                                 ctx=[300, 0, 1040, 1500], qlen=[1, 1, 1, 1],
                                 dtype=jnp.bfloat16, tol=2e-2),
}


def _inputs(case, seed=0):
    rng = np.random.default_rng(seed)
    (Hq, Hkv, D), cb = case.get("heads", GQA_4x2), case["cb"]
    dtype = case.get("dtype", jnp.float32)
    ctx, qlen = np.asarray(case["ctx"]), np.asarray(case["qlen"])
    bt = (np.arange(MB)[None, :] * 2 + np.arange(B)[:, None] * 3) % N
    bt = bt.astype(np.int32)
    kv_pos = np.full((B, RING), -1, np.int32)
    for b in range(B):
        # slot s holds the newest position p < ctx with p % RING == s
        pos = np.arange(max(ctx[b] - RING, 0), ctx[b])
        kv_pos[b, pos % RING] = pos
    used = -(-np.minimum(ctx, RING) // BS)
    if case.get("sentinel"):
        for b in range(B):
            bt[b, used[b]:] = SENTINEL

    def draw(*shape, dtype=dtype):
        return jnp.asarray(rng.normal(size=shape), dtype)

    f32 = jnp.float32
    idx = np.zeros((L, N, BS, W), np.float32)
    idx[..., :DI] = 1.0 if case.get("tie") else rng.normal(size=idx.shape[:-1] + (DI,))
    ki = draw(B, cb, DI, dtype=f32)
    if case.get("tie"):  # the fresh tokens score 0: under every cached slot
        ki = jnp.zeros_like(ki)
    T = case.get("t_bucket", RING)
    return dict(
        q=draw(B, cb, Hq, D), k=draw(L, N, BS, Hkv, D),
        v=draw(L, N, BS, Hkv, D), kn=draw(B, cb, Hkv, D),
        vn=draw(B, cb, Hkv, D), idx=jnp.asarray(idx), ki=ki,
        qi=draw(B, cb, HI, DI, dtype=f32),
        # positive weights: with equal keys every score is then one number
        wi=jnp.abs(draw(B, cb, HI, dtype=f32)) + 0.1,
        q_pos=jnp.asarray(ctx, jnp.int32), q_len=jnp.asarray(qlen, jnp.int32),
        kv_pos=jnp.asarray(kv_pos[:, :T]), bt=jnp.asarray(bt),
        nblk=jnp.asarray(used, jnp.int32),
        slot0=jnp.asarray(ctx % RING, jnp.int32),
    )


def _kernel(x, keep_c, keep_w, layer, scale):
    return np.asarray(pallas_dsa.dsa_paged_attention(
        x["q"], x["k"], x["v"], x["kn"], x["vn"], keep_c, keep_w, x["q_len"],
        x["bt"], x["nblk"], jnp.int32(layer), scale=scale, interpret=True,
    ), np.float32)


@pytest.mark.parametrize("name", list(CASES))
def test_kernel_matches_the_oracles(name):
    case = CASES[name]
    x = _inputs(case)
    (Hq, Hkv, D), cb, topk = case.get("heads", GQA_4x2), case["cb"], case["topk"]
    scale, tol = D ** -0.5, case.get("tol", 2e-5)
    T = x["kv_pos"].shape[1]
    nb = -(-T // BS) if T < RING else None
    assert pallas_dsa.supports(BS, Hq, Hkv, D, cb, x["k"].dtype)
    vis = attn.ragged_cache_visibility(x["q_len"], x["kv_pos"], x["slot0"], RING)
    for layer in case.get("layers", (L - 1,)):
        views = [
            gather_block_view(pool, x["bt"], nb, layer)
            for pool in (x["k"], x["v"], x["idx"])
        ]
        keep = dsa.chunk_selection(
            views[2], x["ki"], x["qi"], x["wi"], x["q_pos"], x["q_len"],
            x["kv_pos"], vis, topk=topk,
        )  # [B, cb, T + cb]
        words = pallas_dsa.pack_queries(keep)
        got = _kernel(x, words[:, :T], words[:, T:], layer, scale)
        want = np.asarray(dsa.sparse_chunk_attention(
            x["q"], *views, x["kn"], x["vn"], x["ki"], x["qi"], x["wi"],
            x["q_pos"], x["q_len"], x["kv_pos"], vis, topk=topk, scale=scale,
        ), np.float32)
        assert got.shape == (B, cb, Hq, D)
        assert np.isfinite(got).all()  # padding queries and done rows too
        for b, n in enumerate(case["qlen"]):
            np.testing.assert_allclose(
                got[b, :n], want[b, :n], rtol=tol, atol=tol
            )
            kept = np.asarray(keep[b, :n]).sum(-1)
            seen = min(case["ctx"][b], RING) + 1 + np.arange(n)
            if T == RING and case["ctx"][b] + cb <= RING:
                np.testing.assert_array_equal(kept, np.minimum(seen, topk))
        if case.get("tie"):
            # of equal scores the earlier slot wins: the first topk positions
            for b, n in enumerate(case["qlen"]):
                if n:
                    np.testing.assert_array_equal(
                        np.flatnonzero(np.asarray(keep[b, 0, :T])),
                        np.arange(min(case["ctx"][b], topk)),
                    )
        if cb > 1:
            continue
        # the decode step: the gather form's own selection and its read
        slots = x["slot0"][:, None]
        keep1 = dsa.decode_selection(
            x["idx"], x["ki"], x["qi"], x["wi"], x["q_pos"][:, None],
            x["kv_pos"], x["bt"], slots, layer, topk=topk, n_blocks=nb,
        )  # [B, T + 1]
        np.testing.assert_array_equal(np.asarray(keep1), np.asarray(keep[:, 0]))
        dec = np.asarray(dsa.sparse_decode_attention(
            x["q"], x["k"], x["v"], x["idx"], x["kn"], x["vn"], x["ki"],
            x["qi"], x["wi"], x["q_pos"][:, None], x["kv_pos"], x["bt"], slots,
            layer, topk=topk, scale=scale, n_blocks=nb,
        ), np.float32)
        words1 = keep1.astype(jnp.int32)
        got1 = _kernel(x, words1[:, :T], words1[:, T:], layer, scale)
        np.testing.assert_allclose(got1, dec, rtol=tol, atol=tol)


def test_bits_past_a_rows_live_queries_are_not_read():
    """A word's bits at and past ``q_len`` may hold anything (a decoding
    row's word is its first query's bit alone; a row beyond the scheduler's
    feeding cap has only that): live queries read the same."""
    case = CASES["mixed-step-short-last-chunk"]
    x = _inputs(case)
    D, topk, T = 128, case["topk"], RING
    vis = attn.ragged_cache_visibility(x["q_len"], x["kv_pos"], x["slot0"], RING)
    ki_view = gather_block_view(x["idx"], x["bt"], None, 0)
    keep = dsa.chunk_selection(
        ki_view, x["ki"], x["qi"], x["wi"], x["q_pos"], x["q_len"],
        x["kv_pos"], vis, topk=topk,
    )
    words = pallas_dsa.pack_queries(keep)
    live = (1 << np.asarray(case["qlen"])) - 1
    noisy = words | jnp.asarray(~live, jnp.int32)[:, None]
    a = _kernel(x, words[:, :T], words[:, T:], 0, D ** -0.5)
    b = _kernel(x, noisy[:, :T], noisy[:, T:], 0, D ** -0.5)
    for r, n in enumerate(case["qlen"]):
        np.testing.assert_array_equal(a[r, :n], b[r, :n])


def test_pack_queries_gives_query_i_bit_i():
    keep = np.zeros((2, 32, 5), bool)
    keep[0, 0, 1] = keep[0, 31, 1] = keep[1, 7, 4] = True
    words = np.asarray(pallas_dsa.pack_queries(jnp.asarray(keep)))
    assert words.dtype == np.int32
    assert words[0, 1] == np.int32(-(2 ** 31) + 1) and words[1, 4] == 1 << 7
    assert np.count_nonzero(words) == 2


def test_the_chunk_width_at_the_cells_shapes():
    """The cell's pools hold 2 KB a slot a pool: 256 slots fit four times
    beside 256 query rows a head at chunks of 32, as at a decode step."""
    assert pallas_dsa.chunk_slots(16, 32, 4, 128, 32, jnp.bfloat16) == 256
    assert pallas_dsa.chunk_slots(16, 32, 4, 128, 1, jnp.bfloat16) == 256


@pytest.mark.parametrize(
    "kwargs",
    [
        dict(head_dim=96),  # a head that is not whole lanes
        dict(block_size=8),  # bfloat16 tiles 16 sublanes
        dict(block_size=48),  # does not divide a chunk of the walk
        dict(chunk=33),  # more queries than a word has bits
        dict(chunk=0),
        dict(n_heads=128, n_kv_heads=128, head_dim=256),  # 64 KB a slot: VMEM
        dict(n_heads=16, n_kv_heads=3),  # heads that do not group
        dict(dtype=jnp.int8),
    ],
)
def test_supports_refuses_what_the_kernel_cannot_take(kwargs):
    shapes = dict(
        block_size=16, n_heads=32, n_kv_heads=4, head_dim=128, chunk=32,
        dtype=jnp.bfloat16,
    )
    assert pallas_dsa.supports(**shapes)
    assert not pallas_dsa.supports(**(shapes | kwargs))


def test_a_call_the_kernel_cannot_take_raises():
    pool = jnp.zeros((1, 4, 16, 2, 96), jnp.float32)  # heads of 96
    fresh = jnp.zeros((1, 1, 2, 96), jnp.float32)
    with pytest.raises(ValueError, match="pallas_dsa does not take"):
        pallas_dsa.dsa_paged_attention(
            jnp.zeros((1, 1, 4, 96), jnp.float32), pool, pool, fresh, fresh,
            jnp.zeros((1, 64), jnp.int32), jnp.zeros((1, 1), jnp.int32),
            jnp.ones((1,), jnp.int32), jnp.zeros((1, 4), jnp.int32),
            jnp.ones((1,), jnp.int32), jnp.int32(0), interpret=True,
        )
