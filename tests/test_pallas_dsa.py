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


# --- the indexer's scores by the walk (``idx_paged_scores``) ----------------

HI_K = 8  # indexer heads the walk takes: whole sublane tiles
TOPK_K = 16
# rows 0-3 of the pool's table: a last block part full (300 = 18.75 blocks),
# a ring that wrapped (every block held, slot order not position order), a
# row with nothing live, a row with nothing cached
INDEX_CTX = [300, 1300, 520, 0]
INDEX_CALLS = {
    # every row's first query (the decode group has only this call)
    "first-query": dict(s=1, lens=[1, 1, 0, 1]),
    # the rows that feed, taken through ``feeding``: slot 2 holds no row
    "fed-chunk": dict(s=8, lens=[8, 5, 0, 0], feeding=[1, 0, B]),
    # narrower chunks and tiles than the cell's: five chunks of two tiles
    "fed-chunk-narrow": dict(s=8, lens=[8, 5, 0, 0], feeding=[1, 0, B],
                             widths=((256,), 128)),
    "first-query-bucketed": dict(s=1, lens=[1, 1, 1, 0], t_bucket=608),
}


def _index_inputs(call, seed=0):
    """The pool and one call's operands: ``(x, rows)``, ``rows`` the batch
    rows the call's rows are (``feeding``; B: no row)."""
    rng = np.random.default_rng(seed)
    S = call["s"]
    x = _inputs(dict(cb=S, topk=TOPK_K, ctx=INDEX_CTX, qlen=call["lens"]), seed)
    T = call.get("t_bucket", RING)
    x["kv_pos"] = x["kv_pos"][:, :T]
    x["qi"] = jnp.asarray(rng.normal(size=(B, S, HI_K, DI)), jnp.float32)
    x["wi"] = jnp.asarray(rng.normal(size=(B, S, HI_K)), jnp.float32)
    rows = np.asarray(call.get("feeding", range(B)))
    return x, rows


def _walked(x, rows, layer, widths=None):
    """``idx_paged_scores`` as ``_make_selected_read`` calls it: the rows
    ``rows`` of the batch, a slot that holds no row walking nothing."""
    r = np.minimum(rows, B - 1)
    lens = jnp.where(jnp.asarray(rows) < B, x["q_len"][r], 0)
    fn = pallas_dsa.idx_paged_scores
    if widths is not None:  # traced anew: the widths are read when tracing
        fn = fn.__wrapped__
    return fn(
        x["qi"][r], x["wi"][r], x["idx"], lens, x["bt"][r], x["nblk"][r],
        jnp.int32(layer), n_slots=x["kv_pos"].shape[1], interpret=True,
    )


@pytest.mark.parametrize("name", list(INDEX_CALLS))
def test_index_scores_by_the_walk_match_the_gathered_views(name, monkeypatch):
    """Wherever the walk goes (the slots of a live row's held blocks) its
    scores are ``index_scores`` over the gathered view's up to float32
    reduction order: a score is 8 weighted ``relu``s of 64-term products of
    unit normals, some tens in size, summed in another order by the kernel's
    tiles than by XLA's einsum: 1e-4 is a few hundred roundings of 2^-24 of
    that size (read: 8e-6), and 1e4 times under the gap of neighbours in rank
    that would move a bit (the bits are held below)."""
    call = INDEX_CALLS[name]
    if "widths" in call:
        monkeypatch.setattr(pallas_dsa, "_INDEX_CHUNK_SLOTS", call["widths"][0])
        monkeypatch.setattr(pallas_dsa, "_INDEX_TILE", call["widths"][1])
    x, rows = _index_inputs(call)
    T = x["kv_pos"].shape[1]
    nb = -(-T // BS) if T < RING else None
    assert pallas_dsa.index_supports(BS, HI_K, W, call["s"], T, jnp.float32)
    pad = jnp.pad(x["qi"], ((0, 0),) * 3 + ((0, W - DI),))
    for layer in range(L):
        got = np.asarray(_walked(x, rows, layer, call.get("widths")))
        want = np.asarray(dsa.index_scores(
            pad, x["wi"], gather_block_view(x["idx"], x["bt"], nb, layer)))
        assert got.shape == (len(rows), call["s"], T)
        for i, b in enumerate(rows):
            if b == B or call["lens"][b] == 0:
                continue
            held = min(int(x["nblk"][b]) * BS, T)
            assert held or INDEX_CTX[b] == 0
            np.testing.assert_allclose(
                got[i, :, :held], want[b, :, :held], rtol=0, atol=1e-4)


def _selected(x, rows, layer, scores):
    """The selection of the call's rows as the served step makes it, from
    the pool (``scores`` None: the XLA forms) or from ``scores``."""
    S, T = x["qi"].shape[1], x["kv_pos"].shape[1]
    nb = -(-T // BS) if T < RING else None
    r = np.minimum(rows, B - 1)
    if S == 1:
        return np.asarray(dsa.decode_selection(
            x["idx"], x["ki"][r], x["qi"][r], x["wi"][r], x["q_pos"][r, None],
            x["kv_pos"][r], x["bt"][r], x["slot0"][r, None], layer,
            topk=TOPK_K, n_blocks=nb, scores=scores,
        ))[:, None]
    vis = attn.ragged_cache_visibility(x["q_len"], x["kv_pos"], x["slot0"], RING)
    view = None if scores is not None else gather_block_view(
        x["idx"], x["bt"][r], nb, layer)
    return np.asarray(dsa.chunk_selection(
        view, x["ki"][r], x["qi"][r], x["wi"][r], x["q_pos"][r],
        x["q_len"][r], x["kv_pos"][r], vis[r], topk=TOPK_K, scores=scores,
    ))


@pytest.mark.parametrize("name", list(INDEX_CALLS))
def test_the_walks_scores_select_the_same_bits(name, monkeypatch):
    """``keep_topk`` over the kernel's scores against ``decode_selection`` /
    ``chunk_selection`` over the gathered view, distinct keys at ``topk`` 16:
    0 positions of a live query chosen otherwise (docs/sparse-attention.md's
    count); and NaN planted wherever the walk need not go (past a row's held
    blocks, a row with nothing live, a feeding slot that holds no row)
    changes no bit of a live row: a score is looked at under ``see`` alone."""
    call = INDEX_CALLS[name]
    if "widths" in call:
        monkeypatch.setattr(pallas_dsa, "_INDEX_CHUNK_SLOTS", call["widths"][0])
        monkeypatch.setattr(pallas_dsa, "_INDEX_TILE", call["widths"][1])
    x, rows = _index_inputs(call)
    T = x["kv_pos"].shape[1]
    layer = L - 1
    got = _walked(x, rows, layer, call.get("widths"))
    held = np.where(
        (rows < B) & (np.asarray(call["lens"] + [0])[rows] > 0),
        np.minimum(np.asarray(x["nblk"])[np.minimum(rows, B - 1)] * BS, T), 0,
    )
    unvisited = np.arange(T)[None, :] >= held[:, None]  # [rows, T]
    planted = jnp.where(unvisited[:, None, :], jnp.nan, got)
    assert np.isnan(np.asarray(planted)).any()
    want = _selected(x, rows, layer, None)
    kept = 0
    for scores in (got, planted):
        keep = _selected(x, rows, layer, scores)
        for i, b in enumerate(rows):
            n = 0 if b == B else call["lens"][b]
            np.testing.assert_array_equal(keep[i, :n], want[i, :n])
            kept += int(want[i, :n].sum())
    assert kept > 0


def test_the_index_walks_chunk_at_the_cells_shapes():
    """The cell's indexer pool holds 512 B a slot, a quarter of a slot of
    keys and values: a chunk of the walk is 1,024 slots at both call shapes
    (PERF.md section 6, PR 52: the widths tried on the chip), a row's
    scores over its ring of 16,896 beside it in VMEM."""
    f32 = jnp.float32
    assert pallas_dsa.index_chunk_slots(16, 16, 128, 32, 16896, f32) == 1024
    assert pallas_dsa.index_chunk_slots(16, 16, 128, 1, 16896, f32) == 1024


@pytest.mark.parametrize(
    "kwargs",
    [
        dict(n_heads=4),  # heads that are not whole sublane tiles
        dict(width=64),  # a slot that is not whole lanes
        dict(block_size=4),
        dict(chunk=33),  # more queries than a keep word has bits
        dict(chunk=0),
        dict(n_slots=262144),  # a row's scores over its ring: VMEM
        dict(dtype=jnp.bfloat16),  # the selection is float32's
    ],
)
def test_index_supports_refuses_what_the_walk_cannot_take(kwargs):
    shapes = dict(block_size=16, n_heads=16, width=128, chunk=32,
                  n_slots=16896, dtype=jnp.float32)
    assert pallas_dsa.index_supports(**shapes)
    assert not pallas_dsa.index_supports(**(shapes | kwargs))
    if "n_slots" not in kwargs:
        return
    pool = jnp.zeros((1, 4, 16, 128), jnp.float32)
    with pytest.raises(ValueError, match="pallas_dsa does not score"):
        pallas_dsa.idx_paged_scores(
            jnp.zeros((1, 32, 16, 64)), jnp.zeros((1, 32, 16)), pool,
            jnp.ones((1,), jnp.int32), jnp.zeros((1, 4), jnp.int32),
            jnp.ones((1,), jnp.int32), jnp.int32(0),
            n_slots=kwargs["n_slots"], interpret=True,
        )
