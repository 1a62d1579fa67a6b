"""Attention over a learned selection of the context (``IndexerConfig``:
DeepSeek Sparse Attention's lightning indexer at Keye-VL-2.0's shapes;
docs/sparse-attention.md).

A layer scores every position a query may see with a few small heads
against ONE cached key a token,

    I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s]),

keeps the ``topk`` best (all of them while there are at most ``topk``), and
every head of the layer attends over those and nothing else. Scores and
selection are float32 at ``Precision.HIGHEST`` whatever the compute dtype: a
top-k is discontinuous, and the program must choose what a float32
reference chooses.

**The tie rule**: exactly ``min(visible, topk)`` positions are kept, and of
equal scores the one EARLIER in the order the candidates are handed over
wins (cached slots ascending, then the step's own fresh tokens: position
order for a row that has not wrapped its ring). ``keep_topk`` finds the
``topk``-th largest score by a bisection over the 32 bits of a float's place
in the order of floats (``ops/sampling.py``'s idea: a fixed number of
compare-and-count passes, no sort) and cuts the tie group at it by a SECOND
bisection, over the bits of an index: the greatest index with no more of the
tie group before it than there is room for. ``32 + N.bit_length()`` passes
over ``N`` candidates (47 at a ring of 16,896) and no running count: on the
chip a ``cumsum`` over the same array cost what 175 passes do. The rule does
not rest on how a sort or a ``top_k`` primitive orders equal values.

The SELECTION is made here whatever reads the keys and values:
``decode_selection`` (a row's one query over its view of the indexer pool)
and ``chunk_selection`` (a query position of a chunk, the step's own fresh
tokens competing), both ending in ``keep_topk``. On a TPU the decode and
mixed steps hand it as bits to ``ops/pallas_dsa.py``, which walks both pools
where they lie (``models/decoder.py: attn_read``'s ``dsa.kernel``), and give
both selections the scores over the cached slots from the same walk over the
indexer's pool (``scores=``: ``index_read``'s ``idx.kernel``); the two XLA
forms of the read below are its oracles, the CPU's path, and the dedicated
prefill's:

* ``sparse_chunk_attention`` - the MASK form, for the mixed step, the
  prefill and the decode step alike: the rows' gathered logical views (keys,
  values, indexer keys) and the step's own fresh tokens, a selection a QUERY
  POSITION as a mask on one merged softmax (``ragged_fresh_kv_attention``'s
  arithmetic and its masks). Rows are worked a few at a time
  (``chunk_rows``) and long chunks a block of queries at a time
  (``lax.map``), so a temporary is ``[rows', heads, 128, T]`` whatever the
  batch and the prompt. A mixed step of more rows than one turn holds works
  every row's FIRST query (a decoding row has no other) and, through all of
  their chunk, only the rows that feed a prompt, at most one turn of them:
  the scheduler admits no more at once (models/decoder.py: ``feed_rows``),
  and the step costs the same whichever rows feed.
* ``sparse_decode_attention`` - the GATHER form of a decode step: scores
  over the row's view of the indexer pool alone, then keys and values of the
  kept tokens read BY TOKEN from the stacked pools, ``(layer, block_tables[b,
  s // bs], s % bs)``: 256 B of indexer key a cached token and 2 KB for each
  of at most ``topk`` kept ones, where a dense step reads 2 KB of every one.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from llmss_tpu.ops.attention import _NEG_INF

_HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32

#: Queries a block of a long chunk holds, and the float32 score bytes one
#: ``lax.map`` turn may hold over its rows (what bounds the temporaries).
QUERY_BLOCK = 128
MAP_BYTES = 512 * 1024 * 1024


def index_scores(qi, wi, ki):
    """``qi`` [.., S, Hi, Di], ``wi`` [.., S, Hi], ``ki`` [.., T, Di], all
    float32 -> ``I`` [.., S, T]: ``sum_j wi[s, j] relu(qi[s, j] . ki[t])``."""
    s = jnp.einsum("...shd,...td->...sht", qi, ki, precision=_HI)
    return jnp.sum(jax.nn.relu(s) * wi[..., None], axis=-2)


def _order_key(x):
    """float32 -> uint32 that ascends as the value does (-inf least; the
    two zeros are one value and get one key)."""
    b = jax.lax.bitcast_convert_type(
        jnp.where(x == 0, jnp.zeros_like(x), x), jnp.uint32
    )
    return jnp.where(b >> 31 == 1, ~b, b | jnp.uint32(1 << 31))


def keep_topk(scores, k: int):
    """``scores`` [.., N] float32, ``-inf`` where a position is no candidate
    -> bool [.., N]: the ``min(candidates, k)`` largest, of equal scores the
    lower index (the module's tie rule)."""
    key = _order_key(scores)

    def count(members):
        return jnp.sum(members, axis=-1, keepdims=True, dtype=jnp.int32)

    def narrow(i, lo):
        # the greatest threshold that still has k keys at or above it
        cand = lo | (jnp.uint32(1 << 31) >> i.astype(jnp.uint32))
        return jnp.where(count(key >= cand) >= k, cand, lo)

    thr = jax.lax.fori_loop(
        0, 32, narrow, jnp.zeros(key.shape[:-1] + (1,), jnp.uint32)
    )
    above, tie = key > thr, key == thr
    room = k - count(above)
    N = key.shape[-1]
    idx, nb = jnp.arange(N, dtype=jnp.int32), N.bit_length()

    def cut(i, lo):
        # the greatest index with at most ``room`` of the tie group before it
        cand = lo | (jnp.int32(1 << (nb - 1)) >> i)
        return jnp.where(count(tie & (idx < cand)) <= room, cand, lo)

    stop = jax.lax.fori_loop(0, nb, cut, jnp.zeros_like(room))
    return (above | (tie & (idx < stop))) & (scores > -jnp.inf)


def _to_pool_width(W: int, qi, ki_new):
    """The queries and the fresh keys zero-padded to the pool's row of ``W``
    numbers (``IndexerConfig.pool_dim``): the scores gain zeros."""
    pad = lambda a: jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, W - a.shape[-1])])
    return pad(qi), pad(ki_new)


def _query_block(S: int) -> int:
    return next(d for d in range(min(S, QUERY_BLOCK), 0, -1) if S % d == 0)


def chunk_rows(B: int, Hq: int, S: int, N: int) -> int:
    """Rows ONE turn of the mask form works at once: what ``MAP_BYTES`` of
    float32 attention scores hold of ``B`` rows with ``Hq`` heads, chunks of
    ``S`` queries (a block of them) and ``N`` keys."""
    return max(1, min(B, MAP_BYTES // (4 * Hq * _query_block(S) * N)))


def chunk_selection(
    ki_view,  # [B, T, W] float32: the rows' view of the indexer pool
    ki_new,  # [B, S, Di or W] float32: the step's own fresh keys
    qi, wi,  # [B, QB, Hi, Di or W], [B, QB, Hi] float32: queries [i0, i0 + QB)
    q_pos0, q_len, kv_pos_old, cache_vis,  # [B], [B], [B, T], [B, T]
    *, topk: int, i0=0, scores=None,
):
    """bool ``[B, QB, T + S]``: what each of a row's queries ``[i0, i0 +
    QB)`` keeps of the cached slots and of the step's ``S`` fresh tokens (the
    mask form's selection). Query ``i`` sees the cached slots ``cache_vis &
    kv_pos <= q_pos0 + i`` and the fresh tokens ``j <= i, j < q_len``, and
    keeps its ``topk`` best of those by the indexer; a padding query (``i >=
    q_len``) keeps all it sees. ``scores`` float32 ``[B, QB, T]``: the
    queries' scores over the cached slots, made elsewhere (``ki_view`` None:
    ``ops/pallas_dsa.py: idx_paged_scores``, the pool walked where it lies);
    one is looked at only where the query sees the slot, so it may be
    anything elsewhere, NaN included."""
    if scores is None:
        qi, ki_new = _to_pool_width(ki_view.shape[-1], qi, ki_new)
    QB, S = qi.shape[1], ki_new.shape[1]
    rel_k = jnp.arange(S, dtype=jnp.int32)
    rel_q = i0 + jnp.arange(QB, dtype=jnp.int32)
    qpos = q_pos0[:, None] + rel_q[None, :]  # [B, QB]
    see_c = cache_vis[:, None, :] & (kv_pos_old[:, None, :] <= qpos[:, :, None])
    see_w = (rel_k[None, None, :] <= rel_q[None, :, None]) & (
        rel_k[None, None, :] < q_len[:, None, None]
    )
    score = jnp.concatenate([
        jnp.where(
            see_c, index_scores(qi, wi, ki_view) if scores is None else scores,
            -jnp.inf,
        ),
        jnp.where(see_w, index_scores(qi, wi, ki_new), -jnp.inf),
    ], axis=-1)  # [B, QB, T + S]
    return keep_topk(score, topk) | (
        (rel_q[None, :] >= q_len[:, None])[:, :, None]
        & jnp.concatenate([see_c, see_w], -1)
    )


def sparse_chunk_attention(
    q,  # [B, S, Hq, D] compute dtype
    k_view, v_view,  # [B, T, Hkv, D] the rows' stale logical views
    ki_view,  # [B, T, W] float32, W >= Di: the pool's zero-padded rows
    k_new, v_new,  # [B, S, Hkv, D] the step's own fresh tokens
    ki_new,  # [B, S, Di] float32
    qi, wi,  # [B, S, Hi, Di], [B, S, Hi] float32
    q_pos0,  # [B] the first query's absolute position
    q_len,  # [B] live queries a row (the rest is padding)
    kv_pos_old,  # [B, T] pre-write slot positions
    cache_vis,  # [B, T] bool (``ragged_cache_visibility``)
    *, topk: int, scale: float | None = None,
):
    """The mask form (module docstring). Query ``i`` of a row sees the cached
    slots ``cache_vis & kv_pos <= q_pos0 + i`` and the fresh tokens ``j <= i,
    j < q_len``; of those it keeps its ``topk`` best by the indexer and
    attends over them in one softmax. A padding query (``i >= q_len``) keeps
    all it sees, so that it stays finite (its output is never read)."""
    B, S, Hq, D = q.shape
    T, Hkv = k_view.shape[1], k_view.shape[2]
    G = Hq // Hkv
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    QB = _query_block(S)
    nq = S // QB

    def block(rows, blk):
        """Some rows' queries ``[i0, i0 + QB)`` against all of their keys."""
        k_c, v_c, ki_c, k_w, v_w, ki_w, pos0, n, kv_pos, vis = rows
        q_b, qi_b, wi_b, i0 = blk
        R = q_b.shape[0]
        keep = chunk_selection(
            ki_c, ki_w, qi_b, wi_b, pos0, n, kv_pos, vis, topk=topk, i0=i0
        )
        keep_c, keep_w = keep[..., :T], keep[..., T:]

        qf = q_b.astype(F32).reshape(R, QB, Hkv, G, D) * scale
        s_c = jnp.einsum("bskgd,btkd->bkgst", qf, k_c.astype(F32))
        s_w = jnp.einsum("bskgd,btkd->bkgst", qf, k_w.astype(F32))
        s_c = jnp.where(keep_c[:, None, None], s_c, _NEG_INF)
        s_w = jnp.where(keep_w[:, None, None], s_w, _NEG_INF)
        m = jnp.maximum(
            jnp.max(s_c, axis=-1, keepdims=True),
            jnp.max(s_w, axis=-1, keepdims=True),
        )
        p_c, p_w = jnp.exp(s_c - m), jnp.exp(s_w - m)
        denom = (jnp.sum(p_c, axis=-1, keepdims=True)
                 + jnp.sum(p_w, axis=-1, keepdims=True))
        out = (
            jnp.einsum("bkgst,btkd->bkgsd", p_c, v_c.astype(F32))
            + jnp.einsum("bkgst,btkd->bkgsd", p_w, v_w.astype(F32))
        ) / denom
        return (
            out.transpose(0, 3, 1, 2, 4).reshape(R, QB, Hq, D).astype(q.dtype)
        )

    rows = (k_view, v_view, ki_view, k_new, v_new, ki_new, q_pos0, q_len,
            kv_pos_old, cache_vis)
    per = chunk_rows(B, Hq, S, T + S)
    if nq == 1 and per >= B:  # one turn: a mixed step's two calls
        return block(rows, (q, qi, wi, jnp.int32(0)))

    def turn(args):
        """``per`` rows (a leading axis) through their chunk, a block of
        queries after another."""
        rows, (q_r, qi_r, wi_r) = args[:10], args[10:]
        R = q_r.shape[0]
        if nq == 1:
            return block(rows, (q_r, qi_r, wi_r, jnp.int32(0)))
        split = lambda a: jnp.moveaxis(
            a.reshape((R, nq, QB) + a.shape[2:]), 1, 0
        )
        out = jax.lax.map(
            lambda blk: block(rows, blk),
            (split(q_r), split(qi_r), split(wi_r),
             jnp.arange(nq, dtype=jnp.int32) * QB),
        )  # [nq, R, QB, Hq, D]
        return jnp.moveaxis(out, 0, 1).reshape(R, S, Hq, D)

    # whole turns of ``per`` rows, the batch padded up to them
    n_turns = -(-B // per)
    pad = n_turns * per - B
    fold = lambda a: jnp.pad(
        a, [(0, pad)] + [(0, 0)] * (a.ndim - 1)
    ).reshape((n_turns, per) + a.shape[1:])
    out = jax.lax.map(turn, tuple(fold(a) for a in (*rows, q, qi, wi)))
    return out.reshape((n_turns * per, S, Hq, D))[:B]


def decode_selection(
    idx_pool,  # [L, N, bs, W] float32, W >= Di
    ki_new,  # [B, 1, Di] float32
    qi, wi,  # [B, 1, Hi, Di], [B, 1, Hi] float32
    q_pos,  # [B, 1]
    kv_pos_old,  # [B, nb * bs] pre-write positions of the slots read
    block_tables,  # [B, MB]
    slots,  # [B, 1] the slot the token will occupy
    layer,
    *, topk: int, n_blocks: int | None = None, scores=None,
):
    """bool ``[B, Tv + 1]``: what a row's ONE query keeps of the ``Tv``
    cached slots read and of its own new token (the last column): scores
    over the row's view of the indexer pool alone. The new token competes
    with the cached ones for its place among the ``topk`` (it is the latest
    position: a tie goes against it). ``scores`` float32 ``[B, 1, Tv]``: the
    query's scores over the cached slots, made elsewhere (``chunk_selection``
    says where and what they may hold); the pool is then not read."""
    from llmss_tpu.engine.cache import gather_block_view

    Tv = kv_pos_old.shape[1]
    if scores is None:
        ki_view = gather_block_view(idx_pool, block_tables, n_blocks, layer)
        qi, ki_new = _to_pool_width(ki_view.shape[-1], qi, ki_new)
    see = (
        (kv_pos_old >= 0) & (kv_pos_old <= q_pos)
        & (jnp.arange(Tv, dtype=jnp.int32)[None, :] != slots)
    )
    score = jnp.concatenate([
        jnp.where(
            see,
            (index_scores(qi, wi, ki_view) if scores is None else scores)[:, 0],
            -jnp.inf,
        ),
        index_scores(qi, wi, ki_new)[:, 0],
    ], axis=-1)  # [B, Tv + 1]
    return keep_topk(score, min(topk, Tv))


def sparse_decode_attention(
    q,  # [B, 1, Hq, D]
    k_pool, v_pool,  # [L, N, bs, Hkv, D] the stacked pools
    idx_pool,  # [L, N, bs, W] float32, W >= Di
    k_new, v_new,  # [B, 1, Hkv, D]
    ki_new,  # [B, 1, Di] float32 (the new token always keeps itself)
    qi, wi,  # [B, 1, Hi, Di], [B, 1, Hi] float32
    q_pos,  # [B, 1]
    kv_pos_old,  # [B, nb * bs] pre-write positions of the slots read
    block_tables,  # [B, MB]
    slots,  # [B, 1] the slot the token will occupy
    layer,
    *, topk: int, scale: float | None = None, n_blocks: int | None = None,
):
    """The gather form of a decode step (module docstring). The new token
    competes with the cached ones for its place among the ``topk`` (it is
    the latest position: a tie goes against it) and is merged into the one
    softmax after the kept cached tokens."""
    B = q.shape[0]
    bs = k_pool.shape[2]
    Tv = kv_pos_old.shape[1]
    topk = min(topk, Tv)
    keep = decode_selection(
        idx_pool, ki_new, qi, wi, q_pos, kv_pos_old, block_tables, slots,
        layer, topk=topk, n_blocks=n_blocks,
    )
    # the kept cached slots, ascending: the topk largest of a key that is
    # Tv - slot where the slot is kept and 0 where it is not. The keys are
    # distinct, so this top_k has no tie to break. (A binary search a row for
    # the r-th kept slot in the running count of kept slots took 10 ms a
    # layer at 32 x 16,896, this 0.75: my chip run, PR 46.)
    slot = jnp.arange(Tv, dtype=jnp.int32)
    key, _ = jax.lax.top_k(jnp.where(keep[:, :Tv], Tv - slot, 0), topk)
    real = key > 0  # a row that keeps fewer than topk
    sel = jnp.where(real, Tv - key, 0)
    blk = jnp.take_along_axis(block_tables, sel // bs, axis=1)
    blk = jnp.minimum(blk, k_pool.shape[1] - 1)
    k_sel = k_pool[layer, blk, sel % bs]  # [B, topk, Hkv, D]
    v_sel = v_pool[layer, blk, sel % bs]
    # one softmax over the kept cached tokens and the new one; a slot past
    # the row's kept count, or the new token where it lost its place, is
    # masked like any dropped position (at least one term is always kept:
    # the new token is a candidate, and topk >= 1)
    penalty = jnp.where(
        jnp.concatenate([real, keep[:, Tv:]], axis=-1), 0.0, _NEG_INF
    ).astype(F32)
    k_all = jnp.concatenate([k_sel, k_new.astype(k_sel.dtype)], axis=1)
    v_all = jnp.concatenate([v_sel, v_new.astype(v_sel.dtype)], axis=1)
    Hq, D, Hkv = q.shape[2], q.shape[3], k_all.shape[2]
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    qf = q[:, 0].astype(F32).reshape(B, Hkv, Hq // Hkv, D) * scale
    s = jnp.einsum("bkgd,btkd->bkgt", qf, k_all.astype(F32))
    p = jax.nn.softmax(s + penalty[:, None, None, :], axis=-1)
    out = jnp.einsum("bkgt,btkd->bkgd", p, v_all.astype(F32))
    return out.reshape(B, 1, Hq, D).astype(q.dtype)
