"""The reads of the pools by the decode and mixed steps of a model that
SELECTS what attention reads (``IndexerConfig``; docs/sparse-attention.md):
the keys and values under the selection (``dsa_paged_attention``, below) and,
before it, the indexer's scores over ITS pool, which the selection is made
from (``idx_paged_scores``, at the end of the file).

The XLA forms (``ops/sparse_attention.py``) fetch a decoding row's kept
tokens one by one (a gather of ``topk`` 1 KB slices a row and pool, behind a
``top_k`` that compacts the kept slots and a look-up of their blocks) and
work a feeding row through gathered views of its whole ring and float32
scores ``[heads, chunk, ring]``. This kernel is ``ops/pallas_kv.py``'s walk
with the selection as one more operand:

* the pools stay in HBM as stored (``[L, N, bs, Hkv, D]``, ``pl.ANY``); a
  grid step is one batch ROW, a ``fori_loop`` walks the row's occupied blocks
  ``K`` at a time through two VMEM buffer pairs, and the trip count is
  ``ceil(n_blocks[row] / K)``: a row with nothing cached or ``q_len == 0``
  walks nothing (the walk visits the step's LIVE rows only);
* **the selection comes as bits**: one int32 word a cached slot of the row's
  logical view (``keep_c`` ``[B, T]``) and one a fresh token of the step
  (``keep_w`` ``[B, CB]``); bit ``i`` of a word says "query ``i`` of this row
  attends over it". Whoever makes the words (``models/decoder.py:
  _forward_selected``, from ``keep_topk``) has already held them to what a
  query may see at all (a live slot, not pending, not after the query), so
  the bits ARE the mask: the kernel looks at no position. At this family's
  envelope (contexts of 4-17 k, ``topk`` 2,048, blocks of 16) a row keeps
  12-50% of its tokens and nearly every block holds a kept one, so walking
  every occupied block and masking beats fetching kept tokens one DMA each
  and beats compacting the table to the blocks that hold any (PERF.md
  section 6, PR 48);
* per KV head: scores over the chunk for the head's ``CB * G`` query rows,
  online softmax with its state in float32 in VMEM, ``p . v_h``: no score
  reaches HBM. A row that decodes in a mixed step (``q_len == 1``) scores its
  first query's ``G`` rows alone;
* the fresh keys and values merge last under ``keep_w``. A query past
  ``q_len`` (padding, and every query of a row with ``q_len == 0``) is given
  fresh key 0 and nothing else, so every denominator is positive and no
  output is NaN (such outputs are never read).

bfloat16 operands, float32 accumulation of both products, float32 softmax
state; the probabilities are rounded to the pool's dtype for the second
product, as ``pallas_kv`` and ``pallas_mla`` do. The chunk's width, the copy
loop and the way heads are taken out of a 16-bit pool's words are
``pallas_kv``'s, written again here so that ``pallas_kv.py`` keeps its text:
its kernel's payload, source lines included, is part of the step programs of
every other family.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from llmss_tpu.ops import pallas_kv
from llmss_tpu.ops.pallas_kv import (
    _BUFFER_BYTES, _CHUNK_SLOTS, _COPIES_UNROLLED, _NEG_INF, _TURNS_UNROLLED,
    _VMEM_BUDGET,
)

#: Queries a row a step: the bits of a word.
MAX_CHUNK = 32


def _fresh_rows(chunk: int) -> int:
    """Fresh keys padded to whole sublane tiles of the widest dtype served."""
    return -(-chunk // 16) * 16


def chunk_slots(
    block_size: int, n_heads: int, n_kv_heads: int, head_dim: int,
    chunk: int, dtype,
) -> int | None:
    """Slots of one chunk of the walk at these shapes (``pallas_kv``'s rule:
    the widest of its ``_CHUNK_SLOTS`` within the buffer and VMEM budgets,
    the fresh keys as many as the chunk); None where the kernel does not
    take the shapes at all."""
    if not 0 < chunk <= MAX_CHUNK or pallas_kv.chunk_slots(
        block_size, n_heads, n_kv_heads, head_dim, 1, dtype
    ) is None:  # the pool's and the heads' own conditions
        return None
    itemsize = jnp.dtype(dtype).itemsize
    rows = chunk * (n_heads // n_kv_heads)
    more_fresh = 4 * n_kv_heads * head_dim * itemsize * (
        _fresh_rows(chunk) - pallas_kv._FRESH_ROWS
    )
    for ks in _CHUNK_SLOTS:
        if (
            ks % (block_size * _COPIES_UNROLLED) == 0
            and 4 * ks * n_kv_heads * head_dim * itemsize <= _BUFFER_BYTES
            and pallas_kv._vmem_bytes(ks, rows, n_kv_heads, head_dim, itemsize)
            + more_fresh <= _VMEM_BUDGET
        ):
            return ks
    return None


def supports(
    block_size: int, n_heads: int, n_kv_heads: int, head_dim: int,
    chunk: int, dtype,
) -> bool:
    """Whether the kernel takes these shapes: ``pallas_kv.supports``'s
    conditions with at most ``MAX_CHUNK`` tokens a row a step (a query is a
    bit of a 32-bit word)."""
    return chunk_slots(
        block_size, n_heads, n_kv_heads, head_dim, chunk, dtype
    ) is not None


def _kernel(
    layer_ref,  # [1] — layer of the stacked pools
    bt_ref,  # [B * NC * K] — flattened clamped block table
    nc_ref,  # [B] — chunks this row walks
    start_ref,  # [B] — chunks walked by the rows before it (buffer parity)
    next_ref,  # [B] — the next row that walks any, or B
    ql_ref,  # [B] — live queries
    kc_ref,  # [1, NC, KS] int32 — keep words of the row's logical slots
    kw_ref,  # [1, 1, F] int32 — keep words of the step's fresh tokens
    q_ref,  # [1, Hkv, R, D], R = CB * G, query-major within a KV head
    k_ref,  # [L, N, bs, Hkv, D] in HBM; [L, N, bs, D] at one head
    v_ref,
    kn_ref,  # [1, Hkv, F, D] — fresh keys, zero rows past CB
    vn_ref,
    o_ref,  # [1, Hkv, R, D]
    kbuf_ref,  # [2, KS, Hkv, D]; [2, KS, D] at one head
    vbuf_ref,
    sem_ref,  # DMA [2, 2]: (pool, buffer)
    m_ref,  # [Hkv, R, 128] f32 (column 0 used)
    l_ref,
    acc_ref,  # [Hkv, R, D] f32
    *,
    scale: float,
    group: int,
    block_size: int,
):
    b = pl.program_id(0)
    n_rows = pl.num_programs(0)
    KS, Hkv = kbuf_ref.shape[1], q_ref.shape[1]
    R = q_ref.shape[2]
    K = KS // block_size
    cols = kc_ref.shape[1] * K  # table columns a row has here
    layer = layer_ref[0]
    n, base = nc_ref[b], start_ref[b]
    qlen = ql_ref[b]

    # pallas_kv's copy loop: a few copies a turn, every loop body once (the
    # kernel is lowered anew for every step program that holds it)
    def fetch(row, c, slot):
        def some(j, carry):
            for u in range(_COPIES_UNROLLED):
                i = j * _COPIES_UNROLLED + u
                blk = bt_ref[row * cols + c * K + i]
                at = pl.ds(i * block_size, block_size)
                for p, (pool, buf) in enumerate(
                    ((k_ref, kbuf_ref), (v_ref, vbuf_ref))
                ):
                    pltpu.make_async_copy(
                        pool.at[layer, blk], buf.at[slot, at],
                        sem_ref.at[p, slot],
                    ).start()
            return carry

        jax.lax.fori_loop(0, K // _COPIES_UNROLLED, some, 0)

    def land(slot):
        # one wait a pool for the K copies of a chunk
        for p, buf in enumerate((kbuf_ref, vbuf_ref)):
            pltpu.make_async_copy(
                buf.at[slot], buf.at[slot], sem_ref.at[p, slot]
            ).wait()

    m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    # query row r = i * group + g of a KV head belongs to query i: bit i
    bit = jnp.left_shift(
        jnp.int32(1),
        jax.lax.broadcasted_iota(jnp.int32, (R, 1), 0) // group,
    )  # [R, 1]

    def update(h, rows, keys, vals, mask):
        """One online-softmax step of KV head ``h``'s first ``rows`` query
        rows over ``keys`` / ``vals`` [T', D] under ``mask`` [rows, T']."""
        s = jax.lax.dot_general(
            q_ref[0, h, :rows], keys, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        s = jnp.where(mask, s, _NEG_INF)
        m_prev, l_prev = m_ref[h, :rows, :1], l_ref[h, :rows, :1]
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        # a row that keeps nothing yet has its max at the float32 min, and
        # exp(s - m) would be exp(0): zero what the mask hides
        p = jnp.where(mask, jnp.exp(s - m_next), 0.0)
        alpha = jnp.exp(m_prev - m_next)
        l_ref[h, :rows, :1] = alpha * l_prev + jnp.sum(
            p, axis=1, keepdims=True
        )
        m_ref[h, :rows, :1] = m_next
        acc_ref[h, :rows] = acc_ref[h, :rows] * alpha + jax.lax.dot_general(
            p.astype(vals.dtype), vals, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    # One head's [KS, D] out of a chunk's [KS, Hkv, D]: a 16-bit pool packs
    # two heads of a slot in every 32-bit word, so the words of a head PAIR
    # are loaded once and each head taken out of its half (pallas_kv.py).
    packed = kbuf_ref.dtype.itemsize == 2 and Hkv > 1
    turns = Hkv // 2 if packed else Hkv

    def heads_of(buf_ref, slot, j):
        """``[(head, its [KS, D])]`` of turn ``j``."""
        if Hkv == 1:  # the pool came without its head axis
            return [(0, buf_ref[slot])]
        if not packed:
            return [(j, buf_ref[slot, :, j, :])]
        w = buf_ref.bitcast(jnp.uint32)[slot, :, j, :]  # [KS, D] words
        halves = (w << 16, w & jnp.uint32(0xFFFF0000))
        return [
            (2 * j + i, pltpu.bitcast(x, jnp.float32).astype(buf_ref.dtype))
            for i, x in enumerate(halves)
        ]

    def each(count, body):
        """``body(i)`` for i < count: written out for a few, a loop of
        ``_TURNS_UNROLLED`` a turn for more."""
        u = _TURNS_UNROLLED if count % _TURNS_UNROLLED == 0 else 1
        if count <= u:
            for i in range(count):
                body(i)
            return

        def some(j, carry):
            for i in range(u):
                body(j * u + i)
            return carry

        jax.lax.fori_loop(0, count // u, some, 0)

    # the first row that walks anything starts its own first chunk
    @pl.when((base == 0) & (n > 0))
    def _():
        fetch(b, 0, 0)

    def walk(rows):
        def chunk(c, carry):
            slot = (base + c) % 2
            nxt = next_ref[b]

            # the row's next chunk, or the first of the next row with any
            more = c + 1 < n

            @pl.when(more | (nxt < n_rows))
            def _():
                fetch(
                    jnp.where(more, b, nxt), jnp.where(more, c + 1, 0),
                    1 - slot,
                )

            land(slot)
            mask = (kc_ref[0, pl.ds(c, 1), :] & bit[:rows]) != 0  # [rows, KS]

            def turn(j):
                for (h, keys), (_, vals) in zip(
                    heads_of(kbuf_ref, slot, j), heads_of(vbuf_ref, slot, j),
                ):
                    update(h, rows, keys, vals, mask)

            each(turns, turn)
            return carry

        jax.lax.fori_loop(0, n, chunk, 0)

    if R > group:
        # a row that decodes (one live query) scores its first query's
        # heads alone: the other CB - 1 queries are padding
        pl.when(qlen == 1)(lambda: walk(group))
        pl.when(qlen != 1)(lambda: walk(R))
    else:
        walk(R)

    fresh = (kw_ref[0] & bit) != 0  # [R, F]

    def merged(h):
        update(h, R, kn_ref[0, h], vn_ref[0, h], fresh)
        o_ref[0, h] = (acc_ref[h] / l_ref[h, :, :1]).astype(o_ref.dtype)

    each(Hkv, merged)


def pack_queries(keep: jax.Array) -> jax.Array:
    """bool ``[.., S, N]`` (query ``i`` keeps candidate ``n``) -> int32
    ``[.., N]`` keep words, bit ``i`` query ``i``'s; ``S <= MAX_CHUNK``."""
    S = keep.shape[-2]
    bits = jnp.left_shift(
        jnp.uint32(1), jnp.arange(S, dtype=jnp.uint32)
    )[:, None]
    words = jnp.sum(jnp.where(keep, bits, jnp.uint32(0)), axis=-2,
                    dtype=jnp.uint32)
    return jax.lax.bitcast_convert_type(words, jnp.int32)


def _walk_plan(block_tables, n_blocks, q_len, N, cols, NC, K):
    """A walk's scalar operands, for rows of ``NC`` chunks of ``K`` blocks
    over the first ``cols`` table columns: the clamped table padded to whole
    chunks and flattened, the chunks each row walks (none where ``q_len`` is
    0), the chunks walked by the rows before it (buffer parity) and the next
    row that walks any (or the row count)."""
    B = block_tables.shape[0]
    bt = jnp.minimum(block_tables[:, :cols], N - 1).astype(jnp.int32)
    bt = jnp.pad(bt, ((0, 0), (0, NC * K - bt.shape[1])))
    nblk = jnp.clip(n_blocks.astype(jnp.int32).reshape(B), 0, cols)
    nc = jnp.where(q_len.reshape(B) > 0, -(-nblk // K), 0)
    start = jnp.cumsum(nc) - nc
    rows = jnp.arange(B, dtype=jnp.int32)
    nxt = jax.lax.cummin(jnp.where(nc > 0, rows, B), reverse=True)
    nxt = jnp.concatenate([nxt[1:], jnp.full((1,), B, jnp.int32)])
    return bt.reshape(-1), nc, start.astype(jnp.int32), nxt


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def dsa_paged_attention(
    q: jax.Array,  # [B, CB, Hq, D] — a CB-token query chunk a row
    k_pool: jax.Array,  # [L, N, bs, Hkv, D] — the stale stacked pools
    v_pool: jax.Array,
    k_new: jax.Array,  # [B, CB, Hkv, D] — the chunk's own fresh keys
    v_new: jax.Array,
    keep_c: jax.Array,  # [B, T] int32 — keep words of the logical slots
    keep_w: jax.Array,  # [B, CB] int32 — keep words of the fresh tokens
    q_len: jax.Array,  # [B] — live queries of the chunk (0..CB)
    block_tables: jax.Array,  # [B, MB] int32 (sentinel >= N = unmapped)
    n_blocks: jax.Array,  # [B] — table columns that hold any token
    layer: jax.Array,  # int32 scalar — pool layer to read
    *,
    scale: float | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Attention of every row's live queries over the cached slots and the
    fresh tokens their keep words name; returns ``[B, CB, Hq, D]`` in q's
    dtype: ``sparse_chunk_attention``'s result on every live query (``i <
    q_len``) given the ``keep`` it computes, ``sparse_decode_attention``'s at
    ``CB == 1``. A word's bits past ``q_len`` are not read. ``keep_c`` may be
    narrower than the tables (a bucketed read): columns past ``T / bs`` are
    not walked."""
    B, CB, Hq, D = q.shape
    L, N, bs, Hkv, _ = k_pool.shape
    G = Hq // Hkv
    R, F = CB * G, _fresh_rows(CB)
    KS = chunk_slots(bs, Hq, Hkv, D, CB, k_pool.dtype)
    if KS is None:
        raise ValueError(
            f"pallas_dsa does not take bs={bs}, Hq={Hq}, Hkv={Hkv}, D={D}, "
            f"chunk={CB}, {k_pool.dtype}"
        )
    if scale is None:
        scale = 1.0 / (D**0.5)
    K = KS // bs
    T = keep_c.shape[1]
    NC = -(-T // KS)
    cols = -(-T // bs)

    q_len = q_len.astype(jnp.int32).reshape(B)
    # query i's own bits, for the live ones; fresh key 0 for the others
    live = jnp.where(
        q_len >= 32, jnp.int32(-1), jnp.left_shift(jnp.int32(1), q_len) - 1
    )[:, None]
    kc = jnp.pad(
        keep_c.astype(jnp.int32) & live, ((0, 0), (0, NC * KS - T))
    ).reshape(B, NC, KS)
    kw = keep_w.astype(jnp.int32) & live
    kw = kw.at[:, 0].set(kw[:, 0] | ~live[:, 0])
    kw = jnp.pad(kw, ((0, 0), (0, F - CB))).reshape(B, 1, F)
    walk = _walk_plan(block_tables, n_blocks, q_len, N, cols, NC, K)

    def by_head(x, n):  # [B, CB, Hkv * n, D] -> [B, Hkv, CB * n, D]
        x = x.reshape(B, CB, Hkv, n, D).transpose(0, 2, 1, 3, 4)
        return x.reshape(B, Hkv, CB * n, D)

    def fresh(x):
        return jnp.pad(by_head(x, 1), ((0, 0), (0, 0), (0, F - CB), (0, 0)))

    # one head: the pool is handed over without its unit axis (pallas_kv.py)
    buf = (2, KS, D) if Hkv == 1 else (2, KS, Hkv, D)
    if Hkv == 1:
        k_pool, v_pool = (x.reshape(L, N, bs, D) for x in (k_pool, v_pool))

    def row(shape):
        return pl.BlockSpec(
            (1,) + shape, lambda b, *_: (b,) + (0,) * len(shape),
            memory_space=pltpu.VMEM,
        )

    out = pl.pallas_call(
        functools.partial(
            _kernel, scale=float(scale), group=G, block_size=bs,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=(B,),
            in_specs=[
                row((NC, KS)),
                row((1, F)),
                row((Hkv, R, D)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
                row((Hkv, F, D)),
                row((Hkv, F, D)),
            ],
            out_specs=row((Hkv, R, D)),
            scratch_shapes=[
                pltpu.VMEM(buf, k_pool.dtype),
                pltpu.VMEM(buf, v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((Hkv, R, 128), jnp.float32),
                pltpu.VMEM((Hkv, R, 128), jnp.float32),
                pltpu.VMEM((Hkv, R, D), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, R, D), q.dtype),
        # rows in order: a row's last chunk starts the next row's first
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )(
        jnp.asarray(layer, jnp.int32).reshape(1), *walk, q_len,
        kc, kw, by_head(q, G), k_pool, v_pool, fresh(k_new), fresh(v_new),
    )
    out = out.reshape(B, Hkv, CB, G, D).transpose(0, 2, 1, 3, 4)
    return out.reshape(B, CB, Hq, D)


# --- the indexer's scores, made where its pool lies -------------------------
#
# ``I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])`` over the cached slots
# (``ops/sparse_attention.py: index_scores``) by the walk above over ONE
# pool, ``PagedKVCache.idx`` (``f32[L, N, bs, W]``: a key zero-padded to a
# lane tile a slot), and no softmax: a grid step a row, the row's occupied
# blocks a chunk at a time through two VMEM buffers, rows with nothing live
# or nothing cached walk nothing. A slot is 512 B where a slot of keys and
# values is 2 KB, so a chunk is wider than the read's. All of a row's queries
# and indexer heads are ONE product a tile of a chunk (``[S * Hi, W] x [W,
# tile]``), float32 operands at ``Precision.HIGHEST`` (a top-k is
# discontinuous: docs/sparse-attention.md, "The hazard"); ``relu``, the
# weights and the sum over heads float32 on the vector unit. It returns
# SCORES, not bits: the masks, ``keep_topk`` and the tie rule stay with the
# selection, which looks at a score only where a query may see the slot.

#: Slots of a chunk of the indexer pool's walk, widest first, and of the tile
#: of a chunk one product scores. On a v5e, cell 6's pool and traffic, a
#: layer's two calls took 0.29-0.31 ms at chunks of 512, 1,024 and 2,048
#: alike (256 lost 3-9% before the scores were written in place): a chunk is
#: 64 copies of 8 KB whatever its turn costs, and their issue is what binds
#: (PERF.md section 6, PR 52).
_INDEX_CHUNK_SLOTS = (1024, 512, 256)
_INDEX_TILE = 512


def index_chunk_slots(
    block_size: int, n_heads: int, width: int, chunk: int, n_slots: int,
    dtype,
) -> int | None:
    """Slots of one chunk of the indexer pool's walk at these shapes; None
    where the kernel does not take them: a float32 pool of whole lane tiles
    a slot, blocks of whole sublane tiles that divide a chunk, indexer heads
    in whole sublane tiles (the sum over heads is a reduction of ``[chunk,
    heads, tile]``), at most ``MAX_CHUNK`` queries a row, and a row's scores
    over the ``n_slots`` it may hold within the VMEM budget beside the
    walk's working set (a grid step owns them)."""
    if (
        jnp.dtype(dtype) != jnp.dtype(jnp.float32) or width % 128
        or block_size % 8 or n_heads % 8 or not 0 < chunk <= MAX_CHUNK
    ):
        return None
    rows = chunk * n_heads
    for ks in _INDEX_CHUNK_SLOTS:
        tile = min(ks, _INDEX_TILE)
        if (
            ks % (block_size * _COPIES_UNROLLED) == 0
            and 2 * ks * width * 4 <= _BUFFER_BYTES
            and 2 * rows * (width + 128) * 4  # queries and weights, twice
            + 2 * ks * width * 4  # the two buffers
            + 3 * rows * tile * 4  # a tile's products, weighted, summed
            # the row's scores, twice, in whole chunks and sublane tiles
            + 2 * -(-n_slots // ks) * ks * -(-chunk // 8) * 8 * 4
            <= _VMEM_BUDGET
        ):
            return ks
    return None


def index_supports(
    block_size: int, n_heads: int, width: int, chunk: int, n_slots: int,
    dtype,
) -> bool:
    """Whether ``idx_paged_scores`` takes these shapes (``index_chunk_slots``
    says which conditions)."""
    return index_chunk_slots(
        block_size, n_heads, width, chunk, n_slots, dtype
    ) is not None


def _index_kernel(
    layer_ref,  # [1] — layer of the stacked pool
    bt_ref,  # [R * NC * K] — flattened clamped block table
    nc_ref,  # [R] — chunks this row walks
    start_ref,  # [R] — chunks walked by the rows before it (buffer parity)
    next_ref,  # [R] — the next row that walks any, or R
    q_ref,  # [1, S * Hi, W] f32 — query-major: row t * Hi + j
    w_ref,  # [1, S * Hi, 1] f32
    idx_ref,  # [L, N, bs, W] f32 in HBM
    o_ref,  # [1, S, NC * KS] f32 — the row's view, whole chunks
    buf_ref,  # [2, KS, W] f32
    sem_ref,  # DMA [2]
    *,
    block_size: int,
    heads: int,
    tile: int,
):
    b = pl.program_id(0)
    n_rows = pl.num_programs(0)
    KS, S = buf_ref.shape[1], o_ref.shape[1]
    K = KS // block_size
    cols = o_ref.shape[2] // block_size  # table columns a row has here
    layer = layer_ref[0]
    n, base = nc_ref[b], start_ref[b]

    def fetch(row, c, slot):
        def some(j, carry):
            for u in range(_COPIES_UNROLLED):
                i = j * _COPIES_UNROLLED + u
                pltpu.make_async_copy(
                    idx_ref.at[layer, bt_ref[row * cols + c * K + i]],
                    buf_ref.at[slot, pl.ds(i * block_size, block_size)],
                    sem_ref.at[slot],
                ).start()
            return carry

        jax.lax.fori_loop(0, K // _COPIES_UNROLLED, some, 0)

    # the first row that walks anything starts its own first chunk
    @pl.when((base == 0) & (n > 0))
    def _():
        fetch(b, 0, 0)

    def chunk(c, carry):
        slot = (base + c) % 2
        nxt = next_ref[b]
        # the row's next chunk, or the first of the next row with any
        more = c + 1 < n

        @pl.when(more | (nxt < n_rows))
        def _():
            fetch(jnp.where(more, b, nxt), jnp.where(more, c + 1, 0), 1 - slot)

        # one wait for the K copies of a chunk
        pltpu.make_async_copy(
            buf_ref.at[slot], buf_ref.at[slot], sem_ref.at[slot]
        ).wait()
        for j in range(0, KS, tile):
            s = jax.lax.dot_general(
                q_ref[0], buf_ref[slot, j:j + tile],
                (((1,), (1,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32,
            )  # [S * Hi, tile]
            s = jnp.maximum(s, 0.0) * w_ref[0]
            o_ref[0, :, pl.ds(pl.multiple_of(c * KS + j, tile), tile)] = (
                jnp.sum(s.reshape(S, heads, tile), axis=1)
            )
        return carry

    jax.lax.fori_loop(0, n, chunk, 0)


@functools.partial(jax.jit, static_argnames=("n_slots", "interpret"))
def idx_paged_scores(
    qi: jax.Array,  # [R, S, Hi, Di <= W] float32 — a row's S queries
    wi: jax.Array,  # [R, S, Hi] float32
    idx_pool: jax.Array,  # [L, N, bs, W] float32 — the stale stacked pool
    q_len: jax.Array,  # [R] — live queries (0: the row walks nothing)
    block_tables: jax.Array,  # [R, MB] int32 (sentinel >= N = unmapped)
    n_blocks: jax.Array,  # [R] — table columns that hold any token
    layer: jax.Array,  # int32 scalar — pool layer to read
    *,
    n_slots: int,
    interpret: bool = False,
) -> jax.Array:
    """float32 ``[R, S, n_slots]``: every query's indexer score over the
    first ``n_slots`` slots of its row's logical view, ``index_scores(qi, wi,
    gather_block_view(idx_pool, ..))`` up to float32 reduction order wherever
    the walk went: the slots of a row's first ``n_blocks`` blocks, for rows
    with ``q_len > 0``. Elsewhere the result is UNDEFINED (NaN included): its
    reader masks by what a query may see, which is false there."""
    R, S, Hi, Di = qi.shape
    L, N, bs, W = idx_pool.shape
    KS = index_chunk_slots(bs, Hi, W, S, n_slots, idx_pool.dtype)
    if KS is None or Di > W:
        raise ValueError(
            f"pallas_dsa does not score bs={bs}, Hi={Hi}, Di={Di}, W={W}, "
            f"chunk={S}, slots={n_slots}, {idx_pool.dtype}"
        )
    K = KS // bs
    NC = -(-n_slots // KS)
    cols = -(-n_slots // bs)

    q = jnp.pad(qi.astype(jnp.float32), ((0, 0),) * 3 + ((0, W - Di),))

    def row(shape):
        return pl.BlockSpec(
            (1,) + shape, lambda b, *_: (b,) + (0,) * len(shape),
            memory_space=pltpu.VMEM,
        )

    out = pl.pallas_call(
        functools.partial(
            _index_kernel, block_size=bs, heads=Hi,
            tile=min(KS, _INDEX_TILE),
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(R,),
            in_specs=[
                row((S * Hi, W)),
                row((S * Hi, 1)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=row((S, NC * KS)),
            scratch_shapes=[
                pltpu.VMEM((2, KS, W), idx_pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((R, S, NC * KS), jnp.float32),
        # rows in order: a row's last chunk starts the next row's first
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )(
        jnp.asarray(layer, jnp.int32).reshape(1),
        *_walk_plan(block_tables, n_blocks, q_len, N, cols, NC, K),
        q.reshape(R, S * Hi, W),
        wi.astype(jnp.float32).reshape(R, S * Hi, 1),
        idx_pool,
    )
    return out[:, :, :n_slots]
