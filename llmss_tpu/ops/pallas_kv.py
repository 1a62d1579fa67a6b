"""The read of a pool of keys and values by the decode and mixed steps.

The XLA oracles (``ops.attention.paged_decode_attention`` /
``ragged_paged_attention``) gather every row's WHOLE ring of keys and of
values into two views, write float32 scores over all of it and read the
value view again for the weighted sum, whatever the rows hold. This kernel
reads both pools where they lie, with the walk of ``ops/pallas_mla.py``:

* the pools stay in HBM as stored (``[L, N, bs, Hkv, D]``, ``pl.ANY``); a
  grid step is one batch ROW, and inside it a ``fori_loop`` walks the row's
  occupied blocks ``K`` at a time, the ``K`` key blocks and ``K`` value
  blocks of a chunk copied into one of two VMEM buffer pairs while the other
  is computed on. The trip count is ``ceil(n_blocks[row] / K)``: the walk
  stops at the row's length, and a row with nothing cached or ``q_len == 0``
  walks nothing. The last chunk of a row starts the first chunk of the next
  row that has any, so only the first copy of a call is waited for with
  nothing to do;
* the chunk's width in slots is chosen from the shapes under a VMEM budget
  (``chunk_slots``): a slot is ``Hkv * D * itemsize`` bytes a pool;
* per KV head: scores ``q_h . k_h^T`` over the chunk for the head's ``CB *
  G`` query rows, online softmax with the running max, sum and accumulator
  in float32 in VMEM, ``p . v_h``: no score reaches HBM;
* where a KV head has too few query rows for its product to fill a tile
  (``attn_form``'s ``heads``: a KV head a query head, or nearly), ALL heads
  of a landed chunk in one product instead: the chunk ``[KS, Hkv, D]`` is
  ``[KS * Hkv, D]`` byte for byte, so ``Q [CB * Hq, D] . K^T`` gives every
  query row's scores over every (slot, head) along the lanes, of which a
  row keeps its own head's (the mask) and the rest is computed and thrown
  away; the same online softmax over a row's lanes, then ``P . V [KS *
  Hkv, D]``. No strided load, no shift, no loop over heads;
* a row that decodes in a mixed step (``q_len == 1``) scores its first
  query's heads alone, not the chunk's ``CB * G`` query rows a head.

Masks are the oracle's: a slot is seen iff it holds a token (``kv_pos >=
0``), is not one of the chunk's ``q_len`` pending ring slots from ``slot0``
(the deferred write overwrites those), ``kv_pos <= q_pos + i`` for query
``i``, and, under a sliding window, ``kv_pos > q_pos + i - window``. Slot
ORDER carries no meaning (a wrapped ring, shared prefix blocks): the table
says where a slot lives, ``kv_pos`` what it holds. The fresh keys and values
merge last under the triangular mask clipped at ``q_len``; fresh key 0 is
seen by every query row, padding rows and rows with ``q_len == 0`` included,
so every denominator is positive and no row's output is NaN (padding rows'
outputs are finite and never read).

bfloat16 operands, float32 accumulation of both products, float32 softmax
state; the probabilities are rounded to the pool's dtype for the second
product, as ``pallas_mla`` and the flash kernel do.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = float(jnp.finfo(jnp.float32).min)
_BIG = jnp.iinfo(jnp.int32).max

# Widths of a chunk of the walk, widest first: K = slots // block_size
# blocks a pool a DMA wave, and the width of a head's score tile. The last
# chunk of a row is copied whole, so a wide chunk over short rows copies
# blocks nobody holds. On a v5e a layer's read took, at 64 rows x 320 blocks
# of [16, 2, 256] with a document mix's lengths, 0.66 ms at 256 and 0.70 at
# 512; at 64 x 64 blocks of [16, 32, 128] with a chat mix's, 2.0 ms at 128
# and 2.2 at 256, one head pair a turn (PERF.md section 6, PR 47).
_CHUNK_SLOTS = (256, 128, 64)
_BUFFER_BYTES = 4 * 2**20  # the two buffer pairs: 128 slots of 8 KB a pool
_COPIES_UNROLLED = 2  # blocks a pool written out in a turn of the issue loop
# Head pairs (or heads) written out in a turn of their loop: at 32 heads of
# 128 the same read took 1.84 ms at one, 1.32 at two and 1.21 at four, which
# lowers 0.2 s a program slower (that shape has no turns since PR 56:
# ``attn_form``).
_TURNS_UNROLLED = 2
# Fresh keys are padded to one sublane tile of the widest dtype served.
_FRESH_ROWS = 16
_VMEM_BUDGET = 12 * 2**20  # under the 16 MiB a v5e kernel may scope


def attn_form(n_heads: int, n_kv_heads: int, chunk: int, dtype) -> str:
    """What is done with a landed chunk at these shapes. ``head``: a product
    a KV head (a head pair a turn), which hands the matrix unit a tile or
    more a head where a KV head has a tile of query rows. ``heads``: ONE
    product a chunk over all its heads, where a KV head has fewer query rows
    than the 8 sublanes of a float32 tile, all heads' rows together are
    within 128, and a slot's heads are whole tiles (so that ``[slots, Hkv,
    D]`` is ``[slots * Hkv, D]`` byte for byte)."""
    rows = chunk * (n_heads // n_kv_heads)
    sublanes = 32 // jnp.dtype(dtype).itemsize
    if rows < 8 and n_kv_heads * rows <= 128 and n_kv_heads % sublanes == 0:
        return "heads"
    return "head"


def _vmem_bytes(
    ks: int, rows: int, Hkv: int, D: int, itemsize: int, heads: bool = False
) -> int:
    """The working set at a chunk of ``ks`` slots; ``rows`` query rows a KV
    head. A tile is 8 sublanes of 32 bits: fewer rows still fill one. Under
    ``heads`` the scores are every query row's over every (slot, head)."""
    scores = 3 * rows * ks * 4  # one head's scores, probabilities, mask
    if heads:
        scores *= Hkv * Hkv
    else:
        rows = -(-rows // 8) * 8
    return (
        4 * Hkv * rows * D * itemsize  # q and out, double-buffered
        + 4 * Hkv * _FRESH_ROWS * D * itemsize  # fresh keys and values
        + 4 * ks * Hkv * D * itemsize  # the two buffer pairs
        + Hkv * rows * (D + 2 * 128) * 4  # accumulator, running max and sum
        + scores
    )


def chunk_slots(
    block_size: int, n_heads: int, n_kv_heads: int, head_dim: int,
    chunk: int, dtype,
) -> int | None:
    """Slots of one chunk of the walk at these shapes: the widest of
    ``_CHUNK_SLOTS`` whose two buffer pairs are within ``_BUFFER_BYTES`` and
    whose working set is within ``_VMEM_BUDGET``; None where the kernel does
    not take the shapes at all."""
    dtype = jnp.dtype(dtype)
    if dtype not in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)):
        return None
    sublanes = 32 // dtype.itemsize
    if (
        head_dim % 128 or n_kv_heads <= 0 or n_heads % n_kv_heads
        or block_size % sublanes or not 0 < chunk <= _FRESH_ROWS
        # a 16-bit pool's heads are taken out of their words in pairs
        or (n_kv_heads > 1 and n_kv_heads % (sublanes // 8))
    ):
        return None
    rows = chunk * (n_heads // n_kv_heads)
    heads = attn_form(n_heads, n_kv_heads, chunk, dtype) == "heads"
    for ks in _CHUNK_SLOTS:
        if (
            ks % (block_size * _COPIES_UNROLLED) == 0
            and 4 * ks * n_kv_heads * head_dim * dtype.itemsize
            <= _BUFFER_BYTES
            and _vmem_bytes(
                ks, rows, n_kv_heads, head_dim, dtype.itemsize, heads
            ) <= _VMEM_BUDGET
        ):
            return ks
    return None


def supports(
    block_size: int, n_heads: int, n_kv_heads: int, head_dim: int,
    chunk: int, dtype,
) -> bool:
    """Whether the kernel takes these shapes: a lane-aligned head, query
    heads a multiple of the pool's, blocks that tile the sublanes of
    ``dtype`` and divide a chunk of the walk, at most ``_FRESH_ROWS`` fresh
    keys, and a working set within ``_VMEM_BUDGET`` at some chunk width."""
    return chunk_slots(
        block_size, n_heads, n_kv_heads, head_dim, chunk, dtype
    ) is not None


def _kernel(
    layer_ref,  # [1] — layer of the stacked pools
    bt_ref,  # [B * NC * K] — flattened clamped block table
    nc_ref,  # [B] — chunks this row walks
    start_ref,  # [B] — chunks walked by the rows before it (buffer parity)
    next_ref,  # [B] — the next row that walks any, or B
    qp_ref,  # [B] — first query's position
    ql_ref,  # [B] — live queries
    sl_ref,  # [B] — logical slot of the first query
    kvp_ref,  # [1, NC, KS] int32 — positions of the row's logical slots
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
    window: int | None,
    group: int,
    block_size: int,
    ring_len: int,
    heads: bool,
):
    """``heads`` (``attn_form``'s ``heads``): the operands come as the pool
    lies, slots major and a slot's heads dense, with no head axis of their
    own: ``kvp_ref`` [1, NC, KS * Hkv] (a slot's position under each of its
    heads), ``q_ref`` / ``o_ref`` [1, CB * Hq, D] (query-major), ``kn_ref``
    / ``vn_ref`` [1, F, Hkv, D], ``m_ref`` / ``l_ref`` [CB * Hq, 128],
    ``acc_ref`` [CB * Hq, D]."""
    b = pl.program_id(0)
    n_rows = pl.num_programs(0)
    KS = kbuf_ref.shape[1]
    if heads:
        F, Hkv = kn_ref.shape[1:3]
        R = q_ref.shape[1] // Hkv
    else:
        Hkv, R, F = q_ref.shape[1], q_ref.shape[2], kn_ref.shape[2]
    K = KS // block_size
    cols = kvp_ref.shape[1] * K  # table columns a row has here
    layer = layer_ref[0]
    n, base = nc_ref[b], start_ref[b]
    qp, qlen, slot0 = qp_ref[b], ql_ref[b], sl_ref[b]

    # The kernel is lowered anew for every step program that holds it, and a
    # replica's set-up pays: a loop of a few copies, not K written out, and
    # every loop body written once.
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
        # one wait a pool for the K copies of a chunk: a semaphore counts
        # what has arrived, and this asks for a whole buffer's worth
        for p, buf in enumerate((kbuf_ref, vbuf_ref)):
            pltpu.make_async_copy(
                buf.at[slot], buf.at[slot], sem_ref.at[p, slot]
            ).wait()

    m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    # query row r = i * group + g of a KV head belongs to query i, at qp + i;
    # under ``heads`` row i * Hq + h * group + g does, and to KV head h
    Hq = Hkv * group
    q_row = jax.lax.broadcasted_iota(
        jnp.int32, (R * Hkv if heads else R, 1), 0
    )
    q_idx = q_row // (Hq if heads else group)
    q_head = q_row % Hq // group if heads else None
    q_pos = qp + q_idx  # [R, 1]; [R * Hkv, 1]

    def update(at, rows, keys, vals, mask):
        """One online-softmax step of the first ``rows`` query rows ``at``
        a KV head ``(h,)``, or of all heads ``()``, over ``keys`` / ``vals``
        [T', D] under ``mask`` [rows, T']."""
        top = (*at, slice(rows))
        s = jax.lax.dot_general(
            q_ref[(0, *top)], keys, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        s = jnp.where(mask, s, _NEG_INF)
        m_prev, l_prev = m_ref[(*top, slice(1))], l_ref[(*top, slice(1))]
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        # a row that sees nothing yet has its max at the float32 min, and
        # exp(s - m) would be exp(0): zero what the mask hides
        p = jnp.where(mask, jnp.exp(s - m_next), 0.0)
        alpha = jnp.exp(m_prev - m_next)
        l_ref[(*top, slice(1))] = alpha * l_prev + jnp.sum(
            p, axis=1, keepdims=True
        )
        m_ref[(*top, slice(1))] = m_next
        acc_ref[top] = acc_ref[top] * alpha + jax.lax.dot_general(
            p.astype(vals.dtype), vals, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    def update_one(h, keys, vals, seen):
        """The same step for ONE query row (a decoding row of a pool with a
        KV head a query head) on the vector unit: a [1, D] x [D, T'] product
        loads the matrix unit with T' x D keys to use one row of it. Keys
        and values come as float32 [T', D], ``seen`` as [T', 1]: the scores
        run down the sublanes, a multiply and a lane reduction a key, and
        the weighted sum is a multiply and a reduction over the keys."""
        q = q_ref[0, h, :1].astype(jnp.float32)  # [1, D]
        s = jnp.sum(keys * q, axis=1, keepdims=True) * scale  # [T', 1]
        s = jnp.where(seen, s, _NEG_INF)
        m_prev, l_prev = m_ref[h, :1, :1], l_ref[h, :1, :1]
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
        p = jnp.where(seen, jnp.exp(s - m_next), 0.0)
        alpha = jnp.exp(m_prev - m_next)
        l_ref[h, :1, :1] = alpha * l_prev + jnp.sum(p, axis=0, keepdims=True)
        m_ref[h, :1, :1] = m_next
        # the probabilities rounded as the matrix unit would be handed them
        p = p.astype(kbuf_ref.dtype).astype(jnp.float32)
        acc_ref[h, :1] = acc_ref[h, :1] * alpha + jnp.sum(
            p * vals, axis=0, keepdims=True
        )

    # One head's [KS, D] out of a chunk's [KS, Hkv, D]. A 16-bit pool packs
    # two heads of a slot in every 32-bit word (the second-minor axis is the
    # packed one, in HBM as in VMEM), and a sublane-strided load of one head
    # would have to repack every word: load the words of a head PAIR once
    # (a strided load of whole words) and take each head out of its half,
    # which as the high half of a float32 is the bfloat16's own value. The
    # two heads of a pair are worked in one turn, so that one's products
    # overlap the other's softmax.
    packed = kbuf_ref.dtype.itemsize == 2 and Hkv > 1
    turns = Hkv // 2 if packed else Hkv

    def heads_of(buf_ref, slot, j, dtype):
        """``[(head, its [KS, D] as dtype)]`` of turn ``j``."""
        if Hkv == 1:  # the pool came without its head axis
            return [(0, buf_ref[slot].astype(dtype))]
        if not packed:
            return [(j, buf_ref[slot, :, j, :].astype(dtype))]
        w = buf_ref.bitcast(jnp.uint32)[slot, :, j, :]  # [KS, D] words
        halves = (w << 16, w & jnp.uint32(0xFFFF0000))
        return [
            (2 * j + i, pltpu.bitcast(x, jnp.float32).astype(dtype))
            for i, x in enumerate(halves)
        ]

    def each(count, body):
        """``body(i)`` for i < count: written out for a few, a loop of
        ``_TURNS_UNROLLED`` a turn for more (the kernel's text is lowered in
        every step program; independent heads side by side fill the waits
        of one another's dependent sums)."""
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

    def lanes(slots):
        """``(slot, KV head)`` of every lane of the scores over ``slots``
        slots, [1, lanes] each: a lane a slot (no head) or, under ``heads``,
        a slot's heads side by side."""
        if not heads:
            return jax.lax.broadcasted_iota(jnp.int32, (1, slots), 1), None
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, slots * Hkv), 1)
        return lane // Hkv, lane % Hkv

    def flat(x):  # [T', Hkv, D] as [T' * Hkv, D]: the same bytes
        return x.reshape(-1, x.shape[-1])

    def visible(rows, c):
        """What the first ``rows`` query rows see of chunk ``c``'s lanes."""
        kvp = kvp_ref[0, pl.ds(c, 1), :]  # [1, lanes]
        slot_idx = c * KS  # the chunk's first slot, then each lane's
        t, h = lanes(KS)
        slot_idx += t
        d = slot_idx - slot0
        d = jnp.where(d < 0, d + ring_len, d)
        seen = (kvp >= 0) & (d >= qlen)
        # hidden slots are given a position no query reaches
        mask = jnp.where(seen, kvp, _BIG) <= q_pos[:rows]  # [rows, lanes]
        if window is not None:
            mask &= kvp > q_pos[:rows] - window
        if heads:  # of every (slot, head), a row keeps its own head's
            mask &= h == q_head[:rows]
        return mask

    def head_by_head(rows, c, slot):
        mask = visible(rows, c)
        if rows == 1:
            # [1, KS] along the lanes to [KS, 1] down the sublanes, by a
            # transpose of whole tiles, once for the chunk's heads
            down = jnp.broadcast_to(mask.astype(jnp.float32), (KS, KS))
            mask = down.T[:, :1] > 0.5
        dtype = jnp.float32 if rows == 1 else kbuf_ref.dtype

        def turn(j):
            for (h, keys), (_, vals) in zip(
                heads_of(kbuf_ref, slot, j, dtype),
                heads_of(vbuf_ref, slot, j, dtype),
            ):
                if rows == 1:
                    update_one(h, keys, vals, mask)
                else:
                    update((h,), rows, keys, vals, mask)

        each(turns, turn)

    def all_heads(rows, c, slot):
        """The landed chunk as ONE [KS * Hkv, D] operand a pool: every
        query row against every (slot, head)."""
        update(
            (), rows, flat(kbuf_ref[slot]), flat(vbuf_ref[slot]),
            visible(rows, c),
        )

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
            (all_heads if heads else head_by_head)(rows, c, slot)
            return carry

        jax.lax.fori_loop(0, n, chunk, 0)

    # the query rows of a chunk, and those of its first query
    every, first = (R * Hkv, Hq) if heads else (R, group)
    if every > first:
        # a row that decodes (one live query) scores its first query's
        # heads alone: the other CB - 1 queries are padding
        pl.when(qlen == 1)(lambda: walk(first))
        pl.when(qlen != 1)(lambda: walk(every))
    else:
        walk(every)

    # fresh key j is seen by query i iff j <= i and j < q_len (and inside
    # the window); key 0 by every query past q_len, so that none sees nothing
    j, h = lanes(F)
    tri = (j <= q_idx) & (j < qlen)
    if window is not None:
        tri &= q_idx - j < window
    tri |= (j == 0) & (q_idx >= qlen)

    if heads:
        update((), every, flat(kn_ref[0]), flat(vn_ref[0]), tri & (h == q_head))
        o_ref[0] = (acc_ref[...] / l_ref[:, :1]).astype(o_ref.dtype)
        return

    def merged(h):
        update((h,), R, kn_ref[0, h], vn_ref[0, h], tri)
        o_ref[0, h] = (acc_ref[h] / l_ref[h, :, :1]).astype(o_ref.dtype)

    each(Hkv, merged)


@functools.partial(
    jax.jit, static_argnames=("ring_len", "scale", "window", "interpret")
)
def kv_paged_attention(
    q: jax.Array,  # [B, CB, Hq, D] — a CB-token query chunk a row
    k_pool: jax.Array,  # [L, N, bs, Hkv, D] — the stale stacked pools
    v_pool: jax.Array,
    k_new: jax.Array,  # [B, CB, Hkv, D] — the chunk's own fresh keys
    v_new: jax.Array,
    q_pos: jax.Array,  # [B] or [B, 1] — FIRST query's absolute position
    q_len: jax.Array,  # [B] — live queries of the chunk (0..CB)
    kv_pos: jax.Array,  # [B, T] — pre-write LOGICAL slot positions
    block_tables: jax.Array,  # [B, MB] int32 (sentinel >= N = unmapped)
    n_blocks: jax.Array,  # [B] — table columns that hold any token
    slot0: jax.Array,  # [B] or [B, 1] — logical slot of the first query
    layer: jax.Array,  # int32 scalar — pool layer to read
    *,
    ring_len: int,
    scale: float | None = None,
    window: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Attention of every row's queries over its cached keys and values and
    its fresh ones; returns ``[B, CB, Hq, D]`` in q's dtype. The contract of
    ``ops.attention.ragged_paged_attention`` on the pools read at ``layer``,
    on every live query (``i < q_len``); at ``CB == 1`` that of
    ``paged_decode_attention``. ``kv_pos`` may be narrower than the tables (a
    bucketed read): columns past ``T / bs`` are not walked."""
    B, CB, Hq, D = q.shape
    L, N, bs, Hkv, _ = k_pool.shape
    G = Hq // Hkv
    R, F = CB * G, _FRESH_ROWS
    KS = chunk_slots(bs, Hq, Hkv, D, CB, k_pool.dtype)
    if KS is None:
        raise ValueError(
            f"pallas_kv does not take bs={bs}, Hq={Hq}, Hkv={Hkv}, D={D}, "
            f"chunk={CB}, {k_pool.dtype}"
        )
    heads = attn_form(Hq, Hkv, CB, k_pool.dtype) == "heads"
    if scale is None:
        scale = 1.0 / (D**0.5)
    K = KS // bs
    T = kv_pos.shape[1]
    NC = -(-T // KS)
    cols = -(-T // bs)

    kvp = jnp.pad(
        kv_pos.astype(jnp.int32), ((0, 0), (0, NC * KS - T)),
        constant_values=-1,
    ).reshape(B, NC, KS)
    bt = jnp.minimum(block_tables[:, :cols], N - 1).astype(jnp.int32)
    bt = jnp.pad(bt, ((0, 0), (0, NC * K - bt.shape[1])))
    q_len = q_len.astype(jnp.int32).reshape(B)
    nblk = jnp.clip(n_blocks.astype(jnp.int32).reshape(B), 0, cols)
    nc = jnp.where(q_len > 0, -(-nblk // K), 0)
    start = jnp.cumsum(nc) - nc
    rows = jnp.arange(B, dtype=jnp.int32)
    nxt = jax.lax.cummin(jnp.where(nc > 0, rows, B), reverse=True)
    nxt = jnp.concatenate([nxt[1:], jnp.full((1,), B, jnp.int32)])

    def by_head(x, n):  # [B, CB, Hkv * n, D] -> [B, Hkv, CB * n, D]
        x = x.reshape(B, CB, Hkv, n, D).transpose(0, 2, 1, 3, 4)
        return x.reshape(B, Hkv, CB * n, D)

    def fresh(x):
        if heads:  # as the pool lies: [B, F, Hkv, D]
            return jnp.pad(x, ((0, 0), (0, F - CB), (0, 0), (0, 0)))
        return jnp.pad(by_head(x, 1), ((0, 0), (0, 0), (0, F - CB), (0, 0)))

    # One head: its unit axis is not the pool's second-minor one on the
    # device (the slots are: dense tiles), and a block of it cannot be cut
    # out of the tiling a two-minor-axes view [.., 1, D] would have.
    buf = (2, KS, D) if Hkv == 1 else (2, KS, Hkv, D)
    if Hkv == 1:
        k_pool, v_pool = (x.reshape(L, N, bs, D) for x in (k_pool, v_pool))
    # the query rows and the softmax state: a KV head's, or all heads'
    # query-major as they come (no transpose around the call)
    rows_of = (CB * Hq,) if heads else (Hkv, R)
    if heads:
        kvp = jnp.repeat(kvp, Hkv, axis=2)

    def row(shape):
        return pl.BlockSpec(
            (1,) + shape, lambda b, *_: (b,) + (0,) * len(shape),
            memory_space=pltpu.VMEM,
        )

    fresh_block = row((F, Hkv, D) if heads else (Hkv, F, D))
    out = pl.pallas_call(
        functools.partial(
            _kernel, scale=float(scale), window=window, group=G,
            block_size=bs, ring_len=ring_len, heads=heads,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=8,
            grid=(B,),
            in_specs=[
                row(kvp.shape[1:]),
                row(rows_of + (D,)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
                fresh_block,
                fresh_block,
            ],
            out_specs=row(rows_of + (D,)),
            scratch_shapes=[
                pltpu.VMEM(buf, k_pool.dtype),
                pltpu.VMEM(buf, v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM(rows_of + (128,), jnp.float32),
                pltpu.VMEM(rows_of + (128,), jnp.float32),
                pltpu.VMEM(rows_of + (D,), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B,) + rows_of + (D,), q.dtype),
        # rows in order: a row's last chunk starts the next row's first
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )(
        jnp.asarray(layer, jnp.int32).reshape(1),
        bt.reshape(-1),
        nc, start.astype(jnp.int32), nxt,
        q_pos.astype(jnp.int32).reshape(B), q_len,
        slot0.astype(jnp.int32).reshape(B),
        kvp, q.reshape(B, -1, D) if heads else by_head(q, G),
        k_pool, v_pool, fresh(k_new), fresh(v_new),
    )
    if heads:
        return out.reshape(B, CB, Hq, D)
    out = out.reshape(B, Hkv, CB, G, D).transpose(0, 2, 1, 3, 4)
    return out.reshape(B, CB, Hq, D)
