"""The read of a latent (MLA) block pool by the decode and mixed steps.

A latent pool holds ONE ``pool_dim``-wide row a token, which is its key and,
in its leading columns, its value; every query head of a row attends over
the same rows (multi-query attention with one shared head, ``CB * H`` query
rows a batch row). The XLA oracle (``ops.attention.ragged_paged_attention``
with keys and values the same pool) gathers every row's whole ring into a
view, writes float32 scores over all of it and reads the view again for the
weighted sum. This kernel reads the pool where it lies:

* the pool stays in HBM as stored (``[L, N, bs, W]``, ``pl.ANY``); a grid
  step is one batch ROW, and inside it a ``fori_loop`` walks the row's
  occupied blocks ``K`` at a time (``_CHUNK_SLOTS`` slots), each chunk ``K``
  block copies into one of two VMEM buffers while the other is computed on.
  The trip count is ``ceil(n_blocks[row] / K)``: the walk stops at the row's
  length, and a row with nothing cached or ``q_len == 0`` walks nothing. The
  last chunk of a row starts the first chunk of the next row that has any,
  so only the first copy of a call is waited for with nothing to do;
* one copy of a block serves keys and values: scores are ``q . blk^T``, the
  weighted sum ``p . blk[:, :v_dim]``;
* online softmax with the running max, sum and accumulator in float32 in
  VMEM: no score reaches HBM;
* a row that decodes in a mixed step (``q_len == 1``) scores its first
  query's ``H`` heads alone, not the chunk's ``CB * H`` query rows.

Masks are the oracle's: a slot is seen iff it holds a token (``kv_pos >=
0``), is not one of the chunk's ``q_len`` pending ring slots from ``slot0``
(the deferred write overwrites those), and ``kv_pos <= q_pos + i`` for query
``i``. Slot ORDER carries no meaning (a wrapped ring, shared prefix blocks):
the table says where a slot lives, ``kv_pos`` what it holds. The fresh
latents merge last under the triangular mask clipped at ``q_len``; fresh key
0 is seen by every query row, padding rows and rows with ``q_len == 0``
included, so every denominator is positive and no row's output is NaN
(padding rows' outputs are finite and never read).

bfloat16 operands, float32 accumulation of both products, float32 softmax
state; the probabilities are rounded to the pool's dtype for the second
product, as ``pallas_kv`` and the flash kernel do.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = float(jnp.finfo(jnp.float32).min)
_BIG = jnp.iinfo(jnp.int32).max

# Slots of one chunk of the walk: K = _CHUNK_SLOTS // block_size blocks a
# DMA wave, and the width of the score tile. On a v5e at 64 rows x 320
# blocks of [16, 640] with a document mix's lengths a layer's read took
# 0.39 ms at 512 and 0.44 at 256 (PERF.md section 6, PR 41).
_CHUNK_SLOTS = 512
_COPIES_UNROLLED = 4  # block copies written out in a turn of the issue loop
# Fresh keys are padded to one sublane tile of the widest dtype served.
_FRESH_ROWS = 16
_VMEM_BUDGET = 12 * 2**20  # under the 16 MiB a v5e kernel may scope


def _vmem_bytes(rows: int, W: int, v_dim: int, itemsize: int) -> int:
    ks = _CHUNK_SLOTS
    return (
        2 * rows * W * itemsize  # q, double-buffered by the pipeline
        + 2 * rows * v_dim * itemsize  # out, likewise
        + 2 * ks * W * itemsize  # the two chunk buffers
        + rows * v_dim * 4  # accumulator
        + 2 * rows * 128 * 4  # running max and sum
        + 3 * rows * ks * 4  # scores, probabilities, mask
    )


def supports(
    block_size: int, n_heads: int, pool_dim: int, chunk: int, dtype,
    v_dim: int | None = None,
) -> bool:
    """Whether the kernel takes these shapes: a lane-aligned row (and value
    width), blocks that tile the sublanes of ``dtype`` and divide a chunk of
    the walk, at most ``_FRESH_ROWS`` fresh keys, and a working set within
    ``_VMEM_BUDGET``."""
    dtype = jnp.dtype(dtype)
    if dtype not in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)):
        return False
    v_dim = pool_dim if v_dim is None else v_dim
    sublanes = 32 // dtype.itemsize
    rows = chunk * n_heads
    return (
        pool_dim % 128 == 0
        and v_dim % 128 == 0
        and 0 < v_dim <= pool_dim
        and block_size % sublanes == 0
        and _CHUNK_SLOTS % (block_size * _COPIES_UNROLLED) == 0
        and 0 < chunk <= _FRESH_ROWS
        and _vmem_bytes(rows, pool_dim, v_dim, dtype.itemsize) <= _VMEM_BUDGET
    )


def _kernel(
    layer_ref,  # [1] — layer of the stacked pool
    bt_ref,  # [B * NC * K] — flattened clamped block table
    nc_ref,  # [B] — chunks this row walks
    start_ref,  # [B] — chunks walked by the rows before it (buffer parity)
    next_ref,  # [B] — the next row that walks any, or B
    qp_ref,  # [B] — first query's position
    ql_ref,  # [B] — live queries
    sl_ref,  # [B] — logical slot of the first query
    kvp_ref,  # [1, NC, KS] int32 — positions of the row's logical slots
    q_ref,  # [1, R, W], R = CB * H, query-major
    pool_ref,  # [L, N, bs, W] in HBM
    lat_ref,  # [1, F, W] — fresh latents, zero rows past CB
    o_ref,  # [1, R, V]
    buf_ref,  # [2, KS, W]
    sem_ref,  # DMA [2]
    m_ref,  # [R, 128] f32 (column 0 used)
    l_ref,
    acc_ref,  # [R, V] f32
    *,
    scale: float,
    heads: int,
    block_size: int,
    ring_len: int,
):
    b = pl.program_id(0)
    n_rows = pl.num_programs(0)
    KS, V = buf_ref.shape[1], acc_ref.shape[1]
    R, F = q_ref.shape[1], lat_ref.shape[1]
    K = KS // block_size
    cols = kvp_ref.shape[1] * K  # table columns a row has here
    layer = layer_ref[0]
    n, base = nc_ref[b], start_ref[b]
    qp, qlen, slot0 = qp_ref[b], ql_ref[b], sl_ref[b]

    def copy(row, c, slot, i):
        return pltpu.make_async_copy(
            pool_ref.at[layer, bt_ref[row * cols + c * K + i]],
            buf_ref.at[slot, pl.ds(i * block_size, block_size)],
            sem_ref.at[slot],
        )

    # loops of a few copies, not K written out: the kernel is lowered anew
    # for every step program that holds it, and a replica's set-up pays
    def each_copy(row, c, slot, act):
        def some(j, carry):
            for u in range(_COPIES_UNROLLED):
                act(copy(row, c, slot, j * _COPIES_UNROLLED + u))
            return carry

        jax.lax.fori_loop(0, K // _COPIES_UNROLLED, some, 0)

    def fetch(row, c, slot):
        each_copy(row, c, slot, lambda cp: cp.start())

    def land(row, c, slot):
        each_copy(row, c, slot, lambda cp: cp.wait())

    m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    # query row r = i * heads + h belongs to query i, at position qp + i
    q_idx = jax.lax.broadcasted_iota(jnp.int32, (R, 1), 0) // heads
    q_pos = qp + q_idx  # [R, 1]

    def update(rows, keys, mask):
        """One online-softmax step of the first ``rows`` query rows over
        ``keys`` [T', W] (values their leading V columns) under ``mask``
        [rows, T']."""
        s = jax.lax.dot_general(
            q_ref[0, :rows], keys, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        s = jnp.where(mask, s, _NEG_INF)
        m_prev, l_prev = m_ref[:rows, :1], l_ref[:rows, :1]
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        # a row that sees nothing yet has its max at the float32 min, and
        # exp(s - m) would be exp(0): zero what the mask hides
        p = jnp.where(mask, jnp.exp(s - m_next), 0.0)
        alpha = jnp.exp(m_prev - m_next)
        l_ref[:rows, :1] = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        m_ref[:rows, :1] = m_next
        acc_ref[:rows] = acc_ref[:rows] * alpha + jax.lax.dot_general(
            p.astype(keys.dtype), keys[:, :V], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
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

            land(b, c, slot)

            kvp = kvp_ref[0, pl.ds(c, 1), :]  # [1, KS]
            slot_idx = c * KS + jax.lax.broadcasted_iota(
                jnp.int32, (1, KS), 1
            )
            d = slot_idx - slot0
            d = jnp.where(d < 0, d + ring_len, d)
            seen = (kvp >= 0) & (d >= qlen)
            # hidden slots are given a position no query reaches
            mask = jnp.where(seen, kvp, _BIG) <= q_pos[:rows]  # [rows, KS]
            update(rows, buf_ref[slot], mask)
            return carry

        jax.lax.fori_loop(0, n, chunk, 0)

    if R > heads:
        # a row that decodes (one live query) scores its first query's
        # heads alone: the other CB - 1 queries are padding
        pl.when(qlen == 1)(lambda: walk(heads))
        pl.when(qlen != 1)(lambda: walk(R))
    else:
        walk(R)

    # fresh key j is seen by query i iff j <= i and j < q_len; key 0 by all
    j = jax.lax.broadcasted_iota(jnp.int32, (1, F), 1)
    update(R, lat_ref[0], (j <= q_idx) & ((j < qlen) | (j == 0)))

    o_ref[0] = (acc_ref[...] / l_ref[:, :1]).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("ring_len", "scale", "v_dim", "interpret")
)
def latent_paged_attention(
    q: jax.Array,  # [B, CB, H, W] — a CB-token query chunk a row
    pool: jax.Array,  # [L, N, bs, W] — the stale stacked latent pool
    lat: jax.Array,  # [B, CB, 1, W] — the chunk's own fresh latents
    q_pos: jax.Array,  # [B] or [B, 1] — FIRST query's absolute position
    q_len: jax.Array,  # [B] — live queries of the chunk (0..CB)
    kv_pos: jax.Array,  # [B, T] — pre-write LOGICAL slot positions
    block_tables: jax.Array,  # [B, MB] int32 (sentinel >= N = unmapped)
    n_blocks: jax.Array,  # [B] — table columns that hold any token
    slot0: jax.Array,  # [B] or [B, 1] — logical slot of the first query
    layer: jax.Array,  # int32 scalar — pool layer to read
    *,
    ring_len: int,
    scale: float,
    v_dim: int | None = None,  # leading columns of a row that are its value
    interpret: bool = False,
) -> jax.Array:
    """Attention of every row's queries over its cached latents and its
    fresh ones; returns ``[B, CB, H, v_dim]`` in q's dtype. The contract of
    ``ops.attention.ragged_paged_attention`` on ``(pool, pool)`` read at
    ``layer``, columns ``[:v_dim]`` of its output, on every live query
    (``i < q_len``). ``kv_pos`` may be narrower than the tables (a bucketed
    read): columns past ``T / bs`` are not walked."""
    B, CB, H, W = q.shape
    L, N, bs, _ = pool.shape
    V = W if v_dim is None else v_dim
    R, KS, F = CB * H, _CHUNK_SLOTS, _FRESH_ROWS
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
    fresh = jnp.pad(lat.reshape(B, CB, W), ((0, 0), (0, F - CB), (0, 0)))

    def row(shape):
        return pl.BlockSpec(
            (1,) + shape, lambda b, *_: (b, 0, 0), memory_space=pltpu.VMEM
        )

    out = pl.pallas_call(
        functools.partial(
            _kernel, scale=float(scale), heads=H, block_size=bs,
            ring_len=ring_len,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=8,
            grid=(B,),
            in_specs=[
                row((NC, KS)),
                row((R, W)),
                pl.BlockSpec(memory_space=pl.ANY),
                row((F, W)),
            ],
            out_specs=row((R, V)),
            scratch_shapes=[
                pltpu.VMEM((2, KS, W), pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((R, 128), jnp.float32),
                pltpu.VMEM((R, 128), jnp.float32),
                pltpu.VMEM((R, V), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, R, V), q.dtype),
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
        kvp, q.reshape(B, R, W), pool, fresh,
    )
    return out.reshape(B, CB, H, V)
