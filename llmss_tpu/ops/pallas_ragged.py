"""Ragged mixed prefill+decode Pallas attention over the paged block pool.

One dispatch, rows at arbitrary phases: each batch row carries a
``(block_table, context_len, query_len)`` triple where ``query_len`` is 1
for rows mid-decode and up to the chunk budget ``CB`` for rows mid-prefill
("Ragged Paged Attention", PAPERS.md). The grid walks ``(row,
table_column)`` exactly like ops/pallas_paged_decode.py — per-row extents
arrive via **scalar prefetch** and drive both the block index maps and the
ragged skip — and causal masking inside the query chunk happens in-kernel
via a per-query-row position bound.

This kernel is the strict generalization of the single-token paged decode
kernel: at ``CB == 1`` the scratch layout, mask booleans, and the exact op
sequence (dot → where → online-softmax update → fresh merge) reduce to
``pallas_paged_decode._kernel``, so an all-decode batch produces
bit-identical outputs (asserted in tests/test_ragged.py). Two deltas the
generalization forces:

* masks vary per query row (query ``i`` of a chunk sees cache positions
  ``<= q_pos + i``), so a block can be visible to some rows and not
  others; probabilities are zeroed under the mask to keep an all-masked
  row's running sum at 0 instead of ``exp(0)·bs``. For visible entries
  the clamp is a bitwise no-op (masked scores are the fp32 min, whose
  exp already underflows to +0 against any finite running max).
* the chunk's pending logical slots are the ``query_len``-long ring range
  starting at ``slot0`` — on ring wrap they hold tokens the chunk
  overwrites — which degenerates to the decode kernel's single
  ``slot_idx != slot`` exclusion at ``query_len == 1``.

Fresh (intra-chunk) keys merge at the last grid column with the ragged
triangular mask ``key j visible to query i iff j <= i and j < query_len``:
key 0 is visible to every query row including padding rows past
``query_len``, so every row's denominator is positive and no NaN can leak
from padding lanes (their outputs are finite garbage the head gather never
reads).

Unlike the decode kernel this one also accepts the int8 pool's dequant
scales: per-slot-per-head scale blocks ride the same index maps and fold
into scores/probabilities exactly like ``ops.attention``'s XLA folding, so
parity tests cover the quantized pool too.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_decode import fresh_key_score
from .pallas_paged_decode import supports  # noqa: F401  (same envelope)

_NEG_INF = float(jnp.finfo(jnp.float32).min)


def _kernel(
    layer_ref,  # [1] int32 scalar-prefetch — layer of the stacked pool
    qp_ref,  # [B] int32 scalar-prefetch — FIRST query's position per row
    qlen_ref,  # [B] int32 scalar-prefetch — live query rows (1..CB)
    slot_ref,  # [B] int32 scalar-prefetch — LOGICAL slot of the first query
    nblk_ref,  # [B] int32 scalar-prefetch — occupied blocks per row
    bt_ref,  # [B*MB] int32 scalar-prefetch — flattened clamped block table
    kvp_ref,  # [1, 1, 1, bs] int32 — positions of this logical block's slots
    q_ref,  # [1, CB, Hq, D]
    k_ref,  # [1, 1, bs, Hkv, D] — one pool block, all heads
    v_ref,  # [1, 1, bs, Hkv, D]
    *rest,  # (ks_ref, vs_ref)? kn_ref, vn_ref, o_ref, m_ref, l_ref, acc_ref
    scale: float,
    window: int | None,
    block_size: int,
    n_kv_heads: int,
    chunk: int,
    ring_len: int,
    quant: bool,
):
    del layer_ref, bt_ref  # consumed by the index_maps, not the body
    if quant:
        ks_ref, vs_ref, kn_ref, vn_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        kn_ref, vn_ref, o_ref, m_ref, l_ref, acc_ref = rest
        ks_ref = vs_ref = None
    b = pl.program_id(0)
    j = pl.program_id(1)
    n_j = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    qp = qp_ref[b]  # scalar — position of query row 0
    qlen = qlen_ref[b]  # scalar
    slot0 = slot_ref[b]  # scalar (logical)
    kvp = kvp_ref[0, 0, 0, :]  # [bs]
    slot_idx = j * block_size + jax.lax.broadcasted_iota(
        jnp.int32, (1, block_size), 1
    )[0]

    Hq, D = q_ref.shape[2], q_ref.shape[3]
    CB = chunk
    Hkv = n_kv_heads
    G = Hq // Hkv

    # The chunk's pending slots are the qlen-long ring range from slot0:
    # those cache entries are overwritten by this chunk's deferred write
    # (at qlen == 1 this is the decode kernel's slot_idx != slot).
    d = slot_idx - slot0
    d = jnp.where(d < 0, d + ring_len, d)
    pending = d < qlen  # [bs]

    # Per-query-row causal bound: flat scratch row i*G+g belongs to query
    # row i at absolute position qp + i.
    row_q = (
        jax.lax.broadcasted_iota(jnp.int32, (CB * G, block_size), 0) // G
    )
    qpi = qp + row_q  # [CB*G, bs]
    mask = (kvp[None, :] <= qpi) & (kvp[None, :] >= 0) & ~pending[None, :]
    if window is not None:
        mask &= kvp[None, :] > qpi - window

    # Ragged skip: columns past the row's occupied prefix re-read the last
    # occupied block (index-map clamp) — never accumulate them twice.
    @pl.when((j < nblk_ref[b]) & jnp.any(mask))
    def _accumulate():
        # Static loop over kv heads (Mosaic's dot_general needs plain 2D
        # operands); head h's flash state lives in scratch rows
        # [h*CB*G, (h+1)*CB*G) — query-major within a head so CB == 1
        # collapses onto the decode kernel's [h*G, (h+1)*G) scheme.
        for h in range(Hkv):
            qh = q_ref[0, :, h * G:(h + 1) * G, :].reshape(CB * G, D)
            kh = k_ref[0, 0, :, h, :]  # [bs, D]
            vh = v_ref[0, 0, :, h, :]
            s = jax.lax.dot_general(
                qh, kh, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale  # [CB*G, bs] f32
            if quant:
                s = s * ks_ref[0, 0, :, h][None, :]
            s = jnp.where(mask, s, _NEG_INF)

            r = slice(h * CB * G, (h + 1) * CB * G)
            m_prev = m_ref[r, :1]  # [CB*G, 1]
            l_prev = l_ref[r, :1]
            m_cur = jnp.max(s, axis=1, keepdims=True)
            m_next = jnp.maximum(m_prev, m_cur)
            p = jnp.exp(s - m_next)  # [CB*G, bs] f32
            # A query row can see nothing in this block while later rows
            # do (per-row causality): with its running max still at the
            # fp32 min, exp(s - m) would be exp(0) — zero it explicitly.
            p = jnp.where(mask, p, 0.0)
            alpha = jnp.exp(m_prev - m_next)  # [CB*G, 1]
            l_ref[r, :1] = alpha * l_prev + jnp.sum(
                p, axis=1, keepdims=True
            )
            m_ref[r, :1] = m_next
            if quant:
                p_v = p * vs_ref[0, 0, :, h][None, :]
                acc_ref[r, :] = acc_ref[r, :] * alpha + jax.lax.dot_general(
                    p_v, vh.astype(jnp.float32), (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
            else:
                acc_ref[r, :] = acc_ref[r, :] * alpha + jax.lax.dot_general(
                    p.astype(vh.dtype), vh, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )

    @pl.when(j == n_j - 1)
    def _merge_fresh_and_finalize():
        # Intra-chunk keys, one online-softmax update per key: key jj is
        # visible to query row i iff jj <= i and jj < qlen. Key 0 is
        # visible to EVERY row (qlen >= 1), padding rows included, so all
        # denominators are positive — no l == 0 guard needed.
        row_q1 = (
            jax.lax.broadcasted_iota(jnp.int32, (CB * G, 1), 0) // G
        )
        qlen_b = qlen  # loop-invariant scalar
        for h in range(Hkv):
            r = slice(h * CB * G, (h + 1) * CB * G)
            qh = q_ref[0, :, h * G:(h + 1) * G, :].reshape(CB * G, D)
            for jj in range(CB):
                kn = kn_ref[0, jj, h:h + 1, :]  # [1, D]
                vn = vn_ref[0, jj, h:h + 1, :]
                s_new = fresh_key_score(qh, kn) * scale  # [CB*G, 1]
                vis = (jj <= row_q1) & (jj < qlen_b)
                if window is not None:
                    vis &= (row_q1 - jj) < window
                s_new = jnp.where(vis, s_new, _NEG_INF)
                m_prev = m_ref[r, :1]
                m_next = jnp.maximum(m_prev, s_new)
                alpha = jnp.exp(m_prev - m_next)
                p_new = jnp.exp(s_new - m_next)  # [CB*G, 1]
                p_new = jnp.where(vis, p_new, 0.0)
                l_ref[r, :1] = l_ref[r, :1] * alpha + p_new
                m_ref[r, :1] = m_next
                acc_ref[r, :] = (
                    acc_ref[r, :] * alpha + p_new * vn.astype(jnp.float32)
                )
            l = l_ref[r, :1]
            acc = acc_ref[r, :]
            o_ref[0, :, h * G:(h + 1) * G, :] = (
                (acc / l).reshape(CB, G, D).astype(o_ref.dtype)
            )


@functools.partial(
    jax.jit,
    static_argnames=("scale", "window", "interpret"),
)
def ragged_paged_attention(
    q: jax.Array,  # [B, CB, Hq, D] — CB-token query chunk per row
    k_pool: jax.Array,  # [L, N, bs, Hkv, D] — stale stacked block pool
    v_pool: jax.Array,
    k_new: jax.Array,  # [B, CB, Hkv, D] — the chunk's own fresh KV
    v_new: jax.Array,
    q_pos: jax.Array,  # [B] or [B, 1] — FIRST query's absolute position
    q_len: jax.Array,  # [B] int32 — live query rows per chunk (1..CB)
    kv_pos: jax.Array,  # [B, MB*bs] — pre-write LOGICAL slot positions
    block_tables: jax.Array,  # [B, MB] int32, pre-clamped OR sentinel
    n_blocks: jax.Array,  # [B] int32 — occupied table prefix per row
    slot0: jax.Array,  # [B] or [B, 1] — logical slot of the first query
    layer: jax.Array,  # int32 scalar or [1] — pool layer to read
    *,
    scale: float | None = None,
    window: int | None = None,
    k_scale_pool: jax.Array | None = None,  # [L, N, bs, Hkv] f32 iff int8
    v_scale_pool: jax.Array | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Ragged chunked attention over one layer of the pool.

    Returns [B, CB, Hq, D] in q's dtype. Same contract as
    ``ops.attention.ragged_paged_attention`` on (k_pool[layer], ...) — the
    XLA gather oracle this kernel is parity-tested against.
    """
    B, CB, Hq, D = q.shape
    L, N, bs, Hkv, _ = k_pool.shape
    MB = block_tables.shape[1]
    if scale is None:
        scale = 1.0 / (D**0.5)
    quant = k_scale_pool is not None

    grid = (B, MB)
    bt_flat = jnp.minimum(block_tables, N - 1).astype(jnp.int32).reshape(-1)
    nblk = jnp.clip(n_blocks.astype(jnp.int32), 0, MB)

    def _col(j, nb, b):
        # Clamp ragged columns onto the row's last occupied block so the
        # repeated DMA is elided; max() guards empty rows (nb == 0).
        return jnp.maximum(jnp.minimum(j, nb[b] - 1), 0)

    def _pool_spec():
        return pl.BlockSpec(
            (1, 1, bs, Hkv, D),
            lambda b, j, lr, qp, ql, sl, nb, bt: (
                lr[0], bt[b * MB + _col(j, nb, b)], 0, 0, 0
            ),
            memory_space=pltpu.VMEM,
        )

    def _scale_spec():
        return pl.BlockSpec(
            (1, 1, bs, Hkv),
            lambda b, j, lr, qp, ql, sl, nb, bt: (
                lr[0], bt[b * MB + _col(j, nb, b)], 0, 0
            ),
            memory_space=pltpu.VMEM,
        )

    in_specs = [
        # [B, MB, 1, bs] — see pallas_paged_decode.py for the unit axis.
        pl.BlockSpec(
            (1, 1, 1, bs),
            lambda b, j, lr, qp, ql, sl, nb, bt: (b, _col(j, nb, b), 0, 0),
        ),
        pl.BlockSpec(
            (1, CB, Hq, D), lambda b, j, *_: (b, 0, 0, 0),
            memory_space=pltpu.VMEM,
        ),
        _pool_spec(),
        _pool_spec(),
    ]
    operands = [
        kv_pos.astype(jnp.int32).reshape(B, MB, 1, bs),
        q.reshape(B, CB, Hq, D),
        k_pool, v_pool,
    ]
    if quant:
        in_specs += [_scale_spec(), _scale_spec()]
        operands += [k_scale_pool, v_scale_pool]
    in_specs += [
        pl.BlockSpec(
            (1, CB, Hkv, D), lambda b, j, *_: (b, 0, 0, 0),
            memory_space=pltpu.VMEM,
        ),
        pl.BlockSpec(
            (1, CB, Hkv, D), lambda b, j, *_: (b, 0, 0, 0),
            memory_space=pltpu.VMEM,
        ),
    ]
    operands += [
        k_new.reshape(B, CB, Hkv, D),
        v_new.reshape(B, CB, Hkv, D),
    ]

    out = pl.pallas_call(
        functools.partial(
            _kernel, scale=float(scale), window=window, block_size=bs,
            n_kv_heads=Hkv, chunk=CB, ring_len=MB * bs, quant=quant,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec(
                (1, CB, Hq, D), lambda b, j, *_: (b, 0, 0, 0),
                memory_space=pltpu.VMEM,
            ),
            scratch_shapes=[
                pltpu.VMEM((CB * Hq, 128), jnp.float32),
                pltpu.VMEM((CB * Hq, 128), jnp.float32),
                pltpu.VMEM((CB * Hq, D), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, CB, Hq, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(
        jnp.asarray(layer, jnp.int32).reshape(1),
        q_pos.astype(jnp.int32).reshape(B),
        q_len.astype(jnp.int32).reshape(B),
        slot0.astype(jnp.int32).reshape(B),
        nblk,
        bt_flat,
        *operands,
    )

    return out
