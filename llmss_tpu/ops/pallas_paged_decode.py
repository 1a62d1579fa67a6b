"""Pallas TPU decode attention over the paged block-pool KV cache.

The XLA paged decode path (``ops.attention.paged_decode_attention``) first
GATHERS each row's blocks into a contiguous logical view — a materialized
``[B, T, Hkv, D]`` copy of the live context every layer every step. This
kernel reads the pool ``[L, num_blocks, bs, Hkv, D]`` directly: the grid
walks ``(row, table_column)`` and each step's block index map resolves
``block_tables[row, col]`` from **scalar-prefetch** SMEM, so the block DMA
pulls exactly the row's own blocks from wherever they sit in the pool — the
gather copy disappears, and HBM traffic is the live context ("Ragged Paged
Attention", PAPERS.md).

Raggedness: rows own different numbers of blocks. ``n_blocks[b]`` (scalar
prefetch) marks row ``b``'s occupied prefix of the table; columns past it
clamp their index map to the row's last occupied block — Mosaic elides the
repeated DMA — and the body skips compute for them. Unmapped/sentinel table
entries are pre-clamped host-side to a valid block; their values are garbage
the position mask (−1 = empty) already rejects.

Semantics are identical to ``paged_decode_attention`` (the CPU/fallback
implementation and the parity oracle in tests/test_paged.py): stale-view
attention merged with the fresh current-token KV in one online softmax, the
pending logical slot masked out, position-arithmetic causal/window masking,
fp32 softmax island. Layout/blocking constraints follow pallas_decode.py:
a block carries all heads of one pool block (``(1, 1, bs, Hkv, D)``, a
contiguous DMA) and per-kv-head dots run as plain 2D ``dot_general``s.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_decode import (
    _KV_VMEM_BUDGET, fresh_key_score, kv_block_vmem_bytes,
)

_NEG_INF = float(jnp.finfo(jnp.float32).min)


def _kernel(
    layer_ref,  # [1] int32 scalar-prefetch — layer of the stacked pool
    qp_ref,  # [B] int32 scalar-prefetch — query's absolute position per row
    slot_ref,  # [B] int32 scalar-prefetch — LOGICAL slot the token takes
    nblk_ref,  # [B] int32 scalar-prefetch — occupied blocks per row
    bt_ref,  # [B*MB] int32 scalar-prefetch — flattened clamped block table
    kvp_ref,  # [1, 1, 1, bs] int32 — positions of this logical block's slots
    q_ref,  # [1, Hq, D]
    k_ref,  # [1, 1, bs, Hkv, D] — one pool block, all heads
    v_ref,  # [1, 1, bs, Hkv, D]
    kn_ref,  # [1, Hkv, D] — fresh current-token K
    vn_ref,  # [1, Hkv, D]
    o_ref,  # [1, Hq, D]
    m_ref,  # [Hq, 128] f32 scratch — running row max
    l_ref,  # [Hq, 128] f32 scratch — running row sum
    acc_ref,  # [Hq, D] f32 scratch — running weighted values
    *,
    scale: float,
    window: int | None,
    block_size: int,
    n_kv_heads: int,
):
    del layer_ref, bt_ref  # consumed by the index_maps, not the body
    b = pl.program_id(0)
    j = pl.program_id(1)
    n_j = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    qp = qp_ref[b]  # scalar
    slot = slot_ref[b]  # scalar (logical)
    kvp = kvp_ref[0, 0, 0, :]  # [bs]
    slot_idx = j * block_size + jax.lax.broadcasted_iota(
        jnp.int32, (1, block_size), 1
    )[0]

    mask = (kvp <= qp) & (kvp >= 0) & (slot_idx != slot)
    if window is not None:
        mask &= kvp > qp - window

    Hq, D = q_ref.shape[1], q_ref.shape[2]
    Hkv = n_kv_heads
    G = Hq // Hkv

    # Ragged skip: columns past the row's occupied prefix re-read the last
    # occupied block (index-map clamp) — never accumulate them twice.
    @pl.when((j < nblk_ref[b]) & jnp.any(mask))
    def _accumulate():
        # Static loop over kv heads (Mosaic's dot_general needs plain 2D
        # operands); each head's flash state lives in scratch rows
        # [h*G, (h+1)*G) — same scheme as pallas_decode.py.
        for h in range(Hkv):
            qh = q_ref[0, h * G:(h + 1) * G, :]  # [G, D]
            kh = k_ref[0, 0, :, h, :]  # [bs, D]
            vh = v_ref[0, 0, :, h, :]
            s = jax.lax.dot_general(
                qh, kh, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale  # [G, bs] f32
            s = jnp.where(mask[None, :], s, _NEG_INF)

            r = slice(h * G, (h + 1) * G)
            m_prev = m_ref[r, :1]  # [G, 1]
            l_prev = l_ref[r, :1]
            m_cur = jnp.max(s, axis=1, keepdims=True)
            m_next = jnp.maximum(m_prev, m_cur)
            p = jnp.exp(s - m_next)  # [G, bs] f32
            alpha = jnp.exp(m_prev - m_next)  # [G, 1]
            l_ref[r, :1] = alpha * l_prev + jnp.sum(
                p, axis=1, keepdims=True
            )
            m_ref[r, :1] = m_next
            acc_ref[r, :] = acc_ref[r, :] * alpha + jax.lax.dot_general(
                p.astype(vh.dtype), vh, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

    @pl.when(j == n_j - 1)
    def _merge_fresh_and_finalize():
        # The fresh token always attends itself (finite logit), so an empty
        # row degenerates cleanly to out = v_new — no l == 0 guard needed.
        for h in range(Hkv):
            r = slice(h * G, (h + 1) * G)
            qh = q_ref[0, r, :]  # [G, D]
            kn = kn_ref[0, h:h + 1, :]  # [1, D]
            vn = vn_ref[0, h:h + 1, :]
            s_new = fresh_key_score(qh, kn) * scale  # [G, 1]
            m_prev = m_ref[r, :1]
            m_next = jnp.maximum(m_prev, s_new)
            alpha = jnp.exp(m_prev - m_next)
            p_new = jnp.exp(s_new - m_next)  # [G, 1]
            l = l_ref[r, :1] * alpha + p_new
            acc = acc_ref[r, :] * alpha + p_new * vn.astype(jnp.float32)
            o_ref[0, r, :] = (acc / l).astype(o_ref.dtype)


def supports(block_size: int, Hq: int, Hkv: int, D: int, dtype) -> bool:
    """Shape envelope the kernel handles (else the caller stays on the XLA
    gather path). Per-block DMAs need sublane-aligned block_size and a
    lane-aligned head dim, and the pipelined K/V block buffers must fit
    the VMEM budget."""
    return (
        Hq % Hkv == 0
        and block_size % 8 == 0
        and D % 128 == 0
        and kv_block_vmem_bytes(block_size, Hkv, D, dtype)
        <= _KV_VMEM_BUDGET
    )


@functools.partial(
    jax.jit,
    static_argnames=("scale", "window", "interpret"),
)
def paged_decode_attention(
    q: jax.Array,  # [B, 1, Hq, D]
    k_pool: jax.Array,  # [L, N, bs, Hkv, D] — stale stacked block pool
    v_pool: jax.Array,
    k_new: jax.Array,  # [B, 1, Hkv, D]
    v_new: jax.Array,
    q_pos: jax.Array,  # [B, 1]
    kv_pos: jax.Array,  # [B, MB*bs] — pre-write LOGICAL slot positions
    block_tables: jax.Array,  # [B, MB] int32, pre-clamped OR sentinel
    n_blocks: jax.Array,  # [B] int32 — occupied table prefix per row
    slots: jax.Array,  # [B, 1] — logical slot the current token will take
    layer: jax.Array,  # int32 scalar or [1] — pool layer to read
    *,
    scale: float | None = None,
    window: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Single-token ragged decode attention over one layer of the pool.

    Returns [B, 1, Hq, D] in q's dtype. Same contract as
    ``ops.attention.paged_decode_attention`` on (k_pool[layer], ...).
    """
    B, S, Hq, D = q.shape
    assert S == 1, "paged decode kernel is single-token"
    L, N, bs, Hkv, _ = k_pool.shape
    MB = block_tables.shape[1]
    if scale is None:
        scale = 1.0 / (D**0.5)

    grid = (B, MB)
    bt_flat = jnp.minimum(block_tables, N - 1).astype(jnp.int32).reshape(-1)
    nblk = jnp.clip(n_blocks.astype(jnp.int32), 0, MB)

    def _col(j, nb, b):
        # Clamp ragged columns onto the row's last occupied block so the
        # repeated DMA is elided; max() guards empty rows (nb == 0).
        return jnp.maximum(jnp.minimum(j, nb[b] - 1), 0)

    out = pl.pallas_call(
        functools.partial(
            _kernel, scale=float(scale), window=window, block_size=bs,
            n_kv_heads=Hkv,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=grid,
            in_specs=[
                # [B, MB, 1, bs]: the unit axis makes the block's last two
                # dims equal the array's — a (1, bs) block of a [MB, bs]
                # plane is not a legal TPU tile.
                pl.BlockSpec(
                    (1, 1, 1, bs),
                    lambda b, j, lr, qp, sl, nb, bt: (
                        b, _col(j, nb, b), 0, 0
                    ),
                ),
                pl.BlockSpec(
                    (1, Hq, D), lambda b, j, *_: (b, 0, 0),
                    memory_space=pltpu.VMEM,
                ),
                pl.BlockSpec(
                    (1, 1, bs, Hkv, D),
                    lambda b, j, lr, qp, sl, nb, bt: (
                        lr[0], bt[b * MB + _col(j, nb, b)], 0, 0, 0
                    ),
                    memory_space=pltpu.VMEM,
                ),
                pl.BlockSpec(
                    (1, 1, bs, Hkv, D),
                    lambda b, j, lr, qp, sl, nb, bt: (
                        lr[0], bt[b * MB + _col(j, nb, b)], 0, 0, 0
                    ),
                    memory_space=pltpu.VMEM,
                ),
                pl.BlockSpec(
                    (1, Hkv, D), lambda b, j, *_: (b, 0, 0),
                    memory_space=pltpu.VMEM,
                ),
                pl.BlockSpec(
                    (1, Hkv, D), lambda b, j, *_: (b, 0, 0),
                    memory_space=pltpu.VMEM,
                ),
            ],
            out_specs=pl.BlockSpec(
                (1, Hq, D), lambda b, j, *_: (b, 0, 0),
                memory_space=pltpu.VMEM,
            ),
            scratch_shapes=[
                pltpu.VMEM((Hq, 128), jnp.float32),
                pltpu.VMEM((Hq, 128), jnp.float32),
                pltpu.VMEM((Hq, D), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hq, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(
        jnp.asarray(layer, jnp.int32).reshape(1),
        q_pos.astype(jnp.int32).reshape(B),
        slots.astype(jnp.int32).reshape(B),
        nblk,
        bt_flat,
        kv_pos.astype(jnp.int32).reshape(B, MB, 1, bs),
        q.reshape(B, Hq, D),
        k_pool, v_pool,
        k_new.reshape(B, Hkv, D),
        v_new.reshape(B, Hkv, D),
    )

    return out.reshape(B, 1, Hq, D)
