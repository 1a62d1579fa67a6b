"""Pallas TPU decode attention over the layer-stacked KV cache.

Why this kernel exists: the decode step scans blocks over layer-stacked
parameters and cache. XLA aliases the *weight* slices into their dots, but
it materializes each layer's KV slice — a ``dynamic_slice`` copying the
full ``[B, T, Hkv, D]`` layer (33 MB at bench scale) every layer every
step, measured at ~0.5 ms of the ~4.3 ms step (round 5,
PROFILE.md@e57f952). This kernel takes the whole stacked cache
``[L, B, T, Hkv, D]`` plus the layer index as a **scalar-prefetch**
argument, so the block DMAs read the layer's KV directly from the stacked
buffer in HBM — the copy disappears.

Semantics are identical to ``ops.attention.fresh_kv_decode_attention``
(the XLA path, kept as the CPU/fallback implementation and the parity
oracle in tests):

- attention over the *stale* cache (current token not yet written), with
  the fresh current-token KV merged into the same online softmax;
- the slot the current token will occupy is masked out of the cache read
  (on ring wrap this drops the token being overwritten, matching
  write-then-attend order);
- position-arithmetic masking (causal, -1 = empty slot, optional sliding
  window — the reference's KV trim, ``generate.py:132-142``, as slot
  arithmetic);
- fp32 softmax island (``gptj_modeling.py:140-143``): scores and m/l/acc
  state fp32; the P·V matmul runs in value dtype with fp32 accumulation.

Blocking: the Mosaic lowering requires a block's last two dims to tile the
array's last two dims, so per-head KV blocks of ``[L, B, T, Hkv, D]`` are
not expressible — instead each block carries **all heads** of a sequence
chunk (``(1, 1, bk, Hkv, D)``, a contiguous DMA) and the per-kv-head dots
batch over the head dim inside the kernel. Grid ``(B, T/bk)`` with the KV
axis innermost/sequential so VMEM accumulators carry across chunks; the
fresh-KV term merges in the last chunk's epilogue.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = float(jnp.finfo(jnp.float32).min)


def _kernel(
    layer_ref,  # [1] int32 scalar-prefetch — layer of the stacked cache
    qp_ref,  # [B] int32 scalar-prefetch — query's absolute position per row
    slot_ref,  # [B] int32 scalar-prefetch — ring slot the token will take
    kvp_ref,  # [1, 1, bk] int32 — absolute position per KV slot (-1 empty)
    q_ref,  # [1, Hq, D]
    k_ref,  # [1, 1, bk, Hkv, D] — chunk of the stacked cache, all heads
    v_ref,  # [1, 1, bk, Hkv, D]
    kn_ref,  # [1, Hkv, D] — fresh current-token K
    vn_ref,  # [1, Hkv, D]
    o_ref,  # [1, Hq, D]
    m_ref,  # [Hq, 128] f32 scratch — running row max
    l_ref,  # [Hq, 128] f32 scratch — running row sum
    acc_ref,  # [Hq, D] f32 scratch — running weighted values
    *,
    scale: float,
    window: int | None,
    block_k: int,
    n_kv_heads: int,
):
    del layer_ref  # consumed by the index_maps, not the body
    b = pl.program_id(0)
    j = pl.program_id(1)
    n_j = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    qp = qp_ref[b]  # scalar
    slot = slot_ref[b]  # scalar
    kvp = kvp_ref[0, 0, :]  # [bk]
    slot_idx = j * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (1, block_k), 1
    )[0]

    mask = (kvp <= qp) & (kvp >= 0) & (slot_idx != slot)
    if window is not None:
        mask &= kvp > qp - window

    Hq, D = q_ref.shape[1], q_ref.shape[2]
    Hkv = n_kv_heads
    G = Hq // Hkv

    @pl.when(jnp.any(mask))
    def _accumulate():
        # Static loop over kv heads (Mosaic's dot_general needs plain 2D
        # operands; a batched form with the head dim mid-operand is not
        # lowerable). Each head's flash state lives in its own scratch row
        # range [h*G, (h+1)*G).
        for h in range(Hkv):
            qh = q_ref[0, h * G:(h + 1) * G, :]  # [G, D]
            kh = k_ref[0, 0, :, h, :]  # [bk, D]
            vh = v_ref[0, 0, :, h, :]
            s = jax.lax.dot_general(
                qh, kh, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale  # [G, bk] f32
            s = jnp.where(mask[None, :], s, _NEG_INF)

            r = slice(h * G, (h + 1) * G)
            m_prev = m_ref[r, :1]  # [G, 1]
            l_prev = l_ref[r, :1]
            m_cur = jnp.max(s, axis=1, keepdims=True)
            m_next = jnp.maximum(m_prev, m_cur)
            p = jnp.exp(s - m_next)  # [G, bk] f32
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
        # cache degenerates cleanly to out = v_new — no l == 0 guard needed.
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


def fresh_key_score(qh: jax.Array, kn: jax.Array) -> jax.Array:
    """``qh [R, D] · kn [1, D] -> [R, 1]`` in fp32 on the VPU. Spelled as a
    ``dot_general`` with one output column, Mosaic lowers it to a
    broadcast-multiply whose bf16 operand reaches an f32 ``vector.broadcast``
    and fails verification (v5e, JAX 0.9.0); the explicit upcast is the
    same arithmetic and lowers in every dtype."""
    return jnp.sum(
        qh.astype(jnp.float32) * kn.astype(jnp.float32), axis=1,
        keepdims=True,
    )


# VMEM the pipelined K and V chunk buffers may take together. The
# compiler's scoped default on v5e is 16 MiB for the whole kernel; half of
# it leaves room for q, the output, the accumulators and the score
# temporaries.
_KV_VMEM_BUDGET = 8 << 20


def kv_block_vmem_bytes(rows: int, Hkv: int, D: int, dtype) -> int:
    """VMEM held by the K and V buffers of one ``(rows, Hkv, D)`` block
    pair: two arrays, double-buffered by the pipeline, with the head axis
    padded to the dtype's sublane tile (8 rows of 32 bits: 8 for f32, 16
    for bf16, 32 for int8) and D to the 128 lanes — so an MQA block
    (Hkv = 1) occupies a full tile per slot."""
    itemsize = jnp.dtype(dtype).itemsize
    sublane = 8 * (4 // itemsize)
    hkv_pad = -(-Hkv // sublane) * sublane
    d_pad = -(-D // 128) * 128
    return 4 * rows * hkv_pad * d_pad * itemsize


def _pick_block_k(
    T: int, Hkv: int, D: int, dtype, block_k: int = 512
) -> int | None:
    """Largest legal KV chunk: divides T, is lane-aligned (%128) unless it
    covers T outright, and its K/V buffers fit ``_KV_VMEM_BUDGET``."""
    def fits(bk):
        return kv_block_vmem_bytes(bk, Hkv, D, dtype) <= _KV_VMEM_BUDGET

    if T <= block_k and fits(T):
        return T
    bk = block_k
    while bk >= 128:
        if T % bk == 0 and fits(bk):
            return bk
        bk //= 2
    return None


def supports(T: int, Hq: int, Hkv: int, D: int, dtype) -> bool:
    """Shape envelope the kernel handles (else the caller stays on the XLA
    ``fresh_kv_decode_attention`` path)."""
    return (
        Hq % Hkv == 0
        and T % 8 == 0
        and D % 128 == 0
        and _pick_block_k(T, Hkv, D, dtype) is not None
    )


@functools.partial(
    jax.jit,
    static_argnames=("scale", "window", "block_k", "interpret"),
)
def decode_attention(
    q: jax.Array,  # [B, 1, Hq, D]
    k_cache: jax.Array,  # [L, B, T, Hkv, D] — stale stacked cache
    v_cache: jax.Array,
    k_new: jax.Array,  # [B, 1, Hkv, D]
    v_new: jax.Array,
    q_pos: jax.Array,  # [B, 1]
    kv_pos: jax.Array,  # [B, T] — pre-write slot positions
    slots: jax.Array,  # [B, 1] — slot the current token will occupy
    layer: jax.Array,  # int32 scalar or [1] — layer to read
    *,
    scale: float | None = None,
    window: int | None = None,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Single-token decode attention reading one layer of the stacked cache.

    Returns [B, 1, Hq, D] in q's dtype. Same contract as
    ``fresh_kv_decode_attention`` with (k_cache[layer], v_cache[layer]).
    """
    B, S, Hq, D = q.shape
    assert S == 1, "decode kernel is single-token"
    L, _, T, Hkv, _ = k_cache.shape
    if scale is None:
        scale = 1.0 / (D**0.5)
    bk = _pick_block_k(T, Hkv, D, k_cache.dtype, block_k)
    assert bk is not None, f"unsupported T={T} (see supports())"

    grid = (B, T // bk)

    out = pl.pallas_call(
        functools.partial(
            _kernel, scale=float(scale), window=window, block_k=bk,
            n_kv_heads=Hkv,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, bk), lambda b, j, *_: (b, 0, j)),
                pl.BlockSpec(
                    (1, Hq, D), lambda b, j, *_: (b, 0, 0),
                    memory_space=pltpu.VMEM,
                ),
                pl.BlockSpec(
                    (1, 1, bk, Hkv, D),
                    lambda b, j, lr, qp, sl: (lr[0], b, j, 0, 0),
                    memory_space=pltpu.VMEM,
                ),
                pl.BlockSpec(
                    (1, 1, bk, Hkv, D),
                    lambda b, j, lr, qp, sl: (lr[0], b, j, 0, 0),
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
        kv_pos.astype(jnp.int32)[:, None, :],
        q.reshape(B, Hq, D),
        k_cache, v_cache,
        k_new.reshape(B, Hkv, D),
        v_new.reshape(B, Hkv, D),
    )

    return out.reshape(B, 1, Hq, D)
