"""Pallas TPU flash attention with position-based masking.

The reference's attention materializes full ``[B, H, S, T]`` score matrices in
fp32 (``gptj_modeling.py:128-169``; ``gpt_bigcode_modeling.py:170-246`` with a
``torch.jit.script`` fused softmax, ``:49-72``). On TPU the XLA einsum chain in
``ops/attention.py`` already fuses well at short context, but its HBM traffic
is O(S·T) for the score tensor. This kernel is the long-context hot path:
blockwise flash attention (online softmax) that never materializes scores,
streaming K/V blocks through VMEM with fp32 accumulators.

Design points:

- **Masking is position arithmetic, not a mask tensor.** The kernel takes the
  same ``q_positions``/``kv_positions`` arrays that drive
  ``ops.attention.make_causal_mask`` — so ring-buffer cache semantics
  (slot order ≠ position order after wrap) and padding (position −1) are
  exact, and no ``[B, S, T]`` bool mask ever hits HBM.
- **Causal block-skip.** A KV block whose every slot is invalid or strictly
  future relative to the query block contributes nothing; the kernel skips its
  matmuls entirely (~2× prefill speedup at long S).
- **GQA/MQA native**: grid is over query heads; the KV block index maps
  ``h → h // group_size`` (MQA = all query heads share head 0, the layout the
  reference engineers by hand in ``gpt_bigcode_modeling.py:150-155``).
- **fp32 softmax island** preserved (reference numerics contract): scores and
  the m/l/acc state are fp32 regardless of input dtype; the P·V matmul runs
  in the value dtype on the MXU with fp32 accumulation.

Grid: ``(B, Hq, S/bq, T/bk)`` with the KV-block axis innermost and
sequential ("arbitrary") so the VMEM scratch accumulators carry across KV
blocks; outputs are written once, on the last KV block.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = float(jnp.finfo(jnp.float32).min)


def _kernel(
    qp_ref,  # [1, 1, bq] int32 — absolute position of each query row
    kvp_ref,  # [1, 1, bk] int32 — absolute position of each KV slot (-1 empty)
    q_ref,  # [1, 1, bq, D]
    k_ref,  # [1, 1, bk, D]
    v_ref,  # [1, 1, bk, D]
    o_ref,  # [1, 1, bq, D]
    m_ref,  # [bq, 128] f32 scratch — running row max
    l_ref,  # [bq, 128] f32 scratch — running row sum
    acc_ref,  # [bq, D] f32 scratch — running weighted values
    *,
    scale: float,
    window: int | None,
):
    j = pl.program_id(3)
    n_j = pl.num_programs(3)

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    qp = qp_ref[0, 0, :]  # [bq]
    kvp = kvp_ref[0, 0, :]  # [bk]

    # Block skip: every contribution is masked iff no slot is both valid and
    # causally visible to the *latest* query in the block (and, with a
    # sliding window, not entirely behind the *earliest* query's window).
    live = (kvp >= 0) & (kvp <= jnp.max(qp))
    if window is not None:
        live &= kvp > jnp.min(qp) - window
    live = jnp.any(live)

    @pl.when(live)
    def _accumulate():
        q = q_ref[0, 0]  # [bq, D]
        k = k_ref[0, 0]  # [bk, D]
        v = v_ref[0, 0]  # [bk, D]

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [bq, bk] f32
        mask = (kvp[None, :] <= qp[:, None]) & (kvp[None, :] >= 0)
        if window is not None:
            mask &= kvp[None, :] > qp[:, None] - window
        s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_ref[:, :1]  # [bq, 1]
        l_prev = l_ref[:, :1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_next = jnp.maximum(m_prev, m_cur)
        # Masked lanes hold finite _NEG_INF: exp underflows to 0 against
        # any real max. A row whose every lane is masked in a *live* block
        # has m_next == _NEG_INF, so p = exp(0) = 1 and the row degrades to
        # the uniform average — same as the XLA path's finite-min masking
        # (and ring_attention.py's identical accumulation).
        p = jnp.exp(s - m_next)  # [bq, bk] f32
        alpha = jnp.exp(m_prev - m_next)  # [bq, 1]
        l_ref[:, :1] = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        m_ref[:, :1] = m_next
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(j == n_j - 1)
    def _finalize():
        l = l_ref[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[:] / l_safe).astype(o_ref.dtype)


def supports(S: int, T: int, Hq: int, Hkv: int, *, min_q: int = 16) -> bool:
    """Whether the kernel is worth dispatching to (else caller uses the XLA
    einsum path). Decode steps (S=1) stay on XLA: they are HBM-bound gathers
    with no score tensor to avoid. Odd T would degrade the KV block size
    toward 1 (a T-step sequential grid) — require lane-friendly lengths."""
    return S >= min_q and S % 8 == 0 and T % 8 == 0 and Hq % Hkv == 0


@functools.partial(
    jax.jit,
    static_argnames=("scale", "block_q", "block_k", "window", "interpret"),
)
def flash_attention(
    q: jax.Array,  # [B, S, Hq, D]
    k: jax.Array,  # [B, T, Hkv, D]
    v: jax.Array,  # [B, T, Hkv, D]
    q_positions: jax.Array,  # [B, S] int32
    kv_positions: jax.Array,  # [B, T] int32, -1 = empty slot
    *,
    scale: float | None = None,
    block_q: int = 1024,
    block_k: int = 512,
    window: int | None = None,  # sliding-window width (None = full causal)
    interpret: bool = False,
) -> jax.Array:
    """Blockwise flash attention; same contract as ``ops.attention.attention``
    with the mask expressed as positions. Returns [B, S, Hq, D] in q's dtype."""
    B, S, Hq, D = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    assert Hq % Hkv == 0, (Hq, Hkv)
    G = Hq // Hkv
    if scale is None:
        scale = 1.0 / (D**0.5)
    # Large query blocks are the bandwidth lever: each query block streams
    # the whole KV, so KV traffic scales with S/bq. VMEM cost per step is
    # O(bq·bk) fp32 scores + O(bq·D) accumulators — a few MB at these sizes.
    bq = min(block_q, S)
    while S % bq:
        bq //= 2
    bk = min(block_k, T)
    while T % bk:
        bk //= 2

    # [B, H, S, D] layout: S rides the sublane dim, D the 128-lane dim.
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)

    grid = (B, Hq, S // bq, T // bk)

    out = pl.pallas_call(
        functools.partial(_kernel, scale=float(scale), window=window),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq), lambda b, h, i, j: (b, 0, i)),
            pl.BlockSpec((1, 1, bk), lambda b, h, i, j: (b, 0, j)),
            pl.BlockSpec(
                (1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, 1, bk, D), lambda b, h, i, j: (b, h // G, j, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, 1, bk, D), lambda b, h, i, j: (b, h // G, j, 0),
                memory_space=pltpu.VMEM,
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0),
            memory_space=pltpu.VMEM,
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hq, S, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
        ),
        interpret=interpret,
    )(q_positions.astype(jnp.int32)[:, None, :],
      kv_positions.astype(jnp.int32)[:, None, :],
      qt, kt, vt)

    return out.transpose(0, 2, 1, 3)
