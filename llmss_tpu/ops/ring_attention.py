"""Ring attention: sequence/context parallelism over the ``sp`` mesh axis.

The reference has **no** long-context story — max context is
``config.n_positions`` and overflow truncates (``generate.py:132-142``,
SURVEY.md §5 "Long-context: absent"). Here sequence parallelism is
first-class: the KV cache's sequence dim is sharded over ``sp``, so context
length scales with the number of chips instead of being bounded by one HBM.

Two collectives patterns, both with fp32 online-softmax accumulation (the
same numerics island as ``ops.attention`` / ``ops.pallas_attention``):

- **Ring prefill** (``ring_attention``): queries are sequence-sharded too.
  Each device computes blockwise attention against its local KV chunk, then
  rotates the KV chunk (+ its position metadata) one hop around the ring with
  ``lax.ppermute``, ``sp`` times. Compute overlaps the permute (the loop is
  unrolled; XLA schedules the collective-permute concurrently with the next
  chunk's matmuls). HBM and VMEM hold only ``1/sp`` of K/V at any time.
- **Distributed decode** (``lse_merge_attention``): single-token queries are
  replicated over ``sp``; each device attends its local KV chunk and the
  partial results merge with a log-sum-exp-weighted ``psum`` — one collective
  per step, no rotation (flash-decoding's split-KV reduction, over chips
  instead of cores).

Both run inside ``shard_map`` (entered by ``ops.attention.dispatch_attention``
when the mesh's ``sp`` axis is >1) and use the same position-based masking as
the rest of the stack, so ring-buffer slot wrap and padding behave
identically with and without sequence parallelism.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

_NEG_INF = float(jnp.finfo(jnp.float32).min)


def _online_block(m, l, acc, q, k, v, q_pos, kv_pos, scale, window=None,
                  exclude=None):
    """One online-softmax accumulation step of grouped-query attention.

    State shapes: m/l [B, Hkv, G, S], acc [B, Hkv, G, S, D] (fp32).
    q [B, S, Hq, D]; k/v [B, C, Hkv, D] — the current KV chunk.
    ``exclude`` [B, C] bool marks chunk slots to mask out regardless of
    position (the deferred-write decode path excludes the slot the
    incoming token will overwrite).
    """
    B, S, Hq, D = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    qf = q.astype(jnp.float32).reshape(B, S, Hkv, G, D) * scale
    s = jnp.einsum("bskgd,btkd->bkgst", qf, k.astype(jnp.float32))
    mask = (kv_pos[:, None, :] <= q_pos[:, :, None]) & (
        kv_pos[:, None, :] >= 0
    )  # [B, S, C]
    if window is not None:
        mask &= kv_pos[:, None, :] > q_pos[:, :, None] - window
    if exclude is not None:
        mask &= ~exclude[:, None, :]
    s = jnp.where(mask[:, None, None], s, _NEG_INF)

    m_cur = jnp.max(s, axis=-1)
    m_new = jnp.maximum(m, m_cur)
    # Masked lanes hold finite _NEG_INF: exp underflows to 0 against any
    # real max; a row with no visible KV anywhere degrades to the uniform
    # average, matching the XLA path's finite-min masking.
    p = jnp.exp(s - m_new[..., None])
    alpha = jnp.exp(m - m_new)
    l_new = alpha * l + jnp.sum(p, axis=-1)
    acc_new = acc * alpha[..., None] + jnp.einsum(
        "bkgst,btkd->bkgsd", p, v.astype(jnp.float32)
    )
    return m_new, l_new, acc_new


def _finish(m, l, acc, q):
    B, S, Hq, D = q.shape
    l_safe = jnp.where(l == 0.0, 1.0, l)
    out = acc / l_safe[..., None]  # [B, Hkv, G, S, D]
    return (
        out.transpose(0, 3, 1, 2, 4).reshape(B, S, Hq, D).astype(q.dtype)
    )


def _init_state(q, Hkv):
    B, S, Hq, D = q.shape
    G = Hq // Hkv
    shape = (B, Hkv, G, S)
    return (
        jnp.full(shape, _NEG_INF, jnp.float32),
        jnp.zeros(shape, jnp.float32),
        jnp.zeros((*shape, D), jnp.float32),
    )


def ring_attention(
    q: jax.Array,  # [B, S_local, Hq, D] — sequence-sharded queries
    k: jax.Array,  # [B, C, Hkv, D] — local KV chunk
    v: jax.Array,
    q_pos: jax.Array,  # [B, S_local]
    kv_pos: jax.Array,  # [B, C]; -1 = empty slot
    *,
    axis_name: str,
    scale: float | None = None,
    window: int | None = None,
) -> jax.Array:
    """Sequence-parallel prefill attention. Must run inside ``shard_map``
    with ``axis_name`` mapped; returns the local [B, S_local, Hq, D] shard."""
    D = q.shape[-1]
    if scale is None:
        scale = 1.0 / (D**0.5)
    # lax.axis_size is JAX 0.5+; psum of a literal 1 is the pre-0.5 idiom
    # and constant-folds to the same static int.
    sp = (
        lax.axis_size(axis_name) if hasattr(lax, "axis_size")
        else lax.psum(1, axis_name)
    )
    m, l, acc = _init_state(q, k.shape[2])
    perm = [(i, (i + 1) % sp) for i in range(sp)]
    for step in range(sp):
        m, l, acc = _online_block(
            m, l, acc, q, k, v, q_pos, kv_pos, scale, window
        )
        if step < sp - 1:
            # Rotate the KV chunk one hop; position metadata travels with it
            # so masking stays exact for any slot/position layout.
            k = lax.ppermute(k, axis_name, perm)
            v = lax.ppermute(v, axis_name, perm)
            kv_pos = lax.ppermute(kv_pos, axis_name, perm)
    return _finish(m, l, acc, q)


def lse_merge_attention(
    q: jax.Array,  # [B, S, Hq, D] — replicated over sp (decode: S=1)
    k: jax.Array,  # [B, C, Hkv, D] — local KV chunk
    v: jax.Array,
    q_pos: jax.Array,  # [B, S] — replicated
    kv_pos: jax.Array,  # [B, C]
    *,
    axis_name: str,
    scale: float | None = None,
    window: int | None = None,
) -> jax.Array:
    """Split-KV decode attention over the ``sp`` axis: local partial softmax
    + one log-sum-exp-weighted psum merge. Returns replicated output."""
    D = q.shape[-1]
    if scale is None:
        scale = 1.0 / (D**0.5)
    m0, l0, acc0 = _init_state(q, k.shape[2])
    m, l, acc = _online_block(
        m0, l0, acc0, q, k, v, q_pos, kv_pos, scale, window
    )
    m_g = lax.pmax(m, axis_name)
    w = jnp.exp(m - m_g)  # all-masked chunk: exp(min - real) == 0, drops out
    l_g = lax.psum(l * w, axis_name)
    acc_g = lax.psum(acc * w[..., None], axis_name)
    return _finish(m_g, l_g, acc_g, q)


def lse_merge_fresh_kv_attention(
    q: jax.Array,  # [B, 1, Hq, D] — replicated over sp
    k: jax.Array,  # [B, C, Hkv, D] — local *stale* KV chunk
    v: jax.Array,
    q_pos: jax.Array,  # [B, 1] — replicated
    kv_pos: jax.Array,  # [B, C] — local chunk positions, pre-write
    k_new: jax.Array,  # [B, 1, Hkv, D] — current token's KV, replicated
    v_new: jax.Array,
    slots: jax.Array,  # [B, 1] — *global* ring slot the token will occupy
    *,
    axis_name: str,
    scale: float | None = None,
    window: int | None = None,
) -> jax.Array:
    """Split-KV decode attention over a **stale** sp-sharded cache with the
    fresh current-token KV merged into the same softmax — the sp>1 analogue
    of ``ops.attention.fresh_kv_decode_attention``, enabling the decode
    loop's deferred-write scatter on sequence-parallel meshes too.

    Each shard masks out the pending slot if it owns it (matching the
    write-then-attend order of the in-scan path on ring wrap), partials
    merge with the LSE-weighted psum, then every shard merges the identical
    replicated fresh-KV term — outputs stay replicated with no extra
    collective. Must run inside ``shard_map`` with ``axis_name`` mapped.
    """
    B, S, Hq, D = q.shape
    C = k.shape[1]
    Hkv = k.shape[2]
    G = Hq // Hkv
    if scale is None:
        scale = 1.0 / (D**0.5)

    start = lax.axis_index(axis_name) * C
    slot_idx = start + jnp.arange(C, dtype=jnp.int32)
    exclude = slot_idx[None, :] == slots  # [B, C] (slots [B,1] broadcasts)

    m0, l0, acc0 = _init_state(q, Hkv)
    m, l, acc = _online_block(
        m0, l0, acc0, q, k, v, q_pos, kv_pos, scale, window, exclude=exclude
    )
    m_g = lax.pmax(m, axis_name)
    w = jnp.exp(m - m_g)
    l_g = lax.psum(l * w, axis_name)
    acc_g = lax.psum(acc * w[..., None], axis_name)

    # Fresh-token term (same math as fresh_kv_decode_attention's s_s):
    # the token always attends itself, so an empty cache degenerates to
    # out = v_new with no l == 0 guard.
    qf = q.astype(jnp.float32).reshape(B, S, Hkv, G, D) * scale
    s_new = jnp.einsum(
        "bskgd,bskd->bkgs", qf, k_new.astype(jnp.float32)
    )  # [B, Hkv, G, S]
    m_f = jnp.maximum(m_g, s_new)
    alpha = jnp.exp(m_g - m_f)
    p_new = jnp.exp(s_new - m_f)
    l_f = l_g * alpha + p_new
    acc_f = acc_g * alpha[..., None] + p_new[..., None] * v_new.astype(
        jnp.float32
    ).transpose(0, 2, 1, 3)[:, :, None]
    return _finish(m_f, l_f, acc_f, q)
