"""Attention core: MHA / GQA / MQA with fp32 softmax islands.

Replaces the reference's per-model attention math (``gptj_modeling.py:128-169``
fp32 masked softmax; ``gpt_bigcode_modeling.py:49-72`` jit-scripted fused
upcast softmax + ``:170-246`` MQA baddbmm path). On TPU none of this needs
hand-fusion — a single einsum→mask→softmax→einsum chain compiles to fused MXU
ops — but the numerics contract is kept: attention probabilities are computed
in fp32 regardless of compute dtype (the reference's ``attn_weights`` fp32
islands), then cast back.

Head layout: ``[batch, seq, heads, head_dim]`` (head_dim rides the 128-lane
minor dimension). GQA/MQA are the general case: ``n_kv_heads`` may be 1 (MQA —
the reference replicates the single KV head across TP ranks,
``gpt_bigcode_modeling.py:150-155``; here the same thing falls out of a
replicated sharding spec on the KV projection).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


_NEG_INF = float(jnp.finfo(jnp.float32).min)

# Attention implementation pin: "xla" | "pallas" | None (the program
# chooses from platform, mesh, dtype and each kernel's ``supports``). Set
# only through ``force_impl``; nothing in the environment is read. "pallas"
# disables the sp ring path (the kernels are single-shard: A/B them against
# "xla" on an sp=1 mesh). A pinned kernel that cannot take the shapes
# raises on a TPU (``forced_pallas_miss``): a run under a kernel's name
# never measures another implementation.
IMPL_OVERRIDE: str | None = None


def pallas_interpret() -> bool:
    """The one place that decides how a Pallas kernel runs: compiled on
    TPU, interpreted on CPU (the tests' forced-``pallas`` parity runs).
    Any other backend has no Pallas TPU path — an error, never a silent
    interpreter."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas TPU kernels run compiled on tpu or interpreted on cpu; "
        f"the default backend is {backend!r}"
    )


def forced_pallas_miss(msg: str) -> None:
    """``force_impl("pallas")`` named a kernel that cannot take these
    shapes. Compiled (TPU) that is an error — the XLA path never runs
    under the kernel's name. Interpreted (the CPU parity tests, which
    pin across whole engines at toy widths) the caller continues on the
    XLA oracle, with a warning saying so."""
    msg = "pallas forced: " + msg
    if not pallas_interpret():
        raise ValueError(msg)
    import warnings

    warnings.warn(msg + "; running the XLA path", stacklevel=3)


class force_impl:
    """The one scoped pin: ``with force_impl("xla"): ...`` traces every
    program inside the block with one pinned implementation (``"xla"``,
    ``"pallas"`` or ``None``) and restores the previous pin on exit.
    shardcheck audits lowered HLO under it (the collective inventory in
    tools/comms_manifest.json is golden against ONE deterministic
    lowering), the CPU tests run a kernel interpreted under it, and a
    builder's A/B script compares the two sides with it.
    """

    def __init__(self, impl: str | None):
        if impl not in (None, "xla", "pallas"):
            raise ValueError(
                f"force_impl takes 'xla', 'pallas' or None, not {impl!r}"
            )
        self.impl = impl
        self._saved: str | None = None

    def __enter__(self):
        global IMPL_OVERRIDE
        self._saved = IMPL_OVERRIDE
        IMPL_OVERRIDE = self.impl
        return self

    def __exit__(self, *exc):
        global IMPL_OVERRIDE
        IMPL_OVERRIDE = self._saved
        return False


def tp_head_plan(Hq: int, Hkv: int, tp: int) -> tuple[bool, bool, str | None]:
    """Shared TP-shardability rule for attention heads: returns
    ``(kv_shard, heads_ok, kv_axis)``.

    Replicated-KV sharding is only correct for MQA (Hkv == 1): local head
    grouping matches global grouping only when KV heads shard alongside
    query heads or there is a single shared KV head.
    """
    from llmss_tpu.parallel.mesh import AXIS_TP

    kv_shard = Hkv % tp == 0
    heads_ok = Hq % tp == 0 and (kv_shard or Hkv == 1)
    return kv_shard, heads_ok, AXIS_TP if kv_shard else None


def sp_plan(mesh, B: int, T: int, Hq: int, Hkv: int) -> tuple[bool, str | None]:
    """Shared sp-shardability rule: whether (batch, cache length, heads) can
    ride the mesh's sp axis. Returns ``(ok, kv_axis)``. Used by both
    prefill/decode routing here and the deferred-write sp decode dispatch
    (models/decoder.py) so the two can never drift."""
    from llmss_tpu.parallel.mesh import AXIS_DP, AXIS_SP, AXIS_TP

    dp, sp, tp = (
        mesh.shape[AXIS_DP], mesh.shape[AXIS_SP], mesh.shape[AXIS_TP]
    )
    _, heads_ok, kv_ax = tp_head_plan(Hq, Hkv, tp)
    ok = sp > 1 and T % sp == 0 and B % dp == 0 and heads_ok
    return ok, kv_ax


def make_causal_mask(
    q_positions: jax.Array,  # [B, S] int — absolute position of each query
    kv_positions: jax.Array,  # [B, T] int — absolute position of each cache slot
    kv_valid: jax.Array,  # [B, T] bool — slot holds a real token
    window: int | None = None,  # sliding-window width (Mistral); None = full
) -> jax.Array:
    """Boolean [B, S, T] mask: query may attend to valid slots at <= position
    (and within the sliding window, when set).

    Replaces the reference's precomputed tril buffer
    (``gptj_modeling.py:55-61``) with position arithmetic that works for both
    contiguous prefill and ring-buffer decode, where cache slot order is not
    position order.
    """
    mask = (kv_positions[:, None, :] <= q_positions[:, :, None]) & kv_valid[
        :, None, :
    ]
    if window is not None:
        mask &= kv_positions[:, None, :] > q_positions[:, :, None] - window
    return mask


def attention(
    q: jax.Array,  # [B, S, Hq, D]
    k: jax.Array,  # [B, T, Hkv, D]
    v: jax.Array,  # [B, T, Hkv, D]
    mask: jax.Array,  # [B, S, T] bool
    *,
    scale: float | None = None,
) -> jax.Array:
    """Scaled dot-product attention, grouped-query general case.

    Returns [B, S, Hq, D] in q's dtype; softmax in fp32.
    """
    B, S, Hq, D = q.shape
    Hkv = k.shape[2]
    assert Hq % Hkv == 0, (Hq, Hkv)
    G = Hq // Hkv
    if scale is None:
        scale = 1.0 / (D ** 0.5)

    qf = q.astype(jnp.float32).reshape(B, S, Hkv, G, D) * scale
    kf = k.astype(jnp.float32)
    logits = jnp.einsum("bskgd,btkd->bkgst", qf, kf)
    logits = jnp.where(mask[:, None, None, :, :], logits, _NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, v.astype(jnp.float32))
    return out.reshape(B, S, Hq, D).astype(q.dtype)


def decode_mask_penalty(
    q_pos: jax.Array,  # [B, 1]
    kv_pos_old: jax.Array,  # [B, T] — pre-write slot positions
    slots: jax.Array,  # [B, 1] — slot the current token will occupy
    window: int | None = None,
) -> jax.Array:
    """Additive fp32 [B, T] mask for ``fresh_kv_decode_attention``: 0 for
    visible slots, fp32-min for masked ones (causal, empty, the pending
    slot, and outside the sliding window). Layer-invariant — compute once
    per decode step and pass to every layer (see ``penalty`` below)."""
    T = kv_pos_old.shape[1]
    slot_idx = jnp.arange(T, dtype=jnp.int32)
    mask = (
        (kv_pos_old <= q_pos)  # q_pos [B, 1] broadcasts over T
        & (kv_pos_old >= 0)
        & (slot_idx[None, :] != slots)
    )  # [B, T]
    if window is not None:
        mask &= kv_pos_old > q_pos - window
    return jnp.where(mask, 0.0, _NEG_INF).astype(jnp.float32)


def window_mask_penalty(
    q_pos0: jax.Array,  # [B, 1] — position of the FIRST window query
    kv_pos_old: jax.Array,  # [B, T] — pre-write slot positions
    slots: jax.Array,  # [B, S] — slots the window's tokens will occupy
) -> jax.Array:
    """Additive fp32 [B, T] cache mask for ``fresh_kv_window_attention``:
    every live cache slot strictly before the window is visible to ALL
    window queries (cache positions < q_pos0 <= any query position), so
    one [B, T] penalty serves the whole window; the S pending slots are
    excluded (on ring wrap they hold tokens the window overwrites).
    Layer-invariant — compute once per step."""
    T = kv_pos_old.shape[1]
    slot_idx = jnp.arange(T, dtype=jnp.int32)
    pending = jnp.any(
        slot_idx[None, :, None] == slots[:, None, :], axis=-1
    )  # [B, T]
    mask = (kv_pos_old < q_pos0) & (kv_pos_old >= 0) & ~pending
    return jnp.where(mask, 0.0, _NEG_INF).astype(jnp.float32)


def fresh_kv_window_attention(
    q: jax.Array,  # [B, S, Hq, D] — a small decode window (S <= ~8)
    k_cache: jax.Array,  # [B, T, Hkv, D] — stale (window NOT written)
    v_cache: jax.Array,
    k_new: jax.Array,  # [B, S, Hkv, D] — the window's own KV
    v_new: jax.Array,
    penalty: jax.Array,  # [B, T] f32 — window_mask_penalty
    *,
    scale: float | None = None,
) -> jax.Array:
    """Deferred-write attention for a multi-token decode window (the
    speculative-verify hot path): one exact softmax over the stale cache
    plus the window's fresh KV with a compile-time triangular intra-window
    mask. The S=1 specialization of this is ``fresh_kv_decode_attention``;
    like it, this exists so the window's cache writes batch into one
    post-scan scatter instead of L in-scan scatters, and so the cache read
    can be bucketed — together ~2.5x cheaper per step than routing a small
    window through the prefill path (measured at 1b2 bench scale).
    Full-causal only: callers with a sliding window use the general path.
    """
    B, S, Hq, D = q.shape
    T, Hkv = k_cache.shape[1], k_cache.shape[2]
    G = Hq // Hkv
    if scale is None:
        scale = 1.0 / (D**0.5)

    qf = q.astype(jnp.float32).reshape(B, S, Hkv, G, D) * scale
    s_c = jnp.einsum("bskgd,btkd->bkgst", qf, k_cache.astype(jnp.float32))
    s_c = s_c + penalty[:, None, None, None, :]
    # Intra-window scores with a compile-time lower-triangular mask
    # (window query i attends window keys j <= i).
    s_w = jnp.einsum(
        "bskgd,btkd->bkgst", qf, k_new.astype(jnp.float32)
    )  # [B, Hkv, G, S, S]
    tri = jnp.tril(jnp.ones((S, S), bool))
    s_w = jnp.where(tri[None, None, None], s_w, _NEG_INF)

    m = jnp.maximum(
        jnp.max(s_c, axis=-1, keepdims=True),
        jnp.max(s_w, axis=-1, keepdims=True),
    )
    p_c = jnp.exp(s_c - m)
    p_w = jnp.exp(s_w - m)
    denom = (
        jnp.sum(p_c, axis=-1, keepdims=True)
        + jnp.sum(p_w, axis=-1, keepdims=True)
    )
    out = (
        jnp.einsum("bkgst,btkd->bkgsd", p_c, v_cache.astype(jnp.float32))
        + jnp.einsum("bkgst,btkd->bkgsd", p_w, v_new.astype(jnp.float32))
    ) / denom
    return (
        out.transpose(0, 3, 1, 2, 4).reshape(B, S, Hq, D).astype(q.dtype)
    )


def fresh_kv_decode_attention(
    q: jax.Array,  # [B, 1, Hq, D]
    k_cache: jax.Array,  # [B, T, Hkv, D] — stale (current token NOT written)
    v_cache: jax.Array,
    k_new: jax.Array,  # [B, 1, Hkv, D] — current token's KV
    v_new: jax.Array,
    q_pos: jax.Array,  # [B, 1]
    kv_pos_old: jax.Array,  # [B, T] — pre-write slot positions
    slots: jax.Array,  # [B, 1] — slot the current token will occupy
    *,
    scale: float | None = None,
    window: int | None = None,
    penalty: jax.Array | None = None,  # [B, T] f32 — precomputed mask
    k_scale: jax.Array | None = None,  # [B, T, Hkv] f32 — int8 cache scales
    v_scale: jax.Array | None = None,
) -> jax.Array:
    """Decode attention over a stale cache + the fresh current-token KV,
    merged in one exact softmax.

    This exists so the decode loop can defer all cache writes to a single
    post-scan scatter: TPU scatter cost is per-op, and one scatter of
    ``[L, B, 1, Hkv, D]`` is far cheaper than ``L`` per-layer scatters
    inside the scan (~25% of decode step time at 1B scale). The slot the
    current token will occupy is masked out of the cache read — on ring
    wrap this also drops the overwritten token, exactly matching the
    write-then-attend order of the in-scan path.

    ``penalty`` optionally supplies ``decode_mask_penalty(q_pos,
    kv_pos_old, slots, window)``. The mask depends only on positions —
    layer-invariant — and the decode scan hoists it: evaluating the
    boolean chain + ``where`` inside the per-layer score fusion measurably
    un-fuses the cache read (~0.6 ms/step at bench scale), while a single
    precomputed additive [B, T] operand keeps the fusion streaming.

    ``k_scale``/``v_scale`` accept an int8 cache's per-token-per-head
    dequant scales **instead of pre-dequantized caches**: the scales
    factor out of both contractions (``Σ_d q·(k8·s_t) = s_t·Σ_d q·k8``
    and ``Σ_t p_t·(v8·s_t) = Σ_t (p_t·s_t)·v8``), so the dots stream the
    raw int8 bytes (dtype convert folds into the dot for free) and the
    scales multiply the small score/probability tensors — no
    materialized bf16 dequant copy of the cache (round 3 paid
    ~1.8 ms/step for one at bench scale). fp32 score math is preserved;
    folding is *more* precise than pre-dequantizing to compute dtype.
    """
    B, S, Hq, D = q.shape
    T, Hkv = k_cache.shape[1], k_cache.shape[2]
    G = Hq // Hkv
    # Single-token decode only: the mask penalty is [B, T] (one query row
    # per batch row); an S > 1 call would broadcast one penalty over all
    # query positions and silently drop per-position causality.
    assert S == 1, f"fresh_kv_decode_attention requires S == 1, got S={S}"
    if scale is None:
        scale = 1.0 / (D**0.5)

    qf = q.astype(jnp.float32).reshape(B, S, Hkv, G, D) * scale
    s_c = jnp.einsum("bskgd,btkd->bkgst", qf, k_cache.astype(jnp.float32))
    if k_scale is not None:
        # [B, T, Hkv] -> [B, Hkv, 1, 1, T]
        s_c = s_c * k_scale.transpose(0, 2, 1)[:, :, None, None, :]
    if penalty is None:
        penalty = decode_mask_penalty(q_pos, kv_pos_old, slots, window)
    # Additive masking: exact for the finite-min convention (adding the
    # fp32 min to any finite score saturates to the min, and max/exp
    # downstream treat it exactly like the where() it replaces).
    s_c = s_c + penalty[:, None, None, None, :]
    # Current token always attends itself (finite logit), so an empty cache
    # degenerates cleanly to out = v_new.
    s_s = jnp.einsum(
        "bskgd,bskd->bkgs", qf, k_new.astype(jnp.float32)
    )[..., None]  # [B, Hkv, G, S, 1]

    m = jnp.maximum(jnp.max(s_c, axis=-1, keepdims=True), s_s)
    p_c = jnp.exp(s_c - m)
    p_s = jnp.exp(s_s - m)
    denom = jnp.sum(p_c, axis=-1, keepdims=True) + p_s
    # Fold the V dequant scales into the probabilities (see docstring) —
    # the contraction below then reads raw int8.
    p_v = p_c
    if v_scale is not None:
        p_v = p_c * v_scale.transpose(0, 2, 1)[:, :, None, None, :]
    if G == 1 and S == 1:
        # Value contraction as a hand-written broadcast-multiply + fp32
        # reduce over t — a MAJOR dim of the [B, T, Hkv, D] cache, so the
        # VPU loop accumulates whole (Hkv, D) lane-planes and XLA fuses the
        # decode scan's per-layer V slice (and dtype convert / int8
        # dequant) into this single pass over the V bytes. Spelled as a
        # dot_general, V instead rides the materialized slice+transpose
        # copy the K-score dot needs (~0.3 ms/step at bench scale). The
        # K side stays a real MXU dot: its contraction is over the minor
        # d dim, where a VPU mult+reduce is a (slow) cross-lane pattern.
        p_t = p_v[:, :, 0, 0, :]  # [B, Hkv, T]
        vterm = jnp.sum(
            p_t.transpose(0, 2, 1)[..., None]
            * v_cache.astype(jnp.float32),
            axis=1,
        )  # [B, Hkv, D]
        out_c = vterm[:, :, None, None, :]  # [B, Hkv, 1, 1, D]
    else:
        out_c = jnp.einsum(
            "bkgst,btkd->bkgsd", p_v, v_cache.astype(jnp.float32)
        )
    out = (
        out_c
        + p_s * v_new.astype(jnp.float32).transpose(0, 2, 1, 3)[:, :, None]
    ) / denom
    return (
        out.transpose(0, 3, 1, 2, 4).reshape(B, S, Hq, D).astype(q.dtype)
    )


def _gather_kv(k_pool, v_pool, block_tables, n_blocks, layer):
    """The rows' logical views of keys and of values; ONE gather where both
    are the same pool (a latent pool: values are columns of the keys)."""
    from llmss_tpu.engine.cache import gather_block_view

    k_view = gather_block_view(k_pool, block_tables, n_blocks, layer)
    if v_pool is k_pool:
        return k_view, k_view
    return k_view, gather_block_view(v_pool, block_tables, n_blocks, layer)


def paged_decode_attention(
    q: jax.Array,  # [B, 1, Hq, D]
    k_pool_layer: jax.Array,  # [N, bs, Hkv, D] — one layer of the block pool
    v_pool_layer: jax.Array,
    k_new: jax.Array,  # [B, 1, Hkv, D]
    v_new: jax.Array,
    q_pos: jax.Array,  # [B, 1]
    kv_pos_old: jax.Array,  # [B, nb*bs] — pre-write LOGICAL slot positions
    block_tables: jax.Array,  # [B, MB] int32 (sentinel >= N = unmapped)
    slots: jax.Array,  # [B, 1] — logical slot the token will occupy
    *,
    scale: float | None = None,
    window: int | None = None,
    penalty: jax.Array | None = None,  # [B, nb*bs] f32 — precomputed mask
    k_scale_layer: jax.Array | None = None,  # [N, bs, Hkv] f32 iff int8
    v_scale_layer: jax.Array | None = None,
    n_blocks: int | None = None,  # bucketed read: first n_blocks table cols
    layer=None,  # set: the pools are the WHOLE stacked pools, read at layer
) -> jax.Array:
    """Paged decode attention, XLA gather fallback: materialize the
    row-indirected logical view of one pool layer (``gather_block_view``)
    and run the exact fresh-KV merged softmax over it. The view has
    IDENTICAL values and slot order to the dense ring a row would hold, so
    this is token-for-token the dense decode path — the parity oracle
    ``ops/pallas_kv.py`` is tested against, and what ``attn_read`` calls
    ``gather``."""
    from llmss_tpu.engine.cache import gather_block_view

    k_view, v_view = _gather_kv(
        k_pool_layer, v_pool_layer, block_tables, n_blocks, layer
    )
    ks = vs = None
    if k_scale_layer is not None:
        ks = gather_block_view(k_scale_layer, block_tables, n_blocks, layer)
        vs = gather_block_view(v_scale_layer, block_tables, n_blocks, layer)
    return fresh_kv_decode_attention(
        q, k_view, v_view, k_new, v_new, q_pos, kv_pos_old, slots,
        scale=scale, window=window, penalty=penalty, k_scale=ks, v_scale=vs,
    )


def ragged_cache_visibility(
    q_len: jax.Array,  # [B] — live query rows per chunk (1..S)
    kv_pos_old: jax.Array,  # [B, T] — pre-write slot positions
    slot0: jax.Array,  # [B] or [B, 1] — logical slot of the first query
    ring_len: int,  # logical ring capacity (cache.max_len)
) -> jax.Array:
    """Query-invariant [B, T] bool cache visibility for
    ``ragged_fresh_kv_attention``: a slot is a candidate iff it holds a
    live token and is not among the chunk's ``q_len`` pending slots — the
    ring range starting at ``slot0``, which the chunk's deferred write
    overwrites (at ``q_len == 1`` this is ``decode_mask_penalty``'s
    ``slot_idx != slot`` exclusion). The per-query causal bound is applied
    on top by the core, since mid-prefill chunks carry intra-chunk causal
    structure a single [B, T] penalty cannot express. Layer-invariant —
    compute once per step and pass to every layer."""
    B, T = kv_pos_old.shape
    slot0 = slot0.reshape(B, 1)
    slot_idx = jnp.arange(T, dtype=jnp.int32)
    d = slot_idx[None, :] - slot0  # [B, T]
    d = jnp.where(d < 0, d + ring_len, d)
    pending = d < q_len[:, None]  # [B, T]
    return (kv_pos_old >= 0) & ~pending


def ragged_fresh_kv_attention(
    q: jax.Array,  # [B, S, Hq, D] — S = chunk budget, ragged per q_len
    k_cache: jax.Array,  # [B, T, Hkv, D] — stale (chunk NOT written)
    v_cache: jax.Array,
    k_new: jax.Array,  # [B, S, Hkv, D] — the chunk's own fresh KV
    v_new: jax.Array,
    q_pos: jax.Array,  # [B] or [B, 1] — FIRST query's absolute position
    q_len: jax.Array,  # [B] — live query rows (1..S); rest are padding
    kv_pos_old: jax.Array,  # [B, T] — pre-write slot positions
    slot0: jax.Array,  # [B] or [B, 1] — logical slot of the first query
    ring_len: int,
    *,
    scale: float | None = None,
    window: int | None = None,
    cache_vis: jax.Array | None = None,  # [B, T] bool — hoisted base mask
    k_scale: jax.Array | None = None,  # [B, T, Hkv] f32 — int8 cache scales
    v_scale: jax.Array | None = None,
) -> jax.Array:
    """Deferred-write attention for a ragged mixed prefill+decode batch:
    one exact softmax over the stale cache plus each row's fresh
    ``q_len``-token chunk. Generalizes ``fresh_kv_window_attention`` from
    the uniform speculative window to per-row raggedness — the causal
    bound varies per query row inside the chunk, the pending-slot
    exclusion covers the chunk's ring range, and the intra-chunk
    triangular mask is clipped at ``q_len`` so padding query rows (``i >=
    q_len``) still attend fresh key 0 and keep a positive denominator (no
    NaN; their outputs are garbage the head gather never reads). This is
    the XLA oracle ``ops/pallas_kv.py``'s mixed step is parity-tested
    against. Int8 scales fold exactly as in ``fresh_kv_decode_attention``."""
    B, S, Hq, D = q.shape
    T, Hkv = k_cache.shape[1], k_cache.shape[2]
    G = Hq // Hkv
    if scale is None:
        scale = 1.0 / (D**0.5)
    q_pos = q_pos.reshape(B, 1)
    rel = jnp.arange(S, dtype=jnp.int32)
    qpos = q_pos + rel[None, :]  # [B, S] — per-query absolute positions

    if cache_vis is None:
        cache_vis = ragged_cache_visibility(
            q_len, kv_pos_old, slot0, ring_len
        )
    mask = cache_vis[:, None, :] & (
        kv_pos_old[:, None, :] <= qpos[:, :, None]
    )  # [B, S, T]
    if window is not None:
        mask &= kv_pos_old[:, None, :] > qpos[:, :, None] - window

    qf = q.astype(jnp.float32).reshape(B, S, Hkv, G, D) * scale
    s_c = jnp.einsum("bskgd,btkd->bkgst", qf, k_cache.astype(jnp.float32))
    if k_scale is not None:
        s_c = s_c * k_scale.transpose(0, 2, 1)[:, :, None, None, :]
    s_c = jnp.where(mask[:, None, None], s_c, _NEG_INF)
    # Intra-chunk scores: fresh key j visible to query i iff j <= i and
    # j < q_len (key 0 ends up visible to every row, padding included).
    s_w = jnp.einsum(
        "bskgd,btkd->bkgst", qf, k_new.astype(jnp.float32)
    )  # [B, Hkv, G, S, S]
    tri = (rel[None, :, None] >= rel[None, None, :]) & (
        rel[None, None, :] < q_len[:, None, None]
    )  # [B, S(query), S(key)]
    if window is not None:
        tri &= (rel[None, :, None] - rel[None, None, :]) < window
    s_w = jnp.where(tri[:, None, None], s_w, _NEG_INF)

    m = jnp.maximum(
        jnp.max(s_c, axis=-1, keepdims=True),
        jnp.max(s_w, axis=-1, keepdims=True),
    )
    p_c = jnp.exp(s_c - m)
    p_w = jnp.exp(s_w - m)
    denom = (
        jnp.sum(p_c, axis=-1, keepdims=True)
        + jnp.sum(p_w, axis=-1, keepdims=True)
    )
    p_cv = p_c
    if v_scale is not None:
        p_cv = p_c * v_scale.transpose(0, 2, 1)[:, :, None, None, :]
    out = (
        jnp.einsum("bkgst,btkd->bkgsd", p_cv, v_cache.astype(jnp.float32))
        + jnp.einsum("bkgst,btkd->bkgsd", p_w, v_new.astype(jnp.float32))
    ) / denom
    return (
        out.transpose(0, 3, 1, 2, 4).reshape(B, S, Hq, D).astype(q.dtype)
    )


def ragged_paged_attention(
    q: jax.Array,  # [B, CB, Hq, D]
    k_pool_layer: jax.Array,  # [N, bs, Hkv, D] — one layer of the block pool
    v_pool_layer: jax.Array,
    k_new: jax.Array,  # [B, CB, Hkv, D]
    v_new: jax.Array,
    q_pos: jax.Array,  # [B] or [B, 1]
    q_len: jax.Array,  # [B]
    kv_pos_old: jax.Array,  # [B, nb*bs] — pre-write LOGICAL slot positions
    block_tables: jax.Array,  # [B, MB] int32 (sentinel >= N = unmapped)
    slot0: jax.Array,  # [B] or [B, 1]
    ring_len: int,
    *,
    scale: float | None = None,
    window: int | None = None,
    cache_vis: jax.Array | None = None,  # [B, nb*bs] bool — hoisted mask
    k_scale_layer: jax.Array | None = None,  # [N, bs, Hkv] f32 iff int8
    v_scale_layer: jax.Array | None = None,
    n_blocks: int | None = None,  # bucketed read: first n_blocks table cols
    layer=None,  # set: the pools are the WHOLE stacked pools, read at layer
) -> jax.Array:
    """Ragged chunked attention, XLA gather fallback: materialize the
    row-indirected logical view of one pool layer (``gather_block_view``)
    and run the exact ragged fresh-KV merged softmax over it — the parity
    oracle of ``ops/pallas_kv.py``'s mixed step and the path mixed
    batches take wherever ``attn_read`` says ``gather``."""
    from llmss_tpu.engine.cache import gather_block_view

    k_view, v_view = _gather_kv(
        k_pool_layer, v_pool_layer, block_tables, n_blocks, layer
    )
    ks = vs = None
    if k_scale_layer is not None:
        ks = gather_block_view(k_scale_layer, block_tables, n_blocks, layer)
        vs = gather_block_view(v_scale_layer, block_tables, n_blocks, layer)
    return ragged_fresh_kv_attention(
        q, k_view, v_view, k_new, v_new, q_pos, q_len, kv_pos_old, slot0,
        ring_len, scale=scale, window=window, cache_vis=cache_vis,
        k_scale=ks, v_scale=vs,
    )


def dispatch_attention(
    q: jax.Array,  # [B, S, Hq, D]
    k: jax.Array,  # [B, T, Hkv, D]
    v: jax.Array,  # [B, T, Hkv, D]
    *,
    mask: jax.Array,  # [B, S, T] bool (XLA path)
    q_positions: jax.Array,  # [B, S] (pallas path)
    kv_positions: jax.Array,  # [B, T] (pallas path)
    scale: float | None = None,
    mesh=None,
    window: int | None = None,  # sliding-window width (None = full causal)
) -> jax.Array:
    """Route to the right implementation:

    - ``sp > 1`` mesh → sequence-parallel ring attention (prefill) or
      split-KV LSE-merge attention (decode) inside ``shard_map``;
    - TPU + prefill-sized S → Pallas flash kernel inside ``shard_map``;
    - otherwise → XLA einsum path with the materialized mask.

    All paths implement identical semantics; the mask and the position pair
    are two encodings of the same constraint, and ``window`` is applied
    uniformly — the XLA fallback folds it into the mask here, so callers
    never need to pre-bake it."""
    from llmss_tpu.ops import pallas_attention, ring_attention as ring_mod

    B, S, Hq, D = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    force = IMPL_OVERRIDE
    if mesh is not None and force != "xla":
        from llmss_tpu.parallel.mesh import AXIS_DP, AXIS_SP, AXIS_TP

        dp, sp, tp = (
            mesh.shape[AXIS_DP], mesh.shape[AXIS_SP], mesh.shape[AXIS_TP]
        )
        kv_shard, heads_ok, kv_ax = tp_head_plan(Hq, Hkv, tp)

        sp_shardable, _ = sp_plan(mesh, B, T, Hq, Hkv)
        sp_ok = force is None and sp_shardable
        if sp_ok:
            # Sequence-parallel path: KV (the cache) sharded over sp.
            ring = S > 1 and S % sp == 0
            q_seq_ax = AXIS_SP if ring else None
            fn = ring_mod.ring_attention if ring else (
                ring_mod.lse_merge_attention
            )
            qs = P(AXIS_DP, q_seq_ax, AXIS_TP, None)
            ks = P(AXIS_DP, AXIS_SP, kv_ax, None)

            def local_sp(q, k, v, qp, kvp):
                return fn(q, k, v, qp, kvp, axis_name=AXIS_SP, scale=scale,
                          window=window)

            return jax.shard_map(
                local_sp, mesh=mesh,
                in_specs=(qs, ks, ks, P(AXIS_DP, q_seq_ax),
                          P(AXIS_DP, AXIS_SP)),
                out_specs=qs, check_vma=False,
            )(q, k, v, q_positions, kv_positions)

        shapes_ok = (
            sp == 1
            and B % dp == 0
            and heads_ok
            and pallas_attention.supports(S, T, Hq, Hkv)
        )
        if force == "pallas" and S > 1 and not shapes_ok:
            forced_pallas_miss(
                "prefill shapes out of the flash kernel envelope "
                f"(sp={sp}, B={B}, dp={dp}, S={S}, T={T}, Hq={Hq}, "
                f"Hkv={Hkv}, tp={tp})"
            )
        if shapes_ok and (force == "pallas" or not pallas_interpret()):
            qs = P(AXIS_DP, None, AXIS_TP, None)
            ks = P(AXIS_DP, None, kv_ax, None)
            ps = P(AXIS_DP, None)
            interp = pallas_interpret()

            def local(q, k, v, qp, kvp):
                return pallas_attention.flash_attention(
                    q, k, v, qp, kvp, scale=scale, window=window,
                    interpret=interp,
                )

            return jax.shard_map(
                local, mesh=mesh, in_specs=(qs, ks, ks, ps, ps),
                out_specs=qs, check_vma=False,
            )(q, k, v, q_positions, kv_positions)
    if window is not None:
        mask = mask & (
            kv_positions[:, None, :] > q_positions[:, :, None] - window
        )
    return attention(q, k, v, mask, scale=scale)
