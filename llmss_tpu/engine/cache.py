"""Preallocated ring-buffer KV cache as a sharded pytree.

The reference grows the cache by concatenation every token
(``gptj_modeling.py:229-236``) and, on overflow of ``n_positions``, trims to
the last ``n-1`` entries host-side (``generate.py:132-142`` — SURVEY.md
§2.11.2). Neither is jittable: XLA requires static shapes. Here the cache is a
fixed ``[L, B, T, Hkv, D]`` buffer; each incoming token's KV is scattered into
slot ``position % T``, and a per-slot ``positions`` array (−1 = empty) both
validates slots and orders them for the causal mask — so overflow naturally
degrades to the reference's sliding-window semantics, but in place, with
donated buffers (no ``torch.cuda.empty_cache()`` workarounds,
``generate.py:187``).

Sharding: heads over ``tp`` when divisible (MHA/GQA); replicated for MQA —
the same layout the reference engineers by hand (replicated single KV head,
``gpt_bigcode_modeling.py:150-155``). Batch over ``dp``.
"""

from __future__ import annotations

import threading
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from llmss_tpu.parallel.mesh import AXIS_DP, AXIS_SP, AXIS_TP


class KVCache(NamedTuple):
    k: jax.Array  # [L, B, T, Hkv, D]
    v: jax.Array  # [L, B, T, Hkv, D]
    positions: jax.Array  # [B, T] int32, -1 = empty slot
    # Per-(layer, row, slot, head) dequant scales, set iff k/v are int8
    # (kv_dtype="int8"): value = int8 * scale. Halves cache HBM footprint;
    # on the decode hot path the scales FOLD into the attention
    # contractions (they factor out of both the d- and t-sums,
    # ops/attention.py), so the dots stream raw int8 and step traffic
    # *drops* — measured faster than the bf16 cache at bench scale.
    k_scale: jax.Array | None = None  # [L, B, T, Hkv] f32
    v_scale: jax.Array | None = None

    @property
    def max_len(self) -> int:
        return self.k.shape[2]

    @property
    def quantized(self) -> bool:
        return self.k_scale is not None


def quantize_kv(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Symmetric per-(…, head) int8 quantization over the feature dim.

    Returns (int8 values, f32 scales of x.shape[:-1]). Scale floor keeps
    all-zero rows (empty slots) exact and division finite."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(
        jnp.round(x.astype(jnp.float32) / scale[..., None]), -127, 127
    ).astype(jnp.int8)
    return q, scale


def dequantize_kv(q: jax.Array, scale: jax.Array, dtype) -> jax.Array:
    return q.astype(dtype) * scale[..., None].astype(dtype)


def cache_specs(
    n_kv_heads: int, tp: int, *, batch_dp: bool = True, seq_sp: bool = False,
    quantized: bool = False,
) -> KVCache:
    """PartitionSpecs for the cache pytree.

    ``batch_dp=False`` replicates the batch dim (needed when the live batch
    is smaller than the dp axis). ``seq_sp=True`` shards the sequence dim
    over ``sp`` — the long-context layout (context scales with chips; ring /
    split-KV attention reads it, absent entirely in the reference,
    SURVEY.md §5).
    """
    head_axis = AXIS_TP if n_kv_heads % tp == 0 else None
    dp_axis = AXIS_DP if batch_dp else None
    seq_axis = AXIS_SP if seq_sp else None
    kv = P(None, dp_axis, seq_axis, head_axis, None)
    scale = P(None, dp_axis, seq_axis, head_axis) if quantized else None
    return KVCache(
        k=kv, v=kv, positions=P(dp_axis, seq_axis),
        k_scale=scale, v_scale=scale,
    )


def cache_specs_for(
    mesh: Mesh, *, batch: int, max_len: int, n_kv_heads: int, dtype,
) -> KVCache:
    """The spec-selection policy (dp only when the batch divides, sp only
    when the length divides) applied to a concrete mesh + shape. The ONE
    place this policy lives: ``init_cache`` creates caches with it and
    ``DecodeEngine.canon_cache`` re-wraps carried caches with it — they
    must agree exactly or the rewrap becomes a real resharding."""
    return cache_specs(
        n_kv_heads,
        mesh.shape[AXIS_TP],
        batch_dp=batch % mesh.shape[AXIS_DP] == 0,
        seq_sp=mesh.shape[AXIS_SP] > 1 and max_len % mesh.shape[AXIS_SP] == 0,
        quantized=jnp.dtype(dtype) == jnp.int8,
    )


def init_cache(
    mesh: Mesh,
    *,
    n_layers: int,
    batch: int,
    max_len: int,
    n_kv_heads: int,
    head_dim: int,
    dtype=jnp.bfloat16,
) -> KVCache:
    quantized = jnp.dtype(dtype) == jnp.int8
    specs = cache_specs_for(
        mesh, batch=batch, max_len=max_len, n_kv_heads=n_kv_heads,
        dtype=dtype,
    )
    shape = (n_layers, batch, max_len, n_kv_heads, head_dim)

    def zeros(spec, shape, dtype):
        return jax.device_put(
            jnp.zeros(shape, dtype), NamedSharding(mesh, spec)
        )

    return KVCache(
        k=zeros(specs.k, shape, dtype),
        v=zeros(specs.v, shape, dtype),
        positions=zeros(specs.positions, (batch, max_len), jnp.int32) - 1,
        k_scale=(
            zeros(specs.k_scale, shape[:-1], jnp.float32)
            if quantized else None
        ),
        v_scale=(
            zeros(specs.v_scale, shape[:-1], jnp.float32)
            if quantized else None
        ),
    )


def write_positions(
    cache_positions: jax.Array,  # [B, T]
    q_positions: jax.Array,  # [B, S] absolute positions being written
    slots: jax.Array,  # [B, S] slot index for each new token
) -> jax.Array:
    """Record the positions of newly written tokens (once per step, shared by
    all layers)."""
    B = cache_positions.shape[0]
    b_idx = jnp.arange(B, dtype=jnp.int32)[:, None]
    return cache_positions.at[b_idx, slots].set(q_positions.astype(jnp.int32))


def write_layer(
    k_cache: jax.Array,  # [B, T, Hkv, D] one layer's cache
    v_cache: jax.Array,
    k_new: jax.Array,  # [B, S, Hkv, D]
    v_new: jax.Array,
    slots: jax.Array,  # [B, S]
) -> tuple[jax.Array, jax.Array]:
    """Scatter new KV into ring slots (per-batch-row scatter: rows may be at
    different sequence offsets under continuous batching)."""
    B = k_cache.shape[0]
    b_idx = jnp.arange(B, dtype=jnp.int32)[:, None]
    k_cache = k_cache.at[b_idx, slots].set(k_new.astype(k_cache.dtype))
    v_cache = v_cache.at[b_idx, slots].set(v_new.astype(v_cache.dtype))
    return k_cache, v_cache


# -- paged layout --------------------------------------------------------------
#
# The dense cache above reserves max_len slots of HBM per row whether the row
# holds 30 tokens or 3000 — at serving scale the reservation, not the live
# context, caps concurrency. The paged layout (vLLM's PagedAttention / TPU
# "Ragged Paged Attention", PAPERS.md) breaks the per-row reservation: KV
# lives in a GLOBAL pool of fixed-size blocks ``[L, num_blocks, block_size,
# Hkv, D]`` and each row maps its logical slots onto pool blocks through a
# small int32 ``block_tables [B, max_blocks]`` indirection. Rows then consume
# HBM proportional to ceil(live_len / block_size) blocks, freed blocks return
# to the pool the moment a row finishes, and rows sharing a prompt prefix
# point their leading table entries at the SAME immutable blocks (refcounted;
# copy-on-write on the first partial block — engine/scheduler.py).
#
# Logical addressing is IDENTICAL to the dense ring: token at absolute
# position p occupies logical slot ``s = p % (max_blocks * block_size)`` and
# physical location ``(block_tables[row, s // bs], s % bs)``. ``positions``
# stays a per-LOGICAL-slot array [B, max_blocks * bs] (−1 = empty), so every
# consumer of dense slot arithmetic — ring-wrap overflow, causal masks,
# decode_mask_penalty — works unchanged on the gathered view, and paged
# decoding is token-for-token equivalent to dense (tests/test_paged.py).


#: Block-table entries >= num_blocks mean "unmapped". The sentinel is
#: POSITIVE out-of-range: scatters drop it under mode="drop", and gathers
#: clamp it to a valid block whose values are then masked by positions
#: (negative would WRAP — the r3 admission-sentinel bug class).
def table_sentinel(num_blocks: int) -> int:
    return num_blocks


class PagedKVCache(NamedTuple):
    # Up to three block pools under one block table (keys, values or one
    # latent for both, and ``idx``, the indexer's keys of a model with
    # learned sparse attention), and a row-indexed state pool.
    # [L_kv, N, bs, Hkv, D] global block pool. A pool's layer axis counts
    # the layers of ITS kind: every layer holds keys and values in most
    # models (L_kv is the depth), a quarter of them where linear-attention
    # layers alternate with attention (cfg.n_kv_layers), and each layer
    # indexes a pool by its index within its kind.
    k: jax.Array
    # [L, N, bs, Hkv, D]; None for a LATENT pool (a model with latent
    # attention, cfg.mla): ``k`` then holds ``[L, N, bs, C + R]``, a token's
    # normed latent beside its shared rotary key, and values are its first
    # C columns, so no second pool is allocated, written or copied. No head
    # axis: a size-1 axis beside the minor one is free for layout
    # assignment to move, and it then transposes the pool whole
    # (docs/latent-cache.md)
    v: jax.Array | None
    block_tables: jax.Array  # [B, MB] int32; >= N = unmapped sentinel
    positions: jax.Array  # [B, MB*bs] int32 per LOGICAL slot, -1 = empty
    # int8 pool variant: per-(layer, block, slot, head) dequant scales —
    # same folding contract as the dense cache (ops/attention.py).
    k_scale: jax.Array | None = None  # [L, N, bs, Hkv] f32
    v_scale: jax.Array | None = None
    # Recurrent state of a model with a Mamba-2 mixer (cfg.ssm) or with
    # linear-attention layers (cfg.linear_attn), a ROW and layer that holds
    # one (L_state = cfg.n_state_layers: its own count, not the block
    # pool's), beside the row's paged keys and values: fixed size, so it needs
    # no blocks and no allocator, only the row. Donated and returned with
    # the rest of the tuple; a step updates each layer's slice in place. A
    # row's state is rebuilt by the prefill that admits a request into the
    # row (a prompt starts from nothing), so finishing a request frees
    # nothing here and a done row's state just stays until then.
    # [L_state, rows, H, P, N] float32 (Mamba-2) or [L_state, rows, H, Dk,
    # Dv] (the delta rule)
    ssm: jax.Array | None = None
    # [L_state, rows, K-1, C] compute dtype (Mamba-2) or [L_state, rows,
    # (K-1) * C] (linear attention: the window flattened likewise)
    conv: jax.Array | None = None
    # Set on an admission view only ([B] int32): the pool row each of the
    # view's B rows stands for. The prefill then starts every row from a
    # zero state and writes its final state to that pool row; an index out
    # of range (positive: the view's padding rows) writes nowhere. None:
    # batch row i is pool row i and goes on from its state.
    state_rows: jax.Array | None = None
    # A THIRD block pool, of a model that selects what attention reads
    # (cfg.indexer, docs/sparse-attention.md): [L_kv, N, bs, W] float32,
    # the ONE indexer key a token holds in a layer, paged by the same block
    # table and written by the same post-scan ``paged_write_stacked`` as its
    # keys and values, read by every later step over the row's whole
    # context. float32 whatever the compute dtype: the selection it feeds
    # is discontinuous, and a stored key must be the numbers the reference
    # computes. W is the key's 64 numbers zero-padded to a whole lane tile
    # (``IndexerConfig.pool_dim``: 512 B a token and layer for 256 B of
    # key, what the device's own layout would pad it to). No head axis, as
    # the latent pool has none. None: the model has no indexer.
    idx: jax.Array | None = None

    @property
    def max_len(self) -> int:
        # Logical capacity per row — what slot arithmetic (``pos %
        # max_len``) and capacity checks see; NOT the pool size.
        return self.positions.shape[1]

    @property
    def block_size(self) -> int:
        return self.k.shape[2]

    @property
    def num_blocks(self) -> int:
        return self.k.shape[1]

    @property
    def max_blocks(self) -> int:
        return self.block_tables.shape[1]

    @property
    def quantized(self) -> bool:
        return self.k_scale is not None


def paged_cache_specs(
    row: tuple[int, ...], tp: int, *, quantized: bool = False,
) -> PagedKVCache:
    """PartitionSpecs for the paged pytree. ``row`` is what one token holds
    in one layer of a pool (``cfg.cache_row``): ``(kv heads, head size)``,
    or ONE vector for a latent pool, which has no head axis to shard
    (replicated, as MQA's one head is) and no ``v``. The pool shards KV
    heads over ``tp`` exactly like the dense cache; blocks are GLOBAL
    indices so the block axis cannot shard over dp — the pool replicates
    across dp (the documented v1 trade: dp>1 meshes pay pool HBM per
    replica; the paged win is per-ROW HBM, which dp never sharded well
    under continuous batching anyway). Tables/positions are tiny and replicated."""
    latent = len(row) == 1
    head_axis = AXIS_TP if not latent and row[0] % tp == 0 else None
    kv = P(None, None, None, None) if latent else P(
        None, None, None, head_axis, None
    )
    scale = P(None, None, None, head_axis) if quantized else None
    # The recurrent state is replicated, like the mixer's weights
    # (models/decoder.py: param_specs). Specs of leaves a cache does not
    # hold are skipped by the callers, leaf by leaf.
    return PagedKVCache(
        k=kv, v=None if latent else kv,
        block_tables=P(None, None), positions=P(None, None),
        k_scale=scale, v_scale=scale, ssm=P(), conv=P(), state_rows=P(),
        idx=P(None, None, None, None),
    )


def ssm_state_shapes(cfg) -> tuple | None:
    """``((shape, dtype) of the recurrent state, (shape, dtype) of the
    convolution window)`` of ONE row and layer for a config that holds a
    state (``cfg.has_state``: either recurrent family), else None. The state
    is float32 whatever the compute dtype (it is summed into for the whole
    life of a request); the window holds inputs of the compute dtype."""
    if cfg.linear_attn is not None:
        m = cfg.linear_attn
        # The state as the update computes on it: its device layout pads a
        # head's 192 values to two lane tiles (a third more memory), and no
        # program re-tiles or moves it; flattened to whole tiles, every
        # layer of every step re-tiled its slice on the way in and out. The
        # window IS flattened: as [rows, K-1, C] the step loop carries it
        # with the ROWS second-minor, and copies the pool into that layout
        # and back around every program (compiled for a described v5e)
        return (
            ((m.n_v_heads, m.key_head_dim, m.value_head_dim), jnp.float32),
            (((m.d_conv - 1) * m.conv_dim,), cfg.compute_dtype),
        )
    m = cfg.ssm
    if m is None:
        return None
    return (
        ((m.n_heads, m.head_dim, m.d_state), jnp.float32),
        ((m.d_conv - 1, m.conv_dim), cfg.compute_dtype),
    )


def paged_cache_specs_for(
    mesh: Mesh, *, row: tuple[int, ...], dtype,
) -> PagedKVCache:
    """Concrete-mesh spec selection for paged caches (the one policy shared
    by ``init_paged_cache`` and ``DecodeEngine.canon_cache``, mirroring
    ``cache_specs_for``)."""
    return paged_cache_specs(
        row, mesh.shape[AXIS_TP], quantized=jnp.dtype(dtype) == jnp.int8,
    )


def init_paged_cache(
    mesh: Mesh,
    *,
    n_layers: int,
    batch: int,
    max_len: int,
    row: tuple[int, ...],
    dtype=jnp.bfloat16,
    block_size: int = 16,
    num_blocks: int | None = None,
    identity_tables: bool = True,
    state_shapes: tuple | None = None,
    state_layers: int = 0,
    index_dim: int | None = None,
) -> PagedKVCache:
    """Zeroed paged cache. ``identity_tables=True`` pre-maps row ``b`` to
    blocks ``[b*MB, (b+1)*MB)`` — a dense-equivalent static layout for the
    engine's own generate paths (no allocator in the loop). The scheduler
    passes False and drives tables from its host-side ``BlockAllocator``.
    ``row`` (``cfg.cache_row``) is the pools' trailing shape: ``(kv heads,
    head size)``, or one vector for a latent pool (ONE pool, no ``v``).
    ``n_layers`` is the block pools' layer axis (``cfg.n_kv_layers``).
    ``state_shapes`` (``ssm_state_shapes(cfg)``) adds the zeroed recurrent
    state of ``batch`` rows over ``state_layers`` layers
    (``cfg.n_state_layers``: the state pools' own layer axis).
    ``index_dim`` (``cfg.indexer.pool_dim``) adds the zeroed float32 pool
    of indexer keys, blocks and layers as the keys' (``idx``)."""
    if max_len % block_size:
        raise ValueError(
            f"max_len {max_len} must be a multiple of block_size "
            f"{block_size}"
        )
    mb = max_len // block_size
    n = num_blocks if num_blocks is not None else batch * mb
    if identity_tables and n < batch * mb:
        raise ValueError(
            f"identity tables need {batch * mb} blocks, pool has {n}"
        )
    quantized = jnp.dtype(dtype) == jnp.int8
    latent = len(row) == 1
    if index_dim is not None and quantized:
        raise ValueError(
            "an indexer's key pool is not carried beside an int8 pool: the "
            "selection reads float32 keys and nothing measures it over "
            "quantized keys and values (docs/sparse-attention.md)"
        )
    if latent and quantized:
        raise ValueError(
            "the latent pool is not carried in int8: its one 'head' holds "
            "a normed latent and a rotary key, which want scales of their "
            "own (docs/latent-cache.md)"
        )
    specs = paged_cache_specs_for(mesh, row=row, dtype=dtype)
    pool_shape = (n_layers, n, block_size, *row)

    def put(spec, x):
        return jax.device_put(x, NamedSharding(mesh, spec))

    if identity_tables:
        tables = jnp.arange(batch * mb, dtype=jnp.int32).reshape(batch, mb)
    else:
        tables = jnp.full((batch, mb), table_sentinel(n), jnp.int32)
    return PagedKVCache(
        k=put(specs.k, jnp.zeros(pool_shape, dtype)),
        v=None if latent else put(specs.v, jnp.zeros(pool_shape, dtype)),
        block_tables=put(specs.block_tables, tables),
        positions=put(
            specs.positions, jnp.full((batch, max_len), -1, jnp.int32)
        ),
        k_scale=(
            put(specs.k_scale, jnp.zeros(pool_shape[:-1], jnp.float32))
            if quantized else None
        ),
        v_scale=(
            put(specs.v_scale, jnp.zeros(pool_shape[:-1], jnp.float32))
            if quantized else None
        ),
        **({} if state_shapes is None else {
            name: put(spec, jnp.zeros((state_layers, batch) + shape, dt))
            for name, spec, (shape, dt) in zip(
                ("ssm", "conv"), (specs.ssm, specs.conv), state_shapes
            )
        }),
        idx=None if index_dim is None else put(
            specs.idx,
            jnp.zeros((n_layers, n, block_size, index_dim), jnp.float32),
        ),
    )


def logical_to_physical(
    block_tables: jax.Array,  # [B, MB]
    slots: jax.Array,  # [B, S] logical slot per new token
    block_size: int,
) -> tuple[jax.Array, jax.Array]:
    """Map logical slots through the row's block table: returns
    ``(block [B, S], offset [B, S])``. Unmapped table entries pass the
    sentinel through — callers scatter with mode="drop". Out-of-range
    logical slots (>= MB*bs — the decode loop's write-suppression
    sentinel for done rows) also map to an OOB block: the table GATHER
    would otherwise clamp onto the row's last real block and the write
    would land."""
    MB = block_tables.shape[1]
    idx = jnp.minimum(slots // block_size, MB - 1)
    blk = jnp.take_along_axis(block_tables, idx, axis=1)
    blk = jnp.where(
        slots < MB * block_size, blk, jnp.int32(jnp.iinfo(jnp.int32).max)
    )
    return blk, slots % block_size


def gather_block_view(
    pool_layer: jax.Array,  # [N, bs, ...] one layer of the pool
    block_tables: jax.Array,  # [B, MB]
    n_blocks: int | None = None,  # read only the first n_blocks table cols
    layer=None,  # set: ``pool_layer`` is the WHOLE pool [L, N, bs, ...]
) -> jax.Array:
    """Materialize a row-indirected logical view ``[B, n_blocks*bs, ...]``
    of one pool layer — the XLA gather fallback's cache operand. Sentinel
    entries clamp to a real block; their values are garbage that the
    position mask (−1 = empty) already excludes.

    With ``layer`` (a traced scalar) the whole stacked pool is indexed by
    layer AND block in the one gather, so a layer scan that closes over the
    pool reads the rows' blocks and never slices a layer out of it."""
    bt = block_tables if n_blocks is None else block_tables[:, :n_blocks]
    if layer is None:
        bt = jnp.minimum(bt, pool_layer.shape[0] - 1)
        view = pool_layer[bt]  # [B, nb, bs, ...]
    else:
        bt = jnp.minimum(bt, pool_layer.shape[1] - 1)
        view = pool_layer[layer, bt]
    return view.reshape(
        (view.shape[0], view.shape[1] * view.shape[2]) + view.shape[3:]
    )


def paged_write_stacked(
    pool: jax.Array,  # [L, N, bs, ...] full stacked pool
    new: jax.Array,  # [L, B, S, ...] fresh values for all layers
    block_tables: jax.Array,  # [B, MB]
    slots: jax.Array,  # [B, S] logical slots
    block_size: int,
) -> jax.Array:
    """One batched all-layer scatter into the pool (the paged analogue of
    the dense post-scan ``cache.k.at[:, b_idx, slots].set``). Writes
    through unmapped table entries are dropped.

    The layer is an INDEX of the scatter, not a window (``pool.at[:, blk,
    off]``): with the layer axis a window, layout assignment may carry the
    pool layer-minor through the step loop while the layer scan slices it
    layer-major, and then transposes the whole pool of k and of v every
    decode step (it did with one KV head; docs/paged-kv.md)."""
    blk, off = logical_to_physical(block_tables, slots, block_size)
    layer = jnp.arange(pool.shape[0], dtype=jnp.int32)[:, None, None]
    return pool.at[layer, blk[None], off[None]].set(
        new.astype(pool.dtype), mode="drop"
    )


def export_blocks(
    cache: PagedKVCache, block_ids, n_tokens: int,
) -> dict:
    """Host-side copy of one row's first ``len(block_ids)`` logical blocks
    — the KV payload a prefill replica hands to a decode replica
    (serve/handoff.py). A pure READ of the pool: tables, positions, and
    allocator refcounts are untouched, so COW-shared prefix blocks can be
    exported while other rows keep referencing them.

    Tail slots at logical position >= ``n_tokens`` are zeroed: they hold
    whatever a previous tenant of the block left behind, and leaking that
    into the wire payload would make the bytes (and their checksum)
    nondeterministic across otherwise identical prefills.

    Returns ``{"k", "v", "k_scale", "v_scale"}`` as host numpy arrays of
    shape ``[L, nb, bs, Hkv, D]`` (scales ``[L, nb, bs, Hkv]``, None on
    bf16 pools), and ``"idx"`` (``[L, nb, bs, W]`` float32) where the cache
    holds an indexer's keys: the blocks' third payload, which
    ``import_blocks(idx=)`` takes back. (The hand-off's wire format and the
    tiered store's blobs have no field for it: both refuse such a model.)
    """
    ids = np.asarray(block_ids, np.int32)
    nb = len(ids)
    bs = cache.block_size
    if not 0 < n_tokens <= nb * bs:
        raise ValueError(
            f"n_tokens {n_tokens} outside (0, {nb} blocks * {bs}]"
        )
    valid = (np.arange(nb * bs) < n_tokens).reshape(nb, bs)
    dev_ids = jnp.asarray(ids)

    def grab(pool):
        if pool is None:
            return None
        seg = np.asarray(jax.device_get(pool[:, dev_ids]))
        mask = valid.reshape((1, nb, bs) + (1,) * (seg.ndim - 3))
        return np.where(mask, seg, np.zeros_like(seg))

    out = {
        "k": grab(cache.k), "v": grab(cache.v),
        "k_scale": grab(cache.k_scale), "v_scale": grab(cache.v_scale),
    }
    if cache.idx is not None:
        out["idx"] = grab(cache.idx)
    return out


def export_dense_row(
    cache: KVCache, row: int, n_tokens: int, block_size: int,
) -> dict:
    """Dense-ring analogue of ``export_blocks``: one row's first
    ``n_tokens`` slots, reshaped into the same ``[L, nb, bs, ...]``
    block layout (``nb = ceil(n_tokens/bs)``, tail zero-padded) so dense
    and paged KV share ONE at-rest blob format (serve/kvstore.py).
    Callers must not have ring-wrapped past ``n_tokens`` — slot ``i``
    must still hold position ``i``'s KV (the scheduler's park guard
    enforces this)."""
    if not 0 < n_tokens <= cache.max_len:
        raise ValueError(
            f"n_tokens {n_tokens} outside (0, {cache.max_len}]"
        )
    nb = -(-n_tokens // block_size)
    pad = nb * block_size - n_tokens

    def grab(buf):
        if buf is None:
            return None
        seg = np.asarray(jax.device_get(buf[:, row, :n_tokens]))
        if pad:
            widths = [(0, 0), (0, pad)] + [(0, 0)] * (seg.ndim - 2)
            seg = np.pad(seg, widths)
        return seg.reshape((seg.shape[0], nb, block_size) + seg.shape[2:])

    return {
        "k": grab(cache.k), "v": grab(cache.v),
        "k_scale": grab(cache.k_scale), "v_scale": grab(cache.v_scale),
    }


def import_blocks(
    cache: PagedKVCache, k, v, k_scale, v_scale, block_ids, idx=None,
) -> PagedKVCache:
    """Scatter exported block payloads into the pool at ``block_ids``
    ([nb] int32; sentinel entries drop under mode="drop", so callers may
    pad nb to a power of two for a bounded compile envelope). The decode
    replica's half of the KV handoff: after this scatter + a table/position
    install, the adopted row decodes as if it had prefilled locally.
    Pure function — the scheduler jits it with the pool donated."""

    def put(pool, seg):
        if pool is None:
            return None
        if seg is None:
            return None
        return pool.at[:, block_ids].set(
            jnp.asarray(seg).astype(pool.dtype), mode="drop"
        )

    return cache._replace(
        k=put(cache.k, k), v=put(cache.v, v),
        k_scale=put(cache.k_scale, k_scale),
        v_scale=put(cache.v_scale, v_scale),
        **({} if cache.idx is None else {"idx": put(cache.idx, idx)}),
    )


class BlockAllocator:
    """Host-side free-list + refcounts for the global block pool.

    Runs on the scheduler's worker thread but is read by metrics/health
    threads, so all state is lock-guarded (graftlint ``guarded_by:``
    discipline). Refcounts let immutable prefix blocks be SHARED by many
    rows' tables: each row increfs on admission and decrefs on finish; a
    block returns to the free list only at refcount zero."""

    def __init__(self, num_blocks: int):
        self.num_blocks = num_blocks
        self._lock = threading.Lock()
        # LIFO free list: recently freed blocks are re-issued first (their
        # pool bytes are most likely still warm in any cache hierarchy).
        self._free_list = list(range(num_blocks - 1, -1, -1))  # guarded_by: self._lock
        self._refs: dict[int, int] = {}  # guarded_by: self._lock
        self.evictions = 0  # guarded_by: self._lock

    @property
    def free_blocks(self) -> int:
        with self._lock:
            return len(self._free_list)

    @property
    def blocks_in_use(self) -> int:
        with self._lock:
            return self.num_blocks - len(self._free_list)

    def alloc(self, n: int) -> list[int] | None:
        """Take ``n`` blocks (refcount 1 each), or None — never partial —
        when the pool can't cover the request (the caller may evict idle
        prefix blocks and retry)."""
        if n < 0:
            raise ValueError(f"alloc({n})")
        with self._lock:
            if n > len(self._free_list):
                return None
            out = [self._free_list.pop() for _ in range(n)]
            for b in out:
                self._refs[b] = 1
            return out

    def incref(self, blocks: list[int]) -> None:
        with self._lock:
            for b in blocks:
                self._refs[b] += 1

    def free(self, blocks: list[int]) -> int:
        """Drop one reference per block; blocks reaching refcount zero
        return to the free list. Returns how many were actually released."""
        released = 0
        with self._lock:
            for b in blocks:
                r = self._refs[b] - 1
                if r:
                    self._refs[b] = r
                else:
                    del self._refs[b]
                    self._free_list.append(b)
                    released += 1
        return released

    def refcount(self, block: int) -> int:
        with self._lock:
            return self._refs.get(block, 0)

    def largest_free_run(self) -> int:
        """Longest contiguous run of free block ids — the fragmentation
        signal for the devtel counter tracks (== free_blocks means the
        pool is unfragmented). O(free) sort+scan; callers throttle."""
        with self._lock:
            ids = sorted(self._free_list)
        best = cur = 1 if ids else 0
        for a, b in zip(ids, ids[1:]):
            cur = cur + 1 if b == a + 1 else 1
            if cur > best:
                best = cur
        return best

    def record_evictions(self, n: int) -> None:
        with self._lock:
            self.evictions += n
