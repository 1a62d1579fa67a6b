"""Paged KV cache: block pool, paged==dense token equivalence, COW prefixes.

The contract everything here pins down: ``kv_layout="paged"`` is a pure
*layout* change. The block pool with per-row tables must produce
**bit-identical tokens** to the dense ring on every path — greedy and
sampled, GQA and MQA, int8 KV, full-capacity generation, continuous
batching with cancellation, and shared-prefix copy-on-write — while
admitting by block-pool capacity instead of row count and never copying
shared prefix blocks per row (asserted through ``kv_blocks_in_use``).
"""

import functools
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llmss_tpu.engine import DecodeEngine, GenerationParams
from llmss_tpu.engine.cache import (
    BlockAllocator, gather_block_view, init_paged_cache,
    logical_to_physical, paged_write_stacked, table_sentinel,
)
from llmss_tpu.engine.scheduler import ContinuousBatcher
from llmss_tpu.models.common import DecoderConfig
from llmss_tpu.models.decoder import init_params
from llmss_tpu.parallel import MeshPlan, make_mesh


def _cfg(n_kv_heads=2, **kw):
    base = dict(
        model_type="llama", vocab_size=64, hidden_size=32, n_layers=2,
        n_heads=4, n_kv_heads=n_kv_heads, head_dim=8, intermediate_size=64,
        max_position_embeddings=64, activation="silu", norm="rmsnorm",
        norm_eps=1e-5, mlp="swiglu", positions="rotary", rope_style="half",
        rotary_dim=8, attn_bias=False, mlp_bias=False,
        tie_word_embeddings=False, dtype="float32",
    )
    base.update(kw)
    return DecoderConfig(**base)


@pytest.fixture(scope="module")
def setup(devices):
    cfg = _cfg()
    mesh = make_mesh(MeshPlan(dp=2, tp=4))
    params = init_params(cfg, mesh, jax.random.key(0))
    return cfg, mesh, params


@pytest.fixture(scope="module")
def dense_engine(setup):
    cfg, mesh, params = setup
    return DecodeEngine(cfg, params, mesh, max_seq_len=64)


@pytest.fixture(scope="module")
def paged_engine(setup):
    cfg, mesh, params = setup
    return DecodeEngine(
        cfg, params, mesh, max_seq_len=64, kv_layout="paged", block_size=16,
    )


# -- host allocator ---------------------------------------------------------


def test_allocator_alloc_free_refcount():
    a = BlockAllocator(4)
    assert a.free_blocks == 4 and a.blocks_in_use == 0
    got = a.alloc(3)
    assert len(got) == 3 and a.blocks_in_use == 3
    # Never-partial: a too-big request returns None and takes nothing.
    assert a.alloc(2) is None
    assert a.free_blocks == 1
    # Shared blocks: refcount 2 survives one free.
    a.incref([got[0]])
    assert a.refcount(got[0]) == 2
    assert a.free(got) == 2  # got[0] NOT released yet
    assert a.refcount(got[0]) == 1
    assert a.free([got[0]]) == 1
    assert a.free_blocks == 4 and a.blocks_in_use == 0
    a.record_evictions(2)
    assert a.evictions == 2


def test_allocator_rejects_negative():
    with pytest.raises(ValueError):
        BlockAllocator(2).alloc(-1)


# -- device layout primitives ----------------------------------------------


def test_logical_to_physical_oob_sentinel():
    """Logical slots past the table's reach must map to a POSITIVE OOB
    physical block (scatter mode='drop' drops it): take_along_axis CLAMPS
    its index, so without the explicit where() an OOB slot would silently
    hit the row's last real block."""
    tables = jnp.asarray([[3, 1], [2, 0]], jnp.int32)  # MB=2, bs=4
    slots = jnp.asarray([[0, 5, 8], [7, 9, 100]], jnp.int32)
    blk, off = logical_to_physical(tables, slots, 4)
    blk, off = np.asarray(blk), np.asarray(off)
    big = np.iinfo(np.int32).max
    np.testing.assert_array_equal(blk, [[3, 1, big], [0, big, big]])
    np.testing.assert_array_equal(off[:, :2], [[0, 1], [3, 1]])


def test_gather_view_matches_identity_pool_and_write_roundtrip(devices):
    """With identity tables the gathered logical view IS the dense ring
    (same values, same slot order), and a paged token scatter lands at
    exactly (slot // bs, slot % bs) of the row's table."""
    mesh = make_mesh(MeshPlan(dp=2, tp=4))
    cache = init_paged_cache(
        mesh, n_layers=2, batch=2, max_len=32, row=(4, 8),
        dtype=jnp.float32, block_size=8,
    )
    rng = np.random.default_rng(0)
    pool = jnp.asarray(
        rng.standard_normal(cache.k.shape), jnp.float32
    )
    view = gather_block_view(pool[0], cache.block_tables)
    # identity tables: row b's blocks are [b*MB, (b+1)*MB)
    want = pool[0].reshape(2, 32, 4, 8)
    np.testing.assert_array_equal(np.asarray(view), np.asarray(want))

    tok = jnp.asarray(rng.standard_normal((2, 2, 1, 4, 8)), jnp.float32)
    slots = jnp.asarray([[9], [30]], jnp.int32)
    new_pool = paged_write_stacked(
        pool, tok, cache.block_tables, slots, cache.block_size
    )
    got = gather_block_view(new_pool[0], cache.block_tables)
    np.testing.assert_array_equal(
        np.asarray(got[0, 9]), np.asarray(tok[0, 0, 0])
    )
    np.testing.assert_array_equal(
        np.asarray(got[1, 30]), np.asarray(tok[0, 1, 0])
    )
    # sentinel tables drop the write entirely
    sent = jnp.full_like(cache.block_tables, table_sentinel(8))
    dropped = paged_write_stacked(pool, tok, sent, slots, cache.block_size)
    np.testing.assert_array_equal(np.asarray(dropped), np.asarray(pool))


@pytest.mark.parametrize("n_blocks", [None, 2], ids=["whole", "bucket"])
@pytest.mark.parametrize(
    "tail", [(4, 8), (4,), (16,)], ids=["kv", "scales", "latent"]
)
def test_gather_view_indexes_the_stack_by_layer(tail, n_blocks):
    """``gather_block_view(pool, tables, nb, layer=l)`` IS
    ``gather_block_view(pool[l], tables, nb)``: one gather addressed by layer
    AND block, which is how every layer scan reads the stacked pool (a slice
    of it ahead of the gather is copied out whole: docs/paged-kv.md). Pools
    ``[L, N, bs, Hkv, D]``, ``[L, N, bs, Hkv]`` (an int8 pool's scales) and
    ``[L, N, bs, C]`` (a latent pool); a sentinel table entry clamps to the
    same real block either way; the layer is traced, as in the scan."""
    L, B, MB, bs = 3, 4, 3, 4
    N = B * MB
    rng = np.random.default_rng(len(tail) + (n_blocks or 0))
    pool = jnp.asarray(rng.standard_normal((L, N, bs) + tail), jnp.float32)
    tables = rng.permutation(N).reshape(B, MB).astype(np.int32)
    tables[2, 1:] = table_sentinel(N)
    tables = jnp.asarray(tables)
    indexed = jax.jit(
        lambda layer: gather_block_view(pool, tables, n_blocks, layer)
    )
    for layer in range(L):
        want = gather_block_view(pool[layer], tables, n_blocks)
        got = indexed(jnp.int32(layer))
        assert got.shape == (B, (n_blocks or MB) * bs) + tail
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("step", ["decode", "mixed"])
def test_int8_attention_reads_values_and_scales_by_layer(step):
    """The XLA paged attention of the decode step and of the mixed step,
    handed the WHOLE stacked int8 pools and their scales with ``layer``,
    gives what it gives on that layer's slices: the scales follow the
    values through the indexed gather."""
    attn = importlib.import_module("llmss_tpu.ops.attention")
    L, B, MB, bs, Hq, Hkv, D = 3, 2, 2, 4, 4, 2, 8
    S = 1 if step == "decode" else 3
    N, T = B * MB, MB * bs
    rng = np.random.default_rng(7)
    k8, v8 = (
        jnp.asarray(rng.integers(-127, 128, (L, N, bs, Hkv, D)), jnp.int8)
        for _ in range(2)
    )
    ks, vs = (
        jnp.asarray(rng.uniform(0.01, 0.1, (L, N, bs, Hkv)), jnp.float32)
        for _ in range(2)
    )
    q = jnp.asarray(rng.standard_normal((B, S, Hq, D)), jnp.float32)
    k_new, v_new = (
        jnp.asarray(rng.standard_normal((B, S, Hkv, D)), jnp.float32)
        for _ in range(2)
    )
    tables = jnp.asarray(rng.permutation(N).reshape(B, MB), jnp.int32)
    ctx = np.array([5, 3])
    kv_pos = jnp.asarray(
        np.where(np.arange(T)[None] < ctx[:, None], np.arange(T)[None], -1),
        jnp.int32,
    )
    at = jnp.asarray(ctx, jnp.int32)

    def run(layer, pools):
        k_p, v_p, ks_p, vs_p = pools
        if step == "decode":
            return attn.paged_decode_attention(
                q, k_p, v_p, k_new, v_new, at[:, None], kv_pos, tables,
                at[:, None], k_scale_layer=ks_p, v_scale_layer=vs_p,
                layer=layer,
            )
        return attn.ragged_paged_attention(
            q, k_p, v_p, k_new, v_new, at, jnp.asarray([S, 1], jnp.int32),
            kv_pos, tables, at, T, k_scale_layer=ks_p, v_scale_layer=vs_p,
            layer=layer,
        )

    outs = []
    for layer in range(L):
        want = jax.jit(functools.partial(run, None))(
            [x[layer] for x in (k8, v8, ks, vs)]
        )
        got = jax.jit(run)(jnp.int32(layer), (k8, v8, ks, vs))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        outs.append(np.asarray(want))
    assert not np.array_equal(outs[0], outs[1])  # the layers do differ


def _write_reference(pool, new, tables, slots, bs):
    """``paged_write_stacked`` said plainly: a loop over layers, rows and
    tokens; a slot past the row's table or a table entry past the pool is
    no write."""
    out = pool.copy()
    n_blocks, mb = pool.shape[1], tables.shape[1]
    for layer in range(pool.shape[0]):
        for b in range(slots.shape[0]):
            for s in range(slots.shape[1]):
                slot = int(slots[b, s])
                if slot >= mb * bs:
                    continue
                blk = int(tables[b, slot // bs])
                if blk >= n_blocks:
                    continue
                out[layer, blk, slot % bs] = new[layer, b, s].astype(
                    pool.dtype
                )
    return out


@pytest.mark.parametrize("head_dim", [8, None], ids=["kv", "scales"])
@pytest.mark.parametrize("n_tok", [1, 4])
@pytest.mark.parametrize("n_kv_heads", [1, 4])
def test_paged_write_stacked_matches_loop(n_kv_heads, n_tok, head_dim):
    """Bitwise the loop above: pools ``[L, N, bs, Hkv, D]`` (float32 values
    cast to a bf16 pool) and ``[L, N, bs, Hkv]`` (the int8 pools' scales),
    one or several tokens a row; a row with a sentinel table and a slot
    ``>= MB*bs`` write nothing, and nothing else in the pool moves."""
    L, B, MB, bs = 3, 4, 2, 4
    N = B * MB + 1
    tail = (n_kv_heads,) if head_dim is None else (n_kv_heads, head_dim)
    dtype = jnp.float32 if head_dim is None else jnp.bfloat16
    rng = np.random.default_rng(n_kv_heads * 10 + n_tok)
    pool = np.asarray(
        jnp.asarray(rng.standard_normal((L, N, bs) + tail), dtype)
    )
    new = rng.standard_normal((L, B, n_tok) + tail).astype(np.float32)
    # Rows own distinct blocks, out of order; row 2's table is unmapped.
    tables = rng.permutation(N)[: B * MB].reshape(B, MB).astype(np.int32)
    tables[2] = table_sentinel(N)
    # Distinct slots within a row; row 1's last token is past its table
    # (the decode loop's write suppression for a done row).
    slots = np.stack([
        rng.permutation(MB * bs)[:n_tok] for _ in range(B)
    ]).astype(np.int32)
    slots[1, -1] = MB * bs
    want = _write_reference(pool, new, tables, slots, bs)
    got = np.asarray(jax.jit(paged_write_stacked, static_argnums=4)(
        jnp.asarray(pool), jnp.asarray(new), jnp.asarray(tables),
        jnp.asarray(slots), bs,
    ))
    assert got.dtype == pool.dtype
    bits = np.uint16 if pool.dtype.itemsize == 2 else np.uint32
    np.testing.assert_array_equal(got.view(bits), want.view(bits))
    # What landed is what was live: rows 0 and 3 whole, row 1 less one.
    moved = (got.view(bits) != pool.view(bits)).reshape(L, N, bs, -1)
    assert moved.any(-1).sum() == L * (3 * n_tok - 1)


# -- engine-level equivalence ----------------------------------------------

PROMPTS = [[5, 9, 23, 40], [3, 14, 15, 9, 26, 5]]


def test_engine_greedy_and_fused_match_dense(dense_engine, paged_engine):
    gen = GenerationParams(max_new_tokens=8, is_greedy=True)
    assert dense_engine.generate(PROMPTS, gen) == paged_engine.generate(
        PROMPTS, gen
    )
    assert dense_engine.generate_fused(
        PROMPTS, gen
    ) == paged_engine.generate_fused(PROMPTS, gen)


def test_engine_sampled_matches_dense(dense_engine, paged_engine):
    gen = GenerationParams(
        max_new_tokens=6, is_greedy=False, temperature=1.1, top_k=20,
        top_p=0.95, seed=7,
    )
    assert dense_engine.generate(PROMPTS, gen) == paged_engine.generate(
        PROMPTS, gen
    )


def test_engine_full_capacity_matches_dense(dense_engine, paged_engine):
    """Generate to the very last ring slot (prompt + new == max_seq_len):
    the final token writes into the last block's last offset."""
    gen = GenerationParams(max_new_tokens=60, is_greedy=True)
    p = [[7, 3, 11, 2]]
    assert dense_engine.generate(p, gen) == paged_engine.generate(p, gen)


def test_engine_mqa_matches_dense(devices):
    cfg = _cfg(n_kv_heads=1)
    mesh = make_mesh(MeshPlan(dp=2, tp=4))
    params = init_params(cfg, mesh, jax.random.key(2))
    gen = GenerationParams(max_new_tokens=6, is_greedy=True)
    d = DecodeEngine(cfg, params, mesh, max_seq_len=64)
    p = DecodeEngine(
        cfg, params, mesh, max_seq_len=64, kv_layout="paged", block_size=16,
    )
    assert d.generate(PROMPTS, gen) == p.generate(PROMPTS, gen)


def test_engine_int8_matches_dense_int8(setup):
    """int8 KV: the paged pool stores the same quantized bits + scales, so
    paged-int8 must equal dense-int8 exactly (both quantize identically)."""
    cfg, mesh, params = setup
    gen = GenerationParams(max_new_tokens=8, is_greedy=True)
    d = DecodeEngine(cfg, params, mesh, max_seq_len=64, kv_dtype="int8")
    p = DecodeEngine(
        cfg, params, mesh, max_seq_len=64, kv_dtype="int8",
        kv_layout="paged", block_size=16,
    )
    assert d.generate(PROMPTS, gen) == p.generate(PROMPTS, gen)


def test_engine_flag_validation(setup):
    cfg, mesh, params = setup
    with pytest.raises(ValueError):
        DecodeEngine(cfg, params, mesh, max_seq_len=64, kv_layout="wat")
    with pytest.raises(ValueError):
        # max_seq_len not divisible by block_size
        DecodeEngine(
            cfg, params, mesh, max_seq_len=64, kv_layout="paged",
            block_size=24,
        )


# -- continuous batching on the block pool ----------------------------------


def test_batcher_paged_matches_dense(dense_engine, paged_engine):
    prompts = PROMPTS + [[7, 8], [1, 2, 3]]
    gen = GenerationParams(max_new_tokens=6, is_greedy=True)
    expected = [dense_engine.generate([p], gen)[0] for p in prompts]
    bat = ContinuousBatcher(paged_engine, rows=2)
    results = {}
    for i, p in enumerate(prompts):
        bat.submit(p, gen, lambda t, i=i: results.__setitem__(i, t))
    bat.run_until_idle()
    for i, e in enumerate(expected):
        assert results[i] == e, (i, results[i], e)
    assert bat.allocator.blocks_in_use == 0  # every block returned


def test_batcher_pool_gated_admission(setup):
    """Admission degrades to BLOCK capacity: 4 row slots but a pool that
    fits only 2 requests at a time — all 4 must still complete with their
    solo tokens (the others requeue), and the pool drains to zero."""
    cfg, mesh, params = setup
    eng = DecodeEngine(
        cfg, params, mesh, max_seq_len=64, kv_layout="paged",
        block_size=16, kv_blocks=6,
    )
    dense = DecodeEngine(cfg, params, mesh, max_seq_len=64)
    gen = GenerationParams(max_new_tokens=30, is_greedy=True)  # 3 blocks
    prompts = [[i + 1, i + 2, i + 3] for i in range(4)]
    expected = [dense.generate([p], gen)[0] for p in prompts]
    bat = ContinuousBatcher(eng, rows=4)
    results = {}
    for i, p in enumerate(prompts):
        bat.submit(p, gen, lambda t, i=i: results.__setitem__(i, t))
    bat.run_until_idle()
    for i, e in enumerate(expected):
        assert results[i] == e, (i, results[i], e)
    assert bat.allocator.blocks_in_use == 0
    assert eng.metrics.to_dict()["kv_blocks_in_use"] == 0


def test_batcher_request_bigger_than_pool_errors(setup):
    """A request that can never fit the pool is answered with an error,
    not requeued forever."""
    cfg, mesh, params = setup
    eng = DecodeEngine(
        cfg, params, mesh, max_seq_len=64, kv_layout="paged",
        block_size=16, kv_blocks=2,
    )
    bat = ContinuousBatcher(eng, rows=2)
    out = {}

    def cb(toks, cancelled=False, error=None):
        out["error"] = error

    bat.submit([1, 2, 3], GenerationParams(max_new_tokens=60), cb)
    bat.run_until_idle()
    assert "KV blocks" in out["error"]
    assert bat.allocator.blocks_in_use == 0


def test_cancel_mid_decode_returns_blocks(paged_engine):
    gen = GenerationParams(max_new_tokens=50, is_greedy=True)
    bat = ContinuousBatcher(paged_engine, rows=2)
    done = {}

    def cb(toks, cancelled=False):
        done.update(toks=toks, cancelled=cancelled)

    bat.submit([5, 9, 23], gen, cb, req_id="r1")
    for _ in range(4):
        bat.step()
    assert bat.allocator.blocks_in_use > 0
    bat.cancel("r1")
    bat.run_until_idle()
    assert done["cancelled"] is True
    assert bat.allocator.blocks_in_use == 0  # freed immediately on cancel


def test_shared_prefix_cow_no_per_row_copies(dense_engine, paged_engine):
    """The acceptance assertion: N rows sharing a prefix hold ONE copy of
    its full blocks (refcounted), not N — observed through the
    kv_blocks_in_use gauge at admission — and still emit exactly the
    dense engine's tokens. The partial tail block is copied per row (COW).
    """
    pfx_tokens = list(range(1, 21))  # 20 toks: 1 full block (bs=16) + tail
    pfx = paged_engine.build_prefix(pfx_tokens)
    gen = GenerationParams(max_new_tokens=5, is_greedy=True)
    full = [pfx_tokens + [30 + i] for i in range(3)]
    expected = [dense_engine.generate([p], gen)[0] for p in full]

    bat = ContinuousBatcher(paged_engine, rows=4)
    results = {}
    for i, p in enumerate(full):
        bat.submit(p, gen, lambda t, i=i: results.__setitem__(i, t),
                   prefix=pfx)
    for _ in range(3):  # admit + a few decode chunks; nothing finished yet
        bat.step()
    # Each row: ceil((21 + 5)/16) = 2 blocks total, 1 shared -> 1 owned.
    # Shared full block counted ONCE. Per-row copies would be 3 * 2 = 6.
    assert bat.allocator.blocks_in_use == 1 + 3 * 1
    bat.run_until_idle()
    for i, e in enumerate(expected):
        assert results[i] == e, (i, results[i], e)
    # After finish only the prefix registry's shared block remains.
    assert bat.allocator.blocks_in_use == 1
    assert paged_engine.metrics.to_dict()["kv_blocks_in_use"] == 1


def test_prefix_eviction_under_pressure(setup):
    """An idle registered prefix is evicted (blocks reclaimed, eviction
    counters tick) when a new request can't otherwise fit the pool."""
    cfg, mesh, params = setup
    eng = DecodeEngine(
        cfg, params, mesh, max_seq_len=64, kv_layout="paged",
        block_size=16, kv_blocks=4,
    )
    pfx = eng.build_prefix(list(range(1, 18)))  # 1 full block
    bat = ContinuousBatcher(eng, rows=2)
    r = {}
    bat.submit(
        list(range(1, 18)) + [40], GenerationParams(max_new_tokens=4),
        lambda t: r.__setitem__("a", t), prefix=pfx,
    )
    bat.run_until_idle()
    assert bat.allocator.blocks_in_use == 1  # idle prefix block retained
    # 4-block pool, 1 held by the idle prefix: this needs all 4.
    bat.submit(
        [9] * 40, GenerationParams(max_new_tokens=24),
        lambda t: r.__setitem__("b", t),
    )
    bat.run_until_idle()
    assert "b" in r and len(r["b"]) == 24
    assert eng.metrics.to_dict()["kv_block_evictions"] == 1
    assert bat.allocator.evictions == 1
    assert bat.allocator.blocks_in_use == 0


# -- prefill bucket ladder for prefixes -------------------------------------


def test_build_prefix_keeps_bucket_shape(dense_engine):
    """build_prefix retains the prefill BUCKET's padded segment, so the
    seed scatter compiles once per bucket — not once per distinct prefix
    length (the removed ~28 s one-time cost)."""
    from llmss_tpu.engine.engine import _bucket

    for plen in (5, 7, 20):
        pfx = dense_engine.build_prefix(list(range(1, plen + 1)))
        assert pfx.length == plen
        assert pfx.k.shape[1] == _bucket(plen, dense_engine.max_seq_len)


# -- metrics surfacing ------------------------------------------------------


def test_kv_gauges_flow_to_producer_metrics(paged_engine):
    """The consumer publishes engine.metrics.to_dict() and the producer's
    /metrics serves broker.read_metrics() verbatim — the kv_* gauges must
    survive the round trip."""
    from llmss_tpu.serve.broker import InProcBroker

    d = paged_engine.metrics.to_dict()
    for k in ("kv_blocks_total", "kv_blocks_in_use", "kv_block_evictions"):
        assert k in d
    broker = InProcBroker()
    broker.publish_metrics(d)
    got = broker.read_metrics()
    assert got["kv_blocks_total"] == d["kv_blocks_total"]
    assert got["kv_blocks_in_use"] == d["kv_blocks_in_use"]


def test_batcher_paged_grouped_matches_dense(dense_engine, paged_engine):
    """Grouped dispatch rides the paged layout unchanged: a paged batcher
    at group_chunks>1 must produce every request's solo dense tokens, with
    admissions landing mid-stream and the block pool draining to zero."""
    prompts = PROMPTS + [[7, 8], [1, 2, 3]]
    gen = GenerationParams(max_new_tokens=6, is_greedy=True)
    expected = [dense_engine.generate([p], gen)[0] for p in prompts]
    bat = ContinuousBatcher(
        paged_engine, rows=2, chunk_steps=2, group_chunks=3,
    )
    results = {}
    for i, p in enumerate(prompts[:2]):
        bat.submit(p, gen, lambda t, i=i: results.__setitem__(i, t))
    bat.step()
    bat.step()  # later admissions land while the first rows are mid-group
    for i, p in enumerate(prompts[2:], start=2):
        bat.submit(p, gen, lambda t, i=i: results.__setitem__(i, t))
    bat.run_until_idle()
    for i, e in enumerate(expected):
        assert results[i] == e, (i, results[i], e)
    assert bat.allocator.blocks_in_use == 0  # every block returned
