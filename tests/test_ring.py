"""Sequence/context parallelism: ring prefill + split-KV decode parity.

The reference truncates at ``n_positions`` (SURVEY.md §2.11.2, §5
"Long-context: absent"); here the cache's sequence dim shards over ``sp``.
These tests run the real collectives (ppermute / pmax / psum) on the virtual
8-device CPU mesh and require exact agreement with the single-device XLA
attention semantics.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from llmss_tpu.engine.cache import init_cache
from llmss_tpu.ops.attention import (
    attention,
    dispatch_attention,
    make_causal_mask,
)
from llmss_tpu.ops.ring_attention import lse_merge_attention, ring_attention
from llmss_tpu.parallel import MeshPlan, make_mesh
from llmss_tpu.parallel.mesh import AXIS_DP, AXIS_SP, AXIS_TP


def _rand(rng, *shape):
    return jnp.asarray(rng.normal(size=shape), jnp.float32)


@pytest.fixture(scope="module")
def sp_mesh(devices):
    return make_mesh(MeshPlan(dp=1, sp=4, tp=2))


def test_ring_prefill_parity(sp_mesh):
    rng = np.random.default_rng(0)
    B, S, Hq, Hkv, D = 2, 32, 8, 4, 16
    T = S
    q, k, v = _rand(rng, B, S, Hq, D), _rand(rng, B, T, Hkv, D), _rand(
        rng, B, T, Hkv, D
    )
    # prefill with per-row padding: row 0 has 20 tokens, row 1 has 32
    kv_pos = np.full((B, T), -1, np.int32)
    kv_pos[0, :20] = np.arange(20)
    kv_pos[1, :] = np.arange(T)
    q_pos = np.broadcast_to(np.arange(T), (B, S)).astype(np.int32)
    q_pos, kv_pos = jnp.asarray(q_pos), jnp.asarray(kv_pos)

    ref = attention(q, k, v, make_causal_mask(q_pos, kv_pos, kv_pos >= 0))

    qs = P(AXIS_DP, AXIS_SP, AXIS_TP, None)
    ks = P(AXIS_DP, AXIS_SP, AXIS_TP, None)
    out = jax.jit(
        jax.shard_map(
            lambda q, k, v, qp, kvp: ring_attention(
                q, k, v, qp, kvp, axis_name=AXIS_SP
            ),
            mesh=sp_mesh,
            in_specs=(qs, ks, ks, P(AXIS_DP, AXIS_SP), P(AXIS_DP, AXIS_SP)),
            out_specs=qs,
            check_vma=False,
        )
    )(q, k, v, q_pos, kv_pos)
    np.testing.assert_allclose(out, ref, atol=2e-2)


def test_lse_merge_decode_parity(sp_mesh):
    rng = np.random.default_rng(1)
    B, Hq, Hkv, D, T = 2, 8, 4, 16, 64
    q = _rand(rng, B, 1, Hq, D)
    k, v = _rand(rng, B, T, Hkv, D), _rand(rng, B, T, Hkv, D)
    # ring-buffer state mid-generation: rows at different positions
    kv_pos = np.full((B, T), -1, np.int32)
    kv_pos[0, :37] = np.arange(37)
    kv_pos[1, :52] = np.arange(52)
    q_pos = np.asarray([[36], [51]], np.int32)
    q_pos, kv_pos = jnp.asarray(q_pos), jnp.asarray(kv_pos)

    ref = attention(q, k, v, make_causal_mask(q_pos, kv_pos, kv_pos >= 0))

    qs = P(AXIS_DP, None, AXIS_TP, None)
    ks = P(AXIS_DP, AXIS_SP, AXIS_TP, None)
    out = jax.jit(
        jax.shard_map(
            lambda q, k, v, qp, kvp: lse_merge_attention(
                q, k, v, qp, kvp, axis_name=AXIS_SP
            ),
            mesh=sp_mesh,
            in_specs=(qs, ks, ks, P(AXIS_DP, None), P(AXIS_DP, AXIS_SP)),
            out_specs=qs,
            check_vma=False,
        )
    )(q, k, v, q_pos, kv_pos)
    np.testing.assert_allclose(out, ref, atol=2e-2)


@pytest.mark.parametrize("S", [32, 1])
def test_dispatch_routes_sp(sp_mesh, S):
    """dispatch_attention picks ring (S>1) / lse-merge (S=1) when sp>1."""
    rng = np.random.default_rng(2)
    B, Hq, Hkv, D, T = 2, 8, 4, 16, 64
    q = _rand(rng, B, S, Hq, D)
    k, v = _rand(rng, B, T, Hkv, D), _rand(rng, B, T, Hkv, D)
    kv_pos = jnp.asarray(np.broadcast_to(np.arange(T), (B, T)), jnp.int32)
    q_pos = jnp.asarray(
        np.broadcast_to(np.arange(T - S, T), (B, S)), jnp.int32
    )
    mask = make_causal_mask(q_pos, kv_pos, kv_pos >= 0)
    ref = attention(q, k, v, mask)
    out = jax.jit(
        lambda q, k, v: dispatch_attention(
            q, k, v, mask=mask, q_positions=q_pos, kv_positions=kv_pos,
            mesh=sp_mesh,
        )
    )(q, k, v)
    np.testing.assert_allclose(out, ref, atol=2e-2)


def test_cache_shards_sequence_over_sp(sp_mesh):
    cache = init_cache(
        sp_mesh, n_layers=2, batch=2, max_len=64, n_kv_heads=4, head_dim=16
    )
    assert cache.k.sharding.spec == P(None, AXIS_DP, AXIS_SP, AXIS_TP, None)
    assert cache.positions.sharding.spec == P(AXIS_DP, AXIS_SP)


def test_engine_generate_sp_parity(devices):
    """Greedy generation on a dp×sp×tp mesh matches the tp-only mesh —
    prefill rides ring attention, decode rides the LSE merge."""
    from llmss_tpu.engine import DecodeEngine, GenerationParams
    from llmss_tpu.models.common import DecoderConfig
    from llmss_tpu.models.decoder import init_params

    cfg = DecoderConfig(
        model_type="llama", vocab_size=256, hidden_size=64, n_layers=2,
        n_heads=8, n_kv_heads=4, head_dim=8, intermediate_size=128,
        max_position_embeddings=128, activation="silu", norm="rmsnorm",
        norm_eps=1e-5, mlp="swiglu", positions="rotary", rope_style="half",
        rotary_dim=8, attn_bias=False, mlp_bias=False,
        tie_word_embeddings=False, dtype="float32",
    )
    prompts = [list(range(1, 30)), [7, 8, 9]]
    gen = GenerationParams(max_new_tokens=6, is_greedy=True)

    mesh_tp = make_mesh(MeshPlan(dp=1, sp=1, tp=8))
    params_tp = init_params(cfg, mesh_tp, jax.random.key(0))
    ref = DecodeEngine(cfg, params_tp, mesh_tp, max_seq_len=64).generate(
        prompts, gen
    )

    mesh_sp = make_mesh(MeshPlan(dp=2, sp=2, tp=2))
    params_sp = init_params(cfg, mesh_sp, jax.random.key(0))
    out = DecodeEngine(cfg, params_sp, mesh_sp, max_seq_len=64).generate(
        prompts, gen
    )
    assert out == ref


def test_lse_merge_fresh_kv_decode_parity(sp_mesh):
    """sp>1 deferred-write decode: attention over the stale sharded cache +
    fresh KV merged in-softmax must equal the XLA fresh-KV oracle, including
    pending-slot exclusion on ring wrap."""
    from llmss_tpu.ops.attention import fresh_kv_decode_attention
    from llmss_tpu.ops.ring_attention import lse_merge_fresh_kv_attention

    rng = np.random.default_rng(3)
    B, Hq, Hkv, D, T = 2, 8, 4, 16, 64
    q = _rand(rng, B, 1, Hq, D)
    k, v = _rand(rng, B, T, Hkv, D), _rand(rng, B, T, Hkv, D)
    k_new, v_new = _rand(rng, B, 1, Hkv, D), _rand(rng, B, 1, Hkv, D)
    # Row 0 mid-fill; row 1 wrapped past T (slot 69 % 64 = 5 will be
    # overwritten and must be excluded from the stale read).
    kv_pos = np.full((B, T), -1, np.int32)
    kv_pos[0, :37] = np.arange(37)
    for p in range(69):
        kv_pos[1, p % T] = p
    q_pos = np.asarray([[37], [69]], np.int32)
    slots = np.asarray([[37], [69 % T]], np.int32)
    q_pos, kv_pos, slots = map(jnp.asarray, (q_pos, kv_pos, slots))

    ref = fresh_kv_decode_attention(
        q, k, v, k_new, v_new, q_pos, kv_pos, slots
    )

    qs = P(AXIS_DP, None, AXIS_TP, None)
    ks = P(AXIS_DP, AXIS_SP, AXIS_TP, None)
    ps = P(AXIS_DP, None)
    out = jax.jit(
        jax.shard_map(
            lambda q, k, v, qp, kvp, kn, vn, sl: (
                lse_merge_fresh_kv_attention(
                    q, k, v, qp, kvp, kn, vn, sl, axis_name=AXIS_SP
                )
            ),
            mesh=sp_mesh,
            in_specs=(qs, ks, ks, ps, P(AXIS_DP, AXIS_SP), P(
                AXIS_DP, None, AXIS_TP, None
            ), P(AXIS_DP, None, AXIS_TP, None), ps),
            out_specs=qs,
            check_vma=False,
        )
    )(q, k, v, q_pos, kv_pos, k_new, v_new, slots)
    np.testing.assert_allclose(out, ref, atol=2e-2)


def test_sp_decode_defers_writes(devices):
    """Receipt for the unified deferred-write path: ``_ablate="no_scatter"``
    suppresses the post-scan batched write *only on the deferred path* (the
    in-scan fallback writes the cache inside the layer scan regardless), so
    an unchanged cache proves the sp>1 mesh routes decode through the
    fresh-KV LSE merge + deferred scatter."""
    from llmss_tpu.engine import DecodeEngine
    from llmss_tpu.models.common import DecoderConfig
    from llmss_tpu.models.decoder import forward, init_params

    cfg = DecoderConfig(
        model_type="llama", vocab_size=256, hidden_size=64, n_layers=4,
        n_heads=8, n_kv_heads=4, head_dim=8, intermediate_size=128,
        max_position_embeddings=128, activation="silu", norm="rmsnorm",
        norm_eps=1e-5, mlp="swiglu", positions="rotary", rope_style="half",
        rotary_dim=8, attn_bias=False, mlp_bias=False,
        tie_word_embeddings=False, dtype="float32",
    )
    mesh = make_mesh(MeshPlan(dp=2, sp=2, tp=2))
    params = init_params(cfg, mesh, jax.random.key(0))
    engine = DecodeEngine(cfg, params, mesh, max_seq_len=64)
    cache = engine.new_cache(2)
    tokens = jnp.asarray([[3], [7]], jnp.int32)
    positions = jnp.asarray([[2], [5]], jnp.int32)
    slots = positions % cache.max_len

    _, cache_abl = forward(
        cfg, params, tokens, positions, cache, slots, last_only=True,
        mesh=mesh, _ablate="no_scatter",
    )
    np.testing.assert_array_equal(
        np.asarray(cache_abl.k), np.asarray(cache.k)
    )

    # And without ablation the deferred scatter does land the fresh KV.
    _, cache_real = forward(
        cfg, params, tokens, positions, cache, slots, last_only=True,
        mesh=mesh,
    )
    assert not np.array_equal(np.asarray(cache_real.k), np.asarray(cache.k))
