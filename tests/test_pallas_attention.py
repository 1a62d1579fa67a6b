"""Pallas flash-attention kernel parity vs the XLA einsum path.

Runs the kernel in interpret mode on the virtual CPU mesh (the de facto fake
backend, SURVEY.md §4), covering the cache semantics the kernel must honor:
contiguous prefill, padding (-1 positions), ring-buffer wrap (slot order ≠
position order), GQA/MQA head grouping, and the shard_map'd dispatch over
dp×tp.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import importlib

attn_mod = importlib.import_module("llmss_tpu.ops.attention")
from llmss_tpu.ops.attention import attention, make_causal_mask
from llmss_tpu.ops.pallas_attention import flash_attention, supports


def _rand(rng, *shape):
    return jnp.asarray(rng.normal(size=shape), jnp.float32)


def _xla_ref(q, k, v, q_pos, kv_pos, scale=None):
    mask = make_causal_mask(q_pos, kv_pos, kv_pos >= 0)
    return attention(q, k, v, mask, scale=scale)


@pytest.mark.parametrize("Hq,Hkv", [(8, 8), (8, 2), (8, 1)])
def test_prefill_parity(Hq, Hkv):
    rng = np.random.default_rng(0)
    B, S, T, D = 2, 64, 128, 64
    q, k, v = _rand(rng, B, S, Hq, D), _rand(rng, B, T, Hkv, D), _rand(
        rng, B, T, Hkv, D
    )
    # 100 valid slots; queries are the last 64 tokens; rest of cache empty.
    kv_pos = np.full((B, T), -1, np.int32)
    kv_pos[:, :100] = np.arange(100)
    q_pos = np.broadcast_to(np.arange(36, 100), (B, S)).astype(np.int32)
    q_pos, kv_pos = jnp.asarray(q_pos), jnp.asarray(kv_pos)

    ref = _xla_ref(q, k, v, q_pos, kv_pos)
    out = flash_attention(q, k, v, q_pos, kv_pos, interpret=True)
    np.testing.assert_allclose(out, ref, atol=2e-2)


def test_ring_wrap_and_block_sizes():
    """Slot order ≠ position order (post-wrap sliding window)."""
    rng = np.random.default_rng(1)
    B, S, T, Hq, Hkv, D = 2, 32, 128, 4, 4, 32
    q, k, v = _rand(rng, B, S, Hq, D), _rand(rng, B, T, Hkv, D), _rand(
        rng, B, T, Hkv, D
    )
    base = np.array([[37], [91]])
    kv_pos = jnp.asarray((np.arange(T)[None, :] + base) % 200 + 50, jnp.int32)
    q_pos = jnp.asarray(rng.integers(60, 240, (B, S)), jnp.int32)
    ref = _xla_ref(q, k, v, q_pos, kv_pos)
    for bq, bk in [(32, 128), (16, 32), (8, 16)]:
        out = flash_attention(
            q, k, v, q_pos, kv_pos, block_q=bq, block_k=bk, interpret=True
        )
        np.testing.assert_allclose(out, ref, atol=2e-2)


def test_custom_scale():
    rng = np.random.default_rng(2)
    B, S, T, Hq, Hkv, D = 1, 16, 64, 2, 2, 32
    q, k, v = _rand(rng, B, S, Hq, D), _rand(rng, B, T, Hkv, D), _rand(
        rng, B, T, Hkv, D
    )
    kv_pos = jnp.asarray(np.broadcast_to(np.arange(T), (B, T)), jnp.int32)
    q_pos = jnp.asarray(np.broadcast_to(np.arange(T - S, T), (B, S)),
                        jnp.int32)
    ref = _xla_ref(q, k, v, q_pos, kv_pos, scale=0.5)
    out = flash_attention(q, k, v, q_pos, kv_pos, scale=0.5, interpret=True)
    np.testing.assert_allclose(out, ref, atol=2e-2)


def test_supports_gating():
    assert supports(128, 256, 8, 8)
    assert supports(16, 128, 8, 1)
    assert not supports(1, 128, 8, 8)  # decode stays on XLA
    assert not supports(12, 128, 8, 8)  # unaligned S
    assert not supports(128, 128, 8, 3)  # non-grouping heads


def test_sharded_dispatch_matches_xla(devices):
    """dispatch_attention under force_impl("pallas") runs the kernel inside
    shard_map over dp×tp on the CPU mesh and must match the XLA path."""
    from llmss_tpu.parallel import MeshPlan, make_mesh

    mesh = make_mesh(MeshPlan(dp=2, sp=1, tp=4))
    rng = np.random.default_rng(3)
    B, S, T, Hq, Hkv, D = 4, 32, 64, 8, 4, 32
    q, k, v = _rand(rng, B, S, Hq, D), _rand(rng, B, T, Hkv, D), _rand(
        rng, B, T, Hkv, D
    )
    kv_pos = np.full((B, T), -1, np.int32)
    kv_pos[:, :48] = np.arange(48)
    q_pos = np.broadcast_to(np.arange(16, 48), (B, S)).astype(np.int32)
    q_pos, kv_pos = jnp.asarray(q_pos), jnp.asarray(kv_pos)
    mask = make_causal_mask(q_pos, kv_pos, kv_pos >= 0)

    ref = attention(q, k, v, mask)
    with attn_mod.force_impl("pallas"):
        out = jax.jit(
            lambda q, k, v: attn_mod.dispatch_attention(
                q, k, v, mask=mask, q_positions=q_pos, kv_positions=kv_pos,
                mesh=mesh,
            )
        )(q, k, v)
    np.testing.assert_allclose(out, ref, atol=2e-2)


def test_fresh_kv_decode_matches_write_then_attend():
    """Deferred-write decode attention == scatter-then-attend, including
    ring-wrap slot reuse and empty caches."""
    from llmss_tpu.engine.cache import write_layer
    from llmss_tpu.ops.attention import fresh_kv_decode_attention

    rng = np.random.default_rng(11)
    B, T, Hq, Hkv, D = 3, 32, 8, 4, 16
    kc = _rand(rng, B, T, Hkv, D)
    vc = _rand(rng, B, T, Hkv, D)
    q = _rand(rng, B, 1, Hq, D)
    k_new, v_new = _rand(rng, B, 1, Hkv, D), _rand(rng, B, 1, Hkv, D)
    for case, (pos_list, qp_list) in {
        "mid": ([12, 20, 0], [12, 20, 0]),  # row 2: empty cache
        "wrap": ([40, 33, 63], [40, 33, 63]),  # past T: slot reuse
    }.items():
        kv_pos = np.full((B, T), -1, np.int32)
        for b, p in enumerate(pos_list):
            n = min(p, T)
            # slots of the last n tokens before position p
            for j in range(n):
                pj = p - 1 - j
                kv_pos[b, pj % T] = pj
        q_pos = jnp.asarray(np.asarray(qp_list, np.int32)[:, None])
        slots = q_pos % T
        kv_pos = jnp.asarray(kv_pos)

        out = fresh_kv_decode_attention(
            q, kc, vc, k_new, v_new, q_pos, kv_pos, slots
        )

        kc2, vc2 = write_layer(kc, vc, k_new, v_new, slots)
        b_idx = np.arange(B)[:, None]
        kv_pos2 = jnp.asarray(np.asarray(kv_pos).copy())
        kv_pos2 = kv_pos2.at[b_idx, np.asarray(slots)].set(
            np.asarray(q_pos)
        )
        ref = attention(
            q, kc2, vc2, make_causal_mask(q_pos, kv_pos2, kv_pos2 >= 0)
        )
        np.testing.assert_allclose(out, ref, atol=2e-2, err_msg=case)


def test_gqa_replicated_kv_falls_back(devices):
    """Hkv=2 with tp=4 can't shard KV heads; the replicated-KV kernel path is
    only valid for MQA, so dispatch must fall back to XLA and stay correct
    (local head→KV grouping would otherwise be wrong — caught in review)."""
    from llmss_tpu.parallel import MeshPlan, make_mesh

    mesh = make_mesh(MeshPlan(dp=2, sp=1, tp=4))
    rng = np.random.default_rng(7)
    B, S, T, Hq, Hkv, D = 2, 32, 32, 8, 2, 16
    q, k, v = _rand(rng, B, S, Hq, D), _rand(rng, B, T, Hkv, D), _rand(
        rng, B, T, Hkv, D
    )
    pos = jnp.asarray(np.broadcast_to(np.arange(T), (B, T)), jnp.int32)
    mask = make_causal_mask(pos, pos, pos >= 0)
    ref = attention(q, k, v, mask)
    with attn_mod.force_impl("pallas"):
        out = jax.jit(
            lambda q, k, v: attn_mod.dispatch_attention(
                q, k, v, mask=mask, q_positions=pos, kv_positions=pos,
                mesh=mesh,
            )
        )(q, k, v)
    np.testing.assert_allclose(out, ref, atol=1e-5)


def test_engine_generate_with_pallas_attention(devices):
    """End-to-end greedy generation is identical with both attention paths."""
    from llmss_tpu.engine import DecodeEngine, GenerationParams
    from llmss_tpu.models.common import DecoderConfig
    from llmss_tpu.models.decoder import init_params
    from llmss_tpu.parallel import MeshPlan, make_mesh

    mesh = make_mesh(MeshPlan(dp=1, sp=1, tp=8))
    cfg = DecoderConfig(
        model_type="llama", vocab_size=256, hidden_size=64, n_layers=2,
        n_heads=8, n_kv_heads=8, head_dim=8, intermediate_size=128,
        max_position_embeddings=128, activation="silu", norm="rmsnorm",
        norm_eps=1e-5, mlp="swiglu", positions="rotary", rope_style="half",
        rotary_dim=8, attn_bias=False, mlp_bias=False,
        tie_word_embeddings=False, dtype="float32",
    )
    params = init_params(cfg, mesh, jax.random.key(0))
    prompts = [[1, 2, 3, 4, 5] * 5, [7, 8, 9]]
    gen = GenerationParams(max_new_tokens=6, is_greedy=True)

    engine = DecodeEngine(cfg, params, mesh, max_seq_len=64)
    ref = engine.generate(prompts, gen)

    with attn_mod.force_impl("pallas"):
        engine2 = DecodeEngine(cfg, params, mesh, max_seq_len=64)
        out = engine2.generate(prompts, gen)
    assert out == ref
