"""Parity: Pallas stacked-cache decode kernel vs the XLA oracle.

``ops.pallas_decode.decode_attention`` must be bit-compatible (to fp
tolerance) with ``ops.attention.fresh_kv_decode_attention`` applied to the
sliced layer, across ring wrap, sliding windows, GQA/MQA grouping, and
empty caches. Runs in interpret mode on CPU (tests/conftest.py forces the
CPU platform)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llmss_tpu.ops.attention import fresh_kv_decode_attention
from llmss_tpu.ops.pallas_decode import decode_attention, supports


def _mk(B, T, Hq, Hkv, D, L=3, n_valid=None, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)

    def arr(*shape):
        return jnp.asarray(rng.standard_normal(shape), dtype)

    q = arr(B, 1, Hq, D)
    k_cache = arr(L, B, T, Hkv, D)
    v_cache = arr(L, B, T, Hkv, D)
    k_new = arr(B, 1, Hkv, D)
    v_new = arr(B, 1, Hkv, D)
    n_valid = T if n_valid is None else n_valid
    # Ring semantics: row b holds positions [0, n_valid + b); slot p % T
    # ends up holding the latest position written there (wrap overwrites).
    kv_pos = np.full((B, T), -1, np.int32)
    q_pos = np.zeros((B, 1), np.int32)
    slots = np.zeros((B, 1), np.int32)
    for b in range(B):
        nv = n_valid + b
        for p in range(nv):
            kv_pos[b, p % T] = p
        q_pos[b, 0] = nv
        slots[b, 0] = nv % T
    return q, k_cache, v_cache, k_new, v_new, (
        jnp.asarray(q_pos), jnp.asarray(kv_pos), jnp.asarray(slots)
    )


@pytest.mark.parametrize(
    "B,T,Hq,Hkv,D,n_valid,window",
    [
        (2, 32, 4, 4, 128, 16, None),  # MHA, half-full cache
        (2, 32, 4, 4, 128, 40, None),  # ring wrap (positions past T)
        (1, 64, 8, 2, 128, 64, None),  # GQA G=4, full
        (2, 32, 4, 1, 128, 20, None),  # MQA
        (2, 32, 4, 4, 128, 30, 8),  # sliding window
        (1, 16, 2, 2, 128, 0, None),  # empty cache -> out == v_new-ish
        (2, 24, 4, 4, 128, 24, None),  # T not a power of two (bk halving)
    ],
)
def test_parity_vs_xla(B, T, Hq, Hkv, D, n_valid, window):
    q, kc, vc, kn, vn, (q_pos, kv_pos, slots) = _mk(
        B, T, Hq, Hkv, D, n_valid=n_valid
    )
    assert supports(T, Hq, Hkv, D, kc.dtype)
    layer = 1
    want = fresh_kv_decode_attention(
        q, kc[layer], vc[layer], kn, vn, q_pos, kv_pos, slots,
        window=window,
    )
    got = decode_attention(
        q, kc, vc, kn, vn, q_pos, kv_pos, slots, jnp.int32(layer),
        window=window, interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
    )


def test_layer_indexing():
    """Each layer index must read its own slice of the stacked cache."""
    q, kc, vc, kn, vn, (q_pos, kv_pos, slots) = _mk(2, 32, 4, 4, 128, L=4)
    outs = []
    for layer in range(4):
        want = fresh_kv_decode_attention(
            q, kc[layer], vc[layer], kn, vn, q_pos, kv_pos, slots
        )
        got = decode_attention(
            q, kc, vc, kn, vn, q_pos, kv_pos, slots, jnp.int32(layer),
            interpret=True,
        )
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
        )
        outs.append(np.asarray(got))
    # Layers hold different KV, so outputs must differ.
    assert not np.allclose(outs[0], outs[1])


def test_bf16_dtype():
    q, kc, vc, kn, vn, (q_pos, kv_pos, slots) = _mk(
        2, 32, 4, 4, 128, n_valid=16, dtype=jnp.bfloat16
    )
    want = fresh_kv_decode_attention(
        q, kc[0], vc[0], kn, vn, q_pos, kv_pos, slots
    )
    got = decode_attention(
        q, kc, vc, kn, vn, q_pos, kv_pos, slots, jnp.int32(0),
        interpret=True,
    )
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=3e-2, atol=3e-2,
    )


def test_forward_integration_kernel_vs_xla(devices):
    """Full fused decode through DecodeEngine: the stacked-cache kernel path
    (forced via IMPL_OVERRIDE='pallas', interpret mode) must produce the
    same greedy tokens as the XLA fresh-KV path on the same 8-device mesh."""
    import importlib

    import jax

    from llmss_tpu.engine import DecodeEngine, GenerationParams
    from llmss_tpu.models.common import DecoderConfig
    from llmss_tpu.models.decoder import init_params
    from llmss_tpu.parallel import MeshPlan, make_mesh

    attn_mod = importlib.import_module("llmss_tpu.ops.attention")

    cfg = DecoderConfig(
        model_type="llama", vocab_size=128, hidden_size=256, n_layers=2,
        n_heads=8, n_kv_heads=4, head_dim=128, intermediate_size=128,
        max_position_embeddings=64, activation="silu", norm="rmsnorm",
        norm_eps=1e-5, mlp="swiglu", positions="rotary", rope_style="half",
        rotary_dim=128, attn_bias=False, mlp_bias=False,
        tie_word_embeddings=False, dtype="float32",
    )
    mesh = make_mesh(MeshPlan(dp=2, tp=4))
    params = init_params(cfg, mesh, jax.random.key(3))
    prompts = [[5, 9, 23, 40], [3, 14, 15, 9, 26, 5]]
    gen = GenerationParams(max_new_tokens=8, is_greedy=True)

    outs = {}
    old = attn_mod.IMPL_OVERRIDE
    for impl in ("xla", "pallas"):
        attn_mod.IMPL_OVERRIDE = impl
        try:
            engine = DecodeEngine(cfg, params, mesh, max_seq_len=64)
            outs[impl] = engine.generate_fused(prompts, gen)
        finally:
            attn_mod.IMPL_OVERRIDE = old
    assert outs["xla"] == outs["pallas"], outs
