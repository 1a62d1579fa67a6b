"""Bucketed decode cache reads: decode HBM traffic follows live context.

The decode step reads only ring slots ``[0, t_bucket)`` when the engine can
prove no row has (or will) wrap past the bucket — the throughput lever that
makes a generously provisioned ring free (round 5, PROFILE.md@e57f952).
These tests pin the semantics: bucketed and full-ring decode produce *bitwise identical* logits
(masked slots contribute exact zeros to every reduction), the bucket policy
refuses wrapped rows, and the whole serving envelope stays single-compile.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llmss_tpu.engine import DecodeEngine, GenerationParams
from llmss_tpu.models.common import DecoderConfig
from llmss_tpu.models.decoder import forward, init_params
from llmss_tpu.parallel import MeshPlan, make_mesh


def _cfg(**kw):
    base = dict(
        model_type="llama", vocab_size=64, hidden_size=32, n_layers=2,
        n_heads=4, n_kv_heads=4, head_dim=8, intermediate_size=64,
        max_position_embeddings=64, activation="silu", norm="rmsnorm",
        norm_eps=1e-5, mlp="swiglu", positions="rotary", rope_style="half",
        rotary_dim=8, attn_bias=False, mlp_bias=False,
        tie_word_embeddings=False, dtype="float32",
    )
    base.update(kw)
    return DecoderConfig(**base)


@pytest.fixture(scope="module")
def setup(devices):
    mesh = make_mesh(MeshPlan(dp=2, tp=4))
    cfg = _cfg()
    params = init_params(cfg, mesh, jax.random.key(0))
    return cfg, params, mesh


def test_ladder_and_policy(setup):
    cfg, params, mesh = setup
    eng = DecodeEngine(cfg, params, mesh, max_seq_len=64)
    assert eng.bucket_ladder() == [32]
    assert eng.decode_bucket(10) == 32
    assert eng.decode_bucket(32) == 32
    assert eng.decode_bucket(33) is None  # no entry covers it -> full ring
    assert eng.decode_bucket(64) is None
    assert eng.decode_bucket(65) is None  # wrapped rows: full-ring semantics


def test_buckets_env_disable(setup, monkeypatch):
    cfg, params, mesh = setup
    monkeypatch.setenv("LLMSS_BUCKETS", "0")
    eng = DecodeEngine(cfg, params, mesh, max_seq_len=64)
    assert eng.bucket_ladder() == []
    assert eng.decode_bucket(4) is None


def test_bucketed_decode_bitwise_logit_parity(setup):
    """A bucketed decode step must equal the full-ring step: the excluded
    slots contribute exp(-inf)=0 terms to every reduction. (Mathematically
    identical; tolerance only for XLA re-tiling reductions per shape —
    observed diffs are ~1e-10 on fp32 logits.)"""
    cfg, params, mesh = setup
    eng = DecodeEngine(cfg, params, mesh, max_seq_len=64)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(1, 64, 9).tolist() for _ in range(4)]
    ids, lens = eng._pad_prompts(prompts)
    sa = eng._sample_args(GenerationParams(), 4)

    def one_step(t_bucket):
        cache = eng.new_cache(4)
        tok, _, cache = eng._prefill(
            eng.params, jnp.asarray(ids), cache, jnp.asarray(lens), sa,
        )
        _, logits, cache = eng._decode(
            eng.params, tok, cache, jnp.asarray(lens), sa, t_bucket=t_bucket,
        )
        return np.asarray(logits), cache

    full, cache_full = one_step(None)
    bucketed, cache_b = one_step(32)
    np.testing.assert_allclose(full, bucketed, rtol=0, atol=1e-6)
    # The write path is untouched: full buffers updated identically.
    np.testing.assert_allclose(
        np.asarray(cache_full.k), np.asarray(cache_b.k), rtol=0, atol=1e-6
    )
    np.testing.assert_array_equal(
        np.asarray(cache_full.positions), np.asarray(cache_b.positions)
    )


def test_bucketed_generate_token_parity(setup, monkeypatch):
    cfg, params, mesh = setup
    eng_b = DecodeEngine(cfg, params, mesh, max_seq_len=64)
    monkeypatch.setenv("LLMSS_BUCKETS", "0")
    eng_f = DecodeEngine(cfg, params, mesh, max_seq_len=64)
    assert eng_b._ladder and not eng_f._ladder
    prompts = [[5, 9, 23, 40], [3, 14, 15, 9, 26, 5], [7], [2, 4]]
    for gen in (
        GenerationParams(max_new_tokens=20, is_greedy=True),
        GenerationParams(
            max_new_tokens=20, is_greedy=False, temperature=0.9, top_k=8,
            top_p=0.9, seed=3,
        ),
    ):
        a = eng_b.generate(prompts, gen, chunk_steps=4)
        b = eng_f.generate(prompts, gen, chunk_steps=4)
        assert a == b
        assert eng_b.generate_fused(prompts, gen) == b


def test_generate_crossing_bucket_boundary_and_wrap(setup):
    """Tokens must be identical as pos crosses the 32-slot bucket boundary
    (bucket -> full-ring switch) and then the ring wrap itself."""
    cfg, params, mesh = setup
    eng = DecodeEngine(cfg, params, mesh, max_seq_len=32)
    prompts = [[5, 9, 23, 40]]
    gen = GenerationParams(max_new_tokens=40, is_greedy=True)  # wraps at 32
    out_chunked = eng.generate(prompts, gen, chunk_steps=4)
    out_single = eng.generate(prompts, gen)
    assert out_chunked == out_single


def test_worker_prewarm_compiles_each_executable_once(setup):
    """Worker-path prewarm covers the full envelope with ONE compile per
    executable signature: generate()/generate_fused() carry canon-resharded
    state, so no steady-state call may key a fresh compile (the round-3
    double-compile workaround is retired)."""
    cfg, params, mesh = setup
    eng = DecodeEngine(cfg, params, mesh, max_seq_len=64)
    n = eng.prewarm(4, chunk_steps=4)
    # prefill buckets (16, 32, 64) + decode x (None, 32) + chunk x (None, 32)
    assert n == 3 + 2 + 2
    sizes = {
        "prefill": eng._prefill._cache_size(),
        "decode": eng._decode._cache_size(),
        "decode_group": eng._decode_group._cache_size(),
    }
    prompts = [[5, 9, 23, 40], [3, 14, 15, 9, 26, 5], [7], [2, 4]]
    gen = GenerationParams(max_new_tokens=30, is_greedy=True)
    eng.generate(prompts, gen, chunk_steps=4)
    eng.generate(prompts, gen)  # single-step path
    # fused with n_steps inside the prewarmed chunk envelope (a fused call
    # with an arbitrary max_new compiles its own n_steps by design)
    eng.generate_fused(prompts, GenerationParams(
        max_new_tokens=5, is_greedy=True,
    ))
    assert eng._prefill._cache_size() == sizes["prefill"]
    assert eng._decode._cache_size() == sizes["decode"]
    assert eng._decode_group._cache_size() == sizes["decode_group"]


def test_submit_rejects_ring_overflow(setup):
    from llmss_tpu.engine.scheduler import ContinuousBatcher

    cfg, params, mesh = setup
    eng = DecodeEngine(cfg, params, mesh, max_seq_len=32)
    b = ContinuousBatcher(eng, rows=2, chunk_steps=2)
    with pytest.raises(ValueError, match="max_seq_len"):
        b.submit([1] * 20, GenerationParams(max_new_tokens=20), lambda t: None)
    # At exactly the ring size it must be accepted.
    got = []
    b.submit(
        [1] * 20, GenerationParams(max_new_tokens=12, is_greedy=True),
        lambda t: got.append(t),
    )
    b.run_until_idle()
    assert len(got) == 1 and len(got[0]) == 12
