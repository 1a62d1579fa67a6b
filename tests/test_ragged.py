"""Ragged mixed prefill+decode dispatch (ISSUE 10).

Two layers of evidence that one ragged program can serve rows at
arbitrary positions — decode rows (``q_len == 1``) and mid-prefill rows
(``q_len`` up to the chunk budget) in the same dispatch (the kernel that
reads the pool in place under it, ``ops/pallas_kv.py``, has its parity
suite in tests/test_pallas_kv.py):

- **engine**: ``_ragged_group`` on an all-decode plan reproduces
  ``_decode_group`` token-for-token, and a chunked 32-token feed
  reproduces the ``_prefill`` + ``_decode_group`` stream;
- **scheduler**: ``ContinuousBatcher(chunked_prefill=...)`` emits the
  exact token streams of the split prefill/decode path on dense,
  sampled, and shared-prefix traces; prewarm compiles NO per-(P, S)
  prefill executables; steady state holds zero recompiles under
  CompileGuard.
"""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llmss_tpu.analysis.compile_guard import CompileGuard
from llmss_tpu.engine import DecodeEngine, GenerationParams
from llmss_tpu.engine.scheduler import ContinuousBatcher
from llmss_tpu.models.common import DecoderConfig
from llmss_tpu.models.decoder import init_params
from llmss_tpu.parallel import MeshPlan, make_mesh

attn = importlib.import_module("llmss_tpu.ops.attention")


# --------------------------------------------------------------------------
# Engine and scheduler (8-device dp=2 x tp=4 mesh, XLA ragged path)
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def mesh(devices):
    return make_mesh(MeshPlan(dp=2, tp=4))


@pytest.fixture(scope="module")
def cfg():
    # The engine runs the XLA ragged oracle: the numerics under test are
    # the dispatch structure, not the kernel (tests/test_pallas_kv.py).
    return DecoderConfig(
        model_type="llama", vocab_size=128, hidden_size=64, n_layers=2,
        n_heads=8, n_kv_heads=4, head_dim=8, intermediate_size=128,
        max_position_embeddings=256, activation="silu", norm="rmsnorm",
        norm_eps=1e-5, mlp="swiglu", positions="rotary", rope_style="half",
        rotary_dim=8, attn_bias=False, mlp_bias=False,
        tie_word_embeddings=False, dtype="float32",
    )


@pytest.fixture(scope="module")
def params(cfg, mesh):
    return init_params(cfg, mesh, jax.random.key(0))


def _paged_engine(cfg, params, mesh, **kw):
    return DecodeEngine(
        cfg, params, mesh, max_seq_len=64, kv_layout="paged", block_size=8,
        **kw,
    )


@pytest.mark.parametrize("kv_dtype", [None, "int8"])
def test_engine_all_decode_matches_decode_group(cfg, params, mesh, kv_dtype):
    """An all-decode plan (q_len == 1, no feeds, every step emitting)
    through _ragged_group reproduces _decode_group's packed tokens and
    counters exactly — the unified dispatch costs nothing on the pure
    decode steady state. With an int8 pool both steps read values AND
    scales out of the stack by layer, and still agree."""
    eng = _paged_engine(cfg, params, mesh, kv_dtype=kv_dtype)
    nB = 4
    gen = GenerationParams(max_new_tokens=8, is_greedy=True)
    sa = eng._sample_args([gen] * nB, nB)
    prompts = [[5, 9, 23, 40], [3, 14, 15, 9], [7, 7, 7, 7], [1, 2, 3, 4]]
    ids = jnp.asarray(prompts, jnp.int32)
    lens = jnp.asarray([len(p) for p in prompts], jnp.int32)
    eos = jnp.full(nB, -1, jnp.int32)

    cache = eng.new_paged_cache(nB, num_blocks=64, identity=True)
    tok, _, cache = eng._prefill(eng.params, ids, cache, lens, sa)
    packed, *_rest = eng._decode_group(
        eng.params, tok, cache, lens, sa, jnp.zeros(nB, bool), eos,
        n_chunks=6, n_steps=1, t_bucket=None,
    )
    curA = _rest[2]
    toksA = np.asarray(packed)[: 6 * nB].reshape(6, nB)

    # lens was donated into _decode_group above — rebuild from host data.
    lens2 = jnp.asarray([len(p) for p in prompts], jnp.int32)
    cache2 = eng.new_paged_cache(nB, num_blocks=64, identity=True)
    tok2, _, cache2 = eng._prefill(eng.params, ids, cache2, lens2, sa)
    cur2 = jnp.asarray([len(p) for p in prompts], jnp.int32)
    nc, cb = 6, 4
    packedR, *_restR = eng._ragged_group(
        eng.params, tok2, cache2, cur2, sa, jnp.zeros(nB, bool), eos,
        jnp.zeros((nc, nB, cb), jnp.int32), jnp.ones((nc, nB), jnp.int32),
        jnp.zeros((nc, nB), bool), jnp.ones((nc, nB), bool),
    )
    curR = _restR[2]
    toksR = np.asarray(packedR)[: nc * nB].reshape(nc, nB)
    assert np.array_equal(toksA, toksR)
    assert np.array_equal(np.asarray(curA), np.asarray(curR))


def test_engine_chunked_feed_matches_prefill_stream(cfg, params, mesh):
    """Feeding a 32-token prompt through _ragged_group in CB=4 chunks
    (emit on the final feed step, then plain decode steps) reproduces the
    _prefill + _decode_group token stream."""
    eng = _paged_engine(cfg, params, mesh)
    prompt = list(range(2, 34))
    gen = GenerationParams(max_new_tokens=8, is_greedy=True)
    sa = eng._sample_args([gen], 1)

    cacheS = eng.new_paged_cache(1, num_blocks=64, identity=True)
    tokS, _, cacheS = eng._prefill(
        eng.params, jnp.asarray([prompt], jnp.int32), cacheS,
        jnp.asarray([len(prompt)], jnp.int32), sa,
    )
    first_tok = int(np.asarray(tokS)[0])
    packedS, *_ = eng._decode_group(
        eng.params, tokS, cacheS, jnp.asarray([len(prompt)], jnp.int32),
        sa, jnp.zeros(1, bool), jnp.full(1, -1, jnp.int32),
        n_chunks=5, n_steps=1, t_bucket=None,
    )
    split_stream = [first_tok] + [
        int(x) for x in np.asarray(packedS)[:5].reshape(5)
    ]

    cb, nc = 4, 13  # 8 feed steps + 5 decode steps
    ids_seq = np.zeros((nc, 1, cb), np.int32)
    qlens = np.ones((nc, 1), np.int32)
    feed = np.zeros((nc, 1), bool)
    emit = np.zeros((nc, 1), bool)
    for c in range(8):
        ids_seq[c, 0] = prompt[c * cb : (c + 1) * cb]
        qlens[c, 0] = cb
        feed[c, 0] = True
        emit[c, 0] = c == 7
    emit[8:, 0] = True
    cacheC = eng.new_paged_cache(1, num_blocks=64, identity=True)
    packedC, *_ = eng._ragged_group(
        eng.params, jnp.zeros(1, jnp.int32), cacheC,
        jnp.zeros(1, jnp.int32), sa, jnp.zeros(1, bool),
        jnp.full(1, -1, jnp.int32), jnp.asarray(ids_seq),
        jnp.asarray(qlens), jnp.asarray(feed), jnp.asarray(emit),
    )
    chunk_stream = [int(x) for x in np.asarray(packedC)[7:nc].reshape(6)]
    assert split_stream == chunk_stream


PROMPTS = [
    list(range(2, 34)),       # 32 tokens — chunked across many steps
    [5, 9, 23],
    [7, 7, 7, 7, 7, 7, 7],
    [40, 41, 42, 43, 44],
]
GENS = [
    GenerationParams(max_new_tokens=8, is_greedy=True),
    GenerationParams(max_new_tokens=6, is_greedy=True),
    GenerationParams(max_new_tokens=5, is_greedy=True),
    GenerationParams(max_new_tokens=7, is_greedy=False, seed=3,
                     temperature=0.9, top_k=20),
]


def _run_trace(cfg, params, mesh, chunked):
    b = ContinuousBatcher(
        _paged_engine(cfg, params, mesh), rows=4, chunk_steps=2,
        group_chunks=2, chunked_prefill=4 if chunked else None,
    )
    outs = {}
    for i, (p, g) in enumerate(zip(PROMPTS, GENS)):
        b.submit(p, g, lambda toks, i=i, **kw: outs.__setitem__(i, toks))
    b.run_until_idle()
    return outs


def test_scheduler_chunked_matches_split(cfg, params, mesh):
    """The chunked-admission batcher must emit the exact token streams of
    the split prefill/decode batcher — greedy AND seeded-sampled rows —
    on a dense-prompt trace with a long prompt riding decode steps."""
    split = _run_trace(cfg, params, mesh, chunked=False)
    chunk = _run_trace(cfg, params, mesh, chunked=True)
    assert split == chunk, (split, chunk)


def test_scheduler_shared_prefix_chunked_matches_split(cfg, params, mesh):
    """Shared-prefix rows (full shared block + COW tail) re-feed only the
    unshared span under chunked prefill; token streams stay identical to
    the split path."""
    shared = list(range(3, 3 + 13))  # 1 full block + 5-token COW tail
    suffixes = [[20, 21, 22], [30], [40, 41, 42, 43, 44, 45]]
    gen = GenerationParams(max_new_tokens=6, is_greedy=True)

    def run(chunked):
        eng = _paged_engine(cfg, params, mesh)
        pfx = eng.build_prefix(shared)
        b = ContinuousBatcher(
            eng, rows=4, chunk_steps=2, group_chunks=2,
            chunked_prefill=4 if chunked else None,
        )
        outs = {}
        for i, s in enumerate(suffixes):
            b.submit(shared + s, gen,
                     lambda toks, i=i, **kw: outs.__setitem__(i, toks),
                     prefix=pfx)
        b.run_until_idle()
        return outs

    assert run(False) == run(True)


def test_prewarm_shrink_and_zero_steady_state_recompiles(cfg, params, mesh):
    """Under chunked prefill the (P, S) prefill ladder is gone: prewarm
    compiles ZERO prefill executables, and a mixed workload (long chunked
    prompt + short prompts) triggers no steady-state recompiles."""
    eng = _paged_engine(cfg, params, mesh)
    b = ContinuousBatcher(eng, rows=4, chunk_steps=2, group_chunks=2,
                          chunked_prefill=4)
    b.prewarm()
    assert b._prefill_row._cache_size() == 0
    guard = CompileGuard({
        **vars(eng),
        "sched_prefill_row": b._prefill_row,
        "sched_merge_positions": b._merge_positions,
    })
    with guard.steady_state():
        outs = {}
        for i, (p, g) in enumerate(zip(PROMPTS[:3], GENS[:3])):
            b.submit(p, g, lambda toks, i=i, **kw: outs.__setitem__(i, toks))
        b.run_until_idle()
    assert sorted(outs) == [0, 1, 2]


def test_mixed_batch_metrics(cfg, params, mesh):
    """The ragged dispatch stamps mixed-batch composition into
    EngineMetrics: chunked prompt tokens, decode vs prefill row-steps,
    and chunk-budget utilization."""
    b = ContinuousBatcher(_paged_engine(cfg, params, mesh), rows=4,
                          chunk_steps=2, group_chunks=2, chunked_prefill=4)
    got = {}
    b.submit(PROMPTS[0], GENS[0], lambda toks, **kw: got.__setitem__(0, toks))
    b.run_until_idle()
    mb = b.engine.metrics.to_dict()["mixed_batch"]
    assert mb["steps"] > 0
    assert mb["prefill_tokens_chunked"] == len(PROMPTS[0])
    assert 0 < mb["chunk_budget_utilization"] <= 1
    assert mb["decode_rows"] + mb["prefill_rows"] > 0


def test_chunked_prefill_requires_paged(cfg, params, mesh):
    dense = DecodeEngine(cfg, params, mesh, max_seq_len=64)
    with pytest.raises(ValueError, match="paged"):
        ContinuousBatcher(dense, rows=2, chunked_prefill=4)
    eng = _paged_engine(cfg, params, mesh)
    with pytest.raises(ValueError):
        ContinuousBatcher(eng, rows=2, chunked_prefill=0)
