"""Keye-VL-2.0's language model (grouped-query attention over a learned
top-k selection of the context, softmax-routed experts with no shared one)
at a small size on the CPU, ``topk`` 16 so that the selection really drops
positions at test lengths: the program against the plain reference
(``benchmark/reference/KeyeVL2.py``, the same file the benchmark uses) in
both compute types, whole and as one chip's share of the experts; the
selection itself against the reference's, position by position; contexts of
at most ``topk`` against dense attention; the third pool; the four shares
adding up; the counters through the batcher; the published checkpoint names;
what ``config_from_hf`` and the engine refuse. Weights are the family's own
seeded draw (``init_params``), norm scales + 1 as the benchmark's server
makes them."""

import dataclasses
import importlib
import importlib.util
import json
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llmss_tpu.engine import DecodeEngine, GenerationParams
from llmss_tpu.engine.cache import export_blocks, import_blocks
from llmss_tpu.engine.scheduler import ContinuousBatcher
from llmss_tpu.models import decoder
from llmss_tpu.models.decoder import forward_ragged, init_params
from llmss_tpu.models.registry import MODEL_REGISTRY, config_from_hf
from llmss_tpu.ops import sparse_attention as dsa
from llmss_tpu.ops.layers import NormParams
from llmss_tpu.parallel import MeshPlan, make_mesh
from llmss_tpu.utils import trace

attention_mod = importlib.import_module("llmss_tpu.ops.attention")

ROOT = Path(__file__).resolve().parent.parent

# The published flags on small sizes: 3 layers of one kind, 4 query heads on
# 2 KV heads of 16, an indexer of 4 heads of 8 that keeps 16 positions, 8
# experts top-2 of width 32 and no shared one.
HF = dict(
    model_type="KeyeVL2", vocab_size=512, hidden_size=64,
    intermediate_size=128, num_hidden_layers=3, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, hidden_act="silu",
    max_position_embeddings=256, rms_norm_eps=1e-6, tie_word_embeddings=False,
    rope_theta=10000000, attention_bias=False, decoder_sparse_step=1,
    rope_scaling={"mrope_section": [2, 3, 3], "rope_type": "default",
                  "type": "default"},
    mlp_only_layers=[], moe_intermediate_size=32, num_experts=8,
    num_experts_per_tok=2, norm_topk_prob=True, use_sliding_window=False,
    sliding_window=None,
    sa_config={"indexer_head_dim": 8, "indexer_num_heads": 4,
               "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
               "q_chunk_size": 512, "topk": 16},
)
TOPK = HF["sa_config"]["topk"]


def share(chip, chips=4):
    """``HF`` as chip ``chip`` of ``chips`` holds it: a quarter of the
    experts, the router whole."""
    return {**HF, "num_experts": HF["num_experts"] // chips,
            "expert_parallel": {"num_experts": HF["num_experts"],
                                "chips": chips, "chip": chip}}


def wide(hf=HF, indexer_heads=8):
    """``hf`` with heads of 128 and 8 indexer heads, which
    ``ops/pallas_dsa.py`` takes: the engine that ``force_impl("pallas")``
    serves through ``dsa.kernel`` and ``idx.kernel`` (with 4 indexer heads,
    out of the walk's envelope: ``dsa.kernel`` over gathered scores)."""
    return {**hf, "head_dim": 128,
            "rope_scaling": {**hf["rope_scaling"],
                             "mrope_section": [16, 24, 24]},
            "sa_config": {**hf["sa_config"],
                          "indexer_num_heads": indexer_heads}}


def dense(hf=HF):
    """``hf`` with a ``topk`` no context reaches: nothing is ever dropped."""
    return {**hf, "sa_config": {**hf["sa_config"], "topk": 1 << 20}}


# float32 is benchmark/lib/check.py's (accumulation order: read 3e-6 here).
# bfloat16 reads 0.04-0.06 at this size, under the harness's 0.15.
TOL = {"float32": 2e-3, "bfloat16": 0.15}
MAX_LEN = 128


def _reference():
    path = ROOT / "benchmark" / "reference" / "KeyeVL2.py"
    spec = importlib.util.spec_from_file_location("ref_keye_vl2", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()


def unit_norm_scales(params):
    return jax.tree.map(
        lambda p: p._replace(scale=p.scale + 1) if isinstance(p, NormParams)
        else p,
        params, is_leaf=lambda p: isinstance(p, NormParams),
    )


@pytest.fixture(scope="module")
def mesh(devices):
    return make_mesh(MeshPlan(tp=1), devices=devices[:1])


def make_engine(mesh, dtype="float32", hf=HF, seed=3, params=None):
    cfg = config_from_hf(types.SimpleNamespace(**hf), dtype=dtype)
    if params is None:
        params = unit_norm_scales(init_params(cfg, mesh, jax.random.key(seed)))
    return DecodeEngine(
        cfg, params, mesh, kv_layout="paged", max_seq_len=MAX_LEN
    )


_ENGINES = {}


def hf_of(held="all", read="xla"):
    hf = HF if held == "all" else share(1)
    return wide(hf) if read == "kernel" else hf


def engine_of(mesh, dtype="float32", held="all", read="xla"):
    """One engine a compute type, share and read for the whole module (its
    jits compile once): all 8 experts held, or chip 1 of 4's experts 2-3;
    ``read`` "kernel": heads of 128, to be driven under ``reading(read)``."""
    if (dtype, held, read) not in _ENGINES:
        _ENGINES[dtype, held, read] = make_engine(
            mesh, dtype, hf_of(held, read))
    return _ENGINES[dtype, held, read]


def reading(read):
    """The context a test drives ``engine_of(.., read=read)`` in: the kernel
    forced (interpreted on the CPU), or the XLA forms as the CPU chooses."""
    return attention_mod.force_impl("pallas" if read == "kernel" else None)


@pytest.fixture(scope="module")
def engine(mesh):
    return engine_of(mesh)


def prompts_of(lens, seed=0, distinct=True):
    """Random prompts with no token twice in a prompt: two positions with
    one token have one indexer key up to float32 rounding, so their scores
    tie up to rounding, and which of the two a query keeps when the pair
    straddles rank ``topk`` is the reduction order's to say, in program and
    reference alike (counted in test_the_selection_agrees_...; one position
    of 16 is a lot here and a 2,048th at the published ``topk``).
    ``distinct`` False: tokens drawn independently."""
    rng = np.random.default_rng(seed)
    V = HF["vocab_size"]
    if distinct:
        return [rng.permutation(V)[:n].tolist() for n in lens]
    return [rng.integers(0, V, n).tolist() for n in lens]


_REF_RUNS = {}


def _ref_run(hf):
    """The reference's jitted full forward for ``hf`` (one compile each)."""
    key = json.dumps(hf, sort_keys=True)
    if key not in _REF_RUNS:
        @jax.jit
        def run(params, ids, last):
            with jax.default_matmul_precision("highest"):
                h = REF.embed(hf, params, ids)
                for kind, lp in REF.layers(hf, params):
                    h = REF.layer(hf, kind, lp, h)
                return REF.head(hf, params, h[jnp.arange(ids.shape[0]), last])

        _REF_RUNS[key] = run
    return _REF_RUNS[key]


def ref_logits(params, seqs, hf=HF):
    """The reference's logits after the last token of each sequence: one
    full forward, float32; sequences padded at the END to MAX_LEN (causal,
    so padding reaches no earlier token)."""
    ids = np.zeros((len(seqs), MAX_LEN), np.int32)
    for i, seq in enumerate(seqs):
        ids[i, : len(seq)] = seq
    last = jnp.asarray([len(seq) - 1 for seq in seqs])
    return np.asarray(_ref_run(hf)(params, jnp.asarray(ids), last))


def err(got, ref):
    return float(np.max(np.abs(got - ref).max(-1) / ref.std(-1)))


def prefill(engine, prompts):
    ids, lens = engine._pad_prompts(prompts)
    sa = engine._sample_args(GenerationParams(is_greedy=True), len(prompts))
    tok, logits, cache = engine._prefill(
        engine.params, jnp.asarray(ids), engine.new_paged_cache(len(prompts)),
        jnp.asarray(lens), sa,
    )
    return tok, np.asarray(logits), cache, jnp.asarray(lens), sa


def decode_run(eng, prompts, steps, at, t_bucket=None):
    """A bucketed prefill of ``prompts`` and ``steps`` cached steps:
    ``{step: (logits, sequences so far)}`` for the prefill (0) and the steps
    ``at``. Each step is fed a token its row has not held yet (not the one
    sampled: see ``prompts_of``)."""
    _, logits, cache, pos, sa = prefill(eng, prompts)
    seqs = [list(p) for p in prompts]
    unused = [[t for t in range(HF["vocab_size"]) if t not in set(p)]
              for p in prompts]
    out = {0: (logits, [list(s) for s in seqs])}
    for step in range(1, steps + 1):
        tok = jnp.asarray([u[7 * step] for u in unused], jnp.int32)
        for s, t in zip(seqs, np.asarray(tok).tolist()):
            s.append(t)
        _, logits, cache = eng._decode(
            eng.params, eng.canon_vec(tok), eng.canon_cache(cache),
            eng.canon_vec(pos), sa, t_bucket=t_bucket,
        )
        pos = pos + 1
        if step in at:
            out[step] = (np.asarray(logits), [list(s) for s in seqs])
    return out


@pytest.mark.parametrize("read", ["xla", "kernel"])
@pytest.mark.parametrize("held", ["all", "a_share"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_prefill_then_cached_steps_match_reference(mesh, dtype, held, read):
    """Prompts of unequal length, all longer than ``topk`` but one, through
    one bucketed prefill (the selection as a mask within the prompt), then
    12 decode steps through the three pools (the selection's kept tokens
    read by token, or, ``read`` "kernel", both pools walked in place under
    the selection as bits: ``dsa.kernel`` forced, interpreted): the logits
    of the prefill and of steps 1, 2 and 12 against the reference's full
    forward of prompt + tokens so far; with all 8 experts held, and as chip
    1 of 4 (the reference given the same share)."""
    hf = hf_of(held, read)
    eng = engine_of(mesh, dtype, held, read)
    with reading(read):
        assert decoder.attn_read(eng.cfg, eng.new_paged_cache(1), mesh, 1) == (
            "dsa.kernel" if read == "kernel" else "dsa.tokens")
        assert decoder.index_read(eng.cfg, eng.new_paged_cache(1), mesh, 1) == (
            "idx.kernel" if read == "kernel" else "gather")
        run = decode_run(eng, prompts_of([21, 60, 37, 9]), 12, (1, 2, 12))
    errors = {step: err(logits, ref_logits(eng.params, seqs, hf))
              for step, (logits, seqs) in run.items()}
    assert max(errors.values()) < TOL[dtype], errors


def test_the_selection_is_what_the_logits_rest_on(mesh, engine):
    """The comparison sees the selection: the same weights served dense
    (``topk`` beyond any context) miss the reference by far more than the
    tolerance at contexts over ``topk``, and the control's fault (the
    indexer's key projection lost: every score ties, the first ``topk``
    positions are kept) does too."""
    prompts = prompts_of([60, 50, 45, 33], seed=5)
    want = ref_logits(engine.params, prompts)
    assert err(prefill(engine, prompts)[1], want) < TOL["float32"]
    served_dense = make_engine(mesh, hf=dense(), params=engine.params)
    assert err(prefill(served_dense, prompts)[1], want) > 0.15
    fault, lost = REF.control(engine.params)
    assert fault == "indexer_key_lost"
    ctl = make_engine(mesh, params=lost)
    assert err(prefill(ctl, prompts)[1], want) > 0.15
    # and the reference with the same fault agrees with the program: ties
    # fall to the earlier position on both sides
    assert err(prefill(ctl, prompts)[1], ref_logits(lost, prompts)) < 2e-3


def mixed_step_logits(eng, prompts, CB, extra_rows=0, pools=False):
    """Prompts fed through ``forward_ragged`` ``CB`` tokens a row a step
    (rows of unequal length, so late steps mix a row that still feeds with
    rows that are idle), then each row's first decoded token through the
    same program. ``extra_rows`` rows beside them are never live. Returns
    ``(logits of the decoded step [B, V], sequences, (moe, dsa) counts)``
    summed over all steps; with ``pools``, the cache after them."""
    B, R = len(prompts), len(prompts) + extra_rows
    cache = eng.new_paged_cache(R)
    fed = [0] * B
    seqs = [list(p) for p in prompts]
    final = {}
    counts = [np.zeros(3, np.int64), np.zeros(4, np.int64)]
    step = jax.jit(
        lambda params, cache, *a, **k: _ragged(eng, params, cache, *a, **k))
    while any(f < len(s) for f, s in zip(fed, seqs)):
        ids = np.full((R, CB), 7, np.int32)
        q_lens = np.zeros((R,), np.int32)
        for i, s in enumerate(seqs):
            chunk = s[fed[i]: fed[i] + CB]
            ids[i, : len(chunk)], q_lens[i] = chunk, len(chunk)
        rel = np.arange(CB)[None]
        live = rel < q_lens[:, None]
        positions = np.asarray(fed + [5] * extra_rows)[:, None] + rel
        logits, cache, moe, sel = step(
            eng.params, cache, jnp.asarray(ids),
            jnp.asarray(positions, jnp.int32),
            jnp.asarray(np.where(live, positions, MAX_LEN), jnp.int32),
            jnp.asarray(np.maximum(q_lens, 1)),
            jnp.asarray(np.where(live, positions, -1), jnp.int32),
        )
        counts[0] += np.asarray(moe)
        counts[1] += np.asarray(sel)
        for i in range(B):
            fed[i] += int(q_lens[i])
            if not q_lens[i] or fed[i] < len(seqs[i]):
                continue
            if len(seqs[i]) == len(prompts[i]):
                # the prompt is in: its next chunk is the token it picked
                seqs[i].append(int(np.argmax(np.asarray(logits)[i, 0])))
            else:
                final[i] = np.asarray(logits)[i, 0]
    assert sorted(final) == list(range(B))
    out = np.stack([final[i] for i in range(B)]), seqs, counts
    return (*out, cache) if pools else out


def _ragged(eng, params, cache, ids, positions, slots, q_lens, kv_pos):
    aux = {}
    logits, cache = forward_ragged(
        eng.cfg, params, ids, positions, cache, slots, q_lens,
        kv_write_positions=kv_pos, aux=aux,
    )
    return logits, cache, aux["moe_counts"], aux["dsa_counts"]


@pytest.mark.parametrize("read", ["xla", "kernel"])
@pytest.mark.parametrize("held", ["all", "a_share"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_mixed_step_matches_the_reference(mesh, dtype, held, read):
    """Logits, not tokens, of the one step program a cell with
    ``chunked_prefill`` times: chunks of 8, so that the chunk ``[16, 24)``
    straddles ``topk`` (its first position keeps all 16 it sees but one, its
    last drops 8) and the chunk's own fresh tokens compete with the cached
    ones, beside a done row and a padding row. The counts: every live token
    adds ``top_k`` pairs a layer; a row's last live query scored its whole
    context and kept at most ``topk`` of it. ``read`` "kernel": the same
    through ``dsa.kernel`` (forced, interpreted), every row's words made by
    ``chunk_selection`` from the scores of the walk (``idx.kernel``)."""
    hf = hf_of(held, read)
    eng = engine_of(mesh, dtype, held, read)
    prompts = prompts_of([45, 12, 61, 30], seed=4)
    with reading(read):
        assert decoder.index_read(eng.cfg, eng.new_paged_cache(1), mesh, 8) == (
            "idx.kernel" if read == "kernel" else "gather")
        got, seqs, (moe, sel) = mixed_step_logits(
            eng, prompts, 8, extra_rows=2)
    assert err(got, ref_logits(eng.params, seqs, hf)) < TOL[dtype]
    tokens = sum(map(len, seqs))
    assert moe[0] + moe[2] == tokens * 2 * 3
    scored, kept, dense_rows, rows = (int(n) for n in sel)
    # a row-step a chunk of 8 and one for the decoded token, 3 layers each
    steps = [-(-len(p) // 8) + 1 for p in prompts]
    assert rows == 3 * sum(steps)
    ends = [e for p in prompts
            for e in [*range(8, len(p), 8), len(p), len(p) + 1]]
    assert scored == 3 * sum(ends)
    assert kept == 3 * sum(min(e, TOPK) for e in ends)
    assert dense_rows == 3 * sum(e <= TOPK for e in ends)


@pytest.mark.parametrize("step", ["decode", "mixed"])
def test_indexer_heads_out_of_the_walks_envelope_score_gathered_views(
    mesh, step,
):
    """``index_read`` adapts to shapes by itself: 4 indexer heads are not
    whole sublane tiles, so under ``dsa.kernel`` the selection gathers the
    rows' views of the indexer pool and scores them in XLA, as it did before
    the walk (PR 48), and the step still matches the reference."""
    hf = wide(indexer_heads=4)
    eng = make_engine(mesh, hf=hf)
    with reading("kernel"):
        cache = eng.new_paged_cache(1)
        for chunk in (1, 8):
            assert decoder.attn_read(eng.cfg, cache, mesh, chunk) == "dsa.kernel"
            assert decoder.index_read(eng.cfg, cache, mesh, chunk) == "gather"
        if step == "decode":
            logits, seqs = decode_run(eng, prompts_of([21, 37]), 2, (2,))[2]
        else:
            logits, seqs, _ = mixed_step_logits(
                eng, prompts_of([29, 12], seed=4), 8, extra_rows=1)
    assert err(logits, ref_logits(eng.params, seqs, hf)) < TOL["float32"]


def test_a_context_of_at_most_topk_is_dense_attention_bit_for_bit(mesh, engine):
    """While a query sees at most ``topk`` positions nothing is dropped, and
    the result IS dense attention: the program with ``topk`` 16 and the same
    program with a ``topk`` no context reaches give the same bits, in the
    prefill, in the mixed step and in the cached step; one position more and
    they part. (A decode step whose read bucket holds at most ``topk`` slots
    runs ``paged_decode_attention`` as every dense family does.)"""
    served_dense = make_engine(mesh, hf=dense(), params=engine.params)
    short, long_ = prompts_of([16, 9, 13, 4], seed=7), prompts_of([18, 9], seed=7)
    a, b = prefill(engine, short)[1], prefill(served_dense, short)[1]
    np.testing.assert_array_equal(a, b)
    assert np.abs(prefill(engine, long_)[1][0]
                  - prefill(served_dense, long_)[1][0]).max() > 1e-4
    within = prompts_of([14, 9, 6], seed=8)  # 14 + a decoded token <= 16
    a, b = (mixed_step_logits(e, within, 8)[0] for e in (engine, served_dense))
    np.testing.assert_array_equal(a, b)
    # the cached step, by token where the bucket is the ring's 128 slots and
    # through the dense read where it is 16: the same set either way
    run = lambda e, tb: decode_run(e, prompts_of([9, 12], seed=9), 3, (3,), tb)[3][0]
    np.testing.assert_array_equal(run(engine, 16), run(served_dense, 16))
    np.testing.assert_allclose(
        run(engine, None), run(served_dense, None), atol=2e-5)


def test_keep_topk_counts_exactly_and_ties_go_to_the_earlier():
    """Exactly ``min(candidates, k)`` are kept; of equal scores the lower
    index; the two zeros are one value; ``-inf`` is never kept."""
    inf = np.inf
    s = jnp.asarray([
        [1.0, 3.0, 3.0, 3.0, 2.0, 3.0, -inf, 0.5],
        [0.0, -0.0, 0.0, -0.0, -1.0, -inf, -inf, -inf],
        [-inf, 5.0, -inf, -inf, -inf, -inf, -inf, -inf],
        [2.0] * 8,
    ], jnp.float32)
    keep = np.asarray(dsa.keep_topk(s, 3))
    assert keep.tolist() == [
        [False, True, True, True, False, False, False, False],
        [True, True, True, False, False, False, False, False],
        [False, True, False, False, False, False, False, False],
        [True, True, True, False, False, False, False, False],
    ]
    rng = np.random.default_rng(0)
    x = rng.standard_normal((64, 300)).astype(np.float32)
    x[rng.random(x.shape) < 0.3] = -np.inf
    x = np.round(x, 1)  # many ties
    keep = np.asarray(dsa.keep_topk(jnp.asarray(x), 40))
    order = np.argsort(-x, axis=-1, kind="stable")
    rank = np.argsort(order, axis=-1, kind="stable")
    np.testing.assert_array_equal(keep, (rank < 40) & np.isfinite(x))


def _running_count_keep(scores, k):
    """The oracle of ``keep_topk``'s tie cut: the ``k``-th largest value from
    a sort, and of the values equal to it the first ``room`` by a RUNNING
    COUNT (``keep_topk``'s own last line until PR 53)."""
    x = np.where(scores == 0, np.float32(0), scores)  # the two zeros are one
    N = x.shape[-1]
    thr = np.sort(x, axis=-1)[..., ::-1][..., min(k, N) - 1:min(k, N)]
    above, tie = x > thr, x == thr
    room = k - above.sum(-1, keepdims=True)
    return (above | (tie & (np.cumsum(tie, axis=-1) <= room))) & (x > -np.inf)


def _tie_cases():
    rng = np.random.default_rng(53)
    normal = lambda *shape: rng.standard_normal(shape).astype(np.float32)
    straddle = normal(6, 40)
    straddle[:, 3::4] = 0.25  # ten equal scores a row, and the cut among them
    straddle[:, ::4] = np.abs(straddle[:, ::4]) + 1.0
    few = normal(5, 50)
    few[:, 7:] = -np.inf
    few[2, :] = -np.inf
    return {
        "tie_group_straddles_the_cut": (straddle, 14),
        "every_score_equal": (np.full((3, 77), 1.5, np.float32), 20),
        "fewer_candidates_than_k": (few, 16),
        "k_at_least_n": (np.round(normal(4, 24), 1), 24),
        "k_beyond_n": (np.round(normal(4, 24), 1), 100),
        "n_is_one": (np.asarray([[0.5], [-np.inf], [0.0]], np.float32), 3),
        "n_one_under_a_power_of_two": (np.round(normal(4, 127), 0), 50),
        "n_a_power_of_two": (np.round(normal(4, 128), 0), 50),
        "n_one_over_a_power_of_two": (np.round(normal(4, 129), 0), 50),
        "the_cells_width_rounded_to_two_places": (
            np.round(normal(2, 3, 16928), 2), 2048),
        "tie_free_normal": (normal(8, 1000), 64),
    }


_TIE_CASES = _tie_cases()


@pytest.mark.parametrize("scores,k", _TIE_CASES.values(), ids=list(_TIE_CASES))
def test_keep_topk_is_the_running_count_bit_for_bit(scores, k):
    """``keep_topk`` cuts its tie group at an INDEX found by a second
    bisection (PR 53); the set is the running count's, bit for bit."""
    keep = np.asarray(jax.jit(dsa.keep_topk, static_argnums=1)(
        jnp.asarray(scores), k))
    np.testing.assert_array_equal(keep, _running_count_keep(scores, k))
    assert (keep.sum(-1) == np.minimum(np.isfinite(scores).sum(-1), k)).all()


def test_keep_topk_holds_no_running_count():
    """No ``cumsum`` / ``cumlogsumexp`` / ``reduce_window`` at any depth of
    ``keep_topk``'s jaxpr: on the chip one running count over a mixed
    step's ``[7, 32, 16,928]`` cost what 175 compare-and-count passes do."""
    def primitives(jaxpr):
        for eqn in jaxpr.eqns:
            yield eqn.primitive.name
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from primitives(sub)

    jaxpr = jax.make_jaxpr(lambda s: dsa.keep_topk(s, 2048))(
        jnp.zeros((7, 32, 16928), jnp.float32))
    names = set(primitives(jaxpr.jaxpr))
    assert {"scan", "reduce_sum"} <= names, names  # the walk sees the loops
    assert not [n for n in names
                if n.startswith(("cum", "reduce_window"))], names


def _recorded_selections(eng, prompts, monkeypatch):
    """The keep masks the program's prefill computes, ``[L][B, S, S]``
    (within the prompt: the cache is empty), recorded through a callback in
    ``keep_topk``."""
    seen = []
    real = dsa.keep_topk

    def recording(scores, k):
        keep = real(scores, k)
        jax.debug.callback(lambda m: seen.append(np.asarray(m)), keep,
                           ordered=True)
        return keep

    monkeypatch.setattr(dsa, "keep_topk", recording)
    fresh = make_engine(
        mesh_of(eng), str(eng.cfg.compute_dtype), params=eng.params)
    prefill(fresh, prompts)
    jax.effects_barrier()
    # layer by layer, rows in order (one call a layer, or one a turn of rows)
    B, S = len(prompts), seen[0].shape[-2]
    flat = np.concatenate([m.reshape(-1, S, m.shape[-1]) for m in seen])
    return list(flat[..., -S:].reshape(-1, B, S, S))


def mesh_of(eng):
    return eng.mesh


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_selection_agrees_with_the_reference_position_by_position(
    mesh, dtype, monkeypatch,
):
    """The hazard, counted (docs/sparse-attention.md): over 4 prompts of 128
    distinct tokens and 3 layers, 1,536 query-layers with up to 112
    positions dropped each and 23,136 positions kept in all, the program
    keeps the positions the float32 reference keeps, in float32 AND in
    bfloat16: the indexer reads the block of the residual that no matrix
    writes, in float32, before any rounding. Read here over three seeds
    (CPU, PR 46): 0 of 69,408 positions chosen otherwise in either compute
    type. With tokens drawn independently (128 of a vocabulary of 512: many
    repeats) two positions with one token have one key up to rounding, a tie
    up to rounding, and 26 (float32) and 34 (bfloat16) of 69,408 differed;
    a five-hundredth is allowed for."""
    eng = engine_of(mesh, dtype)
    for distinct, allowed in ((True, 0), (False, 23136 // 500)):
        prompts = prompts_of([128] * 4, seed=11, distinct=distinct)
        got = _recorded_selections(eng, prompts, monkeypatch)
        monkeypatch.undo()
        ids = jnp.asarray(prompts)
        with jax.default_matmul_precision("highest"):
            h, want = REF.embed(HF, eng.params, ids), []
            for kind, lp in REF.layers(HF, eng.params):
                x = REF._rms(h, lp["ln1"].scale, 1e-6)
                want.append(np.asarray(REF._selection(HF, lp, x, 0, 128)))
                h = REF.layer(HF, kind, lp, h)
        assert len(got) == len(want) == 3
        kept = sum(int(w.sum()) for w in want)
        assert kept == 3 * 4 * sum(min(t + 1, TOPK) for t in range(128))
        differ = sum(int((g != w).sum()) for g, w in zip(got, want)) // 2
        assert differ <= allowed, (dtype, distinct, differ, kept)


def test_the_third_pool_is_written_paged_freed_and_exported(mesh, engine):
    """The indexer's keys lie in a float32 pool of their own under the row's
    block table: a prefill writes the prompt's slots and no other, a step
    writes the token's, the blocks leave with ``export_blocks`` and come
    back with ``import_blocks``, and the batcher's rows give their blocks
    back when they finish."""
    cache = engine.new_paged_cache(2)
    assert cache.idx.shape == (3, 2 * MAX_LEN // 16, 16, 128)  # 8 of 128
    assert cache.idx.dtype == jnp.float32
    assert cache.k.shape == (3, 16, 16, 2, 16)
    prompts = prompts_of([21, 40])
    tok, _, cache, pos, sa = prefill(engine, prompts)
    idx = np.asarray(cache.idx)  # identity tables: row b owns blocks 8b..
    assert not idx[..., 8:].any()  # the row's tail stays zero
    written = np.abs(idx).sum(-1).reshape(3, 2, MAX_LEN) > 0
    # the bucket's padding slots hold what padding tokens computed, under a
    # position of -1; the prompt's own slots are all written
    assert written[:, 0, :21].all() and written[:, 1, :40].all()
    assert not written[:, :, 64:].any()
    _, _, cache2 = engine._decode(
        engine.params, engine.canon_vec(tok), engine.canon_cache(cache),
        engine.canon_vec(pos), sa,
    )
    step = np.abs(np.asarray(cache2.idx) - idx).sum(-1).reshape(3, 2, MAX_LEN)
    assert (step[:, 0] > 0).sum(-1).tolist() == [1, 1, 1]
    assert np.flatnonzero(step[0, 0]).tolist() == [21]
    out = export_blocks(cache2, [0, 1], 22)
    assert out["idx"].shape == (3, 2, 16, 128) and out["idx"].dtype == np.float32
    np.testing.assert_array_equal(out["idx"][:, 0], np.asarray(cache2.idx)[:, 0])
    assert not out["idx"][:, 1, 6:].any()  # past the 22 tokens: zeroed
    empty = engine.new_paged_cache(2)
    back = import_blocks(
        empty, out["k"], out["v"], None, None, jnp.asarray([3, 4]),
        idx=out["idx"])
    np.testing.assert_array_equal(np.asarray(back.idx)[:, 3:5], out["idx"])
    batcher = ContinuousBatcher(engine, rows=2)
    gens = [GenerationParams(max_new_tokens=4, is_greedy=True)] * 3
    run_batcher(batcher, prompts_of([21, 40, 30], seed=2), gens)
    assert batcher.allocator.blocks_in_use == 0
    assert batcher.cache.idx.shape == (3, 16, 16, 128)


def _expert_layer(eng, x):
    """The program's expert layer 1 on ``x`` [T, E] as its normed input, and
    the counts."""
    bp = jax.tree.map(lambda a: a[1], eng.params["blocks"])
    y, counts = decoder._routed_mlp(
        eng.cfg, bp, x[None], x[None], jnp.ones((1, x.shape[0]), bool),
        (eng.params["experts"], jnp.int32(1)),
    )
    return np.asarray(y[0]), np.asarray(counts)


def test_the_four_shares_add_up_to_the_uncut_layer(mesh):
    """The tie between the share and the model: the routed parts that chips
    0..3 compute (each a quarter of the experts, the router whole; there is
    no shared expert to count once) add up to the uncut reference's whole
    layer, and every pair is computed on exactly one chip. The reference's
    own shares add up the same way."""
    whole = make_engine(mesh)
    x = jax.random.normal(jax.random.key(9), (40, HF["hidden_size"]))
    lp = list(REF.layers(HF, whole.params))[1][1]
    with jax.default_matmul_precision("highest"):
        want = np.asarray(REF._experts(HF, lp, x[None])[0])
    got, ref_sum, pairs = 0.0, 0.0, 0
    for chip in range(4):
        hf = share(chip)
        cut = lambda a: a[:, 2 * chip: 2 * chip + 2]
        params = {**whole.params,
                  "experts": jax.tree.map(cut, whole.params["experts"])}
        eng = make_engine(mesh, hf=hf, params=params)
        y, counts = _expert_layer(eng, x)
        got, pairs = got + y, pairs + counts[0]
        assert counts[0] + counts[2] == 40 * 2
        with jax.default_matmul_precision("highest"):
            lp_c = {**lp, **jax.tree.map(lambda a: a[1], params["experts"])}
            ref_sum = ref_sum + np.asarray(REF._experts(hf, lp_c, x[None])[0])
    assert pairs == 40 * 2
    np.testing.assert_allclose(got, want, atol=2e-5)
    np.testing.assert_allclose(ref_sum, want, atol=2e-5)
    y, counts = _expert_layer(whole, x)
    np.testing.assert_allclose(y, want, atol=2e-5)
    assert tuple(counts[[0, 2]]) == (80, 0)


def test_the_tree_has_no_leaf_for_a_shared_expert(engine, mesh):
    p = engine.params
    assert not [k for k in p["blocks"] if k.startswith("shared")]
    assert p["blocks"]["router"].w.shape == (3, 8, 64)
    assert p["blocks"]["idx_q"].w.shape == (3, 64, 32)
    assert p["blocks"]["idx_k"].w.shape == (3, 64, 8)
    assert p["blocks"]["idx_w"].w.shape == (3, 64, 4)
    assert p["blocks"]["idx_k_norm"].bias.shape == (3, 8)
    assert p["blocks"]["q_norm"].scale.shape == (3, 16)
    assert p["experts"]["experts_gate"].shape == (3, 8, 64, 32)
    # the indexer, like the router, reads the routing block alone
    R = decoder.routing_block(engine.cfg)
    for name in ("idx_q", "idx_k", "idx_w"):
        assert not np.asarray(p["blocks"][name].w)[:, R:].any()
        assert np.asarray(p["blocks"][name].w)[:, :R].any()
    shared = engine_of(mesh, held="a_share")
    assert shared.params["experts"]["experts_down"].shape == (3, 2, 32, 64)
    assert (shared.cfg.moe.first, shared.cfg.moe.n_held) == (2, 2)


def run_batcher(batcher, prompts, gens):
    got = {}
    for i, (p, g) in enumerate(zip(prompts, gens)):
        batcher.submit(p, g, lambda toks, i=i, **kw: got.__setitem__(i, toks))
    batcher.run_until_idle()
    return [got[i] for i in range(len(prompts))]


FIVE = [GenerationParams(max_new_tokens=n, is_greedy=True)
        for n in (12, 5, 9, 14, 7)]


@pytest.mark.parametrize("chunk", [None, 8])
def test_batcher_rows_match_isolated_and_count_the_selection(mesh, chunk):
    """Five requests of unequal length through two rows, by dedicated
    admission and through the mixed step (8 tokens a row a step): every row
    is freed and re-admitted, groups run with rows that are done. Each
    request's tokens equal its own alone; no executable compiles after
    prewarm; /metrics counts what the indexer scored and kept over the live
    rows, and the bytes a token holds in the third pool. The dedicated
    admission's prompts share ONE bucket (16: a row decodes from inside
    ``topk`` to past it), the only one prewarmed: a prefill program an
    admission count, not four (PR 49)."""
    eng = engine_of(mesh, held="a_share")
    lens = [21, 40, 37, 9, 30] if chunk else [9, 14, 12, 16, 11]
    prompts = prompts_of(lens, seed=2)
    expected = [eng.generate([p], g)[0] for p, g in zip(prompts, FIVE)]
    batcher = ContinuousBatcher(eng, rows=2, chunked_prefill=chunk)
    batcher.prewarm(seq_buckets=[16])
    before = dict(eng.metrics.to_dict()["loop"])
    compiled = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda e, d, **kw: compiled.append(e)
        if e == "/jax/core/compile/backend_compile_duration" else None)
    assert run_batcher(batcher, prompts, FIVE) == expected
    assert not compiled
    loop = eng.metrics.to_dict()["loop"]
    d = {k: loop[k] - before.get(k, 0) for k in loop if "." in k and k != "spans"}
    assert d["dsa.rows"] > 0 and d["dsa.rows"] % 3 == 0
    assert 0 < d["dsa.dense_rows"] < d["dsa.rows"]
    assert d["dsa.rows"] <= d["dsa.kept"] < d["dsa.scored"]
    assert d["dsa.kept"] <= TOPK * d["dsa.rows"]
    assert d["moe.pairs"] > 0 and d["moe.pairs_elsewhere"] > d["moe.pairs"]
    # 3 layers x a lane tile of float32 a token
    assert eng.metrics.to_dict()["cache"]["index_bytes_per_token"] == 3 * 128 * 4


@pytest.mark.parametrize("read", ["xla", "kernel"])
def test_a_mixed_step_works_one_turn_of_feeding_rows(mesh, monkeypatch, read):
    """Where one turn of the mask form holds fewer rows than the batch (the
    byte budget made small here: one row of chunks of 8 over 128 slots), a
    mixed step works every row's first query and ONE row through its whole
    chunk, the batcher admits one prompt at a time (the others wait in the
    queue, rows free or not), and every request's tokens are still its own
    alone. ``read`` "kernel": the same cap under ``dsa.kernel`` (forced,
    interpreted: the one row's words among every row's first-query bits),
    against the tokens the XLA forms give the same weights."""
    monkeypatch.setattr(dsa, "MAP_BYTES", 4 * 4 * 8 * (MAX_LEN + 8))
    eng = make_engine(mesh, hf=hf_of("a_share", read))
    prompts = prompts_of([21, 40, 37, 9, 30], seed=2)
    expected = [eng.generate([p], g)[0] for p, g in zip(prompts, FIVE)]
    with attention_mod.force_impl("pallas" if read == "kernel" else None):
        if read == "kernel":
            eng = make_engine(
                mesh, hf=hf_of("a_share", read), params=eng.params
            )
            cache = eng.new_paged_cache(4)
            assert decoder.attn_read(eng.cfg, cache, mesh, 8) == "dsa.kernel"
            assert decoder.index_read(eng.cfg, cache, mesh, 8) == "idx.kernel"
        batcher = ContinuousBatcher(eng, rows=4, chunked_prefill=8)
        assert batcher._feed_rows == 1
        assert decoder.feed_rows(eng.cfg, batcher.cache, 8) == 1
        feeding = []
        plan = batcher._plan_ragged

        def watched(*a, **k):
            feeding.append(len(batcher._inflight_prefill))
            return plan(*a, **k)

        monkeypatch.setattr(batcher, "_plan_ragged", watched)
        trace.set_enabled(True)
        trace.recorder().clear()
        assert run_batcher(batcher, prompts, FIVE) == expected
        assert feeding and max(feeding) == 1
        # every group's span says how its program read the three pools
        spans = [sp[5] for sp in trace.recorder().loop_spans()
                 if sp[2] == "sched.dispatch"]
        assert spans and {(a["attn_read"], a["index_read"]) for a in spans} <= (
            {("dsa.kernel", "idx.kernel")} if read == "kernel"
            else {("dsa.tokens", "gather"), ("dsa.mask", "gather")})
    # the budget as shipped holds all four rows: no cap
    monkeypatch.undo()
    assert ContinuousBatcher(eng, rows=4, chunked_prefill=8)._feed_rows is None


def _refused(engine, mesh, feature):
    gen = GenerationParams(max_new_tokens=4, is_greedy=True)
    cfg, params = engine.cfg, engine.params
    if feature == "dense_layout":
        DecodeEngine(cfg, params, mesh, max_seq_len=MAX_LEN)
    elif feature == "int8_pool":
        DecodeEngine(cfg, params, mesh, kv_layout="paged",
                     max_seq_len=MAX_LEN, kv_dtype="int8")
    elif feature == "handoff_export":
        ContinuousBatcher(engine, rows=2, prefill_only=True)
    elif feature == "handoff_adopt":
        ContinuousBatcher(engine, rows=2).adopt(
            {}, [1, 2, 3], 3, 1, gen, lambda *a, **k: None)
    elif feature == "prefix_build":
        engine.build_prefix([1, 2, 3, 4])
    elif feature == "session_park":
        ContinuousBatcher(engine, rows=2).request_park("r", [1, 2, 3])
    elif feature == "speculative":
        from llmss_tpu.engine.speculative import generate_speculative

        generate_speculative(engine, [[1, 2, 3]], gen)
    elif feature in ("prefill_worker", "tiered_store"):
        from llmss_tpu.serve.broker import InProcBroker
        from llmss_tpu.serve.consumer import ContinuousWorker

        ContinuousWorker(
            engine, InProcBroker(), tokenizer=None, rows=2,
            **({"role": "prefill"} if feature == "prefill_worker"
               else {"kvstore": object()}),
        )


@pytest.mark.parametrize("feature,match", [
    ("dense_layout", "indexer.*paged"),
    ("int8_pool", "indexer.*int8"),
    ("handoff_export", "indexer"),
    ("handoff_adopt", "indexer"),
    ("prefix_build", "indexer"),
    ("session_park", "indexer"),
    ("speculative", "indexer"),
    ("prefill_worker", "indexer"),
    ("tiered_store", "indexer"),
])
def test_a_feature_that_does_not_carry_the_third_pool_refuses_the_model(
    engine, mesh, feature, match,
):
    """docs/sparse-attention.md: an int8 pool, the dense ring, the hand-off,
    the tiered store, prefix reuse by snapshot and speculative verify refuse
    this family for ITS reason, the pool of indexer keys they do not carry,
    by one predicate on the config (``cfg.indexer``)."""
    with pytest.raises(ValueError, match=match):
        _refused(engine, mesh, feature)


def test_a_mesh_of_more_than_one_device_is_refused(devices, engine):
    """Tensor and sequence parallelism: refused for the indexer by name
    (a dense model with an indexer would meet this; this family's experts
    refuse ``tp`` first)."""
    cfg = dataclasses.replace(engine.cfg, moe=None)
    for plan in (MeshPlan(tp=2), MeshPlan(sp=2)):
        with pytest.raises(ValueError, match="indexer.*tp == 1"):
            DecodeEngine(
                cfg, engine.params, make_mesh(plan, devices=devices[:2]),
                kv_layout="paged", max_seq_len=MAX_LEN,
            )
    with pytest.raises(ValueError, match="routed experts.*tp == 1"):
        DecodeEngine(
            engine.cfg, engine.params,
            make_mesh(MeshPlan(tp=2), devices=devices[:2]),
            kv_layout="paged", max_seq_len=MAX_LEN,
        )


@pytest.mark.parametrize("key,value,match", [
    ("mlp_only_layers", [0], "mlp_only_layers"),
    ("decoder_sparse_step", 2, "decoder_sparse_step"),
    ("rope_scaling", {"rope_type": "yarn", "factor": 4.0}, "rope_scaling"),
    ("use_sliding_window", True, "use_sliding_window"),
    ("attention_bias", True, "attention_bias"),
    ("sa_config", None, "sa_config"),
    ("sa_config", {**HF["sa_config"], "indexer_num_kv_heads": 2},
     "indexer_num_kv_heads"),
    ("expert_parallel", {"num_experts": 16, "chips": 4, "chip": 0},
     "expert_parallel"),
    ("expert_parallel", {"num_experts": 32, "chips": 4, "chip": 4},
     "expert_parallel"),
])
def test_config_refuses_what_is_not_implemented_by_name(key, value, match):
    with pytest.raises(ValueError, match=f"KeyeVL2: .*{match}"):
        config_from_hf(types.SimpleNamespace(**{**HF, key: value}))


def test_config_translation_from_the_benchmarks_file():
    """The configuration file's keys, which are the catalog's beside the
    share, give grouped-query attention with a per-head QK-norm and full
    rotate-half rotary, the indexer's shapes, and 32 of 128 softmax-routed
    experts from expert 0 with no shared one."""
    conf = json.loads(
        (ROOT / "benchmark/configs/keye-vl-2.0-30b-a3b-1chip.json").read_text())
    cfg = config_from_hf(types.SimpleNamespace(**conf))
    assert (cfg.n_layers, cfg.n_kv_layers, cfg.n_state_layers) == (6, 6, 0)
    assert (cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == (32, 4, 128)
    assert (cfg.positions, cfg.rope_style, cfg.rotary_dim) == ("rotary", "half", None)
    assert cfg.rope_theta == 1e7 and cfg.cache_row == (4, 128)
    assert cfg.qk_norm_per_head and not cfg.qk_norm and not cfg.attn_gate
    assert dataclasses.astuple(cfg.indexer) == (16, 64, 2048)
    x = cfg.moe
    assert (x.n_experts, x.n_held, x.first, x.top_k) == (128, 32, 0, 8)
    assert (x.scoring, x.shared_gate, x.norm_topk_prob) == ("softmax", False, True)
    assert (x.expert_size, x.shared_size, x.n_dense_layers) == (768, 0, 0)
    assert not cfg.has_state and cfg.mla is None and cfg.layer_types is None
    # the published file: no share, every expert held
    whole = {**{k: v for k, v in conf.items() if k != "expert_parallel"},
             "num_experts": 128}
    assert config_from_hf(types.SimpleNamespace(**whole)).moe.count is None


def test_the_named_scopes_are_in_the_lowered_programs(engine):
    """docs/observability.md: the indexer's projections (``dsa.index``), the
    decode step's scores, selection, token gather and attention
    (``dsa.decode``), the mixed step's (``dsa.chunk``), beside the router's
    and the experts', are named scopes of the step programs."""
    tok, _, cache, pos, sa = prefill(engine, prompts_of([9, 12]))
    text = engine._decode.lower(
        engine.params, engine.canon_vec(tok), engine.canon_cache(cache),
        engine.canon_vec(pos), sa).as_text(debug_info=True)
    for scope in ("moe.route", "moe.experts", "dsa.index", "dsa.decode",
                  "attn.qk_norm"):
        assert scope in text, scope
    assert "moe.shared" not in text
    assert decoder.attn_read(engine.cfg, cache, engine.mesh, 1) == "dsa.tokens"
    assert decoder.attn_read(engine.cfg, cache, engine.mesh, 8) == "dsa.mask"
    # under ``dsa.kernel`` the two scopes wrap the selection and ONE call
    eng = engine_of(engine.mesh, read="kernel")
    with reading("kernel"):
        tok, _, cache, pos, sa = prefill(eng, prompts_of([9, 12]))
        text = eng._decode.lower(
            eng.params, eng.canon_vec(tok), eng.canon_cache(cache),
            eng.canon_vec(pos), sa).as_text(debug_info=True)
    assert "dsa.index" in text and "dsa.decode" in text


def test_checkpoint_round_trip_under_the_published_names(mesh, tmp_path):
    """``load_params`` reads back, leaf for leaf, a checkpoint written under
    the names as remembered (the backbone's are Qwen3-MoE's, the indexer's
    DeepSeek-V3.2's ``self_attn.indexer.{wq, wk, k_norm, weights_proj}``; no
    checkpoint was read): torch Linear [out, in], one tensor an expert; as
    chip 1 of 4 only its own experts are read."""
    from safetensors.numpy import save_file

    from llmss_tpu.weights import CheckpointShards

    cfg = config_from_hf(types.SimpleNamespace(**HF), dtype="float32")
    params = unit_norm_scales(init_params(cfg, mesh, jax.random.key(5)))
    host = jax.tree.map(np.asarray, params)
    tensors = {
        "model.embed_tokens.weight": host["wte"],
        "model.norm.weight": host["ln_f"].scale,
        "lm_head.weight": np.ascontiguousarray(host["head"].w.T),
    }

    def put(i, name, a):
        tensors[f"model.layers.{i}.{name}"] = np.ascontiguousarray(a)

    for i in range(cfg.n_layers):
        s = jax.tree.map(lambda a: a[i], host["blocks"])
        put(i, "input_layernorm.weight", s["ln1"].scale)
        put(i, "post_attention_layernorm.weight", s["ln2"].scale)
        put(i, "mlp.gate.weight", s["router"].w)
        for key in ("gate", "up", "down"):
            for e in range(8):
                put(i, f"mlp.experts.{e}.{key}_proj.weight",
                    host["experts"][f"experts_{key}"][i, e].T)
        put(i, "self_attn.q_proj.weight", s["q"].w)
        put(i, "self_attn.k_proj.weight", s["k"].w)
        put(i, "self_attn.v_proj.weight", s["v"].w.T)
        put(i, "self_attn.o_proj.weight", s["o"].w.T)
        put(i, "self_attn.q_norm.weight", s["q_norm"].scale)
        put(i, "self_attn.k_norm.weight", s["k_norm"].scale)
        put(i, "self_attn.indexer.wq.weight", s["idx_q"].w.T)
        put(i, "self_attn.indexer.wk.weight", s["idx_k"].w.T)
        put(i, "self_attn.indexer.weights_proj.weight", s["idx_w"].w.T)
        put(i, "self_attn.indexer.k_norm.weight", s["idx_k_norm"].scale)
        put(i, "self_attn.indexer.k_norm.bias", s["idx_k_norm"].bias)
    save_file(tensors, str(tmp_path / "model.safetensors"))
    ckpt = CheckpointShards(
        [str(tmp_path / "model.safetensors")], dtype=np.float32)
    loaded = MODEL_REGISTRY["KeyeVL2"].load_params(ckpt, cfg, mesh)
    assert jax.tree.structure(params) == jax.tree.structure(loaded)
    for (path, a), c in zip(
        jax.tree_util.tree_leaves_with_path(params), jax.tree.leaves(loaded)
    ):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(c), err_msg=str(path))
    cfg1 = config_from_hf(types.SimpleNamespace(**share(1)), dtype="float32")
    mine = MODEL_REGISTRY["KeyeVL2"].load_params(ckpt, cfg1, mesh)
    np.testing.assert_array_equal(
        np.asarray(mine["experts"]["experts_up"]),
        host["experts"]["experts_up"][:, 2:4])
    assert mine["blocks"]["router"].w.shape == (3, 8, 64)


def test_other_families_build_the_tree_and_cache_they_built_before(mesh):
    """``indexer`` None: no ``idx_*`` leaf, no third pool, and a sigmoid- or
    softmax-routed family with a shared expert keeps its leaves."""
    from tests.test_qwen3_next import HF as QWEN

    cfg = config_from_hf(types.SimpleNamespace(**QWEN), dtype="float32")
    shapes = decoder.param_shapes(cfg)
    assert not [k for k in shapes["blocks"] if k.startswith("idx_")]
    assert "shared_gate" in shapes["blocks"] and "shared_sig" in shapes["linear"]
    eng = DecodeEngine(
        cfg, None, mesh, kv_layout="paged", max_seq_len=MAX_LEN)
    assert eng.new_paged_cache(1).idx is None
