"""Qwen3-Next (three gated-delta-rule layers with grouped value heads to every
gated full-attention layer, softmax-routed experts with a gated shared expert
after every one) at a small size on the CPU: the program against the plain
reference (``benchmark/reference/qwen3_next.py``, the same file the benchmark
uses) in both compute types, whole and as one chip's share of the experts;
the four shares adding up to the uncut layer; pairs routed elsewhere; the
state and the counts through the batcher and the mixed step; the published
checkpoint names; what ``config_from_hf`` refuses. Weights are the family's
own seeded draw (``init_params``), norm scales + 1 as the benchmark's server
makes them."""

import dataclasses
import importlib.util
import json
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llmss_tpu.engine import DecodeEngine, GenerationParams
from llmss_tpu.engine.scheduler import ContinuousBatcher
from llmss_tpu.models import decoder
from llmss_tpu.models.decoder import forward_ragged, init_params
from llmss_tpu.models.registry import MODEL_REGISTRY, config_from_hf
from llmss_tpu.ops import gdn, moe
from llmss_tpu.ops.attention import force_impl
from llmss_tpu.ops.layers import NormParams
from llmss_tpu.parallel import MeshPlan, make_mesh
from llmss_tpu.utils import trace

ROOT = Path(__file__).resolve().parent.parent

# The published flags on small sizes: two whole periods; 2 key heads under 4
# value heads of 32; 4 query heads on 2 KV heads of 32, rotary on 8 of them;
# 16 experts top-4 of width 32 and one gated shared expert.
HF = dict(
    model_type="qwen3_next", vocab_size=256, hidden_size=64,
    intermediate_size=128, num_hidden_layers=8, num_attention_heads=4,
    num_key_value_heads=2, head_dim=32, hidden_act="silu",
    max_position_embeddings=256, rms_norm_eps=1e-6, tie_word_embeddings=False,
    full_attention_interval=4, linear_num_key_heads=2,
    linear_num_value_heads=4, linear_key_head_dim=32,
    linear_value_head_dim=32, linear_conv_kernel_dim=4,
    partial_rotary_factor=0.25, rope_theta=10000000, rope_scaling=None,
    decoder_sparse_step=1, mlp_only_layers=[], moe_intermediate_size=32,
    shared_expert_intermediate_size=32, num_experts=16,
    num_experts_per_tok=4, norm_topk_prob=True, use_sliding_window=False,
)


def share(chip, chips=4):
    """``HF`` as chip ``chip`` of ``chips`` holds it: a quarter of the
    experts, the router whole."""
    return {**HF, "num_experts": HF["num_experts"] // chips,
            "expert_parallel": {"num_experts": HF["num_experts"],
                                "chips": chips, "chip": chip}}


# float32 is benchmark/lib/check.py's: accumulation order (read 1e-5 here), and
# what bfloat16 arithmetic under a float32 configuration fails by a factor of
# 30 and more (test_bfloat16_arithmetic_fails_...). bfloat16 is NOT the
# harness's 0.15, which is held on the chip at the published widths (0.06-0.07
# there, PERF.md section 6): at a hidden size of 64 the block of the residual
# that the draw carries exactly is 8 numbers, both mixers read dot products of
# near-orthogonal vectors, which turn an input's 2^-9 into percents, and the
# worst of 4 x 256 logits reads 0.05-0.16 over seeds, prompts and steps. Twice
# that still sits far under what any fault of the model does (the three
# loader-style faults of tools/qwen3_next_check.py read 0.4-4.5 here).
TOL = {"float32": 2e-3, "bfloat16": 0.3}
MAX_LEN = 128


def _reference():
    path = ROOT / "benchmark" / "reference" / "qwen3_next.py"
    spec = importlib.util.spec_from_file_location("ref_qwen3_next", path)
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


def make_engine(mesh, dtype="float32", hf=HF, seed=3):
    cfg = config_from_hf(types.SimpleNamespace(**hf), dtype=dtype)
    params = unit_norm_scales(init_params(cfg, mesh, jax.random.key(seed)))
    return DecodeEngine(
        cfg, params, mesh, kv_layout="paged", max_seq_len=MAX_LEN
    )


_ENGINES = {}


def engine_of(mesh, dtype="float32", held="all"):
    """One engine a compute type and share for the whole module (its jits
    compile once): all 16 experts held, or chip 1 of 4's experts 4-7."""
    if (dtype, held) not in _ENGINES:
        _ENGINES[dtype, held] = make_engine(
            mesh, dtype, HF if held == "all" else share(1))
    return _ENGINES[dtype, held]


@pytest.fixture(scope="module")
def engine(mesh):
    return engine_of(mesh)


@pytest.fixture(scope="module")
def shared_engine(mesh):
    return engine_of(mesh, held="a_share")


def prompts_of(lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, HF["vocab_size"], n).tolist() for n in lens]


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
    full forward, float32, the recurrence token by token; sequences padded
    at the END to MAX_LEN (causal, so padding reaches no earlier token)."""
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


def decode_errors(eng, prompts, steps, at, hf=HF):
    """Errors against the reference of a bucketed prefill of ``prompts`` and
    of the cached steps ``at`` of ``steps``."""
    tok, logits, cache, pos, sa = prefill(eng, prompts)
    errors = {0: err(logits, ref_logits(eng.params, prompts, hf))}
    seqs = [list(p) for p in prompts]
    for step in range(1, steps + 1):
        for s, t in zip(seqs, np.asarray(tok).tolist()):
            s.append(t)
        tok, logits, cache = eng._decode(
            eng.params, eng.canon_vec(tok), eng.canon_cache(cache),
            eng.canon_vec(pos), sa,
        )
        pos = pos + 1
        if step in at:
            errors[step] = err(
                np.asarray(logits), ref_logits(eng.params, seqs, hf))
    return errors


@pytest.mark.parametrize("held", ["all", "a_share"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_prefill_then_cached_steps_match_reference(mesh, dtype, held):
    """Prompts of unequal length through one bucketed prefill (the chunked
    delta rule, padded positions no-ops and routed nowhere), then 16 decode
    steps through both pools: the logits of the prefill and of steps 1, 2
    and 16 against the reference's full forward of prompt + tokens so far;
    with all 16 experts held, and as chip 1 of 4 (the reference given the
    same share)."""
    hf = HF if held == "all" else share(1)
    errors = decode_errors(
        engine_of(mesh, dtype, held), prompts_of([21, 40, 37, 9]), 16,
        (1, 2, 16), hf,
    )
    assert max(errors.values()) < TOL[dtype], errors


def test_bfloat16_arithmetic_fails_the_float32_tolerance(mesh):
    """The float32 tolerance is tight enough to see the compute type: the
    bfloat16 program reads 30 times the bound and more (0.07-0.15 against
    2e-3), the float32 program a hundredth of it (1e-5)."""
    errors = decode_errors(
        engine_of(mesh, "bfloat16"), prompts_of([21, 40, 37, 9]), 1, (1,))
    assert min(errors.values()) > 10 * TOL["float32"], errors


def test_a_bfloat16_state_fails_the_same_comparison(mesh, monkeypatch):
    """And the state's dtype alone: the float32 program with only the delta
    rule's state rounded to bfloat16 after every update (what a bfloat16
    pool would hold) fails it at the cached steps."""
    def rounded(fn):
        def wrapped(*args, **kw):
            o, state = fn(*args, **kw)
            return o, state.astype(jnp.bfloat16).astype(state.dtype)
        return wrapped

    monkeypatch.setattr(decoder, "gdn_step", rounded(gdn.gdn_step))
    monkeypatch.setattr(decoder, "gdn_chunked", rounded(gdn.gdn_chunked))
    eng = make_engine(mesh)  # its jits trace the patched functions
    errors = decode_errors(eng, prompts_of([21, 40, 37, 9]), 8, (1, 8))
    assert min(errors[1], errors[8]) > TOL["float32"], errors


def mixed_step_logits(eng, prompts, CB, extra_rows=0, pools=False):
    """Prompts fed through ``forward_ragged`` ``CB`` tokens a row a step
    (rows of unequal length, so late steps mix a row that still feeds with
    rows that are idle), then each row's first decoded token through the
    same program. ``extra_rows`` rows beside them are never live: a row that
    is done and padding rows (a slot out of range, no position recorded).
    Returns ``(logits of the decoded step [B, V], sequences, counts)``: the
    routing counts summed over all steps; with ``pools``, the cache after
    them. The program is traced anew, under whatever implementation is
    forced now."""
    B, R = len(prompts), len(prompts) + extra_rows
    cache = eng.new_paged_cache(R)
    fed = [0] * B
    seqs = [list(p) for p in prompts]
    final, counts = {}, np.zeros(3, np.int64)
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
        logits, cache, c = step(
            eng.params, cache, jnp.asarray(ids),
            jnp.asarray(positions, jnp.int32),
            jnp.asarray(np.where(live, positions, MAX_LEN), jnp.int32),
            jnp.asarray(np.maximum(q_lens, 1)),
            jnp.asarray(np.where(live, positions, -1), jnp.int32),
        )
        counts += np.asarray(c)
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
    return logits, cache, aux["moe_counts"]


@pytest.mark.parametrize("held", ["all", "a_share"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_mixed_step_matches_the_reference(mesh, dtype, held):
    """Logits, not tokens, of the one step program a cell with
    ``chunked_prefill`` times: chunks of 8 (the delta rule's chunked form
    from a state that is already there, the experts over rows at different
    lengths), beside a done row and a padding row, which are routed nowhere:
    every live token adds ``top_k`` pairs a layer, computed here or counted
    as held elsewhere, and nothing else does."""
    hf = HF if held == "all" else share(1)
    eng = engine_of(mesh, dtype, held)
    prompts = prompts_of([21, 12, 18, 9], seed=4)
    got, seqs, counts = mixed_step_logits(eng, prompts, 8, extra_rows=2)
    assert err(got, ref_logits(eng.params, seqs, hf)) < TOL[dtype]
    tokens = sum(map(len, seqs))
    assert counts[0] + counts[2] == tokens * 4 * 8
    if held == "all":
        assert counts[2] == 0
    else:  # a quarter of the experts: about a quarter of the pairs
        assert 0.1 < counts[0] / (tokens * 4 * 8) < 0.4


@pytest.mark.filterwarnings("ignore:pallas forced")
def test_the_mixed_step_matches_the_reference_through_the_kernel(mesh):
    """The same comparison with each linear layer's state updated where it
    lies (ops/pallas_gdn.py, interpreted; grouped value heads: the kernel
    takes the key heads repeated): the reference's logits within the
    float32 tolerance, and the logits, the routing counts, the state pool
    and the window pool of the XLA path, beside a done row and a padding
    row that the kernel never visits."""
    eng = engine_of(mesh)
    prompts = prompts_of([21, 12, 18, 9], seed=4)
    with force_impl("xla"):
        want, seqs, counts, pools = mixed_step_logits(
            eng, prompts, 8, extra_rows=2, pools=True)
    with force_impl("pallas"):
        assert decoder.state_update(eng.cfg, pools, mesh, 8) == "gdn.kernel"
        got, seqs_k, counts_k, pools_k = mixed_step_logits(
            eng, prompts, 8, extra_rows=2, pools=True)
    assert seqs_k == seqs and counts_k.tolist() == counts.tolist()
    assert err(got, ref_logits(eng.params, seqs)) < TOL["float32"]
    assert err(got, want) < TOL["float32"]
    for a, b in ((pools_k.ssm, pools.ssm), (pools_k.conv, pools.conv)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5
        )
    # the two rows that were never live hold the zeros they started with
    assert not np.asarray(pools_k.ssm)[:, len(prompts):].any()


@pytest.mark.filterwarnings("ignore:pallas forced")
def test_cached_steps_match_reference_through_the_kernel(mesh):
    """The decode step with the kernel at one position (interpreted): steps
    1, 2 and 8 against the reference, as the XLA path above."""
    with force_impl("pallas"):
        eng = make_engine(mesh)
        assert decoder.state_update(
            eng.cfg, eng.new_paged_cache(4), mesh, 1) == "gdn.kernel"
        errors = decode_errors(eng, prompts_of([21, 40, 37, 9]), 8, (1, 2, 8))
    assert max(errors.values()) < TOL["float32"], errors


@pytest.mark.filterwarnings("ignore:pallas forced")
def test_mixed_and_decode_groups_update_the_pool_in_place(mesh, engine):
    """Five requests through two rows, prompts streamed 8 tokens a row a
    step beside rows that decode, rows done beside rows live: with the
    kernel forced on (interpreted) every request's tokens are those of the
    XLA path, the state pool ends where the XLA path's does, and every
    group's ``sched.dispatch`` span says which update its program was
    traced with."""
    prompts = prompts_of([21, 40, 37, 9, 30], seed=7)

    def serve(eng, how):
        batcher = ContinuousBatcher(eng, rows=2, chunked_prefill=8)
        trace.recorder().clear()
        got = run_batcher(batcher, prompts, FIVE)
        spans = [sp[5] for sp in trace.recorder().loop_spans()
                 if sp[2] == "sched.dispatch"]
        assert {a["kind"] for a in spans} == {"ragged_group", "decode_group"}
        assert {a["state_update"] for a in spans} == {how}
        return got, batcher.cache

    was = trace.enabled()
    trace.set_enabled(True)
    try:
        expected, pools = serve(engine, "xla")
        with force_impl("pallas"):
            got, pools_k = serve(make_engine(mesh), "gdn.kernel")
    finally:
        trace.set_enabled(was)
    assert got == expected
    np.testing.assert_allclose(
        np.asarray(pools_k.ssm), np.asarray(pools.ssm), rtol=1e-4, atol=1e-5
    )


def _expert_layer(eng, hf, x):
    """The program's expert layer 2 (a linear-attention layer's) on ``x``
    [T, E] as its normed input, and the counts."""
    bp = jax.tree.map(lambda a: a[2], eng.params["linear"])
    y, counts = decoder._routed_mlp(
        eng.cfg, bp, x[None], x[None], jnp.ones((1, x.shape[0]), bool),
        (eng.params["experts"], jnp.int32(2)),
    )
    return np.asarray(y[0]), np.asarray(counts)


def test_the_four_shares_add_up_to_the_uncut_layer(mesh):
    """The tie between the share and the model: the routed parts that chips
    0..3 compute (each a quarter of the experts, the router whole), with the
    shared expert, which every chip computes alike, counted once, are the
    uncut reference's whole layer; and every pair is computed on exactly one
    chip. The reference's own shares add up the same way."""
    whole = make_engine(mesh)
    x = jax.random.normal(jax.random.key(9), (40, HF["hidden_size"]))
    lp = next(lp for i, (_, lp) in enumerate(REF.layers(HF, whole.params))
              if i == 2)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(REF._experts(HF, lp, x[None])[0])
        shared = np.asarray(jax.nn.sigmoid(x @ lp["shared_sig"].w) * REF._swiglu(
            x, lp["shared_gate"].w, lp["shared_up"].w, lp["shared_down"].w))
    got, ref_sum, pairs = -3 * shared, -3 * shared, 0
    for chip in range(4):
        hf = share(chip)
        cut = lambda a: a[:, 4 * chip: 4 * chip + 4]
        params = {**whole.params,
                  "experts": jax.tree.map(cut, whole.params["experts"])}
        eng = DecodeEngine(
            config_from_hf(types.SimpleNamespace(**hf), dtype="float32"),
            params, mesh, kv_layout="paged", max_seq_len=MAX_LEN)
        y, counts = _expert_layer(eng, hf, x)
        got, pairs = got + y, pairs + counts[0]
        assert counts[0] + counts[2] == 40 * 4
        with jax.default_matmul_precision("highest"):
            lp_c = {**lp, **jax.tree.map(lambda a: a[2], params["experts"])}
            ref_sum = ref_sum + np.asarray(REF._experts(hf, lp_c, x[None])[0])
    assert pairs == 40 * 4
    np.testing.assert_allclose(got, want, atol=2e-5)
    np.testing.assert_allclose(ref_sum, want, atol=2e-5)
    y, counts = _expert_layer(whole, HF, x)
    np.testing.assert_allclose(y, want, atol=2e-5)
    assert tuple(counts[[0, 2]]) == (160, 0)


def test_pairs_held_elsewhere_read_no_weights_and_return_zero():
    """Experts 8-11 are held; every token chose among 0-7: no group has a
    row, so the grouped matmul meets no expert (weights of NaN would poison
    any product) and every token gets zero back; the pairs are counted as
    held elsewhere. With one choice in range, only that pair is computed."""
    T, E, I = 24, 64, 32
    x = jax.random.normal(jax.random.key(0), (T, E))
    nan = lambda *shape: jnp.full(shape, jnp.nan, jnp.float32)
    idx = jnp.asarray(np.random.default_rng(0).integers(0, 8, (T, 2)))
    w = jnp.full((T, 2), 0.5)
    live = jnp.arange(T) < 20
    y, counts = moe.routed_experts(
        x, idx, w, live, nan(4, E, I), nan(4, E, I), nan(4, I, E),
        jax.nn.silu, first=8)
    assert not np.asarray(y).any()
    assert tuple(np.asarray(counts)) == (0, 0, 40)
    k = jax.random.split(jax.random.key(1), 3)
    gate, up = (jax.random.normal(k_, (4, E, I)) * 0.1 for k_ in k[:2])
    down = jax.random.normal(k[2], (4, I, E)) * 0.1
    idx = idx.at[3, 1].set(10).at[22, 0].set(9)  # row 22 is not live
    y, counts = moe.routed_experts(
        x, idx, w, live, gate, up, down, jax.nn.silu, first=8)
    want = 0.5 * (jax.nn.silu(x[3] @ gate[2]) * (x[3] @ up[2])) @ down[2]
    np.testing.assert_allclose(np.asarray(y[3]), np.asarray(want), atol=1e-5)
    assert not np.asarray(y).any(axis=1)[np.arange(T) != 3].any()
    assert tuple(np.asarray(counts)) == (1, 1, 39)


def test_softmax_router_is_the_published_one():
    """``route_softmax``: a float32 softmax over ALL the experts, the
    largest ``top_k``, renormalised over the chosen; no bias, no factor."""
    x = jax.random.normal(jax.random.key(2), (5, 64), jnp.bfloat16)
    wr = jax.random.normal(jax.random.key(3), (16, 64), jnp.bfloat16) * 0.3
    idx, w = moe.route_softmax(x, wr, top_k=4, norm=True)
    p = np.asarray(jax.nn.softmax(
        x.astype(jnp.float32) @ wr.astype(jnp.float32).T, -1))
    order = np.argsort(-p, axis=1)[:, :4]
    np.testing.assert_array_equal(np.asarray(idx), order)
    chosen = np.take_along_axis(p, order, 1)
    np.testing.assert_allclose(
        np.asarray(w), chosen / chosen.sum(1, keepdims=True), rtol=1e-5)
    _, raw = moe.route_softmax(x, wr, top_k=4, norm=False)
    np.testing.assert_allclose(np.asarray(raw), chosen, rtol=1e-5)
    assert w.dtype == jnp.float32


def test_the_two_pools_and_the_three_stacks(engine, shared_engine):
    """Six of eight layers hold a state of 4 VALUE heads, two hold keys and
    values of 2 heads; the experts of all eight layers are one stack, of the
    experts held."""
    cache = engine.new_paged_cache(2)
    assert cache.k.shape[0] == cache.v.shape[0] == 2
    assert cache.k.shape[3:] == (2, 32)
    assert cache.ssm.shape == (6, 2, 4, 32, 32) and cache.ssm.dtype == jnp.float32
    assert cache.conv.shape == (6, 2, 3 * (2 * 64 + 128))
    p = engine.params
    assert p["blocks"]["q"].w.shape == (2, 2 * 128, 64)  # a query and a gate
    assert p["blocks"]["q_norm"].scale.shape == (2, 32)
    assert p["linear"]["gdn_ab"].w.shape == (6, 64, 8)
    assert p["linear"]["router"].w.shape == (6, 16, 64)
    assert p["experts"]["experts_gate"].shape == (8, 16, 64, 32)
    assert shared_engine.params["experts"]["experts_down"].shape == (8, 4, 32, 64)
    assert shared_engine.params["blocks"]["router"].w.shape == (2, 16, 64)
    assert (shared_engine.cfg.moe.first, shared_engine.cfg.moe.n_held) == (4, 4)


def run_batcher(batcher, prompts, gens):
    got = {}
    for i, (p, g) in enumerate(zip(prompts, gens)):
        batcher.submit(p, g, lambda toks, i=i, **kw: got.__setitem__(i, toks))
    batcher.run_until_idle()
    return [got[i] for i in range(len(prompts))]


FIVE = [GenerationParams(max_new_tokens=n, is_greedy=True)
        for n in (12, 5, 9, 14, 7)]


@pytest.mark.parametrize("chunk", [None, 8])
def test_batcher_rows_match_isolated_and_count_their_pairs(shared_engine, chunk):
    """Five requests of unequal length through two rows, by dedicated
    admission and through the mixed step (8 tokens a row a step): every row
    is freed and re-admitted, groups run with rows that are done. Each
    request's tokens equal its own alone; no executable compiles after
    prewarm; /metrics counts the pairs computed here and, for a share, the
    pairs held elsewhere (about three quarters of all). The dedicated
    admission's prompts share ONE bucket (64), the only one prewarmed: a
    prefill program an admission count, not four (3.5 s each at this toy
    size, PR 49)."""
    eng = shared_engine
    lens = [21, 40, 37, 9, 30] if chunk else [37, 40, 33, 61, 50]
    prompts = prompts_of(lens, seed=2)
    expected = [eng.generate([p], g)[0] for p, g in zip(prompts, FIVE)]
    batcher = ContinuousBatcher(eng, rows=2, chunked_prefill=chunk)
    batcher.prewarm(seq_buckets=[64])
    before = dict(eng.metrics.to_dict()["loop"])
    compiled = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda e, d, **kw: compiled.append(e)
        if e == "/jax/core/compile/backend_compile_duration" else None)
    assert run_batcher(batcher, prompts, FIVE) == expected
    assert not compiled
    loop = eng.metrics.to_dict()["loop"]
    d = {k: loop[k] - before.get(k, 0) for k in loop if k.startswith("moe.")}
    assert d["moe.layer_steps"] > 0 and d["moe.layer_steps"] % 8 == 0
    assert 0 < d["moe.experts_hit"] <= d["moe.pairs"]
    total = d["moe.pairs"] + d["moe.pairs_elsewhere"]
    assert total % 4 == 0 and 0.5 < d["moe.pairs_elsewhere"] / total < 0.95
    gauges = eng.metrics.to_dict()["cache"]
    assert (gauges["state_layers"], gauges["kv_layers"]) == (6, 2)


def _refused(engine, mesh, feature):
    gen = GenerationParams(max_new_tokens=4, is_greedy=True)
    if feature == "dense_layout":
        DecodeEngine(engine.cfg, engine.params, mesh, max_seq_len=MAX_LEN)
    elif feature == "handoff_export":
        ContinuousBatcher(engine, rows=2, prefill_only=True)
    elif feature == "prefix_build":
        engine.build_prefix([1, 2, 3, 4])
    elif feature == "session_park":
        ContinuousBatcher(engine, rows=2).request_park("r", [1, 2, 3])
    elif feature == "speculative":
        from llmss_tpu.engine.speculative import generate_speculative

        generate_speculative(engine, [[1, 2, 3]], gen)


@pytest.mark.parametrize("feature", [
    "dense_layout", "handoff_export", "prefix_build", "session_park",
    "speculative",
])
def test_a_feature_that_does_not_carry_the_state_refuses_the_model(
    engine, mesh, feature,
):
    """docs/recurrent-state.md: the hand-off, the tiered store, prefix
    reuse by snapshot and speculation (so the published multi-token
    prediction module too) refuse this family for ITS reason, the recurrent
    state, not for the latent family's."""
    with pytest.raises(ValueError, match="recurrent state"):
        _refused(engine, mesh, feature)


def test_tensor_parallel_is_refused_for_the_experts(devices, engine):
    mesh2 = make_mesh(MeshPlan(tp=2), devices=devices[:2])
    with pytest.raises(ValueError, match="routed experts.*tp == 1"):
        DecodeEngine(
            engine.cfg, engine.params, mesh2, kv_layout="paged",
            max_seq_len=MAX_LEN,
        )


@pytest.mark.parametrize("key,value,match", [
    ("mlp_only_layers", [0], "mlp_only_layers"),
    ("decoder_sparse_step", 2, "decoder_sparse_step"),
    ("rope_scaling", {"type": "yarn", "factor": 4.0}, "rope_scaling"),
    ("use_sliding_window", True, "use_sliding_window"),
    ("attention_bias", True, "attention_bias"),
    ("shared_expert_intermediate_size", 0, "shared expert"),
    ("full_attention_interval", 16, "both kinds"),
    ("layer_types", ["linear_attention"] * 7, "both kinds"),
    ("expert_parallel", {"num_experts": 16, "chips": 4, "chip": 0},
     "expert_parallel"),
])
def test_config_refuses_what_is_not_implemented_by_name(key, value, match):
    with pytest.raises(ValueError, match=f"qwen3_next: .*{match}"):
        config_from_hf(types.SimpleNamespace(**{**HF, key: value}))


def test_config_translation_from_the_benchmarks_file():
    """The configuration file's keys, which are the catalog's beside the
    share, give the two kinds' counts, the period, grouped value heads,
    partial rotate-half rotary, the gated attention with its per-head
    QK-norm, and 128 of 512 softmax-routed experts from expert 0."""
    conf = json.loads(
        (ROOT / "benchmark/configs/qwen3-next-80b-a3b-1chip.json").read_text())
    cfg = config_from_hf(types.SimpleNamespace(**conf))
    assert cfg.period == ("linear_attention",) * 3 + ("full_attention",)
    assert (cfg.n_layers, cfg.n_kv_layers, cfg.n_state_layers) == (8, 2, 6)
    m = cfg.linear_attn
    assert (m.n_heads, m.n_v_heads, m.key_head_dim, m.value_head_dim) == (
        16, 32, 128, 128)
    assert (m.conv_dim, m.value_dim, m.allow_neg_eigval) == (8192, 4096, False)
    assert (cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == (16, 2, 256)
    assert (cfg.positions, cfg.rope_style, cfg.rotary_dim) == ("rotary", "half", 64)
    assert cfg.rope_theta == 1e7 and cfg.cache_row == (2, 256)
    assert cfg.attn_gate and cfg.qk_norm_per_head and not cfg.qk_norm
    assert not cfg.post_norm and cfg.norm_scale_offset == 0.0
    x = cfg.moe
    assert (x.n_experts, x.n_held, x.first, x.top_k) == (512, 128, 0, 10)
    assert (x.scoring, x.shared_gate, x.norm_topk_prob) == ("softmax", True, True)
    assert (x.expert_size, x.shared_size, x.n_dense_layers) == (512, 512, 0)
    assert cfg.has_state and cfg.mla is None and cfg.ssm is None
    # the published file: no share, every expert held
    whole = {k: v for k, v in conf.items() if k != "expert_parallel"}
    assert config_from_hf(types.SimpleNamespace(**whole)).moe.count is None


def test_the_named_scopes_are_in_the_lowered_programs(engine):
    """docs/observability.md: the router, the grouped matmul and the shared
    expert, the four of the delta rule, and this family's own two
    (``attn.gate``, ``attn.qk_norm``) are named scopes of the step
    programs, where a profile's op details show them."""
    tok, _, cache, pos, sa = prefill(engine, prompts_of([9, 12]))
    text = engine._decode.lower(
        engine.params, engine.canon_vec(tok), engine.canon_cache(cache),
        engine.canon_vec(pos), sa).as_text(debug_info=True)
    for scope in ("moe.route", "moe.experts", "moe.shared", "gdn.conv",
                  "gdn.decode", "gdn.gate", "attn.gate", "attn.qk_norm"):
        assert scope in text, scope


def test_checkpoint_round_trip_under_the_published_names(mesh, tmp_path):
    """``load_params`` reads back, leaf for leaf, a checkpoint written under
    the published implementation's names and layouts: ``in_proj_qkvz`` and
    ``in_proj_ba`` interleaved a key head, conv1d [C, 1, K], the
    zero-centred norm scales stored without their 1, torch Linear
    [out, in], one tensor an expert; as chip 1 of 4 only its own experts
    are read."""
    from safetensors.numpy import save_file

    from llmss_tpu.weights import CheckpointShards

    cfg = config_from_hf(types.SimpleNamespace(**HF), dtype="float32")
    params = unit_norm_scales(init_params(cfg, mesh, jax.random.key(5)))
    host = jax.tree.map(np.asarray, params)
    m = cfg.linear_attn
    Hk, r, Dk, Dv = m.n_heads, m.n_v_heads // m.n_heads, 32, 32
    E, kd = 64, m.key_dim
    tensors = {
        "model.embed_tokens.weight": host["wte"],
        "model.norm.weight": host["ln_f"].scale - 1,
        "lm_head.weight": np.ascontiguousarray(host["head"].w.T),
    }

    def put(i, name, a):
        tensors[f"model.layers.{i}.{name}"] = np.ascontiguousarray(a)

    seen = {"linear_attention": 0, "full_attention": 0}
    for i, kind in enumerate(cfg.layer_types):
        j = seen[kind]
        seen[kind] += 1
        s = jax.tree.map(
            lambda a: a[j],
            host["linear" if kind == "linear_attention" else "blocks"])
        put(i, "input_layernorm.weight", s["ln1"].scale - 1)
        put(i, "post_attention_layernorm.weight", s["ln2"].scale - 1)
        put(i, "mlp.gate.weight", s["router"].w)
        put(i, "mlp.shared_expert_gate.weight", s["shared_sig"].w.T)
        for key in ("gate", "up", "down"):
            put(i, f"mlp.shared_expert.{key}_proj.weight",
                s[f"shared_{key}"].w.T)
            for e in range(16):
                put(i, f"mlp.experts.{e}.{key}_proj.weight",
                    host["experts"][f"experts_{key}"][i, e].T)
        if kind == "full_attention":
            put(i, "self_attn.q_proj.weight", s["q"].w)
            put(i, "self_attn.k_proj.weight", s["k"].w)
            put(i, "self_attn.v_proj.weight", s["v"].w.T)
            put(i, "self_attn.o_proj.weight", s["o"].w.T)
            put(i, "self_attn.q_norm.weight", s["q_norm"].scale - 1)
            put(i, "self_attn.k_norm.weight", s["k_norm"].scale - 1)
            continue
        qkv, z = s["gdn_qkv"].w.T, s["gdn_g"].w.T  # [out, E]
        heads = lambda a, width: a.reshape(Hk, width, E)
        put(i, "linear_attn.in_proj_qkvz.weight", np.concatenate([
            heads(qkv[:kd], Dk), heads(qkv[kd:2 * kd], Dk),
            heads(qkv[2 * kd:], r * Dv), heads(z, r * Dv),
        ], axis=1).reshape(-1, E))
        ab = s["gdn_ab"].w.T  # a then b, [2 Hv, E]
        put(i, "linear_attn.in_proj_ba.weight", np.concatenate([
            heads(ab[Hk * r:], r), heads(ab[:Hk * r], r),
        ], axis=1).reshape(-1, E))
        put(i, "linear_attn.conv1d.weight", s["gdn_conv"].w.T[:, None, :])
        put(i, "linear_attn.out_proj.weight", s["gdn_o"].w.T)
        put(i, "linear_attn.A_log", s["gdn_A_log"])
        put(i, "linear_attn.dt_bias", s["gdn_dt_bias"])
        put(i, "linear_attn.norm.weight", s["gdn_norm"].scale)
    save_file(tensors, str(tmp_path / "model.safetensors"))
    ckpt = CheckpointShards(
        [str(tmp_path / "model.safetensors")], dtype=np.float32)
    loaded = MODEL_REGISTRY["qwen3_next"].load_params(ckpt, cfg, mesh)
    assert jax.tree.structure(params) == jax.tree.structure(loaded)
    for (path, a), c in zip(
        jax.tree_util.tree_leaves_with_path(params), jax.tree.leaves(loaded)
    ):
        # a zero-centred scale went through ``w - 1`` and ``w + 1``
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(c), rtol=0, atol=2e-7, err_msg=str(path))
    cfg1 = config_from_hf(types.SimpleNamespace(**share(1)), dtype="float32")
    mine = MODEL_REGISTRY["qwen3_next"].load_params(ckpt, cfg1, mesh)
    np.testing.assert_array_equal(
        np.asarray(mine["experts"]["experts_up"]),
        host["experts"]["experts_up"][:, 4:8])
    assert mine["linear"]["router"].w.shape == (6, 16, 64)


def test_the_share_changes_nothing_but_the_experts_held(mesh):
    """One seed, the whole model and chip 0's share: every leaf outside the
    stacked experts has the same shape, the router scores all 16 in both."""
    whole = config_from_hf(types.SimpleNamespace(**HF))
    mine = config_from_hf(types.SimpleNamespace(**share(0)))
    assert dataclasses.replace(
        mine, moe=dataclasses.replace(mine.moe, count=None)) == whole
    a, b = decoder.param_shapes(whole), decoder.param_shapes(mine)
    assert jax.tree.structure(a) == jax.tree.structure(b)
    differ = [
        jax.tree_util.keystr(p) for (p, x), y in zip(
            jax.tree_util.tree_leaves_with_path(a), jax.tree.leaves(b))
        if x.shape != y.shape
    ]
    assert sorted(differ) == sorted(
        f"['experts']['experts_{k}']" for k in ("gate", "up", "down"))
