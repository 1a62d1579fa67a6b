"""Olmo-Hybrid (three gated-delta-rule layers to every full-attention layer)
at a small size on the CPU: the program against the plain reference
(``benchmark/reference/olmo_hybrid.py``, the same file the benchmark uses),
the chunked form of the delta rule against its one-step form, the recurrent
state through admission, the mixed step, decode, re-admission and replay, the
two pools' layer axes, and every serving feature that must carry the state or
refuse the model. Weights are the family's own seeded draw (``init_params``),
norm scales + 1 as the benchmark's server makes them."""

import importlib.util
import json
import os
import subprocess
import sys
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llmss_tpu.engine import DecodeEngine, GenerationParams
from llmss_tpu.engine.scheduler import ContinuousBatcher
from llmss_tpu.models import decoder
from llmss_tpu.models.decoder import init_params
from llmss_tpu.models.registry import MODEL_REGISTRY, config_from_hf
from llmss_tpu.ops import gdn
from llmss_tpu.ops.attention import force_impl
from llmss_tpu.ops.layers import NormParams
from llmss_tpu.parallel import MeshPlan, make_mesh
from llmss_tpu.utils import trace

ROOT = Path(__file__).resolve().parent.parent

# Two whole periods; heads of 32 keys, so that 40 tokens do not overload a
# head's memory (see ``decoder._gdn_family_draw``).
HF = dict(
    model_type="olmo_hybrid", vocab_size=256, hidden_size=64,
    intermediate_size=128, num_hidden_layers=8, num_attention_heads=2,
    num_key_value_heads=2, hidden_act="silu", max_position_embeddings=256,
    attention_bias=False, rms_norm_eps=1e-6, tie_word_embeddings=False,
    layer_types=(["linear_attention"] * 3 + ["full_attention"]) * 2,
    linear_num_key_heads=2, linear_num_value_heads=2, linear_key_head_dim=32,
    linear_value_head_dim=64, linear_conv_kernel_dim=4,
    linear_allow_neg_eigval=True, rope_parameters={"rope_theta": None},
)
# benchmark/lib/check.py's: float32 is accumulation order (read 1e-5 here);
# bfloat16 is the rounding of the compute dtype through 16 post-normed
# branches (read 0.04-0.11 here over prefill and 64 steps)
TOL = {"float32": 2e-3, "bfloat16": 0.15}
MAX_LEN = 128


def _reference():
    path = ROOT / "benchmark" / "reference" / "olmo_hybrid.py"
    spec = importlib.util.spec_from_file_location("ref_olmo_hybrid", path)
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


def make_engine(mesh, dtype="float32"):
    cfg = config_from_hf(types.SimpleNamespace(**HF), dtype=dtype)
    params = unit_norm_scales(init_params(cfg, mesh, jax.random.key(3)))
    return DecodeEngine(
        cfg, params, mesh, kv_layout="paged", max_seq_len=MAX_LEN
    )


@pytest.fixture(scope="module")
def engine(mesh):
    return make_engine(mesh)


def prompts_of(lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, HF["vocab_size"], n).tolist() for n in lens]


@jax.jit
def _ref_forward(params, ids, last):
    with jax.default_matmul_precision("highest"):
        h = REF.embed(HF, params, ids)
        for kind, lp in REF.layers(HF, params):
            h = REF.layer(HF, kind, lp, h)
        return REF.head(HF, params, h[jnp.arange(ids.shape[0]), last])


def ref_logits(params, seqs):
    """The reference's logits after the last token of each sequence: one
    full forward, float32, the recurrence token by token; sequences padded
    at the END to MAX_LEN (causal, so padding reaches no earlier token)."""
    ids = np.zeros((len(seqs), MAX_LEN), np.int32)
    for i, seq in enumerate(seqs):
        ids[i, : len(seq)] = seq
    last = jnp.asarray([len(seq) - 1 for seq in seqs])
    return np.asarray(_ref_forward(params, jnp.asarray(ids), last))


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


def decode_errors(eng, prompts, steps, at):
    """Errors against the reference of a bucketed prefill of ``prompts`` and
    of the cached steps ``at`` of ``steps``."""
    tok, logits, cache, pos, sa = prefill(eng, prompts)
    errors = {0: err(logits, ref_logits(eng.params, prompts))}
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
            errors[step] = err(np.asarray(logits), ref_logits(eng.params, seqs))
    return errors


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_prefill_then_64_cached_steps_match_reference(mesh, dtype):
    """Prompts of unequal length through one bucketed prefill (the chunked
    form, padded positions no-ops), then 64 decode steps through both pools
    (the one-step form): the logits of the prefill and of steps 1, 2, 32 and
    64 against the reference's full forward of prompt + tokens so far."""
    errors = decode_errors(
        make_engine(mesh, dtype), prompts_of([21, 40, 37, 9]), 64,
        (1, 2, 32, 64),
    )
    assert max(errors.values()) < TOL[dtype], errors


def test_a_bfloat16_state_fails_the_same_comparison(mesh, monkeypatch):
    """The tolerance is tight enough to see the state's dtype: the float32
    program with only the delta rule's state rounded to bfloat16 after every
    update (what a bfloat16 pool would hold) fails it at every cached step
    (0.005-0.006 against the float32 state's 1e-5; the prefill's own logits
    are read before its final state is rounded)."""
    def rounded(fn):
        def wrapped(*args, **kw):
            o, state = fn(*args, **kw)
            return o, state.astype(jnp.bfloat16).astype(state.dtype)
        return wrapped

    monkeypatch.setattr(decoder, "gdn_step", rounded(gdn.gdn_step))
    monkeypatch.setattr(decoder, "gdn_chunked", rounded(gdn.gdn_chunked))
    eng = make_engine(mesh)  # its jits trace the patched functions
    errors = decode_errors(eng, prompts_of([21, 40, 37, 9]), 32, (1, 32))
    assert min(errors[1], errors[32]) > 2 * TOL["float32"], errors
    assert errors[0] < TOL["float32"], errors


def test_padded_batch_gives_each_row_what_it_gets_alone(engine):
    """The padding trap: in a bucket of 64, rows of 9 to 40 tokens. Padded
    positions must leave the state untouched (``g = 0``, ``beta = 0``) and
    the window must be the one at the true length: prefill logits AND the
    next cached step equal the row's own, alone in its bucket of 16 or 64."""
    prompts = prompts_of([21, 40, 37, 9], seed=1)
    tok, logits, cache, pos, sa = prefill(engine, prompts)
    _, step, _ = engine._decode(
        engine.params, engine.canon_vec(tok), engine.canon_cache(cache),
        engine.canon_vec(pos), sa,
    )
    for i, p in enumerate(prompts):
        tok1, logits1, cache1, pos1, sa1 = prefill(engine, [p])
        _, step1, _ = engine._decode(
            engine.params, engine.canon_vec(tok1),
            engine.canon_cache(cache1), engine.canon_vec(pos1), sa1,
        )
        assert err(logits[i:i + 1], logits1) < 2e-5, i
        assert err(np.asarray(step)[i:i + 1], np.asarray(step1)) < 2e-5, i


def test_the_two_pools_have_the_layer_counts_of_their_kinds(engine):
    """Six of eight layers hold a state, two hold keys and values: each pool
    has its own kind's count on its layer axis, and both parameter stacks
    likewise."""
    cache = engine.new_paged_cache(2)
    assert cache.k.shape[0] == cache.v.shape[0] == 2
    assert cache.ssm.shape == (6, 2, 2, 32, 64) and cache.ssm.dtype == jnp.float32
    assert cache.conv.shape == (6, 2, 3 * (2 * 64 + 128))
    assert engine.params["blocks"]["q"].w.shape[0] == 2
    assert engine.params["linear"]["gdn_qkv"].w.shape == (6, 64, 256)
    assert "gdn_qkv" not in engine.params["blocks"]
    assert "q" not in engine.params["linear"]


def run_batcher(batcher, prompts, gens):
    got = {}
    for i, (p, g) in enumerate(zip(prompts, gens)):
        batcher.submit(p, g, lambda toks, i=i, **kw: got.__setitem__(i, toks))
    batcher.run_until_idle()
    return [got[i] for i in range(len(prompts))]


FIVE = [GenerationParams(max_new_tokens=n, is_greedy=True)
        for n in (12, 5, 9, 14, 7)]


def test_batcher_rows_match_isolated_and_readmitted_rows_start_clean(engine):
    """Five requests of unequal length through two rows: every row is freed
    and re-admitted, admissions are bucketed and padded, groups run with
    rows that are done. Each request's tokens equal its own alone, so a
    re-admitted row started from a zero state and a done row's state went
    nowhere. The gauges say what the pools hold."""
    prompts = prompts_of([21, 40, 37, 9, 30], seed=2)
    expected = [engine.generate([p], g)[0] for p, g in zip(prompts, FIVE)]
    batcher = ContinuousBatcher(engine, rows=2)
    assert run_batcher(batcher, prompts, FIVE) == expected
    gauges = engine.metrics.to_dict()["cache"]
    assert gauges["state_bytes"] == (
        batcher.cache.ssm.nbytes + batcher.cache.conv.nbytes)
    # two rows x six layers of a float32 [2, 32, 64] state and a float32
    # window of 3 steps over 2 x 64 + 128 channels
    assert gauges["state_bytes"] == 2 * 6 * (2 * 32 * 64 + 3 * 256) * 4
    assert (gauges["state_layers"], gauges["kv_layers"]) == (6, 2)


@pytest.mark.parametrize("chunk", [4, 8])
def test_the_mixed_step_carries_the_state(engine, chunk):
    """Prompts streamed through the ragged mixed-batch program, ``chunk``
    tokens a step beside rows that decode: a chunk goes on from the row's
    state (the chunked form at a chunk of ``chunk``), columns past the
    chunk's live length are no-ops, and admission zeroes the state of a row
    that another request left behind. Tokens equal each request's own
    alone; no executable compiles after prewarm."""
    prompts = prompts_of([21, 40, 37, 9, 30], seed=7)
    expected = [engine.generate([p], g)[0] for p, g in zip(prompts, FIVE)]
    batcher = ContinuousBatcher(engine, rows=2, chunked_prefill=chunk)
    batcher.prewarm()
    compiled = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda e, d, **kw: compiled.append(e)
        if e == "/jax/core/compile/backend_compile_duration" else None)
    assert run_batcher(batcher, prompts, FIVE) == expected
    assert not compiled


def feed_mixed(engine, prompts, CB):
    """Prompts fed through ``forward_ragged`` ``CB`` tokens a row a step
    (rows of unequal length, so late steps mix a row that still feeds with
    rows that are idle), then each row's first decoded token through the
    same program. Returns ``(that step's logits [B, V], the sequences, the
    cache)``; the program is traced anew, under whatever implementation is
    forced now."""
    from llmss_tpu.models.decoder import forward_ragged

    B = len(prompts)
    cache = engine.new_paged_cache(B)
    fed = [0] * B
    seqs = [list(p) for p in prompts]
    final = {}
    step = jax.jit(lambda params, ids, positions, cache, slots, q_lens, kv:
                   forward_ragged(engine.cfg, params, ids, positions, cache,
                                  slots, q_lens, kv_write_positions=kv))
    while any(f < len(s) for f, s in zip(fed, seqs)):
        ids = np.zeros((B, CB), np.int32)
        q_lens = np.zeros((B,), np.int32)
        for i, s in enumerate(seqs):
            chunk = s[fed[i]: fed[i] + CB]
            ids[i, : len(chunk)], q_lens[i] = chunk, len(chunk)
        rel = np.arange(CB)[None]
        live = rel < q_lens[:, None]
        positions = np.asarray(fed)[:, None] + rel
        logits, cache = step(
            engine.params, jnp.asarray(ids),
            jnp.asarray(positions, jnp.int32), cache,
            jnp.asarray(np.where(live, positions, MAX_LEN), jnp.int32),
            jnp.asarray(np.maximum(q_lens, 1)),
            jnp.asarray(np.where(live, positions, -1), jnp.int32),
        )
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
    return np.stack([final[i] for i in range(B)]), seqs, cache


@pytest.mark.filterwarnings("ignore:pallas forced")
@pytest.mark.parametrize("path", ["xla", "pallas"])
def test_the_mixed_step_matches_the_reference(engine, path):
    """Logits, not tokens: prompts fed through ``forward_ragged`` four
    tokens a row a step, then each row's first decoded token through the
    same program: that step's logits against the reference's full forward
    of prompt + first token; on the XLA path and with the state updated
    where it lies (ops/pallas_gdn.py, interpreted)."""
    with force_impl(path):
        got, seqs, _ = feed_mixed(engine, prompts_of([21, 12, 18, 9], seed=4), 4)
    assert err(got, ref_logits(engine.params, seqs)) < TOL["float32"]


@pytest.mark.filterwarnings("ignore:pallas forced")
@pytest.mark.parametrize("chunk", [4, 8])
def test_the_kernel_leaves_the_pools_the_xla_path_leaves(engine, chunk):
    """The same mixed steps on both paths: the logits, the state pool and
    the window pool agree to float32 rounding (rows that feed, rows that
    decode one token and rows that idle in one step)."""
    prompts = prompts_of([21, 12, 18, 9], seed=5)
    with force_impl("xla"):
        want, seqs, pools = feed_mixed(engine, prompts, chunk)
    with force_impl("pallas"):
        got, seqs_k, pools_k = feed_mixed(engine, prompts, chunk)
    assert seqs_k == seqs
    assert err(got, want) < TOL["float32"]
    assert pools_k.ssm.dtype == jnp.float32
    for a, b in ((pools_k.ssm, pools.ssm), (pools_k.conv, pools.conv)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5
        )


@pytest.mark.filterwarnings("ignore:pallas forced")
def test_cached_steps_match_reference_through_the_kernel(mesh):
    """The decode step with each linear layer's state updated where it lies
    (the kernel at one position, interpreted): steps 1, 2 and 16 against
    the reference, as the XLA path above."""
    with force_impl("pallas"):
        eng = make_engine(mesh)
        assert decoder.state_update(
            eng.cfg, eng.new_paged_cache(4), mesh, 1) == "gdn.kernel"
        errors = decode_errors(eng, prompts_of([21, 40, 37, 9]), 16, (1, 2, 16))
    assert max(errors.values()) < TOL["float32"], errors


@pytest.mark.filterwarnings("ignore:pallas forced")
def test_mixed_and_decode_groups_update_the_pool_in_place(mesh, engine):
    """Five requests through two rows, prompts streamed 4 tokens a row a
    step beside rows that decode, rows done beside rows live, rows freed and
    admitted again: with the kernel forced on (interpreted) every request's
    tokens are those of the XLA path, the pools end where the XLA path's
    do, and every group's ``sched.dispatch`` span says which update its
    program was traced with."""
    prompts = prompts_of([21, 40, 37, 9, 30], seed=7)

    def serve(eng, how):
        batcher = ContinuousBatcher(eng, rows=2, chunked_prefill=4)
        for chunk in (1, 4):
            assert decoder.state_update(
                eng.cfg, batcher.cache, mesh, chunk) == how
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


def test_preempt_and_replay_equals_uninterrupted(engine):
    """A low-priority request evicted mid-decode and resumed by replaying
    prompt + emitted tokens through one prefill (the chunked form rebuilds
    the state the steps had built) ends with the tokens of the unpreempted
    run."""
    gen_low = GenerationParams(max_new_tokens=12, is_greedy=True)
    gen_hi = GenerationParams(max_new_tokens=4, is_greedy=True)
    p_low, p_hi = prompts_of([11, 6], seed=3)
    exp_low = engine.generate([p_low], gen_low)[0]
    exp_hi = engine.generate([p_hi], gen_hi)[0]
    b = ContinuousBatcher(engine, rows=1)
    got, evicted = {}, {}

    def cb(key):
        return lambda toks, **kw: got.__setitem__(key, list(toks))

    b.preempt_cb = lambda rid, toks: evicted.__setitem__(rid, list(toks))
    b.submit(p_low, gen_low, cb("low"), req_id="low", priority=2)
    for _ in range(3):
        b.step()
    b.submit(p_hi, gen_hi, cb("hi"), req_id="hi", priority=0)
    b.step()
    toks = evicted["low"]
    assert 0 < len(toks) < gen_low.max_new_tokens
    b.submit(
        p_low + toks,
        GenerationParams(max_new_tokens=12 - len(toks), is_greedy=True),
        cb("low"), req_id="low", priority=2, replayed=len(toks),
    )
    b.run_until_idle()
    assert got["hi"] == exp_hi
    assert got["low"] == exp_low


@pytest.mark.parametrize("T,chunk", [(5, 8), (8, 8), (13, 8), (33, 16), (4, 64)])
def test_chunked_form_equals_the_one_step_recurrence(T, chunk):
    """``gdn_chunked`` against ``gdn_step`` token by token, from a non-zero
    state, with ``beta`` up to 2 (a negative eigenvalue), decays from none to
    strong, and per-row lengths: positions at or after a row's length have
    ``g = 0`` and ``beta = 0`` and must leave the state as it was."""
    B, H, Dk, Dv = 3, 2, 8, 16
    ks = jax.random.split(jax.random.key(T * 100 + chunk), 6)
    q = gdn.l2_normalize(jax.random.normal(ks[0], (B, T, H, Dk))) * Dk ** -0.5
    k = gdn.l2_normalize(jax.random.normal(ks[1], (B, T, H, Dk)))
    v = jax.random.normal(ks[2], (B, T, H, Dv))
    beta = jax.random.uniform(ks[3], (B, T, H), minval=0.0, maxval=2.0)
    g = -jnp.exp(jax.random.uniform(ks[4], (B, T, H), minval=-6.0, maxval=1.0))
    s0 = jax.random.normal(ks[5], (B, H, Dk, Dv))
    lens = jnp.asarray([T, max(T - 3, 1), 0])
    live = (jnp.arange(T)[None] < lens[:, None])[..., None]
    beta, g = jnp.where(live, beta, 0.0), jnp.where(live, g, 0.0)
    assert float(beta.max()) > 1.5

    o_chunked, s_chunked = gdn.gdn_chunked(q, k, v, g, beta, s0, chunk)
    s, outs = s0, []
    for t in range(T):
        o, s = gdn.gdn_step(q[:, t], k[:, t], v[:, t], g[:, t], beta[:, t], s)
        outs.append(o)
    o_steps = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(s_chunked, s, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(
        jnp.where(live[..., None], o_chunked, 0.0),
        jnp.where(live[..., None], o_steps, 0.0), rtol=2e-4, atol=2e-4)
    np.testing.assert_array_equal(s_chunked[2], s0[2])  # lens 0: untouched


@pytest.mark.parametrize("T", [128, 512])
def test_the_chunk_scan_is_no_loop_of_its_own(T):
    """Several chunks lower to straight-line code: as a loop nested in the
    layer scan, eight chunks of 64 (a 512-token prefill at the published
    widths, three periods) compiled to a program that never returned on a
    v5e, where seven chunks or one period ran (PERF.md section 6, PR 40)."""
    B, H, Dk, Dv = 1, 2, 8, 16
    z = lambda *shape: jnp.zeros(shape, jnp.float32)
    text = jax.jit(gdn.gdn_chunked).lower(
        z(B, T, H, Dk), z(B, T, H, Dk), z(B, T, H, Dv), z(B, T, H), z(B, T, H),
        z(B, H, Dk, Dv)).as_text()
    assert T // gdn.CHUNK > 1 and "stablehlo.while" not in text


def _refused(engine, mesh, feature):
    gen = GenerationParams(max_new_tokens=4, is_greedy=True)
    if feature == "dense_layout":
        DecodeEngine(engine.cfg, engine.params, mesh, max_seq_len=MAX_LEN)
    elif feature == "handoff_export":
        ContinuousBatcher(engine, rows=2, prefill_only=True)
    elif feature == "handoff_adopt":
        ContinuousBatcher(engine, rows=2).adopt(
            "r", 1, 4, {"k": None}, gen, lambda *a, **k: None)
    elif feature == "prefix_build":
        engine.build_prefix([1, 2, 3, 4])
    elif feature == "prefix_submit":
        prefix = types.SimpleNamespace(length=2, tokens=(1, 2))
        ContinuousBatcher(engine, rows=2).submit(
            [1, 2, 3], gen, lambda *a, **k: None, prefix=prefix)
    elif feature == "session_park":
        ContinuousBatcher(engine, rows=2).request_park("r", [1, 2, 3])
    elif feature == "speculative":
        from llmss_tpu.engine.speculative import generate_speculative

        generate_speculative(engine, [[1, 2, 3]], gen)
    elif feature in ("worker_decode_role", "worker_prefill_role",
                     "worker_kvstore"):
        from llmss_tpu.serve.broker import InProcBroker
        from llmss_tpu.serve.consumer import ContinuousWorker

        kw = {"worker_kvstore": {"kvstore": object()},
              "worker_decode_role": {"role": "decode"},
              "worker_prefill_role": {"role": "prefill"}}[feature]
        ContinuousWorker(engine, InProcBroker(), rows=2, **kw)


@pytest.mark.parametrize("feature", [
    "dense_layout", "handoff_export", "handoff_adopt",
    "prefix_build", "prefix_submit", "session_park", "speculative",
    "worker_decode_role", "worker_prefill_role", "worker_kvstore",
])
def test_a_feature_that_does_not_carry_the_state_refuses_the_model(
    engine, mesh, feature,
):
    """docs/recurrent-state.md: what does not carry the recurrent state
    raises for this family by the same errors as for ``falcon_h1`` (one
    predicate, ``DecoderConfig.has_state``); none runs and is silently
    wrong."""
    with pytest.raises(ValueError, match="recurrent state"):
        _refused(engine, mesh, feature)


def test_tp_must_divide_the_heads(devices, engine):
    """Two heads here: ``tp=4`` is refused at construction."""
    mesh4 = make_mesh(MeshPlan(tp=4), devices=devices[:4])
    with pytest.raises(ValueError, match="does not divide the 2 heads"):
        DecodeEngine(
            engine.cfg, engine.params, mesh4, kv_layout="paged",
            max_seq_len=MAX_LEN,
        )


def test_config_translation_from_the_catalogs_keys():
    """The configuration file's keys, which are the catalog's, give the two
    kinds' counts, the period, the published head sizes, no positions, the
    post-norm block with its QK-norm, and a pool of whole head tiles."""
    conf = json.loads(
        (ROOT / "benchmark/configs/olmo-hybrid-7b-1chip.json").read_text())
    cfg = config_from_hf(types.SimpleNamespace(**conf))
    assert cfg.period == ("linear_attention",) * 3 + ("full_attention",)
    assert (cfg.n_layers, cfg.n_kv_layers, cfg.n_state_layers) == (12, 3, 9)
    m = cfg.linear_attn
    assert (m.n_heads, m.key_head_dim, m.value_head_dim, m.d_conv) == (30, 96, 192, 4)
    assert (m.conv_dim, m.allow_neg_eigval) == (11520, True)
    assert (cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == (30, 30, 128)
    assert cfg.positions == "none" and cfg.post_norm and cfg.qk_norm
    assert cfg.has_state and cfg.ssm is None and cfg.mla is None
    assert cfg.cache_row == (32, 128)  # 30 heads padded to whole (16, 128) tiles
    bad = {**conf, "layer_types": conf["layer_types"][:11]}
    with pytest.raises(ValueError, match="layer_types"):
        config_from_hf(types.SimpleNamespace(**bad))
    rotary = {**conf, "rope_parameters": {"rope_theta": 500000.0}}
    with pytest.raises(ValueError, match="rotary"):
        config_from_hf(types.SimpleNamespace(**rotary))


def test_grouped_value_heads_equal_their_key_heads_repeated(mesh):
    """The shared mixer serves more value heads than key heads (PR 44 lifted
    the refusal): 2 key heads under 4 value heads give the logits of the
    ungrouped model of 4 key heads whose query and key projections (and
    their convolution's channels) are each key head's repeated for its two
    value heads, which the reference covers. A count that is no multiple is
    refused by name."""
    grouped = {**HF, "linear_num_value_heads": 4, "linear_value_head_dim": 32}
    cfg = config_from_hf(types.SimpleNamespace(**grouped), dtype="float32")
    assert (cfg.linear_attn.n_heads, cfg.linear_attn.n_v_heads) == (2, 4)
    params = unit_norm_scales(init_params(cfg, mesh, jax.random.key(3)))
    eng = DecodeEngine(cfg, params, mesh, kv_layout="paged", max_seq_len=MAX_LEN)
    assert eng.new_paged_cache(2).ssm.shape == (6, 2, 4, 32, 32)

    def repeated(w):  # [.., q | k | v] columns: each q and k head twice
        q, k, v = jnp.split(w, [64, 128], axis=-1)
        twice = lambda a: jnp.repeat(
            a.reshape(*a.shape[:-1], 2, 32), 2, axis=-2
        ).reshape(*a.shape[:-1], 128)
        return jnp.concatenate([twice(q), twice(k), v], axis=-1)

    lin = params["linear"]
    wide = {**params, "linear": {
        **lin, "gdn_qkv": lin["gdn_qkv"]._replace(w=repeated(lin["gdn_qkv"].w)),
        "gdn_conv": lin["gdn_conv"]._replace(w=repeated(lin["gdn_conv"].w)),
    }}
    cfg4 = config_from_hf(types.SimpleNamespace(
        **{**grouped, "linear_num_key_heads": 4}), dtype="float32")
    eng4 = DecodeEngine(cfg4, wide, mesh, kv_layout="paged", max_seq_len=MAX_LEN)
    prompts = prompts_of([21, 9], seed=5)
    want = prefill(eng4, prompts)[1]
    assert err(want, ref_logits_of(cfg4, wide, prompts, grouped)) < TOL["float32"]
    assert err(prefill(eng, prompts)[1], want) < 2e-5
    with pytest.raises(ValueError, match="multiple of"):
        config_from_hf(types.SimpleNamespace(
            **{**HF, "linear_num_value_heads": 3}))


def ref_logits_of(cfg, params, seqs, hf):
    """``ref_logits`` for another config of this family: ``hf`` with the
    key heads the program's config has."""
    hf = {**hf, "linear_num_key_heads": cfg.linear_attn.n_heads,
          "linear_num_value_heads": cfg.linear_attn.n_v_heads}
    ids = np.zeros((len(seqs), MAX_LEN), np.int32)
    for i, seq in enumerate(seqs):
        ids[i, : len(seq)] = seq
    last = jnp.asarray([len(seq) - 1 for seq in seqs])
    with jax.default_matmul_precision("highest"):
        h = REF.embed(hf, params, jnp.asarray(ids))
        for kind, lp in REF.layers(hf, params):
            h = REF.layer(hf, kind, lp, h)
        return np.asarray(REF.head(hf, params, h[jnp.arange(len(seqs)), last]))


def test_padded_pool_heads_change_nothing(mesh, monkeypatch):
    """A pool of more heads than the model has (30 -> 32 at the published
    widths; forced here): q, k and v are padded with zero heads and the
    padding's output dropped, so the logits are the unpadded program's."""
    prompts = prompts_of([21, 9], seed=5)
    want = prefill(make_engine(mesh), prompts)[1]
    monkeypatch.setattr(
        type(make_engine(mesh).cfg), "pool_kv_heads", property(lambda self: 4))
    wide = make_engine(mesh)
    assert wide.new_paged_cache(2).k.shape[3] == 4
    assert err(prefill(wide, prompts)[1], want) < 2e-5


@pytest.mark.parametrize("model_type,heads,kv,want", [
    ("gpt2", 20, 20, 20), ("gpt2", 25, 25, 25), ("llama", 40, 40, 40),
    ("llama", 40, 8, 8), ("olmo_hybrid", 30, 30, 32), ("olmo_hybrid", 2, 2, 2),
])
def test_only_a_pattern_of_kinds_pads_its_pool_heads(model_type, heads, kv, want):
    """The padding is the period scan's (the one program compiled with it for
    a described v5e): a multi-head family of 20, 25 or 40 heads keeps the
    pool it had, head for head."""
    import dataclasses

    cfg = config_from_hf(types.SimpleNamespace(**HF))
    if model_type != "olmo_hybrid":
        cfg = dataclasses.replace(
            cfg, model_type=model_type, layer_types=None, linear_attn=None)
    cfg = dataclasses.replace(cfg, n_heads=heads, n_kv_heads=kv, head_dim=128)
    assert cfg.pool_kv_heads == want and cfg.cache_row == (want, 128)


def test_checkpoint_round_trip_under_the_published_names(mesh, tmp_path):
    """``load_params`` reads back, leaf for leaf, a checkpoint written under
    the published implementation's names and layouts (torch Linear [out,
    in], conv1d [C, 1, K]; q, k, v and their convolutions as three tensors
    each, a and b as two), the two kinds interleaved as ``layer_types`` has
    them."""
    from safetensors.numpy import save_file

    from llmss_tpu.weights import CheckpointShards

    cfg = config_from_hf(types.SimpleNamespace(**HF), dtype="float32")
    params = init_params(cfg, mesh, jax.random.key(5))
    host = jax.tree.map(np.asarray, params)
    kd, vd, H = 64, 128, 2
    tensors = {
        "model.embed_tokens.weight": host["wte"],
        "model.norm.weight": host["ln_f"].scale,
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
        put(i, "post_attention_layernorm.weight", s["ln1"].scale)
        put(i, "post_feedforward_layernorm.weight", s["ln2"].scale)
        for key in ("gate", "up", "down"):
            put(i, f"mlp.{key}_proj.weight", s[key].w.T)
        if kind == "full_attention":
            put(i, "self_attn.q_proj.weight", s["q"].w)
            put(i, "self_attn.k_proj.weight", s["k"].w)
            put(i, "self_attn.v_proj.weight", s["v"].w.T)
            put(i, "self_attn.o_proj.weight", s["o"].w.T)
            put(i, "self_attn.q_norm.weight", s["q_norm"].scale)
            put(i, "self_attn.k_norm.weight", s["k_norm"].scale)
            continue
        cuts = {"q": (0, kd), "k": (kd, 2 * kd), "v": (2 * kd, 2 * kd + vd)}
        for key, (lo, hi) in cuts.items():
            put(i, f"linear_attn.{key}_proj.weight", s["gdn_qkv"].w[:, lo:hi].T)
            put(i, f"linear_attn.{key}_conv1d.weight",
                s["gdn_conv"].w[:, lo:hi].T[:, None, :])
        put(i, "linear_attn.a_proj.weight", s["gdn_ab"].w[:, :H].T)
        put(i, "linear_attn.b_proj.weight", s["gdn_ab"].w[:, H:].T)
        put(i, "linear_attn.g_proj.weight", s["gdn_g"].w.T)
        put(i, "linear_attn.o_proj.weight", s["gdn_o"].w.T)
        put(i, "linear_attn.A_log", s["gdn_A_log"])
        put(i, "linear_attn.dt_bias", s["gdn_dt_bias"])
        put(i, "linear_attn.o_norm.weight", s["gdn_norm"].scale)
    save_file(tensors, str(tmp_path / "model.safetensors"))
    ckpt = CheckpointShards(
        [str(tmp_path / "model.safetensors")], dtype=np.float32)
    loaded = MODEL_REGISTRY["olmo_hybrid"].load_params(ckpt, cfg, mesh)
    assert jax.tree.structure(params) == jax.tree.structure(loaded)
    for a, c in zip(jax.tree.leaves(params), jax.tree.leaves(loaded)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c))


def test_tensor_parallel_mesh_keeps_the_mixer_replicated(devices):
    """``tp=2``: attention (its QK-norm over a sharded projection) and the
    MLP shard as for every family, the linear-attention stack's mixer leaves
    and the state pools are replicated; prefill and a cached step still
    match the reference."""
    mesh2 = make_mesh(MeshPlan(tp=2), devices=devices[:2])
    errors = decode_errors(make_engine(mesh2), prompts_of([21, 40], seed=6), 1, (1,))
    assert max(errors.values()) < TOL["float32"], errors


def test_the_four_scopes_are_in_the_lowered_programs(engine):
    """docs/observability.md: ``gdn.conv``, ``gdn.gate`` and the form of the
    delta rule a program runs (``gdn.prefill`` for an admission and a mixed
    step's chunk, ``gdn.decode`` for a decode step) are named scopes of the
    step programs, where a profile's op details show them."""
    tok, _, cache, pos, sa = prefill(engine, prompts_of([9, 12]))
    ids, lens = engine._pad_prompts(prompts_of([9, 12]))
    texts = {
        "prefill": engine._prefill.lower(
            engine.params, jnp.asarray(ids), engine.new_paged_cache(2),
            jnp.asarray(lens), sa),
        "decode": engine._decode.lower(
            engine.params, engine.canon_vec(tok), engine.canon_cache(cache),
            engine.canon_vec(pos), sa),
    }
    texts = {k: v.as_text(debug_info=True) for k, v in texts.items()}
    for name, own, other in (("prefill", "gdn.prefill", "gdn.decode"),
                             ("decode", "gdn.decode", "gdn.prefill")):
        assert {s for s in ("gdn.conv", "gdn.gate", own)
                if s in texts[name]} == {"gdn.conv", "gdn.gate", own}
        assert other not in texts[name]


def test_int8_keys_and_values_are_carried_beside_the_state(mesh, engine):
    """``kv_dtype="int8"``: the attention layers' pools are quantized (their
    scales have the pools' layer count), the linear-attention layers never
    touch them; prefill and a cached step stay within int8's own error of
    the reference (0.01-0.02 here)."""
    eng = DecodeEngine(
        engine.cfg, engine.params, mesh, kv_layout="paged",
        max_seq_len=MAX_LEN, kv_dtype="int8",
    )
    cache = eng.new_paged_cache(2)
    assert cache.k.dtype == jnp.int8 and cache.k_scale.shape[0] == 2
    assert cache.ssm.dtype == jnp.float32 and cache.ssm.shape[0] == 6
    errors = decode_errors(eng, prompts_of([21, 40], seed=8), 2, (1, 2))
    assert max(errors.values()) < 0.05, errors


def test_the_chip_check_script_reads_its_controls(tmp_path):
    """``tools/olmo_hybrid_check.py`` (the builder's comparison on the chip:
    the mixed step against the reference, with precision controls) at the toy
    size in float32: the program is the reference to accumulation order, a
    bfloat16 state is NOT, and neither is the reference with its residual at
    3 mantissa bits; the matmuls' precision changes nothing on a CPU."""
    out = tmp_path / "check.jsonl"
    subprocess.run(
        [sys.executable, "tools/olmo_hybrid_check.py",
         "tests/benchmark/toy_olmo_hybrid/configs/tiny-olmo-hybrid.json",
         "--seed", "4100000009", "--paths", "mixed", "--mixed-lens", "20", "40",
         "--out", str(out)],
        cwd=ROOT, env={**os.environ, "JAX_PLATFORMS": "cpu"}, check=True,
        timeout=600, capture_output=True,
    )
    head, *rows = map(json.loads, out.read_text().splitlines())
    assert head["tolerance"] == TOL["float32"] and head["chunk"] == 4
    got = {r["what"]: r for r in rows}
    assert got["program"]["correct"] and max(got["program"]["logits"]) < 1e-4
    assert got["delta rule's matmuls at default precision"]["correct"]
    assert got["embedding at size 1"]["correct"]
    assert not got["state rounded to bfloat16 after every call"]["correct"]
    assert not got["reference, residual at 3 mantissa bits"]["correct"]
    assert (max(got["reference, residual at 3 mantissa bits"]["logits"])
            > 8 * max(got["reference, residual at 7 mantissa bits"]["logits"]))

