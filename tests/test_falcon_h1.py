"""Falcon-H1 (a Mamba-2 mixer beside attention in every block) at a small
size on the CPU: the program against the plain reference
(``benchmark/reference/falcon_h1.py``, the same file the benchmark uses), the
recurrent state through admission, decode, re-admission and replay, every
multiplier, and every serving feature that must carry the state or refuse
the model. Weights are the family's own seeded draw (``init_params``), norm
scales + 1 as the benchmark's server makes them."""

import dataclasses
import importlib.util
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llmss_tpu.engine import DecodeEngine, GenerationParams
from llmss_tpu.engine.scheduler import ContinuousBatcher
from llmss_tpu.models.decoder import init_params
from llmss_tpu.models.registry import MODEL_REGISTRY, config_from_hf
from llmss_tpu.ops.layers import NormParams
from llmss_tpu.ops.ssm import causal_conv, ssd_scan, ssm_step
from llmss_tpu.parallel import MeshPlan, make_mesh

ROOT = Path(__file__).resolve().parent.parent

# The published multipliers (Falcon-H1-34B-Instruct) on small sizes; q_size
# (4 x 8) differs from the hidden size, as at 34B.
HF = dict(
    model_type="falcon_h1", vocab_size=256, hidden_size=64,
    num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
    head_dim=8, intermediate_size=128, max_position_embeddings=256,
    hidden_act="silu", rms_norm_eps=1e-5, rope_theta=1e11, rope_scaling=None,
    attention_bias=False, mlp_bias=False, tie_word_embeddings=False,
    mamba_d_ssm=64, mamba_n_heads=4, mamba_d_head=16, mamba_n_groups=2,
    mamba_d_state=16, mamba_d_conv=4, mamba_chunk_size=8, mamba_expand=2,
    mamba_conv_bias=True, mamba_proj_bias=False, mamba_rms_norm=True,
    mamba_norm_before_gate=False, mamba_use_mlp=True,
    attention_in_multiplier=1, attention_out_multiplier=0.0375,
    embedding_multiplier=5.656854249492381,
    key_multiplier=0.011048543456039804, lm_head_multiplier=0.0078125,
    mlp_multipliers=[0.1767766952966369, 0.011160714285714284],
    ssm_in_multiplier=0.25,
    ssm_multipliers=[0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                     0.3535533905932738],
    ssm_out_multiplier=0.08838834764831845,
)
TOL = {"float32": 2e-3, "bfloat16": 0.15}  # benchmark/lib/check.py's
MAX_LEN = 128


def _reference():
    path = ROOT / "benchmark" / "reference" / "falcon_h1.py"
    spec = importlib.util.spec_from_file_location("ref_falcon_h1", path)
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


def make_engine(mesh, dtype="float32", **cfg_changes):
    cfg = config_from_hf(types.SimpleNamespace(**HF), dtype=dtype)
    params = unit_norm_scales(init_params(cfg, mesh, jax.random.key(3)))
    if cfg_changes:
        cfg = dataclasses.replace(cfg, **cfg_changes)
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


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_prefill_then_64_cached_steps_match_reference(mesh, dtype):
    """Prompts of unequal length through one bucketed prefill, then 64
    decode steps through the cache: the logits of the prefill and of steps
    1, 2, 32 and 64 against the reference's full forward of prompt +
    tokens so far."""
    eng = make_engine(mesh, dtype)
    prompts = prompts_of([21, 40, 37, 9])
    tok, logits, cache, pos, sa = prefill(eng, prompts)
    assert err(logits, ref_logits(eng.params, prompts)) < TOL[dtype]
    seqs = [list(p) for p in prompts]
    for step in range(1, 65):
        for s, t in zip(seqs, np.asarray(tok).tolist()):
            s.append(t)
        tok, logits, cache = eng._decode(
            eng.params, eng.canon_vec(tok), eng.canon_cache(cache),
            eng.canon_vec(pos), sa,
        )
        pos = pos + 1
        if step in (1, 2, 32, 64):
            e = err(np.asarray(logits), ref_logits(eng.params, seqs))
            assert e < TOL[dtype], (step, e)


def test_padded_batch_gives_each_row_what_it_gets_alone(engine):
    """The padding trap: in a bucket of 64, rows of 9 to 40 tokens. Padded
    positions must leave the state untouched (time step 0) and the window
    must be the one at the true length: prefill logits AND the next cached
    step equal the row's own, alone in its bucket of 16 or 64."""
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


def run_batcher(batcher, prompts, gens):
    got = {}
    for i, (p, g) in enumerate(zip(prompts, gens)):
        batcher.submit(p, g, lambda toks, i=i, **kw: got.__setitem__(i, toks))
    batcher.run_until_idle()
    return [got[i] for i in range(len(prompts))]


def test_batcher_rows_match_isolated_and_readmitted_rows_start_clean(engine):
    """Five requests of unequal length through two rows: every row is freed
    and re-admitted, admissions are bucketed and padded, groups run with
    rows that are done. Each request's tokens equal its own alone, so a
    re-admitted row started from a zero state and a done row's state went
    nowhere."""
    prompts = prompts_of([21, 40, 37, 9, 30], seed=2)
    gens = [GenerationParams(max_new_tokens=n, is_greedy=True)
            for n in (12, 5, 9, 14, 7)]
    expected = [engine.generate([p], g)[0] for p, g in zip(prompts, gens)]
    batcher = ContinuousBatcher(engine, rows=2)
    before = engine.metrics.to_dict()
    got = run_batcher(batcher, prompts, gens)
    assert got == expected
    after = engine.metrics.to_dict()
    assert after["cache"]["state_bytes"] == (
        batcher.cache.ssm.nbytes + batcher.cache.conv.nbytes)
    # two rows x two layers of a float32 [4, 16, 16] state and a float32
    # window of 3 steps over 64 + 2 x 32 channels
    assert after["cache"]["state_bytes"] == 2 * 2 * (4 * 16 * 16 + 3 * 128) * 4
    assert "ssm" not in after  # the mixer's steps are loop.decode_steps
    assert after["loop"]["decode_steps"] > before["loop"]["decode_steps"]


def test_chunked_prefill_carries_the_state(engine):
    """Prompts streamed through the ragged mixed-batch program, 8 tokens a
    step beside rows that decode: a chunk goes on from the row's state,
    columns past the chunk's live length are no-ops, and admission zeroes
    the state of a row that another request left behind. Tokens equal each
    request's own alone; no executable compiles after prewarm."""
    prompts = prompts_of([21, 40, 37, 9, 30], seed=7)
    gens = [GenerationParams(max_new_tokens=n, is_greedy=True)
            for n in (12, 5, 9, 14, 7)]
    expected = [engine.generate([p], g)[0] for p, g in zip(prompts, gens)]
    batcher = ContinuousBatcher(engine, rows=2, chunked_prefill=8)
    batcher.prewarm()
    compiled = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda e, d, **kw: compiled.append(e)
        if e == "/jax/core/compile/backend_compile_duration" else None)
    assert run_batcher(batcher, prompts, gens) == expected
    assert not compiled


def test_preempt_and_replay_equals_uninterrupted(engine):
    """A low-priority request evicted mid-decode and resumed by replaying
    prompt + emitted tokens through one prefill (the scan rebuilds the
    state the steps had built) ends with the tokens of the unpreempted
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


@pytest.mark.parametrize("S,chunk", [(5, 8), (8, 8), (13, 8), (33, 16)])
def test_chunked_scan_equals_token_by_token(S, chunk):
    """``ssd_scan`` against ``ssm_step`` applied S times, from a state that
    is not zero, at lengths that are not multiples of the chunk; a padded
    tail (dt 0) changes neither the outputs before it nor the state."""
    B, H, P, G, N = 2, 4, 8, 2, 16
    k = jax.random.split(jax.random.key(S), 6)
    x = jax.random.normal(k[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(k[1], (B, S, H)) - 2)
    A = -jnp.exp(jax.random.normal(k[2], (H,)))
    Bm = jax.random.normal(k[3], (B, S, G, N))
    Cm = jax.random.normal(k[4], (B, S, G, N))
    s0 = jax.random.normal(k[5], (B, H, P, N))
    y, s = ssd_scan(x, dt, A, Bm, Cm, s0, chunk)
    ys, st = [], s0
    for t in range(S):
        y_t, st = ssm_step(x[:, t], dt[:, t], A, Bm[:, t], Cm[:, t], st)
        ys.append(y_t)
    np.testing.assert_allclose(y, jnp.stack(ys, 1), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(s, st, rtol=2e-4, atol=2e-4)

    def pad(a, v=0.0):
        return jnp.pad(a, [(0, 0), (0, 3)] + [(0, 0)] * (a.ndim - 2),
                       constant_values=v)

    y2, s2 = ssd_scan(pad(x, 1.0), pad(dt), A, pad(Bm, 1.0), pad(Cm, 1.0),
                      s0, chunk)
    np.testing.assert_allclose(y2[:, :S] - y, 0, atol=2e-4)
    np.testing.assert_allclose(s2, s, rtol=2e-4, atol=2e-4)


def test_conv_window_is_taken_at_the_true_length():
    B, S, C, K = 3, 6, 5, 4
    k = jax.random.split(jax.random.key(0), 4)
    x = jax.random.normal(k[0], (B, S, C))
    win = jax.random.normal(k[1], (B, K - 1, C))
    w, b = jax.random.normal(k[2], (K, C)), jax.random.normal(k[3], (C,))
    lens = jnp.asarray([6, 2, 0])
    y, new = causal_conv(x, win, w, b, lens)
    full = jnp.concatenate([win, x], 1)
    for i, n in enumerate([6, 2, 0]):
        np.testing.assert_allclose(new[i], full[i, n:n + K - 1])
    want = sum(full[:, j:j + S] * w[j] for j in range(K)) + b
    np.testing.assert_allclose(y, want, rtol=1e-5, atol=1e-5)


def _one(m):
    """1 where the multiplier is not 1 already, else 2: a change either way."""
    return 2.0 if m == 1.0 else 1.0


MULTIPLIERS = (
    [(f, None) for f in ("embed_multiplier", "attn_in_multiplier",
                         "key_multiplier", "attn_out_multiplier",
                         "lm_head_multiplier")]
    + [("mlp_multipliers", i) for i in range(2)]
    + [("ssm.in_multiplier", None), ("ssm.out_multiplier", None)]
    + [("ssm.multipliers", i) for i in range(5)]
)


@pytest.mark.parametrize(
    "field,index", MULTIPLIERS,
    ids=[f if i is None else f"{f}[{i}]" for f, i in MULTIPLIERS],
)
def test_every_multiplier_is_checked(mesh, engine, field, index):
    """Each fixed scalar of the architecture set to 1 (2 where it is 1) in
    the PROGRAM only: the logits must leave the float32 tolerance against
    the reference, which keeps the published value. A multiplier the
    seeded draw hides is a multiplier nobody checks."""
    cfg = engine.cfg
    holder, name = (cfg.ssm, field[4:]) if field.startswith("ssm.") else (
        cfg, field)
    old = getattr(holder, name)
    if index is None:
        new = _one(old)
    else:
        new = tuple(_one(m) if i == index else m for i, m in enumerate(old))
    changed = dataclasses.replace(holder, **{name: new})
    if holder is cfg.ssm:
        changed = dataclasses.replace(cfg, ssm=changed)
    eng = DecodeEngine(
        changed, engine.params, mesh, kv_layout="paged", max_seq_len=MAX_LEN
    )
    prompts = prompts_of([21, 40], seed=4)
    ref = ref_logits(engine.params, prompts)
    assert err(prefill(engine, prompts)[1], ref) < TOL["float32"]
    assert err(prefill(eng, prompts)[1], ref) > 10 * TOL["float32"]


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
    raises, at construction where the feature has one; none runs and is
    silently wrong."""
    with pytest.raises(ValueError, match="recurrent state"):
        _refused(engine, mesh, feature)


def test_checkpoint_round_trip_under_the_published_names(mesh, tmp_path):
    """``load_params`` reads back, leaf for leaf, a checkpoint written under
    the published implementation's names and layouts (torch Linear [out,
    in], conv1d [C, 1, K])."""
    from safetensors.numpy import save_file

    from llmss_tpu.weights import CheckpointShards

    cfg = config_from_hf(types.SimpleNamespace(**HF), dtype="float32")
    params = init_params(cfg, mesh, jax.random.key(5))
    b = jax.tree.map(np.asarray, params["blocks"])
    names = {
        "input_layernorm.weight": b["ln1"].scale,
        "pre_ff_layernorm.weight": b["ln2"].scale,
        "self_attn.q_proj.weight": b["q"].w,
        "self_attn.k_proj.weight": b["k"].w,
        "self_attn.v_proj.weight": b["v"].w.transpose(0, 2, 1),
        "self_attn.o_proj.weight": b["o"].w.transpose(0, 2, 1),
        "feed_forward.gate_proj.weight": b["gate"].w.transpose(0, 2, 1),
        "feed_forward.up_proj.weight": b["up"].w.transpose(0, 2, 1),
        "feed_forward.down_proj.weight": b["down"].w.transpose(0, 2, 1),
        "mamba.in_proj.weight": b["ssm_in"].w.transpose(0, 2, 1),
        "mamba.out_proj.weight": b["ssm_out"].w.transpose(0, 2, 1),
        "mamba.conv1d.weight": b["ssm_conv"].w.transpose(0, 2, 1)[:, :, None],
        "mamba.conv1d.bias": b["ssm_conv"].b,
        "mamba.dt_bias": b["ssm_dt_bias"], "mamba.A_log": b["ssm_A_log"],
        "mamba.D": b["ssm_D"], "mamba.norm.weight": b["ssm_norm"].scale,
    }
    tensors = {
        f"model.layers.{i}.{n}": np.ascontiguousarray(a[i])
        for n, a in names.items() for i in range(cfg.n_layers)
    }
    tensors["model.embed_tokens.weight"] = np.asarray(params["wte"])
    tensors["model.final_layernorm.weight"] = np.asarray(params["ln_f"].scale)
    tensors["lm_head.weight"] = np.ascontiguousarray(
        np.asarray(params["head"].w).T)
    save_file(tensors, str(tmp_path / "model.safetensors"))
    ckpt = CheckpointShards(
        [str(tmp_path / "model.safetensors")], dtype=np.float32)
    loaded = MODEL_REGISTRY["falcon_h1"].load_params(ckpt, cfg, mesh)
    want, got = jax.tree.leaves(params), jax.tree.leaves(loaded)
    assert jax.tree.structure(params) == jax.tree.structure(loaded)
    for a, c in zip(want, got):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c))


def test_tensor_parallel_mesh_keeps_the_mixer_replicated(devices):
    """``tp=2``: attention and the MLP shard as for every family, the
    mixer's leaves and the state pool are replicated (``param_specs``);
    prefill and a cached step still match the reference."""
    mesh2 = make_mesh(MeshPlan(tp=2), devices=devices[:2])
    eng = make_engine(mesh2)
    prompts = prompts_of([21, 40], seed=6)
    tok, logits, cache, pos, sa = prefill(eng, prompts)
    assert err(logits, ref_logits(eng.params, prompts)) < TOL["float32"]
    _, step, _ = eng._decode(
        eng.params, eng.canon_vec(tok), eng.canon_cache(cache),
        eng.canon_vec(pos), sa,
    )
    seqs = [p + [t] for p, t in zip(prompts, np.asarray(tok).tolist())]
    assert err(np.asarray(step), ref_logits(eng.params, seqs)) < TOL["float32"]
