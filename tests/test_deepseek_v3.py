"""deepseek_v3 (latent attention over ONE paged pool, a leading dense layer,
then sigmoid-routed experts beside a shared one) at a small size on the CPU:
the program against the plain reference (``benchmark/reference/
deepseek_v3.py``, the same file the benchmark uses; it MATERIALISES every
head's keys and values, the program attends in the absorbed form), the latent
pool through admission, grouped decode, the mixed step, preemption and prefix
reuse, the routing counts, every published constant, and every serving
feature that must carry the latent pool or refuse the model. Weights are the
family's own seeded draw (``init_params``), norm scales + 1 as the
benchmark's server makes them."""

import contextlib
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
from llmss_tpu.models import decoder
from llmss_tpu.models.decoder import forward, init_params
from llmss_tpu.models.registry import MODEL_REGISTRY, config_from_hf
from llmss_tpu.ops import moe
from llmss_tpu.ops.attention import force_impl
from llmss_tpu.ops.layers import NormParams
from llmss_tpu.parallel import MeshPlan, make_mesh
from llmss_tpu.utils import trace

ROOT = Path(__file__).resolve().parent.parent

# kanana-2-30b-a3b's flags and constants on small sizes: 4 heads of 24 =
# 16 + 8 rotary, values of 16, a latent of 32 + 8, 8 experts top-2, one
# shared, one dense layer before two expert layers.
HF = dict(
    model_type="deepseek_v3", vocab_size=512, hidden_size=64,
    num_attention_heads=4, num_key_value_heads=4, head_dim=8,
    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, qk_head_dim=24,
    v_head_dim=16, q_lora_rank=None, intermediate_size=128,
    moe_intermediate_size=32, n_routed_experts=8, num_experts_per_tok=2,
    n_shared_experts=1, first_k_dense_replace=1, moe_layer_freq=1, n_group=1,
    topk_group=1, norm_topk_prob=True, routed_scaling_factor=2.448,
    scoring_func="sigmoid", topk_method="noaux_tc", num_hidden_layers=3,
    max_position_embeddings=256, rms_norm_eps=1e-6, rope_theta=1000000,
    rope_interleave=True, rope_scaling=None, hidden_act="silu",
    attention_bias=False, tie_word_embeddings=False,
)
TOL = {"float32": 2e-3, "bfloat16": 0.15}  # benchmark/lib/check.py's
MAX_LEN = 128


def _reference():
    path = ROOT / "benchmark" / "reference" / "deepseek_v3.py"
    spec = importlib.util.spec_from_file_location("ref_deepseek_v3", path)
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
    full forward in float32, sequences padded at the END to MAX_LEN."""
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
def test_prefill_then_cached_steps_match_reference(mesh, dtype):
    """Prefill of four unequal prompts in one padded bucket, then 12 decode
    steps through the latent pool (absorbed attention over the stale pool
    merged with the fresh latent), each against the reference's full forward
    with materialised heads."""
    eng = make_engine(mesh, dtype)
    prompts = prompts_of([21, 40, 37, 9], seed=1)
    tok, logits, cache, pos, sa = prefill(eng, prompts)
    assert cache.v is None and cache.k.shape[-1] == 128  # 40 in one tile
    assert err(logits, ref_logits(eng.params, prompts)) < TOL[dtype]
    seqs = [list(p) for p in prompts]
    worst = 0.0
    for _ in range(12):
        for s, t in zip(seqs, np.asarray(tok).tolist()):
            s.append(t)
        tok, step, cache = eng._decode(
            eng.params, eng.canon_vec(tok), eng.canon_cache(cache),
            eng.canon_vec(pos), sa,
        )
        pos = pos + 1
        worst = max(worst, err(np.asarray(step), ref_logits(eng.params, seqs)))
    assert worst < TOL[dtype]


def test_absorbed_attention_equals_materialised_heads(engine):
    """One layer's latent attention alone: the program's absorbed form over
    full causal attention of the latents against the reference's, which
    rebuilds every head's keys and values."""
    cfg = engine.cfg
    lp = jax.tree.map(lambda a: a[0], engine.params["blocks"])
    x = jax.random.normal(jax.random.key(1), (2, 24, 64), jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(24), (2, 24))
    mask = jnp.tril(jnp.ones((24, 24), bool))[None].repeat(2, 0)

    def attend(q, latent):
        from llmss_tpu.ops.attention import attention

        return attention(q, latent, latent, mask, scale=cfg.attn_scale)

    got, latent = decoder._latent_attention(
        cfg, lp, x, positions, None, attend)
    with jax.default_matmul_precision("highest"):
        want = REF._attention(HF, lp, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)
    # what is cached: 32 normed numbers, the rotary key, zeros to the tile
    assert latent.shape == (2, 24, 1, 128)
    assert not np.asarray(latent[..., 40:]).any()


def run_batcher(batcher, prompts, gens):
    got = {}
    for i, (p, g) in enumerate(zip(prompts, gens)):
        batcher.submit(p, g, lambda toks, i=i, **kw: got.__setitem__(i, toks))
    batcher.run_until_idle()
    return [got[i] for i in range(len(prompts))]


FIVE = ([21, 40, 37, 9, 30], (12, 5, 9, 14, 7))


def _five(seed, lens=FIVE[0]):
    prompts = prompts_of(lens, seed=seed)
    gens = [GenerationParams(max_new_tokens=n, is_greedy=True) for n in FIVE[1]]
    return prompts, gens


def test_batcher_rows_match_isolated_and_metrics_count_the_routing(engine):
    """Five requests through two rows: bucketed admission into the shared
    latent pool, grouped decode with rows that are done, rows freed and
    re-admitted. Tokens equal each request's own alone; /metrics gains the
    latent gauge and the routing counters. The prompts share one bucket
    (64): a prefill program an admission count, not three (PR 49)."""
    prompts, gens = _five(2, [37, 40, 33, 61, 50])
    expected = [engine.generate([p], g)[0] for p, g in zip(prompts, gens)]
    batcher = ContinuousBatcher(engine, rows=2)
    assert run_batcher(batcher, prompts, gens) == expected
    after = engine.metrics.to_dict()
    # three layers of a 128-wide (40 padded to a tile) float32 row
    assert after["cache"] == {"latent_bytes_per_token": 3 * 128 * 4}
    loop = after["loop"]
    assert loop["moe.layer_steps"] % 2 == 0 and loop["moe.layer_steps"] > 0
    # two experts a live token a layer; never more pairs than 2 rows give
    assert 0 < loop["moe.pairs"] <= 2 * 2 * loop["moe.layer_steps"]
    assert loop["moe.pairs"] % 2 == 0
    assert 0 < loop["moe.experts_hit"] <= loop["moe.pairs"]


@contextlib.contextmanager
def served_by(engine, mesh, impl):
    """The engine whose step programs read the pool by ``impl``: the
    module's own (the gather, as the CPU chooses) or a fresh one traced with
    the latent read kernel forced on (interpreted)."""
    if impl is None:
        yield engine
        return
    with force_impl(impl):
        eng = make_engine(mesh)
        cache = eng.new_paged_cache(2)
        assert decoder.attn_read(eng.cfg, cache, mesh, 8) == "mla.kernel"
        assert decoder.attn_read(eng.cfg, cache, mesh, 1) == "mla.kernel"
        yield eng


IMPLS = pytest.mark.parametrize("impl", [None, "pallas"], ids=["xla", "pallas"])


@IMPLS
def test_the_mixed_step_carries_the_latent_pool(engine, mesh, impl):
    """Prompts streamed through the mixed step, 8 tokens a row a step,
    beside rows that decode; tokens equal each request's own alone (on the
    gather, whichever read serves) and no executable compiles after
    prewarm."""
    prompts, gens = _five(7)
    expected = [engine.generate([p], g)[0] for p, g in zip(prompts, gens)]
    trace.set_enabled(True)
    trace.recorder().clear()
    with served_by(engine, mesh, impl) as eng:
        _mixed_step(eng, prompts, gens, expected)
    # every group's span says which read its program was traced with, the
    # blocks its rows held and the blocks of their rings (2 rows x 8)
    spans = [sp[5] for sp in trace.recorder().loop_spans()
             if sp[2] == "sched.dispatch"]
    assert {a["kind"] for a in spans} == {"ragged_group", "decode_group"}
    assert {a["attn_read"] for a in spans} == {
        "mla.kernel" if impl else "gather"}
    for a in spans:
        ring = 2 * -(-(a.get("t_bucket") or MAX_LEN) // 16)
        assert 0 <= a["blocks_read"] <= a["blocks_ring"] == ring
    reads = [a["blocks_read"] for a in spans]
    assert reads[0] == 0 and max(reads) == 5, reads  # nothing cached at first


def _mixed_step(engine, prompts, gens, expected):
    batcher = ContinuousBatcher(engine, rows=2, chunked_prefill=8)
    batcher.prewarm()
    compiled = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda e, d, **kw: compiled.append(e)
        if e == "/jax/core/compile/backend_compile_duration" else None)
    assert run_batcher(batcher, prompts, gens) == expected
    assert not compiled


@IMPLS
def test_preempt_and_replay_equals_uninterrupted(engine, mesh, impl):
    gen_low = GenerationParams(max_new_tokens=12, is_greedy=True)
    gen_hi = GenerationParams(max_new_tokens=4, is_greedy=True)
    p_low, p_hi = prompts_of([11, 6], seed=3)
    exp_low = engine.generate([p_low], gen_low)[0]
    exp_hi = engine.generate([p_hi], gen_hi)[0]
    with served_by(engine, mesh, impl) as eng:
        _preempt_and_replay(eng, gen_low, gen_hi, p_low, p_hi, exp_low, exp_hi)


def _preempt_and_replay(engine, gen_low, gen_hi, p_low, p_hi, exp_low, exp_hi):
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


def test_prefix_reuse_shares_latent_blocks(engine):
    """A latent block is a block: a retained prefix seeds rows through the
    same tables and copy-on-write as keys and values do, and the tokens
    equal those of the whole prompt prefilled from nothing."""
    shared = prompts_of([20], seed=8)[0]
    tails = prompts_of([5, 9], seed=9)
    gen = GenerationParams(max_new_tokens=6, is_greedy=True)
    expected = [engine.generate([shared + t], gen)[0] for t in tails]
    pfx = engine.build_prefix(shared)
    assert pfx.v is None and pfx.k.shape[-1] == 128
    batcher = ContinuousBatcher(engine, rows=2)
    got = {}
    for i, t in enumerate(tails):
        batcher.submit(shared + t, gen,
                       lambda toks, i=i, **kw: got.__setitem__(i, toks),
                       prefix=pfx)
    batcher.run_until_idle()
    assert [got[0], got[1]] == expected


def _routing_count(params, seqs, live_from=0):
    """(pairs, experts_hit) of the expert layers over every token of
    ``seqs`` by the reference's own router, in numpy."""
    ids = np.zeros((len(seqs), max(map(len, seqs))), np.int32)
    for i, seq in enumerate(seqs):
        ids[i, : len(seq)] = seq
    live = np.zeros(ids.shape, bool)
    for i, seq in enumerate(seqs):
        live[i, : len(seq)] = True
    pairs = hit = 0
    with jax.default_matmul_precision("highest"):
        h = REF.embed(HF, params, jnp.asarray(ids))
        for kind, lp in REF.layers(HF, params):
            if kind == "moe":
                h1 = h + REF._attention(
                    HF, lp, REF._rms(h, lp["ln1"].scale, 1e-6))
                x = REF._rms(h1, lp["ln2"].scale, 1e-6)
                s = jax.nn.sigmoid(x @ lp["router"].w.astype(jnp.float32).T)
                _, chosen = jax.lax.top_k(s + lp["router"].b, 2)
                chosen = np.asarray(chosen)[live]
                pairs += chosen.size
                hit += len(np.unique(chosen))
            h = REF.layer(HF, kind, lp, h)
    return pairs, hit


def _forward_counts(engine, ids, lens, done_rows=()):
    """One prefill-shaped forward through the program with the counts."""
    cfg = engine.cfg
    B, S = ids.shape
    cache = engine.new_paged_cache(B)
    rel = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    valid = rel < jnp.asarray(lens)[:, None]
    slots = rel % cache.max_len
    if done_rows:
        dead = jnp.zeros((B, 1), bool).at[jnp.asarray(done_rows)].set(True)
        slots = jnp.where(dead, cache.max_len, slots)
    aux = {}
    logits, _ = forward(
        cfg, engine.params, jnp.asarray(ids), rel, cache, slots,
        gather_idx=jnp.asarray(lens) - 1,
        kv_write_positions=jnp.where(valid, rel, -1), mesh=engine.mesh,
        aux=aux,
    )
    return np.asarray(logits[:, 0]), np.asarray(aux["moe_counts"])


def test_dead_tokens_add_no_pair_and_change_no_live_logits(engine):
    """A bucket's padding, a padding ROW and a row that is done (its slot
    out of range, as the decode loop marks it) route nowhere: the counts
    equal a numpy count over the live tokens alone by the reference's
    router, and the live rows' logits are what they are without them."""
    prompts = prompts_of([21, 13, 30], seed=4)
    ids, lens = engine._pad_prompts(prompts)
    alone, counts = _forward_counts(engine, ids, lens)
    assert tuple(counts) == (*_routing_count(engine.params, prompts), 0)
    assert counts[0] == 2 * 2 * sum(map(len, prompts))  # top-2, two layers
    # a done row and a row of nothing but padding beside the first two
    ids2 = np.concatenate([ids, np.full((1, ids.shape[1]), 7, np.int32)])
    lens2 = np.asarray([21, 13, 30, 0], np.int32)
    both, counts2 = _forward_counts(engine, ids2, lens2, done_rows=(2,))
    assert tuple(counts2) == (*_routing_count(engine.params, prompts[:2]), 0)
    np.testing.assert_allclose(both[:2], alone[:2], atol=2e-6)


@pytest.mark.parametrize("stacked", [False, True])
def test_grouped_matmul_leaves_rows_behind_the_last_group_alone(stacked):
    """The dropless grouped SwiGLU against a loop over the experts, with a
    group of no rows and dead rows at the end; ``stacked``: the experts of
    three layers in one stack, the second layer's read in place."""
    k = jax.random.split(jax.random.key(0), 4)
    N, E, I, T = 8, 64, 32, 40
    x = jax.random.normal(k[0], (T, E))
    gate, up = (jax.random.normal(k_, (N, E, I)) * 0.1 for k_ in k[1:3])
    down = jax.random.normal(k[3], (N, I, E)) * 0.1
    idx = jnp.asarray(np.random.default_rng(0).integers(0, 7, (T, 2)))
    w = jnp.ones((T, 2)) * 0.5
    live = jnp.arange(T) < 31
    if stacked:
        stack = lambda a: jnp.stack([a * 0 + 7.0, a, a * 0 - 7.0])
        y, counts = jax.jit(
            lambda l: moe.routed_experts(
                x, idx, w, live, stack(gate), stack(up), stack(down),
                jax.nn.silu, layer=l)
        )(jnp.int32(1))
    else:
        y, counts = moe.routed_experts(
            x, idx, w, live, gate, up, down, jax.nn.silu)
    want = np.zeros((T, E), np.float32)
    for t in range(31):
        for e in np.asarray(idx[t]):
            want[t] += 0.5 * np.asarray(
                (jax.nn.silu(x[t] @ gate[e]) * (x[t] @ up[e])) @ down[e])
    np.testing.assert_allclose(np.asarray(y), want, atol=1e-5)
    assert not np.asarray(y[31:]).any()
    assert tuple(np.asarray(counts)) == (62, len(np.unique(idx[:31])), 0)


def _wrong_bias(x, router_w, bias, *, top_k, norm, scale):
    """``moe.route`` with the selection bias ALSO in the weights."""
    s = jax.nn.sigmoid(x.astype(jnp.float32) @ router_w.astype(jnp.float32).T)
    s = s + bias.astype(jnp.float32)
    w, idx = jax.lax.top_k(s, top_k)
    return idx.astype(jnp.int32), w / (w.sum(1, keepdims=True) + 1e-20) * scale


CONSTANTS = {
    "routed_scaling_factor": lambda c: dataclasses.replace(
        c, moe=dataclasses.replace(c.moe, routed_scaling_factor=1.0)),
    "norm_topk_prob": lambda c: dataclasses.replace(
        c, moe=dataclasses.replace(c.moe, norm_topk_prob=False)),
    "sqrt_192": lambda c: dataclasses.replace(c, attn_scale=16 ** -0.5),
    "rope_interleave": lambda c: dataclasses.replace(c, rope_style="half"),
    "rope_theta": lambda c: dataclasses.replace(c, rope_theta=1e4),
    "rms_norm_eps": lambda c: dataclasses.replace(c, norm_eps=1e-2),
    "bias_in_selection_only": None,
}


@pytest.mark.parametrize("constant", sorted(CONSTANTS))
def test_every_published_constant_is_checked(
    mesh, engine, monkeypatch, constant,
):
    """Each constant set wrong in the PROGRAM alone leaves the float32
    tolerance against the reference, which keeps the published value."""
    prompts = prompts_of([21, 40], seed=4)
    ref = ref_logits(engine.params, prompts)
    assert err(prefill(engine, prompts)[1], ref) < TOL["float32"]
    change = CONSTANTS[constant]
    if change is None:
        monkeypatch.setattr(moe, "route", _wrong_bias)
        # a new config object, so that nothing compiled is found again
        change = lambda c: dataclasses.replace(c, max_position_embeddings=255)
    eng = DecodeEngine(
        change(engine.cfg), engine.params, mesh, kv_layout="paged",
        max_seq_len=MAX_LEN,
    )
    assert err(prefill(eng, prompts)[1], ref) > 5 * TOL["float32"]


def test_the_routers_float32_input_chooses_as_the_published_rounding_does():
    """The one departure of the program's router from the published bfloat16
    model (docs/latent-cache.md): it reads the normed input in float32 where
    the published code rounds it to bfloat16 and upcasts that. On weights
    that look like any model's (iid, no routing block; the published 2048
    wide, 128 experts, top-6) the two forms choose the same experts but
    at near-ties: a single expert swapped for the next one, their scores
    under a thousandth apart, about once in 200 tokens; the weights of the
    same choice differ by the rounding alone."""
    E, N, K, T = 2048, 128, 6, 2048
    k = jax.random.split(jax.random.key(38), 4)
    bf16 = jnp.bfloat16
    h = jax.random.normal(k[0], (T, E)).astype(bf16)
    ln = NormParams((1 + 0.02 * jax.random.normal(k[1], (E,))).astype(bf16), None)
    w = (jax.random.normal(k[2], (N, E)) / E ** 0.5).astype(bf16)
    b = (0.02 * jax.random.normal(k[3], (N,))).astype(bf16)
    cfg = config_from_hf(types.SimpleNamespace(**HF))
    x32 = decoder._norm(cfg, h, ln, jnp.float32)  # what _latent_block routes
    published = decoder._norm(cfg, h, ln)  # rounded; route() upcasts it
    assert x32.dtype == jnp.float32 and published.dtype == bf16
    kw = dict(top_k=K, norm=True, scale=2.448)
    (i0, w0), (i1, w1) = moe.route(x32, w, b, **kw), moe.route(published, w, b, **kw)
    o0, o1 = np.argsort(np.asarray(i0), 1), np.argsort(np.asarray(i1), 1)
    i0, i1 = (np.take_along_axis(np.asarray(i), o, 1) for i, o in ((i0, o0), (i1, o1)))
    same = (i0 == i1).all(1)
    assert 0.98 < same.mean() < 1.0  # rare, and this draw does have some
    s = np.asarray(jax.nn.sigmoid(
        x32 @ w.astype(jnp.float32).T) + b.astype(jnp.float32))
    for t in np.flatnonzero(~same):
        mine, theirs = set(i0[t]) - set(i1[t]), set(i1[t]) - set(i0[t])
        assert len(mine) == len(theirs) == 1
        assert abs(s[t, mine.pop()] - s[t, theirs.pop()]) < 2e-3
    w0, w1 = (np.take_along_axis(np.asarray(x), o, 1) for x, o in ((w0, o0), (w1, o1)))
    np.testing.assert_allclose(w0[same], w1[same], atol=2e-3)


def _refused(engine, mesh, devices, feature):
    gen = GenerationParams(max_new_tokens=4, is_greedy=True)
    kw = dict(kv_layout="paged", max_seq_len=MAX_LEN)
    if feature == "dense_layout":
        DecodeEngine(engine.cfg, engine.params, mesh, max_seq_len=MAX_LEN)
    elif feature == "int8_pool":
        DecodeEngine(engine.cfg, engine.params, mesh, kv_dtype="int8", **kw)
    elif feature == "tensor_parallel":
        mesh2 = make_mesh(MeshPlan(tp=2), devices=devices[:2])
        DecodeEngine(engine.cfg, engine.params, mesh2, **kw)
    elif feature == "handoff_export":
        ContinuousBatcher(engine, rows=2, prefill_only=True)
    elif feature == "handoff_adopt":
        ContinuousBatcher(engine, rows=2).adopt(
            "r", 1, 4, {"k": None}, gen, lambda *a, **k: None)
    elif feature == "session_park":
        ContinuousBatcher(engine, rows=2).request_park("r", [1, 2, 3])
    elif feature == "speculative":
        from llmss_tpu.engine.speculative import generate_speculative

        generate_speculative(engine, [[1, 2, 3]], gen)
    else:
        from llmss_tpu.serve.broker import InProcBroker
        from llmss_tpu.serve.consumer import ContinuousWorker

        kw = {"worker_kvstore": {"kvstore": object()},
              "worker_decode_role": {"role": "decode"},
              "worker_prefill_role": {"role": "prefill"}}[feature]
        ContinuousWorker(engine, InProcBroker(), rows=2, **kw)


@pytest.mark.parametrize("feature", [
    "dense_layout", "int8_pool", "tensor_parallel", "handoff_export",
    "handoff_adopt", "session_park", "speculative", "worker_decode_role",
    "worker_prefill_role", "worker_kvstore",
])
def test_a_feature_that_does_not_carry_the_latent_pool_refuses_the_model(
    engine, mesh, devices, feature,
):
    """docs/latent-cache.md: what does not carry the latent pool raises by
    name, at construction where the feature has one."""
    with pytest.raises(ValueError, match="latent pool"):
        _refused(engine, mesh, devices, feature)


@pytest.mark.parametrize("key,value", [
    ("q_lora_rank", 1536), ("n_group", 8), ("topk_group", 4),
    ("rope_scaling", {"type": "yarn", "factor": 40}),
    ("scoring_func", "softmax"), ("rope_interleave", False),
])
def test_a_form_of_the_family_that_is_not_implemented_is_refused(key, value):
    with pytest.raises(ValueError, match="deepseek_v3: .* not implemented"):
        config_from_hf(types.SimpleNamespace(**{**HF, key: value}))


def test_checkpoint_round_trip_under_the_published_names(mesh, tmp_path):
    """``load_params`` reads back, leaf for leaf, a checkpoint written under
    the published implementation's names and layouts (torch Linear [out,
    in], an expert a module)."""
    from safetensors.numpy import save_file

    from llmss_tpu.weights import CheckpointShards

    cfg = config_from_hf(types.SimpleNamespace(**HF), dtype="float32")
    params = init_params(cfg, mesh, jax.random.key(5))
    tensors = {
        "model.embed_tokens.weight": np.asarray(params["wte"]),
        "model.norm.weight": np.asarray(params["ln_f"].scale),
        "lm_head.weight": np.ascontiguousarray(np.asarray(params["head"].w).T),
    }

    def t(a):  # [in, out] here, [out, in] there
        return np.ascontiguousarray(np.swapaxes(a, -1, -2))

    for stack, first in (("lead", 0), ("blocks", 1)):
        b = jax.tree.map(np.asarray, params[stack])
        for j in range(b["q"].w.shape[0]):
            pre = f"model.layers.{first + j}."
            tensors.update({
                pre + "input_layernorm.weight": b["ln1"].scale[j],
                pre + "post_attention_layernorm.weight": b["ln2"].scale[j],
                pre + "self_attn.q_proj.weight": b["q"].w[j],
                pre + "self_attn.kv_a_proj_with_mqa.weight": t(b["kv_a"].w[j]),
                pre + "self_attn.kv_a_layernorm.weight": b["kv_norm"].scale[j],
                pre + "self_attn.kv_b_proj.weight": t(b["kv_b"].w[j]),
                pre + "self_attn.o_proj.weight": t(b["o"].w[j]),
            })
            if stack == "lead":
                for k in ("gate", "up", "down"):
                    tensors[pre + f"mlp.{k}_proj.weight"] = t(b[k].w[j])
                continue
            tensors[pre + "mlp.gate.weight"] = b["router"].w[j]
            tensors[pre + "mlp.gate.e_score_correction_bias"] = b["router"].b[j]
            for k in ("gate", "up", "down"):
                tensors[pre + f"mlp.shared_experts.{k}_proj.weight"] = t(
                    b[f"shared_{k}"].w[j])
                for e in range(cfg.moe.n_experts):
                    tensors[pre + f"mlp.experts.{e}.{k}_proj.weight"] = t(
                        b[f"experts_{k}"][j, e])
    save_file(tensors, str(tmp_path / "model.safetensors"))
    ckpt = CheckpointShards(
        [str(tmp_path / "model.safetensors")], dtype=np.float32)
    loaded = MODEL_REGISTRY["deepseek_v3"].load_params(ckpt, cfg, mesh)
    assert jax.tree.structure(params) == jax.tree.structure(loaded)
    for a, c in zip(jax.tree.leaves(params), jax.tree.leaves(loaded)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c))


def test_the_seeded_draw_keeps_the_routing_block_to_the_embedding(engine):
    """What the draw promises (``_routed_family_draw``): nothing writes the
    first eighth of the hidden dimensions, the router reads nothing else,
    and a token's experts are the same in float32 and in bfloat16."""
    R = decoder.routing_block(engine.cfg)
    p = engine.params
    for stack, leaves in (("lead", ("o", "down")), (
            "blocks", ("o", "shared_down", "experts_down"))):
        for name in leaves:
            leaf = p[stack][name]
            w = np.asarray(leaf.w if hasattr(leaf, "w") else leaf)
            assert not w[..., :R].any() and w[..., R:].any(), (stack, name)
    assert not np.asarray(p["blocks"]["router"].w)[..., R:].any()
    assert np.asarray(p["blocks"]["router"].b).any()
