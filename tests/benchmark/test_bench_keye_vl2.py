"""What PR 46 added to the benchmark: the KeyeVL2 reference's contract
(``dims`` for one chip's share with a floor that a sparse step cannot beat,
``layers`` yielding each layer with its held experts, a control that
fails), the configuration's, the traffic mix's and the cell's entries in
``BENCHMARK.json``, the readers of ``benchmark/lib/dsa.py`` on made-up
contexts (functions there, not per-layer metrics: that module says why), and
a toy KeyeVL2 cell served by ``run.py --manifest --allow-cpu`` on the CPU
(its own toy tree, ``tests/benchmark/toy_keye_vl2/``: the files the benchmark
had are not edited). No device number is produced here."""

import json
import math
import os
import subprocess
import sys
import types
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark.lib import costs, dsa, manifest, moe, peaks, stats  # noqa: E402
from benchmark.lib import qwen3_next as q3n  # noqa: E402

CELL = "keye-vl-2.0-30b-a3b-1chip.longdoc"
CONFIG = json.loads(
    (ROOT / "benchmark" / "configs" / "keye-vl-2.0-30b-a3b-1chip.json").read_text())
MODEL = {k: v for k, v in CONFIG.items() if k not in manifest.HARNESS_KEYS}
REF = manifest.load_module("reference", "KeyeVL2")
V5E = peaks.peaks_for("TPU v5 lite")
READERS = ["dsa_pct", "dsa_kept_share", "dsa_read_roofline"]
EARLIER = ["starcoderbase-1b.gen", "falcon-h1-34b-1chip.chat",
           "kanana-2-30b-a3b-1chip.doc", "olmo-hybrid-7b-1chip.chat",
           "qwen3-next-80b-a3b-1chip.doc"]


def test_the_manifest_has_the_cell_and_what_was_there_has_not_moved():
    """The configuration and the cell are appended to ``BENCHMARK.json``;
    the cell reports ``tpot_p90_ms`` and ``setup_s`` (not the first-token
    tail: the cell's file says why) and joins the lists of the six accepted
    per-layer metrics the issue names, last in each; it runs a mix of its
    own, ``longdoc``. No per-layer entry is appended. The earlier cells are
    held by NAME and ORDER, not by the length of any list, so the next
    appended cell leaves this test green."""
    m = manifest.load()
    c = manifest.cell(m, CELL)
    assert c["entry"]["chips"] == 1 and c["entry"]["traffic"] == "longdoc"
    assert c["traffic"]["top_p"] == 1.0 and "top_k" not in c["traffic"]
    assert c["traffic"]["prompt"] == {
        "dist": "lognormal", "median": 8192, "sigma": 0.5, "min": 4096, "max": 16384}
    assert c["traffic"]["output"] == {
        "dist": "lognormal", "median": 256, "sigma": 0.4, "min": 128, "max": 512}
    assert (c["traffic"]["warmup_s"], c["traffic"]["cooldown_s"]) == (6, 10)
    assert c["traffic"]["sampled_share"] == 0.5 and c["traffic"]["stream"]
    # every prompt at least twice the selection, with room for its output
    topk = MODEL["sa_config"]["topk"]
    assert c["traffic"]["prompt"]["min"] >= 2 * topk
    assert c["traffic"]["prompt"]["max"] + c["traffic"]["output"]["max"] == (
        c["serve"]["max_seq_len"])
    assert c["serve"]["chunked_prefill"] in (16, 32, 64, 128)
    assert (c["serve"]["rows"], c["serve"]["max_seq_len"]) == (32, 16896)
    assert sorted(e["name"] for e in c["end_to_end"]) == ["setup_s", "tpot_p90_ms"]
    assert {e["name"] for e in c["per_layer"]} == {
        "decode_step_dev_ms", "host_turn_pct", "loop_host_ms_per_step",
        "host_ms_per_group", "first_token_p50_ms", "decode_step_mfu_roofline"}
    names = [w["name"] for w in m["workloads"]]
    assert names[:5] == EARLIER and names.index(CELL) >= 5
    assert [x["name"] for x in m["configs"]][:5] == [
        n.rsplit(".", 1)[0] for n in EARLIER]
    assert c["entry"]["config"] in [x["name"] for x in m["configs"]][5:]
    for e in m["end_to_end"] + m["per_layer"]:
        cells = e.get("workloads", [])
        if CELL in cells:
            before = cells[:cells.index(CELL)]
            assert before == [n for n in EARLIER if n in before] and before
    tpot = next(e for e in m["end_to_end"] if e["name"] == "tpot_p90_ms")
    mfu = next(e for e in m["per_layer"] if e["name"] == "decode_step_mfu_roofline")
    assert tpot["workloads"] == mfu["workloads"]
    assert not set(READERS) & {e["name"] for e in m["per_layer"]}
    assert len(c["entry"]["why"]) <= 200
    assert f"{c['params']['rate']} req/s" in c["entry"]["why"]
    assert f"{c['serve']['chunked_prefill']} tokens a row a step" in c["entry"]["why"]
    cfg = next(x for x in m["configs"] if x["name"] == c["entry"]["config"])
    assert cfg["file"] == "benchmark/configs/keye-vl-2.0-30b-a3b-1chip.json"
    assert cfg["source"] == CONFIG["source"]
    assert all(1 <= len(e["why"]) <= 200 for e in m["configs"] + m["workloads"])


def test_the_configuration_keeps_every_published_key_but_the_four_reduced():
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if not catalog.exists():
        pytest.skip("no catalog on this machine")
    row = next(json.loads(line) for line in catalog.read_text().splitlines()
               if '"Keye-VL-2.0-30B-A3B"' in line)
    assert CONFIG["source"] == row["source_url"]
    differ = sorted(k for k, v in row["config"].items() if MODEL.get(k, "absent") != v)
    assert differ == sorted(CONFIG["reduced"]) == [
        "max_position_embeddings", "num_experts", "num_hidden_layers", "vocab_size"]
    # a quarter of the experts and of the vocabulary, with the published
    # count and the four chips beside them; the indexer's group whole
    ep = MODEL["expert_parallel"]
    assert ep == {"num_experts": row["config"]["num_experts"], "chips": 4, "chip": 0}
    assert MODEL["num_experts"] * ep["chips"] == ep["num_experts"]
    assert MODEL["vocab_size"] * 4 == row["config"]["vocab_size"]
    assert MODEL["sa_config"] == row["config"]["sa_config"]
    assert MODEL["rope_scaling"] == row["config"]["rope_scaling"]
    assert CONFIG["serve"]["max_seq_len"] == MODEL["max_position_embeddings"] == 16896
    assert set(MODEL) - set(row["config"]) == {"expert_parallel"}
    for key in ("source", "reduced", "assumed", "memory", "why"):
        assert CONFIG[key]
    for key in ("deployment", "published", "indexer", "indexer_dtype", "qk_norm",
                "expert_parallel", "load", "weights", "served_context",
                "admission", "checkpoint_names"):
        assert CONFIG["assumed"][key], key


def test_dims_match_the_programs_parameter_shapes_and_give_a_floor_under_the_sparse_reckoning():
    """``total_params`` leaves out the embedding's slice (held, gathered by
    row); with it, the count is the program's own: 1,190 M. ``kv_layers`` is
    0: the floor is the held parameters and the operations outside
    attention, which every step pays, and it lies UNDER what a sparse step
    must read at the cell's rows and context (parameters, 256 B of indexer
    key a cached token, 2 KB for each of 2,048 kept), which lies far under
    what ``costs.py`` would charge a dense read of the whole context."""
    from llmss_tpu.models.decoder import param_shapes
    from llmss_tpu.models.registry import config_from_hf

    d = REF.dims(MODEL)
    cfg = config_from_hf(types.SimpleNamespace(**MODEL))
    n = sum(math.prod(x.shape) for x in jax.tree.leaves(param_shapes(cfg)))
    assert n == d["total_params"] + MODEL["vocab_size"] * MODEL["hidden_size"]
    assert n == pytest.approx(1.18997e9, rel=1e-4)
    assert (d["layers"], d["kv_layers"]) == (6, 0)
    expert = 3 * 2048 * 768
    per_token = d["matmul_params"] - 2048 * 37984
    assert per_token == pytest.approx(
        6 * (18.874e6 + 2.261e6 + 0.262e6 + 2 * expert), rel=1e-3)
    rows, context = 24, 9000
    floor = costs.decode_step_floor_s(d, "bfloat16", V5E, rows=rows, context=context)
    assert floor["bound_by"] == "memory"
    assert floor["bytes"] == 2 * d["total_params"]
    assert floor["floor_s"] == pytest.approx(2.72e-3, rel=0.02)
    z = dsa.sizes(manifest.cell(manifest.load(), CELL))
    sparse = floor["floor_s"] + dsa.read_floor_s(
        z, V5E, scored=6 * rows * context, kept=6 * rows * z["topk"])
    assert sparse == pytest.approx(3.86e-3, rel=0.02)
    dense = costs.decode_step_floor_s(
        {**d, "kv_layers": 6}, "bfloat16", V5E, rows=rows, context=context)
    assert floor["floor_s"] < sparse < dense["floor_s"]
    assert dense["floor_s"] == pytest.approx(5.96e-3, rel=0.02)


def test_the_accepted_families_sizes_do_not_read_this_configuration():
    """Why the readers are a new file: ``lib/moe.py`` wants the latent
    family's keys and ``lib/qwen3_next.py`` the linear mixer's."""
    assert moe.sizes(MODEL) is None and q3n.sizes(MODEL) is None
    assert dsa.sizes(manifest.cell(manifest.load(), EARLIER[4])) is None


def test_layers_yield_each_layer_with_its_held_experts_and_a_control_that_ties():
    small = {**MODEL, "vocab_size": 256, "hidden_size": 64,
             "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
             "moe_intermediate_size": 32, "num_experts": 2,
             "num_experts_per_tok": 2, "num_hidden_layers": 3,
             "expert_parallel": {"num_experts": 8, "chips": 4, "chip": 2},
             "sa_config": {**MODEL["sa_config"], "indexer_head_dim": 8,
                           "indexer_num_heads": 4, "topk": 16}}
    from llmss_tpu.models.decoder import param_shapes
    from llmss_tpu.models.registry import config_from_hf

    shapes = param_shapes(config_from_hf(types.SimpleNamespace(**small)))
    params = jax.tree.map(lambda s: jax.numpy.ones(s.shape, s.dtype), shapes)
    got = list(REF.layers(small, params))
    assert [kind for kind, _ in got] == ["sparse_attention"] * 3
    for _, lp in got:  # the held experts, the router whole, no shared expert
        assert lp["experts_gate"].shape == (2, 64, 32)
        assert lp["router"].w.shape == (8, 64)
        assert lp["idx_q"].w.shape == (64, 32) and lp["idx_k"].w.shape == (64, 8)
        assert not [k for k in lp if k.startswith("shared")]
    assert REF._share(small) == (8, 4, 2)
    name, faulty = REF.control(params)
    assert name == "indexer_key_lost"
    assert not jax.numpy.any(faulty["blocks"]["idx_k"].w)
    assert jax.numpy.all(faulty["blocks"]["idx_q"].w == 1)


# Names in the form the chip's profile gives them (the output's shape, then
# the operands with theirs), at this cell's shapes: 32 rows of 16,896 slots,
# 1,056 blocks a row, 7 rows feeding chunks of 32.
KV_GATHER = ("%fusion.1075 = bf16[33792,16,4,128]{3,2,1,0:T(4,128)(2,1)} fusion(bf16[6,33792,16,4,128]"
             "{4,3,2,1,0:T(4,128)(2,1)} %get-tuple-element.5498, s32[33792]{0:T(1024)S(1)} %copy.1)")
INDEX_SCORES = ("%fusion.400 = f32[7,32,16,16896]{3,2,1,0:T(8,128)} fusion(f32[7,32,16,128]{3,2,1,0} %pad.3, "
                "f32[7,16896,128]{2,1,0} %gather.7)")
SELECT_PASS = "%fusion.411 = s32[7,32,1]{2,1,0} fusion(u32[7,32,16928]{2,1,0:T(8,128)} %fusion.405, u32[7,32,1]{2,1,0} %or.7)"
ATTN_SCORES = ("%fusion.420 = f32[7,4,8,32,16896]{4,3,2,1,0:T(8,128)} fusion(f32[7,32,4,8,128]{4,3,2,1,0} %multiply.9, "
               "bf16[7,16896,4,128]{3,2,1,0} %gather.8)")
FIRST_QUERY = "%fusion.380 = f32[32,4,8,1,16897]{4,3,2,1,0:T(8,128)} fusion(f32[32,4,8,1,16896] %fusion.379, f32[32,4,8,1,1] %fusion.378)"
GMM = "%gmm.7 = bf16[8192,768]{1,0} custom-call(bf16[8192,2048], bf16[192,2048,768], s32[192])"
Q_PROJ = ("%fusion.31 = bf16[32,32,4096]{2,0,1:T(8,128)(2,1)} fusion(bf16[32,32,2048]{2,0,1} %fusion.30, "
          "bf16[6,4096,2048]{2,1,0:T(8,128)(2,1)} %get-tuple-element.11)")
IDX_PROJ = ("%fusion.33 = f32[32,32,1024]{2,1,0:T(8,128)} fusion(f32[32,32,2048]{2,1,0} %fusion.29, "
            "bf16[6,2048,1024]{2,1,0:T(8,128)(2,1)} %get-tuple-element.12)")
HEAD = "%fusion.2 = f32[32,37984]{1,0:T(8,128)} fusion(bf16[32,2048]{1,0} %fusion.809, bf16[2048,37984])"


@pytest.mark.parametrize("name,counted", [
    (KV_GATHER, True), (INDEX_SCORES, True), (SELECT_PASS, True),
    (ATTN_SCORES, True), (FIRST_QUERY, True), (GMM, False), (Q_PROJ, False),
    (IDX_PROJ, False), (HEAD, False),
])
def test_ops_are_told_by_the_ring_and_the_rows_blocks(name, counted):
    """2,048 is the hidden size and ``topk`` (and a 32 x 64 mixed step's
    tokens): it tells nothing. The ring (16,896), the ring plus a step's fresh tokens
    and the rows' blocks (33,792) are only the selection's."""
    z = dsa.sizes(manifest.cell(manifest.load(), CELL))
    assert dsa.is_selection_op(name, z) is counted


def _ctx(live_rows=24):
    """A made-up traced run: 6 s of profile in a 51 s window, 25 groups of 4
    steps dispatched inside the trace; the counters of 850 steps with
    ``live_rows`` rows at 9,000 tokens of context each."""
    c = manifest.cell(manifest.load(), CELL)
    t0 = 100.0
    spans_ = [[i, None, "sched.dispatch", t0 + 0.2 * i, 0.001,
               {"chunks": 1, "k": 4}] for i in range(1, 26)]
    n = 850 * 6 * live_rows  # row-layer-steps
    zero = {"dsa.scored": 0, "dsa.kept": 0, "dsa.dense_rows": 0, "dsa.rows": 0}
    after = {"dsa.scored": n * 9000, "dsa.kept": n * 2048, "dsa.dense_rows": 0,
             "dsa.rows": n}
    loop0 = {"decode_steps": 0, "spans": {"loop": {"seconds": 1.0}}, **zero}
    loop1 = {"decode_steps": 850, "spans": {"loop": {"seconds": 52.0}}, **after}
    return {
        "records": [], "cell": c, "peaks": V5E, "stats": stats,
        "dims": REF.dims(MODEL), "costs": costs, "window": {"w0": 80.0, "w1": 131.0},
        "metrics_before": {"loop": loop0}, "metrics_after": {"loop": loop1},
        "flight_trace": {"loop": {"spans": spans_}},
        "trace": {"busy_s": 5.9, "window_s": 6.0, "t_start": t0, "t_stop": t0 + 6.0,
                  "ops": [[ATTN_SCORES, 1.6], [KV_GATHER, 0.9], [INDEX_SCORES, 0.5],
                          [SELECT_PASS, 0.4], [FIRST_QUERY, 0.2], [GMM, 0.6],
                          [Q_PROJ, 0.2], [IDX_PROJ, 0.1], [HEAD, 0.1]]},
    }


def test_the_readers_on_a_made_up_trace():
    ctx = _ctx()
    assert dsa.dsa_pct(ctx) == pytest.approx(100 * 3.6 / 5.9)
    assert dsa.dsa_kept_share(ctx) == pytest.approx(100 * 2048 / 9000)
    # 100 steps in the trace: 3.6 s / 100 = 36 ms of selection a step, over
    # 6 layers x 24 rows x (9,000 x 256 B + 2,048 x 2,048 B) / 819 GB/s
    floor = 6 * 24 * (9000 * 256 + 2048 * 2048) / 819e9
    assert floor == pytest.approx(1.143e-3, rel=1e-3)
    assert dsa.dsa_read_roofline(ctx) == pytest.approx(100 * floor / 36e-3)


def test_the_read_roofline_stays_under_100_with_most_rows_done():
    """The floor is from the counters, which sum over LIVE rows: with 2 of
    the 32 rows live the selection's ops still work all 32 (the views are
    gathered for every row), and the share falls; charged by all 32 rows it
    would have stood still (PR 45 (1)). Even an implementation that reached
    the floor would read 100, never above."""
    few, many = _ctx(live_rows=2), _ctx(live_rows=24)
    assert dsa.dsa_read_roofline(few) == pytest.approx(
        dsa.dsa_read_roofline(many) * 2 / 24)
    assert dsa.dsa_read_roofline(few) < dsa.dsa_read_roofline(many) < 100
    z = dsa.sizes(few["cell"])
    floor = dsa.read_floor_s(z, V5E, scored=6 * 2 * 9000, kept=6 * 2 * 2048)
    few["trace"]["ops"] = [[ATTN_SCORES, floor * 100]]  # at the floor itself
    assert dsa.dsa_read_roofline(few) == pytest.approx(100.0)


@pytest.mark.parametrize("name", READERS)
@pytest.mark.parametrize("what", ["no_such_ops", "parent_program", "old_cell", "no_trace"])
def test_a_reader_returns_nothing_where_there_is_nothing_to_read(name, what):
    """A trace without the selection's ops; the parent's /metrics (no
    ``loop.dsa.*``); a cell whose configuration has no indexer; an untraced
    run without counters. None, never an exception."""
    ctx = _ctx()
    if what == "no_such_ops":
        ctx["trace"]["ops"] = [[HEAD, 1.5], [GMM, 0.2]]
        ctx["metrics_before"] = ctx["metrics_after"] = {
            "loop": {"decode_steps": 5, "spans": {}}}
    elif what == "parent_program":
        for key in ("metrics_before", "metrics_after"):
            ctx[key] = {"loop": {k: v for k, v in ctx[key]["loop"].items()
                                 if not k.startswith("dsa.")}}
    elif what == "old_cell":
        ctx["cell"] = manifest.cell(manifest.load(), "qwen3-next-80b-a3b-1chip.doc")
        ctx["metrics_before"] = ctx["metrics_after"] = {
            "loop": {"decode_steps": 5, "spans": {}}}
    else:
        ctx["trace"] = None
        ctx["metrics_before"] = ctx["metrics_after"] = None
    assert getattr(dsa, name)(ctx) is None


@pytest.fixture(scope="module")
def toy_run():
    """A toy KeyeVL2 cell (chip 1 of 4's share, ``topk`` 16 under prompts of
    24 to 96) through ``run.py`` on the CPU, traced."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "BENCH_RUN": "ignored",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=1"}
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--manifest",
         "tests/benchmark/toy_keye_vl2/BENCHMARK.json", "--allow-cpu",
         "--workload", "tiny-keye-vl2.toy-longdoc", "--seed", str(2**31 + 46),
         "--seconds", "3", "--trace", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900,
    )
    assert p.returncode == 0, p.stderr[-3000:]
    return p


def test_a_toy_keye_vl2_cell_end_to_end_on_the_cpu(toy_run):
    """The reference through ``lib/check.py`` with its file alone (prefill,
    the selection as a mask within the prompt, and one cached step, the kept
    tokens read by token, inside the bfloat16 tolerance; the control far
    outside it), zero compilations in the window, every request answered,
    admission through the mixed step. bfloat16, not the other toys' float32:
    two positions of a prompt that hold the same token tie up to rounding,
    and which of them a query keeps is then the reduction order's; one
    position of 16 moves a float32 comparison and not a bfloat16 one."""
    lines = toy_run.stdout.strip().splitlines()
    last, detail = json.loads(lines[-1]), json.loads(lines[-2])
    assert last["correct"] is True and last["failed"] == 0, toy_run.stderr[-3000:]
    assert detail["compilations_in_window"] == 0
    assert detail["logits"]["control_fault"] == "indexer_key_lost"
    assert detail["logits"]["prefill"] < 0.15 and detail["logits"]["decode"] < 0.15
    assert detail["logits"]["control"] > 1.0
    assert min(detail["logits"]["prompt_lens"]) > 16  # the selection is real
    assert {"host_turn_pct", "loop_host_ms_per_step", "host_ms_per_group",
            "first_token_p50_ms"} <= set(last["metrics"])
    assert "tpot_p90_ms" in detail["end_to_end"]
    assert "chunked_prefill=8" in toy_run.stderr
