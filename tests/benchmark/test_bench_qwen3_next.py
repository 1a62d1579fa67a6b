"""What PR 44 added to the benchmark: the qwen3_next reference's contract
(``dims`` for one chip's share, ``layers`` yielding the two kinds in the
published pattern's order from the ragged tree with each layer's held experts,
a control that fails), the configuration's and the cell's entries in
``BENCHMARK.json``, the readers of ``benchmark/lib/qwen3_next.py`` on made-up
contexts (functions there, not per-layer metrics: that module says why), and a
toy qwen3_next cell served by ``run.py --manifest --allow-cpu`` on the CPU
(its own toy tree, ``tests/benchmark/toy_qwen3_next/``: the files the
benchmark had are not edited). No device number is produced here."""

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

from benchmark.lib import costs, gdn, manifest, moe, peaks, stats  # noqa: E402
from benchmark.lib import qwen3_next as q3n  # noqa: E402

CELL = "qwen3-next-80b-a3b-1chip.doc"
CONFIG = json.loads(
    (ROOT / "benchmark" / "configs" / "qwen3-next-80b-a3b-1chip.json").read_text())
MODEL = {k: v for k, v in CONFIG.items() if k not in manifest.HARNESS_KEYS}
REF = manifest.load_module("reference", "qwen3_next")
V5E = peaks.peaks_for("TPU v5 lite")
READERS = ["experts_pct", "experts_grouped_roofline",
           "experts_tokens_per_expert", "experts_elsewhere_pct", "gdn_pct",
           "gdn_update_roofline"]
EARLIER = ["starcoderbase-1b.gen", "falcon-h1-34b-1chip.chat",
           "kanana-2-30b-a3b-1chip.doc", "olmo-hybrid-7b-1chip.chat"]


def test_the_manifest_has_the_cell_and_what_was_there_has_not_moved():
    """The configuration and the cell are appended to ``BENCHMARK.json``;
    the cell reports both tails and ``setup_s`` and joins the lists of the
    twelve accepted per-layer metrics whose readers find something in it
    (those ``olmo-hybrid-7b-1chip.chat`` is on); it runs the mix
    ``kanana-2-30b-a3b-1chip.doc`` runs, unedited. No per-layer entry is
    appended. The earlier cells are held by NAME and ORDER, not by the
    length of any list, so the next appended cell leaves this test green."""
    m = manifest.load()
    c = manifest.cell(m, CELL)
    assert c["entry"]["chips"] == 1 and c["entry"]["traffic"] == "doc"
    assert c["traffic"]["top_p"] == 1.0 and "top_k" not in c["traffic"]
    assert c["traffic"]["prompt"] == {
        "dist": "lognormal", "median": 1536, "sigma": 0.6, "min": 512, "max": 4096}
    assert c["serve"]["chunked_prefill"] == 8
    assert (c["serve"]["rows"], c["serve"]["max_seq_len"]) == (64, 5120)
    assert sorted(e["name"] for e in c["end_to_end"]) == [
        "setup_s", "tpot_p90_ms", "ttft_p90_ms"]
    assert {e["name"] for e in c["per_layer"]} == {
        "decode_step_dev_ms", "host_turn_pct", "loop_host_ms_per_step",
        "host_ms_per_group", "first_token_p50_ms", "decode_step_mfu_roofline",
        "gen_late_p90_ms", "queue_wait_p50_ms", "broker_wait_p50_ms",
        "row_wait_p50_ms", "first_token_lag_p50_ms", "stream_lag_p50_ms"}
    names = [w["name"] for w in m["workloads"]]
    assert names[:4] == EARLIER and names.index(CELL) >= 4
    assert [x["name"] for x in m["configs"]][:4] == [
        n.rsplit(".", 1)[0] for n in EARLIER]
    assert c["entry"]["config"] in [x["name"] for x in m["configs"]][4:]
    for e in m["end_to_end"] + m["per_layer"]:
        cells = e.get("workloads", [])
        if CELL in cells:
            before = cells[:cells.index(CELL)]
            assert before == [n for n in EARLIER if n in before] and before
    tpot = next(e for e in m["end_to_end"] if e["name"] == "tpot_p90_ms")
    mfu = next(e for e in m["per_layer"] if e["name"] == "decode_step_mfu_roofline")
    assert tpot["workloads"] == mfu["workloads"]
    assert not set(READERS) & {e["name"] for e in m["per_layer"]}
    sampler = next(e for e in m["per_layer"] if e["name"] == "sampler_search_pct")
    assert CELL not in sampler["workloads"]
    assert len(c["entry"]["why"]) <= 200
    assert f"{c['params']['rate']} req/s" in c["entry"]["why"]
    cfg = next(x for x in m["configs"] if x["name"] == c["entry"]["config"])
    assert cfg["file"] == "benchmark/configs/qwen3-next-80b-a3b-1chip.json"
    assert cfg["source"] == CONFIG["source"]
    assert all(1 <= len(e["why"]) <= 200 for e in m["configs"] + m["workloads"])


def test_the_configuration_keeps_every_published_key_but_the_four_reduced():
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if not catalog.exists():
        pytest.skip("no catalog on this machine")
    row = next(json.loads(line) for line in catalog.read_text().splitlines()
               if '"Qwen3-Next-80B-A3B-Instruct"' in line)
    assert CONFIG["source"] == row["source_url"]
    differ = sorted(k for k, v in row["config"].items() if MODEL.get(k, "absent") != v)
    assert differ == sorted(CONFIG["reduced"]) == [
        "max_position_embeddings", "num_experts", "num_hidden_layers", "vocab_size"]
    # two whole periods; a quarter of the experts and of the vocabulary,
    # with the published counts and the four chips beside them
    ep = MODEL["expert_parallel"]
    assert MODEL["num_hidden_layers"] == 2 * row["config"]["full_attention_interval"]
    assert ep == {"num_experts": row["config"]["num_experts"], "chips": 4, "chip": 0}
    assert MODEL["num_experts"] * ep["chips"] == ep["num_experts"]
    assert MODEL["vocab_size"] * 4 == row["config"]["vocab_size"]
    assert CONFIG["serve"]["max_seq_len"] == MODEL["max_position_embeddings"] == 5120
    assert set(MODEL) - set(row["config"]) == {"expert_parallel"}
    for key in ("source", "reduced", "assumed", "memory"):
        assert CONFIG[key]
    for key in ("deployment", "published", "norm_scales", "mtp"):
        assert CONFIG["assumed"][key], key


def test_dims_match_the_programs_parameter_shapes():
    """``total_params`` leaves out the embedding's slice (held, gathered by
    row); with it, the count is the program's own: 3,667 M. A token meets
    its mixer, the router, the shared expert and the 2.5 of its ten experts
    that are held here on average."""
    from llmss_tpu.models.decoder import param_shapes
    from llmss_tpu.models.registry import config_from_hf

    dims = REF.dims(MODEL)
    cfg = config_from_hf(types.SimpleNamespace(**MODEL))
    n = sum(math.prod(x.shape) for x in jax.tree.leaves(param_shapes(cfg)))
    assert n == dims["total_params"] + MODEL["vocab_size"] * MODEL["hidden_size"]
    assert n == pytest.approx(3.6673e9, rel=1e-4)
    assert (dims["layers"], dims["kv_layers"]) == (8, 2)
    assert costs.kv_bytes_per_token(dims, "bfloat16") == 2 * 2 * 2 * 256 * 2
    z = q3n.sizes(MODEL)
    assert dims["state_bytes_per_row"] == q3n.state_bytes_per_row(z) + 6 * 3 * 8192 * 2
    expert = 3 * 2048 * 512
    per_token = dims["matmul_params"] - 2048 * 37984
    assert per_token == pytest.approx(
        6 * 33.686e6 + 2 * 27.263e6 + 8 * (1.0486e6 + 3.1478e6 + 2.5 * expert),
        rel=1e-3)
    # the issue's reckoning: 8.8 GB a step, 10.7 ms at the chip's bandwidth
    floor = costs.decode_step_floor_s(dims, "bfloat16", V5E, rows=64, context=0)
    assert floor["bound_by"] == "memory"
    assert floor["floor_s"] == pytest.approx(10.7e-3, rel=0.03)
    assert q3n.state_update_floor_s(z, V5E, rows=64) == pytest.approx(1.97e-3, rel=0.01)
    assert q3n.grouped_floor_s(z, "bfloat16", V5E, hit=128, pairs=1280) == (
        pytest.approx(7.86e-3, rel=0.01))


def test_the_accepted_families_sizes_do_not_read_this_configuration():
    """Why the readers are a new file: ``lib/moe.py`` wants the latent
    family's keys and ``lib/gdn.py`` wants ``layer_types`` (and sizes the
    values by the KEY heads: 2,048 where the model has 4,096)."""
    assert moe.sizes(MODEL) is None and gdn.sizes(MODEL) is None
    kinds = [k for k, _ in zip(REF._kinds(MODEL), range(8))]
    z = gdn.sizes({**MODEL, "layer_types": kinds})
    assert z["value"] == 2048 and q3n.sizes(MODEL)["value"] == 4096


def _small():
    small = {**MODEL, "vocab_size": 256, "hidden_size": 64,
             "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
             "linear_num_key_heads": 2, "linear_num_value_heads": 4,
             "linear_key_head_dim": 8, "linear_value_head_dim": 16,
             "moe_intermediate_size": 32, "shared_expert_intermediate_size": 32,
             "num_experts": 4, "num_experts_per_tok": 4,
             "expert_parallel": {"num_experts": 16, "chips": 4, "chip": 2}}
    from llmss_tpu.models.decoder import param_shapes
    from llmss_tpu.models.registry import config_from_hf

    shapes = param_shapes(config_from_hf(types.SimpleNamespace(**small)))
    return small, jax.tree.map(lambda s: jax.numpy.ones(s.shape, s.dtype), shapes)


def test_layers_yield_the_kinds_in_the_published_order_with_their_experts():
    small, params = _small()
    got = list(REF.layers(small, params))
    assert [kind for kind, _ in got] == (
        ["linear_attention"] * 3 + ["full_attention"]) * 2
    assert "gdn_qkv" in got[0][1] and "q" not in got[0][1]
    assert "q_norm" in got[3][1] and "gdn_qkv" not in got[3][1]
    assert got[3][1]["q"].w.shape == (2 * 4 * 32, 64)  # a query and a gate
    assert got[4][1]["gdn_ab"].w.shape == (64, 8)  # a value head each
    for _, lp in got:  # the held experts, the router whole
        assert lp["experts_gate"].shape == (4, 64, 32)
        assert lp["router"].w.shape == (16, 64)
    assert REF._share(small) == (16, 8, 4)
    name, faulty = REF.control(params)
    assert name == "shared_expert_gate_lost"
    for stack in ("blocks", "linear"):
        assert not jax.numpy.any(faulty[stack]["shared_sig"].w)
        assert jax.numpy.all(faulty[stack]["shared_gate"].w == 1)


# Names in the form the chip's profile gives them (the output's shape, then
# the operands with theirs), at this configuration's widths.
GMM = "%gmm.7 = bf16[5120,512]{1,0} custom-call(bf16[5120,2048], bf16[1024,2048,512], s32[1024])"
GMM_DOWN = ("%fusion.88 = bf16[5120,2048]{1,0:T(8,128)(2,1)} fusion(bf16[5120,512]{1,0} %gmm.8, "
            "bf16[8,128,512,2048]{3,2,1,0} %get-tuple-element.99)")
SORT = "%sort.3 = (s32[5120]{0}, s32[5120]{0}) sort(s32[5120]{0} %pad.2, s32[5120]{0} %iota.7)"
KV_GATHER = ("%fusion.1577 = bf16[20480,16,2,256]{3,2,1,0:T(2,128)(2,1)} fusion(bf16[2,20480,16,2,256]"
             "{4,3,2,1,0:T(2,128)(2,1)} %get-tuple-element.7233, s32[20480]{0:T(1024)S(1)} %copy.1)")
GATHER = "%gather.12 = bf16[5120,2048]{1,0:T(8,128)(2,1)} gather(bf16[512,2048]{1,0} %fusion.5, s32[5120]{0} %min.2)"
TOPK = "%custom-call.9 = (f32[512,10]{1,0}, s32[512,10]{1,0}) custom-call(f32[512,512]{1,0} %softmax.2)"
STATE_SLICE = ("%dynamic-slice_bitcast_fusion.6 = f32[64,32,128,128]{3,2,1,0:T(8,128)} fusion("
               "f32[6,64,32,128,128]{4,3,2,1,0:T(8,128)} %get-tuple-element.7114, s32[]{:T(128)} %select_n.2835)")
STATE_READ = ("%multiply_reduce_fusion.4 = f32[64,32,16,128]{3,2,1,0:T(8,128)} fusion(f32[64,32,128,128]"
              "{3,2,1,0:T(8,128)} %dynamic-slice_bitcast_fusion.6, f32[64,32,16,128]{3,2,1,0} %custom-call.3)")
WINDOW = "%fusion.594 = bf16[64,24576]{1,0:T(8,128)(2,1)} fusion(bf16[64,11,8192]{2,1,0} %concatenate.9)"
QKV_PROJ = ("%fusion.912 = bf16[64,8,8192]{2,0,1:T(8,128)(2,1)} fusion(bf16[64,8,2048]{2,0,1} %fusion.910, "
            "bf16[6,2048,8192]{2,1,0:T(8,128)(2,1)} %get-tuple-element.4362)")
GATED_NORM = "%fusion.77 = f32[64,8,32,128]{3,2,1,0:T(8,128)} fusion(f32[64,32,8,128]{3,2,1,0} %fusion.70)"
Q_PROJ = ("%fusion.31 = bf16[64,8,8192]{2,0,1:T(8,128)(2,1)} fusion(bf16[64,8,2048]{2,0,1} %fusion.30, "
          "bf16[2,8192,2048]{2,1,0:T(8,128)(2,1)} %get-tuple-element.11)")
RING = "%transpose.1584 = bf16[64,320,16,2,256]{4,3,2,1,0:T(2,128)(2,1)} transpose(bf16[64,320,16,2,256] %gather.1000)"
SHARED = ("%fusion.40 = bf16[64,8,512]{2,0,1:T(8,128)(2,1)} fusion(bf16[64,8,2048]{2,0,1} %fusion.39, "
          "bf16[6,2048,512]{2,1,0:T(8,128)(2,1)} %get-tuple-element.12)")
HEAD = "%fusion.2 = f32[64,37984]{1,0:T(8,128)} fusion(bf16[64,2048]{1,0} %fusion.809, bf16[2048,37984])"


@pytest.mark.parametrize("name,kind", [
    (GMM, "GROUPED"), (GMM_DOWN, "GROUPED"), (SORT, None), (KV_GATHER, None),
    (GATHER, "ROUTED"), (TOPK, "ROUTED"), (STATE_SLICE, "STATE"),
    (STATE_READ, "STATE"), (WINDOW, "MIXER"), (QKV_PROJ, "MIXER"),
    (GATED_NORM, "MIXER"), (Q_PROJ, None), (RING, None), (SHARED, None),
    (HEAD, None),
])
def test_ops_are_told_by_the_shapes_only_these_layers_have(name, kind):
    """8,192, 4,096 and 512 are everybody's widths here: the gated query
    projection, the gathered ring, the gather of the keys and values by
    ``[rows x blocks]`` block ids and the shared expert are NOT counted."""
    assert q3n.op_kind(name, q3n.sizes(MODEL)) == kind


def _ctx():
    """A made-up traced run: 6 s of profile in a 51 s window, 50 groups of 4
    steps dispatched inside the trace; the counters of 1,800 steps."""
    c = manifest.cell(manifest.load(), CELL)
    t0 = 100.0
    spans_ = [[i, None, "sched.dispatch", t0 + 0.03 * i, 0.001,
               {"chunks": 1, "k": 4}] for i in range(1, 51)]
    moe0 = {"moe.pairs": 0, "moe.experts_hit": 0, "moe.layer_steps": 0,
            "moe.pairs_elsewhere": 0}
    moe1 = {"moe.pairs": 1800 * 8 * 1250, "moe.experts_hit": 1800 * 8 * 125,
            "moe.layer_steps": 1800 * 8, "moe.pairs_elsewhere": 1800 * 8 * 3750}
    loop0 = {"decode_steps": 0, "spans": {"loop": {"seconds": 1.0}}, **moe0}
    loop1 = {"decode_steps": 1800, "spans": {"loop": {"seconds": 52.0}}, **moe1}
    gauges = {"state_bytes": 1, "state_layers": 6, "kv_layers": 2}
    return {
        "records": [], "cell": c, "peaks": V5E, "stats": stats,
        "dims": REF.dims(MODEL), "costs": costs, "window": {"w0": 80.0, "w1": 131.0},
        "metrics_before": {"loop": loop0, "cache": gauges},
        "metrics_after": {"loop": loop1, "cache": gauges},
        "flight_trace": {"loop": {"spans": spans_}},
        "trace": {"busy_s": 5.9, "window_s": 6.0, "t_start": t0, "t_stop": t0 + 6.0,
                  "ops": [[GMM, 2.0], [GMM_DOWN, 0.4], [TOPK, 0.1], [GATHER, 0.1],
                          [KV_GATHER, 1.2],
                          [STATE_READ, 0.9], [STATE_SLICE, 0.3], [QKV_PROJ, 0.2],
                          [WINDOW, 0.1], [RING, 0.8], [SHARED, 0.1], [HEAD, 0.2]]},
    }


def test_the_readers_on_a_made_up_trace():
    ctx, z = _ctx(), q3n.sizes(MODEL)
    assert q3n.experts_pct(ctx) == pytest.approx(100 * 2.6 / 5.9)
    assert q3n.gdn_pct(ctx) == pytest.approx(100 * 1.5 / 5.9)
    assert q3n.experts_tokens_per_expert(ctx) == pytest.approx(10.0)
    assert q3n.experts_elsewhere_pct(ctx) == pytest.approx(75.0)
    # 200 steps in the trace: 2.4 s / 200 = 12 ms of grouped matmul a step
    # over a floor at 125 experts hit: 8 x 125 x 6.29 MB / 819 GB/s = 7.68 ms
    floor = q3n.grouped_floor_s(z, "bfloat16", V5E, hit=125, pairs=1250)
    assert floor == pytest.approx(7.68e-3, rel=0.01)
    assert q3n.experts_grouped_roofline(ctx) == pytest.approx(100 * floor / 12e-3)
    # the update: 1.2 s / 200 = 6 ms a step over 1.97 ms
    assert q3n.gdn_update_roofline(ctx) == pytest.approx(100 * 1.966e-3 / 6e-3, rel=0.01)
    ctx["trace"]["ops"][5][1] = 2.1  # 2.4 s / 200 = 12 ms a step
    assert q3n.gdn_update_roofline(ctx) == pytest.approx(16.4, rel=0.01)


@pytest.mark.parametrize("name", READERS)
@pytest.mark.parametrize("what", ["no_such_ops", "parent_program", "old_cell", "no_trace"])
def test_a_reader_returns_nothing_where_there_is_nothing_to_read(name, what):
    """A trace without these layers' ops; the parent's /metrics (no
    ``moe.pairs_elsewhere``; the ops are then another family's at best); a
    cell whose configuration has no such layers; an untraced run without
    counters. None, never an exception."""
    ctx = _ctx()
    if what == "no_such_ops":
        ctx["trace"]["ops"] = [[HEAD, 1.5], [RING, 0.2]]
        ctx["metrics_before"] = ctx["metrics_after"] = {"loop": {"decode_steps": 5, "spans": {}}}
    elif what == "parent_program":
        ctx["trace"]["ops"] = [[HEAD, 1.5], [SHARED, 0.2]]
        for key in ("metrics_before", "metrics_after"):
            ctx[key] = {"loop": {k: v for k, v in ctx[key]["loop"].items()
                                 if k != "moe.pairs_elsewhere"}}
    elif what == "old_cell":
        ctx["cell"] = manifest.cell(manifest.load(), "olmo-hybrid-7b-1chip.chat")
        ctx["metrics_before"] = ctx["metrics_after"] = {"loop": {"decode_steps": 5, "spans": {}}}
    else:
        ctx["trace"] = None
        ctx["metrics_before"] = ctx["metrics_after"] = None
    assert getattr(q3n, name)(ctx) is None


@pytest.fixture(scope="module")
def toy_run():
    """A toy qwen3_next cell (chip 1 of 4's share) through ``run.py`` on the
    CPU, traced."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "BENCH_RUN": "ignored",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=1"}
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--manifest",
         "tests/benchmark/toy_qwen3_next/BENCHMARK.json", "--allow-cpu",
         "--workload", "tiny-qwen3-next.toy-doc", "--seed", str(2**31 + 44),
         "--seconds", "3", "--trace", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900,
    )
    assert p.returncode == 0, p.stderr[-3000:]
    return p


def test_a_toy_qwen3_next_cell_end_to_end_on_the_cpu(toy_run):
    """The reference through ``lib/check.py`` with its file alone (prefill
    and one cached step inside the float32 tolerance, the control outside
    it), zero compilations in the window, every request answered, admission
    through the mixed step."""
    lines = toy_run.stdout.strip().splitlines()
    last, detail = json.loads(lines[-1]), json.loads(lines[-2])
    assert last["correct"] is True and last["failed"] == 0, toy_run.stderr[-3000:]
    assert detail["compilations_in_window"] == 0
    assert detail["logits"]["control_fault"] == "shared_expert_gate_lost"
    assert detail["logits"]["prefill"] < 1e-4 and detail["logits"]["decode"] < 1e-4
    assert detail["logits"]["control"] > 0.1
    assert {"host_turn_pct", "loop_host_ms_per_step", "host_ms_per_group",
            "first_token_p50_ms", "gen_late_p90_ms", "queue_wait_p50_ms",
            "broker_wait_p50_ms", "row_wait_p50_ms", "first_token_lag_p50_ms",
            "stream_lag_p50_ms"} <= set(last["metrics"])
    assert "ttft_p90_ms" in detail["end_to_end"]
    assert "chunked_prefill=8" in toy_run.stderr
