"""What PR 38 added to the benchmark: the deepseek_v3 reference's contract
(``dims``, ``layers`` yielding ``dense`` then ``moe``, a control that fails),
the manifest's new entries, the experts' and the latent's costs, the five
readers of ``benchmark/lib/moe.py`` on made-up contexts (functions there, not
per-layer metrics yet: that module says why), and a toy deepseek_v3 cell
served by ``run.py --manifest --allow-cpu`` on the CPU (its own toy tree,
``tests/benchmark/toy_deepseek_v3/``: the files the benchmark had are not
edited). No device number is produced here."""

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

from benchmark.lib import costs, manifest, moe, peaks, stats  # noqa: E402

CELL = "kanana-2-30b-a3b-1chip.doc"
CONFIG = json.loads(
    (ROOT / "benchmark" / "configs" / "kanana-2-30b-a3b-1chip.json").read_text())
MODEL = {k: v for k, v in CONFIG.items() if k not in manifest.HARNESS_KEYS}
REF = manifest.load_module("reference", "deepseek_v3")
V5E = peaks.peaks_for("TPU v5 lite")
NEW = ["moe_pct", "moe_decode_roofline", "moe_tokens_per_expert", "mla_pct",
       "mla_decode_roofline"]


def test_the_manifest_has_the_new_entries_and_nothing_else_moved():
    """The cell reports both tails and ``setup_s`` and joins the lists of the
    accepted per-layer metrics whose readers find something in it. No
    per-layer entry is appended and the cell is not on
    ``sampler_search_pct``'s list: the accepted
    ``test_bench_sampler_search.py`` pins ``per_layer[-1]`` and its two cells
    (PERF.md section 7: a ``benchmark`` PR's to pin by name)."""
    m = manifest.load()
    c = manifest.cell(m, CELL)
    assert c["entry"]["chips"] == 1 and c["traffic"]["top_p"] == 1.0
    assert "top_k" not in c["traffic"]
    assert c["traffic"]["prompt"] == {
        "dist": "lognormal", "median": 1536, "sigma": 0.6, "min": 512, "max": 4096}
    assert c["traffic"]["output"] == {
        "dist": "lognormal", "median": 256, "sigma": 0.4, "min": 128, "max": 512}
    assert sorted(e["name"] for e in c["end_to_end"]) == [
        "setup_s", "tpot_p90_ms", "ttft_p90_ms"]
    assert {e["name"] for e in c["per_layer"]} == {
        "decode_step_dev_ms", "host_turn_pct", "loop_host_ms_per_step",
        "host_ms_per_group", "first_token_p50_ms", "decode_step_mfu_roofline",
        "gen_late_p90_ms", "queue_wait_p50_ms", "broker_wait_p50_ms",
        "row_wait_p50_ms", "first_token_lag_p50_ms", "stream_lag_p50_ms"}
    assert [w["name"] for w in m["workloads"]] == [
        "starcoderbase-1b.gen", "falcon-h1-34b-1chip.chat", CELL]
    assert m["per_layer"][-1]["name"] == "sampler_search_pct"
    assert len(c["entry"]["why"]) <= 200


def test_the_configuration_keeps_every_published_key_but_the_two_reduced():
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if not catalog.exists():
        pytest.skip("no catalog on this machine")
    row = next(json.loads(line) for line in catalog.read_text().splitlines()
               if '"kanana-2-30b-a3b-instruct-2601"' in line)
    assert CONFIG["source"] == row["source_url"]
    differ = sorted(k for k, v in row["config"].items() if MODEL.get(k, "absent") != v)
    assert differ == sorted(CONFIG["reduced"]) == [
        "max_position_embeddings", "num_hidden_layers"]
    assert MODEL["num_hidden_layers"] == 7 and CONFIG["serve"]["max_seq_len"] == 5120


def test_dims_match_the_programs_parameter_shapes():
    """``total_params`` leaves out the embedding table (held, gathered by
    row); with it, the count is the program's own. The cached quantity is
    priced as the 576 numbers of the latent, not as 32 heads."""
    from llmss_tpu.models.decoder import param_shapes
    from llmss_tpu.models.registry import config_from_hf

    dims = REF.dims(MODEL)
    cfg = config_from_hf(types.SimpleNamespace(**MODEL))
    n = sum(math.prod(x.shape) for x in jax.tree.leaves(param_shapes(cfg)))
    assert n == dims["total_params"] + MODEL["vocab_size"] * MODEL["hidden_size"]
    assert n == pytest.approx(4.4297e9, rel=1e-3)
    assert dims["matmul_params"] == pytest.approx(713e6, rel=5e-3)
    assert (dims["kv_heads"], dims["head_dim"]) == (1, 288)
    assert costs.kv_bytes_per_token(dims, "bfloat16") == 7 * 576 * 2
    z = moe.sizes(MODEL)
    assert moe.latent_bytes_per_token(z, "bfloat16") == 7 * 576 * 2
    assert cfg.cache_row == (640,) and z["row"] == 640
    assert moe.expert_bytes(z, "bfloat16") == pytest.approx(9.44e6, rel=1e-3)
    floor = costs.decode_step_floor_s(dims, "bfloat16", V5E, rows=36, context=2000)
    assert floor["bound_by"] == "memory"
    assert floor["floor_s"] == pytest.approx(10.9e-3, rel=0.03)
    # the experts' part of it: 105 of 128 hit at 36 rows, 128 in a mixed step
    assert moe.grouped_floor_s(z, "bfloat16", V5E, hit=105, pairs=216) == (
        pytest.approx(7.26e-3, rel=0.01))
    assert moe.grouped_floor_s(z, "bfloat16", V5E, hit=128, pairs=1200) == (
        pytest.approx(8.85e-3, rel=0.01))
    # operations bound it only from about 31,000 pairs a layer
    assert moe.grouped_floor_s(z, "bfloat16", V5E, hit=128, pairs=62000) == (
        pytest.approx(2 * 8.9e-3, rel=0.01))


def test_layers_yield_the_dense_stack_then_the_expert_stack():
    from llmss_tpu.models.decoder import param_shapes
    from llmss_tpu.models.registry import config_from_hf

    small = {**MODEL, "num_hidden_layers": 3, "vocab_size": 256,
             "hidden_size": 64, "intermediate_size": 128,
             "moe_intermediate_size": 32, "n_routed_experts": 8}
    shapes = param_shapes(config_from_hf(types.SimpleNamespace(**small)))
    params = jax.tree.map(lambda s: jax.numpy.ones(s.shape, s.dtype), shapes)
    got = list(REF.layers(small, params))
    assert [kind for kind, _ in got] == ["dense", "moe", "moe"]
    assert "gate" in got[0][1] and "router" not in got[0][1]
    assert got[1][1]["experts_gate"].shape == (8, 64, 32)
    name, faulty = REF.control(params)
    assert not jax.numpy.any(faulty["blocks"]["router"].b)
    assert faulty["blocks"]["router"].w is params["blocks"]["router"].w
    assert name == "selection_bias_lost"


# Names as the chip's profile has them (my chip run, PR 38: the first traced
# run of the cell, cut to a few operands): the output's shape, then the
# operands with theirs.
GMM_UP = ("%gmm.11 = bf16[6144,768]{1,0:T(8,128)(2,1)S(1)} custom-call(s32[]{:T(128)} "
          "%get-tuple-element.3884, s32[129]{0:T(256)S(1)} %pad_add_fusion.13)")
GMM_DOWN = ("%gmm.13 = bf16[6144,2048]{1,0:T(8,128)(2,1)S(1)} custom-call(s32[]{:T(128)} "
            "%get-tuple-element.3884, s32[129]{0:T(256)S(1)} %pad_add_fusion.13)")
GMM_DECODE = "%gmm.12 = bf16[384,768]{1,0:T(8,128)(2,1)S(1)} custom-call(%get-tuple-element.3799)"
PAIR_GATHER = ("%fusion.751 = bf16[6144,2048]{1,0:T(8,128)(2,1)S(1)} fusion(bf16[1024,2048]"
               "{1,0:T(8,128)(2,1)S(1)} %bitcast.855, s32[6144]{0:T(1024)S(1)} %get-tuple-element.3891)")
ROUTER = ("%fusion.755 = f32[6144]{0:T(1024)S(1)} fusion(f32[1024,128]{1,0:T(8,128)S(1)} "
          "%get-tuple-element.3894, s32[6144]{0:T(1024)S(1)} %reshape.1962)")
SHARED = ("%fusion.757 = bf16[64,16,1536]{2,1,0:T(8,128)(2,1)} fusion(bf16[64,16,2048]"
          "{2,1,0:T(8,128)(2,1)S(1)} %get-tuple-element.3890, bf16[6,2048,1536]{2,1,0} %get)")
GATHER = ("%fusion.730 = bf16[20480,16,640]{2,1,0:T(8,128)(2,1)} fusion(bf16[7,20480,16,640]"
          "{3,2,1,0:T(8,128)(2,1)} %bitcast.834, s32[20480]{0:T(1024)S(1)} %copy-done.4)")
SCORES = ("%fusion.731 = (f32[64,32,16]{1,2,0:T(8,128)S(1)}, f32[64,1,32,16,5120]"
          "{4,2,3,0,1:T(8,128)}) fusion(bf16[64,5120,640,1]{2,1,3,0:T(8,128)(2,1)} %bitcast.836)")
SCORE_SUM = ("%fusion.733 = f32[64,32,16]{1,2,0:T(8,128)S(1)} fusion(f32[64,1,32,16,5120]"
             "{4,2,3,0,1:T(8,128)} %get-tuple-element.3876, f32[64,32,16]{1,2,0} %max.47)")
WEIGHTED_SUM = ("%fusion.735 = bf16[64,16,32,640]{3,2,1,0:T(8,128)(2,1)} fusion(f32[64,32,16,640]"
                "{3,1,2,0:T(8,128)S(1)} %fusion.732, bf16[64,5120,640,1]{2,1,3,0} %bitcast.83)")
FRESH = ("%fusion.719 = bf16[64,16,576]{2,1,0:T(8,128)(2,1)S(1)} fusion(bf16[64,16,2048]"
         "{2,1,0:T(8,128)(2,1)S(1)} %get-tuple-element.4054, f32[2048]{0:T(1024)S(1)} %bitcast.874)")
O_LAT = "%fusion.9 = bf16[64,32,512]{2,1,0:T(8,128)(2,1)} fusion(bf16[64,32,128]{2,1,0} %p.3)"
HEAD = ("%is-finite_reduce_fusion.2 = (pred[64]{0:T(512)(128)(4,1)S(1)}, f32[64,128256]"
        "{1,0:T(8,128)S(1)}) fusion(bf16[64,2048]{1,0:T(8,128)(2,1)S(1)} %fusion.680)")
Q_PROJ = ("%fusion.715 = bf16[64,16,32,192]{3,1,2,0:T(8,128)(2,1)S(1)} fusion(bf16[32,192,2048,1]"
          "{2,1,0,3:T(8,128)(2,1)S(1)} %bitcast.861, bf16[64,16,2048]{2,1,0} %x)")
DENSE_MLP = ("%fusion.675 = bf16[64,16,6144]{2,1,0:T(8,128)(2,1)S(1)} fusion(bf16[1,2048,6144]"
             "{2,1,0:T(8,128)(2,1)S(1)} %copy-done.118, bf16[64,16,6144]{2,1,0} %fusio)")
LEDGER_STYLE = "_gmm.12___bf16_384_768__1_0:T_8_128__2_1_S_1___custom-call__get-"


@pytest.mark.parametrize("name,kind", [
    (GMM_UP, "GROUPED"), (GMM_DOWN, "GROUPED"), (GMM_DECODE, "GROUPED"),
    (LEDGER_STYLE, "GROUPED"), (PAIR_GATHER, "MOE"), (ROUTER, "MOE"),
    (SHARED, "MOE"), (GATHER, "POOL"), (SCORES, "POOL"), (SCORE_SUM, "POOL"),
    (WEIGHTED_SUM, "POOL"), (FRESH, "MLA"), (O_LAT, "MLA"), (HEAD, None),
    (Q_PROJ, None), (DENSE_MLP, None),
])
def test_ops_are_told_by_the_shapes_only_these_layers_have(name, kind):
    assert moe.op_kind(name, moe.sizes(MODEL)) == kind


def _reader(name):
    return getattr(moe, name)


def _ctx():
    """A made-up traced run: 6 s of profile in a 51 s window, 36 requests
    decoding at a context of 2,000, 300 steps dispatched inside the trace."""
    c = manifest.cell(manifest.load(), CELL)
    t0 = 100.0
    records = [{
        "first": 0.0, "done": 1e9, "body": {"token_ids": [0] * 1900},
        "increments": [[1.0, 100]],
    } for _ in range(36)]
    spans_ = [[i, None, "sched.dispatch", t0 + 0.02 * i, 0.001,
               {"chunks": 1, "k": 4}] for i in range(1, 76)]
    loop0 = {"decode_steps": 0, "moe.pairs": 0, "moe.experts_hit": 0,
             "moe.layer_steps": 0, "spans": {"loop": {"seconds": 1.0}}}
    loop1 = {"decode_steps": 2550, "moe.pairs": 2550 * 6 * 216,
             "moe.experts_hit": 2550 * 6 * 105, "moe.layer_steps": 2550 * 6,
             "spans": {"loop": {"seconds": 52.0}}}
    return {
        "records": records, "cell": c, "peaks": V5E, "stats": stats,
        "dims": REF.dims(MODEL), "costs": costs, "window": {"w0": 80.0, "w1": 131.0},
        "metrics_before": {"loop": loop0, "cache": {"latent_bytes_per_token": 8960}},
        "metrics_after": {"loop": loop1, "cache": {"latent_bytes_per_token": 8960}},
        "flight_trace": {"loop": {"spans": spans_}},
        "trace": {"busy_s": 5.9, "window_s": 6.0, "t_start": t0, "t_stop": t0 + 6.0,
                  "ops": [[GMM_UP, 1.2], [GMM_DOWN, 0.6], [PAIR_GATHER, 0.1],
                          [ROUTER, 0.05], [SHARED, 0.25], [GATHER, 0.5],
                          [SCORES, 0.2], [WEIGHTED_SUM, 0.2], [FRESH, 0.02],
                          [HEAD, 0.3], [DENSE_MLP, 0.4]]},
    }


def test_the_five_readers_on_a_made_up_trace():
    ctx, z = _ctx(), moe.sizes(MODEL)
    assert _reader("moe_pct")(ctx) == pytest.approx(100 * 2.2 / 5.9)
    assert _reader("mla_pct")(ctx) == pytest.approx(100 * 0.92 / 5.9)
    assert _reader("moe_tokens_per_expert")(ctx) == pytest.approx(216 / 105)
    # 75 groups of 4 steps inside the trace: 300 steps; 1.8 s / 300 = 6 ms
    got = _reader("moe_decode_roofline")(ctx)
    assert got == pytest.approx(100 * moe.grouped_floor_s(
        z, "bfloat16", V5E, hit=105, pairs=216) / 6e-3)
    assert 100 < got < 125  # a made-up time under the floor reads over 100
    ctx["trace"]["ops"][0][1] = 2.4  # 3.0 s / 300 = 10 ms a step
    assert _reader("moe_decode_roofline")(ctx) == pytest.approx(72.6, rel=0.01)
    # 36 rows x 2,000 tokens x 8,064 bytes over 819 GB/s = 0.709 ms; 3 ms read
    assert _reader("mla_decode_roofline")(ctx) == pytest.approx(
        100 * (36 * 2000 * 8064 / 819e9) / (0.9 / 300), rel=1e-3)


@pytest.mark.parametrize("name", NEW)
@pytest.mark.parametrize("what", ["no_such_ops", "parent_program", "old_cell", "no_trace"])
def test_a_reader_returns_nothing_where_there_is_nothing_to_read(name, what):
    """A trace without these layers' ops; the parent's /metrics (no routing
    counters, no ``cache`` block); a cell whose configuration has neither
    experts nor a latent; an untraced run. None, never an exception."""
    ctx = _ctx()
    if what == "no_such_ops":
        ctx["trace"]["ops"] = [[HEAD, 1.5], [DENSE_MLP, 0.2]]
        ctx["metrics_after"] = ctx["metrics_before"]
    elif what == "parent_program":
        plain = {"loop": {"decode_steps": 5, "spans": {"loop": {"seconds": 9.0}}}}
        ctx["metrics_before"] = {"loop": {"decode_steps": 0, "spans": {"loop": {"seconds": 1.0}}}}
        ctx["metrics_after"] = plain
        ctx["trace"]["ops"] = [[HEAD, 1.5], [DENSE_MLP, 0.2]]
    elif what == "old_cell":
        ctx["cell"] = manifest.cell(manifest.load(), "starcoderbase-1b.gen")
        ctx["metrics_before"] = ctx["metrics_after"] = {"loop": {}}
    else:
        ctx["trace"] = None
        ctx["metrics_before"] = ctx["metrics_after"] = None
    assert _reader(name)(ctx) is None


@pytest.fixture(scope="module")
def toy_run():
    """A toy deepseek_v3 cell through ``run.py`` on the CPU, traced."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "BENCH_RUN": "ignored",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=1"}
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--manifest",
         "tests/benchmark/toy_deepseek_v3/BENCHMARK.json", "--allow-cpu",
         "--workload", "tiny-deepseek-v3.toy-doc", "--seed", str(2**31 + 38),
         "--seconds", "3", "--trace", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600,
    )
    assert p.returncode == 0, p.stderr[-3000:]
    return p


def test_a_toy_deepseek_v3_cell_end_to_end_on_the_cpu(toy_run):
    lines = toy_run.stdout.strip().splitlines()
    last, detail = json.loads(lines[-1]), json.loads(lines[-2])
    assert last["correct"] is True and last["failed"] == 0, toy_run.stderr[-3000:]
    assert detail["compilations_in_window"] == 0
    assert detail["logits"]["control_fault"] == "selection_bias_lost"
    assert detail["logits"]["prefill"] < 1e-4 and detail["logits"]["decode"] < 1e-4
    # admitted through the mixed step, a request still leaves every seam of
    # its way to the first token: the readers the root manifest lists it for
    assert {"gen_late_p90_ms", "queue_wait_p50_ms", "broker_wait_p50_ms",
            "row_wait_p50_ms", "first_token_lag_p50_ms", "stream_lag_p50_ms",
            "first_token_p50_ms"} <= set(last["metrics"])
    assert "ttft_p90_ms" in detail["end_to_end"]
