"""What PR 40 added to the benchmark: the olmo_hybrid reference's contract
(``dims`` with its ``kv_layers``, ``layers`` yielding the two kinds in the
published pattern's order from the ragged tree, a control that fails), the
configuration's and the cell's entries in ``BENCHMARK.json``, the
delta rule's costs, the two readers of
``benchmark/lib/gdn.py`` on made-up contexts (functions there, not per-layer
metrics yet: that module says why), and a toy olmo_hybrid cell served by
``run.py --manifest --allow-cpu`` on the CPU (its own toy tree,
``tests/benchmark/toy_olmo_hybrid/``: the files the benchmark had are not
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

from benchmark.lib import costs, gdn, manifest, peaks, stats  # noqa: E402

CELL = "olmo-hybrid-7b-1chip.chat"
CONFIG = json.loads(
    (ROOT / "benchmark" / "configs" / "olmo-hybrid-7b-1chip.json").read_text())
MODEL = {k: v for k, v in CONFIG.items() if k not in manifest.HARNESS_KEYS}
REF = manifest.load_module("reference", "olmo_hybrid")
V5E = peaks.peaks_for("TPU v5 lite")
NEW = ["gdn_pct", "gdn_decode_roofline"]


EARLIER = ["starcoderbase-1b.gen", "falcon-h1-34b-1chip.chat",
           "kanana-2-30b-a3b-1chip.doc"]


def test_the_manifest_has_the_cell_and_what_was_there_has_not_moved():
    """The configuration and the cell are appended to ``BENCHMARK.json``. The
    cell reports both tails (``ttft_p90_ms`` spread by 1.44% / 1.20% over its
    two sets of six runs: PR 38's rule) and ``setup_s`` and joins the lists
    of the accepted per-layer metrics whose readers find something in it; it
    runs the mix ``falcon-h1-34b-1chip.chat`` runs, unedited. No per-layer
    entry is appended. The earlier cells are held by NAME and ORDER, not by
    the length of the list: the accepted ``test_bench_deepseek_v3.py`` holds
    the list to exactly its three cells, so it fails on any cell after its
    own, this one included (PERF.md section 7 (5): a ``benchmark`` PR's to
    relax, as here)."""
    m = manifest.load()
    c = manifest.cell(m, CELL)
    assert c["entry"]["chips"] == 1 and c["entry"]["traffic"] == "chat"
    assert c["traffic"]["top_p"] == 1.0 and "top_k" not in c["traffic"]
    assert c["traffic"]["prompt"] == {
        "dist": "lognormal", "median": 96, "sigma": 0.7, "min": 33, "max": 256}
    assert c["serve"]["chunked_prefill"] in (4, 8)
    assert (c["serve"]["rows"], c["serve"]["max_seq_len"]) == (64, 1024)
    assert sorted(e["name"] for e in c["end_to_end"]) == [
        "setup_s", "tpot_p90_ms", "ttft_p90_ms"]
    assert {e["name"] for e in c["per_layer"]} == {
        "decode_step_dev_ms", "host_turn_pct", "loop_host_ms_per_step",
        "host_ms_per_group", "first_token_p50_ms", "decode_step_mfu_roofline",
        "gen_late_p90_ms", "queue_wait_p50_ms", "broker_wait_p50_ms",
        "row_wait_p50_ms", "first_token_lag_p50_ms", "stream_lag_p50_ms"}
    # appended, and only appended: the earlier cells lead every list they
    # were on, in their order, and this cell comes after them
    names = [w["name"] for w in m["workloads"]]
    assert names[:3] == EARLIER and names.index(CELL) >= 3
    assert [x["name"] for x in m["configs"]][:3] == [n.rsplit(".", 1)[0] for n in EARLIER]
    assert c["entry"]["config"] in [x["name"] for x in m["configs"]][3:]
    for e in m["end_to_end"] + m["per_layer"]:
        cells = e.get("workloads", [])
        if CELL in cells:
            before = cells[:cells.index(CELL)]
            assert before == [n for n in EARLIER if n in before] and before
    tpot = next(e for e in m["end_to_end"] if e["name"] == "tpot_p90_ms")
    mfu = next(e for e in m["per_layer"] if e["name"] == "decode_step_mfu_roofline")
    assert tpot["workloads"] == mfu["workloads"]
    assert "sampler_search_pct" in [e["name"] for e in m["per_layer"]]
    assert not set(NEW) & {e["name"] for e in m["per_layer"]}
    assert len(c["entry"]["why"]) <= 200
    assert f"{c['params']['rate']:g} req/s" in c["entry"]["why"]
    cfg = next(x for x in m["configs"] if x["name"] == c["entry"]["config"])
    assert cfg["file"] == "benchmark/configs/olmo-hybrid-7b-1chip.json"
    assert cfg["source"] == CONFIG["source"]
    assert all(1 <= len(e["why"]) <= 200 for e in m["configs"] + m["workloads"])


def test_the_configuration_keeps_every_published_key_but_the_three_reduced():
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if not catalog.exists():
        pytest.skip("no catalog on this machine")
    row = next(json.loads(line) for line in catalog.read_text().splitlines()
               if '"Olmo-Hybrid-7B"' in line)
    assert CONFIG["source"] == row["source_url"]
    differ = sorted(k for k, v in row["config"].items() if MODEL.get(k, "absent") != v)
    assert differ == sorted(CONFIG["reduced"]) == [
        "layer_types", "max_position_embeddings", "num_hidden_layers"]
    # three whole periods of the published pattern, from its start
    assert MODEL["layer_types"] == row["config"]["layer_types"][:12]
    assert MODEL["num_hidden_layers"] == 12 and CONFIG["serve"]["max_seq_len"] == 1024
    for key in ("source", "reduced", "assumed", "memory"):
        assert CONFIG[key]


def test_dims_match_the_programs_parameter_shapes():
    """``total_params`` leaves out the embedding table (held, gathered by
    row); with it, the count is the program's own. Keys and values are
    priced over the three layers that hold any, the state over the nine that
    hold one."""
    from llmss_tpu.models.decoder import param_shapes
    from llmss_tpu.models.registry import config_from_hf

    dims = REF.dims(MODEL)
    cfg = config_from_hf(types.SimpleNamespace(**MODEL))
    n = sum(math.prod(x.shape) for x in jax.tree.leaves(param_shapes(cfg)))
    assert n == dims["total_params"] + MODEL["vocab_size"] * MODEL["hidden_size"]
    assert n == pytest.approx(3.268e9, rel=1e-3)
    assert (dims["layers"], dims["kv_layers"]) == (12, 3)
    assert costs.kv_bytes_per_token(dims, "bfloat16") == 3 * 2 * 30 * 128 * 2
    z = gdn.sizes(MODEL)
    assert dims["state_bytes_per_row"] == gdn.state_bytes_per_row(z, "bfloat16")
    assert dims["state_bytes_per_row"] == 9 * (30 * 96 * 192 * 4 + 3 * 11520 * 2)
    assert gdn.weight_bytes(z, "bfloat16") == pytest.approx(1.5975e9, rel=1e-3)
    # the issue's reckoning: 11.1 ms at 64 rows, 44 of them at 350 tokens
    floor = costs.decode_step_floor_s(dims, "bfloat16", V5E, rows=64, context=240)
    assert floor["bound_by"] == "memory"
    assert floor["floor_s"] == pytest.approx(11.1e-3, rel=0.03)
    # of which the mixers: 2 x 1.31 GB of state and 1.60 GB of weights
    assert gdn.decode_update_floor_s(z, "bfloat16", V5E, rows=64) == (
        pytest.approx(5.16e-3, rel=0.01))


def _small():
    small = {**MODEL, "num_hidden_layers": 8, "layer_types": MODEL["layer_types"][:8],
             "vocab_size": 256, "hidden_size": 64, "intermediate_size": 128,
             "num_attention_heads": 2, "num_key_value_heads": 2,
             "linear_num_key_heads": 2, "linear_num_value_heads": 2,
             "linear_key_head_dim": 8, "linear_value_head_dim": 16}
    from llmss_tpu.models.decoder import param_shapes
    from llmss_tpu.models.registry import config_from_hf

    shapes = param_shapes(config_from_hf(types.SimpleNamespace(**small)))
    return small, jax.tree.map(lambda s: jax.numpy.ones(s.shape, s.dtype), shapes)


def test_layers_yield_the_kinds_in_the_published_order_from_two_stacks():
    small, params = _small()
    got = list(REF.layers(small, params))
    assert [kind for kind, _ in got] == small["layer_types"]
    assert "gdn_qkv" in got[0][1] and "q" not in got[0][1]
    assert "q_norm" in got[3][1] and "gdn_qkv" not in got[3][1]
    assert got[4][1]["gdn_qkv"].w.shape == (64, 2 * 16 + 32)
    name, faulty = REF.control(params)
    ab = faulty["linear"]["gdn_ab"].w
    assert name == "beta_projection_lost"
    assert not jax.numpy.any(ab[..., 2:]) and jax.numpy.all(ab[..., :2] == 1)


# Names as the chip's profile has them (my chip run, PR 40, call 3: the traced
# run of the cell, cut to a few operands): the output's shape, then the
# operands with theirs. The flat forms are an earlier tree's (call 2).
STATE_SLICE = ("%dynamic-slice_bitcast_fusion.7 = f32[64,30,96,192]{3,2,1,0:T(8,128)} fusion("
               "f32[9,64,30,96,192]{4,3,2,1,0:T(8,128)} %fusion.921, s32[]{:T(128)} %select_n.874)")
STATE_WRITE = ("%fusion.921 = f32[9,64,30,96,192]{4,3,2,1,0:T(8,128)} fusion(f32[9,64,30,96,192]"
               "{4,3,2,1,0:T(8,128)} %get-tuple-element.4298, s32[]{:T(128)} %select_n.872, f32[64,30,192])")
STATE_READ = ("%multiply_reduce_fusion.14 = f32[64,30,8,192]{3,2,1,0:T(8,128)S(1)} fusion(f32[64,30,96,192]"
              "{3,2,1,0:T(8,128)} %dynamic-slice_bitcast_fusion.7, f32[64,30,8,96]{3,2,1,0} %custom-call.83)")
FLAT_SLICE = ("%constant_dynamic-slice_fusion.42 = f32[1,64,30,18432]{3,1,2,0:T(8,128)} fusion("
              "f32[9,64,30,18432]{3,1,2,0:T(8,128)} %fusion.924, s32[]{:T(128)} %select_n.874)")
QKV_PROJ = ("%fusion.912 = bf16[64,4,11520]{2,0,1:T(8,128)(2,1)S(1)} fusion(bf16[64,4,3840]"
            "{2,0,1:T(8,128)(2,1)S(1)} %fusion.910, bf16[9,3840,11520]{2,1,0:T(8,128)(2,1)} %get-tuple-element.4362)")
WINDOW = ("%fusion.594 = bf16[64,34560]{1,0:T(8,128)(2,1)} fusion(bf16[64,7,11520]{2,1,0} %concatenate.9)")
GATE = ("%fusion.911 = bf16[64,4,5760]{2,0,1:T(8,128)(2,1)} fusion(bf16[64,4,3840]{2,0,1:T(8,128)(2,1)S(1)} "
        "%fusion.910, bf16[9,3840,5760]{2,1,0:T(8,128)(2,1)} %get-tuple-element.4361)")
OUT_PROJ = ("%fusion.903 = (f32[64,4]{0,1:T(4,128)S(1)}, bf16[64,4,3840]{2,0,1:T(8,128)(2,1)S(1)}) fusion("
            "bf16[9,5760,3840]{2,1,0:T(8,128)(2,1)} %get-tuple-element.4360, f32[64,4,30,192]{3,2,1,0} %fusion.902)")
AB = "%fusion.592 = f32[64,4,60]{2,1,0:T(8,128)} fusion(bf16[64,4,3840]{2,1,0} %fusion.588)"
MLP = ("%fusion.907 = bf16[64,4,11008]{2,0,1:T(8,128)(2,1)S(1)} fusion(bf16[64,4,3840]{2,0,1:T(8,128)(2,1)S(1)} "
       "%fusion.905, bf16[9,3840,11008]{2,1,0:T(8,128)(2,1)} %get-tuple-element.4365)")
HEAD = ("%is-finite_reduce_fusion.2 = (pred[64]{0:T(512)(128)(4,1)S(1)}, f32[64,100352]"
        "{1,0:T(8,128)S(1)}) fusion(bf16[64,3840]{1,0:T(8,128)(2,1)S(1)} %fusion.809, bf16[3840,100352])")
PAGED_GATHER = ("%fusion.955 = bf16[4096,16,32,128]{3,2,1,0:T(8,128)(2,1)} fusion(bf16[3,4096,16,32,128]"
                "{4,3,2,1,0:T(8,128)(2,1)} %get-tuple-element.4380, s32[4096]{0:T(1024)S(1)} %copy-done.3)")
SCORES = ("%fusion.960 = (f32[64,32,4]{2,1,0:T(8,128)S(1)}, f32[64,32,1,4,1024]{4,3,1,0,2:T(4,128)S(1)}) "
          "fusion(bf16[64,4,30,128]{3,1,2,0:T(4,128)(2,1)S(1)} %copy-done.25, bf16[64,1024,32,128]{3,2,1,0})")
ATTN_PROJ = ("%constant_dynamic-slice_fusion.36 = bf16[1,3840,3840]{1,2,0:T(8,128)(2,1)} fusion("
             "bf16[3,3840,3840]{1,2,0:T(8,128)(2,1)} %get-tuple-element.4378, s32[]{:T(128)S(6)} %select_n.875)")
LEDGER_STYLE = "_fusion.921___f32_9_64_30_96_192__4_3_2_1_0:T_8_128____fusion_f32_9_64_30_96_192_"


@pytest.mark.parametrize("name,mine", [
    (STATE_SLICE, True), (STATE_WRITE, True), (STATE_READ, True),
    (FLAT_SLICE, True), (QKV_PROJ, True), (WINDOW, True), (GATE, True),
    (OUT_PROJ, True), (AB, True), (LEDGER_STYLE, True), (MLP, False),
    (HEAD, False), (PAGED_GATHER, False), (SCORES, False), (ATTN_PROJ, False),
])
def test_ops_are_told_by_the_shapes_only_the_mixer_has(name, mine):
    assert gdn.is_mixer_op(name, gdn.sizes(MODEL)) is mine


def _ctx():
    """A made-up traced run: 6 s of profile in a 51 s window, 75 groups of 4
    steps dispatched inside the trace."""
    c = manifest.cell(manifest.load(), CELL)
    t0 = 100.0
    spans_ = [[i, None, "sched.dispatch", t0 + 0.02 * i, 0.001,
               {"chunks": 1, "k": 4}] for i in range(1, 76)]
    loop0 = {"decode_steps": 0, "spans": {"loop": {"seconds": 1.0}}}
    loop1 = {"decode_steps": 2550, "spans": {"loop": {"seconds": 52.0}}}
    gauges = {"state_bytes": 1313832960, "state_layers": 9, "kv_layers": 3}
    return {
        "records": [], "cell": c, "peaks": V5E, "stats": stats,
        "dims": REF.dims(MODEL), "costs": costs, "window": {"w0": 80.0, "w1": 131.0},
        "metrics_before": {"loop": loop0, "cache": gauges},
        "metrics_after": {"loop": loop1, "cache": gauges},
        "flight_trace": {"loop": {"spans": spans_}},
        "trace": {"busy_s": 5.9, "window_s": 6.0, "t_start": t0, "t_stop": t0 + 6.0,
                  "ops": [[STATE_READ, 1.5], [STATE_SLICE, 0.6], [STATE_WRITE, 0.6],
                          [QKV_PROJ, 0.2], [GATE, 0.05], [OUT_PROJ, 0.05],
                          [MLP, 1.2], [HEAD, 0.4], [PAGED_GATHER, 0.2]]},
    }


def test_the_two_readers_on_a_made_up_trace():
    ctx, z = _ctx(), gdn.sizes(MODEL)
    assert gdn.gdn_pct(ctx) == pytest.approx(100 * 3.0 / 5.9)
    # 300 steps inside the trace: 3.0 s / 300 = 10 ms a step over a floor of
    # 5.16 ms at all 64 rows
    got = gdn.gdn_decode_roofline(ctx)
    assert got == pytest.approx(
        100 * gdn.decode_update_floor_s(z, "bfloat16", V5E, rows=64) / 10e-3)
    assert got == pytest.approx(51.6, rel=0.01)
    ctx["trace"]["ops"][0][1] = 3.0  # 4.5 s / 300 = 15 ms a step
    assert gdn.gdn_decode_roofline(ctx) == pytest.approx(34.4, rel=0.01)


@pytest.mark.parametrize("name", NEW)
@pytest.mark.parametrize("what", ["no_such_ops", "parent_program", "old_cell", "no_trace"])
def test_a_reader_returns_nothing_where_there_is_nothing_to_read(name, what):
    """A trace without the mixer's ops; the parent's /metrics (no
    ``cache.state_layers``); a cell whose configuration has no such layers;
    an untraced run. None, never an exception."""
    ctx = _ctx()
    if what == "no_such_ops":
        ctx["trace"]["ops"] = [[HEAD, 1.5], [MLP, 0.2]]
    elif what == "parent_program":
        ctx["metrics_before"] = {"loop": {"decode_steps": 0}}
        ctx["metrics_after"] = {"loop": {"decode_steps": 5},
                                "cache": {"state_bytes": 1}}
    elif what == "old_cell":
        ctx["cell"] = manifest.cell(manifest.load(), "falcon-h1-34b-1chip.chat")
    else:
        ctx["trace"] = None
        ctx["metrics_before"] = ctx["metrics_after"] = None
    assert getattr(gdn, name)(ctx) is None


@pytest.fixture(scope="module")
def toy_run():
    """A toy olmo_hybrid cell through ``run.py`` on the CPU, traced."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "BENCH_RUN": "ignored",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=1"}
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--manifest",
         "tests/benchmark/toy_olmo_hybrid/BENCHMARK.json", "--allow-cpu",
         "--workload", "tiny-olmo-hybrid.toy-chat", "--seed", str(2**31 + 40),
         "--seconds", "3", "--trace", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600,
    )
    assert p.returncode == 0, p.stderr[-3000:]
    return p


def test_a_toy_olmo_hybrid_cell_end_to_end_on_the_cpu(toy_run):
    """The reference through ``lib/check.py`` (prefill and one cached step
    inside the float32 tolerance, the control outside it), zero compilations
    in the window, every request answered, admission through the mixed
    step."""
    lines = toy_run.stdout.strip().splitlines()
    last, detail = json.loads(lines[-1]), json.loads(lines[-2])
    assert last["correct"] is True and last["failed"] == 0, toy_run.stderr[-3000:]
    assert detail["compilations_in_window"] == 0
    assert detail["logits"]["control_fault"] == "beta_projection_lost"
    assert detail["logits"]["prefill"] < 1e-4 and detail["logits"]["decode"] < 1e-4
    assert detail["logits"]["control"] > 0.1
    # admitted through the mixed step, a request still leaves every seam of
    # its way to the first token: the readers the root manifest lists it for
    assert {"host_turn_pct", "loop_host_ms_per_step", "host_ms_per_group",
            "first_token_p50_ms", "gen_late_p90_ms", "queue_wait_p50_ms",
            "broker_wait_p50_ms", "row_wait_p50_ms", "first_token_lag_p50_ms",
            "stream_lag_p50_ms"} <= set(last["metrics"])
    assert "ttft_p90_ms" in detail["end_to_end"]
    assert "chunked_prefill=4" in toy_run.stderr
