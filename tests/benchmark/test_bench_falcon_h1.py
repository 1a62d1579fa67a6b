"""What PR 29 added to the benchmark: the falcon_h1 reference through
``lib/check.py`` at a small size on the CPU (its control failing the
tolerance), the manifest's new entries, the mixer's costs, and the two
``ssm_*`` readers on a recorded toy trace. No device number is produced
here."""

import importlib.util
import json
import math
import sys
import types
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark.lib import check, costs, manifest, peaks, ssm, stats  # noqa: E402

CELL = "falcon-h1-34b-1chip.chat"
CONFIG = json.loads(
    (ROOT / "benchmark" / "configs" / "falcon-h1-34b-1chip.json").read_text())
MODEL = {k: v for k, v in CONFIG.items() if k not in manifest.HARNESS_KEYS}
# every width cut, every multiplier and flag as published
SMALL = {**MODEL, "vocab_size": 256, "hidden_size": 64, "num_hidden_layers": 2,
         "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8,
         "intermediate_size": 128, "mamba_d_ssm": 64, "mamba_n_heads": 4,
         "mamba_d_head": 16, "mamba_d_state": 16, "mamba_chunk_size": 8}


def _engine(hf, dtype):
    from llmss_tpu.engine import DecodeEngine
    from llmss_tpu.models.decoder import init_params
    from llmss_tpu.models.registry import config_from_hf
    from llmss_tpu.parallel import MeshPlan, make_mesh

    mesh = make_mesh(MeshPlan(tp=1), devices=jax.devices()[:1])
    cfg = config_from_hf(types.SimpleNamespace(**hf), dtype=dtype)
    params = init_params(cfg, mesh, jax.random.key(2**31 + 29))
    spec = importlib.util.spec_from_file_location(
        "bench_server", ROOT / "benchmark" / "server.py")
    server = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(server)
    params = server._unit_norm_scales(params)
    return DecodeEngine(cfg, params, mesh, kv_layout="paged", max_seq_len=128)


@pytest.mark.parametrize("dtype,inside", [("float32", 1e-4), ("bfloat16", 0.15)])
def test_the_engine_matches_the_reference_and_the_control_fails(dtype, inside):
    """Prefill and one cached step on 4 seeded prompts of unequal length,
    as a chip run compares them; the mixer's branch lost is far outside
    either tolerance."""
    out = check.reference_check(_engine(SMALL, dtype), SMALL, seed=5, lo=33, hi=100)
    assert out["ok"], out
    assert out["tolerance"] == check.LOGITS_TOL[dtype]
    assert out["prefill"] < inside and out["decode"] < inside
    assert out["control_fault"] == "mixer_lost"
    assert out["control"] > 5 * check.LOGITS_TOL["bfloat16"]


def test_the_manifest_has_the_new_entries_and_nothing_else_moved():
    m = manifest.load()
    c = manifest.cell(m, CELL)
    assert c["entry"]["chips"] == 1 and c["traffic"]["top_p"] == 1.0
    assert sorted(e["name"] for e in c["end_to_end"]) == ["setup_s", "tpot_p90_ms"]
    assert {e["name"] for e in c["per_layer"]} == {
        "decode_step_dev_ms", "host_turn_pct", "loop_host_ms_per_step",
        "host_ms_per_group", "ssm_pct", "ssm_decode_roofline",
        "first_token_p50_ms", "decode_step_mfu_roofline", "sampler_search_pct"}
    assert [w["name"] for w in m["workloads"]][0] == "starcoderbase-1b.gen"
    old = manifest.cell(m, "starcoderbase-1b.gen")
    assert not {e["name"] for e in old["per_layer"]} & {
        "ssm_pct", "ssm_decode_roofline", "first_token_p50_ms"}
    assert c["traffic"]["output"]["min"] >= 128


def test_the_configuration_keeps_every_published_key_but_the_two_reduced():
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if not catalog.exists():
        pytest.skip("no catalog on this machine")
    row = next(json.loads(line) for line in catalog.read_text().splitlines()
               if '"Falcon-H1-34B-Instruct"' in line)
    assert CONFIG["source"] == row["source_url"]
    differ = sorted(k for k, v in row["config"].items() if MODEL.get(k, "absent") != v)
    assert differ == sorted(CONFIG["reduced"]) == [
        "max_position_embeddings", "num_hidden_layers"]


def test_dims_match_the_programs_parameter_shapes():
    """``total_params`` leaves out the embedding table (held, not read by a
    step); with it, the count is the program's own."""
    from llmss_tpu.models.decoder import param_shapes
    from llmss_tpu.models.registry import config_from_hf

    dims = manifest.load_module("reference", "falcon_h1").dims(MODEL)
    cfg = config_from_hf(types.SimpleNamespace(**MODEL))
    n = sum(math.prod(x.shape) for x in jax.tree.leaves(param_shapes(cfg)))
    assert n == dims["total_params"] + MODEL["vocab_size"] * MODEL["hidden_size"]
    assert n == pytest.approx(4.8245e9, rel=1e-3)
    from llmss_tpu.engine.cache import ssm_state_shapes

    assert dims["state_bytes_per_row"] == cfg.n_layers * sum(
        math.prod(shape) * jax.numpy.dtype(dt).itemsize
        for shape, dt in ssm_state_shapes(cfg))
    assert dims["state_bytes_per_row"] == pytest.approx(21.1e6, rel=5e-3)
    z = ssm.sizes(MODEL)
    assert ssm.state_bytes_per_row(z, "bfloat16") == dims["state_bytes_per_row"]
    floor = costs.decode_step_floor_s(
        dims, "bfloat16", peaks.peaks_for("TPU v5 lite"), rows=64, context=250)
    assert floor["bound_by"] == "memory"
    assert floor["floor_s"] == pytest.approx(12.0e-3, rel=0.03)
    # the mixer's part of that floor: state twice and 0.68 GB of weights
    mixer = ssm.decode_update_floor_s(z, "bfloat16", peaks.peaks_for("TPU v5 lite"), 64)
    assert mixer == pytest.approx(4.1e-3, rel=0.03)
    assert ssm.weight_bytes(z, "bfloat16") == pytest.approx(0.684e9, rel=5e-3)


# Names as the chip's compiler writes them (compiled for a described v5e at
# the cell's size): output shape, then the operands'.
POOL_UPDATE = ("%fusion.383 = f32[5,64,32,128,256]{4,3,2,1,0:T(8,128)} fusion("
               "f32[5,64,32,128,256]{4,3,2,1,0:T(8,128)} %gte, f32[64,32,256]{2,1,0} %b)")
STATE_READ = ("%fusion.44 = f32[64,32,128]{2,1,0:T(8,128)} fusion(f32[5,64,32,128,256]"
              "{4,3,2,1,0:T(8,128)} %p0, s32[] %l, f32[64,32,256]{2,1,0} %c)")
IN_PROJ = "%fusion.12 = bf16[64,1,9248]{2,0,1:T(8,128)(2,1)} fusion(bf16[64,1,5120]{2,0,1} %x, bf16[5,5120,9248]{2,1,0} %w)"
ADMIT_SCAN = "%fusion.7 = f32[2,2,16,128,256]{4,3,2,1,0:T(8,128)} fusion(f32[2,2,16,128,256]{4,3,2,1,0} %s0, f32[2,128,2,256]{3,2,1,0} %b)"
ADMIT_PROJ = "%convolution.3 = bf16[2,256,9248]{2,1,0:T(8,128)(2,1)} convolution(bf16[2,256,5120]{2,1,0} %x, bf16[5120,9248]{1,0} %w)"
ADMIT_POOL = ("%fusion.9 = f32[5,64,32,128,256]{4,3,2,1,0:T(8,128)} fusion("
              "f32[5,64,32,128,256]{4,3,2,1,0:T(8,128)} %pool, f32[2,32,128,256]{3,2,1,0} %new)")
MLP = "%fusion.307 = bf16[64,1,21504]{2,0,1:T(8,128)(2,1)S(1)} fusion(bf16[64,1,5120]{2,0,1} %x)"
KV_POOL = "%copy.62 = bf16[5,4096,16,4,128]{4,2,1,3,0:T(8,128)(2,1)} copy(bf16[5,4096,16,4,128]{4,3,2,1,0} %k)"
LEDGER_STYLE = "_fusion.383___f32_5_64_32_128_256__4_3_2_1_0:T_8_128____fusion_f32_5_64_32_128_256_"


@pytest.mark.parametrize("name,kind", [
    (POOL_UPDATE, "decode"), (STATE_READ, "decode"), (IN_PROJ, "decode"),
    (ADMIT_SCAN, "prefill"), (ADMIT_PROJ, "prefill"), (ADMIT_POOL, "prefill"),
    (MLP, None), (KV_POOL, None), (LEDGER_STYLE, "decode"),
])
def test_the_mixers_ops_are_told_by_the_shapes_only_the_mixer_has(name, kind):
    assert ssm.op_kind(name, ssm.sizes(MODEL), rows=64) == kind


def _reader(name):
    return manifest.load_module("layer_metrics", name)


def _ctx():
    """A toy trace of 6 s: 300 decode steps of 64 rows with 5 ms of the
    mixer's decode ops a step, 1.5 s in all, two admissions of 100 and 200
    tokens with 3 ms of its scan ops, 5.4 s busy."""
    c = manifest.cell(manifest.load(), CELL)
    recs = [{"first": 0.5, "done": 99.0, "increments": [],
             "body": {"token_ids": [0] * 100}} for _ in range(31)]
    recs += [{"first": 11.0, "done": 99.0, "increments": [],
              "body": {"token_ids": [0] * 200}},
             {"first": 12.0, "done": 99.0, "increments": [],
              "body": {"token_ids": [0] * 100}}]
    return {
        "cell": c, "records": recs, "info": {}, "stats": stats, "costs": costs,
        "peaks": peaks.peaks_for("TPU v5 lite"), "dims": {},
        "window": {"w0": 0.0, "w1": 51.0},
        "metrics_before": {"cache": {"state_bytes": 1},
                           "loop": {"decode_steps": 1000, "spans": {}}},
        "metrics_after": {"cache": {"state_bytes": 1},
                          "loop": {"decode_steps": 3550,
                                   "spans": {"loop": {"seconds": 50.0}}}},
        "flight": None, "flight_trace": None,
        "trace": {"devices": 1, "busy_s": 5.4, "window_s": 6.0,
                  "t_start": 10.0, "t_stop": 16.0, "programs": {}, "gaps": [],
                  "ops": [[MLP, 1.5], [POOL_UPDATE, 1.0], [STATE_READ, 0.4],
                          [IN_PROJ, 0.1], [KV_POOL, 0.2], [ADMIT_SCAN, 0.002],
                          [ADMIT_PROJ, 0.001]]},
    }


def test_the_two_readers_on_a_recorded_toy_trace():
    ctx = _ctx()
    assert _reader("ssm_pct").read(ctx) == pytest.approx(100 * 1.503 / 5.4)
    # 2550 steps in 51 s -> 300 in the 6 s traced; 1.5 s / 300 = 5 ms a step
    z, pk = ssm.sizes(MODEL), ctx["peaks"]
    # the floor counts all 64 rows the program updates, not the ~32 live
    got = _reader("ssm_decode_roofline").read(ctx)
    assert got == pytest.approx(
        100 * ssm.decode_update_floor_s(z, "bfloat16", pk, 64) / 5e-3)
    assert 60 < got < 100
    ctx["records"] = ctx["records"][:5]  # fewer live rows: the same share
    assert _reader("ssm_decode_roofline").read(ctx) == pytest.approx(got)


@pytest.mark.parametrize("name", ["ssm_pct", "ssm_decode_roofline"])
@pytest.mark.parametrize("what", ["no_mixer_ops", "parent_program", "old_cell"])
def test_a_reader_returns_nothing_where_there_is_nothing_to_read(name, what):
    """A trace without the mixer's ops; the parent's /metrics (no ``cache``
    block: the program has no state pool); a cell whose configuration has
    no mixer. None, never an exception."""
    ctx = _ctx()
    if what == "no_mixer_ops":
        ctx["trace"]["ops"] = [[MLP, 1.5], [KV_POOL, 0.2]]
    elif what == "parent_program":
        ctx["metrics_before"] = ctx["metrics_after"] = {"loop": {}}
        ctx["trace"]["ops"] = [[MLP, 1.5], [KV_POOL, 0.2]]
    else:
        ctx["cell"] = manifest.cell(manifest.load(), "starcoderbase-1b.gen")
    assert _reader(name).read(ctx) is None


def test_first_token_median_is_the_windows_own():
    """The reader hands on what ``run.py`` took from the request log; a
    window that answered nothing gives None."""
    read = _reader("first_token_p50_ms").read
    assert read({"info": {"ttft_ms": {"n": 178, "p50": 1240.4, "p90": 2477.5}}}) == 1240.4
    assert read({"info": {}}) is None and read({}) is None
    c = manifest.cell(manifest.load(), CELL)
    assert "first_token_p50_ms" in [e["name"] for e in c["per_layer"]]
    assert c["serve"]["chunked_prefill"] == 4 and c["params"]["rate"] == 3.5
