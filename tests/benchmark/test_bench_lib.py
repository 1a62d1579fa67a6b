"""The yardstick's arithmetic: percentiles, costs, peaks, the trace
reduction, and the reductions the per-layer metrics share. CPU only, no
device number is produced here."""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark.lib import costs, manifest, peaks, reduce, stats, xplane  # noqa: E402


def test_percentile_interpolates_and_counts():
    v = list(range(1, 101))
    assert stats.percentile(v, 50) == pytest.approx(50.5)
    assert stats.percentile(v, 90) == pytest.approx(90.1)
    s = stats.summary(v, 90)
    assert s["n"] == 100 and s["beyond"] == 10
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_unknown_device_is_an_error():
    assert peaks.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")


def _dims(config_name):
    import json

    # by file, not by manifest entry: a configuration whose cell is not
    # proven yet keeps its file in the tree
    hf = json.loads(
        (ROOT / "benchmark" / "configs" / f"{config_name}.json").read_text())
    return manifest.load_module("reference", hf["model_type"]).dims(hf), hf


@pytest.mark.parametrize("name,params_b,kv_bytes", [
    ("starcoderbase-1b", 1.137, 12288),
    ("gpt-j-6b-l16", 3.634, 256 * 1024),
    ("starcoder-15b-tp4", 15.518, 20480),
])
def test_costs_from_shapes(name, params_b, kv_bytes):
    dims, hf = _dims(name)
    assert dims["total_params"] / 1e9 == pytest.approx(params_b, abs=2e-3)
    assert costs.kv_bytes_per_token(dims, "bfloat16") == kv_bytes
    floor = costs.decode_step_floor_s(
        dims, "bfloat16", peaks.peaks_for("TPU v5 lite"), rows=16, context=512)
    assert floor["bound_by"] == "memory"
    assert floor["floor_s"] > costs.param_bytes(dims, "bfloat16") / 819e9


@pytest.mark.parametrize("name", [
    "starcoderbase-1b", "gpt-j-6b-l16", "starcoder-15b-tp4"])
def test_dims_match_the_programs_parameter_shapes(name):
    import math
    import types

    import jax

    from llmss_tpu.models.decoder import param_shapes
    from llmss_tpu.models.registry import config_from_hf

    dims, hf = _dims(name)
    model = {k: v for k, v in hf.items() if k not in manifest.HARNESS_KEYS}
    cfg = config_from_hf(types.SimpleNamespace(**model))
    n = sum(math.prod(x.shape) for x in jax.tree.leaves(param_shapes(cfg)))
    assert n == dims["total_params"]


def test_busy_union_and_self_time():
    ev = [("while", 0.0, 100.0), ("fusion.1", 10.0, 30.0),
          ("all-reduce.2", 50.0, 20.0), ("copy", 150.0, 10.0)]
    assert xplane.merged((s, s + d) for _n, s, d in ev) == [(0.0, 100.0), (150.0, 160.0)]
    st = xplane.self_times(ev)
    assert st == {"while": 50.0, "fusion.1": 30.0, "all-reduce.2": 20.0, "copy": 10.0}
    assert xplane.program_name("jit__decode_group_impl(123)") == "jit__decode_group_impl"


XSPACE = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 100000000 }
    events { metadata_id: 2 offset_ps: 150000000 duration_ps: 50000000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 100000000 }
    events { metadata_id: 4 offset_ps: 20000000 duration_ps: 40000000 }
    events { metadata_id: 5 offset_ps: 150000000 duration_ps: 50000000 } }
  event_metadata { key: 1 value { id: 1 name: "jit__decode_group_impl(1)" } }
  event_metadata { key: 2 value { id: 2 name: "jit__prefill_impl(2)" } }
  event_metadata { key: 3 value { id: 3 name: "while.1" } }
  event_metadata { key: 4 value { id: 4 name: "all-reduce.7" } }
  event_metadata { key: 5 value { id: 5 name: "fusion.9" } } }
planes { id: 2 name: "/host:CPU"
  lines { id: 3 name: "python" timestamp_ns: 900
    events { metadata_id: 1 offset_ps: 0 duration_ps: 1000000 } }
  event_metadata { key: 1 value { id: 1 name: "step" } } }
"""


def test_reduce_a_synthetic_xplane(tmp_path):
    from jax.profiler import ProfileData

    pb = tmp_path / "plugins" / "profile" / "t" / "host.xplane.pb"
    pb.parent.mkdir(parents=True)
    pb.write_bytes(ProfileData.text_proto_to_serialized_xspace(XSPACE))
    planes = xplane.read(xplane.find_xplane(tmp_path))
    assert set(xplane.device_planes(planes)) == {"/device:TPU:0"}
    out = xplane.reduce(planes, host_window_s=None)
    assert out["devices"] == 1
    assert out["busy_s"] == pytest.approx(150e-6)
    assert out["window_s"] == pytest.approx(200e-6)
    assert out["programs"]["jit__decode_group_impl"] == {"s": pytest.approx(100e-6), "n": 1}
    assert dict(map(tuple, out["ops"]))["all-reduce.7"] == pytest.approx(40e-6)
    assert dict(map(tuple, out["ops"]))["while.1"] == pytest.approx(60e-6)
    # one idle gap of 50 us, starting 100 us after the device's first event
    (start, length), = out["gaps"]
    assert length == pytest.approx(50e-6)
    assert start == pytest.approx(100e-6 + 100e-9)  # the host plane starts 100 ns earlier
    assert xplane.reduce({"/host:CPU": planes["/host:CPU"]}) == {"devices": 0}


def _rec(first, incs, prompt=10, done=None):
    return {"first": first, "done": done, "increments": incs,
            "body": {"token_ids": [0] * prompt}}


def test_the_batch_is_read_off_the_request_log():
    a = _rec(0.5, [(0.5, 1), (1.1, 8), (1.4, 8), (1.8, 4)], done=3.0)
    b = _rec(0.6, [(0.6, 1), (1.1, 8), (1.4, 8), (1.8, 4)], done=3.0)
    c = _rec(1.4, [(1.4, 1), (1.8, 4)], done=3.0)
    batch = reduce.batch_between([a, b, c], 1.0, 2.0, n=4)
    assert 2.0 <= batch["rows"] <= 3.0 and batch["context"] > 10


def _reader(name):
    return manifest.load_module("layer_metrics", name)


READERS = sorted(p.stem for p in (ROOT / "benchmark" / "layer_metrics").glob("*.py"))


@pytest.mark.parametrize("name", READERS)
def test_a_reader_with_nothing_to_read_returns_nothing(name):
    ctx = {"info": {}, "flight": None, "trace": None, "records": [],
           "flight_trace": None, "metrics_before": {}, "metrics_after": {},
           "stats": stats, "costs": costs, "peaks": None, "dims": {}, "cell": {}}
    assert _reader(name).read(ctx) is None


def test_readers_on_counters_and_spans():
    ctx = {
        "metrics_after": {"host_overhead": {"dispatch": {"p50_ms": 1.5},
                                            "callback": {"p50_ms": 0.5}}},
        "flight": {"requests": {"a": {"events": [
            {"name": "enqueue", "t": 1.0}, {"name": "admit", "t": 1.25}]}}},
        "stats": stats,
    }
    assert _reader("host_ms_per_group").read(ctx) == pytest.approx(2.0)
    assert _reader("queue_wait_p50_ms").read(ctx) == pytest.approx(250.0)


def test_the_floor_prices_keys_and_values_where_they_are_and_state_twice():
    """One attention layer of eleven, recurrent state in the others: the
    bytes worked out by hand."""
    dims = {"layers": 11, "kv_layers": 1, "heads": 32, "kv_heads": 2,
            "head_dim": 128, "matmul_params": 3_000_000, "total_params": 10_000_000,
            "state_bytes_per_row": 5 * 128 * 64 * 128 * 4}
    pk = {"hbm_bytes_per_s": 1e9, "bf16_flops_per_s": 1e12}
    assert costs.kv_bytes_per_token(dims, "bfloat16") == 1 * 2 * 2 * 128 * 2
    out = costs.decode_step_floor_s(dims, "bfloat16", pk, rows=64, context=2048)
    by_hand = (10_000_000 * 2            # every parameter held here, once
               + 1024 * 64 * 2048        # 1 KiB a token in the one layer
               + 2 * 64 * 20_971_520)    # each row's state read and written
    assert out["bytes"] == by_hand == 2_838_572_288
    assert out["floor_s"] == pytest.approx(by_hand / 1e9)
    # attention's operations in the one layer that attends
    assert out["flops"] == 64 * (2.0 * 3_000_000 + 4.0 * 2048 * 32 * 128 * 1)
    # four chips: parameters and state split; 2 KV heads do not divide by 4,
    # so every chip reads the whole cache
    four = costs.decode_step_floor_s(
        dims, "bfloat16", pk, rows=64, context=2048, chips=4)
    assert four["bytes"] == (20_000_000 + 2 * 64 * 20_971_520) / 4 + 1024 * 64 * 2048


@pytest.mark.parametrize("config,dtype,want_bytes,want_flops", [
    ("tiny-bigcode", "float32", 3437824.0, 13025280.0),
    ("tiny-bigcode", "bfloat16", 1718912.0, 13025280.0),
    ("tiny-gptj", "float32", 12949504.0, 16629760.0),
    ("tiny-gptj", "bfloat16", 6474752.0, 16629760.0),
])
def test_a_family_without_the_new_keys_is_priced_as_before(
        config, dtype, want_bytes, want_flops):
    """Pinned at the values ``costs.py`` gave before ``kv_layers`` and
    ``state_bytes_per_row`` existed (40 rows, 300 tokens of context)."""
    import json

    hf = json.loads(
        (ROOT / "tests/benchmark/toy/configs" / f"{config}.json").read_text())
    dims = manifest.load_module("reference", hf["model_type"]).dims(hf)
    assert "kv_layers" not in dims and "state_bytes_per_row" not in dims
    out = costs.decode_step_floor_s(
        dims, dtype, peaks.peaks_for("TPU v5 lite"), rows=40, context=300)
    assert (out["bytes"], out["flops"]) == (want_bytes, want_flops)
    assert out["floor_s"] == pytest.approx(want_bytes / 819e9)


def test_the_manifests_cell_keeps_its_floor():
    """`starcoderbase-1b` at 40 rows and 300 tokens: 2.957 ms, the number
    behind every `decode_step_mfu_roofline` (and, until PR 32, every
    `decode_group_roofline`) the ledger holds."""
    dims, _hf = _dims("starcoderbase-1b")
    out = costs.decode_step_floor_s(
        dims, "bfloat16", peaks.peaks_for("TPU v5 lite"), rows=40, context=300)
    assert out["bytes"] == 2421870592.0 and out["bound_by"] == "memory"
    assert out["floor_s"] == pytest.approx(0.00295710694993895)
