"""The two plain float32 references against the program's decoder, at a toy
size through the paged path - the same comparison a chip run makes at the
published widths (benchmark/lib/check.py)."""

import json
import sys
import types
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark.lib import check, manifest  # noqa: E402


def _engine(config_file, dtype):
    from llmss_tpu.engine import DecodeEngine
    from llmss_tpu.models.decoder import init_params
    from llmss_tpu.models.registry import config_from_hf
    from llmss_tpu.parallel import MeshPlan, make_mesh

    cfg_json = json.loads((ROOT / config_file).read_text())
    hf = {k: v for k, v in cfg_json.items() if k not in manifest.HARNESS_KEYS}
    mesh = make_mesh(MeshPlan(tp=1), devices=jax.devices()[:1])
    cfg = config_from_hf(types.SimpleNamespace(**hf), dtype=dtype)
    params = init_params(cfg, mesh, jax.random.key(2**31 + 11))
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "bench_server", ROOT / "benchmark" / "server.py")
    server = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(server)
    params = server._unit_norm_scales(params)
    return DecodeEngine(cfg, params, mesh, kv_layout="paged", max_seq_len=64), hf


@pytest.mark.parametrize("config_file", [
    "tests/benchmark/toy/configs/tiny-bigcode.json",
    "tests/benchmark/toy/configs/tiny-gptj.json",
])
def test_float32_engine_matches_the_reference(config_file):
    engine, hf = _engine(config_file, "float32")
    out = check.reference_check(engine, hf, seed=3, lo=17, hi=40)
    assert out["ok"], out
    assert out["prefill"] < 1e-4 and out["decode"] < 1e-4
    # the negative control: a dropped bias is off by far more than either bound
    assert out["control_dropped_bias"] > 10 * check.LOGITS_TOL["bfloat16"]


def test_bfloat16_engine_sits_inside_its_tolerance_and_outside_float32s():
    engine, hf = _engine("tests/benchmark/toy/configs/tiny-gptj.json", "bfloat16")
    out = check.reference_check(engine, hf, seed=4, lo=17, hi=40)
    assert out["ok"], out
    assert out["tolerance"] == check.LOGITS_TOL["bfloat16"]
    assert max(out["prefill"], out["decode"]) > check.LOGITS_TOL["float32"]


def test_logits_error_refuses_shapes_and_nans():
    import numpy as np

    a = np.ones((2, 8), np.float32) * np.arange(8)
    assert check.logits_error(a, a) == 0.0
    with pytest.raises(RuntimeError):
        check.logits_error(a[:, :4], a)
    b = a.copy()
    b[0, 0] = np.nan
    with pytest.raises(RuntimeError):
        check.logits_error(b, a)
