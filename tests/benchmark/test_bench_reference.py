"""The two plain float32 references against the program's decoder, at a toy
size through the paged path - the same comparison a chip run makes at the
published widths (benchmark/lib/check.py)."""

import importlib.util
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
    assert out["control_fault"] == "dropped_bias"
    assert out["control"] > 10 * check.LOGITS_TOL["bfloat16"]


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


# -- a family the program cannot serve: the check needs its file alone --------

TWO_KIND = {"model_type": "two_kind", "vocab_size": 128, "width": 32,
            "inner": 64, "pattern": "MMAMMM"}


def _two_kind():
    import numpy as np

    spec = importlib.util.spec_from_file_location(
        "two_kind", ROOT / "tests/benchmark/toy/reference/two_kind.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    rng = np.random.default_rng(5)
    E, I, V = TWO_KIND["width"], TWO_KIND["inner"], TWO_KIND["vocab_size"]
    draw = lambda *shape: rng.normal(0, 0.2, shape).astype(np.float32)
    params = {
        "wte": draw(V, E),
        "mlp": {"w1": draw(5, E, I), "w2": draw(5, I, E)},
        "attn": {n: draw(1, E, E) for n in "qkvo"},
    }
    return mod, params


def _numpy_forward(params, ids):
    """The 'system under test' for the made-up family: the same model written
    a second time, whole stack in numpy float64, one sequence at a time."""
    import numpy as np

    h = np.asarray(params["wte"], np.float64)[ids]
    rms = lambda x: x / np.sqrt((x * x).mean(-1, keepdims=True) + 1e-6)
    m = 0
    for letter in TWO_KIND["pattern"]:
        x = rms(h)
        if letter == "M":
            w1, w2 = params["mlp"]["w1"][m], params["mlp"]["w2"][m]
            h = h + np.maximum(x @ w1, 0) ** 2 @ w2
            m += 1
        else:
            q, k, v, o = (np.asarray(params["attn"][n][0], np.float64)
                          for n in "qkvo")
            s = (x @ q) @ (x @ k).T / np.sqrt(h.shape[-1])
            s = np.where(np.tril(np.ones_like(s, bool)), s, -np.inf)
            w = np.exp(s - s.max(-1, keepdims=True))
            h = h + w / w.sum(-1, keepdims=True) @ (x @ v) @ o
    return rms(h) @ np.asarray(params["wte"], np.float64).T


def _run_on(prompts):
    import numpy as np

    def run(params):
        pre = [_numpy_forward(params, np.asarray(p))[-1] for p in prompts]
        first = [int(np.argmax(x)) for x in pre]
        dec = [_numpy_forward(params, np.asarray(p + [t]))[-1]
               for p, t in zip(prompts, first)]
        return (np.asarray(pre, np.float32), np.asarray(dec, np.float32),
                first)

    return run


def test_a_family_of_two_layer_kinds_and_no_bias_goes_through_the_check():
    ref, params = _two_kind()
    prompts = check.check_prompts(TWO_KIND["vocab_size"], 9, 5, 12)
    kinds = [k for k, _lp in ref.layers(TWO_KIND, params)]
    assert kinds == ["mlp", "mlp", "attn", "mlp", "mlp", "mlp"]
    out = check.compare(ref, TWO_KIND, params, prompts, _run_on(prompts),
                        check.LOGITS_TOL["float32"])
    assert out["ok"], out
    assert out["prefill"] < 1e-4 and out["decode"] < 1e-4
    assert out["control_fault"] == "attn_output_lost"
    assert out["control"] > 1.0  # of the reference logits' deviations


def test_a_control_that_changes_nothing_fails_the_check():
    ref, params = _two_kind()
    prompts = check.check_prompts(TWO_KIND["vocab_size"], 9, 5, 12)
    idle = types.SimpleNamespace(
        **{n: getattr(ref, n) for n in ("embed", "layers", "layer", "head")},
        control=lambda p: ("nothing", p))
    out = check.compare(idle, TWO_KIND, params, prompts, _run_on(prompts),
                        check.LOGITS_TOL["float32"])
    assert not out["ok"]
    assert out["control"] == out["prefill"] < out["tolerance"]
