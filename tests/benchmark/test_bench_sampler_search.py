"""``sampler_search_pct`` on hand-made /metrics snapshots: 100 x the window's
``filter_steps`` over its ``decode_steps``, and nothing from a program whose
``loop`` block has no such counter. CPU only, no device number is produced
here."""

import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark.lib import manifest  # noqa: E402

READER = manifest.load_module("layer_metrics", "sampler_search_pct")


def _ctx(before, after):
    def block(steps, filtered, loop_s):
        b = {"decode_steps": steps,
             "spans": {"loop": {"seconds": loop_s, "count": 1}}}
        if filtered is not None:
            b["filter_steps"] = filtered
        return {"loop": b}
    return {"metrics_before": block(*before), "metrics_after": block(*after)}


@pytest.mark.parametrize("before, after, want", [
    ((1000, 400, 300.0), (1700, 1072, 351.0), 96.0),  # 672 of 700 steps
    ((1000, 1000, 300.0), (1700, 1700, 351.0), 100.0),
    ((1000, 400, 300.0), (1700, 400, 351.0), 0.0),  # no filtered row all window
])
def test_sampler_search_pct_is_the_windows_ratio(before, after, want):
    assert READER.read(_ctx(before, after)) == pytest.approx(want)


@pytest.mark.parametrize("ctx", [
    _ctx((1000, None, 300.0), (1700, None, 351.0)),  # the parent: no counter
    _ctx((1000, 400, 300.0), (1000, 400, 351.0)),  # no step in the window
    _ctx((1000, 400, 300.0), (1700, 1072, 300.0)),  # no loop span: tracing off
    {"metrics_before": {}, "metrics_after": {}},  # no loop block at all
    {},
])
def test_sampler_search_pct_gives_nothing_without_the_counter(ctx):
    assert READER.read(ctx) is None


def test_sampler_search_pct_is_declared_for_both_cells():
    """Appended last. ``starcoderbase-1b.gen`` filters (``top_p`` 0.95);
    ``falcon-h1-34b-1chip.chat`` (appended by PR 32) samples at ``top_p`` 1
    with no ``top_k`` and reads 0.0: the proof that no row of that mix pays
    the keep-set search."""
    entry = json.loads((ROOT / "BENCHMARK.json").read_text())["per_layer"][-1]
    assert entry == {
        "name": "sampler_search_pct", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "kernels",
        "moves": "tpot_p90_ms",
        "workloads": ["starcoderbase-1b.gen", "falcon-h1-34b-1chip.chat"],
    }
