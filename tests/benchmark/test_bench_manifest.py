"""Rules of BENCHMARK.json the harness relies on, and that every name in it
finds its file."""

import copy
import json
import re
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark.lib import manifest  # noqa: E402

REAL = manifest.load()


def test_the_committed_manifest_is_valid():
    manifest.validate(REAL)
    assert REAL["command"] == ["python3", "benchmark/run.py"]
    assert REAL["paths"] == ["benchmark", "tests/benchmark"]
    assert 1 <= REAL["run_seconds"] <= 51
    assert len(json.dumps(REAL)) < 64 * 1024


@pytest.mark.parametrize("cell", [w["name"] for w in REAL["workloads"]])
def test_every_cell_finds_its_files(cell):
    c = manifest.cell(REAL, cell)
    assert ("rate" in c["params"]) != ("clients" in c["params"])
    assert (c["traffic"]["loop"] == "open") == ("rate" in c["params"])
    assert c["serve"]["kv_layout"] == "paged"
    assert c["entry"]["chips"] == c["config"]["chips"]
    longest = c["traffic"]["prompt"]["max"] + c["traffic"]["output"]["max"]
    assert longest <= c["serve"]["max_seq_len"]
    for e in c["per_layer"]:
        assert (ROOT / "benchmark" / "layer_metrics" / f"{e['name']}.py").exists()
    ref = ROOT / "benchmark" / "reference" / f"{c['model']['model_type']}.py"
    assert ref.exists()
    assert len(c["end_to_end"]) >= 2 and c["per_layer"]


def test_every_metric_has_a_reader_and_every_reader_an_entry():
    readers = {p.stem for p in (ROOT / "benchmark" / "layer_metrics").glob("*.py")}
    assert {e["name"] for e in REAL["per_layer"]} == readers


def test_one_share_of_the_whole_step_bounds_every_claim_on_tpot():
    """Exactly one per-layer metric carries ``mfu`` as a part of its name: a
    share of the chip's peak in %, it moves ``tpot_p90_ms`` and is read in
    every cell that reports ``tpot_p90_ms``, so a PR that takes a kernel off
    the path (and silences that kernel's roofline) still has a share that
    bounds what it claims."""
    mfu = [e for e in REAL["per_layer"]
           if "mfu" in re.split(r"[_.\-]", e["name"])]
    assert [e["name"] for e in mfu] == ["decode_step_mfu_roofline"]
    (e,) = mfu
    assert (e["unit"], e["better"], e["moves"]) == ("%", "higher", "tpot_p90_ms")
    assert e["source"] == "device_trace" and e["layer"] == "step programs"
    tpot = next(x for x in REAL["end_to_end"] if x["name"] == "tpot_p90_ms")
    cells = [w["name"] for w in REAL["workloads"]]
    assert sorted(e["workloads"]) == sorted(tpot.get("workloads", cells))
    # every other roofline share moves the same metric in cells it covers
    for r in REAL["per_layer"]:
        if r["name"].endswith("_roofline"):
            assert r["moves"] == e["moves"]
            assert set(r["workloads"]) <= set(e["workloads"])


@pytest.mark.parametrize("path", sorted(
    p.relative_to(ROOT).as_posix()
    for d in ("benchmark", "tests/benchmark") for p in (ROOT / d).rglob("*")
    if p.is_file() and "__pycache__" not in p.parts
))
def test_file_names_use_the_characters_of_a_name(path):
    assert all(manifest.NAME.match(part) for part in path.split("/")), path


def _broken(edit):
    m = copy.deepcopy(REAL)
    edit(m)
    return m


def _name_with_space(m):
    m["per_layer"][0]["name"] = "gen late"


def _name_with_percent(m):
    m["per_layer"][0]["name"] = "kv_in_use_%"


def _unit_with_space(m):
    m["end_to_end"][0]["unit"] = "tokens per s"


def _unit_too_long(m):
    m["end_to_end"][0]["unit"] = "x" * 17


def _moves_unreported(m):
    # a metric of a fixed-rate cell that claims to move a saturated cell's
    # rate, which its own cells do not report
    m["end_to_end"].append({**m["end_to_end"][0], "name": "total_tok_s",
                            "workloads": []})
    e = next(e for e in m["per_layer"] if e["moves"] == "ttft_p90_ms")
    e["moves"] = "total_tok_s"


def _moves_unknown(m):
    m["per_layer"][0]["moves"] = "nothing"


def _too_many_four_chip_cells(m):
    m["workloads"].append({**m["workloads"][0], "name": "other",
                           "traffic": "other"})
    for w in m["workloads"]:
        w["chips"] = 4


def _pair_twice(m):
    w = copy.deepcopy(m["workloads"][0])
    w["name"] = "again"
    m["workloads"].append(w)


def _no_setup(m):
    m["end_to_end"] = [e for e in m["end_to_end"] if e["name"] != "setup_s"]


def _extra_top_key(m):
    m["notes"] = "x"


def _cell_without_layer_metric(m):
    cell = m["workloads"][0]["name"]
    for e in m["per_layer"]:
        if "workloads" in e:
            e["workloads"] = [w for w in e["workloads"] if w != cell] or ["x"]


@pytest.mark.parametrize("edit", [
    _name_with_space, _name_with_percent, _unit_with_space, _unit_too_long,
    _moves_unreported, _moves_unknown, _too_many_four_chip_cells, _pair_twice,
    _no_setup, _extra_top_key, _cell_without_layer_metric,
], ids=lambda f: f.__name__.strip("_"))
def test_a_broken_manifest_is_refused(edit):
    with pytest.raises((manifest.ManifestError, KeyError)):
        manifest.validate(_broken(edit))
