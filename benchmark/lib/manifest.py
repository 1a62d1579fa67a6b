"""Reads ``BENCHMARK.json`` and the data files its names point at, and
refuses a manifest that breaks the rules the harness relies on. Everything
that belongs to one configuration, one traffic mix, one cell or one
per-layer metric sits in a file of its own, found by NAME - there is no
``if`` on a name anywhere in the harness."""

from __future__ import annotations

import importlib.util
import json
import re
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
# Keys of a configuration file that are the harness's, not the model's.
HARNESS_KEYS = {"name", "source", "reduced", "assumed", "chips", "mesh",
                "dtype", "serve", "memory", "why"}


class ManifestError(ValueError):
    pass


def load_module(kind: str, name: str):
    """The file ``benchmark/<kind>/<name>.py`` as a module: how a per-layer
    metric's reader and a model family's reference are found by name (names
    may hold ``-`` and ``.``, so they are loaded by path, not imported)."""
    path = ROOT / "benchmark" / kind / f"{name}.py"
    if not path.exists():
        raise FileNotFoundError(f"no {kind} file for {name!r}: {path}")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _need(cond: bool, msg: str) -> None:
    if not cond:
        raise ManifestError(msg)


def validate(m: dict) -> None:
    _need(set(m) == TOP_KEYS, f"top-level keys {sorted(m)} != {sorted(TOP_KEYS)}")
    names: set[str] = set()
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in m[kind]:
            _need(bool(NAME.match(e["name"])), f"bad name {e['name']!r}")
            _need((kind, e["name"]) not in names, f"duplicate {e['name']!r}")
            names.add((kind, e["name"]))
    metric_names = [e["name"] for e in m["end_to_end"] + m["per_layer"]]
    _need(len(metric_names) == len(set(metric_names)),
          "a metric name is used twice")
    cfgs = {c["name"] for c in m["configs"]}
    cells = {w["name"] for w in m["workloads"]}
    pairs = set()
    for w in m["workloads"]:
        _need(w["config"] in cfgs, f"{w['name']}: unknown config")
        _need(bool(NAME.match(w["traffic"])), f"bad traffic name {w['traffic']!r}")
        _need(w["chips"] in (1, 4), f"{w['name']}: chips must be 1 or 4")
        _need((w["config"], w["traffic"]) not in pairs,
              f"{w['name']}: config and traffic pair appears twice")
        pairs.add((w["config"], w["traffic"]))
    four = sum(1 for w in m["workloads"] if w["chips"] == 4)
    _need(four <= max(1, len(cells) // 4),
          f"{four} of {len(cells)} cells ask for four chips: more than a "
          "quarter (one always may)")
    e2e = {e["name"]: e for e in m["end_to_end"]}
    _need("setup_s" in e2e, "no setup_s among the end-to-end metrics")
    for e in m["end_to_end"] + m["per_layer"]:
        _need(bool(UNIT.match(e["unit"])), f"{e['name']}: bad unit {e['unit']!r}")
        _need(e["better"] in ("lower", "higher"), f"{e['name']}: better?")
        for w in e.get("workloads", ()):
            _need(w in cells, f"{e['name']}: unknown cell {w!r}")

    def cells_of(e):
        return set(e.get("workloads", cells))

    for e in m["per_layer"]:
        _need(e["moves"] in e2e, f"{e['name']} moves unknown {e['moves']!r}")
        missing = cells_of(e) - cells_of(e2e[e["moves"]])
        _need(not missing,
              f"{e['name']} moves {e['moves']}, which cells "
              f"{sorted(missing)} do not report")
    for c in cells:
        mine = [e for e in m["end_to_end"] if c in cells_of(e)]
        _need(len(mine) >= 2, f"{c}: needs setup_s and one more end-to-end metric")
        _need(any(c in cells_of(e) for e in m["per_layer"]),
              f"{c}: no per-layer metric")


def load(path: Path | None = None) -> dict:
    path = Path(path) if path else ROOT / "BENCHMARK.json"
    m = json.loads(path.read_text())
    validate(m)
    return m


def cell(m: dict, name: str) -> dict:
    """Everything one cell is made of: its manifest entry, its
    configuration (file contents), its traffic mix, its own parameters
    (``cells/<name>.json`` beside the configuration and traffic
    directories), and the metrics it reports."""
    entry = next((w for w in m["workloads"] if w["name"] == name), None)
    if entry is None:
        raise ManifestError(
            f"no cell {name!r}; have {[w['name'] for w in m['workloads']]}")
    cfg_entry = next(c for c in m["configs"] if c["name"] == entry["config"])
    cfg_path = ROOT / cfg_entry["file"]
    data = cfg_path.parent.parent
    config = json.loads(cfg_path.read_text())
    _need(sorted(config.get("reduced", [])) == sorted(cfg_entry["reduced"]),
          f"{cfg_entry['name']}: 'reduced' differs between BENCHMARK.json "
          "and the configuration file")
    traffic = json.loads((data / "traffic" / f"{entry['traffic']}.json").read_text())
    params = json.loads((data / "cells" / f"{name}.json").read_text())

    def mine(e):
        return name in e.get("workloads", [name])

    return {
        "entry": entry, "config": config, "traffic": traffic, "params": params,
        "model": {k: v for k, v in config.items() if k not in HARNESS_KEYS},
        "serve": {**config["serve"], **params.get("serve", {})},
        "end_to_end": [e for e in m["end_to_end"] if mine(e)],
        "per_layer": [e for e in m["per_layer"] if mine(e)],
    }
