#!/usr/bin/env python
"""Aggregate every ``*_BENCH.json`` receipt into one trajectory file.

The repo accumulates bench receipts PR over PR — ``TRACE_BENCH``,
``SLO_BENCH``, … — but nothing collates them, so the
"bench trajectory" exists only as loose files. This tool builds
``TREND.json``: per-family, per-metric series ordered by revision, each
sample carrying its value/unit/vs_baseline and the receipt's
``bench_provenance`` block when present.

Receipt shapes handled (the three that exist in the tree):

- **runner receipts** (``BENCH_r*``, ``MULTICHIP_r*``): ``{"n", "cmd",
  "rc", "tail"}`` with JSON metric lines (``{"metric", "value", ...}``)
  embedded in the captured ``tail`` text. The FIRST metric line per
  receipt is the headline sample — later lines are config variants
  (int8 KV, a bigger model) whose values are not comparable release to
  release (r05 appends a 7b config; diffing it against r04's 1b2
  headline would read as a 94% "regression").
- **flat receipts** (``SERVE_BENCH``, ``PREFIX_BENCH``, …): a top-level
  ``{"metric", "value", ...}`` dict — one sample.
- **structured receipts** (``PD_BENCH``, ``RAGGED_BENCH``, …): nested
  dicts — every numeric leaf up to depth 3 becomes a dotted-path metric.

``--check FAMILY:metric`` gates CI: exit 1 when the newest receipt's
headline for that metric regressed more than ``--threshold`` (default
10%, lower-is-worse — every headline in the tree is a rate) against the
previous receipt in the family. Families with fewer than two receipts
pass vacuously (a trend needs two points).

Usage:
    python tools/bench_trend.py                    # write TREND.json
    python tools/bench_trend.py --check BENCH:decode_tokens_per_sec_per_chip
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# BENCH_r01 / MULTICHIP_r03 → family BENCH / MULTICHIP, revision 1 / 3.
_REV_RE = re.compile(r"^(?P<family>.+?)_r(?P<rev>\d+)$")
# A JSON metric line inside a captured tail.
_TAIL_LINE_RE = re.compile(r"^\{.*\}$", re.M)

_MAX_LEAF_DEPTH = 3


def _iter_numeric_leaves(obj, path=()):
    if isinstance(obj, bool):
        return
    if isinstance(obj, (int, float)):
        yield ".".join(path), float(obj)
    elif isinstance(obj, dict) and len(path) < _MAX_LEAF_DEPTH:
        for k, v in obj.items():
            yield from _iter_numeric_leaves(v, (*path, str(k)))


def parse_receipt(path: str) -> dict:
    """One receipt file → {family, rev, metrics: [...], provenance?}."""
    stem = os.path.splitext(os.path.basename(path))[0]
    m = _REV_RE.match(stem)
    family, rev = (m.group("family"), int(m.group("rev"))) if m else (stem, 0)
    with open(path) as f:
        d = json.load(f)
    out = {"family": family, "rev": rev, "file": os.path.basename(path)}
    if isinstance(d.get("provenance"), dict):
        out["provenance"] = d["provenance"]

    metrics: list[dict] = []
    if isinstance(d.get("tail"), str):
        # Runner receipt: metric lines embedded in the captured output.
        seen_headline: set[str] = set()
        for raw in _TAIL_LINE_RE.findall(d["tail"]):
            try:
                line = json.loads(raw)
            except json.JSONDecodeError:
                continue
            name = line.get("metric")
            if not name or not isinstance(line.get("value"), (int, float)):
                continue
            metrics.append({
                "metric": name, "value": float(line["value"]),
                "unit": line.get("unit"),
                "vs_baseline": line.get("vs_baseline"),
                # First occurrence per receipt is the comparable headline;
                # the rest are config variants.
                "headline": name not in seen_headline,
            })
            seen_headline.add(name)
        if "rc" in d:
            metrics.append({
                "metric": "rc", "value": float(d["rc"]), "headline": True,
            })
    elif "metric" in d and isinstance(d.get("value"), (int, float)):
        metrics.append({
            "metric": d["metric"], "value": float(d["value"]),
            "unit": d.get("unit"), "vs_baseline": d.get("vs_baseline"),
            "headline": True,
        })
    else:
        for name, v in _iter_numeric_leaves(
            {k: val for k, val in d.items() if k != "provenance"}
        ):
            metrics.append({"metric": name, "value": v, "headline": True})
    out["metrics"] = metrics
    return out


def build_trend(root: str = REPO) -> dict:
    """All receipts → {families: {family: {series: {metric: [samples]}}}}.

    Within a family, samples are ordered by revision number (``_rNN``);
    each sample is the receipt's HEADLINE value for that metric.
    """
    receipts = []
    for pat in ("*_BENCH.json", "BENCH_*.json", "MULTICHIP_*.json"):
        receipts.extend(glob.glob(os.path.join(root, pat)))
    families: dict[str, dict] = {}
    for path in sorted(set(receipts)):
        try:
            r = parse_receipt(path)
        except (json.JSONDecodeError, OSError) as e:
            print(f"bench_trend: skipping {path}: {e}", file=sys.stderr)
            continue
        fam = families.setdefault(
            r["family"], {"receipts": [], "series": {}},
        )
        fam["receipts"].append(r["file"])
        for m in r["metrics"]:
            if not m.get("headline"):
                continue
            fam["series"].setdefault(m["metric"], []).append({
                "rev": r["rev"], "file": r["file"], "value": m["value"],
                **({"unit": m["unit"]} if m.get("unit") else {}),
                **(
                    {"vs_baseline": m["vs_baseline"]}
                    if m.get("vs_baseline") is not None else {}
                ),
                **(
                    {"provenance": r["provenance"]}
                    if "provenance" in r else {}
                ),
            })
    for fam in families.values():
        fam["receipts"].sort()
        for pts in fam["series"].values():
            pts.sort(key=lambda p: (p["rev"], p["file"]))
    return {
        "format": "llmss-bench-trend-v1",
        "n_families": len(families),
        "families": families,
    }


def check_regression(
    trend: dict, family: str, metric: str, threshold: float = 0.10,
) -> tuple[bool, str]:
    """(ok, message): the newest headline vs the previous one. A drop
    greater than ``threshold`` fails — every headline metric in the tree
    is higher-is-better (a rate or a count of passing checks)."""
    fam = trend["families"].get(family)
    if fam is None:
        return False, f"unknown family {family!r} (have: " + ", ".join(
            sorted(trend["families"])) + ")"
    pts = fam["series"].get(metric)
    if pts is None:
        return False, f"family {family!r} has no metric {metric!r}"
    if len(pts) < 2:
        return True, (
            f"{family}:{metric}: only {len(pts)} receipt(s) — a trend "
            "needs two points; passing vacuously"
        )
    prev, cur = pts[-2], pts[-1]
    if prev["value"] <= 0:
        return True, f"{family}:{metric}: previous value non-positive; skip"
    delta = (cur["value"] - prev["value"]) / prev["value"]
    msg = (
        f"{family}:{metric}: {prev['value']} ({prev['file']}) -> "
        f"{cur['value']} ({cur['file']}) = {delta:+.1%} "
        f"(threshold -{threshold:.0%})"
    )
    return delta >= -threshold, msg


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument(
        "--check", metavar="FAMILY:METRIC",
        help="fail (exit 1) on >threshold regression of the named "
             "headline metric between the two newest receipts",
    )
    ap.add_argument(
        "--threshold", type=float, default=0.10,
        help="max tolerated fractional drop (default 0.10)",
    )
    ap.add_argument(
        "--out", default=os.path.join(REPO, "TREND.json"),
        help="trajectory file to write (default TREND.json at repo root)",
    )
    ap.add_argument(
        "--no-write", action="store_true",
        help="check only; don't rewrite the trend file",
    )
    args = ap.parse_args(argv)

    trend = build_trend()
    if not args.no_write:
        with open(args.out, "w") as f:
            json.dump(trend, f, indent=1, sort_keys=True)
            f.write("\n")
        print(
            f"wrote {args.out}: {trend['n_families']} families, "
            + ", ".join(
                f"{name} ({len(fam['series'])} series)"
                for name, fam in sorted(trend["families"].items())
            )
        )
    if args.check:
        if ":" not in args.check:
            print("--check wants FAMILY:METRIC", file=sys.stderr)
            return 2
        family, metric = args.check.split(":", 1)
        ok, msg = check_regression(trend, family, metric, args.threshold)
        print(("OK  " if ok else "FAIL ") + msg)
        return 0 if ok else 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
