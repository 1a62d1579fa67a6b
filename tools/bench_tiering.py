"""KV tiering bench: fleet-tiered prefix/session store vs per-worker LRU.

Runs ``scenarios/sessions.json`` — a multi-tenant multi-turn trace whose
shared-prefix working set (24 prefixes) exceeds any one replica's local
prefix LRU (4 slots) — through the deterministic fleet simulator in two
arms:

- **tiered**: the fleet-shared tier store (``fleet.kv_tiering``) is on.
  Prefixes evicted from a replica's local LRU demote to T1 host RAM
  (spilling to the T2 blob store under cap pressure) and promote back on
  the next miss anywhere in the fleet; finished session turns park their
  KV and the next turn resumes it without re-prefill.
- **baseline**: the same trace, same seed, with ``kv_tiering.enabled``
  flipped off — each worker has only its local prefix LRU, and every
  session turn re-prefills its full history. This is the pre-tiering
  code path, byte-identical to it.

Headline checks: the tiered arm must beat the baseline on fleet prefix
hit rate AND per-turn TTFT p95, and must avoid a nonzero number of
re-prefill tokens (the baseline, with no tier store, avoids none).
``--out PATH`` writes the full result.

    python tools/bench_tiering.py
    python tools/bench_tiering.py --check-determinism --out -
"""

from __future__ import annotations

import argparse
import copy
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from llmss_tpu.sim import run_scenario  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_SCENARIO = os.path.join(REPO, "scenarios", "sessions.json")


def _local_hit_rate(report: dict) -> float | None:
    """Prefix hit rate for an arm with no tier store: local LRU hits
    only, read from the flat sim counters."""
    f = report.get("faults") or {}
    hits = f.get("prefix_hits", 0)
    misses = f.get("prefix_misses", 0)
    total = hits + misses
    return round(hits / total, 6) if total else None


def run_all(scenario_path: str, n_requests: int | None,
            seed: int | None) -> dict:
    from llmss_tpu.sim.scenario import load_scenario

    base = load_scenario(scenario_path)
    if "kv_tiering" not in (base.get("fleet") or {}):
        raise SystemExit(
            f"{scenario_path}: scenario has no fleet.kv_tiering block — "
            "nothing to compare"
        )

    tiered_spec = copy.deepcopy(base)
    baseline_spec = copy.deepcopy(base)
    baseline_spec["fleet"]["kv_tiering"] = {"enabled": False}

    tiered = run_scenario(tiered_spec, n_requests=n_requests, seed=seed)
    baseline = run_scenario(baseline_spec, n_requests=n_requests, seed=seed)

    kt = tiered["kv_tiers"]
    tiered_hit = kt["fleet_prefix_hit_rate"]
    base_hit = _local_hit_rate(baseline)
    tiered_ttft = tiered["latency_ms"]["ttft_p95"]
    base_ttft = baseline["latency_ms"]["ttft_p95"]
    avoided = kt["reprefill_tokens_avoided"]

    checks = {
        # Headline: fleet-wide prefix reuse beats per-worker LRU reuse.
        "tiered_higher_prefix_hit_rate": (
            tiered_hit is not None and base_hit is not None
            and tiered_hit > base_hit
        ),
        # Promotions + session resume are cheaper than re-prefilling, so
        # the tail TTFT must come down.
        "tiered_lower_ttft_p95": tiered_ttft < base_ttft,
        # Parked sessions and tier hits must have skipped real prefill
        # work; the baseline (no tier store) avoids none by construction.
        "reprefill_tokens_avoided": avoided > 0,
        "sessions_resumed": kt["sessions_resumed"] > 0,
        # The baseline arm must be the pre-tiering code path: no tier
        # telemetry at all.
        "baseline_untiered": "kv_tiers" not in baseline,
        "zero_invariant_violations": (
            tiered["invariants"]["violations"] == 0
            and baseline["invariants"]["violations"] == 0
        ),
    }

    return {
        "bench": "kv_tiering",
        "scenario_file": os.path.relpath(scenario_path, REPO),
        "tiered": {
            "fleet_prefix_hit_rate": tiered_hit,
            "ttft_p95_ms": tiered_ttft,
            "reprefill_tokens_avoided": avoided,
            "sessions_parked": kt["sessions_parked"],
            "sessions_resumed": kt["sessions_resumed"],
            "tier_demotes": kt["tier_demotes"],
            "t1_spills": kt.get("t1_spills", 0),
            "prefix_hits_local": kt["prefix_hits_local"],
            "prefix_hits_tier": kt["prefix_hits_tier"],
            "prefix_misses": kt["prefix_misses"],
            "virtual_s": tiered["virtual_s"],
        },
        "baseline": {
            "prefix_hit_rate": base_hit,
            "ttft_p95_ms": base_ttft,
            "virtual_s": baseline["virtual_s"],
        },
        "checks": checks,
    }


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--scenario", default=DEFAULT_SCENARIO)
    ap.add_argument(
        "--requests", type=int, default=None,
        help="override the scenario's request count",
    )
    ap.add_argument("--seed", type=int, default=None)
    ap.add_argument(
        "--out", default="-",
        help="where to write the full result; '-' (default) writes "
             "nothing",
    )
    ap.add_argument(
        "--check-determinism", action="store_true",
        help="run both arms twice and fail unless the serialized results "
             "are byte-identical",
    )
    args = ap.parse_args(argv)

    result = run_all(args.scenario, args.requests, args.seed)
    if args.check_determinism:
        again = run_all(args.scenario, args.requests, args.seed)
        a = json.dumps(result, sort_keys=True)
        b = json.dumps(again, sort_keys=True)
        if a != b:
            print("DETERMINISM FAIL: same-seed re-run differs",
                  file=sys.stderr)
            return 1
        print("determinism: byte-identical same-seed re-run",
              file=sys.stderr)

    checks = result["checks"]
    passed = sum(bool(v) for v in checks.values())
    ok = passed == len(checks)
    full = {
        **result,
        "checks_passed": passed,
    }
    if args.out != "-":
        with open(args.out, "w") as f:
            json.dump(full, f, indent=1, sort_keys=True)
            f.write("\n")

    t, b = result["tiered"], result["baseline"]
    print(json.dumps({
        "metric": "tiering_checks_passed",
        "value": passed,
        "unit": (
            f"of {len(checks)} checks (hit rate {t['fleet_prefix_hit_rate']}"
            f" vs {b['prefix_hit_rate']} baseline; ttft_p95 "
            f"{t['ttft_p95_ms']}ms vs {b['ttft_p95_ms']}ms; "
            f"{t['reprefill_tokens_avoided']} re-prefill tokens avoided; "
            f"failed: "
            f"{sorted(k for k, v in checks.items() if not v) or 'none'})"
        ),
        "ok": ok,
    }))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
