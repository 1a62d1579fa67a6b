"""Fleet storm: a million simulated requests through repeated failure.

Runs the ``scenarios/storm.json`` scenario on the deterministic fleet
simulator (``llmss_tpu.sim``): a 16-replica mixed unified +
prefill/decode fleet absorbing ~1M requests at ~1500 rps while seeded
correlated kill waves, broker partitions, fleet-wide latency spikes,
heartbeat stalls, and handoff-mid-kill storms fire every few tens of
virtual seconds — with the full invariant catalog (exactly-one terminal
response, zero lost / zero double-answered, preemption refunds consume
no delivery attempts, KV accounts balance at drain, DLQ holds only
genuine poison) asserted continuously and at drain.

The run is byte-reproducible: same scenario + same seed produces a
byte-identical report (``--check-determinism`` proves it by running
twice and comparing serialized reports; ``--out PATH`` writes it).
``--requests`` scales the storm down for CI without touching the
scenario file.

    python tools/sim_storm.py                         # the full 1M storm
    python tools/sim_storm.py --requests 20000 --check-determinism
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from llmss_tpu.sim import run_scenario  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_SCENARIO = os.path.join(REPO, "scenarios", "storm.json")


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--scenario", default=DEFAULT_SCENARIO)
    ap.add_argument(
        "--requests", type=int, default=None,
        help="override the scenario's request count (CI scale-down)",
    )
    ap.add_argument("--seed", type=int, default=None)
    ap.add_argument(
        "--out", default="-",
        help="where to write the full report; '-' (default) writes "
             "nothing",
    )
    ap.add_argument(
        "--check-determinism", action="store_true",
        help="run the scenario twice and fail unless the serialized "
             "reports are byte-identical",
    )
    args = ap.parse_args(argv)

    report = run_scenario(
        args.scenario, n_requests=args.requests, seed=args.seed,
    )
    if args.check_determinism:
        again = run_scenario(
            args.scenario, n_requests=args.requests, seed=args.seed,
        )
        a = json.dumps(report, sort_keys=True)
        b = json.dumps(again, sort_keys=True)
        if a != b:
            print("DETERMINISM FAIL: same-seed re-run differs",
                  file=sys.stderr)
            return 1
        print("determinism: byte-identical same-seed re-run", file=sys.stderr)

    full = {
        "bench": "fleet_storm",
        "scenario_file": os.path.relpath(args.scenario, REPO),
        "report": report,
    }
    if args.out != "-":
        with open(args.out, "w") as f:
            json.dump(full, f, indent=1, sort_keys=True)
            f.write("\n")

    r = report["requests"]
    print(json.dumps({
        "metric": "storm_requests_per_s",
        "value": report["throughput"]["requests_per_s"],
        "unit": (
            f"req/s virtual ({r['submitted']} submitted, {r['ok']} ok, "
            f"{r['deadline_shed']} deadline-shed, {r['shed']} brownout-shed, "
            f"{r['dead_lettered']} dead-lettered over "
            f"{report['virtual_s']}s; {report['faults'].get('kills', 0)} "
            f"kills, {report['faults'].get('poison_crashes', 0)} poison "
            f"crashes; invariants: {report['invariants']['violations']} "
            "violations)"
        ),
        "ok": report["invariants"]["violations"] == 0,
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
