"""Autoscale bench: closed-loop fleet controller vs static peak fleet.

Runs the ``scenarios/diurnal.json`` scenario — a diurnal heavy-tailed
arrival trace — through the deterministic fleet simulator in five arms:

- **controlled**: floor-sized fleet + the reconciling FleetController
  (``serve/controller.py``) spawning/retiring replicas from burn,
  backlog, and utilization telemetry.
- **static**: the same trace on a fixed fleet sized at the controlled
  arm's PEAK replica count — what you must provision without a
  controller.
- **killwave_fast**: a 6-replica kill wave at the evening peak with a
  2s cold start (well inside the 10s burn headroom). The controller
  must replace the dead capacity while the brownout ladder never moves:
  every escalation ask is suppressed (scale-before-shed).
- **killwave_slow**: the same wave with a 30s cold start (past the burn
  headroom). Scaling structurally cannot respond in time, so the
  controller must ALLOW the ladder to engage — shedding is the correct
  lever, and the bench asserts it actually fired.
- **crash**: the controller is crashed mid-climb and restarted 3s later
  as a brand-new instance reconciling from the registry, while the dead
  instance keeps ticking as a zombie. Zero duplicate spawns (checker-
  certified) and every zombie actuation dies at the epoch fence. A
  telemetry stall overlay asserts the staleness hold.

The headline check: the controlled fleet spends FEWER replica-seconds
(chip-hours) than the static peak fleet at equal-or-better per-class
TTFT SLO attainment. ``--out PATH`` writes the full result.

    python tools/bench_autoscale.py
    python tools/bench_autoscale.py --check-determinism --out -
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
DEFAULT_SCENARIO = os.path.join(REPO, "scenarios", "diurnal.json")

# Attainment slack for "equal-or-better": the controlled arm rides
# closer to the edge by design; more than this is a real SLO regression.
ATTAINMENT_EPS = 0.02

KILL_WAVE = {
    "kind": "kill_wave", "at_s": 270.0, "count": 6,
    "respawn_after_s": None, "stagger_s": 0.5,
}


def _arm_specs(base: dict) -> dict[str, dict]:
    """The five arm specs, all derived from the one scenario file."""
    arms: dict[str, dict] = {}

    arms["controlled"] = copy.deepcopy(base)

    fast = copy.deepcopy(base)
    fast["fleet"]["controller"]["cold_start_s"] = 2.0
    fast["fleet"]["controller"]["ceiling"] = 16
    fast["fleet"]["brownout"]["high"] = 2.0
    fast["faults"] = [copy.deepcopy(KILL_WAVE)]
    arms["killwave_fast"] = fast

    slow = copy.deepcopy(base)
    slow["fleet"]["controller"]["cold_start_s"] = 30.0
    slow["fleet"]["controller"]["ceiling"] = 16
    slow["fleet"]["brownout"]["high"] = 2.0
    slow["faults"] = [copy.deepcopy(KILL_WAVE)]
    arms["killwave_slow"] = slow

    crash = copy.deepcopy(base)
    crash["faults"] = [
        {"kind": "controller_crash", "at_s": 95.0,
         "restart_after_s": 3.0, "zombie": True},
        {"kind": "telemetry_stall", "at_s": 120.0, "duration_s": 8.0},
    ]
    arms["crash"] = crash

    return arms


def _static_spec(base: dict, peak: int) -> dict:
    st = copy.deepcopy(base)
    del st["fleet"]["controller"]
    st["fleet"].pop("brownout", None)
    st["fleet"]["replicas"] = [
        {**base["fleet"]["replicas"][0], "count": peak},
    ]
    return st


def _attainment(report: dict) -> dict[str, float]:
    return {
        cls: v["ttft_attainment"]
        for cls, v in (report.get("classes") or {}).items()
        if v.get("ttft_attainment") is not None
    }


def _summarize(name: str, rep: dict) -> dict:
    fl = rep.get("fleet") or {}
    cc = (fl.get("controller") or {}).get("counters") or {}
    bo = fl.get("brownout") or {}
    return {
        "arm": name,
        "virtual_s": rep["virtual_s"],
        "replica_seconds": fl.get("replica_seconds"),
        "peak_alive": fl.get("peak_alive"),
        "spawns": fl.get("spawns"),
        "retires": fl.get("retires"),
        "zombie_fenced": fl.get("zombie_fenced"),
        "controller_counters": cc or None,
        "brownout_transitions": bo.get("transitions_total"),
        "brownout_suppressed": bo.get("suppressed_escalations"),
        "kills": rep["faults"].get("kills", 0),
        "controller_crashes": rep["faults"].get("controller_crashes", 0),
        "controller_restarts": rep["faults"].get("controller_restarts", 0),
        "shed": sum(
            v["shed"] for v in (rep.get("classes") or {}).values()
        ),
        "attainment": _attainment(rep),
        "violations": rep["invariants"]["violations"],
    }


def run_all(scenario_path: str, n_requests: int | None,
            seed: int | None) -> dict:
    from llmss_tpu.sim.scenario import load_scenario

    base = load_scenario(scenario_path)
    arms = _arm_specs(base)
    reports = {
        name: run_scenario(
            copy.deepcopy(spec), n_requests=n_requests, seed=seed,
        )
        for name, spec in arms.items()
    }
    peak = reports["controlled"]["fleet"]["peak_alive"]
    static_spec = _static_spec(base, peak)
    reports["static"] = run_scenario(
        copy.deepcopy(static_spec), n_requests=n_requests, seed=seed,
    )

    ctl, sta = reports["controlled"], reports["static"]
    fast, slow = reports["killwave_fast"], reports["killwave_slow"]
    crash = reports["crash"]

    ctl_chips = ctl["fleet"]["replica_seconds"]
    # A static fleet pays for every replica over the whole span.
    sta_chips = round(peak * sta["virtual_s"], 6)
    ctl_att, sta_att = _attainment(ctl), _attainment(sta)

    fast_bo = fast["fleet"]["brownout"]
    slow_bo = slow["fleet"]["brownout"]
    checks = {
        # Headline: fewer chip-seconds at equal-or-better attainment.
        "controlled_fewer_chips": ctl_chips < sta_chips,
        "equal_or_better_slo": all(
            ctl_att.get(cls, 0.0) >= sta_att[cls] - ATTAINMENT_EPS
            for cls in sta_att
        ),
        # Kill wave, cold start inside the burn headroom: the controller
        # replaces dead capacity and the ladder never moves — every
        # escalation ask suppressed, nothing shed.
        "killwave_fast_controller_replaces": (
            fast["faults"].get("kills", 0) == KILL_WAVE["count"]
            and fast["fleet"]["spawns"] >= KILL_WAVE["count"]
        ),
        "killwave_fast_brownout_never_moves": (
            fast_bo["transitions_total"] == 0
            and fast_bo["suppressed_escalations"] > 0
        ),
        # Kill wave, cold start past the burn headroom: scaling cannot
        # respond in time, so the ladder MUST engage.
        "killwave_slow_brownout_engages": (
            slow_bo["transitions_total"] > 0
            and slow["fleet"]["controller"]["counters"][
                "escalations_allowed"] > 0
        ),
        # Crash + zombie: a fresh epoch reconciles with zero duplicate
        # spawns (any dup is an invariant violation) and every actuation
        # the zombie plans dies at the epoch fence.
        "crash_restart_reconciles": (
            crash["faults"].get("controller_crashes", 0) == 1
            and crash["faults"].get("controller_restarts", 0) == 1
        ),
        "crash_zombie_fenced": (
            crash["fleet"]["zombie_fenced"] > 0
            and crash["fleet"]["controller"]["counters"]["fenced"] == 0
        ),
        "crash_stale_telemetry_holds": (
            crash["faults"].get("telemetry_stalls", 0) == 1
            and crash["fleet"]["controller"]["counters"]["held_stale"] > 0
        ),
        "zero_invariant_violations": all(
            r["invariants"]["violations"] == 0 for r in reports.values()
        ),
    }

    return {
        "bench": "fleet_autoscale",
        "scenario_file": os.path.relpath(scenario_path, REPO),
        "chips": {
            "controlled_replica_seconds": ctl_chips,
            "static_replica_seconds": sta_chips,
            "savings_frac": round(1.0 - ctl_chips / sta_chips, 6),
            "static_fleet_size": peak,
        },
        "attainment": {"controlled": ctl_att, "static": sta_att},
        "arms": {n: _summarize(n, r) for n, r in reports.items()},
        "checks": checks,
    }


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--scenario", default=DEFAULT_SCENARIO)
    ap.add_argument(
        "--requests", type=int, default=None,
        help="override the scenario's request count (NOTE: the kill-wave "
             "overlays fire at fixed virtual times — shrinking the trace "
             "below them voids those checks)",
    )
    ap.add_argument("--seed", type=int, default=None)
    ap.add_argument(
        "--out", default="-",
        help="where to write the full result; '-' (default) writes "
             "nothing",
    )
    ap.add_argument(
        "--check-determinism", action="store_true",
        help="run every arm twice and fail unless the serialized results "
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

    ch = result["chips"]
    print(json.dumps({
        "metric": "autoscale_checks_passed",
        "value": passed,
        "unit": (
            f"of {len(checks)} checks (controlled "
            f"{ch['controlled_replica_seconds']} vs static "
            f"{ch['static_replica_seconds']} replica-s, "
            f"{round(ch['savings_frac'] * 100, 1)}% saved at fleet size "
            f"{ch['static_fleet_size']}; failed: "
            f"{sorted(k for k, v in checks.items() if not v) or 'none'})"
        ),
        "ok": ok,
    }))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
