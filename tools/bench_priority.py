"""Priority bench: FIFO vs tiered+preemption vs tiered+brownout.

Replays two heavy-tailed traces (the ROADMAP mixed-tenant scenario: a
steady drip of long batch jobs, moderate standard traffic, interactive
arrivals in tight bursts) through three scheduling arms of the
deterministic fleet simulator (``llmss_tpu.sim``) — one continuous-
batching replica over the REAL broker, scheduler preemption policy, and
``BrownoutController`` — and reports per-class TTFT p95/p99, SLO
attainment, and a chips-equivalent figure. The **burst** trace is
recoverable overload — the FIFO-vs-tiered p95 headline, where the
brownout ladder sheds background work so bursts land on free rows
instead of paying the one-eviction-per-cycle train. The **overload**
trace is sustained demand beyond capacity, where priorities alone
cannot save interactive and the degradation-ordering claims (batch
before standard before interactive, interactive never shed) are
asserted on real shed counts.

The arms:

- ``fifo``     — one class-blind queue, no preemption, admit-all: every
  request submits as one SLO class (the broker's class queues collapse
  to FIFO) and a side-table classifier keeps per-class accounting
  honest. The static-fleet baseline: interactive bursts queue behind
  batch rows.
- ``tiered``   — class-priority queues + paged-KV preemption: an
  interactive arrival blocked on row capacity evicts the lowest-class
  running row (the scheduler's REAL ``select_preemption_victim``:
  victim strictly outranked, fewest emitted tokens; refund to the head
  of its class queue; resume replays the emitted prefix).
- ``brownout`` — tiered plus the real ``BrownoutController`` driven by
  the interactive SLO burn rate over the sim's sliding TTFT window,
  walking the cap-batch -> shed-batch -> shed-standard ladder.

The simulator advances in decode-step cycles (every resident row emits
one token per fused step); prompt prefill is metered through the ragged
chunk path before the first token, and a resumed row re-charges prefill
over prompt+emitted — the same cost shape the scheduler's
chunked-replay resume pays. Virtual time makes the bench exactly
reproducible: no sleeps, no wall-clock — and the sim's invariant
catalog (exactly-one-terminal, preemption refunds never consume
delivery attempts, KV balance) is asserted at drain of every arm.

``chips_equivalent`` is the static-fleet cost of buying the same
interactive TTFT p95 without priorities: the smallest N data-parallel
replicas at which the arm meets the interactive target. FIFO needs
several chips; the tiered arms hit the target on one — that delta is
the PR's capacity claim.

Also times the scheduler's real ``_maybe_preempt`` no-op paths (idle,
and pending-but-not-blocked) on a live ContinuousBatcher — the per-step
host tax every deployment with ``preempt_cb`` set pays — against the
25 µs budget. Writes nothing (prints the full result, then one
headline JSON line); exits nonzero if any acceptance assertion fails.
"""

from __future__ import annotations

import json
import math
import os
import random
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from llmss_tpu.serve.protocol import (  # noqa: E402
    SLO_CLASS_BATCH,
    SLO_CLASS_INTERACTIVE,
    SLO_CLASS_STANDARD,
)
from llmss_tpu.sim import FleetSim  # noqa: E402

SEED = 1405
ROWS = 12
STEP_S = 0.02  # one fused decode step: every resident row advances one token
#: Tokens per fused chunk — the scheduling quantum: admission, eviction
#: (one per cycle, the ContinuousBatcher bound), and row-freeing happen
#: once per CHUNK_TOKENS steps, so the quantum sets the eviction-train
#: latency an interactive burst pays when rows are pinned by batch.
CHUNK_TOKENS = 2
PREFILL_TOKEN_S = 0.0004
PREFILL_CHUNK = 64  # ragged metering: prompt tokens per row per step
TRACE_S = 120.0

#: per-class TTFT targets (ms) at p95 — mirrors DEFAULT_SLO_OBJECTIVES.
TTFT_TARGET_MS = {
    SLO_CLASS_INTERACTIVE: 500.0,
    SLO_CLASS_STANDARD: 2000.0,
    SLO_CLASS_BATCH: 15000.0,
}
SLO_TARGET = 0.95
US_PER_CALL_BUDGET = 25.0
MAX_CHIPS = 12

CLASSES = (SLO_CLASS_INTERACTIVE, SLO_CLASS_STANDARD, SLO_CLASS_BATCH)


def build_trace(overload: bool = False) -> list[dict]:
    """A heavy-tailed bursty arrival trace, identical across arms.

    Batch max_new is Pareto(a=1.1) — a long tail of multi-hundred-token
    jobs that pin rows for seconds. Interactive arrives as tight bursts
    on top of a steady drip; during a burst the offered row demand far
    exceeds ROWS, which is the moment the arms diverge.

    The default shape is bursty-but-recoverable: overload comes in
    spikes the fleet can absorb between bursts (the FIFO-vs-tiered p95
    headline). ``overload=True`` triples the background classes and
    doubles the burst cadence — sustained demand beyond capacity where
    priorities alone cannot save interactive and the brownout ladder
    must shed (the degradation-ordering scenario).
    """
    rng = random.Random(SEED)
    reqs = []
    batch_rate = 7.5 if overload else 2.5
    std_rate = 12.0 if overload else 4.0
    burst_n, burst_gap = (24, 4) if overload else (16, 8)

    t = 0.0
    while t < TRACE_S:  # batch drip: long, heavy-tailed
        t += rng.expovariate(batch_rate)
        reqs.append({
            "cls": SLO_CLASS_BATCH, "arrival": t, "plen": 256,
            "max_new": min(512, int(24 * rng.paretovariate(1.1))),
        })
    t = 0.0
    while t < TRACE_S:  # standard background
        t += rng.expovariate(std_rate)
        reqs.append({
            "cls": SLO_CLASS_STANDARD, "arrival": t, "plen": 64,
            "max_new": 8 + int(rng.expovariate(1 / 24)),
        })
    t = 0.0
    while t < TRACE_S:  # interactive: drip + tight bursts
        t += rng.expovariate(1.2)
        reqs.append({
            "cls": SLO_CLASS_INTERACTIVE, "arrival": t, "plen": 24,
            "max_new": 4 + int(rng.expovariate(1 / 6)),
        })
    for burst0 in range(4, int(TRACE_S), burst_gap):
        for _ in range(burst_n):
            reqs.append({
                "cls": SLO_CLASS_INTERACTIVE,
                "arrival": burst0 + rng.random() * 0.4,
                "plen": 24, "max_new": 4 + int(rng.expovariate(1 / 6)),
            })
    reqs.sort(key=lambda r: r["arrival"])
    for i, r in enumerate(reqs):
        r["id"] = f"pr{i:05d}"
    return reqs


def make_spec(arm: str, trace: list[dict], chips: int) -> dict:
    rows = [
        {
            "id": r["id"],
            "arrival_s": r["arrival"],
            "prompt_len": r["plen"],
            "max_new": r["max_new"],
            # The FIFO arm is class-blind: everything rides one queue.
            "slo_class": (
                SLO_CLASS_STANDARD if arm == "fifo" else r["cls"]
            ),
        }
        for r in trace
    ]
    spec = {
        "format": "llmss-scenario/1",
        "name": f"bench-priority-{arm}-{chips}",
        "seed": SEED,
        # Long-prompt admission cycles can run past a short visibility
        # timeout; the bench measures scheduling, not lease churn.
        "broker": {"kind": "inproc", "lease_s": 30.0},
        "cost_model": {
            "kind": "table",
            "prefill_token_s": PREFILL_TOKEN_S,
            "decode_step_s": STEP_S,
        },
        "fleet": {
            "replicas": [{
                "count": chips, "role": "unified", "rows": ROWS,
                "chunk_tokens": CHUNK_TOKENS, "prefill_chunk": PREFILL_CHUNK,
                "admit_burst": ROWS, "preempt": arm != "fifo",
            }],
            "router_policy": "shared",
        },
        "workload": {"kind": "trace", "rows": rows},
        "metrics": {"per_class": True},
    }
    if arm == "brownout":
        spec["fleet"]["brownout"] = {
            "ttft_target_s": TTFT_TARGET_MS[SLO_CLASS_INTERACTIVE] / 1e3,
            "burn": "attainment", "slo_target": SLO_TARGET,
            # ``low=0`` latches rungs for the trace duration (the
            # controller de-escalates on ``burn < low``, strict): every
            # de-escalation re-admits the batch backlog into rows, and
            # the next burst pays a one-eviction-per-cycle train to
            # clear it — on a 2-minute trace with bursts every 8 s the
            # flap costs more interactive attainment than any batch
            # throughput it buys back.
            "high": 1.0, "low": 0.0, "dwell_s": 6.0, "check_s": 0.5,
        }
    return spec


def _pct(vals, q) -> float | None:
    if not vals:
        return None
    s = sorted(vals)
    i = min(len(s) - 1, math.ceil(q * len(s)) - 1)
    return round(s[i] * 1e3, 1)


def simulate(arm: str, trace: list[dict], chips: int = 1) -> dict:
    """Run one arm over the trace on a ``chips``-replica data-parallel
    fleet; returns per-class latency/attainment stats."""
    sim = FleetSim(make_spec(arm, trace, chips))
    true_cls = {r["id"]: r["cls"] for r in trace}
    # Per-class accounting keeps the TRUE class even in the class-blind
    # FIFO arm (everything submits as one class there).
    sim.classify = lambda req: true_cls[req.id]
    sim.run()

    out = {
        "classes": {},
        "preemptions": sim.counters["preemptions"],
        "chip_busy_s": round(sum(r.busy_s for r in sim.replicas), 1),
    }
    for c in CLASSES:
        tgt = TTFT_TARGET_MS[c]
        vals = sim._cls_ttft[c]  # per-class TTFT samples (true class)
        offered = sim._cls_offered[c]
        within = sum(1 for v in vals if v * 1e3 <= tgt)
        out["classes"][c] = {
            "offered": offered,
            "completed": sim._cls_done[c],
            "shed": sim._cls_shed[c],
            "ttft_p50_ms": _pct(vals, 0.50),
            "ttft_p95_ms": _pct(vals, 0.95),
            "ttft_p99_ms": _pct(vals, 0.99),
            "ttft_target_ms": tgt,
            # attainment over OFFERED traffic: a shed request is a
            # degraded request — brownout can't launder its sheds out of
            # the denominator.
            "slo_attainment": round(within / offered, 4)
            if offered else None,
        }
    if sim.ctrl is not None:
        out["brownout"] = sim.ctrl.state()
    return out


def chips_equivalent(arm: str, trace: list[dict]) -> int | None:
    """Smallest static N-chip fleet at which ``arm`` meets the
    interactive TTFT p95 target; None if > MAX_CHIPS."""
    tgt = TTFT_TARGET_MS[SLO_CLASS_INTERACTIVE]
    for n in range(1, MAX_CHIPS + 1):
        r = simulate(arm, trace, chips=n)
        p95 = r["classes"][SLO_CLASS_INTERACTIVE]["ttft_p95_ms"]
        if p95 is not None and p95 <= tgt:
            return n
    return None


def preempt_hook_microbench() -> dict:
    """Host cost of the scheduler's real ``_maybe_preempt`` no-op paths
    on a live ContinuousBatcher: idle (no pending), and the steady-state
    pending-but-unblocked check. These run once per step in every
    deployment that sets ``preempt_cb``."""
    import jax

    from llmss_tpu.engine import DecodeEngine
    from llmss_tpu.engine.scheduler import ContinuousBatcher
    from llmss_tpu.models.common import DecoderConfig
    from llmss_tpu.models.decoder import init_params
    from llmss_tpu.parallel import MeshPlan, make_mesh

    cfg = DecoderConfig(
        model_type="llama", vocab_size=64, hidden_size=32, n_layers=2,
        n_heads=4, n_kv_heads=2, head_dim=8, intermediate_size=64,
        max_position_embeddings=64, activation="silu", norm="rmsnorm",
        norm_eps=1e-5, mlp="swiglu", positions="rotary", rope_style="half",
        rotary_dim=8, attn_bias=False, mlp_bias=False,
        tie_word_embeddings=False, dtype="float32",
    )
    mesh = make_mesh(MeshPlan(dp=1, tp=len(jax.devices())))
    params = init_params(cfg, mesh, jax.random.key(0))
    engine = DecodeEngine(cfg, params, mesh, max_seq_len=64)
    bat = ContinuousBatcher(engine, rows=4)
    bat.preempt_cb = lambda rid, toks: None

    n = 20000
    best_idle = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        for _ in range(n):
            bat._maybe_preempt()
        best_idle = min(best_idle, (time.perf_counter() - t0) / n)

    # steady-state: a pending head exists but free rows remain, so the
    # hook reads the head's priority and returns without scanning rows
    # (only index 7 — priority — is touched on this path).
    fake = (None, None, None, None, None, None, None, 1, 0)
    with bat._lock:
        bat.pending.append(fake)
    best_pending = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        for _ in range(n):
            bat._maybe_preempt()
        best_pending = min(best_pending, (time.perf_counter() - t0) / n)
    with bat._lock:
        bat.pending.clear()
    return {
        "idle_us": round(best_idle * 1e6, 3),
        "pending_unblocked_us": round(best_pending * 1e6, 3),
        "budget_us": US_PER_CALL_BUDGET,
    }


def main() -> int:
    # Scenario 1 — bursty-but-recoverable: the p95 headline. Tiered
    # scheduling absorbs what FIFO cannot; brownout additionally keeps
    # rows free BEFORE each burst lands (shed batch/standard instead of
    # paying the eviction train), which is what buys the p95 target on
    # one chip.
    burst_trace = build_trace()
    burst = {}
    for arm in ("fifo", "tiered", "brownout"):
        burst[arm] = simulate(arm, burst_trace)
        burst[arm]["chips_equivalent"] = chips_equivalent(arm, burst_trace)
    # Scenario 2 — sustained overload: demand exceeds capacity for the
    # whole trace, priorities alone cannot protect interactive, and the
    # ladder must walk. Degradation ordering is asserted HERE, on real
    # shed counts, never on a trace where nothing degrades.
    over_trace = build_trace(overload=True)
    over = {arm: simulate(arm, over_trace)
            for arm in ("fifo", "tiered", "brownout")}
    micro = preempt_hook_microbench()

    fifo_i = burst["fifo"]["classes"][SLO_CLASS_INTERACTIVE]
    bo_i = burst["brownout"]["classes"][SLO_CLASS_INTERACTIVE]
    obo = over["brownout"]["classes"]

    def degradation(c):
        # 1 - attainment over OFFERED traffic (sheds count against the
        # class): the "how much did this class hurt" score the ladder
        # ordering is judged by.
        return 1.0 - (obo[c]["slo_attainment"] or 0.0)

    def att(arms, arm):
        a = arms[arm]["classes"][SLO_CLASS_INTERACTIVE]["slo_attainment"]
        return a or 0.0

    checks = {
        # the headline: brownout meets the interactive target that FIFO
        # blows through on the same single chip
        "brownout_interactive_p95_meets_target":
            bo_i["ttft_p95_ms"] <= TTFT_TARGET_MS[SLO_CLASS_INTERACTIVE],
        "fifo_interactive_p95_violates":
            fifo_i["ttft_p95_ms"] > TTFT_TARGET_MS[SLO_CLASS_INTERACTIVE],
        "preemption_engaged": burst["tiered"]["preemptions"] > 0,
        # overload: the ladder actually walked — batch was shed and the
        # controller recorded transitions (not a vacuous pass)
        "brownout_engaged":
            obo[SLO_CLASS_BATCH]["shed"] > 0
            and over["brownout"]["brownout"]["transitions_total"] > 0,
        # degradation is ordered: batch before standard before
        # interactive, and interactive is never shed in ANY scenario
        "degradation_order_batch_standard_interactive":
            degradation(SLO_CLASS_BATCH)
            >= degradation(SLO_CLASS_STANDARD)
            >= degradation(SLO_CLASS_INTERACTIVE),
        "standard_sheds_only_after_batch":
            obo[SLO_CLASS_STANDARD]["shed"] == 0
            or obo[SLO_CLASS_BATCH]["shed"] > 0,
        "interactive_never_shed": all(
            arms[a]["classes"][SLO_CLASS_INTERACTIVE]["shed"] == 0
            for arms in (burst, over) for a in arms
        ),
        # under overload, shedding buys interactive more attainment than
        # either priorities alone or FIFO
        "brownout_protects_interactive_under_overload":
            att(over, "brownout") >= att(over, "tiered")
            and att(over, "brownout") > att(over, "fifo"),
        "preempt_hook_within_budget":
            max(micro["idle_us"], micro["pending_unblocked_us"])
            <= US_PER_CALL_BUDGET,
    }

    out = {
        "bench": "priority_scheduling",
        "config": {
            "seed": SEED, "rows": ROWS, "step_s": STEP_S,
            "chunk_tokens": CHUNK_TOKENS,
            "prefill_chunk": PREFILL_CHUNK,
            "prefill_token_s": PREFILL_TOKEN_S, "trace_s": TRACE_S,
            "n_requests_burst": len(burst_trace),
            "n_requests_overload": len(over_trace),
            "ttft_targets_ms": TTFT_TARGET_MS, "slo_target": SLO_TARGET,
        },
        "scenarios": {"burst": burst, "overload": over},
        "preempt_hook": micro,
        "checks": checks,
        "checks_passed": sum(1 for v in checks.values() if v),
        "ok": all(checks.values()),
    }
    print(json.dumps(out))
    print(json.dumps({
        "metric": "interactive_ttft_p95_ms",
        "value": bo_i["ttft_p95_ms"],
        "unit": (
            f"ms on 1 chip under brownout (fifo={fifo_i['ttft_p95_ms']} ms; "
            f"chips-equivalent fifo={burst['fifo']['chips_equivalent']} vs "
            f"brownout={burst['brownout']['chips_equivalent']}; "
            f"{burst['tiered']['preemptions']} preemptions in burst arm; "
            f"overload sheds batch={obo[SLO_CLASS_BATCH]['shed']} "
            f"standard={obo[SLO_CLASS_STANDARD]['shed']} interactive=0; "
            f"preempt hook {micro['pending_unblocked_us']} us)"
        ),
        "ok": out["ok"],
        "failed_checks": [k for k, v in checks.items() if not v],
    }))
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
