"""SLO-plane overhead bench: cost attribution + windowed series on the hot path.

The SLO plane (PR 11) adds two things to every request on top of the
flight recorder: a terminal ``RequestCost`` derivation at broker
``push_response`` (one timeline scan + ~10 windowed-series updates) and
the per-heartbeat cached series export. Its acceptance bar: at most
~25 µs of host time per request over tracing alone, and under 1%
end-to-end throughput delta on the cost-model workload.

Three modes isolate the increments:

- ``off``   — recorder disabled: nothing records (the LLMSS_TRACE=0 path).
- ``trace`` — recorder on, but the cost-ingestion hook stubbed out: the
  PR-10 tracing baseline.
- ``slo``   — everything on: cost records derived and folded into the
  windowed registry at each respond.

Workload mirrors tools/bench_trace.py: N requests over InProcBroker →
PrefillWorker → LKVH → DecodeWorker with ScriptedEngine (no device,
worst case for instrumentation). The microcost is timed directly on the
respond-path hook over real recorded timelines (deterministic); the
throughput delta comes from median-of-paired adjacent trace/slo runs
with DECODE_STEP_COST_S charged per decode chunk, which cancels machine
drift a best-of comparison cannot. Writes nothing; prints one JSON
line.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from llmss_tpu.serve import broker as broker_mod  # noqa: E402
from llmss_tpu.serve.broker import InProcBroker  # noqa: E402
from llmss_tpu.serve.chaos import ScriptedEngine  # noqa: E402
from llmss_tpu.serve.handoff import DecodeWorker, PrefillWorker  # noqa: E402
from llmss_tpu.serve.protocol import GenerateRequest  # noqa: E402
from llmss_tpu.utils import metrics as metrics_mod  # noqa: E402
from llmss_tpu.utils import trace  # noqa: E402

N_REQUESTS = int(os.environ.get("SLO_BENCH_REQUESTS", 400))
MAX_NEW = int(os.environ.get("SLO_BENCH_MAX_NEW", 32))
PROMPT_LEN = int(os.environ.get("SLO_BENCH_PROMPT", 16))
REPEATS = int(os.environ.get("SLO_BENCH_REPEATS", 5))
DECODE_STEP_COST_S = float(os.environ.get("SLO_STEP_COST_S", 0.002))

US_PER_REQ_BUDGET = 25.0
THROUGHPUT_PCT_BUDGET = 1.0


def run_once(mode: str, chunk_delay_s: float = 0.0) -> float:
    """One full serve pass in ``mode``; returns wall seconds."""
    trace.set_enabled(mode != "off")
    trace.recorder().clear()
    metrics_mod.series().clear()
    stubbed = None
    if mode == "trace":
        stubbed = broker_mod._observe_cost
        broker_mod._observe_cost = lambda resp: None
    try:
        b = InProcBroker(lease_s=30.0)
        pre = PrefillWorker(
            ScriptedEngine(chunk_delay_s=chunk_delay_s), b, worker_id="p0",
        )
        dec = DecodeWorker(
            ScriptedEngine(chunk_delay_s=chunk_delay_s), b, worker_id="d0",
        )
        reqs = [
            GenerateRequest(
                id=f"s{i}",
                token_ids=[(i + j) % 50257 for j in range(PROMPT_LEN)],
                max_new_tokens=MAX_NEW,
            )
            for i in range(N_REQUESTS)
        ]
        t0 = time.monotonic()
        for r in reqs:
            b.push_request(r)
        done = 0
        while done < N_REQUESTS:
            pre.run_once()
            dec.run_once()
            while b.wait_response(reqs[done].id, timeout=0.0) is not None:
                done += 1
                if done == N_REQUESTS:
                    break
        elapsed = time.monotonic() - t0
    finally:
        if stubbed is not None:
            broker_mod._observe_cost = stubbed

    if mode == "slo":
        # every request produced exactly one terminal cost record
        total = metrics_mod.series().counter("requests_total").total
        assert total == N_REQUESTS, (total, N_REQUESTS)
    return elapsed


def main() -> int:
    for m in ("off", "trace", "slo"):  # warmup off the clock
        run_once(m)

    def paired(chunk_delay_s: float, pairs: int):
        """Median slo-minus-trace delta over adjacent (trace, slo) pairs.

        Machine drift here dwarfs the ~10ms signal over a multi-minute
        sweep, so diff-of-best-runs is hopeless; adjacent pairs see the
        same drift and difference it away. Within-pair order alternates
        to cancel ordering bias; median rejects the loud outlier pairs.
        """
        deltas, t_tr, t_slo = [], float("inf"), float("inf")
        for p in range(pairs):
            order = ("trace", "slo") if p % 2 == 0 else ("slo", "trace")
            got = {m: run_once(m, chunk_delay_s) for m in order}
            deltas.append(got["slo"] - got["trace"])
            t_tr = min(t_tr, got["trace"])
            t_slo = min(t_slo, got["slo"])
        deltas.sort()
        return deltas[len(deltas) // 2], t_tr, t_slo

    # Pass 1 — the plane's host microcost: time the exact respond-path
    # hook (local_cost + observe_request_cost) over the REAL timelines the
    # warmup's slo run left in the recorder. Deterministic where a
    # wall-clock A/B of whole ~100ms serve loops is noise-bound around a
    # ~10ms signal. (Re-ingesting inflates the registry's cumulative
    # counters; nothing below reads them.)
    run_once("slo")
    ids = trace.recorder().req_ids()
    hook_best = float("inf")
    for _ in range(10 * REPEATS):
        t0 = time.monotonic()
        for rid in ids:
            c = trace.local_cost(rid)
            if c is not None:
                metrics_mod.observe_request_cost(c)
        hook_best = min(hook_best, (time.monotonic() - t0) / len(ids))
    slo_us_per_req = hook_best * 1e6

    # Pass 2 — acceptance workload: decode chunks cost chip time.
    d_e2e, best_trace, best_slo = paired(DECODE_STEP_COST_S, 2 * REPEATS)
    overhead_pct = d_e2e / best_trace * 100.0
    best = {
        "off": min(run_once("off", DECODE_STEP_COST_S)
                   for _ in range(REPEATS)),
        "trace": best_trace,
        "slo": best_slo,
    }

    # On-demand cost: one /slo evaluation over the registry the slo pass
    # left behind (informational — this is endpoint-time, not hot-path).
    exports = [metrics_mod.series().export()]
    t0 = time.monotonic()
    slo_payload = metrics_mod.evaluate_slos(exports)
    eval_ms = (time.monotonic() - t0) * 1e3
    assert slo_payload["objectives"], "SLO evaluation returned no objectives"
    trace.set_enabled(True)  # restore the default

    tokens = N_REQUESTS * MAX_NEW
    out = {
        "bench": "slo_plane_overhead",
        "requests": N_REQUESTS,
        "max_new_tokens": MAX_NEW,
        "repeats": REPEATS,
        "decode_step_cost_s": DECODE_STEP_COST_S,
        "slo_overhead_us_per_request": round(slo_us_per_req, 1),
        "wall_s_off": round(best["off"], 4),
        "wall_s_trace": round(best["trace"], 4),
        "wall_s_slo": round(best["slo"], 4),
        "tok_per_s_trace": round(tokens / best["trace"], 1),
        "tok_per_s_slo": round(tokens / best["slo"], 1),
        "overhead_pct_vs_trace": round(overhead_pct, 2),
        "slo_eval_ms": round(eval_ms, 2),
        "us_budget": US_PER_REQ_BUDGET,
        "pct_budget": THROUGHPUT_PCT_BUDGET,
        "within_budget": (
            slo_us_per_req <= US_PER_REQ_BUDGET
            and overhead_pct < THROUGHPUT_PCT_BUDGET
        ),
    }
    print(json.dumps(out))
    return 0 if out["within_budget"] else 1


if __name__ == "__main__":
    sys.exit(main())
