"""Ragged bench: unified ragged dispatch (chunked prefill) vs split path.

The workload is the one the ragged program exists for (ISSUE 10): long
prompts landing while short interactive rows are mid-decode. The SPLIT
path admits a prompt through the (P, S) prefill bucket ladder — the
prompt pads to the next power-of-two bucket and the whole padded prefill
runs INLINE, stalling every co-batched row's fused decode step; a prompt
past the prewarmed ladder additionally pays the bucket's XLA compile,
the multi-second TTFT cliff. The UNIFIED-RAGGED path admits the same
prompt as extra query rows of the decode dispatch: up to ``CHUNK_BUDGET``
prompt tokens per row per step, so prefill compute is metered across
steps and no bucket (or its compile) exists at all.

Both arms run on the deterministic fleet simulator (``llmss_tpu.sim``):
one unified replica whose ``prefill_mode`` selects the path (``split`` =
bucket ladder + mid-serve compile, ``chunked`` = ragged metering with
``prefill_chunk = CHUNK_BUDGET``), priced by a :class:`DeviceCostModel`
charging ``PREFILL_TOKEN_COST_S`` per prompt token, ``DECODE_STEP_COST_S``
per fused step, and ``BUCKET_COMPILE_S`` once per bucket beyond the
prewarmed ladder — so the comparison is deterministic and free of host
noise; the scheduler arithmetic (admission, chunk metering, head-of-line
stalls) is the thing being measured, and requests ride the REAL broker
with the invariant catalog asserted at drain. Runs on CPU in one process
(no JAX, no device). Writes nothing; prints the full result, then one
headline JSON line.
Asserts the claims the subsystem ships on: decode step-time stdev no
worse on the all-decode trace (the ragged program is not allowed to tax
the steady state) and materially lower TTFT p95 plus lower decode stdev
on the mixed long-prompt trace.
"""

from __future__ import annotations

import json
import os
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from llmss_tpu.sim import FleetSim  # noqa: E402

ROWS = int(os.environ.get("RAGGED_ROWS", 8))
CHUNK_BUDGET = int(os.environ.get("RAGGED_CB", 16))
N_LONG = int(os.environ.get("RAGGED_LONG", 8))
N_SHORT = int(os.environ.get("RAGGED_SHORT", 24))
LONG_PROMPT = int(os.environ.get("RAGGED_LONG_PROMPT", 384))
SHORT_PROMPT = int(os.environ.get("RAGGED_SHORT_PROMPT", 24))
LONG_NEW = int(os.environ.get("RAGGED_LONG_NEW", 16))
SHORT_NEW = int(os.environ.get("RAGGED_SHORT_NEW", 32))
ARRIVAL_GAP_S = float(os.environ.get("RAGGED_ARRIVAL_GAP_S", 0.004))

PREFILL_TOKEN_COST_S = float(
    os.environ.get("RAGGED_PREFILL_TOKEN_COST_S", 50e-6)
)
DECODE_STEP_COST_S = float(os.environ.get("RAGGED_DECODE_STEP_COST_S", 1.5e-3))
# First use of a prompt bucket past the prewarmed ladder compiles a fresh
# (P, S) executable mid-serve — the stall chunked prefill retires.
BUCKET_COMPILE_S = float(os.environ.get("RAGGED_BUCKET_COMPILE_S", 2.5))
PREWARM_MAX_BUCKET = int(os.environ.get("RAGGED_PREWARM_MAX", 128))


def make_trace(long_prompt: int, n_long: int) -> list[dict]:
    """Mixed trace, interleaved so long prefills keep landing while short
    interactive rows are mid-decode. ``n_long == 0`` gives the all-decode
    control trace (every prompt fits one chunk / the smallest bucket)."""
    longs = [
        {"plen": long_prompt, "new": LONG_NEW} for _ in range(n_long)
    ]
    shorts = [
        {"plen": SHORT_PROMPT, "new": SHORT_NEW} for _ in range(N_SHORT)
    ]
    out: list[dict] = []
    ratio = max(1, N_SHORT // max(n_long, 1))
    while longs or shorts:
        if longs:
            out.append(longs.pop(0))
        for _ in range(ratio):
            if shorts:
                out.append(shorts.pop(0))
    return [
        {
            "id": f"rg{i:04d}",
            "arrival_s": i * ARRIVAL_GAP_S,
            "token_ids": [3000 + i] * r["plen"],
            "max_new": r["new"],
        }
        for i, r in enumerate(out)
    ]


def make_spec(mode: str, rows: list[dict]) -> dict:
    return {
        "format": "llmss-scenario/1",
        "name": f"bench-ragged-{mode}",
        "seed": 0,
        "broker": {"kind": "inproc", "lease_s": 10.0},
        "cost_model": {
            "kind": "table",
            "prefill_token_s": PREFILL_TOKEN_COST_S,
            "decode_step_s": DECODE_STEP_COST_S,
            "bucket_compile_s": BUCKET_COMPILE_S,
            "prewarm_max_bucket": PREWARM_MAX_BUCKET,
        },
        "fleet": {
            "replicas": [{
                "count": 1, "role": "unified", "rows": ROWS,
                "chunk_tokens": 1, "admit_burst": ROWS,
                "prefill_mode": "split" if mode == "split" else "chunked",
                "prefill_chunk": CHUNK_BUDGET,
            }],
            "router_policy": "shared",
        },
        "workload": {"kind": "trace", "rows": rows},
        "metrics": {"step_gaps": True},
    }


def run_mode(mode: str, trace: list[dict]) -> dict:
    sim = FleetSim(make_spec(mode, trace))
    report = sim.run()
    tp = report["throughput"]
    elapsed = (
        tp["tokens_out"] / tp["tokens_per_s"] if tp["tokens_per_s"] else 0.0
    )
    ttfts = report["latency_ms"]
    gaps_ms = [g * 1e3 for g in sim.step_gaps]
    return {
        "mode": mode,
        "requests": len(trace),
        "tokens": tp["tokens_out"],
        "elapsed_s": round(elapsed, 3),
        "tok_s_chip": round(tp["tokens_out"] / elapsed, 1)
        if elapsed else 0.0,
        "ttft_p50_ms": round(ttfts["ttft_p50"], 3),
        "ttft_p95_ms": round(ttfts["ttft_p95"], 3),
        "decode_step_ms_mean": round(statistics.fmean(gaps_ms), 3),
        "decode_step_ms_stdev": round(statistics.stdev(gaps_ms), 3),
        "decode_step_ms_p95": round(
            statistics.quantiles(gaps_ms, n=20)[18], 3
        ),
        "buckets_compiled_mid_serve": sim.counters["buckets_compiled"],
    }


def main():
    mixed = make_trace(LONG_PROMPT, N_LONG)
    # All-decode control: every prompt fits one chunk AND the smallest
    # prewarmed bucket, so both paths insert identical prefill work and
    # the ragged program must not tax the pure-decode cadence.
    alldec = make_trace(CHUNK_BUDGET, 0)

    result = {
        "config": {
            "rows": ROWS,
            "chunk_budget": CHUNK_BUDGET,
            "trace": {
                "long": {"n": N_LONG, "prompt": LONG_PROMPT,
                         "max_new": LONG_NEW},
                "short": {"n": N_SHORT, "prompt": SHORT_PROMPT,
                          "max_new": SHORT_NEW},
                "arrival_gap_s": ARRIVAL_GAP_S,
            },
            "prefill_token_cost_s": PREFILL_TOKEN_COST_S,
            "decode_step_cost_s": DECODE_STEP_COST_S,
            "bucket_compile_s": BUCKET_COMPILE_S,
            "prewarm_max_bucket": PREWARM_MAX_BUCKET,
        },
        "mixed": {
            "split": run_mode("split", mixed),
            "ragged": run_mode("ragged", mixed),
        },
        "all_decode": {
            "split": run_mode("split", alldec),
            "ragged": run_mode("ragged", alldec),
        },
    }

    ms, mr = result["mixed"]["split"], result["mixed"]["ragged"]
    as_, ar = result["all_decode"]["split"], result["all_decode"]["ragged"]
    # The claims the subsystem ships on: metering beats monopolizing on
    # the mixed trace, and costs nothing when there is nothing to meter.
    assert mr["ttft_p95_ms"] < 0.5 * ms["ttft_p95_ms"], result
    assert mr["decode_step_ms_stdev"] < ms["decode_step_ms_stdev"], result
    assert (
        ar["decode_step_ms_stdev"] <= as_["decode_step_ms_stdev"] + 0.05
    ), result
    assert mr["buckets_compiled_mid_serve"] == 0, result

    print(json.dumps(result))
    print(json.dumps({
        "metric": "ragged_mixed_ttft_p95_ms",
        "value": mr["ttft_p95_ms"],
        "unit": (
            f"ms sim (ragged CB={CHUNK_BUDGET} vs split bucket ladder "
            f"{ms['ttft_p95_ms']}ms; decode step stdev "
            f"{mr['decode_step_ms_stdev']} vs "
            f"{ms['decode_step_ms_stdev']} ms mixed, "
            f"{ar['decode_step_ms_stdev']} vs "
            f"{as_['decode_step_ms_stdev']} ms all-decode; "
            f"{mr['tok_s_chip']} vs {ms['tok_s_chip']} tok/s/chip; "
            f"split compiled {ms['buckets_compiled_mid_serve']} bucket(s) "
            "mid-serve, ragged 0)"
        ),
        "vs_baseline": round(
            mr["ttft_p95_ms"] / max(ms["ttft_p95_ms"], 1e-9), 3
        ),
    }))


if __name__ == "__main__":
    main()
