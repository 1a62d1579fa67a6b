"""PD bench: disaggregated prefill/decode vs unified continuous batching.

The workload is the one disaggregation exists for (handoff.py): a mixed
trace of long-prompt/short-decode requests interleaved with short
interactive ones. A UNIFIED replica runs prefill and decode on the same
chip, so every long prefill it admits stalls the fused decode steps of
its co-batched rows — the stall shows up as decode step-time variance
and TTFT tail. A DISAGGREGATED fleet (1 prefill + 1 decode replica at
the same chip count) absorbs prefills on the prefill chip and ships the
paged blocks through the broker handoff channel; the decode chip's only
non-step work is adopting a payload (an HBM-bandwidth block import, ~3
orders of magnitude cheaper than a long prefill).

Both arms run on the deterministic fleet simulator (``llmss_tpu.sim``):
the chip is a :class:`DeviceCostModel` charging
``PREFILL_TOKEN_COST_S`` per prompt token, ``DECODE_STEP_COST_S`` per
fused step, and payload bytes over ``HBM_GBPS`` for an adopt — but the
TRANSFER PLANE IS REAL: records ride the broker's
push_handoff/pop_handoff/push_response with full-size payloads
(``KV_BYTES_PER_TOKEN`` defaults to the 1b2 dims in bf16), leases
touched per cycle, so handoff bytes per request and the delivery
counters come from the broker, not the model — and the sim's invariant
catalog (exactly-one-terminal, KV balance, …) is asserted at drain.
Virtual clock: the run is byte-reproducible and takes milliseconds of
wall time regardless of the simulated seconds.

Runs on CPU in one process (no JAX, no device). Writes nothing;
prints the full result, then one headline JSON line. Asserts the
structural claims the subsystem ships on: zero lost/errored requests in
both modes, every multi-token request handed off exactly once, and strictly lower decode step-time variance
for the disaggregated fleet.
"""

from __future__ import annotations

import json
import os
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from llmss_tpu.sim import FleetSim  # noqa: E402

N_CHIPS = 2  # both fleets: 2 unified vs 1 prefill + 1 decode
ROWS = int(os.environ.get("PD_ROWS", 8))  # decode rows per chip
N_LONG = int(os.environ.get("PD_LONG", 8))
N_SHORT = int(os.environ.get("PD_SHORT", 24))
LONG_PROMPT = int(os.environ.get("PD_LONG_PROMPT", 256))
SHORT_PROMPT = int(os.environ.get("PD_SHORT_PROMPT", 32))
LONG_NEW = int(os.environ.get("PD_LONG_NEW", 16))
SHORT_NEW = int(os.environ.get("PD_SHORT_NEW", 32))
ARRIVAL_GAP_S = float(os.environ.get("PD_ARRIVAL_GAP_S", 0.005))

PREFILL_TOKEN_COST_S = float(os.environ.get("PD_PREFILL_TOKEN_COST_S", 50e-6))
DECODE_STEP_COST_S = float(os.environ.get("PD_DECODE_STEP_COST_S", 1.5e-3))
ADOPT_CONST_S = float(os.environ.get("PD_ADOPT_CONST_S", 1e-3))
HBM_GBPS = float(os.environ.get("BENCH_HBM_GBPS", 819.0))  # v5e
# 1b2 dims bf16: k+v x 20 layers x 16 kv heads x 128 head_dim x 2 bytes.
KV_BYTES_PER_TOKEN = int(
    os.environ.get("PD_KV_BYTES_PER_TOKEN", 2 * 20 * 16 * 128 * 2)
)


def make_trace_rows() -> list[dict]:
    """Mixed trace, interleaved so long prefills keep landing while
    short interactive rows are mid-decode."""
    longs = [
        {"token_ids": [1000 + i] * LONG_PROMPT, "max_new": LONG_NEW}
        for i in range(N_LONG)
    ]
    shorts = [
        {"token_ids": [2000 + i] * SHORT_PROMPT, "max_new": SHORT_NEW}
        for i in range(N_SHORT)
    ]
    out: list[dict] = []
    ratio = max(1, N_SHORT // max(N_LONG, 1))
    while longs or shorts:
        if longs:
            out.append(longs.pop(0))
        for _ in range(ratio):
            if shorts:
                out.append(shorts.pop(0))
    for i, row in enumerate(out):
        row["id"] = f"pd{i:04d}"
        row["arrival_s"] = i * ARRIVAL_GAP_S
    return out


def make_spec(mode: str) -> dict:
    # prefill_chunk covers the whole prompt: the unified arm prefills
    # INLINE in one fused step, stalling co-batched decode — the
    # head-of-line cost disaggregation removes. chunk_tokens=1 so every
    # decode step is one gap sample.
    inline = max(LONG_PROMPT, SHORT_PROMPT)
    common = {
        "rows": ROWS, "chunk_tokens": 1, "prefill_chunk": inline,
        "admit_burst": 1,
    }
    if mode == "unified":
        replicas = [{"count": N_CHIPS, "role": "unified", **common}]
    else:
        replicas = [
            {"count": 1, "role": "prefill", **common,
             "sized_handoff_payload": True},
            {"count": 1, "role": "decode", **common},
        ]
    return {
        "format": "llmss-scenario/1",
        "name": f"bench-pd-{mode}",
        "seed": 0,
        "broker": {"kind": "inproc", "lease_s": 5.0},
        "cost_model": {
            "kind": "table",
            "prefill_token_s": PREFILL_TOKEN_COST_S,
            "decode_step_s": DECODE_STEP_COST_S,
            "adopt_const_s": ADOPT_CONST_S,
            "kv_bytes_per_token": KV_BYTES_PER_TOKEN,
            "wire_gbps": HBM_GBPS,
        },
        "fleet": {"replicas": replicas, "router_policy": "shared"},
        "workload": {"kind": "trace", "rows": make_trace_rows()},
        "metrics": {"step_gaps": True},
    }


def run_mode(mode: str) -> dict:
    sim = FleetSim(make_spec(mode))
    report = sim.run()
    r = report["requests"]
    tp = report["throughput"]
    # Virtual span from submit of the first request to the last
    # completion (recover it from the rounded rate rather than the
    # drain-padded clock).
    elapsed = (
        tp["tokens_out"] / tp["tokens_per_s"] if tp["tokens_per_s"] else 0.0
    )
    delivery = report["delivery"]
    gaps_ms = [g * 1e3 for g in sim.step_gaps]
    return {
        "mode": mode,
        "requests": r["submitted"],
        "lost": r["submitted"] - r["answered"],
        "errored": r["answered"] - r["ok"],
        "tokens": tp["tokens_out"],
        "tok_s_chip": round(tp["tokens_out"] / elapsed / N_CHIPS, 1)
        if elapsed else 0.0,
        "ttft_p50_ms": round(report["latency_ms"]["ttft_p50"], 3),
        "ttft_p95_ms": round(report["latency_ms"]["ttft_p95"], 3),
        "decode_step_ms_mean": round(statistics.fmean(gaps_ms), 3),
        "decode_step_ms_stdev": round(statistics.stdev(gaps_ms), 3),
        "decode_step_ms_p95": round(
            statistics.quantiles(gaps_ms, n=20)[18], 3
        ),
        "handoffs": delivery.get("handoffs", 0),
        "handoff_bytes": delivery.get("handoff_bytes", 0),
        "handoff_bytes_per_request": (
            round(delivery["handoff_bytes"] / delivery["handoffs"])
            if delivery.get("handoffs") else 0
        ),
        "reprefills": delivery.get("reprefills", 0),
        "elapsed_s": round(elapsed, 3),
    }


def main():
    unified = run_mode("unified")
    disagg = run_mode("disagg")
    result = {
        "config": {
            "chips": N_CHIPS,
            "rows_per_chip": ROWS,
            "trace": {
                "long": {"n": N_LONG, "prompt": LONG_PROMPT,
                         "max_new": LONG_NEW},
                "short": {"n": N_SHORT, "prompt": SHORT_PROMPT,
                          "max_new": SHORT_NEW},
                "arrival_gap_s": ARRIVAL_GAP_S,
            },
            "prefill_token_cost_s": PREFILL_TOKEN_COST_S,
            "decode_step_cost_s": DECODE_STEP_COST_S,
            "adopt_const_s": ADOPT_CONST_S,
            "kv_bytes_per_token": KV_BYTES_PER_TOKEN,
            "hbm_gbps": HBM_GBPS,
        },
        "unified": unified,
        "disagg": disagg,
    }
    # The claims the subsystem ships on: nothing lost or errored, every
    # multi-token request handed off exactly once, and the decode chip's
    # step cadence freed of prefill stalls.
    for mode in (unified, disagg):
        assert mode["lost"] == 0 and mode["errored"] == 0, result
    assert disagg["handoffs"] == N_LONG + N_SHORT, result
    assert unified["handoffs"] == 0, result
    assert (
        disagg["decode_step_ms_stdev"] < unified["decode_step_ms_stdev"]
    ), result
    print(json.dumps(result))
    print(json.dumps({
        "metric": "pd_disagg_decode_tok_s_chip",
        "value": disagg["tok_s_chip"],
        "unit": (
            f"tok/s/chip sim ({N_CHIPS} chips, 1P+1D vs {N_CHIPS} unified"
            f"={unified['tok_s_chip']}; decode step stdev "
            f"{disagg['decode_step_ms_stdev']} vs "
            f"{unified['decode_step_ms_stdev']} ms, ttft_p95 "
            f"{disagg['ttft_p95_ms']} vs {unified['ttft_p95_ms']} ms, "
            f"{disagg['handoff_bytes_per_request'] / 1e6:.1f} MB/handoff)"
        ),
        "vs_baseline": round(
            disagg["tok_s_chip"] / max(unified["tok_s_chip"], 1e-9), 3
        ),
    }))


if __name__ == "__main__":
    main()
