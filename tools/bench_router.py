"""Router bench: shared queue vs prefix-affinity routing, 3 sim workers.

The workload is the one the ``prefix_affinity`` policy exists for: many
tenants, each with its own shared system prompt, interleaved so that
consecutive requests almost never share a prefix. Each simulated worker
holds a small prefix LRU (``LRU_SLOTS`` per worker — fewer than the
tenant count, more than tenants/worker), and a prefill that misses the
LRU pays the full prompt (``MISS_COST_S``) while a hit COW-attaches the
resident prefix and pays only the suffix — the same shape as a real
paged-KV COW prefix hit vs a full prefill.

With the shared queue every worker eventually sees every tenant and the
LRUs thrash; with prefix-affinity each tenant's requests ride to one
owning replica, so the fleet-wide working set fits. Both arms run on
the deterministic fleet simulator (``llmss_tpu.sim``): the REAL
``Router`` routes (or the ``shared`` null policy pushes to the shared
queue), replicas publish their resident prefix hashes in fleet
snapshots, and the invariant catalog is asserted at drain. The bench
measures the worker-observed prefix hit rate, p50/p95 TTFT, and
aggregate tokens/s for both modes and asserts the direction of the
result.

Runs on CPU in one process (no JAX, no device). Writes
nothing; prints one JSON line.
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from llmss_tpu.sim import FleetSim  # noqa: E402

N_WORKERS = int(os.environ.get("ROUTER_WORKERS", 3))
N_TENANTS = int(os.environ.get("ROUTER_TENANTS", 8))
N_REQUESTS = int(os.environ.get("ROUTER_REQUESTS", 120))
LRU_SLOTS = int(os.environ.get("ROUTER_LRU_SLOTS", 4))
MISS_COST_S = float(os.environ.get("ROUTER_MISS_COST_S", 0.015))
HIT_COST_S = float(os.environ.get("ROUTER_HIT_COST_S", 0.0015))
TOKEN_COST_S = float(os.environ.get("ROUTER_TOKEN_COST_S", 0.0002))
MAX_NEW = 16
PREFIX_LEN = 32


def make_trace_rows() -> list[dict]:
    """Interleaved multi-tenant trace: request i belongs to tenant
    i % N_TENANTS, so back-to-back requests never share a prefix."""
    prefixes = [
        [1000 + t] * PREFIX_LEN for t in range(N_TENANTS)
    ]
    return [
        {
            "id": f"rt{i:04d}",
            "arrival_s": 0.0,  # burst submit, like the original bench
            "token_ids": prefixes[i % N_TENANTS] + [i + 1],
            "prefix_token_ids": prefixes[i % N_TENANTS],
            "max_new": MAX_NEW,
        }
        for i in range(N_REQUESTS)
    ]


def make_spec(mode: str) -> dict:
    return {
        "format": "llmss-scenario/1",
        "name": f"bench-router-{mode}",
        "seed": 0,
        "broker": {"kind": "inproc", "lease_s": 10.0},
        "cost_model": {
            "kind": "table",
            # Full prompt (prefix + 1 suffix token) on a miss prices at
            # MISS_COST_S; a COW hit prefills only the suffix token.
            "prefill_token_s": MISS_COST_S / (PREFIX_LEN + 1),
            "decode_step_s": TOKEN_COST_S,
        },
        "fleet": {
            "replicas": [{
                "count": N_WORKERS, "role": "unified", "rows": 1,
                "chunk_tokens": MAX_NEW, "prefill_chunk": PREFIX_LEN + 1,
                "admit_burst": 1, "prefix_lru_slots": LRU_SLOTS,
            }],
            "router_policy": (
                "prefix_affinity" if mode == "affinity" else "shared"
            ),
        },
        "workload": {"kind": "trace", "rows": make_trace_rows()},
    }


def run_mode(mode: str) -> dict:
    sim = FleetSim(make_spec(mode))
    report = sim.run()
    tp = report["throughput"]
    elapsed = (
        tp["tokens_out"] / tp["tokens_per_s"] if tp["tokens_per_s"] else 0.0
    )
    hits = sim.counters["prefix_hits"]
    n = hits + sim.counters["prefix_misses"]
    out = {
        "mode": mode,
        "requests": n,
        "prefix_hit_rate": round(hits / n, 4),
        "ttft_p50_ms": round(report["latency_ms"]["ttft_p50"], 3),
        "ttft_p95_ms": round(report["latency_ms"]["ttft_p95"], 3),
        "tokens_per_s": round(tp["tokens_out"] / elapsed, 1)
        if elapsed else 0.0,
        "elapsed_s": round(elapsed, 3),
    }
    if sim.router is not None:
        out["router"] = sim.router.stats()
    return out


def main():
    shared = run_mode("shared")
    affinity = run_mode("affinity")
    result = {
        "config": {
            "workers": N_WORKERS,
            "tenants": N_TENANTS,
            "requests": N_REQUESTS,
            "lru_slots_per_worker": LRU_SLOTS,
            "miss_cost_s": MISS_COST_S,
            "hit_cost_s": HIT_COST_S,
            "token_cost_s": TOKEN_COST_S,
            "max_new_tokens": MAX_NEW,
        },
        "shared": shared,
        "affinity": affinity,
    }
    # The claims the policy ships on: strictly better prefix locality, no
    # TTFT regression.
    assert affinity["prefix_hit_rate"] > shared["prefix_hit_rate"], result
    assert affinity["ttft_p50_ms"] <= shared["ttft_p50_ms"], result
    print(json.dumps(result))


if __name__ == "__main__":
    main()
