"""int8 KV where it matters: long context — capacity AND throughput.

The int8 cache halves KV HBM bytes, which is a *capacity* feature: twice
the rows×context fits one chip. This bench pins that claim with numbers
on real hardware (1b2 flagship dims, ring 2048, 1024-token prompts):

1. throughput: decode step time bf16 vs int8 at a batch both fit;
2. capacity: a batch whose bf16 cache CANNOT be allocated next to the
   params (driven to OOM and caught) but whose int8 cache serves fine —
   the "2x rows/context" receipt;
3. the sp>1 dequant bound: on sequence-parallel meshes the int8 layer is
   pre-dequantized before the shard_map'd attention (models/decoder.py),
   an analytic extra-traffic bound reported per step.

Writes INT8_BENCH.json (not kept in the tree; the round-5 copy is at
commit e57f952); prints one JSON line.
"""

from __future__ import annotations

import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import chunk_schedule, flagship_cfg, slope_time  # noqa: E402

RING = int(os.environ.get("INT8_RING", 2048))
PROMPT = int(os.environ.get("INT8_PROMPT", 1024))
BATCH_BOTH = int(os.environ.get("INT8_BATCH", 24))
BATCH_BIG = int(os.environ.get("INT8_BATCH_BIG", 48))
N_SLOPE = (16, 112)
CHUNK = 16


def step_ms_for(engine, cfg, batch) -> float:
    from llmss_tpu.engine import GenerationParams

    gen = GenerationParams(max_new_tokens=N_SLOPE[1], is_greedy=True)
    rng = np.random.default_rng(0)
    prompts = [
        rng.integers(0, cfg.vocab_size, PROMPT).tolist()
        for _ in range(batch)
    ]
    ids, lens = engine._pad_prompts(prompts)
    sa = engine._sample_args(gen, batch)
    eos = engine.canon_vec(jnp.full(batch, -1, jnp.int32))
    done = jnp.zeros(batch, bool)

    def prepare(n):
        cache = engine.new_cache(batch)
        tok0, _, cache = engine._prefill(
            engine.params, jnp.asarray(ids), cache, jnp.asarray(lens), sa,
        )
        tok0 = engine.canon_vec(tok0)
        cache = engine.canon_cache(cache)
        cur0 = engine.canon_vec(jnp.asarray(lens))
        sched = chunk_schedule(engine, int(lens.max()), n, CHUNK)
        state = {"cache": cache}

        def run():
            cache, tok, cur = state["cache"], tok0, cur0
            total = jnp.zeros((), jnp.int32)
            for k, tb in sched:
                toks, cache, cur, _, _ = engine._decode_many(
                    engine.params, tok, cache, cur, sa, done, eos,
                    n_steps=k, t_bucket=tb,
                )
                cache = engine.canon_cache(cache)
                cur = engine.canon_vec(cur)
                tok = engine.canon_vec(toks[:, -1])
                total = total + jnp.sum(toks)
            state["cache"] = cache
            _ = int(total)

        return run

    return slope_time(prepare, N_SLOPE)[0]


def main():
    from llmss_tpu.engine import DecodeEngine
    from llmss_tpu.models.decoder import init_params
    from llmss_tpu.parallel import MeshPlan, make_mesh

    mesh = make_mesh(MeshPlan(tp=len(jax.devices())))
    cfg = flagship_cfg("1b2")
    params = init_params(cfg, mesh, jax.random.key(0))
    kv_gb = lambda b, dtype_bytes: (  # noqa: E731
        2 * cfg.n_layers * b * RING * cfg.n_kv_heads * cfg.head_dim
        * dtype_bytes / 1e9
    )

    out = {
        "config": {
            "model": "1b2", "ring": RING, "prompt": PROMPT,
            "batch_both": BATCH_BOTH, "batch_big": BATCH_BIG,
            "bf16_cache_gb_at_batch_big": round(kv_gb(BATCH_BIG, 2), 2),
            "int8_cache_gb_at_batch_big": round(
                kv_gb(BATCH_BIG, 1) + kv_gb(BATCH_BIG, 2) / 256, 2
            ),
        },
    }

    # 1. throughput at a batch both dtypes fit
    for kv in (None, "int8"):
        eng = DecodeEngine(
            cfg, params, mesh, max_seq_len=RING, kv_dtype=kv,
        )
        ms = step_ms_for(eng, cfg, BATCH_BOTH)
        out[f"step_ms_{kv or 'bf16'}_b{BATCH_BOTH}"] = round(ms, 3)
        out[f"tok_s_chip_{kv or 'bf16'}_b{BATCH_BOTH}"] = round(
            BATCH_BOTH / ms * 1e3, 1
        )

    # 2. capacity: bf16 at BATCH_BIG should not fit beside the params;
    # int8 must serve it.
    try:
        eng = DecodeEngine(cfg, params, mesh, max_seq_len=RING)
        ms = step_ms_for(eng, cfg, BATCH_BIG)
        out["bf16_big_batch"] = {
            "fit": True, "step_ms": round(ms, 3),
            "note": "bf16 unexpectedly fit - capacity margin larger "
                    "than modeled",
        }
    except Exception as e:  # noqa: BLE001 — OOM is the expected outcome
        out["bf16_big_batch"] = {
            "fit": False,
            "error": type(e).__name__ + ": " + str(e)[:200],
        }
    eng = DecodeEngine(cfg, params, mesh, max_seq_len=RING, kv_dtype="int8")
    ms = step_ms_for(eng, cfg, BATCH_BIG)
    out["int8_big_batch"] = {
        "fit": True, "step_ms": round(ms, 3),
        "tok_s_chip": round(BATCH_BIG / ms * 1e3, 1),
    }

    # 3. analytic sp>1 dequant bound (models/decoder.py pre-dequantizes
    # each layer's int8 shard to bf16 before the shard_map'd attention):
    # per step, per shard: 2 (k+v) x L x B x (T/sp) x Hkv x D x 2 bytes
    # written + the int8 read it replaces — an upper bound of one extra
    # bf16 cache-copy per step.
    out["sp_dequant_bound_gb_per_step_per_shard"] = {
        "formula": "2*L*B*(T/sp)*Hkv*D*2 bytes written (+int8 read)",
        "example_sp2_b8": round(
            2 * cfg.n_layers * 8 * (RING // 2) * cfg.n_kv_heads
            * cfg.head_dim * 2 / 1e9, 3
        ),
    }

    speedup = out[f"step_ms_bf16_b{BATCH_BOTH}"] / out[
        f"step_ms_int8_b{BATCH_BOTH}"
    ]
    result = {
        "metric": "int8_kv_long_context",
        "value": out["int8_big_batch"]["tok_s_chip"],
        "unit": (
            f"tok/s/chip (1b2, ring={RING}, prompt={PROMPT}, int8 KV at "
            f"batch={BATCH_BIG} — bf16 "
            + ("OOMs" if not out["bf16_big_batch"]["fit"] else "fits(!)")
            + f" there; at batch={BATCH_BOTH} both fit: int8 "
            f"{speedup:.2f}x bf16 step time)"
        ),
        "vs_baseline": round(speedup, 3),
    }
    out["headline"] = result
    print(json.dumps(result))
    from bench import bench_provenance

    out["provenance"] = bench_provenance()
    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "INT8_BENCH.json"), "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
