"""Benchmark: decode throughput (tokens/sec/chip) on the flagship model.

Run on real TPU hardware by the driver. Prints ONE JSON line per benched
config — the HEADLINE LAST: **Llama-2-7B dims, the BASELINE.md
north-star scale** (the ~1.2B lines print first: the series tracked since
round 1, kept for cross-round comparability, plus its int8-KV variant):
``{"metric": ..., "value": N, "unit": ..., "vs_baseline": N}``.

The reference publishes no numbers (BASELINE.md), so ``vs_baseline`` is
reported against the **HBM-bandwidth roofline** for batched decode on this
chip: a decode step must stream all parameter bytes plus the live KV-cache
bytes from HBM, so

    roofline_tokens_per_sec = batch * BW / (param_bytes + batch * kv_bytes)

``vs_baseline`` = measured / roofline — i.e. the fraction of the chip's
theoretical decode ceiling this framework reaches (1.0 is perfect), with
``kv_bytes`` accounted at an average half-full ring in bf16.

Methodology: steady-state decode cost is the **marginal** time per fused
decode step, measured by the slope method — run the decode at two step
counts and take (t(N2) - t(N1)) / (N2 - N1). This cancels constant per-call
overhead (dispatch + fetch latency per call: not measured on the current
machine; a served request pays it, this headline does not see it) and
matches what a long-running serving process sustains. The decode runs in
CHUNK-step fused scans chained back-to-back (dispatches are async — no host
sync between chunks), exactly like the serving path, so the engine's
**bucketed cache reads** are measured: each chunk reads only the ring
prefix covering the rows' live context (engine.decode_bucket), not the
whole provisioned ring. The ring (``MAX_SEQ``) is sized so the slope window
never wraps — positions stay inside the advertised context. Prefill
latency is its own number (TTFT, reported in ``unit``), not smeared into
decode throughput. As an independent cross-check, ``unit`` also reports
the achieved HBM rate implied by the measured step time over the bytes the
step actually streams (params + the mean bucketed KV prefix).

Models: Llama-architecture ~1.2B (the series tracked across rounds, plus
its int8-KV variant) and Llama-2-7B dims — the BASELINE.md north-star
scale and the headline, printed last — all random-init bf16 weights.
``BENCH_MODEL=1b2|7b`` restricts to one.
"""

from __future__ import annotations

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

# Batch 16 is the 1b2 headline point (vs_baseline peaks there: params
# dominate the roofline denominator); 7B runs batch 4 (params + cache fill
# the chip). BENCH_KV_DTYPE=int8 halves cache memory (2x rows/context).
PROMPT = int(os.environ.get("BENCH_PROMPT", 128))
DECODE = int(os.environ.get("BENCH_DECODE", 128))
KV_DTYPE = os.environ.get("BENCH_KV_DTYPE") or None  # "int8" halves KV bytes
CHUNK = int(os.environ.get("BENCH_CHUNK", 32))  # serving-path fused chunk

MODEL = os.environ.get("BENCH_MODEL")  # "1b2" | "7b" | None = both

_MODEL_DIMS = {
    # ~1.2B: the headline config — fits one v5e with generous cache room.
    "1b2": dict(hidden_size=2048, n_layers=20, n_heads=16,
                intermediate_size=5504),
    # Llama-2-7B dims (BASELINE.md north-star scale): 13.5 GB bf16 params
    # on a 16 GB v5e — single-chip analogue of the TP=8 config.
    "7b": dict(hidden_size=4096, n_layers=32, n_heads=32,
               intermediate_size=11008),
}

# Per-model operating point: batch and slope-method step counts (the 7B
# window is shorter because its params already fill 13.5 of 16 GB). The
# ring is derived as PROMPT + n_slope[1] so the slope window never wraps,
# whatever BENCH_PROMPT is set to.
_MODEL_RUN = {
    "1b2": dict(batch=16, n_slope=(64, 320)),
    "7b": dict(batch=4, n_slope=(32, 224)),
}

BATCH = int(os.environ.get("BENCH_BATCH", 0))  # 0 = per-model default


def require_tpu() -> None:
    """Fail before any work when JAX found no TPU: a time or a rate from
    another backend is never printed under a device metric's name."""
    backend = jax.default_backend()
    if backend != "tpu":
        raise SystemExit(
            f"this benchmark measures the chip; JAX's default backend is "
            f"{backend!r}, not 'tpu'"
        )


def hbm_gbps() -> float:
    """Peak HBM GB/s of the attached device, from the one peaks table
    (``utils/devtel.DEVICE_PEAKS``; an unknown device is an error)."""
    from llmss_tpu.utils import devtel

    return devtel.device_peaks()[1] / 1e9


def bench_provenance() -> dict:
    """Host/accelerator provenance stamped into every bench JSON.

    Every ``*_BENCH.json`` / ``BENCH_*.json`` writer in the repo includes
    this block so a reader can tell a CPU-backend functional run from a
    real-TPU run without parsing the ``unit`` string. It initializes the
    JAX backend — a launcher whose children need the chip must not call
    it (tools/bench_load.py takes the stamp from its child instead).
    """
    import platform as _plat

    dev = jax.devices()[0]
    return {
        "python": _plat.python_version(),
        "machine": _plat.machine(),
        "backend": jax.default_backend(),
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "device_count": jax.device_count(),
    }


def flagship_cfg(model: str = "1b2"):
    from llmss_tpu.models.common import DecoderConfig

    dims = _MODEL_DIMS[model]
    return DecoderConfig(
        model_type="llama",
        vocab_size=32000,
        n_kv_heads=dims["n_heads"],
        head_dim=128,
        max_position_embeddings=4096,
        activation="silu",
        norm="rmsnorm",
        norm_eps=1e-5,
        mlp="swiglu",
        positions="rotary",
        rope_style="half",
        rotary_dim=128,
        attn_bias=False,
        mlp_bias=False,
        tie_word_embeddings=False,
        dtype="bfloat16",
        **dims,
    )


def roofline_tokens_per_sec(
    cfg, param_bytes: float, batch: int, max_seq: int,
) -> float:
    """HBM-bandwidth decode ceiling: params + avg-half-full bf16 KV per
    step. The single definition of ``vs_baseline`` shared by bench.py and
    bench_serve.py so the two lines stay directly comparable."""
    kv_bytes_per_token = (
        2 * cfg.n_layers * cfg.n_kv_heads * cfg.head_dim * 2 * max_seq / 2
    )  # avg half-full cache, k+v, bf16
    return batch * hbm_gbps() * 1e9 / (
        param_bytes + batch * kv_bytes_per_token
    )


def slope_time(
    prepare, n_slope=(64, 320), reps: int = 3
) -> tuple[float, float]:
    """Marginal ms per decode step + constant ms, via the slope method.

    ``prepare(n)`` must return a zero-arg callable that runs one n-step
    decode **to completion** — force it with a host fetch of a scalar
    reduction, which cannot return before the device has finished. The
    single methodology shared by bench.py and tools/profile_decode.py.
    """
    times = {}
    for n in n_slope:
        run = prepare(n)
        run()  # compile + warm
        best = float("inf")
        for _i in range(reps):
            t0 = time.perf_counter()
            run()
            best = min(best, time.perf_counter() - t0)
        times[n] = best
        # Drop the closure (and the cache it carries) BEFORE the next
        # prepare(): at big-ring configs two live caches OOM the chip.
        del run
    n1, n2 = n_slope
    slope_ms = (times[n2] - times[n1]) / (n2 - n1) * 1e3
    const_ms = times[n1] * 1e3 - slope_ms * n1
    return slope_ms, const_ms


def chunk_schedule(engine, start_pos: int, n_steps: int, chunk: int):
    """The (n_steps_in_chunk, t_bucket) sequence a chained-chunk decode of
    ``n_steps`` runs, starting with every row at ``start_pos``. Shared by
    the runner and the achieved-bandwidth accounting."""
    out = []
    pos = start_pos
    left = n_steps
    while left > 0:
        k = min(chunk, left)
        out.append((k, engine.decode_bucket(pos + k)))
        pos += k
        left -= k
    return out


def _decode_slope_ms(engine, ids, lens, sa, eos, batch, n_slope):
    """Serving-path decode: chained CHUNK-step fused scans with bucketed
    cache reads, dispatched back-to-back (async), one forcing fetch at the
    end. Marginal cost via the slope method."""
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
            cache = state["cache"]
            tok, cur = tok0, cur0
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
            _ = int(total)  # forced completion

        return run

    return slope_time(prepare, n_slope)


def run_model(model: str, kv_dtype: str | None = KV_DTYPE) -> dict:
    from llmss_tpu.engine import DecodeEngine, GenerationParams
    from llmss_tpu.models.decoder import init_params
    from llmss_tpu.parallel import MeshPlan, make_mesh

    run_cfg = _MODEL_RUN[model]
    batch = BATCH or run_cfg["batch"]
    n_slope = run_cfg["n_slope"]
    max_seq = int(os.environ.get("BENCH_MAX_SEQ", 0)) or (
        PROMPT + n_slope[1]
    )

    n_dev = len(jax.devices())
    mesh = make_mesh(MeshPlan(tp=n_dev))
    cfg = flagship_cfg(model)
    params = init_params(cfg, mesh, jax.random.key(0))
    n_params = sum(
        np.prod(x.shape) for x in jax.tree.leaves(params)
    )
    param_bytes = float(n_params) * 2  # bf16

    engine = DecodeEngine(
        cfg, params, mesh, max_seq_len=max_seq, kv_dtype=kv_dtype,
    )
    gen = GenerationParams(max_new_tokens=DECODE, is_greedy=True)

    rng = np.random.default_rng(0)
    prompts = [
        rng.integers(0, cfg.vocab_size, PROMPT).tolist() for _ in range(batch)
    ]
    ids, lens = engine._pad_prompts(prompts)
    sa = engine._sample_args(gen, batch)
    eos = engine.canon_vec(jnp.full(batch, -1, jnp.int32))

    # Warmup: compile prefill once.
    cache = engine.new_cache(batch)
    tok, _, cache = engine._prefill(
        engine.params, jnp.asarray(ids), cache, jnp.asarray(lens), sa,
    )
    _ = np.asarray(tok)
    del cache

    # TTFT: prefill + first sampled token on host, compiled path.
    ttft_ms = float("inf")
    for _i in range(3):
        cache = engine.new_cache(batch)
        t0 = time.perf_counter()
        tok, _, cache = engine._prefill(
            engine.params, jnp.asarray(ids), cache, jnp.asarray(lens), sa,
        )
        _ = np.asarray(tok)  # the token must actually reach the host
        ttft_ms = min(ttft_ms, (time.perf_counter() - t0) * 1e3)
        del cache

    # Decode throughput: marginal chained-chunk cost, steady state.
    step_ms, _ = _decode_slope_ms(engine, ids, lens, sa, eos, batch, n_slope)
    tok_per_sec_per_chip = batch / (step_ms * 1e-3) / n_dev

    # Sampled decode (BASELINE config #3): same slope with every row
    # running temperature + top-k + top-p through the static top-k bucket
    # path (ops/sampling.py) — must stay within a few % of greedy.
    sampled_ms = None
    if kv_dtype is None:
        gen_s = GenerationParams(
            max_new_tokens=DECODE, is_greedy=False, temperature=0.8,
            top_k=40, top_p=0.95, seed=1,
        )
        sa_s = engine._sample_args(gen_s, batch)
        sampled_ms, _ = _decode_slope_ms(
            engine, ids, lens, sa_s, eos, batch, n_slope
        )

    roofline = roofline_tokens_per_sec(cfg, param_bytes, batch, max_seq)
    # Independent cross-check: achieved HBM rate over the bytes a step in
    # the slope window actually streams — params + the mean bucketed KV
    # prefix (the full ring where no bucket applied).
    kv_token_bytes = 2 * cfg.n_layers * batch * (
        cfg.n_kv_heads * cfg.head_dim
    ) * (1 if kv_dtype == "int8" else 2)
    n1, n2 = n_slope
    per_step = []
    for k, tb in chunk_schedule(engine, int(lens.max()), n2, CHUNK):
        per_step += [tb if tb is not None else max_seq] * k
    mean_kv_bytes = kv_token_bytes * float(np.mean(per_step[n1:n2]))
    achieved_gbps = (param_bytes + mean_kv_bytes) / (step_ms * 1e-3) / 1e9
    return {
        "metric": "decode_tokens_per_sec_per_chip",
        "value": round(tok_per_sec_per_chip, 1),
        "unit": (
            f"tok/s/chip ({model} bf16, batch={batch}, "
            + (f"kv={kv_dtype}, " if kv_dtype else "")
            + f"ring={max_seq}, ttft_ms={ttft_ms:.0f}, "
            f"step_ms={step_ms:.2f}, "
            + (
                f"sampled_step_ms={sampled_ms:.2f}, "
                if sampled_ms is not None else ""
            )
            + f"achieved_hbm_gbps={achieved_gbps:.0f})"
        ),
        "vs_baseline": round(tok_per_sec_per_chip / roofline, 3),
    }


def run_paged_ab(model: str) -> dict:
    """Paged-vs-dense KV A/B (``python bench.py paged`` or BENCH_PAGED=1).

    Two halves, written to ``BENCH_PAGED.json``:

    1. **Per-layout decode cost** at identical batch/ring: marginal step
       time via the slope method (the paged engine runs identity tables —
       the dense-equivalent pool, so the delta IS the layout's indirection
       cost), tok/s/chip, and the achieved HBM rate over the bytes each
       step streams. The two runs must emit bit-identical tokens.
    2. **Capacity accounting** in a serving-shaped scenario (each request
       uses half its ring provision): the dense batcher provisions
       rows x max_seq and caps concurrency at its row count; the paged
       batcher gets the SAME KV byte budget as a block pool and must
       sustain 2x the concurrent rows with the same tokens — with KV HBM
       bytes per served token measured for both (provisioned bytes over
       tokens actually materialized).
    """
    from llmss_tpu.engine import DecodeEngine, GenerationParams
    from llmss_tpu.engine.scheduler import ContinuousBatcher
    from llmss_tpu.models.decoder import init_params
    from llmss_tpu.parallel import MeshPlan, make_mesh

    run_cfg = _MODEL_RUN[model]
    batch = BATCH or run_cfg["batch"]
    n_slope = run_cfg["n_slope"]
    bsz = int(os.environ.get("BENCH_BLOCK_SIZE", 16))
    max_seq = int(os.environ.get("BENCH_MAX_SEQ", 0)) or (
        PROMPT + n_slope[1]
    )
    max_seq = -(-max_seq // bsz) * bsz  # block-aligned ring

    n_dev = len(jax.devices())
    mesh = make_mesh(MeshPlan(tp=n_dev))
    cfg = flagship_cfg(model)
    params = init_params(cfg, mesh, jax.random.key(0))
    param_bytes = float(sum(
        np.prod(x.shape) for x in jax.tree.leaves(params)
    )) * 2
    kv_el_bytes = 1 if KV_DTYPE == "int8" else 2
    # KV bytes one row holds per token across all layers (k+v).
    row_tok_bytes = 2 * cfg.n_layers * cfg.n_kv_heads * cfg.head_dim * (
        kv_el_bytes
    )

    gen = GenerationParams(max_new_tokens=DECODE, is_greedy=True)
    rng = np.random.default_rng(0)
    prompts = [
        rng.integers(0, cfg.vocab_size, PROMPT).tolist() for _ in range(batch)
    ]

    result: dict = {"config": dict(
        model=model, batch=batch, ring=max_seq, block_size=bsz,
        prompt=PROMPT, decode=DECODE, kv_dtype=KV_DTYPE or "bf16",
        n_devices=n_dev, backend=jax.default_backend(),
    )}
    toks_ab = {}
    for layout in ("dense", "paged"):
        extra = (
            dict(kv_layout="paged", block_size=bsz)
            if layout == "paged" else {}
        )
        engine = DecodeEngine(
            cfg, params, mesh, max_seq_len=max_seq, kv_dtype=KV_DTYPE,
            **extra,
        )
        ids, lens = engine._pad_prompts(prompts)
        sa = engine._sample_args(gen, batch)
        eos = engine.canon_vec(jnp.full(batch, -1, jnp.int32))
        toks_ab[layout] = engine.generate(prompts, gen)
        step_ms, _ = _decode_slope_ms(
            engine, ids, lens, sa, eos, batch, n_slope
        )
        n1, n2 = n_slope
        per_step = []
        for k, tb in chunk_schedule(engine, int(lens.max()), n2, CHUNK):
            per_step += [tb if tb is not None else max_seq] * k
        mean_kv = batch * row_tok_bytes * float(np.mean(per_step[n1:n2]))
        result[layout] = {
            "step_ms": round(step_ms, 3),
            "tok_s_chip": round(batch / (step_ms * 1e-3) / n_dev, 1),
            "achieved_hbm_gbps": round(
                (param_bytes + mean_kv) / (step_ms * 1e-3) / 1e9, 2
            ),
        }
        del engine
    result["tokens_identical_engine"] = toks_ab["dense"] == toks_ab["paged"]
    result["provenance"] = bench_provenance()

    # -- capacity half: same KV byte budget, 2x the concurrent rows ------
    rows_d = batch
    mb = max_seq // bsz
    budget_blocks = rows_d * mb  # == the dense batcher's rows_d * max_seq
    g = min(DECODE, max_seq // 4)
    ps = max_seq // 2 - g  # prompt + new == half the ring provision
    short = [
        rng.integers(0, cfg.vocab_size, ps).tolist()
        for _ in range(2 * rows_d)
    ]
    gen_s = GenerationParams(max_new_tokens=g, is_greedy=True)

    def serve(engine, rows):
        bat = ContinuousBatcher(engine, rows=rows)
        results = {}
        for i, p in enumerate(short):
            bat.submit(
                p, gen_s, lambda t, i=i: results.__setitem__(i, t)
            )
        peak_rows = peak_blocks = 0
        while not bat.idle:
            bat.step()
            peak_rows = max(peak_rows, len(bat.active))
            if engine.kv_layout == "paged":
                peak_blocks = max(
                    peak_blocks, bat.allocator.blocks_in_use
                )
        return results, peak_rows, peak_blocks

    dense_eng = DecodeEngine(
        cfg, params, mesh, max_seq_len=max_seq, kv_dtype=KV_DTYPE,
    )
    paged_eng = DecodeEngine(
        cfg, params, mesh, max_seq_len=max_seq, kv_dtype=KV_DTYPE,
        kv_layout="paged", block_size=bsz, kv_blocks=budget_blocks,
    )
    out_d, rows_peak_d, _ = serve(dense_eng, rows_d)
    out_p, rows_peak_p, blocks_peak = serve(paged_eng, 2 * rows_d)
    served = 2 * rows_d * (ps + g)  # tokens materialized by the scenario
    result["serving"] = {
        "requests": 2 * rows_d,
        "tokens_per_request": ps + g,
        "kv_budget_bytes": budget_blocks * bsz * row_tok_bytes,
        "concurrent_rows_dense": rows_peak_d,
        "concurrent_rows_paged": rows_peak_p,
        "concurrency_ratio": round(rows_peak_p / rows_peak_d, 2),
        # dense serves the 2R requests in two R-row waves, each wave
        # provisioning rows_d full rings; paged provisions only the
        # blocks it actually mapped.
        "kv_hbm_bytes_per_served_token_dense": round(
            2 * rows_d * max_seq * row_tok_bytes / served, 1
        ),
        "kv_hbm_bytes_per_served_token_paged": round(
            blocks_peak * bsz * row_tok_bytes / served, 1
        ),
        "tokens_identical_serving": all(
            out_d[i] == out_p[i] for i in range(2 * rows_d)
        ),
    }
    with open(
        os.path.join(os.path.dirname(__file__), "BENCH_PAGED.json"), "w"
    ) as f:
        json.dump(result, f, indent=1)
    identical = (
        result["tokens_identical_engine"]
        and result["serving"]["tokens_identical_serving"]
    )
    return {
        "metric": "paged_vs_dense_decode",
        "value": result["paged"]["tok_s_chip"],
        "unit": (
            f"tok/s/chip paged ({model}, batch={batch}, ring={max_seq}, "
            f"bs={bsz}; dense={result['dense']['tok_s_chip']}, "
            f"rows {rows_peak_d}->{rows_peak_p} at equal KV budget, "
            f"identical_tokens={identical})"
        ),
        "vs_baseline": round(
            result["paged"]["tok_s_chip"]
            / max(result["dense"]["tok_s_chip"], 1e-9), 3
        ),
    }


def main():
    # Default sweep: the 1b2 series (bf16 — comparable across rounds —
    # and int8 KV: half the cache bytes, scales folded into the attention
    # contractions), then the HEADLINE LAST: Llama-2-7B dims, the
    # BASELINE.md north-star scale. BENCH_MODEL (optionally with
    # BENCH_KV_DTYPE) restricts to that single line; BENCH_KV_DTYPE alone
    # restricts to a single 1b2 line in that dtype.
    import sys

    from llmss_tpu.parallel import initialize_runtime

    require_tpu()
    initialize_runtime()
    if "paged" in sys.argv[1:] or os.environ.get("BENCH_PAGED"):
        print(
            json.dumps(run_paged_ab(MODEL or "1b2")), flush=True
        )
        return
    if MODEL:
        runs = [(MODEL, KV_DTYPE)]
    elif KV_DTYPE:
        runs = [("1b2", KV_DTYPE)]
    else:
        runs = [("1b2", None), ("1b2", "int8"), ("7b", None)]
    for model, kv in runs:
        result = run_model(model, kv)
        print(json.dumps(result), flush=True)
        # Free this model's params/executables before the next config —
        # 7B params alone are 13.5 GB of the 16 GB chip.
        jax.clear_caches()
        import gc

        gc.collect()


if __name__ == "__main__":
    main()
