"""On-chip decode-step profiler: op-level breakdown + ablation timings.

Writes PROFILE.md (not kept in the tree; the round-5 copy is at commit
e57f952): where each microsecond of the decode step goes, measured two
independent ways —

1. **xprof op table**: a ``jax.profiler`` trace of the steady-state fused
   decode scan, parsed into per-op self-time via the xprof converter
   (no TensorBoard UI needed).
2. **Ablation timings**: variants of the decode step with one component
   removed (lm-head, sampling, cache scatter, attention) compiled and timed
   separately; the delta attributes wall time to the removed component.

Timing methodology — every call carries a constant overhead (dispatch +
host fetch round-trip; not measured on the current machine). Every timing
here therefore (a) forces completion with a host fetch of a scalar
reduction and
(b) uses the **slope method**: run the fused scan at two step counts and
take (t(N2) - t(N1)) / (N2 - N1), which cancels all constant overhead and
yields the true marginal cost per decode step.

Run on the chip: ``python tools/profile_decode.py`` (fails without one).
Writes ``PROFILE.md`` (top-op table + ablations) and prints a JSON summary.
"""

from __future__ import annotations

import glob
import json
import os
import sys
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import (  # noqa: E402
    _MODEL_RUN, DECODE, PROMPT, flagship_cfg, hbm_gbps, require_tpu,
    slope_time,
)

MODEL = os.environ.get("PROFILE_MODEL", "1b2")
BATCH = int(os.environ.get("BENCH_BATCH", 0)) or _MODEL_RUN[MODEL]["batch"]

TRACE_DIR = os.environ.get("PROFILE_TRACE_DIR", "/tmp/llmss_profile")


def host_overhead_breakdown(metrics) -> dict:
    """Per-group host-overhead receipts from an ``EngineMetrics``: how
    much host time each grouped-decode dispatch costs (enqueue + canon
    rewraps), what the ONE packed device→host fetch per group blocks for,
    and what the host-side bookkeeping (token accounting, stream flushes)
    adds — plus the sync/dispatch counters that say how often the host
    touches the device at all. Shared by bench_serve.py and
    tools/bench_spec.py so both bench JSONs carry the same breakdown."""
    ho = metrics.to_dict()["host_overhead"]
    return {
        "host_syncs": ho["host_syncs"],
        "groups_dispatched": ho["groups_dispatched"],
        "dispatch_ms": {k: ho["dispatch"][k]
                        for k in ("mean_ms", "p50_ms", "p95_ms")},
        "fetch_ms": {k: ho["fetch"][k]
                     for k in ("mean_ms", "p50_ms", "p95_ms")},
        "callback_ms": {k: ho["callback"][k]
                        for k in ("mean_ms", "p50_ms", "p95_ms")},
    }


def _build():
    from llmss_tpu.engine import DecodeEngine, GenerationParams
    from llmss_tpu.models.decoder import init_params
    from llmss_tpu.parallel import MeshPlan, make_mesh

    n_dev = len(jax.devices())
    mesh = make_mesh(MeshPlan(tp=n_dev))
    cfg = flagship_cfg(MODEL)
    params = init_params(cfg, mesh, jax.random.key(0))
    engine = DecodeEngine(cfg, params, mesh, max_seq_len=PROMPT + DECODE)
    return cfg, params, mesh, engine


def _prompts(cfg):
    rng = np.random.default_rng(0)
    return [
        rng.integers(0, cfg.vocab_size, PROMPT).tolist() for _ in range(BATCH)
    ]


# -- ablation variants --------------------------------------------------------


def _step_variant(cfg, mesh, variant: str):
    """A fused N-step decode scan with one component removed."""
    from llmss_tpu.models.decoder import forward
    from llmss_tpu.ops.sampling import sample

    def body(params, sample_args, carry, _):
        tokens, cache, cur_pos = carry
        positions = cur_pos[:, None]
        slots = positions % cache.max_len
        logits, cache = forward(
            cfg, params, tokens[:, None], positions, cache, slots,
            last_only=True, mesh=mesh,
            _ablate=variant if variant not in ("full", "no_sample") else None,
        )
        if variant in ("no_sample", "no_head"):
            # A full-logits reduction keeps every vocab column live (a
            # single-element read would let XLA fold the slice into the head
            # matmul, silently ablating it) without paying argmax-over-V;
            # no_head additionally skips the vocab projection itself.
            # head-only cost = t(no_sample) - t(no_head).
            tok = jnp.sum(logits[:, 0], axis=-1).astype(
                jnp.int32
            ) % cfg.vocab_size
        else:
            tok = sample(logits[:, 0], counters=cur_pos + 1, **sample_args)
        return (tok, cache, cur_pos + 1), tok

    def many(params, tokens, cache, cur_pos, sample_args, n_steps):
        carry, toks = jax.lax.scan(
            partial(body, params, sample_args), (tokens, cache, cur_pos),
            None, length=n_steps,
        )
        return toks, carry[1]

    return jax.jit(many, donate_argnums=(2,), static_argnames=("n_steps",))


def run_ablations(cfg, mesh, engine, prompts):
    """Time decode-scan variants; each removal's delta vs full = its cost."""
    from llmss_tpu.engine import GenerationParams

    gen = GenerationParams(max_new_tokens=8, is_greedy=True)
    sa = engine._sample_args(gen, BATCH)
    ids, lens = engine._pad_prompts(prompts)

    results = {}
    for variant in ("full", "no_sample", "no_head", "no_scatter", "no_attn"):
        stepper = _step_variant(cfg, mesh, variant)

        def prepare(n):
            cache = engine.new_cache(BATCH)
            tok, _, cache = engine._prefill(
                engine.params, jnp.asarray(ids), cache, jnp.asarray(lens),
                sa,
            )
            cur = jnp.asarray(lens)
            state = {"cache": cache}

            def run():
                toks, state["cache"] = stepper(
                    engine.params, tok, state["cache"], cur, sa, n
                )
                _ = float(jnp.sum(toks))  # forced completion

            return run

        slope_ms, const_ms = slope_time(prepare)
        results[variant] = {"ms_per_step": slope_ms, "const_ms": const_ms}
    return results


# -- xprof trace --------------------------------------------------------------


def capture_trace(engine, prompts):
    from llmss_tpu.engine import GenerationParams

    gen = GenerationParams(max_new_tokens=DECODE, is_greedy=True)
    engine.generate_fused(prompts, gen)  # warm/compile
    os.makedirs(TRACE_DIR, exist_ok=True)
    jax.profiler.start_trace(TRACE_DIR)
    engine.generate_fused(prompts, gen)
    jax.profiler.stop_trace()


def parse_trace() -> list[dict] | None:
    """Extract per-op self-time from the xplane via the xprof converter."""
    paths = sorted(
        glob.glob(os.path.join(TRACE_DIR, "**", "*.xplane.pb"),
                  recursive=True),
        key=os.path.getmtime,
    )
    if not paths:
        return None
    xspace = [paths[-1]]
    data = None
    for modname in ("xprof.convert", "tensorboard_plugin_profile.convert"):
        try:
            import importlib

            raw_to_tool_data = importlib.import_module(
                f"{modname}.raw_to_tool_data"
            )
            data, _ = raw_to_tool_data.xspace_to_tool_data(
                xspace, "framework_op_stats", {}
            )
            break
        except Exception as e:  # noqa: BLE001 — try the next converter
            print(f"[profile] {modname} failed: {e!r}", file=sys.stderr)
    if data is None:
        return None
    if isinstance(data, bytes):
        data = data.decode("utf-8", "replace")
    try:
        tbl = json.loads(data)
    except json.JSONDecodeError:
        return None
    if isinstance(tbl, list):
        tbl = tbl[0]
    cols = [c.get("label", c.get("id", "")) for c in tbl.get("cols", [])]
    rows = []
    for r in tbl.get("rows", []):
        vals = [c.get("v") for c in r.get("c", [])]
        rows.append(dict(zip(cols, vals)))
    return rows


def _fmt_op_table(ops: list[dict], n_top: int = 15) -> tuple[str, float]:
    dev = [r for r in ops if r.get("Host/device") == "Device"]
    dev.sort(key=lambda r: -float(r.get("Total self-time (us)", 0) or 0))
    total_ms = sum(
        float(r.get("Total self-time (us)", 0) or 0) for r in dev
    ) / 1e3
    lines = [
        "| self-time (ms) | occurrences | GB/s | bound by | op |",
        "|---|---|---|---|---|",
    ]
    for r in dev[:n_top]:
        t = float(r.get("Total self-time (us)", 0) or 0) / 1e3
        occ = int(float(r.get("#Occurrences", 0) or 0))
        bw = float(r.get("Measured Memory BW (GBytes/Sec)", 0) or 0)
        name = str(r.get("Operation Name", ""))
        # Strip the jit wrapper chain for readability.
        name = name.replace("jit(<unknown>)/", "").replace(
            "while/body/closed_call/", ""
        )
        lines.append(
            f"| {t:.2f} | {occ} | {bw:.0f} | "
            f"{r.get('Bound by', '')} | `{name[:90]}` |"
        )
    return "\n".join(lines), total_ms


def write_profile_md(cfg, param_bytes, ablations, ops, full_ms):
    deltas = {
        k: ablations["full"]["ms_per_step"] - v["ms_per_step"]
        for k, v in ablations.items() if k != "full"
    }
    head_only_ms = (
        ablations["no_sample"]["ms_per_step"]
        - ablations["no_head"]["ms_per_step"]
    )
    abl_lines = [
        "| variant | ms/step (marginal) | delta vs full (= component cost) |",
        "|---|---|---|",
        f"| full | {ablations['full']['ms_per_step']:.3f} | — |",
    ]
    for k, v in ablations.items():
        if k == "full":
            continue
        abl_lines.append(
            f"| {k} | {v['ms_per_step']:.3f} | {deltas[k]:+.3f} |"
        )

    # Stream floor from the actual run configuration (env-overridable).
    max_seq = PROMPT + DECODE
    kv_buffer_gb = 2 * cfg.n_layers * BATCH * max_seq * (
        cfg.n_kv_heads * cfg.head_dim * 2
    ) / 1e9
    param_gb = param_bytes / 1e9
    peak_gbps = hbm_gbps()
    param_floor_ms = param_gb / peak_gbps * 1e3
    kv_floor_ms = kv_buffer_gb / peak_gbps * 1e3
    floor_ms = param_floor_ms + kv_floor_ms

    op_section = "(xprof trace parse unavailable on this host)"
    if ops:
        tbl, total_ms = _fmt_op_table(ops)
        op_section = (
            f"Total device self-time in trace: {total_ms:.1f} ms "
            f"(one `generate_fused` call: prefill + {DECODE}-step fused "
            f"decode + host fetches).\n\n{tbl}"
        )

    md = f"""# Decode-step profile (v5e single chip)

Flagship model: 1.2B llama-class bf16, batch={BATCH}, prompt={PROMPT},
cache={PROMPT + DECODE}. Generated by `tools/profile_decode.py` on real
hardware; see its docstring for the timing methodology (slope method —
marginal cost per step, constant dispatch/fetch overhead cancelled).

## Steady-state decode step: {full_ms:.2f} ms  (batch {BATCH} → \
{BATCH / full_ms * 1e3:.0f} tok/s/chip)

Stream floor at {peak_gbps:.0f} GB/s: params {param_gb:.2f} GB →
{param_floor_ms:.2f} ms; full KV buffer read {kv_buffer_gb:.2f} GB →
{kv_floor_ms:.2f} ms; total ≈ {floor_ms:.2f} ms/step. Measured
{full_ms:.2f} ms = {floor_ms / full_ms * 100:.0f}% of the floor.

## Ablations (slope method, each variant removes one component)

{chr(10).join(abl_lines)}

`no_attn` removes the cache-read einsums and softmax; `no_scatter` removes
the post-scan KV cache write; `no_head` removes the vocab projection *and*
sampling (its delta is head+sampling combined — head-only cost is
t(no_sample) − t(no_head) = {head_only_ms:.3f} ms); `no_sample` replaces
argmax/top-k/top-p with a full-logits-reduction token derivation.

## Top device ops (xprof, one traced `generate_fused` call)

{op_section}

## Reading

- The per-layer weight `dot_general`s stream at ~680 GB/s (83% of peak):
  the scan's weight slices are prefetched into alternate memory by XLA
  (the `S(1)` copies in the HLO) and are near the practical ceiling.
- The attention-over-cache cost (`no_attn` delta) is essentially the
  HBM stream cost of the KV bytes read: the per-layer cache
  `dynamic-slice` copies land in alternate memory (`S(1)` in the HLO —
  on-chip), so the only HBM traffic is the read itself. Round 4 measured
  alternative layouts exhaustively on-chip (head-major, K-transposed —
  both net slower, see git history); round 5 re-measured the mask-variant
  space (`tools/exp_mask.py`: additive penalty / inline iota / post-exp
  multiplicative / no mask at all are within noise of each other — the
  r4 "dynamic mask costs 0.6 ms" diagnosis no longer reproduces) and
  concluded the full-ring step simply runs at the chip's practical
  transfer efficiency (~690 GB/s ≈ 84% of nominal, the same rate the
  weight stream achieves).
- The remaining lever was therefore to read FEWER bytes: the engine's
  **bucketed cache reads** (round 5) slice each layer's KV fetch to the
  ring prefix covering live context via a hand-emitted
  `lax.dynamic_slice` — the serving path's decode cost follows occupancy,
  not ring size (`bench.py` measures that path; the ablations here run
  the full-ring step, the worst case). Emitting the small slice directly
  matters: XLA does not fold a static T-slice into the scan's per-layer
  slice (pre-scan slicing materializes a fresh operand, +1.3 ms/step;
  in-body slicing adds an HBM round-trip, +0.3 ms/step).
- The post-scan deferred KV scatter now fuses to ~0 marginal cost (the
  `no_scatter` delta); round 3 measured it at 0.08 ms.
- IDLE in the trace is the host-side gaps of `generate_fused` (dispatch
  and fetch latency per call), not device work — the slope method cancels
  it, `bench.py` measures the same way; a served request pays it.
"""
    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "PROFILE.md"), "w") as f:
        f.write(md)


def main():
    require_tpu()
    cfg, params, mesh, engine = _build()
    prompts = _prompts(cfg)
    param_bytes = sum(
        np.prod(x.shape) * x.dtype.itemsize
        for x in jax.tree.leaves(params)
    )

    ablations = run_ablations(cfg, mesh, engine, prompts)
    capture_trace(engine, prompts)
    ops = parse_trace()

    full = ablations["full"]["ms_per_step"]
    write_profile_md(cfg, param_bytes, ablations, ops, full)
    print(json.dumps({
        "ablations_ms_per_step": {
            k: round(v["ms_per_step"], 3) for k, v in ablations.items()
        },
        "deltas_ms": {
            k: round(full - v["ms_per_step"], 3)
            for k, v in ablations.items() if k != "full"
        },
        "tok_per_sec_at_full": round(BATCH / full * 1e3, 1),
        "n_trace_ops": len(ops) if ops else 0,
        # Accumulated over the ablation runs above — what the host paid
        # per grouped dispatch while the device did the work.
        "host_overhead": host_overhead_breakdown(engine.metrics),
    }))
    if ops:
        with open("/tmp/llmss_ops.json", "w") as f:
            json.dump(ops, f, indent=1)
        print("op table -> /tmp/llmss_ops.json")


if __name__ == "__main__":
    main()
