"""The builder's readings for ``KeyeVL2`` on the device the process finds
(PR 46), beside what ``benchmark/lib/check.py`` runs (the engine's ``_prefill``
and one ``_decode``, at 4 x 4,096: the selection as a mask within the prompt,
then by token): after ``tools/qwen3_next_check.py``.

``--mixed``: the MIXED step, the only step program a cell with
``chunked_prefill`` times, at the cell's chunk and the published widths
against the float32 reference's logits (prompts longer than ``topk``, so that
every late query drops positions; no more rows feed at once than a step
works: ``models/decoder.py: feed_rows``; with ``--mixed-rows`` above that cap
the step runs as the cell's does: the feeding rows' selection laid over every
row's first query's). The row says which read the step was traced with
(``attn_read``: ``dsa.kernel`` on a TPU since PR 48) and how it scored the
indexer's pool (``index_read``: ``idx.kernel`` there since PR 52); ``--control``
adds the same step on the reference module's faulty parameters.

``--steps 16,32,64,128``: what one mixed step costs at each chunk, at the
cell's rows and ring with every row live at ``--context`` tokens and as many
rows feeding as a step works, and what a decode-only step costs: the table
the cell's ``chunked_prefill`` was chosen from.

``--select``: the selection alone, ``keep_topk``'s bisection beside a
``lax.top_k`` threshold at a decode step's and a mixed step's shapes, and the
tie rule on the device (equal scores: the earlier position).

    python3 tools/keye_vl2_check.py benchmark/configs/keye-vl-2.0-30b-a3b-1chip.json \\
        --seed 4100000009 --mixed --steps 16,32,64,128 --select \\
        --out chiprun_out/keye_vl2_check.jsonl
"""

from __future__ import annotations

import argparse
import functools
import json
import sys
import time
import types
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmark.lib import check, manifest  # noqa: E402
from benchmark.server import _unit_norm_scales  # noqa: E402
from llmss_tpu.engine import DecodeEngine, GenerationParams  # noqa: E402
from llmss_tpu.models import decoder  # noqa: E402
from llmss_tpu.models.registry import config_from_hf  # noqa: E402
from llmss_tpu.ops import sparse_attention as dsa  # noqa: E402
from llmss_tpu.parallel import MeshPlan, initialize_runtime, make_mesh  # noqa: E402
from tools.olmo_hybrid_check import mixed_logits  # noqa: E402


def profiled(run, say, what, n_steps):
    """``run()`` once more under ``jax.profiler``, reduced by the
    benchmark's own ``lib/xplane.py``: the 25 ops with most device self
    time, in milliseconds a step."""
    import shutil
    import tempfile

    from benchmark.lib import xplane

    d = tempfile.mkdtemp(prefix="keye-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level, opts.host_tracer_level = 0, 1
    jax.profiler.start_trace(d, profiler_options=opts)
    t0 = time.perf_counter()
    run()
    dur = time.perf_counter() - t0
    jax.profiler.stop_trace()
    out = xplane.reduce(xplane.read(xplane.find_xplane(d)), dur)
    shutil.rmtree(d, ignore_errors=True)
    say({"what": f"profile of {what}", "busy_ms_per_step":
         out.get("busy_s", 0) / n_steps * 1e3,
         "ops_ms_per_step": [[name[:200], s / n_steps * 1e3]
                             for name, s in out.get("ops", [])[:25]]})


def reads(engine, cache, chunk):
    """How a step of ``chunk`` tokens a row reads the pools, as traced here."""
    return {f.__name__: f(engine.cfg, cache, engine.mesh, chunk)
            for f in (decoder.attn_read, decoder.index_read)}


def step_times(engine, rows, chunks, context, say, n_steps=4, reps=3,
               profile=False):
    """Milliseconds a step of the mixed group at each chunk (``rows`` live
    rows at ``context`` tokens, as many of them feeding a whole chunk as a
    step works) and of the decode group, on the pool the cell holds."""
    eng, R = engine, rows
    sa = eng._sample_args(GenerationParams(is_greedy=True), R)
    held = np.where(np.arange(eng.max_seq_len) < context,
                    np.arange(eng.max_seq_len), -1).astype(np.int32)

    def fresh():
        cache = eng.new_paged_cache(R)
        cache = cache._replace(positions=jax.device_put(
            jnp.broadcast_to(jnp.asarray(held), (R, eng.max_seq_len)),
            cache.positions.sharding))
        return (eng.canon_vec(jnp.ones(R, jnp.int32)), eng.canon_cache(cache),
                eng.canon_vec(jnp.full(R, context, jnp.int32)))

    def timed(run, what):
        tok, cache, pos = fresh()
        out = run(tok, cache, pos)  # compiles
        jax.block_until_ready(out[0])
        best = []
        for _ in range(reps):
            tok, cache, pos = (eng.canon_vec(out[1]), eng.canon_cache(out[2]),
                               eng.canon_vec(jnp.full(R, context, jnp.int32)))
            t0 = time.perf_counter()
            out = run(tok, cache, pos)
            jax.block_until_ready(out[0])
            best.append((time.perf_counter() - t0) / n_steps * 1e3)
        if profile:
            def again():
                o = run(eng.canon_vec(out[1]), eng.canon_cache(out[2]),
                        eng.canon_vec(jnp.full(R, context, jnp.int32)))
                jax.block_until_ready(o[0])
                return o

            profiled(again, say, what, n_steps)
        del out
        return best

    live, eos = jnp.zeros(R, bool), jnp.full(R, -1, jnp.int32)
    ms = timed(lambda tok, cache, pos: eng._decode_group(
        eng.params, tok, cache, pos, sa, live, eos,
        n_chunks=1, n_steps=n_steps, t_bucket=None), "the decode group")
    say({"what": "decode group", "rows": R, "context": context,
         **reads(eng, fresh()[1], 1), "ms_per_step": ms})
    for C in chunks:
        cap = decoder.feed_rows(eng.cfg, fresh()[1], C) or R
        q = np.ones((n_steps, R), np.int32)
        q[:, :cap] = C
        feed = q > 1
        ms = timed(lambda tok, cache, pos: eng._ragged_group(
            eng.params, tok, cache, pos, sa, live, eos,
            jnp.ones((n_steps, R, C), jnp.int32), jnp.asarray(q),
            jnp.asarray(feed), jnp.asarray(~feed)),
            f"the mixed group at {C}")
        say({"what": "mixed group", "rows": R, "context": context, "chunk": C,
             **reads(eng, fresh()[1], C), "rows_feeding": cap, "tokens_fed_a_step": cap * C,
             "ms_per_step": ms,
             "ms_per_fed_token": min(ms) / (cap * C)})


def selection(say, topk):
    """``keep_topk`` beside a ``lax.top_k`` threshold, and the tie rule."""
    def by_top_k(scores, k):
        thr = jax.lax.top_k(scores, k)[0][..., -1:]
        above, tie = scores > thr, scores == thr
        room = k - jnp.sum(above, -1, keepdims=True, dtype=jnp.int32)
        keep = above | (tie & (jnp.cumsum(tie, -1, dtype=jnp.int32) <= room))
        return keep & (scores > -jnp.inf)

    rng = np.random.default_rng(0)
    for name, shape in (("decode", (32, 16896 + 1)), ("mixed", (3, 64, 16960))):
        x = rng.standard_normal(shape).astype(np.float32)
        x[rng.random(shape) < 0.4] = -np.inf
        x = jnp.asarray(np.round(x, 2))  # a few hundred values: ties abound
        row = {"what": "selection", "shape": list(shape), "topk": topk}
        keeps = {}
        for impl, fn in (("bisect", dsa.keep_topk), ("top_k", by_top_k)):
            f = jax.jit(functools.partial(fn, k=topk))
            t0 = time.perf_counter()
            keeps[impl] = jax.block_until_ready(f(x))
            row[f"{impl}_first_call_s"] = time.perf_counter() - t0
            t0 = time.perf_counter()
            for _ in range(10):
                out = f(x)
            jax.block_until_ready(out)
            row[f"{impl}_ms"] = (time.perf_counter() - t0) * 100
        xn = np.asarray(x)
        order = np.argsort(-xn, axis=-1, kind="stable")
        rank = np.argsort(order, axis=-1, kind="stable")
        want = (rank < topk) & np.isfinite(xn)
        row["bisect_is_the_stable_rank_rule"] = bool(
            (np.asarray(keeps["bisect"]) == want).all())
        row["top_k_agrees"] = bool(
            (np.asarray(keeps["top_k"]) == want).all())
        say({**row, "case": name})


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("config")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chunk", type=int, default=64)
    ap.add_argument("--mixed", action="store_true")
    ap.add_argument("--mixed-lens", type=int, nargs=2, default=(2200, 3000))
    ap.add_argument("--mixed-rows", type=int, default=3)
    ap.add_argument("--control", action="store_true",
                    help="with --mixed: the same step on the reference "
                    "module's faulty parameters, which must miss the limit")
    ap.add_argument("--steps", default="")
    ap.add_argument("--context", type=int, default=8192)
    ap.add_argument("--select", action="store_true")
    ap.add_argument("--profile", action="store_true",
                    help="with --steps: the ops of each timed program")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    conf = json.loads(Path(args.config).read_text())
    hf = {k: v for k, v in conf.items() if k not in manifest.HARNESS_KEYS}
    initialize_runtime()  # the persistent compile cache, as the server has it
    cfg = config_from_hf(types.SimpleNamespace(**hf), dtype=conf["dtype"])
    mesh = make_mesh(MeshPlan(tp=1), devices=jax.devices()[:1])
    params = _unit_norm_scales(decoder.init_params(
        cfg, mesh, jax.random.key(args.seed)))
    out = open(args.out, "a") if args.out else None

    def say(row):
        line = json.dumps(row)
        print("KEYE_VL2_CHECK", line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    say({"device": jax.devices()[0].device_kind, "dtype": conf["dtype"],
         "seed": args.seed, "chunk": args.chunk})
    engine = DecodeEngine(cfg, params, mesh, kv_layout="paged",
                          max_seq_len=conf["serve"]["max_seq_len"])
    if args.select:
        selection(say, cfg.indexer.topk)
    if args.steps:
        step_times(engine, conf["serve"]["rows"],
                   [int(c) for c in args.steps.split(",")], args.context, say,
                   profile=args.profile)
    if args.mixed:
        ref = check.load_reference(hf["model_type"])
        tol = check.LOGITS_TOL[conf["dtype"]]
        prompts = check.check_prompts(
            hf["vocab_size"], args.seed, *args.mixed_lens, n=args.mixed_rows)
        shape = types.SimpleNamespace(  # all that ``feed_rows`` asks a cache
            block_tables=np.zeros((len(prompts), 1)),
            max_len=engine.max_seq_len)
        cap = decoder.feed_rows(cfg, shape, args.chunk)
        pre, dec, first = mixed_logits(
            engine, params, prompts, args.chunk, cap=cap)
        want = check.reference_logits(ref, hf, params, prompts, first)
        errs = [check.logits_error(pre, want[0]),
                check.logits_error(dec, want[1])]
        row = {}
        if args.control:  # the reference module's one fault must FAIL
            fault, faulty = ref.control(params)
            lost = mixed_logits(engine, faulty, prompts, args.chunk, cap=cap)
            row = {"control": check.logits_error(lost[0], want[0]),
                   "control_fault": fault}
        say({"what": "program", "path": "mixed", "chunk": args.chunk,
             **reads(engine, engine.new_paged_cache(1), args.chunk),
             "rows_feeding_at_once": cap or len(prompts), **row,
             "prompt_lens": [len(p) for p in prompts], "logits": errs,
             "rms": [check.logits_error(pre, want[0], rms=True),
                     check.logits_error(dec, want[1], rms=True)],
             "tolerance": tol, "correct": bool(max(errs) < tol)})


if __name__ == "__main__":
    main()
