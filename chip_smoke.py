#!/usr/bin/env python3
"""chip_smoke.py — serve one full-size model on the TPU through the normal path.

The quickest proof that the system still starts on the chip. One process:

    HF-format sharded safetensors on disk (synthesized from --seed)
      → initialize_runtime() → make_mesh → load_model (native gather)
      → DecodeEngine(kv_layout="paged") → ContinuousWorker(group_chunks>1)
      → prewarm() → ProducerServer on a localhost port
      → HTTP POST /generate, plain and "stream": true

built as ``llmss-consumer`` builds it (serve/consumer.py ``main``), with two
differences this installation forces: ``InProcBroker`` for ``RedisBroker``
(no Redis here) and ``tokenizer=None`` with ``token_ids`` requests (no
network, so no tokenizer files). Then a second window on the same engine
with chunked prefill (the ragged mixed-batch program), and a logits
comparison against HF transformers' float32 forward of the same checkpoint.

Run it with no arguments on a machine with one chip. ``--chips 4`` runs only
the tensor-parallel path (the same checkpoint served with ``MeshPlan(tp=4)``)
and what it is compared with (the same prompts' logits on a one-device mesh).

There is no CPU or rehearsal mode: without a TPU the first check exits
non-zero. The phases are functions of (config, mesh) so that
tests/test_chip_smoke.py runs them at a tiny size on the CPU mesh.

Everything informative is printed on earlier lines; the LAST line of stdout
is ``{"ok": true, "device": {"platform": ..., "kind": ..., "count": N}}``.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import dataclasses
import hashlib
import json
import math
import os
import tempfile
import threading
import time
import urllib.request
from pathlib import Path

# -- the model ----------------------------------------------------------------

# bigcode/starcoderbase-1b, the reference's second family (GPT-BigCode) at its
# published config.json: every width and the full depth. ≈1.14 B parameters,
# ≈2.27 GB in bf16.
STARCODERBASE_1B = dict(
    vocab_size=49152, n_positions=8192, n_embd=2048, n_layer=24, n_head=16,
    n_inner=8192, multi_query=True, activation_function="gelu_pytorch_tanh",
    layer_norm_epsilon=1e-5,
)
# No network here: what could not be checked against the hub is listed, and
# printed by the run.
ASSUMED = (
    "activation_function=gelu_pytorch_tanh and layer_norm_epsilon=1e-5 "
    "(GPTBigCodeConfig defaults; the issue says 'gelu MLP')",
    "tied input/output embeddings (GPTBigCodeConfig default)",
)

# -- how the smoke serves it --------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """One serving envelope: engine, worker and traffic of a window."""

    max_seq_len: int = 2048
    rows: int = 8
    chunk_steps: int = 8
    group_chunks: int = 4
    chunked_prefill: int = 32  # tokens per step, second window only
    # Prompt lengths are drawn from (min_prompt, max_prompt]; the engine
    # buckets them to powers of two, so these span the buckets 64 and 128.
    min_prompt: int = 33
    max_prompt: int = 128
    n_requests: int = 16
    max_new_tokens: int = 64
    stream_every: int = 4  # a quarter of the requests over SSE
    oracle_prompts: int = 4

    def buckets(self) -> list[int]:
        from llmss_tpu.engine.engine import _bucket

        return sorted({
            _bucket(n, self.max_seq_len)
            for n in (self.min_prompt, self.max_prompt)
        })


# The four-chip run pays every compile four times over, so it serves a
# narrower envelope: fewer rows, one prompt bucket, a short context (the
# decode programs are compiled once per cache-read bucket, and the number of
# buckets follows max_seq_len).
TP_SERVE = ServeConfig(
    max_seq_len=256, rows=4, chunk_steps=4, group_chunks=2,
    min_prompt=33, max_prompt=64, n_requests=6, max_new_tokens=32,
)

# Logits tolerance, in units of the reference logits' standard deviation
# over the vocabulary (max |engine − reference| / std(reference), worst
# prompt). The reference computes in float32 from the same bf16-stored
# weights, so the engine's whole error is the rounding of its own compute
# dtype. float32: accumulation-order noise, measured near 1e-6. bfloat16: 8
# bits of mantissa, rounded once per matmul output and residual add — two
# dozen layers of relative 2^-9 errors adding in quadrature, and the worst
# of 49,152 vocabulary entries is taken; measured on the v5e at 0.055
# (prefill) and 0.063 (cached decode step). Both bounds sit below what a
# wrong model does: a float32 engine that computes in bfloat16 misses the
# float32 bound by an order of magnitude, and dropping one projection's
# bias (the negative control every run makes; N(0, 0.02) values) misses
# either.
LOGITS_TOL = {"float32": 2e-3, "bfloat16": 0.1}


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


# -- phase 0: the device ------------------------------------------------------


def require_tpu(n_chips: int) -> dict:
    """Exit non-zero unless JAX's default backend is a TPU with exactly
    ``n_chips`` devices. Runs before any other work."""
    import jax

    backend = jax.default_backend()
    if backend != "tpu":
        raise SystemExit(
            f"chip_smoke: JAX's default backend is {backend!r}, not 'tpu' "
            "— this script has no CPU mode"
        )
    devs = jax.devices()
    if len(devs) != n_chips:
        raise SystemExit(
            f"chip_smoke: asked for {n_chips} chip(s), JAX found {len(devs)}"
        )
    import importlib.metadata as md

    import jaxlib

    device = {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs),
    }
    log(f"device {json.dumps(device)} jax={jax.__version__} "
        f"jaxlib={jaxlib.__version__} libtpu={md.version('libtpu')}")
    return device


class CompileCounter:
    """Counts JAX's own monitoring events: backend compiles (every
    executable built or fetched from the persistent cache) and the
    persistent cache's hits and writes."""

    def __init__(self):
        import jax

        self.compiles = self.cache_hits = self.cache_writes = 0
        self.compile_s = 0.0
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(self._on_dur)

    def _on_event(self, event: str, **kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_writes += 1

    def _on_dur(self, event: str, duration: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += duration


# -- phase 1: the checkpoint --------------------------------------------------


def synthesize_checkpoint(hf_kwargs: dict, seed: int, root: Path) -> Path:
    """HF-format sharded safetensors checkpoint of ``GPTBigCodeForCausalLM``
    with seeded random weights, under a fixed name made from the config and
    the seed; reused when complete. Matrices and biases are N(0, 0.02) (HF's
    initializer_range; biases too, so that a dropped bias shows), LayerNorm
    scales 1 + N(0, 0.02). Stored in bf16, as the hub stores it."""
    import torch
    from transformers import GPTBigCodeConfig, GPTBigCodeForCausalLM

    tag = hashlib.sha256(
        json.dumps(hf_kwargs, sort_keys=True).encode()
    ).hexdigest()[:12]
    path = root / f"gpt_bigcode-{tag}-seed{seed}"
    done = path / ".complete"
    if done.exists():
        log(f"checkpoint reused: {path}")
        return path
    t0 = time.monotonic()
    cfg = GPTBigCodeConfig(**hf_kwargs)
    with torch.device("meta"):
        model = GPTBigCodeForCausalLM(cfg)
    model = model.to_empty(device="cpu").to(torch.bfloat16)
    gen = torch.Generator().manual_seed(seed)
    for name, p in model.named_parameters():
        p.data.normal_(0.0, 0.02, generator=gen)
        if ".ln_" in name and name.endswith(".weight"):
            p.data.add_(1.0)
    model.tie_weights()
    model.save_pretrained(path, safe_serialization=True, max_shard_size="500MB")
    done.touch()
    n_files = len(list(path.glob("*.safetensors")))
    n_params = sum(p.numel() for p in model.parameters())
    log(f"checkpoint written: {path} ({n_params / 1e9:.3f} B params, "
        f"{n_files} safetensors file(s), {time.monotonic() - t0:.1f} s)")
    return path


# -- phase 2: load ------------------------------------------------------------


def load(ckpt: Path, mesh, dtype: str):
    """``load_model`` through the native gather; a failed native build is an
    error here, not a slower reader."""
    import jax

    from llmss_tpu.models.registry import load_model
    from llmss_tpu.weights import native_st

    native_st.require_native()
    t0 = time.monotonic()
    cfg, params = load_model(str(ckpt), mesh, dtype=dtype)
    jax.block_until_ready(params)
    n_bytes = sum(x.nbytes for x in jax.tree.leaves(params))
    log(f"loaded {n_bytes / 1e9:.3f} GB of parameters in "
        f"{time.monotonic() - t0:.1f} s over {mesh.devices.size} device(s) "
        "(reader: native st_gather)")
    return cfg, params


# -- phase 3: the serving stack -----------------------------------------------


class Stack:
    """Engine → ContinuousWorker → InProcBroker → ProducerServer, wired as
    ``llmss-consumer``/``llmss-producer`` wire them, in one process."""

    def __init__(
        self, engine, serve: ServeConfig, *, chunked_prefill: int | None,
        counter: CompileCounter,
    ):
        from llmss_tpu.analysis.compile_guard import CompileGuard
        from llmss_tpu.serve.broker import InProcBroker
        from llmss_tpu.serve.consumer import ContinuousWorker
        from llmss_tpu.serve.producer import ProducerServer
        from llmss_tpu.utils.metrics import EngineMetrics

        self.engine, self.serve, self.counter = engine, serve, counter
        # Fresh counters per stack: /metrics then reads exactly what this
        # stack's window sent.
        engine.metrics = EngineMetrics()
        self.broker = InProcBroker()
        self.worker = ContinuousWorker(
            engine, self.broker, tokenizer=None, rows=serve.rows,
            chunk_steps=serve.chunk_steps, group_chunks=serve.group_chunks,
            chunked_prefill=chunked_prefill,
        )
        batcher = self.worker.batcher
        pool = batcher.cache.k
        log(f"paged pool: {pool.shape[1]} blocks x {engine.block_size} "
            f"slots, k+v {2 * pool.nbytes / 1e6:.1f} MB, rows={serve.rows}, "
            f"max_seq_len={engine.max_seq_len}, chunked_prefill="
            f"{chunked_prefill}")
        c0, s0, t0 = counter.compiles, counter.compile_s, time.monotonic()
        n_exec = self.worker.prewarm(seq_buckets=serve.buckets())
        log(f"prewarm: {n_exec} executables (buckets {serve.buckets()}) in "
            f"{time.monotonic() - t0:.1f} s; {counter.compiles - c0} backend "
            f"compiles, {counter.compile_s - s0:.1f} s in the compiler")
        # Every jitted program of the engine and of the batcher: none may
        # grow a new cache entry inside a request window.
        self.guard = CompileGuard({
            **{f"engine.{k}": v for k, v in vars(engine).items()},
            **{f"batcher.{k}": v for k, v in vars(batcher).items()},
        })
        self.server = ProducerServer(self.broker, host="127.0.0.1", port=0)
        self.server.start()
        self.base = f"http://127.0.0.1:{self.server.port}"
        self.worker_error: BaseException | None = None
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def _run(self) -> None:
        try:
            self.worker.run_forever(self._stop)
        except BaseException as e:  # noqa: BLE001 — re-raised by the window
            self.worker_error = e

    def close(self) -> None:
        self._stop.set()
        self._thread.join(timeout=60)
        self.server.stop()
        if self._thread.is_alive():
            raise RuntimeError("worker thread did not stop")
        if self.worker_error is not None:
            raise self.worker_error

    def get(self, path: str) -> tuple[int, dict]:
        with urllib.request.urlopen(self.base + path, timeout=30) as r:
            return r.status, json.loads(r.read())


def make_requests(serve: ServeConfig, vocab_size: int, seed: int) -> list[dict]:
    import numpy as np

    rng = np.random.default_rng(seed)
    lens = rng.integers(serve.min_prompt, serve.max_prompt + 1,
                        serve.n_requests)
    # Both ends of the range are always present, so every bucket is hit.
    lens[0], lens[1] = serve.min_prompt, serve.max_prompt
    return [
        {
            "id": f"smoke-{seed}-{i}",
            "token_ids": rng.integers(0, vocab_size, int(n)).tolist(),
            "max_new_tokens": serve.max_new_tokens,
            "is_greedy": True,
            "stream": i % serve.stream_every == 0,
        }
        for i, n in enumerate(lens)
    ]


def _post_generate(base: str, body: dict, min_events: int) -> dict:
    """One request over a real socket. Raises on any fault; returns the
    token ids and, for a streamed request, the number of SSE increments."""
    req = urllib.request.Request(
        base + "/generate", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"},
    )
    want = body["max_new_tokens"]
    with urllib.request.urlopen(req, timeout=600) as r:
        if r.status != 200:
            raise RuntimeError(f"{body['id']}: HTTP {r.status}")
        if not body["stream"]:
            resp, events = json.loads(r.read()), 0
        else:
            streamed, events, resp, event = [], 0, None, "message"
            for raw in r:
                line = raw.decode().rstrip("\n")
                if line.startswith("event: "):
                    event = line[len("event: "):]
                elif line.startswith("data: "):
                    payload = json.loads(line[len("data: "):])
                    if event == "done":
                        resp = payload
                    elif event == "message":
                        streamed += payload["token_ids"]
                        events += 1
                    else:
                        raise RuntimeError(f"{body['id']}: SSE {event}: "
                                           f"{payload}")
                elif not line:
                    event = "message"
            if resp is None:
                raise RuntimeError(f"{body['id']}: stream ended with no "
                                   "done event")
            if streamed != resp["token_ids"]:
                raise RuntimeError(f"{body['id']}: streamed increments "
                                   "differ from the final response")
            if events < min_events:
                raise RuntimeError(f"{body['id']}: {events} SSE events for "
                                   f"{want} tokens, expected >= {min_events}")
    if resp.get("error"):
        raise RuntimeError(f"{body['id']}: error {resp['error']!r}")
    if resp.get("id") != body["id"] or len(resp["token_ids"]) != want:
        raise RuntimeError(
            f"{body['id']}: got id {resp.get('id')!r} with "
            f"{len(resp['token_ids'])} tokens, expected {want}"
        )
    return {"token_ids": resp["token_ids"], "events": events}


def serve_window(stack: Stack, requests: list[dict], name: str) -> dict:
    """Send ``requests`` concurrently over HTTP and hold the stack to its
    contract: every request answered in full, streams delivered in
    increments, the pool back to idle, /health 200, /metrics counting
    exactly what was sent, and no compilation inside the window."""
    serve, batcher = stack.serve, stack.worker.batcher
    idle_blocks = batcher.allocator.blocks_in_use
    compiles0 = stack.counter.compiles
    stack.guard.snapshot()
    # The worker streams one increment per fetched group at most; a request
    # therefore arrives in at least this many SSE events.
    per_group = serve.group_chunks * serve.chunk_steps
    min_events = math.ceil(serve.max_new_tokens / per_group)
    t0 = time.monotonic()
    with concurrent.futures.ThreadPoolExecutor(len(requests)) as pool:
        futures = [
            pool.submit(_post_generate, stack.base, body, min_events)
            for body in requests
        ]
        pending = set(futures)
        while pending:
            _, pending = concurrent.futures.wait(pending, timeout=1.0)
            if stack.worker_error is not None:
                raise RuntimeError("worker died") from stack.worker_error
        results = [f.result() for f in futures]  # re-raises the first fault
    wall = time.monotonic() - t0

    # The worker publishes its counters to the broker every few loop
    # iterations: give the last publish a moment to land.
    n, toks = len(requests), len(requests) * serve.max_new_tokens
    want = {"requests_served": n, "tokens_generated": toks, "errors": 0}
    deadline = time.monotonic() + 30
    while True:
        m = stack.get("/metrics")[1]
        got = {k: m.get(k) for k in want}
        if (got == want and batcher.idle) or time.monotonic() > deadline:
            break
        time.sleep(0.05)
    if got != want or not batcher.idle:
        raise RuntimeError(f"{name}: /metrics says {got}, sent {want} "
                           f"(batcher idle: {batcher.idle})")
    if batcher.allocator.blocks_in_use != idle_blocks:
        raise RuntimeError(
            f"{name}: {batcher.allocator.blocks_in_use} blocks in use after "
            f"the window, {idle_blocks} before"
        )
    code, health = stack.get("/health")
    if code != 200:
        raise RuntimeError(f"{name}: /health {code} {health}")
    stack.guard.assert_no_recompiles()
    in_window = stack.counter.compiles - compiles0
    if in_window:
        raise RuntimeError(f"{name}: {in_window} backend compilations "
                           "inside the request window")
    streamed = [r for r, b in zip(results, requests) if b["stream"]]
    out = {
        "requests": n, "streamed": len(streamed),
        "sse_events": sum(r["events"] for r in streamed),
        "tokens": toks, "wall_s": round(wall, 2),
        "compilations_in_window": in_window,
        "mixed_batch_steps": m["mixed_batch"]["steps"],
    }
    log(f"{name}: {json.dumps(out)}")
    return out


# -- phase 4: logits ----------------------------------------------------------


def engine_logits(
    engine, prompts: list[list[int]], *, params=None, first=None,
):
    """Next-token logits from the engine's prefill, and from one decode step
    through the paged cache on ``first`` (default: the token the prefill
    picked, greedy). Returns ``(prefill [B, V], decode [B, V], first [B])``."""
    import jax.numpy as jnp
    import numpy as np

    from llmss_tpu.engine import GenerationParams

    params = engine.params if params is None else params
    B = len(prompts)
    ids, lens = engine._pad_prompts(prompts)
    sa = engine._sample_args(GenerationParams(is_greedy=True), B)
    cache = engine.new_paged_cache(B)
    tok, logits0, cache = engine._prefill(
        params, jnp.asarray(ids), cache, jnp.asarray(lens), sa,
    )
    if first is not None:
        tok = jnp.asarray(first, jnp.int32)
    _, logits1, _ = engine._decode(
        params, engine.canon_vec(tok), engine.canon_cache(cache),
        engine.canon_vec(jnp.asarray(lens)), sa,
    )
    return (np.asarray(logits0, np.float32), np.asarray(logits1, np.float32),
            np.asarray(tok).tolist())


def hf_logits(ckpt: Path, prompts: list[list[int]], first: list[int]):
    """The independent oracle: HF transformers' float32 forward of the same
    checkpoint on the host CPU — the last position of each prompt, and of
    each prompt followed by the engine's first token."""
    import numpy as np
    import torch
    from transformers import GPTBigCodeForCausalLM

    model = GPTBigCodeForCausalLM.from_pretrained(
        ckpt, dtype=torch.float32
    ).eval()
    pre, dec = [], []
    with torch.no_grad():
        for p, t in zip(prompts, first):
            out = model(torch.tensor([p + [t]])).logits[0]
            # Causal: position len(p)-1 of the longer sequence is the
            # prompt's own last position.
            pre.append(out[len(p) - 1].numpy())
            dec.append(out[len(p)].numpy())
    return np.stack(pre), np.stack(dec)


def logits_error(got, ref) -> float:
    """max |got − ref| over the vocabulary in units of std(ref), worst row."""
    import numpy as np

    if got.shape != ref.shape or not np.isfinite(got).all():
        raise RuntimeError(f"logits {got.shape} vs reference {ref.shape}, "
                           f"finite: {bool(np.isfinite(got).all())}")
    return float(np.max(np.abs(got - ref).max(-1) / ref.std(-1)))


def oracle_prompts(serve: ServeConfig, vocab_size: int, seed: int):
    import numpy as np

    rng = np.random.default_rng(seed + 1)
    # All in the largest bucket the smoke serves, lengths differing.
    lo = serve.max_prompt // 2 + 1
    return [
        rng.integers(0, vocab_size, int(n)).tolist()
        for n in rng.integers(lo, serve.max_prompt + 1, serve.oracle_prompts)
    ]


def oracle_check(ckpt: Path, engine, serve: ServeConfig, seed: int) -> dict:
    """Engine prefill and cached-decode logits against the HF float32
    oracle, plus the negative control: the same comparison with one
    projection's bias dropped (the MLP output's, as a loader that skips it
    would) must FAIL the tolerance."""
    import jax

    prompts = oracle_prompts(serve, engine.cfg.vocab_size, seed)
    got_pre, got_dec, first = engine_logits(engine, prompts)
    ref_pre, ref_dec = hf_logits(ckpt, prompts, first)
    tol = LOGITS_TOL[str(engine.cfg.compute_dtype)]
    errs = {
        "prefill": logits_error(got_pre, ref_pre),
        "decode": logits_error(got_dec, ref_dec),
    }
    blocks = engine.params["blocks"]
    fc_out = blocks["fc_out"]
    dropped = {**engine.params, "blocks": {**blocks, "fc_out": type(fc_out)(
        w=fc_out.w, b=fc_out.b * 0,
    )}}
    jax.block_until_ready(dropped)
    ctl_pre, _, _ = engine_logits(engine, prompts, params=dropped)
    control = logits_error(ctl_pre, ref_pre)
    out = {"tolerance": tol, **errs, "control_dropped_bias": control,
           "prompt_lens": [len(p) for p in prompts]}
    log(f"logits vs HF float32 ({engine.cfg.compute_dtype}): "
        f"{json.dumps(out)}")
    worst = max(errs.values())
    if not worst < tol:
        raise RuntimeError(f"logits off the oracle by {worst} > {tol}")
    if not control > tol:
        raise RuntimeError(
            f"negative control passed: dropping the fc_out bias moves the "
            f"logits by only {control} <= tolerance {tol}"
        )
    return out


# -- the one-chip run ---------------------------------------------------------


def run_single(
    hf_kwargs: dict, serve: ServeConfig, mesh, *, seed: int, dtype: str,
    ckpt_root: Path, counter: CompileCounter,
) -> dict:
    """Checkpoint → load → grouped-decode window → chunked-prefill window →
    oracle, on ``mesh``. Any phase that fails raises."""
    from llmss_tpu.engine import DecodeEngine

    ckpt = synthesize_checkpoint(hf_kwargs, seed, ckpt_root)
    cfg, params = load(ckpt, mesh, dtype)
    engine = DecodeEngine(
        cfg, params, mesh, kv_layout="paged", max_seq_len=serve.max_seq_len,
    )
    out = {}
    for i, (name, chunked) in enumerate((
        ("grouped_decode", None), ("chunked_prefill", serve.chunked_prefill),
    )):
        stack = Stack(engine, serve, chunked_prefill=chunked, counter=counter)
        try:
            out[name] = serve_window(
                stack, make_requests(serve, cfg.vocab_size, seed + i), name,
            )
        finally:
            stack.close()
        del stack  # the next worker allocates its own pool
    if out["grouped_decode"]["mixed_batch_steps"]:
        raise RuntimeError("the grouped-decode window ran ragged steps")
    if not out["chunked_prefill"]["mixed_batch_steps"]:
        raise RuntimeError("the chunked-prefill window never ran "
                           "_ragged_group")
    out["logits"] = oracle_check(ckpt, engine, serve, seed)
    return out


# -- the four-chip run --------------------------------------------------------


def run_tp(
    hf_kwargs: dict, serve: ServeConfig, devices, *, seed: int, dtype: str,
    ckpt_root: Path, counter: CompileCounter,
) -> dict:
    """The same checkpoint served with ``MeshPlan(tp=len(devices))`` through
    the same stack, compared with the same prompts' logits on a one-device
    mesh in the same process."""
    import jax
    import numpy as np

    from jax.sharding import NamedSharding, PartitionSpec

    from llmss_tpu.analysis.shardcheck import collective_inventory
    from llmss_tpu.engine import DecodeEngine, GenerationParams
    from llmss_tpu.models.decoder import param_specs
    from llmss_tpu.ops.attention import tp_head_plan
    from llmss_tpu.parallel import MeshPlan, make_mesh

    tp = len(devices)
    ckpt = synthesize_checkpoint(hf_kwargs, seed, ckpt_root)
    mesh = make_mesh(MeshPlan(tp=tp), devices=devices)
    cfg, params = load(ckpt, mesh, dtype)

    # Parameters spread: every device holds exactly the shard param_specs
    # gives it — about 1/tp of the bytes; only the MQA k/v projections,
    # the norms and the row-parallel biases are replicated. (Code that has
    # never seen more than one chip may put everything on the first.)
    specs = jax.tree.leaves(
        param_specs(cfg, tp), is_leaf=lambda x: isinstance(x, PartitionSpec)
    )
    leaves = jax.tree.leaves(params)
    total = sum(x.nbytes for x in leaves)
    ideal = sum(
        math.prod(NamedSharding(mesh, s).shard_shape(x.shape))
        * x.dtype.itemsize
        for x, s in zip(leaves, specs, strict=True)
    )
    held = {
        d.id: sum(
            s.data.nbytes for x in leaves for s in x.addressable_shards
            if s.device == d
        )
        for d in devices
    }
    shares = {d: round(b / total, 4) for d, b in held.items()}
    log(f"parameter bytes held per device / total: {shares} "
        f"(param_specs: {ideal / total:.4f})")
    if set(held.values()) != {ideal} or ideal / total > 1 / tp + 0.06:
        raise RuntimeError(f"parameters not spread 1/{tp} per device: "
                           f"{held}, param_specs give {ideal} of {total}")

    engine = DecodeEngine(
        cfg, params, mesh, kv_layout="paged", max_seq_len=serve.max_seq_len,
    )
    stack = Stack(engine, serve, chunked_prefill=None, counter=counter)
    try:
        # The pool's KV heads shard over tp when they divide it and are
        # replicated for MQA — never anything else.
        kv_shard, _, _ = tp_head_plan(cfg.n_heads, cfg.n_kv_heads, tp)
        pool = stack.worker.batcher.cache.k
        if pool.sharding.is_fully_replicated == kv_shard:
            raise RuntimeError(
                f"paged pool sharding {pool.sharding} with n_kv_heads="
                f"{cfg.n_kv_heads}, tp={tp}"
            )
        log(f"paged pool KV: n_kv_heads={cfg.n_kv_heads}, fully_replicated="
            f"{pool.sharding.is_fully_replicated}")
        window = serve_window(
            stack, make_requests(serve, cfg.vocab_size, seed), f"tp{tp}",
        )
        # The compiled decode program reduces the two row-parallel matmuls
        # of a layer (attention output, MLP output) across tp.
        batcher, rows = stack.worker.batcher, serve.rows
        hlo = engine._decode_group.lower(
            engine.params, batcher._tokens_dev, batcher.cache,
            batcher._cur_pos_dev,
            engine._sample_args(GenerationParams(), rows),
            np.ones(rows, bool), np.full(rows, -1, np.int32),
            n_chunks=serve.group_chunks, n_steps=serve.chunk_steps,
            t_bucket=None,
        ).compile().as_text()
        collectives = collective_inventory(hlo)
        log(f"decode_group collectives: {json.dumps(collectives)}")
        if collectives.get("all-reduce", {}).get("count", 0) < 2:
            raise RuntimeError("the tp decode program has fewer than the two "
                               f"all-reduces of a layer: {collectives}")
    finally:
        stack.close()

    # What it is compared with: one device, same checkpoint, same prompts.
    mesh1 = make_mesh(MeshPlan(tp=1), devices=devices[:1])
    cfg1, params1 = load(ckpt, mesh1, dtype)
    engine1 = DecodeEngine(
        cfg1, params1, mesh1, kv_layout="paged",
        max_seq_len=serve.max_seq_len,
    )
    prompts = oracle_prompts(serve, cfg.vocab_size, seed)
    pre, dec, first = engine_logits(engine, prompts)
    pre1, dec1, _ = engine_logits(engine1, prompts, first=first)
    # Two engines that each sit within the tolerance of the float32 truth
    # differ by at most twice it; a sharding fault (a partial sum that never
    # met its all-reduce) is off by whole standard deviations.
    tol = 2 * LOGITS_TOL[str(cfg.compute_dtype)]
    errs = {"prefill": logits_error(pre, pre1),
            "decode": logits_error(dec, dec1), "tolerance": tol}
    log(f"tp{tp} logits vs one device: {json.dumps(errs)}")
    if not max(errs["prefill"], errs["decode"]) < tol:
        raise RuntimeError(f"tp{tp} logits differ from one device: {errs}")
    return {"window": window, "shares": shares, "collectives": collectives,
            "logits": errs}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    device = require_tpu(args.chips)

    import jax

    from llmss_tpu.parallel import MeshPlan, initialize_runtime, make_mesh

    counter = CompileCounter()
    initialize_runtime()
    log("compile cache: "
        f"{jax.config.jax_compilation_cache_dir} (JAX_COMPILATION_CACHE_DIR "
        f"{'set' if os.environ.get('JAX_COMPILATION_CACHE_DIR') else 'unset'})")
    log(f"model: bigcode/starcoderbase-1b {json.dumps(STARCODERBASE_1B)}")
    for a in ASSUMED:
        log(f"assumed: {a}")
    ckpt_root = Path(tempfile.gettempdir()) / "llmss-chip-smoke"
    common = dict(seed=args.seed, dtype="bfloat16", ckpt_root=ckpt_root,
                  counter=counter)
    t0 = time.monotonic()
    if args.chips == 1:
        run_single(STARCODERBASE_1B, ServeConfig(), make_mesh(MeshPlan()),
                   **common)
    else:
        run_tp(STARCODERBASE_1B, TP_SERVE, jax.devices(), **common)
    peak = max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
        for d in jax.devices()
    )
    log(f"done in {time.monotonic() - t0:.1f} s; peak device memory "
        f"{peak / 1e9:.2f} GB; {counter.compiles} backend compiles "
        f"({counter.compile_s:.1f} s), persistent cache: "
        f"{counter.cache_hits} hits, {counter.cache_writes} writes")
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
