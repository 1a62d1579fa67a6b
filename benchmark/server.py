#!/usr/bin/env python3
"""The process that holds the chip: one model served through the normal path.

    configuration file -> initialize_runtime() -> make_mesh(MeshPlan(tp=chips))
      -> init_params(cfg, mesh, key(seed))      (weights made on the device)
      -> DecodeEngine(kv_layout="paged") -> ContinuousWorker on an InProcBroker
      -> prewarm(seq_buckets = what the mix can produce)
      -> ProducerServer on a localhost port, run_forever in a thread

wired as ``chip_smoke.Stack`` wires it. Started by ``benchmark/run.py`` with
one JSON argument (the cell's spec); talks to it over stdin/stdout, one JSON
object a line: it prints ``{"ready": ...}`` when the HTTP server is up, then
answers commands (``snapshot``, ``trace_start``, ``trace_stop``, ``flight``,
``check``, ``stop``). Logs go to stderr. No TPU, or the wrong number of
devices: exit non-zero before any work (the spec's ``allow_cpu`` is the
harness's test-only flag).
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import tempfile
import threading
import time
import types
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def log(msg: str) -> None:
    print(f"[bench.server] {msg}", file=sys.stderr, flush=True)


def say(obj: dict) -> None:
    sys.stdout.write(json.dumps(obj) + "\n")
    sys.stdout.flush()


class CompileCounter:
    """JAX's own monitoring events: backend compiles (every executable built
    or fetched from the persistent cache), seconds in the compiler, and the
    persistent cache's hits and writes (after ``chip_smoke.CompileCounter``)."""

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

    def to_dict(self) -> dict:
        return {"compiles": self.compiles, "compile_s": self.compile_s,
                "cache_hits": self.cache_hits,
                "cache_writes": self.cache_writes}


def require_device(chips: int, allow_cpu: bool) -> dict:
    import jax

    devs = jax.devices()
    if jax.default_backend() != "tpu" and not allow_cpu:
        raise SystemExit(
            f"benchmark: JAX's default backend is {jax.default_backend()!r}, "
            "not 'tpu' - the benchmark has no CPU mode")
    if not allow_cpu and len(devs) != chips:
        raise SystemExit(
            f"benchmark: the cell asks for {chips} chip(s), JAX found "
            f"{len(devs)}")
    if len(devs) < chips:
        raise SystemExit(f"benchmark: {chips} device(s) needed, {len(devs)} found")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips if allow_cpu else len(devs)}


def prompt_buckets(mix: dict, max_seq_len: int) -> list[int]:
    """The prompt buckets this mix's lengths can fall into."""
    from llmss_tpu.engine.engine import _bucket

    lo, hi = mix["prompt"]["min"], mix["prompt"]["max"]
    out, b = set(), _bucket(lo, max_seq_len)
    while True:
        out.add(b)
        if b >= min(hi, max_seq_len):
            break
        b = _bucket(b + 1, max_seq_len)
    return sorted(out)


class Served:
    """Engine -> ContinuousWorker -> InProcBroker -> ProducerServer."""

    def __init__(self, spec: dict, counter: CompileCounter):
        import jax

        from llmss_tpu.engine import DecodeEngine
        from llmss_tpu.models.decoder import init_params
        from llmss_tpu.models.registry import config_from_hf
        from llmss_tpu.parallel import MeshPlan, initialize_runtime, make_mesh
        from llmss_tpu.serve.broker import InProcBroker
        from llmss_tpu.serve.consumer import ContinuousWorker
        from llmss_tpu.serve.producer import ProducerServer

        t0 = time.monotonic()
        chips, serve = spec["chips"], spec["serve"]
        initialize_runtime()
        log(f"compile cache: {jax.config.jax_compilation_cache_dir} "
            "(JAX_COMPILATION_CACHE_DIR "
            f"{'set' if os.environ.get('JAX_COMPILATION_CACHE_DIR') else 'unset'})")
        mesh = make_mesh(MeshPlan(tp=chips), devices=jax.devices()[:chips])
        self.hf = spec["model"]
        cfg = config_from_hf(
            types.SimpleNamespace(**self.hf), dtype=spec["dtype"])
        params = init_params(cfg, mesh, jax.random.key(spec["seed"]))
        # init_params draws every leaf from N(0, 0.02); a norm scale near 0
        # would switch the blocks off. Norm scales become 1 + N(0, 0.02), as
        # chip_smoke's checkpoint has them - one jitted call on the device.
        params = _unit_norm_scales(params)
        jax.block_until_ready(params)
        n_bytes = sum(x.nbytes for x in jax.tree.leaves(params))
        log(f"weights: {n_bytes / 1e9:.3f} GB made on {chips} device(s) in "
            f"{time.monotonic() - t0:.1f} s")
        self.engine = DecodeEngine(
            cfg, params, mesh, kv_layout=serve["kv_layout"],
            max_seq_len=serve["max_seq_len"],
        )
        self.broker = InProcBroker()
        if spec.get("fault") == "stream_token":
            # Test-only (tests/benchmark): the timed path broken underneath,
            # one streamed token altered where the worker hands it over, so
            # that a whole run can be seen to come out not `correct`.
            push = self.broker.push_stream
            self.broker.push_stream = lambda rid, toks: push(
                rid, [toks[0] ^ 1, *toks[1:]])
        self.worker = ContinuousWorker(
            self.engine, self.broker, tokenizer=None, rows=serve["rows"],
            chunked_prefill=serve.get("chunked_prefill"),
        )
        batcher = self.worker.batcher
        held = jax.tree.leaves(batcher.cache)
        log(f"cache: {sum(x.nbytes for x in held) / 1e9:.3f} GB in "
            f"{len(held)} arrays, blocks of {self.engine.block_size} slots, "
            f"rows={serve['rows']}, max_seq_len={self.engine.max_seq_len}, "
            f"chunked_prefill={serve.get('chunked_prefill')}")
        buckets = prompt_buckets(spec["traffic"], self.engine.max_seq_len)
        c0, s0, t1 = counter.compiles, counter.compile_s, time.monotonic()
        n_exec = self.worker.prewarm(seq_buckets=buckets)
        self.prewarm = {
            "executables": n_exec, "buckets": buckets,
            "seconds": time.monotonic() - t1,
            "backend_compiles": counter.compiles - c0,
            "compiler_s": counter.compile_s - s0,
        }
        log(f"prewarm: {json.dumps(self.prewarm)}")
        self.idle_blocks = batcher.allocator.blocks_in_use
        self.server = ProducerServer(self.broker, host="127.0.0.1", port=0)
        self.server.start()
        self.worker_error: BaseException | None = None
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def _run(self) -> None:
        try:
            self.worker.run_forever(self._stop)
        except BaseException as e:  # noqa: BLE001 - reported by snapshot
            self.worker_error = e
            log(f"worker died: {e!r}")

    def stop_worker(self) -> None:
        self._stop.set()
        self._thread.join(timeout=60)
        if self._thread.is_alive():
            raise RuntimeError("worker thread did not stop")

    def close(self) -> None:
        self.stop_worker()
        self.server.stop()


def _unit_norm_scales(params):
    """scale + 1 on every norm, each array kept in its own sharding; every
    other leaf is passed through untouched."""
    import jax

    from llmss_tpu.ops.layers import NormParams

    def fix(p):
        if not isinstance(p, NormParams):
            return p
        s = p.scale
        return p._replace(
            scale=jax.jit(lambda x: x + 1, out_shardings=s.sharding)(s))

    return jax.tree.map(
        fix, params, is_leaf=lambda p: isinstance(p, NormParams))


def memory_peak() -> int:
    import jax

    return max(
        int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
        for d in jax.devices()
    )


def main() -> None:
    spec = json.loads(sys.argv[1])
    # The flight recorder is on by default in the program; the end-to-end
    # run measures with it off, the traced run with it on.
    os.environ["LLMSS_TRACE"] = "1" if spec["trace"] else "0"
    sys.path.insert(0, str(ROOT))
    device = require_device(spec["chips"], spec.get("allow_cpu", False))
    import jax

    counter = CompileCounter()
    served = Served(spec, counter)
    from benchmark.lib import check

    say({"ready": True, "port": served.server.port, "device": device,
         "prewarm": served.prewarm, "compile": counter.to_dict(),
         "dims": check.load_reference(served.hf["model_type"]).dims(served.hf)})
    trace_dir, trace_t = None, [0.0, 0.0]
    try:
        for line in sys.stdin:
            cmd = json.loads(line)
            op = cmd["cmd"]
            if op == "snapshot":
                b = served.worker.batcher
                say({
                    "compile": counter.to_dict(),
                    "memory_peak_bytes": memory_peak(),
                    "idle": bool(b.idle),
                    "blocks_in_use": b.allocator.blocks_in_use,
                    "idle_blocks": served.idle_blocks,
                    "worker_error": (repr(served.worker_error)
                                     if served.worker_error else None),
                })
            elif op == "trace_start":
                trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 1
                jax.profiler.start_trace(trace_dir, profiler_options=opts)
                trace_t[0] = time.monotonic()
                say({"tracing": True, "t": trace_t[0]})
            elif op == "trace_stop":
                trace_t[1] = time.monotonic()
                jax.profiler.stop_trace()
                say({"tracing": False, "t": trace_t[1]})
            elif op == "trace_reduce":
                from benchmark.lib import xplane

                planes = xplane.read(xplane.find_xplane(trace_dir))
                log("trace planes: " + json.dumps({
                    p: {l: len(e) for l, e in lines.items()}
                    for p, lines in planes.items()
                })[:4000])
                out = xplane.reduce(planes, trace_t[1] - trace_t[0])
                out.update(t_start=trace_t[0], t_stop=trace_t[1])
                out["ops"] = out.get("ops", [])[:40]
                shutil.rmtree(trace_dir, ignore_errors=True)
                say(out)
            elif op == "flight":
                from llmss_tpu.utils import trace

                say(trace.recorder().export() if trace.enabled() else {})
            elif op == "check":
                served.stop_worker()
                # The pool is not needed any more; the check brings its own
                # small paged cache and one float32 layer.
                served.worker.batcher.cache = None
                lo, hi = cmd["prompt_lens"]
                try:
                    say(check.reference_check(
                        served.engine, served.hf, spec["seed"], lo, hi))
                except Exception as e:  # noqa: BLE001 - reported, run fails
                    log(f"reference check failed: {e!r}")
                    say({"ok": False, "error": repr(e)})
            elif op == "stop":
                break
            else:
                say({"error": f"unknown command {op!r}"})
    finally:
        served.close()
    say({"stopped": True})


if __name__ == "__main__":
    main()
