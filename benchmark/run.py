#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

This process is the load generator and never imports JAX. It starts
``benchmark/server.py`` (which holds the chip and serves the cell's model
through the program's normal path), offers the cell's traffic over HTTP on
real sockets, and prints ONE JSON object as the last line of stdout:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` (and
``breakdown`` with ``--trace 1``). Everything else it says goes to stderr or
to earlier stdout lines. See benchmark/README.md.

    python3 benchmark/run.py --sweep <cell> [--rates 4,8,12] [--seeds a,b,c]

finds a fixed-rate cell's knee: one server, one set-up, a ladder of rates,
a window a rate a seed, and a row a rate with each tail's range over the seeds.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import queue
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

from benchmark.lib import costs, loadgen, manifest, peaks, reduce, stats  # noqa: E402

DRAIN_TIMEOUT_S = 120.0
TRACE_SECONDS = 6.0


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


class Server:
    """The child process that holds the chip, and the line protocol to it."""

    def __init__(self, spec: dict):
        self.t_spawn = time.monotonic()
        # The compile cache stays where the program puts it (the directory
        # JAX_COMPILATION_CACHE_DIR names if set, else a fixed one inside the
        # checkout), without a size cap: a cell's programs are read in the
        # same order in every run, so a least-recently-used cache that is a
        # little too small misses on EVERY program (seen on the chip: 185 MB
        # of programs under a 192 MB cap, every second run cold).
        env = {**os.environ, "JAX_COMPILATION_CACHE_MAX_SIZE": "-1"}
        self.proc = subprocess.Popen(
            [sys.executable, str(ROOT / "benchmark" / "server.py"),
             json.dumps(spec)],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
            cwd=str(ROOT), env=env,
        )
        self._lines: queue.Queue = queue.Queue()
        self._turn = threading.Lock()  # one command and its answer at a time
        self._reader = threading.Thread(target=self._read, daemon=True)
        self._reader.start()
        self.port: int | None = None

    def _read(self) -> None:
        for line in self.proc.stdout:
            self._lines.put(line)
        self._lines.put(None)

    def _next(self, timeout: float) -> dict:
        deadline = time.monotonic() + timeout
        while True:
            try:
                line = self._lines.get(timeout=max(0.1, min(
                    1.0, deadline - time.monotonic())))
            except queue.Empty:
                if time.monotonic() > deadline:
                    raise TimeoutError("the server did not answer") from None
                continue
            if line is None:
                raise RuntimeError(
                    f"the server exited (code {self.proc.wait()})")
            line = line.strip()
            if line.startswith("{"):
                return json.loads(line)

    def wait_ready(self, timeout: float) -> dict:
        ready = self._next(timeout)
        self.port = ready["port"]
        # /health answers 200 once the HTTP front end and the worker are up.
        while True:
            try:
                with urllib.request.urlopen(
                    f"http://127.0.0.1:{self.port}/health", timeout=5
                ) as r:
                    if r.status == 200:
                        break
            except (urllib.error.URLError, OSError):
                pass
            if time.monotonic() - self.t_spawn > timeout:
                raise TimeoutError("/health never answered 200")
            time.sleep(0.05)
        ready["setup_s"] = time.monotonic() - self.t_spawn
        return ready

    def cmd(self, obj: dict, timeout: float = 300.0) -> dict:
        with self._turn:
            self.proc.stdin.write(json.dumps(obj) + "\n")
            self.proc.stdin.flush()
            return self._next(timeout)

    def get(self, path: str, tries: int = 4) -> dict:
        """GET a JSON document; a front end busy with many open streams may
        need more than one try."""
        for i in range(tries):
            try:
                with urllib.request.urlopen(
                    f"http://127.0.0.1:{self.port}{path}", timeout=30
                ) as r:
                    return json.loads(r.read())
            except (urllib.error.URLError, OSError):
                if i == tries - 1:
                    raise
                time.sleep(1.0)

    def stop(self) -> None:
        """Ask the server to stop, wait until it has ended, kill it if it
        does not: no process is left behind."""
        if self.proc.poll() is None:
            try:
                self.proc.stdin.write(json.dumps({"cmd": "stop"}) + "\n")
                self.proc.stdin.flush()
                self.proc.stdin.close()
            except (BrokenPipeError, OSError):
                pass
            try:
                self.proc.wait(timeout=90)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
        self._reader.join(timeout=5)


def server_spec(cell: dict, args) -> dict:
    return {
        "model": cell["model"], "dtype": cell["config"]["dtype"],
        "chips": cell["entry"]["chips"], "serve": cell["serve"],
        "traffic": cell["traffic"], "seed": args.seed,
        "trace": bool(args.trace), "allow_cpu": bool(args.allow_cpu),
        "fault": args.fault,
    }


# -- one window ---------------------------------------------------------------


def run_window(server: Server, the_plan: dict, *, trace: bool) -> dict:
    """Drive one plan against a ready server; collect what the window saw:
    request records, /metrics and compile counts at both ends of the window
    and (traced) the trace's start and stop and the flight recorder as it
    stood when the trace stopped."""
    state: dict = {}

    async def main():
        loop = asyncio.get_running_loop()

        async def call(obj):
            return await loop.run_in_executor(None, server.cmd, obj)

        async def on_window(phase):
            state[f"snap_{phase}"] = await call({"cmd": "snapshot"})
            _s, body = await loadgen.http(
                "127.0.0.1", server.port, "GET", "/metrics")
            state[f"metrics_{phase}"] = json.loads(body)

        async def take_trace():
            # From inside the window, a third of the way in, when occupancy
            # is steady; never longer than half the window.
            seconds = the_plan["seconds"]
            await asyncio.sleep(the_plan["warmup_s"] + seconds / 3)
            await call({"cmd": "trace_start"})
            await asyncio.sleep(min(TRACE_SECONDS, seconds / 2))
            await call({"cmd": "trace_stop"})
            state["flight_trace"] = await call({"cmd": "flight"})

        tracer = asyncio.create_task(take_trace()) if trace else None
        out = await loadgen.drive(
            "127.0.0.1", server.port, the_plan, timeout=DRAIN_TIMEOUT_S,
            on_window=on_window)
        if tracer:
            await tracer
        return out

    state.update(asyncio.run(main()))
    return state


def settle(server: Server, records: list[dict], before: dict) -> dict:
    """After the drain: wait until /metrics counts what was sent and the
    pool is back to idle (the worker publishes every few loop turns)."""
    ok = [r for r in records if r.get("final") is not None and not r["error"]]
    want = {
        "requests_served": before.get("requests_served", 0) + len(ok),
        "tokens_generated": before.get("tokens_generated", 0)
        + sum(len(r["final"]) for r in ok),
        "errors": before.get("errors", 0),
    }
    deadline = time.monotonic() + 30
    while True:
        m = server.get("/metrics")
        snap = server.cmd({"cmd": "snapshot"})
        got = {k: m.get(k) for k in want}
        idle = snap["idle"] and snap["blocks_in_use"] == snap["idle_blocks"]
        if (got == want and idle) or time.monotonic() > deadline:
            return {"metrics": m, "snapshot": snap, "want": want, "got": got,
                    "idle": idle}
        time.sleep(0.1)


def judge_requests(records: list[dict]) -> list[str]:
    """What every answered request must satisfy; returns the faults."""
    faults = []
    seen = set()
    for r in records:
        b = r["body"]
        if r["error"]:
            faults.append(f"{b['id']}: {r['error']}")
            continue
        if r.get("resp_id") != b["id"] or b["id"] in seen:
            faults.append(f"{b['id']}: answered as {r.get('resp_id')!r}")
        seen.add(b["id"])
        if r["final"] is None or len(r["final"]) != b["max_new_tokens"]:
            faults.append(
                f"{b['id']}: {len(r['final'] or [])} tokens, "
                f"asked for {b['max_new_tokens']}")
        if b["stream"] and r["tokens"] != r["final"]:
            faults.append(f"{b['id']}: streamed increments differ from the "
                          "final response")
    return faults


def end_to_end(state: dict, loop: str) -> tuple[dict, dict]:
    """The window's end-to-end numbers and what stands behind them."""
    w0, w1, recs = state["w0"], state["w1"], state["records"]
    out, info = {}, {}
    timeout_ms = DRAIN_TIMEOUT_S * 1e3
    if loop == "open":
        win = [r for r in recs if r["segment"] == "window"]
        ttft = [
            (r["first"] - r["due"]) * 1e3
            if r["first"] is not None and not r["error"] else timeout_ms
            for r in win
        ]
        tpot = [
            (r["last"] - r["first"]) / (len(r["tokens"]) - 1) * 1e3
            for r in win
            if not r["error"] and r["first"] is not None
            and len(r["tokens"]) > 1
        ]
        late = [(r["sent"] - r["due"]) * 1e3 for r in win]
        if ttft:
            out["ttft_p90_ms"] = stats.percentile(ttft, 90)
            info["ttft_ms"] = stats.summary(ttft, 90)
        if tpot:
            out["tpot_p90_ms"] = stats.percentile(tpot, 90)
            info["tpot_ms"] = stats.summary(tpot, 90)
        if late:
            info["gen_late_ms"] = stats.summary(late, 90)
        info["attempted"] = len(win)
        info["failed"] = sum(1 for r in win if r["error"])
    else:
        done = [r for r in recs
                if not r["error"] and r["done"] is not None
                and w0 <= r["done"] <= w1]
        toks = sum(len(r["body"]["token_ids"]) + len(r["final"]) for r in done)
        failed = [r for r in recs if r["error"]]
        out["total_tok_s"] = toks / (w1 - w0)
        info["completed_in_window"] = len(done)
        info["req_per_s"] = len(done) / (w1 - w0)
        info["generated_tok_s"] = sum(len(r["final"]) for r in done) / (w1 - w0)
        info["attempted"] = len(done) + len(failed)
        info["failed"] = len(failed)
    return out, info


def label_gaps(trace: dict, records: list[dict]) -> list[list]:
    """The trace's longest device-idle gaps, each named by what this process
    could see from outside while it lasted (both processes read one
    CLOCK_MONOTONIC; the trace's first event is taken as its start)."""
    out = []
    for start, length in trace.get("gaps", [])[:10]:
        a = trace["t_start"] + start
        b = a + length
        live = [r for r in records
                if r.get("sent") is not None and r["sent"] <= b
                and (r["done"] is None or r["done"] >= a)]
        if not live:
            label = "no request in flight"
        elif any(r["first"] is None or r["first"] >= a for r in live):
            label = "worker loop, a request awaiting its first token"
        else:
            label = "worker loop, every request in flight decoding"
        out.append([label, length])
    return out


def layer_values(entries: list[dict], ctx: dict):
    """Each per-layer metric's reader on ``ctx``: ``(entry, value)`` for
    those that found something to read."""
    for e in entries:
        v = manifest.load_module("layer_metrics", e["name"]).read(ctx)
        if v is not None:
            yield e, v


# -- a whole run --------------------------------------------------------------


def run_cell(args) -> int:
    m = manifest.load(args.manifest)
    cell = manifest.cell(m, args.workload)
    params, mix = cell["params"], cell["traffic"]
    the_plan = loadgen.plan(
        mix, seed=args.seed, seconds=args.seconds,
        vocab=cell["model"]["vocab_size"], tag=f"s{args.seed}",
        rate=params.get("rate"), clients=params.get("clients"),
        max_total=cell["serve"]["max_seq_len"],
    )
    server = Server(server_spec(cell, args))
    try:
        ready = server.wait_ready(timeout=1150)
        setup_s = ready["setup_s"]
        log(f"ready: {json.dumps(ready)}")
        before = server.get("/metrics")
        state = run_window(server, the_plan, trace=bool(args.trace))
        recs = state["records"]
        settled = settle(server, recs, before)
        e2e, info = end_to_end(state, the_plan["loop"])
        e2e["setup_s"] = setup_s
        faults = judge_requests(recs)
        compiles_in_window = (
            state["snap_end"]["compile"]["compiles"]
            - state["snap_start"]["compile"]["compiles"])
        if compiles_in_window:
            faults.append(f"{compiles_in_window} compilations in the window")
        if settled["got"] != settled["want"]:
            faults.append(f"/metrics says {settled['got']}, sent "
                          f"{settled['want']}")
        if not settled["idle"]:
            faults.append("the pool did not return to idle after the drain")
        if settled["snapshot"]["worker_error"]:
            faults.append(f"worker: {settled['snapshot']['worker_error']}")
        trace = flight = None
        if args.trace:
            trace = server.cmd({"cmd": "trace_reduce"}, timeout=600)
            flight = server.cmd({"cmd": "flight"})
            log(f"flight recorder: {len(flight.get('requests', {}))} requests")
        memory_peak = server.cmd({"cmd": "snapshot"})["memory_peak_bytes"]
        # Prompts of the mix's shortest length up to 128 tokens: the lengths
        # PR 21's tolerance was measured on.
        lo = mix["prompt"]["min"]
        chk = server.cmd(
            {"cmd": "check",
             "prompt_lens": [lo, min(max(lo, 128), mix["prompt"]["max"])]},
            timeout=900)
        log(f"logits vs float32 reference: {json.dumps(chk)}")
        if not chk.get("ok"):
            faults.append(f"logits off the reference: {chk}")
    finally:
        server.stop()

    device = {**ready["device"], "memory_peak_bytes": memory_peak}
    print(json.dumps({
        "cell": args.workload, "seed": args.seed, "setup": {
            "setup_s": setup_s, "prewarm": ready["prewarm"],
            "compile": ready["compile"]},
        "window": info,
        "end_to_end": e2e, "compilations_in_window": compiles_in_window,
        "logits": chk, "faults": faults[:20],
    }), flush=True)
    result = {
        "correct": not faults, "attempted": info["attempted"],
        "failed": info["failed"], "metrics": {}, "device": device,
    }
    if args.trace:
        ctx = {
            "cell": cell, "dims": ready["dims"],
            "peaks": peaks.peaks_for(device["kind"]) if not args.allow_cpu
            else None,
            "costs": costs, "stats": stats, "records": recs,
            "window": {"w0": state["w0"], "w1": state["w1"]},
            "metrics_before": state["metrics_start"],
            "metrics_after": state["metrics_end"],
            "flight": flight, "flight_trace": state.get("flight_trace"),
            "trace": trace, "info": info,
        }
        for e, v in layer_values(cell["per_layer"], ctx):
            result["metrics"][e["name"]] = {"value": v, "unit": e["unit"]}
        if trace and trace.get("devices"):
            device["busy_s"] = trace["busy_s"]
            device["window_s"] = trace["window_s"]
            result["breakdown"] = {
                "device_ops": [[n[:160], t] for n, t in trace["ops"][:10]],
                "idle_gaps": label_gaps(trace, recs),
            }
            log("programs: " + json.dumps(trace["programs"]))
            groups = [k for _t, k in reduce.dispatches_in_trace(ctx)]
            log(f"groups dispatched inside the trace: {len(groups)}, of "
                f"{sorted(set(groups))} steps, {sum(groups)} steps in all")
    else:
        units = {e["name"]: e["unit"] for e in cell["end_to_end"]}
        for name, unit in units.items():
            if name in e2e:
                result["metrics"][name] = {"value": e2e[name], "unit": unit}
    print(json.dumps(result), flush=True)
    # Each number compared beside its limit: the last lines of stderr.
    tol = chk.get("tolerance")
    for name, got, op, limit in [
        ("logits prefill", chk.get("prefill"), "<", tol),
        ("logits decode", chk.get("decode"), "<", tol),
        (f"logits control ({chk.get('control_fault')})", chk.get("control"),
         ">", tol),
        ("compilations in the window", compiles_in_window, "==", 0),
        ("/metrics counts", settled["got"], "==", settled["want"]),
        ("faults of any kind", len(faults), "==", 0),
    ]:
        log(f"compared: {name} {got} {op} {limit}")
    log(f"correct: {not faults}")
    return 0


# -- the sweep ----------------------------------------------------------------


def in_flight_max(records: list[dict], a: str, w0: float, w1: float) -> int:
    """The most requests at once between their ``a`` time (``sent``: in
    flight; ``first``: holding a row and decoding) and ``done``, at any
    moment of the window, by the generator's own log."""
    marks = []
    for r in records:
        if r.get(a) is None:
            continue
        end = r["done"] if r.get("done") is not None else w1
        if r[a] <= w1 and end >= w0:
            marks += [(max(r[a], w0), 1), (end, -1)]
    n = most = 0
    for _t, d in sorted(marks):  # at one instant an end sorts before a start
        n += d
        most = max(most, n)
    return most


def sweep_window(cell: dict, state: dict, flight: dict | None) -> dict:
    """What one window of a sweep read. Sustained: >= 98% of the requests
    due answered, and the last quarter's median first-token time under 1.5
    times the first quarter's (a backlog that grows by more within one short
    window is growing)."""
    w0, w1, recs = state["w0"], state["w1"], state["records"]
    win = [r for r in recs if r["segment"] == "window"]
    ok = [r for r in win if not r["error"] and r["first"] is not None]
    ttft = [(r["first"] - r["due"]) * 1e3 for r in ok]
    q = max(1, len(ttft) // 4)
    first_q = stats.percentile(ttft[:q], 50) if ttft else None
    last_q = stats.percentile(ttft[-q:], 50) if ttft else None
    e2e, info = end_to_end(state, "open")
    done = [r for r in recs if not r["error"] and r["done"] is not None
            and w0 <= r["done"] <= w1]
    row = {
        "due": len(win), "answered": len(ok),
        "answered_share": len(ok) / max(1, len(win)),
        "ttft_p50_ms": info.get("ttft_ms", {}).get("p50"),
        "ttft_p90_ms": e2e.get("ttft_p90_ms"),
        "tpot_p50_ms": info.get("tpot_ms", {}).get("p50"),
        "tpot_p90_ms": e2e.get("tpot_p90_ms"),
        "ttft_first_quarter_p50_ms": first_q,
        "ttft_last_quarter_p50_ms": last_q,
        "in_flight_max": in_flight_max(recs, "sent", w0, w1),
        "decoding_max": in_flight_max(recs, "first", w0, w1),
        "generated_tok_s": sum(len(r["final"]) for r in done) / (w1 - w0),
        "gen_late_p90_ms": info.get("gen_late_ms", {}).get("p90"),
        "compilations": state["snap_end"]["compile"]["compiles"]
        - state["snap_start"]["compile"]["compiles"],
    }
    if flight:
        # Spans are on (--trace 1; no profile is taken in a sweep): the
        # cell's span- and counter-read metrics over this window, from this
        # window's requests and the loop spans since it began.
        ids = {r["body"]["id"] for r in recs}
        spans_ = (flight.get("loop") or {}).get("spans", ())
        flight = {**flight, "requests": {
            k: v for k, v in flight.get("requests", {}).items() if k in ids
        }, "loop": {"spans": [sp for sp in spans_ if sp[3] >= state["t0"]]}}
        ctx = {"cell": cell, "stats": stats, "records": recs, "info": info,
               "window": {"w0": w0, "w1": w1}, "flight": flight,
               "metrics_before": state["metrics_start"],
               "metrics_after": state["metrics_end"]}
        row.update((e["name"], v) for e, v in layer_values(
            [e for e in cell["per_layer"]
             if e["source"] in ("program_span", "program_counter")], ctx))
    row["sustained"] = bool(
        row["answered_share"] >= 0.98 and first_q is not None
        and last_q < 1.5 * first_q)
    return row


def sweep_rate(rate: float, windows: list[dict], bounds: dict) -> dict:
    """One row a rate: each end-to-end metric's median and full range over
    the seeds' windows. Sustained: every window is, and no bounded metric's
    range over the seeds is wider than its bound allows a PR to move it - a
    tail that the order of arrivals moves by more cannot be read there."""
    row = {"rate": rate, "seeds": [w["seed"] for w in windows],
           "in_flight_max": max(w["in_flight_max"] for w in windows)}
    ok = all(w["sustained"] for w in windows)
    for name, bound in bounds.items():
        vals = [w[name] for w in windows if w.get(name) is not None]
        if not vals:
            continue
        mid = stats.percentile(vals, 50)
        share = (max(vals) - min(vals)) / mid
        row[name] = {"median": mid, "min": min(vals), "max": max(vals),
                     "range_share": share}
        ok = ok and share < bound
    row["sustained"] = bool(ok)
    return row


def run_sweep(args) -> int:
    """One set-up, a ladder of rates, at each rate one window a seed
    (``--seeds``; the server's weights are the first seed's). ``--trace 1``
    turns the program's spans on, not the profiler."""
    m = manifest.load(args.manifest)
    cell = manifest.cell(m, args.sweep)
    mix = {**cell["traffic"], "loop": "open"}
    rates = [float(x) for x in args.rates.split(",")]
    seeds = [int(x) for x in args.seeds.split(",")] if args.seeds else [args.seed]
    args.seed = seeds[0]
    bounds = {e["name"]: e["bound"] for e in cell["end_to_end"]
              if e["name"] != "setup_s"}
    server = Server(server_spec(cell, args))
    windows, rows = [], []
    try:
        ready = server.wait_ready(timeout=1150)
        log(f"ready: {json.dumps(ready)}")
        for rate in rates:
            here = []
            for seed in seeds:
                the_plan = loadgen.plan(
                    mix, seed=seed, seconds=args.seconds,
                    vocab=cell["model"]["vocab_size"], tag=f"r{rate:g}s{seed}",
                    rate=rate, max_total=cell["serve"]["max_seq_len"])
                before = server.get("/metrics")
                state = run_window(server, the_plan, trace=False)
                settle(server, state["records"], before)
                flight = server.cmd({"cmd": "flight"}) if args.trace else None
                w = {"rate": rate, "seed": seed,
                     **sweep_window(cell, state, flight)}
                here.append(w)
                print(json.dumps(w), flush=True)
            windows += here
            rows.append(sweep_rate(rate, here, bounds))
            print(json.dumps(rows[-1]), flush=True)
            if any(w["answered_share"] < 0.9 for w in here):
                break  # far above the knee: the rest would only queue
    finally:
        server.stop()
    knee = max((r["rate"] for r in rows if r["sustained"]), default=None)
    print(json.dumps({"sweep": args.sweep, "device": ready["device"],
                      "knee": knee, "rows": rows, "windows": windows}),
          flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--sweep", metavar="CELL")
    ap.add_argument("--rates", default="2,4,8,12,16,20,24")
    ap.add_argument("--seeds", default=None,
                    help="with --sweep: a window a rate for each of a,b,c")
    # Test-only: another manifest (a toy tree under tests/benchmark/) and
    # leave to run on whatever backend JAX finds. The driver passes none of
    # the three.
    ap.add_argument("--manifest", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--allow-cpu", action="store_true", help=argparse.SUPPRESS)
    # Test-only: break the served path underneath a whole run.
    ap.add_argument("--fault", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.seconds is None:
        args.seconds = float(manifest.load(args.manifest)["run_seconds"])
    if args.sweep:
        return run_sweep(args)
    if not args.workload:
        ap.error("--workload or --sweep is needed")
    return run_cell(args)


if __name__ == "__main__":
    sys.exit(main())
