"""The one general traffic generator and HTTP load client.

A traffic mix is a data file of parameters (``benchmark/traffic/<name>.json``);
this module turns it, a seed and the cell's rate or client count into a plan
of requests, and drives the plan against the server over real sockets from
ONE thread (asyncio), timing every request from when it was DUE.

What the seed decides: every run of a cell has the SAME SET of prompt
lengths, output lengths, sampled requests and inter-arrival gaps (the
quantile midpoints of the mix's distributions, so no seed draws a heavier
run than another), and ``--seed`` draws their ORDER and pairing, the token
ids and the per-request sampling seeds. The work of a window is fixed; when
each piece of it arrives is the seed's. (On the chip the same lengths and gaps
in another order moved a 90th-percentile first-token time by some per cent:
that is the spread the bounds are set from, not one frozen ordering.)
"""

from __future__ import annotations

import asyncio
import json
import math
import random
import statistics
import time

_NORMAL = statistics.NormalDist()


def _lengths(dist: dict, n: int) -> list[int]:
    """``n`` lengths: the quantile midpoints of the clipped distribution."""
    if dist["dist"] == "lognormal":
        mu, sigma = math.log(dist["median"]), dist["sigma"]
        out = [
            math.exp(mu + sigma * _NORMAL.inv_cdf((i + 0.5) / n))
            for i in range(n)
        ]
    elif dist["dist"] == "fixed":
        out = [dist["value"]] * n
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    lo, hi = dist.get("min", 1), dist.get("max", 1 << 30)
    return [int(min(max(round(x), lo), hi)) for x in out]


def _gaps(arrivals: dict, rate: float, n: int) -> list[float]:
    """``n`` inter-arrival gaps with mean 1/rate: the quantile midpoints of
    the exponential distribution (Poisson arrivals, stratified)."""
    if arrivals.get("process", "poisson") != "poisson":
        raise ValueError(f"unknown arrival process {arrivals['process']!r}")
    raw = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
    scale = n / (rate * sum(raw))
    return [g * scale for g in raw]


def _bodies(mix: dict, n: int, vocab: int, rng: random.Random, tag: str,
            max_total: int | None) -> list[dict]:
    prompts = _lengths(mix["prompt"], n)
    outputs = _lengths(mix["output"], n)
    rng.shuffle(prompts)
    rng.shuffle(outputs)
    n_sampled = round(n * mix.get("sampled_share", 0.0))
    sampled = [True] * n_sampled + [False] * (n - n_sampled)
    rng.shuffle(sampled)
    out = []
    for i in range(n):
        p, o = prompts[i], outputs[i]
        if max_total is not None and p + o > max_total:
            p = max_total - o  # the envelope's context: trim the prompt
        body = {
            "id": f"{tag}-{i}",
            "token_ids": rng.choices(range(vocab), k=p),
            "max_new_tokens": o,
            "is_greedy": not sampled[i],
            "stream": bool(mix.get("stream", False)),
        }
        if sampled[i]:
            body.update(
                temperature=mix.get("temperature", 1.0),
                top_p=mix.get("top_p", 1.0),
                seed=rng.randrange(1 << 31),
            )
        out.append(body)
    return out


def plan(mix: dict, *, seed: int, seconds: float, vocab: int, tag: str,
         rate: float | None = None, clients: int | None = None,
         max_total: int | None = None) -> dict:
    """The requests of one run. Open loop (``rate``): three segments -
    warm-up, the measured window, cool-down (so the window's last requests
    finish under load) - each its own stratified sample, with ``due`` times
    relative to the start of the warm-up. Closed loop (``clients``): one
    stratified sequence the clients draw from in order."""
    rng = random.Random(seed)
    warm, cool = mix.get("warmup_s", 5.0), mix.get("cooldown_s", 0.0)
    if mix["loop"] == "open":
        if not rate:
            raise ValueError("an open-loop mix needs the cell's rate")
        reqs, t = [], 0.0
        for seg, length in (("warmup", warm), ("window", seconds),
                            ("cooldown", cool)):
            n = max(1, round(rate * length)) if length > 0 else 0
            if not n:
                continue
            gaps = _gaps(mix.get("arrivals", {}), rate, n)
            rng.shuffle(gaps)
            bodies = _bodies(mix, n, vocab, rng, f"{tag}-{seg}", max_total)
            for g, b in zip(gaps, bodies):
                t += g
                reqs.append({"segment": seg, "due": t, "body": b})
        return {"loop": "open", "warmup_s": warm, "seconds": seconds,
                "requests": reqs}
    if mix["loop"] == "closed":
        if not clients:
            raise ValueError("a closed-loop mix needs the cell's clients")
        n = int(mix.get("sequence", 4096))
        bodies = _bodies(mix, n, vocab, rng, tag, max_total)
        return {"loop": "closed", "warmup_s": warm, "seconds": seconds,
                "clients": clients,
                "requests": [{"segment": None, "due": None, "body": b}
                             for b in bodies]}
    raise ValueError(f"unknown loop {mix['loop']!r}")


# -- the HTTP client ----------------------------------------------------------


async def http(host: str, port: int, method: str, path: str,
               body: bytes | None = None, timeout: float = 30.0):
    """One HTTP/1.0-style exchange; returns ``(status, body bytes)``."""
    async def go():
        reader, writer = await asyncio.open_connection(host, port)
        try:
            head = (f"{method} {path} HTTP/1.1\r\nHost: {host}\r\n"
                    "Connection: close\r\n")
            if body is not None:
                head += ("Content-Type: application/json\r\n"
                         f"Content-Length: {len(body)}\r\n")
            writer.write(head.encode() + b"\r\n" + (body or b""))
            await writer.drain()
            status = int((await reader.readline()).split()[1])
            while (await reader.readline()) not in (b"\r\n", b"\n", b""):
                pass
            return status, await reader.read()
        finally:
            writer.close()
    return await asyncio.wait_for(go(), timeout)


async def generate(host: str, port: int, rec: dict, timeout: float) -> None:
    """POST one /generate and fill ``rec`` with what came back and when:
    ``sent``, ``first`` (first token event), ``last`` (last token event),
    ``done``, ``tokens`` (as streamed), ``increments`` (time and size of each
    token event), ``final`` (the response's ids), ``events``, ``status``,
    ``error``."""
    body = rec["body"]
    payload = json.dumps(body).encode()
    rec.update(first=None, last=None, done=None, tokens=[], final=None,
               events=0, increments=[], status=None, error=None)

    async def go():
        reader, writer = await asyncio.open_connection(
            host, port, limit=1 << 22)
        try:
            writer.write(
                (f"POST /generate HTTP/1.1\r\nHost: {host}\r\n"
                 "Connection: close\r\nContent-Type: application/json\r\n"
                 f"Content-Length: {len(payload)}\r\n\r\n").encode() + payload
            )
            await writer.drain()
            rec["status"] = int((await reader.readline()).split()[1])
            while (await reader.readline()) not in (b"\r\n", b"\n", b""):
                pass
            if rec["status"] != 200 or not body["stream"]:
                raw = await reader.read()
                now = time.monotonic()
                resp = json.loads(raw) if raw else {}
                if rec["status"] == 200 and not resp.get("error"):
                    rec["final"] = resp.get("token_ids")
                    rec["resp_id"] = resp.get("id")
                    rec["first"] = rec["last"] = rec["done"] = now
                else:
                    rec["error"] = f"HTTP {rec['status']}: {resp.get('error')}"
                return
            event = "message"
            while True:
                raw = await reader.readline()
                if not raw:
                    break
                line = raw.decode().rstrip("\r\n")
                if line.startswith("event: "):
                    event = line[7:]
                elif line.startswith("data: "):
                    now = time.monotonic()
                    data = json.loads(line[6:])
                    if event == "done":
                        rec["done"] = now
                        rec["final"] = data.get("token_ids")
                        rec["resp_id"] = data.get("id")
                        if data.get("error"):
                            rec["error"] = f"done: {data['error']}"
                    elif event == "message":
                        if data["token_ids"]:
                            if rec["first"] is None:
                                rec["first"] = now
                            rec["last"] = now
                            rec["tokens"] += data["token_ids"]
                            rec["increments"].append(
                                (now, len(data["token_ids"])))
                            rec["events"] += 1
                    else:
                        rec["error"] = f"SSE {event}: {data}"
                elif not line:
                    event = "message"
            if rec["done"] is None and rec["error"] is None:
                rec["error"] = "stream ended with no done event"
        finally:
            writer.close()

    rec["sent"] = time.monotonic()
    try:
        await asyncio.wait_for(go(), timeout)
    except (asyncio.TimeoutError, OSError, ValueError, IndexError) as e:
        rec["error"] = rec["error"] or f"{type(e).__name__}: {e}"


async def drive(host: str, port: int, the_plan: dict, *, timeout: float,
                on_window=None) -> dict:
    """Run a plan. Returns ``{"t0", "w0", "w1", "records"}``: monotonic
    times of the start, of the window's two ends, and one record per request
    sent. ``on_window(phase)`` is awaited at "start" and "end" of the
    window."""
    records: list[dict] = []
    warm, seconds = the_plan["warmup_s"], the_plan["seconds"]
    t0 = time.monotonic() + 0.05
    w0, w1 = t0 + warm, t0 + warm + seconds
    tasks: list[asyncio.Task] = []

    async def window_events():
        await asyncio.sleep(max(0.0, w0 - time.monotonic()))
        if on_window:
            await on_window("start")
        await asyncio.sleep(max(0.0, w1 - time.monotonic()))
        if on_window:
            await on_window("end")

    watcher = asyncio.create_task(window_events())

    if the_plan["loop"] == "open":
        window_tasks: list[asyncio.Task] = []
        for r in the_plan["requests"]:
            if r["segment"] == "cooldown" and window_tasks and all(
                t.done() for t in window_tasks
            ):
                break  # every window request has finished: no need for more
            delay = t0 + r["due"] - time.monotonic()
            if delay > 0:
                await asyncio.sleep(delay)
            rec = {"segment": r["segment"], "due": t0 + r["due"],
                   "body": r["body"]}
            records.append(rec)
            task = asyncio.create_task(generate(host, port, rec, timeout))
            tasks.append(task)
            if r["segment"] == "window":
                window_tasks.append(task)
    else:
        reqs, sent = the_plan["requests"], 0

        async def client():
            nonlocal sent
            while time.monotonic() < w1:
                # The sequence is drawn in order and starts over (under new
                # ids) if a fast system exhausts it.
                turn, i = divmod(sent, len(reqs))
                sent += 1
                body = reqs[i]["body"]
                if turn:
                    body = {**body, "id": f"{body['id']}-t{turn}"}
                now = time.monotonic()
                rec = {"segment": "window" if now >= w0 else "warmup",
                       "due": now, "body": body}
                records.append(rec)
                await generate(host, port, rec, timeout)

        tasks = [asyncio.create_task(client())
                 for _ in range(the_plan["clients"])]
    await watcher
    if tasks:
        await asyncio.wait(tasks, timeout=timeout)
    for t in tasks:
        if not t.done():
            t.cancel()
    await asyncio.gather(*tasks, return_exceptions=True)
    return {"t0": t0, "w0": w0, "w1": w1, "records": records}
