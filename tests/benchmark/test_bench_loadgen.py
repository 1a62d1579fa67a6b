"""The traffic generator and the load client, against a stand-in HTTP server
that speaks the producer's protocol. CPU only, no model."""

import asyncio
import json
import sys
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark.lib import loadgen  # noqa: E402

GEN = json.loads((ROOT / "benchmark" / "traffic" / "gen.json").read_text())
SAT = json.loads((ROOT / "benchmark" / "traffic" / "complete-sat.json").read_text())


def _plan(seed, **kw):
    return loadgen.plan(GEN, seed=seed, seconds=20, vocab=1000, tag="t",
                        rate=5.0, **kw)


def test_same_seed_same_requests_and_due_times():
    a, b = _plan(2**31 + 7), _plan(2**31 + 7)
    assert a == b
    due = [r["due"] for r in a["requests"]]
    assert due == sorted(due)
    assert [r["segment"] for r in a["requests"]].count("window") == 100


def _schedule(p, segment="window"):
    due = [r["due"] for r in p["requests"]]
    gaps = [b - a for a, b in zip([0.0] + due, due)]
    return [(round(g, 9), len(r["body"]["token_ids"]),
             r["body"]["max_new_tokens"], r["body"]["is_greedy"])
            for g, r in zip(gaps, p["requests"]) if r["segment"] == segment]


def test_every_seed_gets_the_same_work_in_another_order():
    a, b = _schedule(_plan(1)), _schedule(_plan(2**31 + 2))
    assert a != b
    for i in range(4):  # the same gaps, lengths and sampled share, paired anew
        assert sorted(x[i] for x in a) == sorted(x[i] for x in b)
    lens, outs = sorted(x[1] for x in a), sorted(x[2] for x in a)
    assert lens[0] >= 33 and lens[-1] <= 256 and outs[0] >= 32 and outs[-1] <= 512
    assert sum(1 for x in a if not x[3]) == 50
    # the window's arrivals fill the window: the last is due at its end
    win = [r["due"] for r in _plan(1)["requests"] if r["segment"] == "window"]
    assert win[-1] == pytest.approx(GEN["warmup_s"] + 20, abs=1e-6)
    # token ids and sampling seeds are the seed's too
    pa, pb = _plan(1), _plan(2)
    assert [r["body"]["token_ids"] for r in pa["requests"]] != [
        r["body"]["token_ids"] for r in pb["requests"]]


def test_an_unknown_arrival_process_is_refused():
    with pytest.raises(ValueError, match="arrival process"):
        loadgen.plan({**GEN, "arrivals": {"process": "gamma", "cv": 2.5}},
                     seed=3, seconds=20, vocab=1000, tag="t", rate=5.0)


def test_a_prompt_is_trimmed_to_the_envelope():
    p = loadgen.plan(SAT, seed=1, seconds=5, vocab=1000, tag="t", clients=4,
                     max_total=2048)
    assert all(len(r["body"]["token_ids"]) + r["body"]["max_new_tokens"] <= 2048
               for r in p["requests"])
    assert p["clients"] == 4 and len(p["requests"]) == SAT["sequence"]


class _Standin:
    """Answers /generate like the producer: SSE increments then ``done`` for
    a streamed request, one JSON body otherwise; counts how many requests it
    holds at once."""

    def __init__(self, hold_s=0.05):
        outer = self
        self.live = self.peak = self.served = 0
        self.lock = threading.Lock()

        class H(BaseHTTPRequestHandler):
            def log_message(self, *a):
                pass

            def do_POST(self):
                body = json.loads(self.rfile.read(int(self.headers["Content-Length"])))
                with outer.lock:
                    outer.live += 1
                    outer.peak = max(outer.peak, outer.live)
                toks = list(range(body["max_new_tokens"]))
                try:
                    if body["stream"]:
                        self.send_response(200)
                        self.send_header("Content-Type", "text/event-stream")
                        self.end_headers()
                        for i in range(0, len(toks), 8):
                            time.sleep(hold_s / 4)
                            self.wfile.write(b"data: " + json.dumps(
                                {"token_ids": toks[i:i + 8]}).encode() + b"\n\n")
                            self.wfile.flush()
                        self.wfile.write(b"event: done\ndata: " + json.dumps(
                            {"id": body["id"], "token_ids": toks}).encode() + b"\n\n")
                    else:
                        time.sleep(hold_s)
                        out = json.dumps({"id": body["id"], "token_ids": toks}).encode()
                        self.send_response(200)
                        self.send_header("Content-Length", str(len(out)))
                        self.end_headers()
                        self.wfile.write(out)
                finally:
                    with outer.lock:
                        outer.live -= 1
                        outer.served += 1

        self.httpd = ThreadingHTTPServer(("127.0.0.1", 0), H)
        self.port = self.httpd.server_address[1]
        self.thread = threading.Thread(target=self.httpd.serve_forever, daemon=True)
        self.thread.start()

    def close(self):
        self.httpd.shutdown()
        self.httpd.server_close()
        self.thread.join(timeout=5)
        assert not self.thread.is_alive()


@pytest.fixture
def standin():
    s = _Standin()
    yield s
    s.close()


def test_open_loop_times_from_due_and_reports_lateness(standin):
    mix = {**GEN, "warmup_s": 0.2, "cooldown_s": 0.2,
           "output": {"dist": "fixed", "value": 24}}
    p = loadgen.plan(mix, seed=5, seconds=1.0, vocab=100, tag="o", rate=30.0)
    out = asyncio.run(loadgen.drive("127.0.0.1", standin.port, p, timeout=10))
    win = [r for r in out["records"] if r["segment"] == "window"]
    assert len(win) == 30 and not any(r["error"] for r in win)
    for r in win:
        assert out["w0"] - 1e-6 <= r["due"] <= out["w1"] + 1e-6
        assert 0 <= r["sent"] - r["due"] < 0.5  # how late the generator ran
        assert r["due"] <= r["sent"] <= r["first"] <= r["last"] <= r["done"]
        assert r["tokens"] == r["final"] == list(range(24))
        assert [n for _t, n in r["increments"]] == [8, 8, 8]
        assert r["resp_id"] == r["body"]["id"]


def test_closed_loop_holds_exactly_its_clients(standin):
    mix = {**SAT, "warmup_s": 0.2, "sequence": 16,
           "prompt": {"dist": "fixed", "value": 4},
           "output": {"dist": "fixed", "value": 3}}
    p = loadgen.plan(mix, seed=5, seconds=0.6, vocab=100, tag="c", clients=5)
    out = asyncio.run(loadgen.drive("127.0.0.1", standin.port, p, timeout=10))
    assert standin.peak == 5
    recs = out["records"]
    assert len(recs) > 16  # the sequence started over, under new ids
    assert len({r["body"]["id"] for r in recs}) == len(recs)
    assert not any(r["error"] for r in recs)
    assert {r["segment"] for r in recs} == {"warmup", "window"}


def test_a_refused_request_is_a_failure_not_a_crash():
    # nothing listens on this port
    p = loadgen.plan({**GEN, "warmup_s": 0.0, "cooldown_s": 0.0}, seed=1,
                     seconds=0.2, vocab=10, tag="x", rate=10.0)
    out = asyncio.run(loadgen.drive("127.0.0.1", 1, p, timeout=2))
    assert out["records"] and all(r["error"] for r in out["records"])
