"""The readers of the program's loop track and first-token seams
(benchmark/lib/spans.py and the per-layer metrics on it), each on a
hand-made ``ctx``. CPU only, no device number is produced here."""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark.lib import manifest, reduce, spans, stats  # noqa: E402


def _reader(name):
    return manifest.load_module("layer_metrics", name)


def _request(enqueue, lease, submit, prefill, admit, write=None, rows=()):
    """One request's flight-recorder timeline (more events than the seams,
    a redelivered ``lease`` among them: the first of a name counts)."""
    evs = [("accept", enqueue - 0.001), ("enqueue", enqueue), ("lease", lease),
           ("sched_submit", submit), ("prefill_dispatch", prefill),
           ("admit", admit), ("lease", admit + 0.5)]
    if write is not None:
        evs.append(("first_write", write))
    evs += [("group_dispatch", t) for t in rows]
    return {"trace_id": None, "dropped": 0, "events": [
        {"name": n, "t": t, "attrs": {"chunks": 1, "k": 4}}
        for n, t in sorted(evs, key=lambda e: e[1])]}


FLIGHT = {"requests": {
    "a": _request(10.000, 10.140, 10.141, 10.425, 10.715, 10.716),
    "b": _request(11.000, 11.100, 11.102, 11.390, 11.690, 11.694),
    "c": _request(12.000, 12.200, 12.201, 12.480, 12.790, 12.791),
    # still waiting for a row when the export was taken: in the first wait
    # only
    "d": {"trace_id": None, "dropped": 0, "events": [
        {"name": "enqueue", "t": 13.0}, {"name": "lease", "t": 13.15}]},
}}


@pytest.mark.parametrize("name,a,b,want", [
    ("broker_wait_p50_ms", "enqueue", "lease", 145.0),
    ("row_wait_p50_ms", "sched_submit", "prefill_dispatch", 284.0),
    ("first_token_lag_p50_ms", "prefill_dispatch", "admit", 300.0),
    ("stream_lag_p50_ms", "admit", "first_write", 1.0),
])
def test_a_wait_is_the_median_between_two_seams(name, a, b, want):
    ctx = {"flight": FLIGHT, "stats": stats}
    assert _reader(name).read(ctx) == pytest.approx(want, abs=1e-6)
    assert spans.SEAMS.index(b) == spans.SEAMS.index(a) + 1
    # a program that does not record the seam: nothing, not a zero
    old = {"requests": {k: {**r, "events": [
        e for e in r["events"] if e["name"] not in (a, b)[1:]]}
        for k, r in FLIGHT["requests"].items()}}
    assert _reader(name).read({"flight": old, "stats": stats}) is None
    assert _reader(name).read({"flight": {}, "stats": stats}) is None


def test_the_waits_add_up_to_enqueue_to_admit_for_every_request():
    """broker wait + (lease -> sched_submit) + row wait + first-token lag is
    ``enqueue`` -> ``admit``, the span ``queue_wait_p50_ms`` reads whole."""
    for rid in "abc":
        one = {"requests": {rid: FLIGHT["requests"][rid]}}
        parts = [spans.waits_ms(one, a, b)[0]
                 for a, b in zip(spans.SEAMS[:4], spans.SEAMS[1:5])]
        whole = _reader("queue_wait_p50_ms").read(
            {"flight": one, "stats": stats})
        assert sum(parts) == pytest.approx(whole, abs=1e-9)
        assert all(p >= 0 for p in parts)


def _loop_track(groups, gap, t0=1.0, steps=(1, 4)):
    """A loop track of ``groups`` iterations ``gap`` seconds apart, each one
    ``loop`` span with a ``sched.dispatch`` child of ``steps`` chunks x k."""
    out, seq = [], 0
    for g in range(groups):
        t = t0 + g * gap
        it = seq = seq + 1
        seq += 1
        out.append([seq, it, "sched.dispatch", t + 0.0005, 0.0015, {
            "group": g + 1, "kind": "decode_group", "chunks": steps[0],
            "k": steps[1], "rows_live": 40, "has_admission": False}])
        seq += 1
        out.append([seq, it, "sched.fetch_wait", t + 0.002, gap - 0.003,
                    {"group": g}])
        out.append([it, None, "loop", t, gap - 0.0005, {"iteration": g}])
    return {"spans": out, "dropped": 0}


def _per_request_events(track, throttle_s=0.05):
    """What the scheduler records beside the track: a ``group_dispatch`` a
    live request at each dispatch, throttled per request. The requests
    joined the batch one group after another, as requests do."""
    reqs = {}
    for joined, rid in enumerate(("x", "y", "z")):
        last, evs = None, []
        for sp in track["spans"]:
            t = sp[3] + sp[4]
            if sp[2] == "sched.dispatch" and sp[5]["group"] > joined and (
                    last is None or t - last >= throttle_s):
                last = t
                evs.append({"name": "group_dispatch", "t": t, "attrs": {
                    "chunks": sp[5]["chunks"], "k": sp[5]["k"], "loop": sp[0]}})
        reqs[rid] = {"events": evs}
    return reqs


TRACE = {"devices": 1, "t_start": 2.0, "t_stop": 8.0, "window_s": 6.0,
         "programs": {"jit__unknown": {"s": 5.4, "n": 30},
                      "jit__admit_merge_impl": {"s": 0.1, "n": 9}}}


def test_device_step_time_agrees_with_the_old_reader_on_long_groups():
    track = _loop_track(groups=40, gap=0.289)
    flight = {"requests": _per_request_events(track), "loop": track}
    ctx = {"trace": TRACE, "flight_trace": flight}
    old = reduce.decode_step_seconds(ctx) * 1e3
    new = _reader("decode_step_dev_ms").read(ctx)
    assert new == pytest.approx(old, rel=1e-9)
    # 90% of the window in step programs, 4 steps every 289 ms
    assert new == pytest.approx(0.9 * 289 / 4, rel=1e-9)
    inside = [s for s in spans.loop_spans(flight, "sched.dispatch")
              if 2.0 <= s["t0"] + s["dur"] <= 8.0]
    assert [s["group"] for s in inside] == list(range(5, 26))


def test_device_step_time_reads_groups_the_old_reader_cannot_count():
    """Groups 20 ms apart: the per-request events are throttled to one in 50
    ms, so ``decode_step_ms`` gives nothing; the track has every group."""
    track = _loop_track(groups=400, gap=0.020)
    flight = {"requests": _per_request_events(track), "loop": track}
    ctx = {"trace": TRACE, "flight_trace": flight}
    assert _reader("decode_step_ms").read(ctx) is None
    assert _reader("decode_step_dev_ms").read(ctx) == pytest.approx(
        0.9 * 20 / 4, rel=1e-9)
    # fewer than three dispatches inside, no device, or no track: nothing
    few = {"loop": _loop_track(groups=2, gap=0.289, t0=3.0)}
    assert _reader("decode_step_dev_ms").read(
        {"trace": TRACE, "flight_trace": few}) is None
    assert _reader("decode_step_dev_ms").read(
        {"trace": {**TRACE, "devices": 0}, "flight_trace": flight}) is None
    assert _reader("decode_step_dev_ms").read(
        {"trace": TRACE, "flight_trace": {"requests": flight["requests"]}}) is None


def _loop_block(**seconds):
    return {"decode_steps": seconds.pop("steps"),
            "spans": {n.replace("_", ".", 1): {"seconds": s, "count": 1}
                      for n, s in seconds.items()}}


def test_loop_readers_take_the_windows_difference_of_the_counters():
    before = _loop_block(steps=1000, loop=300.0, sched_fetch_wait=250.0,
                         loop_idle=40.0, sched_dispatch=2.0)
    after = _loop_block(steps=1700, loop=351.0, sched_fetch_wait=299.5,
                        loop_idle=40.5, sched_dispatch=2.4)
    ctx = {"metrics_before": {"loop": before}, "metrics_after": {"loop": after}}
    d = spans.loop_delta(ctx)
    assert d["decode_steps"] == 700
    assert d["seconds"]["sched.dispatch"] == pytest.approx(0.4)
    # 51 s of loop, 49.5 blocked on the device, 0.5 on an empty queue
    assert _reader("loop_host_ms_per_step").read(ctx) == pytest.approx(
        1.0 / 700 * 1e3)
    assert _reader("host_turn_pct").read(ctx) == pytest.approx(
        100 * 1.0 / 50.5)
    # a span that appeared inside the window counts from zero
    del before["spans"]["loop.idle"]
    assert spans.loop_delta(ctx)["seconds"]["loop.idle"] == 40.5
    # tracing off: the counters are there, the spans are not
    off = {"metrics_before": {"loop": {**before, "spans": {}}},
           "metrics_after": {"loop": {**after, "spans": {}}}}
    assert spans.loop_delta(off) is None
    assert _reader("loop_host_ms_per_step").read(off) is None
    assert _reader("host_turn_pct").read(off) is None


def _dispatches(groups):
    """A flight export whose loop track holds one ``sched.dispatch`` span a
    group: ``(t0, chunks, k)``."""
    return {"loop": {"dropped": 0, "spans": [
        [i + 1, 0, "sched.dispatch", t0, 0.002,
         {"group": i, "chunks": c, "k": k, "rows_live": 40}]
        for i, (t0, c, k) in enumerate(groups)
    ] + [[99, 0, "sched.plan", 20.0, 0.001, None]]}}


def test_long_group_pct_is_the_share_of_the_windows_busy_groups():
    read = _reader("long_group_pct").read
    cell = {"params": {"long_group_steps": 8}}
    window = {"w0": 20.0, "w1": 30.0}
    # ten groups inside the window, three of them the busy group's 8 steps
    # (one as 2 chunks of 4); a long one before the window is not counted
    groups = [(19.5, 1, 8)] + [
        (20.5 + i, *((1, 8) if i in (2, 3) else (2, 4) if i == 7 else (1, 4)))
        for i in range(10)]
    ctx = {"cell": cell, "window": window, "flight": _dispatches(groups)}
    assert read(ctx) == pytest.approx(30.0)
    assert read({**ctx, "flight": _dispatches([(21.0, 1, 4)])}) == 0.0
    # nothing to read: spans off, no dispatch inside the window, or a cell
    # that names no long group - never a zero
    assert read({**ctx, "flight": {}}) is None
    assert read({**ctx, "flight": _dispatches([(19.5, 1, 8)])}) is None
    assert read({**ctx, "cell": {"params": {}}}) is None
