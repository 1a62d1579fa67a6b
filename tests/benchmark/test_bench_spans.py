"""The readers of the program's loop track and first-token seams
(benchmark/lib/spans.py and the per-layer metrics on it), each on a
hand-made ``ctx``. CPU only, no device number is produced here."""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark.lib import costs, manifest, peaks, reduce, spans, stats  # noqa: E402


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


TRACE = {"devices": 1, "t_start": 2.0, "t_stop": 8.0, "window_s": 6.0,
         "programs": {"jit__unknown": {"s": 5.4, "n": 30},
                      "jit__admit_merge_impl": {"s": 0.1, "n": 9}}}

PEAKS = peaks.peaks_for("TPU v5 lite")
# ten layers of keys and values, and 5 MiB of recurrent state a row
DIMS = {"layers": 10, "heads": 16, "kv_heads": 4, "head_dim": 128,
        "matmul_params": 900_000_000, "total_params": 1_000_000_000,
        "state_bytes_per_row": 5 * 2**20}
CELL = {"config": {"dtype": "bfloat16"}, "entry": {"chips": 1}}


def _records(rows=8, prompt=100, tokens=40):
    """``rows`` requests decoding all through the trace: first token at 1.0,
    ``tokens`` more by 1.5, done long after."""
    return [{"first": 1.0, "done": 99.0, "increments": [(1.0, 1), (1.5, tokens)],
             "body": {"token_ids": [0] * prompt}} for _ in range(rows)]


def _step_ctx(track, trace=TRACE, dims=DIMS, **over):
    return {"trace": trace, "flight_trace": {"loop": track}, "cell": CELL,
            "dims": dims, "peaks": PEAKS, "costs": costs,
            "records": _records(), **over}


def test_device_step_time_is_the_traces_share_over_the_tracks_steps():
    track = _loop_track(groups=40, gap=0.289)
    ctx = _step_ctx(track)
    # 90% of the window in step programs, 4 steps every 289 ms
    assert _reader("decode_step_dev_ms").read(ctx) == pytest.approx(
        0.9 * 289 / 4, rel=1e-9)
    assert reduce.step_seconds_in_trace(ctx) == pytest.approx(
        0.9 * 0.289 / 4, rel=1e-9)
    inside = reduce.dispatches_in_trace(ctx)
    assert [k for _t, k in inside] == [4] * 21
    assert inside[0][0] == pytest.approx(1.0 + 4 * 0.289 + 0.002)
    assert reduce.program_seconds(TRACE, "_admit_merge") == (0.1, 9)


@pytest.mark.parametrize("gap_ms", [10, 28, 56])
def test_the_steps_share_reads_the_same_at_any_group_length(gap_ms):
    """Groups of 4 steps 10, 28 and 56 ms apart, the device 2 ms in step
    programs for every step of each: one ``sched.dispatch`` span a group, so
    the step reads 2 ms and the share the same at all three (the retired
    reader counted per-request events throttled to one in 50 ms, and read
    nothing at the first two)."""
    track = _loop_track(groups=int(9.0 / (gap_ms / 1e3)), gap=gap_ms / 1e3)
    busy = 6.0 * (4 * 0.002) / (gap_ms / 1e3)
    trace = {**TRACE, "programs": {"jit__unknown": {"s": busy, "n": 1}}}
    ctx = _step_ctx(track, trace)
    assert _reader("decode_step_dev_ms").read(ctx) == pytest.approx(2.0, rel=1e-9)
    floor = costs.decode_step_floor_s(
        DIMS, "bfloat16", PEAKS, rows=8, context=141)["floor_s"]
    assert _reader("decode_step_mfu_roofline").read(ctx) == pytest.approx(
        100 * floor / 0.002, rel=1e-9)


def test_the_steps_share_is_the_floor_over_the_device_step_time_to_the_digit():
    """``costs.decode_step_floor_s`` at the rows and context the request log
    gives, over ``decode_step_dev_ms``'s own reading of the same trace: the
    retired ``decode_group_roofline``'s quotient with the count of steps
    taken from the spans."""
    ctx = _step_ctx(_loop_track(groups=40, gap=0.289))
    batch = reduce.batch_between(ctx["records"], 2.0, 8.0)
    assert batch == {"rows": 8.0, "context": 141.0}
    floor = costs.decode_step_floor_s(DIMS, "bfloat16", PEAKS, **batch)
    step_ms = _reader("decode_step_dev_ms").read(ctx)
    got = _reader("decode_step_mfu_roofline").read(ctx)
    assert got == 100.0 * floor["floor_s"] / (step_ms / 1e3)
    assert floor["bound_by"] == "memory" and 0 < got < 100


def test_the_steps_floor_holds_every_live_rows_state_twice():
    """A configuration with a recurrent state: the floor's bytes are the
    parameters, the keys and values in flight and 2 x rows x state; without
    the state the same trace reads a lower share."""
    ctx = _step_ctx(_loop_track(groups=40, gap=0.289))
    kv = 10 * 2 * 4 * 128 * 2 * 8 * 141
    by_hand = (2 * 1_000_000_000 + kv + 2 * 8 * 5 * 2**20) / 819e9
    step = 0.9 * 0.289 / 4
    got = _reader("decode_step_mfu_roofline").read(ctx)
    assert got == pytest.approx(100 * by_hand / step, rel=1e-12)
    stateless = {k: v for k, v in DIMS.items() if k != "state_bytes_per_row"}
    less = _reader("decode_step_mfu_roofline").read({**ctx, "dims": stateless})
    assert got - less == pytest.approx(
        100 * (2 * 8 * 5 * 2**20 / 819e9) / step, rel=1e-9)


@pytest.mark.parametrize("missing", [
    "no_trace", "no_device", "no_peaks", "two_spans", "no_track", "no_rows"])
def test_the_steps_share_reads_nothing_where_something_is_missing(missing):
    """None, never a zero and never an exception: a share of a peak that
    reads 0 would be a lie."""
    ctx = _step_ctx(_loop_track(groups=40, gap=0.289))
    assert _reader("decode_step_mfu_roofline").read(ctx) is not None
    ctx.update({
        "no_trace": {"trace": None},
        "no_device": {"trace": {**TRACE, "devices": 0}},
        "no_peaks": {"peaks": None},
        "two_spans": {"flight_trace": {
            "loop": _loop_track(groups=2, gap=0.289, t0=3.0)}},
        "no_track": {"flight_trace": {"requests": {}}},
        "no_rows": {"records": []},
    }[missing])
    assert _reader("decode_step_mfu_roofline").read(ctx) is None
    if missing not in ("no_peaks", "no_rows"):  # the step time needs neither
        assert _reader("decode_step_dev_ms").read(ctx) is None


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
