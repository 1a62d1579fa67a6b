"""benchmark/run.py end to end: a toy cell on the CPU backend under the
harness's test-only flag, and a non-zero exit without a TPU when the flag is
absent. Nothing here is a device number."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
TOY = "tests/benchmark/toy/BENCHMARK.json"


def _start(*args, devices=1):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "BENCH_RUN": "ignored",
           "XLA_FLAGS": f"--xla_force_host_platform_device_count={devices}"}
    return subprocess.Popen(
        [sys.executable, "benchmark/run.py", *args], cwd=ROOT, env=env,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
    )


def _toy(cell, seed, seconds, trace, devices=1):
    return _start("--manifest", TOY, "--allow-cpu", "--workload", cell,
                  "--seed", str(seed), "--seconds", str(seconds),
                  "--trace", str(trace), devices=devices)


@pytest.fixture(scope="module")
def toy_runs():
    """The three toy cells, each run once, and a sweep of one of them;
    started together, because most of a run is waiting for its own window."""
    procs = {
        "open": _toy("tiny-bigcode.toy-open", 2**31 + 5, 3, 0),
        "closed": _toy("tiny-gptj.toy-closed", 7, 2, 1),
        "tp4": _toy("tiny-bigcode-tp4.toy-sat", 9, 2, 0, devices=4),
        "broken": _start(
            "--manifest", TOY, "--allow-cpu", "--fault", "stream_token",
            "--workload", "tiny-bigcode.toy-open", "--seed", "11",
            "--seconds", "2", "--trace", "0"),
        "sweep": _start("--manifest", TOY, "--allow-cpu", "--sweep",
                        "tiny-bigcode.toy-open", "--rates", "2,4", "--seeds",
                        "5,6,7", "--seconds", "2"),
    }
    out = {}
    try:
        for name, p in procs.items():
            stdout, stderr = p.communicate(timeout=300)
            out[name] = (p.returncode, stdout, stderr)
    finally:
        for p in procs.values():
            if p.poll() is None:
                p.kill()
                p.wait()
    return out


def _last(run):
    rc, stdout, stderr = run
    assert rc == 0, stderr[-3000:]
    last = json.loads(stdout.strip().splitlines()[-1])
    assert last["correct"] is True, stdout[-3000:]
    return last


def test_a_toy_cell_end_to_end_on_the_cpu(toy_runs):
    last = _last(toy_runs["open"])
    assert set(last) == {"correct", "attempted", "failed", "metrics", "device"}
    assert last["attempted"] == 12 and last["failed"] == 0
    assert set(last["metrics"]) == {"ttft_p90_ms", "tpot_p90_ms", "setup_s"}
    assert all(m["value"] > 0 for m in last["metrics"].values())
    assert last["device"]["platform"] == "cpu" and last["device"]["count"] == 1
    detail = json.loads(toy_runs["open"][1].strip().splitlines()[-2])
    assert detail["compilations_in_window"] == 0
    assert detail["logits"]["ok"] and not detail["faults"]


def test_a_traced_saturated_toy_cell_reports_its_layer_metrics(toy_runs):
    last = _last(toy_runs["closed"])
    # counters are there; the CPU has no device plane, so no device-trace
    # metric, no busy_s and no breakdown appear
    assert set(last["metrics"]) == {"host_ms_per_group"}
    assert "busy_s" not in last["device"] and "breakdown" not in last


def test_a_four_device_toy_cell_runs_tensor_parallel(toy_runs):
    last = _last(toy_runs["tp4"])
    assert set(last["metrics"]) == {"total_tok_s", "setup_s"}
    assert last["device"]["count"] == 4
    assert "on 4 device(s)" in toy_runs["tp4"][2]


def test_a_run_whose_served_path_alters_a_token_is_not_correct(toy_runs):
    """The whole of a run but the look for a chip, with the served path
    broken underneath (one streamed token altered where the worker hands it
    to the broker): the run ends, and `correct` comes out false."""
    rc, stdout, stderr = toy_runs["broken"]
    assert rc == 0, stderr[-3000:]
    lines = stdout.strip().splitlines()
    last, detail = json.loads(lines[-1]), json.loads(lines[-2])
    assert last["correct"] is False
    assert last["attempted"] == 8 and last["failed"] == 0
    assert any("streamed increments differ" in f for f in detail["faults"])
    # the model itself was sound: the logits check alone would have passed
    assert detail["logits"]["ok"]
    assert "[bench] correct: False" in stderr.splitlines()[-1]


def test_a_sweep_over_seeds_gives_a_row_a_rate_with_the_range(toy_runs):
    rc, stdout, stderr = toy_runs["sweep"]
    assert rc == 0, stderr[-3000:]
    last = json.loads(stdout.strip().splitlines()[-1])
    assert [r["rate"] for r in last["rows"]] == [2.0, 4.0]
    assert [(w["rate"], w["seed"]) for w in last["windows"]] == [
        (r, s) for r in (2.0, 4.0) for s in (5, 6, 7)]
    for row in last["rows"]:
        mine = [w for w in last["windows"] if w["rate"] == row["rate"]]
        assert row["seeds"] == [5, 6, 7]
        for name in ("ttft_p90_ms", "tpot_p90_ms"):
            vals = [w[name] for w in mine]
            assert row[name]["min"] == min(vals) and row[name]["max"] == max(vals)
            assert row[name]["range_share"] == pytest.approx(
                (max(vals) - min(vals)) / row[name]["median"])
        assert row["in_flight_max"] == max(w["in_flight_max"] for w in mine) >= 1
        assert all(w["compilations"] == 0 for w in mine)
    assert last["knee"] == max(
        (r["rate"] for r in last["rows"] if r["sustained"]), default=None)


def test_a_rate_whose_tail_moves_with_the_seed_is_not_sustained():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "bench_run", ROOT / "benchmark" / "run.py")
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    bounds = {"ttft_p90_ms": 0.06, "tpot_p90_ms": 0.01}

    def window(seed, ttft, tpot=74.0, sustained=True, in_flight=40):
        return {"seed": seed, "ttft_p90_ms": ttft, "tpot_p90_ms": tpot,
                "sustained": sustained, "in_flight_max": in_flight}

    steady = run.sweep_rate(2.0, [
        window(1, 830.0), window(2, 840.0), window(3, 850.0)], bounds)
    assert steady["sustained"] and steady["seeds"] == [1, 2, 3]
    assert steady["ttft_p90_ms"] == {
        "median": 840.0, "min": 830.0, "max": 850.0,
        "range_share": pytest.approx(20 / 840)}
    # one seed's arrivals reach the scheduler's busy mode: every window is
    # answered and flat, but the tail cannot be read at this rate
    edge = run.sweep_rate(2.4, [
        window(1, 845.0), window(2, 850.0), window(3, 910.0, in_flight=49)],
        bounds)
    assert not edge["sustained"] and edge["in_flight_max"] == 49
    assert edge["ttft_p90_ms"]["range_share"] == pytest.approx(65 / 850)
    # the between-token tail has a bound of its own; a window that is not
    # sustained by itself fails the rate
    assert not run.sweep_rate(2.0, [
        window(1, 840.0, 73.0), window(2, 841.0, 74.0)], bounds)["sustained"]
    assert not run.sweep_rate(2.0, [
        window(1, 840.0), window(2, 841.0, sustained=False)], bounds)["sustained"]
    # the generator's log: requests overlapping at one instant of the window
    recs = [{"sent": 0.0, "first": 0.5, "done": 3.0},
            {"sent": 1.0, "first": 1.5, "done": 2.0},
            {"sent": 2.0, "first": 2.5, "done": None},
            {"sent": 9.0, "first": None, "done": None}]
    assert run.in_flight_max(recs, "sent", 0.0, 5.0) == 2
    assert run.in_flight_max(recs, "first", 0.0, 5.0) == 2
    assert run.in_flight_max(recs, "sent", 2.5, 5.0) == 2
    assert run.in_flight_max(recs, "sent", 3.5, 5.0) == 1


def test_no_tpu_no_result():
    p = _start("--workload", "starcoderbase-1b.gen", "--seed", "1",
               "--seconds", "1", "--trace", "0")
    stdout, stderr = p.communicate(timeout=120)
    assert p.returncode != 0
    assert not any(line.startswith('{"correct"') for line in stdout.splitlines())
    assert "no CPU mode" in stderr
