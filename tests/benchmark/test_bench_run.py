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
    """The three toy cells, each run once; started together, because most of
    a run is waiting for its own window."""
    procs = {
        "open": _toy("tiny-bigcode.toy-open", 2**31 + 5, 3, 0),
        "closed": _toy("tiny-gptj.toy-closed", 7, 2, 1),
        "tp4": _toy("tiny-bigcode-tp4.toy-sat", 9, 2, 0, devices=4),
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


def test_no_tpu_no_result():
    p = _start("--workload", "starcoderbase-1b.gen", "--seed", "1",
               "--seconds", "1", "--trace", "0")
    stdout, stderr = p.communicate(timeout=120)
    assert p.returncode != 0
    assert not any(line.startswith('{"correct"') for line in stdout.splitlines())
    assert "no CPU mode" in stderr
