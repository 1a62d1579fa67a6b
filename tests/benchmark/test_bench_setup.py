"""What PR 42 added to the benchmark: the four readers of set-up in
``benchmark/lib/setup.py`` on made-up ``/metrics`` blocks (functions there,
not per-layer metrics yet: that module says why), and that nothing the
manifest had has moved: ``per_layer[-1]`` is still ``sampler_search_pct``,
the sets of cells 2, 3 and 4 are what their own tests pin, and no entry
that is there moves ``setup_s`` for another cell than
``starcoderbase-1b.gen``. No device number is produced here."""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark.lib import manifest, setup  # noqa: E402

READERS = {
    "setup_before_prewarm_s": "program_span",
    "prewarm_s": "program_span",
    "prewarm_trace_lower_s": "program_counter",
    "prewarm_compile_s": "program_counter",
}

SUMS = {
    "setup_before_prewarm_s": {"setup.runtime", "setup.weights",
                               "setup.engine", "setup.cache"},
    "prewarm_s": {"setup.prewarm"},
    "prewarm_trace_lower_s": {"setup.jax.trace", "setup.jax.lower"},
    "prewarm_compile_s": {"setup.jax.compile"},
}

# /metrics ``loop.spans`` of a warm replica as the program records it with
# tracing on (made-up seconds; the names are the program's)
SPANS = {
    "loop": {"seconds": 91.5, "count": 3400},
    "setup.runtime": {"seconds": 0.25, "count": 2},
    "setup.weights": {"seconds": 3.5, "count": 1},
    "setup.engine": {"seconds": 0.125, "count": 1},
    "setup.cache": {"seconds": 1.125, "count": 1},
    "setup.prewarm": {"seconds": 66.0, "count": 1},
    "setup.prewarm.decode_group": {"seconds": 30.0, "count": 32},
    "setup.prewarm.drain": {"seconds": 2.0, "count": 1},
    "setup.jax.trace": {"seconds": 21.0, "count": 80},
    "setup.jax.lower": {"seconds": 12.5, "count": 80},
    "setup.jax.compile": {"seconds": 11.25, "count": 80},
    "setup.jax.cache_fetch": {"seconds": 9.0, "count": 67},
}


def ctx(spans):
    return {"metrics_before": {"loop": {"decode_steps": 0, "spans": {}}},
            "metrics_after": {"loop": {"decode_steps": 9, "spans": spans}}}


@pytest.mark.parametrize("name, want", [
    ("setup_before_prewarm_s", 0.25 + 3.5 + 0.125 + 1.125),
    ("prewarm_s", 66.0),
    ("prewarm_trace_lower_s", 21.0 + 12.5),
    ("prewarm_compile_s", 11.25),
])
def test_a_reader_sums_its_names_absolute_after_the_window(name, want):
    read = getattr(setup, name)
    assert read(ctx(SPANS)) == pytest.approx(want)
    # absolute, not a difference over the window: what stood before the
    # window is not subtracted
    c = ctx(SPANS)
    c["metrics_before"] = c["metrics_after"]
    assert read(c) == pytest.approx(want)


@pytest.mark.parametrize("name", sorted(READERS))
def test_a_reader_gives_none_where_the_program_recorded_nothing(name):
    read = getattr(setup, name)
    # tracing off, or the parent: no block, an empty block, no set-up name
    assert read({}) is None
    assert read({"metrics_after": None}) is None
    assert read({"metrics_after": {"loop": {"decode_steps": 9}}}) is None
    assert read(ctx({})) is None
    assert read(ctx({"loop": {"seconds": 1.0, "count": 3}})) is None
    # one of the names it sums is missing: nothing, not a part of the sum;
    # a name it does not sum changes nothing
    for missing in (k for k in SPANS if k.startswith("setup.")):
        got = read(ctx({k: v for k, v in SPANS.items() if k != missing}))
        if missing in SUMS[name]:
            assert got is None
        else:
            assert got == pytest.approx(read(ctx(SPANS)))


def test_the_split_is_inside_what_it_splits():
    c = ctx(SPANS)
    assert (setup.prewarm_trace_lower_s(c) + setup.prewarm_compile_s(c)
            <= setup.prewarm_s(c))
    assert setup.seconds(c, "setup.jax.cache_fetch") <= (
        setup.prewarm_compile_s(c))
    assert setup.seconds(c, "setup.prewarm", "no.such.span") is None


def test_the_manifest_is_where_it_was_and_the_readers_are_undeclared():
    """An accepted test pins ``per_layer[-1]``, so an entry could only go in
    the middle of the list, which reads as an edit of what was there: the
    four readers stay functions (PR 38's pattern). Should a ``benchmark`` PR
    declare them, they move ``setup_s`` on the set-up layer, in seconds."""
    m = manifest.load()
    assert m["per_layer"][-1]["name"] == "sampler_search_pct"
    assert len(m["per_layer"]) >= 16
    declared = {e["name"]: e for e in m["per_layer"]}
    files = {p.stem for p in (ROOT / "benchmark" / "layer_metrics").glob("*.py")}
    assert set(declared) == files
    for name, source in READERS.items():
        assert callable(getattr(setup, name))
        e = declared.get(name)
        if e is not None:
            assert (e["unit"], e["better"], e["layer"], e["moves"]) == (
                "s", "lower", "set-up", "setup_s")
            assert e["source"] == source
            assert e["workloads"][0] == "starcoderbase-1b.gen"
    # nothing that is there moves setup_s yet: every accepted metric moves
    # a tail
    assert {e["moves"] for e in m["per_layer"] if e["name"] not in READERS} == {
        "ttft_p90_ms", "tpot_p90_ms"}


def test_the_sets_of_the_other_cells_are_what_their_tests_pin():
    m = manifest.load()
    common = {"decode_step_dev_ms", "host_turn_pct", "loop_host_ms_per_step",
              "host_ms_per_group", "first_token_p50_ms",
              "decode_step_mfu_roofline"}
    waits = {"gen_late_p90_ms", "queue_wait_p50_ms", "broker_wait_p50_ms",
             "row_wait_p50_ms", "first_token_lag_p50_ms", "stream_lag_p50_ms"}
    want = {
        "falcon-h1-34b-1chip.chat": common | {
            "ssm_pct", "ssm_decode_roofline", "sampler_search_pct"},
        "kanana-2-30b-a3b-1chip.doc": common | waits,
        "olmo-hybrid-7b-1chip.chat": common | waits,
    }
    for cell, names in want.items():
        got = {e["name"] for e in manifest.cell(m, cell)["per_layer"]}
        assert got == names
    first = {e["name"] for e in
             manifest.cell(m, "starcoderbase-1b.gen")["per_layer"]}
    assert len(first - set(READERS)) == 13 and "long_group_pct" in first
