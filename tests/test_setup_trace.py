"""Set-up from inside (``docs/observability.md``, "Set-up").

- every phase of a replica's bring-up is a span on the loop track
  (``setup.runtime``, ``setup.weights``, ``setup.engine``, ``setup.cache``,
  ``setup.prewarm`` and its children), each call of a step program by a
  prewarm is one ``setup.prewarm.<family>`` child from a closed set of names,
  and their seconds reach ``/metrics`` ``loop.spans`` even where they closed
  before an ``EngineMetrics`` existed;
- JAX's own trace / lower / compile seconds are kept until ``mark_steady()``
  and put down to the span they were spent in, an inner trace not counted
  twice; after it a compile is a steady-state recompile as before;
- with tracing off: no span, no sum, no listener, the same programs.
"""

import jax
import jax.numpy as jnp
import pytest

from llmss_tpu.engine import DecodeEngine
from llmss_tpu.models.common import DecoderConfig
from llmss_tpu.models.decoder import init_params
from llmss_tpu.parallel import MeshPlan, make_mesh, mesh as mesh_mod
from llmss_tpu.serve.broker import InProcBroker
from llmss_tpu.serve.consumer import ContinuousWorker
from llmss_tpu.utils import devtel, trace
from llmss_tpu.utils.metrics import EngineMetrics

JAX_NAMES = ("setup.jax.trace", "setup.jax.lower", "setup.jax.compile")
TRACE, LOWER, COMPILE, FETCH = devtel._JAX_SECONDS


@pytest.fixture(autouse=True)
def clean():
    """Every test starts with tracing on, an empty recorder and an observer
    that is not steady."""
    trace.set_enabled(True)
    trace.recorder().clear()
    devtel.reset()
    yield
    trace.set_enabled(True)
    trace.recorder().clear()
    devtel.reset()


def _worker(chunked=None):
    """The whole bring-up of a toy replica but the prewarm."""
    cfg = DecoderConfig(
        model_type="llama", vocab_size=64, hidden_size=32, n_layers=2,
        n_heads=4, n_kv_heads=2, head_dim=8, intermediate_size=64,
        max_position_embeddings=64, activation="silu", norm="rmsnorm",
        norm_eps=1e-5, mlp="swiglu", positions="rotary", rope_style="half",
        rotary_dim=8, attn_bias=False, mlp_bias=False,
        tie_word_embeddings=False, dtype="float32",
    )
    mesh = make_mesh(MeshPlan(tp=1), devices=jax.devices()[:1])
    params = init_params(cfg, mesh, jax.random.key(0))
    engine = DecodeEngine(
        cfg, params, mesh, max_seq_len=32, kv_layout="paged",
    )
    return ContinuousWorker(
        engine, InProcBroker(), rows=2, chunk_steps=2,
        chunked_prefill=chunked,
    )


def _setup_spans():
    return [sp for sp in trace.recorder().loop_spans()
            if sp[2].startswith("setup.")]


def _spans_of(worker):
    return worker.engine.metrics.to_dict()["loop"]["spans"]


@pytest.mark.parametrize("chunked", [None, 4])
def test_a_prewarm_is_one_span_with_a_child_for_every_program_call(chunked):
    devtel.install_monitoring_hook()
    w = _worker(chunked)
    n = w.prewarm(seq_buckets=[16])
    spans = _setup_spans()
    (prewarm,) = [sp for sp in spans if sp[2] == "setup.prewarm"]
    seq, parent, _name, t0, dur, attrs = prewarm
    assert parent is None and attrs["executables"] == n
    children = [sp for sp in spans if sp[1] == seq]
    families = [sp for sp in children
                if sp[2] not in ("setup.prewarm.drain", "setup.prewarm.gc")]
    assert len(families) >= n
    assert {sp[2] for sp in families} <= set(devtel.PREWARM_SPANS.values())
    want = {"decode_group", "admit_merge", "merge_positions"} | (
        {"ragged_group"} if chunked else {"prefill_row"})
    assert {sp[2].rsplit(".", 1)[1] for sp in families} == want
    for sp in families:
        a = sp[5]
        # JAX's seconds lie inside the call, and a call that compiled has
        # some (a jit of a function another engine of this process already
        # compiled, ``admit_merge``, finds its program: ``compiled`` False)
        jax_s = sum(a.get(k, 0.0) for k in ("trace_s", "lower_s", "compile_s"))
        assert jax_s <= sp[4] + 1e-3
        assert (jax_s > 0) == a["compiled"]
        if sp[2] in ("setup.prewarm.decode_group",
                     "setup.prewarm.ragged_group",
                     "setup.prewarm.prefill_row"):
            assert a["compiled"] is True
        assert t0 <= sp[3] and sp[3] + sp[4] <= t0 + dur + 1e-6
    keys = {sp[2]: set(sp[5]) for sp in families}
    assert {"chunks", "k", "t_bucket"} <= keys["setup.prewarm.decode_group"]
    assert "P" in keys["setup.prewarm.admit_merge"]
    if not chunked:
        assert {"P", "S"} <= keys["setup.prewarm.prefill_row"]
    assert [sp[2] for sp in children[-2:]] == [
        "setup.prewarm.drain", "setup.prewarm.gc"]
    if chunked:
        return
    # the same envelope on a second replica built alike
    trace.recorder().clear()
    devtel.reset()
    w2 = _worker(chunked)
    assert w2.prewarm(seq_buckets=[16]) == n
    again = [sp for sp in _setup_spans()
             if sp[2] in devtel.PREWARM_SPANS.values()]
    assert len(again) == len(families)


def test_metrics_hold_every_phase_with_the_seconds_of_its_spans():
    devtel.install_monitoring_hook()
    w = _worker()
    w.prewarm(seq_buckets=[16])
    got = _spans_of(w)
    by_name: dict = {}
    for sp in _setup_spans():
        acc = by_name.setdefault(sp[2], [0.0, 0])
        acc[0] += sp[4]
        acc[1] += 1
    for name in ("setup.runtime", "setup.weights", "setup.engine",
                 "setup.cache", "setup.prewarm", "setup.prewarm.drain",
                 "setup.prewarm.gc", "setup.prewarm.decode_group"):
        assert name in by_name
    for name, (seconds, count) in by_name.items():
        assert got[name]["count"] == count
        assert got[name]["seconds"] == pytest.approx(seconds, abs=1e-4)
    # the phases that closed before the engine's metrics existed carry
    # their attributes on the track
    attrs = {sp[2]: sp[5] for sp in _setup_spans() if sp[5]}
    assert attrs["setup.runtime"]["devices"] == 1
    assert attrs["setup.weights"]["bytes"] > 0
    assert attrs["setup.cache"]["bytes"] > 0
    # JAX's seconds: the sum over the spans they were put down to
    for name, attr in (("setup.jax.trace", "trace_s"),
                       ("setup.jax.lower", "lower_s"),
                       ("setup.jax.compile", "compile_s")):
        on_spans = sum((sp[5] or {}).get(attr, 0.0) for sp in _setup_spans())
        assert got[name]["count"] > 0
        assert got[name]["seconds"] == pytest.approx(on_spans, abs=1e-3)
    in_prewarm = sum(got[n]["seconds"] for n in JAX_NAMES)
    assert in_prewarm < sum(
        got[n]["seconds"] for n in by_name if n.count(".") == 1)


def test_after_steady_a_compile_is_a_recompile_and_no_setup_second():
    devtel.install_monitoring_hook()
    w = _worker()
    w.prewarm(seq_buckets=[16])
    obs = devtel.observer()
    assert obs.export()["steady"] and obs.export()["steady_recompiles"] == 0
    before = {n: dict(_spans_of(w)[n]) for n in JAX_NAMES}
    n_spans = len(_setup_spans())
    jax.jit(lambda x: x * 3 + 1)(jnp.ones(7)).block_until_ready()
    ex = obs.export()
    assert ex["steady_recompiles"] >= 1
    assert ex["events"][-1]["steady_state"]
    assert ex["events"][-1]["name"] == "backend_compile_duration"
    assert {n: _spans_of(w)[n] for n in JAX_NAMES} == before
    assert len(_setup_spans()) == n_spans


def test_tracing_off_no_span_no_sum_no_listener(monkeypatch):
    devtel.install_monitoring_hook()
    traced = _worker().prewarm(seq_buckets=[16])
    trace.recorder().clear()
    trace.set_enabled(False)
    installed = []
    monkeypatch.setattr(devtel, "_HOOK_INSTALLED", False)
    monkeypatch.setattr(
        devtel, "install_monitoring_hook", lambda: installed.append(1))
    monkeypatch.setattr(mesh_mod, "_initialized", False)
    monkeypatch.setattr(mesh_mod, "_initialize", lambda *a: None)
    mesh_mod.initialize_runtime()
    assert devtel.setup_span("setup.runtime") is trace.NO_LOOP_SPAN
    w = _worker()
    assert w.prewarm(seq_buckets=[16]) == traced
    assert installed == []
    assert trace.recorder().loop_spans() == []
    assert _spans_of(w) == {}
    assert trace.recorder()._setup_held == {}


def test_runtime_start_installs_the_listener_when_tracing(monkeypatch):
    installed = []
    monkeypatch.setattr(
        devtel, "install_monitoring_hook", lambda: installed.append(1))
    monkeypatch.setattr(mesh_mod, "_initialized", False)
    monkeypatch.setattr(mesh_mod, "_initialize", lambda *a: None)
    mesh_mod.initialize_runtime()
    assert installed == [1]
    assert [sp[2] for sp in _setup_spans()] == ["setup.runtime"]


def test_the_non_continuous_prewarm_has_its_span_too():
    devtel.install_monitoring_hook()
    engine = _worker().engine
    trace.recorder().clear()
    n = engine.prewarm(2, chunk_steps=2)
    spans = _setup_spans()
    (prewarm,) = [sp for sp in spans if sp[2] == "setup.prewarm"]
    assert prewarm[5]["executables"] == n
    children = [sp for sp in spans if sp[1] == prewarm[0]]
    assert [sp[2] for sp in children].count("setup.prewarm.drain") == 1
    names = [sp[2] for sp in children if sp[2] != "setup.prewarm.drain"]
    assert len(names) == n
    assert set(names) == {"setup.prewarm.prefill", "setup.prewarm.decode",
                          "setup.prewarm.decode_group"}


def test_sums_wait_in_the_recorder_until_metrics_adopt_them():
    rec = trace.FlightRecorder(proc="p")
    rec.add_setup("setup.runtime", 0.25)
    rec.add_setup("setup.runtime", 0.5)
    rec.add_setup("setup.jax.trace", 1.0)
    m = EngineMetrics()
    rec.adopt_setup(m.add_loop_span)
    rec.add_setup("setup.jax.trace", 2.0)
    got = {k: v for k, v in m.to_dict()["loop"]["spans"].items()
           if k in ("setup.runtime", "setup.jax.trace")}
    assert got == {
        "setup.runtime": {"seconds": 0.75, "count": 2},
        "setup.jax.trace": {"seconds": 3.0, "count": 2},
    }
    # a later engine's metrics take over, and start from nothing held
    m2 = EngineMetrics()
    rec.adopt_setup(m2.add_loop_span)
    rec.add_setup("setup.cache", 0.125)
    assert "setup.cache" not in m.to_dict()["loop"]["spans"]
    assert m2.to_dict()["loop"]["spans"]["setup.cache"] == {
        "seconds": 0.125, "count": 1}


def test_an_inner_trace_is_not_counted_twice():
    """JAX reports a jitted function traced inside another's trace (or
    lowering) with a duration of its own; only the outermost counts."""
    obs = devtel.CompileObserver()
    m = EngineMetrics()
    with devtel.setup_span("setup.weights") as outer:
        with devtel.setup_span("setup.cache") as inner:
            obs.on_monitoring_scalar(TRACE, 0.0)
            obs.on_monitoring_scalar(TRACE, 0.0)
            obs.on_monitoring_event(TRACE, 0.25)  # the inner one: dropped
            obs.on_monitoring_event(TRACE, 1.0)
            obs.on_monitoring_scalar(LOWER, 0.0)
            obs.on_monitoring_scalar(TRACE, 0.0)
            obs.on_monitoring_event(TRACE, 0.125)  # inside a lowering
            obs.on_monitoring_event(LOWER, 0.5)
            obs.on_monitoring_scalar(COMPILE, 0.0)
            obs.on_monitoring_event(FETCH, 0.25)  # a part of the compile
            obs.on_monitoring_event(COMPILE, 2.0)
            assert inner.seq is not None and outer.seq is not None
    spans = {sp[2]: sp for sp in _setup_spans()}
    assert spans["setup.cache"][1] == spans["setup.weights"][0]
    assert spans["setup.cache"][5] == {
        "trace_s": 1.0, "lower_s": 0.5, "compile_s": 2.0,
        "cache_fetch_s": 0.25}
    assert spans["setup.weights"][5] is None
    got = m.to_dict()["loop"]["spans"]
    assert {n: got[n] for n in got if n.startswith("setup.jax.")} == {
        "setup.jax.trace": {"seconds": 1.0, "count": 1},
        "setup.jax.lower": {"seconds": 0.5, "count": 1},
        "setup.jax.compile": {"seconds": 2.0, "count": 1},
        "setup.jax.cache_fetch": {"seconds": 0.25, "count": 1},
    }
    assert [e["name"] for e in obs.events()] == ["backend_compile_duration"]
