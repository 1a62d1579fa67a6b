"""Device telemetry plane (utils/devtel.py): compile forensics, counter
tracks, and the observability wiring that rides along with it (/profile
slot stealing, Prometheus label escaping).

CPU-backed like every tier-1 suite: the CONTRACTS under test —
steady-state recompile flagging, Chrome counter-event schema — are
platform-independent.
"""

import time

import pytest

from llmss_tpu.serve.broker import InProcBroker
from llmss_tpu.serve.producer import ProducerServer
from llmss_tpu.utils import devtel, trace
from llmss_tpu.utils import metrics as metrics_mod

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from llmss_tpu.engine import DecodeEngine, GenerationParams  # noqa: E402
from llmss_tpu.engine.scheduler import ContinuousBatcher  # noqa: E402
from llmss_tpu.models.common import DecoderConfig  # noqa: E402
from llmss_tpu.models.decoder import init_params  # noqa: E402
from llmss_tpu.parallel import MeshPlan, make_mesh  # noqa: E402


@pytest.fixture(autouse=True)
def clean_devtel():
    """Every test starts with tracing on and empty accumulators."""
    trace.set_enabled(True)
    trace.recorder().clear()
    devtel.reset()
    yield
    trace.set_enabled(True)
    trace.recorder().clear()
    devtel.reset()


def _tiny_batcher():
    cfg = DecoderConfig(
        model_type="llama", vocab_size=64, hidden_size=32, n_layers=2,
        n_heads=4, n_kv_heads=2, head_dim=8, intermediate_size=64,
        max_position_embeddings=64, activation="silu", norm="rmsnorm",
        norm_eps=1e-5, mlp="swiglu", positions="rotary", rope_style="half",
        rotary_dim=8, attn_bias=False, mlp_bias=False,
        tie_word_embeddings=False, dtype="float32",
    )
    mesh = make_mesh(MeshPlan(dp=2, tp=4))
    params = init_params(cfg, mesh, jax.random.key(0))
    engine = DecodeEngine(
        cfg, params, mesh, max_seq_len=64, kv_layout="paged",
    )
    batcher = ContinuousBatcher(engine, rows=2, chunk_steps=2, group_chunks=2)
    return engine, batcher


@pytest.fixture(scope="module")
def warm(devices):
    """One prewarmed tiny engine+batcher for the whole module (prewarm is
    the expensive part; tests re-enable/reset devtel around it)."""
    trace.set_enabled(True)
    devtel.reset()
    engine, batcher = _tiny_batcher()
    batcher.prewarm()
    return engine, batcher


def _serve(batcher, n=2, max_new=4, prefix="dv"):
    gen = GenerationParams(max_new_tokens=max_new, is_greedy=True)
    got = {}
    for i in range(n):
        batcher.submit(
            [5 + i, 9, 3], gen, lambda t, i=i: got.__setitem__(i, t),
            req_id=f"{prefix}{i}",
        )
    batcher.run_until_idle()
    assert len(got) == n
    return got


# -- counter tracks -----------------------------------------------------------


def test_counter_tracks_pass_chrome_schema(warm):
    engine, batcher = warm
    batcher._devtel_last_t = float("-inf")  # defeat the sampler throttle
    _serve(batcher, n=3, max_new=8, prefix="ctr")
    # The scheduler's group-boundary sampler recorded counter samples;
    # they ride the same Chrome export as the spans.
    doc = trace.to_chrome_trace(
        [trace.recorder().export()], counters=[devtel.export()],
    )
    evs = doc["traceEvents"]
    assert {e["ph"] for e in evs} <= {"M", "X", "i", "C"}
    cs = [e for e in evs if e["ph"] == "C"]
    tracks = {e["name"] for e in cs}
    assert {"rows", "queue_depth", "kv_blocks", "kv_fragmentation"} <= tracks
    assert not {"mfu", "mbu"} & tracks
    for e in cs:
        assert e["ts"] >= 0
        assert e["cat"] == "counter"
        assert isinstance(e["args"], dict) and e["args"]
        for v in e["args"].values():
            assert isinstance(v, (int, float))


def test_largest_run_fragmentation_signal():
    assert devtel.largest_run([]) == 0
    assert devtel.largest_run([4]) == 1
    assert devtel.largest_run([1, 2, 3, 7, 8]) == 3
    assert devtel.largest_run([0, 2, 4]) == 1


# -- compile forensics --------------------------------------------------------


def test_steady_recompile_attributed_and_flagged_on_slo():
    obs = devtel.observer()

    @jax.jit
    def f(x):
        return x * 2 + 1

    obs.watch("f", f)
    f(jnp.ones(4))  # warmup compile
    obs.mark_steady()
    f(jnp.ones(8))  # steady-state recompile: a new shape signature
    obs._last_sample = float("-inf")  # defeat the sweep throttle
    grew = obs.maybe_sample("req-attr")
    assert grew == 1
    ev = [e for e in obs.events() if e.get("req_id") == "req-attr"]
    assert ev and ev[0]["steady_state"] and ev[0]["source"] == "cache_size"
    # The attributed compile span rides the triggering request's timeline.
    names = {e["name"] for e in trace.recorder().events_for("req-attr")}
    assert "compile" in names

    # The REAL /slo payload path flags it (local export via the broker
    # collection the producer uses).
    ps = ProducerServer(broker=InProcBroker())
    flag = ps.slo().get("compile")
    assert flag and flag["flagged"] and flag["steady_state_recompiles"] >= 1
    comp = ps.compiles()
    assert comp["n_compiles"] >= 1
    assert any(e.get("req_id") == "req-attr" for e in comp["compiles"])


def test_trace_off_devtel_silent_zero_recompiles(warm):
    """LLMSS_TRACE=0 gates the whole plane: a warmed batcher serving with
    tracing off must record NOTHING in devtel and, under CompileGuard,
    hit the jit caches exactly as before — zero new compiles."""
    from llmss_tpu.analysis import CompileGuard

    engine, batcher = warm
    trace.set_enabled(False)
    assert not devtel.enabled()
    guard = CompileGuard.for_engine(engine)
    with guard.steady_state():
        _serve(batcher, prefix="off")
    ex = devtel.export()
    assert ex["counters"] == []
    assert ex["compiles"]["events"] == []
    assert ex["compiles"]["steady_recompiles"] == 0


# -- Prometheus rendering -----------------------------------------------------


def test_prometheus_label_value_escaping():
    hostile = 'w"1\\evil\nid'
    text = metrics_mod.render_prometheus(
        {"fleet": {"workers": {hostile: {"tokens_generated": 3}}}},
    )
    line = next(
        ln for ln in text.splitlines() if ln.startswith("llmss_fleet_worker")
    )
    # Escaped per the text-format spec; the raw newline must not survive
    # into the sample line (it would truncate the scrape).
    assert '\\"1' in line and "\\\\evil" in line and "\\nid" in line
    assert line.endswith(" 3")


# -- /profile slot lifecycle --------------------------------------------------


def test_profile_slot_steals_wedged_holder_and_auto_releases(tmp_path):
    from llmss_tpu.serve import producer as producer_mod

    with producer_mod._PROFILE_LOCK:
        saved = (
            producer_mod._PROFILE_ACTIVE, producer_mod._PROFILE_GEN,
            producer_mod._PROFILE_DEADLINE,
        )
    try:
        # A live holder within its deadline still refuses overlap.
        with producer_mod._PROFILE_LOCK:
            producer_mod._PROFILE_GEN += 1
            producer_mod._PROFILE_ACTIVE = producer_mod._PROFILE_GEN
            producer_mod._PROFILE_DEADLINE = time.monotonic() + 30.0
        code, body = producer_mod.start_profile(
            log_dir=str(tmp_path / "a"), duration_s=0.2,
        )
        assert code == 409 and body["retry_after_s"] > 0

        # A wedged holder (deadline blown: its capture thread hung or
        # died) no longer wedges profiling until restart — the slot is
        # stolen, not refused.
        with producer_mod._PROFILE_LOCK:
            producer_mod._PROFILE_DEADLINE = time.monotonic() - 1.0
        code, body = producer_mod.start_profile(
            log_dir=str(tmp_path / "b"), duration_s=0.2,
        )
        assert code == 202 and body.get("stole_wedged_slot") is True

        # The thief's capture auto-stops and frees the slot.
        deadline = time.monotonic() + 10.0
        while True:
            with producer_mod._PROFILE_LOCK:
                if producer_mod._PROFILE_ACTIVE == 0:
                    break
            assert time.monotonic() < deadline, "profile never released"
            time.sleep(0.05)
    finally:
        with producer_mod._PROFILE_LOCK:
            (
                producer_mod._PROFILE_ACTIVE, producer_mod._PROFILE_GEN,
                producer_mod._PROFILE_DEADLINE,
            ) = saved
