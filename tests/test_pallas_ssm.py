"""The state pool's update kernel (``ops.pallas_ssm``, interpret mode on the
CPU) against the XLA oracles ``ops.ssm.ssm_step`` (one position) and
``ops.ssm.ssd_scan`` (a mixed step's few) on the layer sliced out of the pool,
and a small Falcon-H1 served through mixed and decode groups with the kernel
forced on against the same engine on the XLA path."""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llmss_tpu.engine import DecodeEngine, GenerationParams
from llmss_tpu.engine.scheduler import ContinuousBatcher
from llmss_tpu.models import decoder
from llmss_tpu.models.decoder import init_params
from llmss_tpu.models.registry import config_from_hf
from llmss_tpu.ops import pallas_ssm
from llmss_tpu.ops.attention import force_impl
from llmss_tpu.ops.ssm import ssd_scan, ssm_step
from llmss_tpu.parallel import MeshPlan, make_mesh
from llmss_tpu.utils import trace
from tests.test_falcon_h1 import HF as FALCON_H1

L, ROWS, P, N = 3, 4, 16, 128


def _inputs(H, G, T, lens, seed=0):
    rng = np.random.default_rng(seed)

    def f(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    lens = jnp.asarray(lens, jnp.int32)
    live = jnp.arange(T)[None, :] < lens[:, None]
    # dt as the mixer hands it over: 0 from a row's length on
    dt = jnp.where(live[..., None], jnp.abs(f(ROWS, T, H)) * 0.3, 0.0)
    return dict(
        pool=f(L, ROWS, H, P, N), x=f(ROWS, T, H, P), dt=dt,
        A=-jnp.abs(f(H)) - 0.05, Bm=f(ROWS, T, G, N), Cm=f(ROWS, T, G, N),
        lens=lens,
    ), np.asarray(live)


@pytest.mark.parametrize("layer", [0, L - 1], ids=["first", "last"])
@pytest.mark.parametrize("groups", [1, 2])
@pytest.mark.parametrize(
    # 8 heads a grid step: one whole block, a block and a half, half a block
    "heads", [8, 12, 4], ids=["H8", "H12-overhang", "H4-narrow"],
)
@pytest.mark.parametrize(
    "T,lens", [(1, [1, 0, 1, 1]), (4, [0, 1, 4, 3])], ids=["step", "chunk"],
)
def test_kernel_matches_the_oracle(T, lens, heads, groups, layer):
    x, live = _inputs(heads, groups, T, lens, seed=heads + groups + T)
    assert pallas_ssm.supports(heads, P, N, groups, T)
    y, pool = pallas_ssm.ssm_pool_update(
        x["pool"], x["x"], x["dt"], x["A"], x["Bm"], x["Cm"], x["lens"],
        jnp.int32(layer), interpret=True,
    )
    old = x["pool"][layer]
    if T == 1:
        y_ref, s_ref = ssm_step(
            x["x"][:, 0], x["dt"][:, 0], x["A"], x["Bm"][:, 0], x["Cm"][:, 0],
            old,
        )
        y_ref = y_ref[:, None]
    else:
        y_ref, s_ref = ssd_scan(
            x["x"], x["dt"], x["A"], x["Bm"], x["Cm"], old, chunk=8
        )
    y, pool, before = np.asarray(y), np.asarray(pool), np.asarray(x["pool"])
    assert y.shape == (ROWS, T, heads, P) and np.isfinite(y).all()
    # every real position; a later one reads zero, and nobody reads it
    np.testing.assert_allclose(
        y[live], np.asarray(y_ref)[live], rtol=2e-5, atol=2e-5
    )
    assert not y[~live].any()
    np.testing.assert_allclose(
        pool[layer], np.asarray(s_ref), rtol=2e-6, atol=2e-6
    )
    # a row of length 0 keeps its state, and every other layer its own, BIT
    # for bit: both were moved, not computed on
    for b, n in enumerate(lens):
        if n == 0:
            assert (pool[layer, b] == before[layer, b]).all()
    others = [l for l in range(L) if l != layer]
    assert (pool[others] == before[others]).all()


@pytest.mark.parametrize(
    "kwargs",
    [
        dict(d_state=64),  # a state row that is not whole lanes
        dict(head_dim=20),  # does not tile the sublanes
        dict(head_dim=256),  # more than one transpose holds
        dict(chunk=16),  # an admission's scan, not a step's few positions
        dict(chunk=0),
        dict(n_heads=30, n_groups=4),  # heads in no whole groups
        dict(d_state=2048),  # [8, 128, 2048] four times over: VMEM
        dict(dtype=jnp.bfloat16),  # the state is float32
    ],
    ids=lambda kw: "-".join(f"{k}={v}" for k, v in kw.items()),
)
def test_supports_refuses(kwargs):
    ok = dict(n_heads=32, head_dim=128, d_state=256, n_groups=2, chunk=4)
    assert pallas_ssm.supports(**ok)
    assert pallas_ssm.supports(**{**ok, "chunk": 1})
    assert not pallas_ssm.supports(**{**ok, **kwargs})


# tests/test_falcon_h1.py's small Falcon-H1 with a state row of whole lanes
HF = {**FALCON_H1, "mamba_d_state": 128}


def _engine(mesh):
    cfg = config_from_hf(types.SimpleNamespace(**HF), dtype="float32")
    return DecodeEngine(
        cfg, init_params(cfg, mesh, jax.random.key(3)), mesh,
        kv_layout="paged", max_seq_len=128,
    )


@pytest.mark.filterwarnings("ignore:pallas forced")
def test_mixed_and_decode_groups_update_the_pool_in_place(devices):
    """Five requests through two rows, prompts streamed 4 tokens a row a
    step beside rows that decode, rows done beside rows live, rows freed and
    admitted again: with the kernel forced on (interpreted) every request's
    tokens are those of the XLA path, and every group's ``sched.dispatch``
    span says which update its program was traced with."""
    mesh = make_mesh(MeshPlan(tp=1), devices=devices[:1])
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, 256, n).tolist() for n in (21, 40, 37, 9, 30)]
    gens = [GenerationParams(max_new_tokens=n, is_greedy=True)
            for n in (12, 5, 9, 14, 7)]

    def serve(eng, how):
        batcher = ContinuousBatcher(eng, rows=2, chunked_prefill=4)
        assert decoder.state_update(eng.cfg, batcher.cache, mesh, 4) == how
        assert decoder.state_update(eng.cfg, batcher.cache, mesh, 1) == how
        got = {}
        for i, (p, g) in enumerate(zip(prompts, gens)):
            batcher.submit(
                p, g, lambda toks, i=i, **kw: got.__setitem__(i, toks))
        trace.recorder().clear()
        batcher.run_until_idle()
        spans = [sp[5] for sp in trace.recorder().loop_spans()
                 if sp[2] == "sched.dispatch"]
        assert {a["kind"] for a in spans} == {"ragged_group", "decode_group"}
        assert {a["state_update"] for a in spans} == {how}
        return [got[i] for i in range(len(prompts))]

    was = trace.enabled()
    trace.set_enabled(True)
    try:
        eng = _engine(mesh)
        expected = serve(eng, "xla")
        with force_impl("pallas"):
            assert serve(_engine(mesh), "ssm.kernel") == expected
        with force_impl("xla"):  # the override's other word: never
            cache = eng.new_paged_cache(2)
            assert decoder.state_update(eng.cfg, cache, mesh, 1) == "xla"
    finally:
        trace.set_enabled(was)
