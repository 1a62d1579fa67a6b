"""The delta rule's update kernel (``ops.pallas_gdn``, interpret mode on the
CPU) against the XLA oracles ``ops.gdn.gdn_step`` (one position) and
``ops.gdn.gdn_chunked`` (a mixed step's few) on the layer sliced out of the
pool, and ``models.decoder.state_update``'s answer for the three kinds of
config."""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llmss_tpu.engine import DecodeEngine
from llmss_tpu.models import decoder
from llmss_tpu.models.decoder import init_params
from llmss_tpu.models.registry import config_from_hf
from llmss_tpu.ops import pallas_gdn
from llmss_tpu.ops.attention import force_impl
from llmss_tpu.ops.gdn import gdn_chunked, gdn_step, l2_normalize
from llmss_tpu.parallel import MeshPlan, make_mesh
from tests.test_falcon_h1 import HF as FALCON_H1
from tests.test_olmo_hybrid import HF as OLMO_HYBRID
from tests.test_qwen3_next import HF as QWEN3_NEXT

L, ROWS, DK = 3, 4, 16


def _inputs(H, Hk, Dv, T, lens, seed=0):
    rng = np.random.default_rng(seed)

    def f(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    lens = jnp.asarray(lens, jnp.int32)
    live = jnp.arange(T)[None, :] < lens[:, None]
    # as the mixer hands them over: keys of unit length, a value head each,
    # g and beta 0 from a row's length on
    q, k = (
        jnp.repeat(l2_normalize(f(ROWS, T, Hk, DK)), H // Hk, axis=2)
        for _ in range(2)
    )
    g = jnp.where(live[..., None], -jnp.abs(f(ROWS, T, H)) * 0.3, 0.0)
    beta = jnp.where(live[..., None], jax.nn.sigmoid(f(ROWS, T, H)) * 2, 0.0)
    return dict(
        pool=f(L, ROWS, H, DK, Dv), q=q * DK ** -0.5, k=k, v=f(ROWS, T, H, Dv),
        g=g, beta=beta, lens=lens,
    ), np.asarray(live)


@pytest.mark.parametrize("layer", [0, L - 1], ids=["first", "last"])
@pytest.mark.parametrize(
    # value heads a key head: equal, grouped; 12 heads are a block of 8 and
    # one that hangs over where the budget is a block of 8's (the cell's 30
    # heads under a block of 24 are the same two turns at 2.5 times the cost)
    "heads,key_heads,budget",
    [(4, 4, None), (4, 2, None), (12, 12, 8)],
    ids=["H4", "H4-grouped", "H12-overhang"],
)
@pytest.mark.parametrize("Dv", [128, 192], ids=["Dv128", "Dv192-ragged-lanes"])
@pytest.mark.parametrize(
    "T,lens",
    [(1, [1, 0, 1, 1]), (4, [0, 1, 4, 3]), (8, [8, 0, 1, 5]), (4, [0] * 4)],
    ids=["step", "chunk4", "chunk8", "none-live"],
)
def test_kernel_matches_the_oracle(
    monkeypatch, T, lens, Dv, heads, key_heads, budget, layer,
):
    if budget is not None:
        monkeypatch.setattr(
            pallas_gdn, "_VMEM_BUDGET",
            pallas_gdn._vmem_bytes(budget, T, DK, Dv),
        )
        assert pallas_gdn._head_block(heads, T, DK, Dv) == budget
    else:
        assert pallas_gdn._head_block(heads, T, DK, Dv) == heads
    x, live = _inputs(heads, key_heads, Dv, T, lens, seed=heads + Dv + T)
    assert pallas_gdn.supports(heads, DK, Dv, T)
    # not through the jitted entry: its cache would keep the first budget
    o, pool = pallas_gdn.gdn_pool_update.__wrapped__(
        x["pool"], x["q"], x["k"], x["v"], x["g"], x["beta"], x["lens"],
        pallas_gdn.live_rows(x["lens"]), jnp.int32(layer), interpret=True,
    )
    old = x["pool"][layer]
    if T == 1:
        o_ref, s_ref = gdn_step(
            x["q"][:, 0], x["k"][:, 0], x["v"][:, 0], x["g"][:, 0],
            x["beta"][:, 0], old,
        )
        o_ref = o_ref[:, None]
    else:
        o_ref, s_ref = gdn_chunked(
            x["q"], x["k"], x["v"], x["g"], x["beta"], old
        )
    o, pool, before = np.asarray(o), np.asarray(pool), np.asarray(x["pool"])
    assert o.shape == (ROWS, T, heads, Dv) and np.isfinite(o).all()
    # every real position; a later one reads zero, and nobody reads it
    np.testing.assert_allclose(
        o[live], np.asarray(o_ref)[live], rtol=2e-5, atol=2e-5
    )
    assert not o[~live].any()
    np.testing.assert_allclose(
        pool[layer], np.asarray(s_ref), rtol=2e-5, atol=2e-5
    )
    # a row of length 0 keeps its state, and every other layer its own, BIT
    # for bit: neither was touched
    for b, n in enumerate(lens):
        if n == 0:
            assert (pool[layer, b] == before[layer, b]).all()
    others = [l for l in range(L) if l != layer]
    assert (pool[others] == before[others]).all()


@pytest.mark.parametrize(
    "lens,rows,n",
    [
        ([0, 3, 0, 1], [1, 3, 3, 3], 2),
        ([2, 2, 2, 2], [0, 1, 2, 3], 4),
        ([0, 0, 0, 0], [0, 0, 0, 0], 0),
        ([0, 0, 0, 5], [3, 3, 3, 3], 1),
    ],
)
def test_the_grid_walks_the_live_rows_and_stays_on_the_last(lens, rows, n):
    got, count = pallas_gdn.live_rows(jnp.asarray(lens, jnp.int32))
    assert np.asarray(got).tolist() == rows and int(count[0]) == n


@pytest.mark.parametrize(
    "kwargs",
    [
        dict(dtype=jnp.bfloat16),  # the state is float32
        dict(chunk=9),  # an admission's scan, not a step's few positions
        dict(chunk=0),
        dict(key_dim=20),  # does not tile the sublanes
        dict(key_dim=256),  # more than one transpose holds
        dict(value_dim=16384),  # 8 heads of [128, 16384] four times over
    ],
    ids=lambda kw: "-".join(f"{k}={v}" for k, v in kw.items()),
)
def test_supports_refuses(kwargs):
    ok = dict(n_heads=32, key_dim=128, value_dim=128, chunk=8)
    assert pallas_gdn.supports(**ok)
    assert pallas_gdn.supports(**{**ok, "chunk": 1})
    # olmo-hybrid-7b's: 30 heads of 96 x 192, stored as 256
    assert pallas_gdn.supports(n_heads=30, key_dim=96, value_dim=192, chunk=4)
    assert not pallas_gdn.supports(**{**ok, **kwargs})


def test_the_head_block_is_the_row_or_whole_sublane_tiles_of_heads():
    assert pallas_gdn._head_block(32, 8, 128, 128) == 32
    assert pallas_gdn._head_block(30, 4, 96, 192) == 30
    # a row that does not fit: the most tiles of 8 heads that do
    assert pallas_gdn._head_block(96, 8, 128, 128) == 64
    assert pallas_gdn._head_block(30, 4, 128, 1024) == 8
    assert pallas_gdn._head_block(30, 4, 128, 16384) == 0


def _engine(hf, mesh):
    cfg = config_from_hf(types.SimpleNamespace(**hf), dtype="float32")
    return DecodeEngine(
        cfg, init_params(cfg, mesh, jax.random.key(3)), mesh,
        kv_layout="paged", max_seq_len=128,
    )


@pytest.mark.filterwarnings("ignore:pallas forced")
@pytest.mark.parametrize(
    "hf,kernel",
    [
        (OLMO_HYBRID, "gdn.kernel"),
        (QWEN3_NEXT, "gdn.kernel"),
        ({**FALCON_H1, "mamba_d_state": 128}, "ssm.kernel"),
    ],
    ids=["olmo_hybrid", "qwen3_next", "falcon_h1"],
)
def test_state_update_answers_by_the_kind_of_state(devices, hf, kernel):
    """Each kind of state has its kernel where a step's batch rows ARE the
    pool's rows on one device, forced here (interpreted) as a TPU chooses it
    from the shapes; the XLA path on the CPU unforced, under ``force ==
    "xla"``, and under an admission view."""
    mesh = make_mesh(MeshPlan(tp=1), devices=devices[:1])
    eng = _engine(hf, mesh)
    cache = eng.new_paged_cache(2)
    for chunk in (1, 4, 8):
        assert decoder.state_update(eng.cfg, cache, mesh, chunk) == "xla"
        with force_impl("pallas"):
            assert decoder.state_update(eng.cfg, cache, mesh, chunk) == kernel
        with force_impl("xla"):
            assert decoder.state_update(eng.cfg, cache, mesh, chunk) == "xla"
    view = cache._replace(state_rows=jnp.zeros((2,), jnp.int32))
    with force_impl("pallas"):
        assert decoder.state_update(eng.cfg, view, mesh, 4) == "xla"


@pytest.mark.filterwarnings("ignore:pallas forced")
def test_state_update_is_xla_across_devices_and_without_a_state(devices):
    """Under ``tp`` 2 the mixer's heads are another mesh's; a config with no
    state has nothing to update."""
    mesh2 = make_mesh(MeshPlan(tp=2), devices=devices[:2])
    eng = _engine(OLMO_HYBRID, mesh2)
    with force_impl("pallas"):
        assert decoder.state_update(
            eng.cfg, eng.new_paged_cache(2), mesh2, 4
        ) == "xla"
    plain = dict(
        model_type="llama", vocab_size=256, hidden_size=64,
        intermediate_size=128, num_hidden_layers=2, num_attention_heads=2,
        num_key_value_heads=2, hidden_act="silu", rms_norm_eps=1e-6,
        max_position_embeddings=256, tie_word_embeddings=False,
        rope_theta=10000.0,
    )
    mesh = make_mesh(MeshPlan(tp=1), devices=devices[:1])
    eng = _engine(plain, mesh)
    with force_impl("pallas"):
        assert decoder.state_update(
            eng.cfg, eng.new_paged_cache(2), mesh, 1
        ) == "xla"
