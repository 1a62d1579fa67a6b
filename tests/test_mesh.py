"""Mesh construction and plan resolution (parity with utils/dist.py roles)."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from llmss_tpu.parallel import AXIS_DP, AXIS_TP, MeshPlan, make_mesh


def test_default_plan_is_all_tp(devices):
    # Reference default: world group == TP group (dist.py:77).
    mesh = make_mesh()
    assert mesh.shape[AXIS_TP] == 8
    assert mesh.shape[AXIS_DP] == 1


def test_plan_resolution():
    assert MeshPlan(dp=2, tp=None).resolve(8) == (2, 1, 4)
    assert MeshPlan(dp=2, sp=2, tp=2).resolve(8) == (2, 2, 2)
    with pytest.raises(ValueError):
        MeshPlan(dp=3).resolve(8)
    with pytest.raises(ValueError):
        MeshPlan(dp=2, tp=2).resolve(8)


def test_psum_over_tp_axis(devices):
    # A real collective over the virtual mesh — the FakeGroup upgrade.
    mesh = make_mesh(MeshPlan(tp=8))
    x = jnp.arange(8.0)

    def f(x):
        return jax.lax.psum(x, AXIS_TP)

    y = jax.shard_map(
        f, mesh=mesh, in_specs=P(AXIS_TP), out_specs=P()
    )(x)
    assert y.shape == (1,)
    assert float(y[0]) == 28.0


def test_sharded_matmul_gspmd(devices):
    # Column-parallel matmul via NamedSharding: XLA partitions without error.
    mesh = make_mesh(MeshPlan(tp=8))
    w = jax.device_put(
        jnp.ones((16, 32)), NamedSharding(mesh, P(None, AXIS_TP))
    )
    x = jnp.ones((4, 16))
    y = jax.device_get(jax.jit(lambda x, w: x @ w)(x, w))
    assert y.shape == (4, 32)
    assert float(y[0, 0]) == 16.0
