"""Test harness: force a virtual 8-device CPU mesh before JAX initializes.

This is the TPU-native analogue of the reference's ``FakeGroup`` /``DEBUG=1``
testing affordance (``utils/dist.py:14-37,62-63``): the same TP program runs on
any dev box, but here the collectives are *real* (XLA CPU collectives over 8
virtual devices) rather than no-ops, so sharded numerics are actually tested.
"""

import os

# XLA flags must be set before the CPU backend initializes.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
# Keep CPU compile times sane on small test shapes.
os.environ.setdefault("JAX_ENABLE_X64", "0")

import jax  # noqa: E402

# The env var alone is read too late if anything imported jax before this
# file (a sitecustomize, say) — override via config as well: backends
# are not yet initialized at conftest import time, so it still wins.
jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual CPU devices, got {len(devs)}"
    return devs


@pytest.fixture(scope="module")
def toy_engine(devices):
    """A two-layer llama-shaped engine on a dp=2 x tp=4 mesh, for tests that
    drive the scheduler or the worker and not a model."""
    from llmss_tpu.engine import DecodeEngine
    from llmss_tpu.models.common import DecoderConfig
    from llmss_tpu.models.decoder import init_params
    from llmss_tpu.parallel import MeshPlan, make_mesh

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
    return DecodeEngine(cfg, params, mesh, max_seq_len=64)
