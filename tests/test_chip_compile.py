"""Ask the chip's compiler, without the chip.

The TPU compiler is installed in the sandbox and compiles for a chip that is
described, not attached (``topologies.get_topology_desc``). Interpret mode
cannot show what it shows: a block shape the TPU cannot tile, a kernel that
wants more VMEM than it may use, a bf16 operand reaching an f32 vector op.
The four Pallas kernels are compiled here with ``interpret=False`` for v5e
at the widths of three models the repo serves, at the engine's default
``block_size`` and a real cache length. A compile that passes is a compile,
not a chip run.

Plus: where ``initialize_runtime()`` puts the persistent compile cache.
"""

import functools
import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else it logs under /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from llmss_tpu.ops import (
    pallas_attention, pallas_decode, pallas_paged_decode, pallas_ragged,
)
from llmss_tpu.parallel import mesh as mesh_mod

# (n_heads, n_kv_heads, head_dim)
WIDTHS = {
    "starcoderbase-1b": (16, 1, 128),  # MQA — the chip_smoke model
    "mistral-7b": (32, 8, 128),  # GQA
    "gpt-j-6b": (16, 16, 256),  # MHA, head_dim 256: Hkv*D = 4096
}
B, S, T, L, CB = 4, 512, 1024, 2, 8
BS = 16  # DecodeEngine's default block_size
DT = jnp.bfloat16  # what the chip serves in


@pytest.fixture(scope="module")
def v5e():
    """One described v5e device; the persistent compile cache is switched
    off around these compiles (an entry written for a described device
    cannot be read back without a chip, and the next run would warn)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo.devices[0]
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _kernel_call(kernel: str, Hq: int, Hkv: int, D: int):
    """(function, argument shapes) of one kernel at one width set."""
    i32 = jnp.int32
    MB, N = T // BS, B * (T // BS)
    pool = ((L, N, BS, Hkv, D), DT)
    if kernel == "flash":
        assert pallas_attention.supports(S, T, Hq, Hkv)
        return pallas_attention.flash_attention, [
            ((B, S, Hq, D), DT), ((B, T, Hkv, D), DT), ((B, T, Hkv, D), DT),
            ((B, S), i32), ((B, T), i32),
        ]
    if kernel == "dense_decode":
        assert pallas_decode.supports(T, Hq, Hkv, D, DT)
        return pallas_decode.decode_attention, [
            ((B, 1, Hq, D), DT), ((L, B, T, Hkv, D), DT),
            ((L, B, T, Hkv, D), DT), ((B, 1, Hkv, D), DT),
            ((B, 1, Hkv, D), DT), ((B, 1), i32), ((B, T), i32),
            ((B, 1), i32), ((), i32),
        ]
    if kernel == "paged_decode":
        assert pallas_paged_decode.supports(BS, Hq, Hkv, D, DT)
        return pallas_paged_decode.paged_decode_attention, [
            ((B, 1, Hq, D), DT), pool, pool, ((B, 1, Hkv, D), DT),
            ((B, 1, Hkv, D), DT), ((B, 1), i32), ((B, MB * BS), i32),
            ((B, MB), i32), ((B,), i32), ((B, 1), i32), ((), i32),
        ]
    assert kernel == "ragged"
    assert pallas_ragged.supports(BS, Hq, Hkv, D, DT)
    return pallas_ragged.ragged_paged_attention, [
        ((B, CB, Hq, D), DT), pool, pool, ((B, CB, Hkv, D), DT),
        ((B, CB, Hkv, D), DT), ((B,), i32), ((B,), i32),
        ((B, MB * BS), i32), ((B, MB), i32), ((B,), i32), ((B,), i32),
        ((), i32),
    ]


@pytest.mark.parametrize("model", WIDTHS)
@pytest.mark.parametrize(
    "kernel", ["flash", "dense_decode", "paged_decode", "ragged"]
)
def test_kernel_compiles_for_v5e(v5e, kernel, model):
    fn, shapes = _kernel_call(kernel, *WIDTHS[model])
    on_chip = SingleDeviceSharding(v5e)
    args = [
        jax.ShapeDtypeStruct(shape, dtype, sharding=on_chip)
        for shape, dtype in shapes
    ]
    compiled = jax.jit(
        functools.partial(fn, interpret=False)
    ).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_supports_refuses_what_vmem_cannot_hold():
    """``supports()`` and the compiler agree: a K/V block pair that cannot
    fit the kernels' VMEM budget is refused up front (float32 at GPT-J
    widths needs a 128-slot chunk of 32 KB slots, double-buffered, x2)."""
    assert pallas_decode._pick_block_k(1024, 16, 256, jnp.bfloat16) == 256
    assert pallas_decode.supports(1024, 16, 16, 256, jnp.float32)
    assert not pallas_decode.supports(1024, 64, 64, 256, jnp.float32)
    assert not pallas_paged_decode.supports(2048, 64, 64, 256, jnp.float32)


@pytest.fixture
def fresh_runtime(monkeypatch):
    """``initialize_runtime()`` as a new process would run it, with the
    config it touches restored afterwards."""
    was = (
        jax.config.jax_compilation_cache_dir,
        jax.config.jax_persistent_cache_min_compile_time_secs,
    )
    monkeypatch.setattr(mesh_mod, "_initialized", False)
    monkeypatch.delenv("JAX_COORDINATOR_ADDRESS", raising=False)
    yield
    jax.config.update("jax_compilation_cache_dir", was[0])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", was[1])


@pytest.mark.parametrize("placed_from_outside", [True, False])
def test_compile_cache_placement(
    fresh_runtime, monkeypatch, tmp_path, placed_from_outside,
):
    """With JAX_COMPILATION_CACHE_DIR set the program sets no directory in
    code; unset, the cache lives at one fixed path inside the checkout."""
    sentinel = str(tmp_path / "untouched")
    jax.config.update("jax_compilation_cache_dir", sentinel)
    if placed_from_outside:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "x"))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    mesh_mod.initialize_runtime()
    got = jax.config.jax_compilation_cache_dir
    if placed_from_outside:
        assert got == sentinel
    else:
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert got == os.path.join(repo, ".jax_compile_cache")
        assert os.path.isdir(got)
