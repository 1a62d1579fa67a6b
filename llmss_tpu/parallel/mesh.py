"""Mesh construction, multi-host init, and dtype policy.

Replaces the reference's ``initialize_torch_distributed()``
(``utils/dist.py:40-77``): where the reference spawns one OS process per GPU
and rendezvouses via torchrun env vars into a NCCL/Gloo world group that
doubles as the TP group (``dist.py:77``), we run single-controller JAX — one
Python process per host — and express parallelism as named axes of a device
mesh. Collectives are compiled by XLA onto ICI (intra-slice) / DCN
(cross-slice); there is no communication library to initialize or time out.

Axes:

- ``dp``: data / batch parallelism (replicated weights, sharded batch).
- ``sp``: sequence/context parallelism for long-context prefill
  (absent in the reference, first-class here).
- ``tp``: tensor (Megatron-style) parallelism — the reference's only strategy.

The reference's ``FakeGroup`` debug backend (``dist.py:14-37``, activated by
``world_size == 1`` or ``DEBUG=1``) is structurally unnecessary here: a
1-device mesh runs the exact same program with collectives compiled to no-ops.
For multi-device testing without hardware, use a virtual CPU mesh (see
``tests/conftest.py``: ``XLA_FLAGS=--xla_force_host_platform_device_count=8``).
"""

from __future__ import annotations

import dataclasses
import os
from pathlib import Path
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from llmss_tpu.utils import devtel

AXIS_DP = "dp"
AXIS_SP = "sp"
AXIS_TP = "tp"

# Mesh axis order: dp outermost (rides DCN across slices), then sp, then tp
# innermost so TP collectives map onto the fastest ICI links.
AXIS_ORDER = (AXIS_DP, AXIS_SP, AXIS_TP)

# Default home of the persistent compile cache (ignored by git); see
# ``initialize_runtime``.
COMPILE_CACHE_DIR = str(
    Path(__file__).resolve().parents[2] / ".jax_compile_cache"
)

_initialized = False


def initialize_runtime(
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
) -> None:
    """Initialize multi-host JAX if running in a multi-process environment.

    Replaces ``torch.distributed.init_process_group`` (``dist.py:65-73``).
    Single-host (the common case, and always the case under test) is a no-op —
    unlike the reference there is no fake-backend switch to get wrong.

    Multi-process settings are read from the standard JAX env vars or cloud
    TPU metadata by ``jax.distributed.initialize`` itself; explicit arguments
    override.
    """
    global _initialized
    if _initialized:
        return
    if devtel.enabled():
        # From here on JAX's trace / lower / compile seconds are kept
        # (``setup.jax.*``): the weights' programs come before any prewarm.
        devtel.install_monitoring_hook()
    with devtel.setup_span("setup.runtime"):
        _initialize(coordinator_address, num_processes, process_id)
    _initialized = True


def _initialize(coordinator_address, num_processes, process_id) -> None:
    # Persistent XLA compilation cache: the serving prewarm compiles the
    # whole executable envelope; with the cache a restarted worker reloads
    # those executables instead of recompiling. Where the operator placed
    # it (JAX_COMPILATION_CACHE_DIR) JAX already reads the variable and no
    # directory is set here. Otherwise it lives at one fixed path inside
    # the checkout — the path is part of the cache key, so a directory
    # that moves (home, temp name, pid) never hits.
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        os.makedirs(COMPILE_CACHE_DIR, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    explicit = coordinator_address is not None or num_processes is not None
    in_multiprocess_env = explicit or os.environ.get("JAX_COORDINATOR_ADDRESS")
    if in_multiprocess_env:
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
        )


@dataclasses.dataclass(frozen=True)
class MeshPlan:
    """A parallelism plan: how many devices along each named axis.

    The reference hard-wires one strategy — TP over the whole world
    (``dist.py:77``). Here the plan is explicit and composable; ``tp=None``
    means "all remaining devices", reproducing the reference default.
    """

    dp: int = 1
    sp: int = 1
    tp: int | None = None

    def resolve(self, n_devices: int) -> tuple[int, int, int]:
        tp = self.tp
        if tp is None:
            if n_devices % (self.dp * self.sp) != 0:
                raise ValueError(
                    f"{n_devices} devices not divisible by dp*sp="
                    f"{self.dp * self.sp}"
                )
            tp = n_devices // (self.dp * self.sp)
        total = self.dp * self.sp * tp
        if total != n_devices:
            raise ValueError(
                f"plan dp={self.dp} sp={self.sp} tp={tp} needs {total} "
                f"devices, have {n_devices}"
            )
        return self.dp, self.sp, tp


def make_mesh(
    plan: MeshPlan | None = None,
    *,
    devices: Sequence[jax.Device] | None = None,
) -> Mesh:
    """Build the device mesh for a parallelism plan.

    Uses ``jax.make_mesh`` when laying out over all devices so JAX picks an
    ICI-friendly device order for the axis shape; falls back to a reshape of
    an explicit device list (used by tests to build submeshes).
    """
    with devtel.setup_span("setup.runtime") as sp:
        mesh = _make_mesh(plan or MeshPlan(), devices)
        sp.set(devices=mesh.size)
    return mesh


def _make_mesh(plan: MeshPlan, devices: Sequence[jax.Device] | None) -> Mesh:
    # Auto axis types: the classic GSPMD model — parameters carry
    # NamedShardings, activations get with_sharding_constraint hints, XLA
    # propagates and inserts collectives. (JAX 0.9's default is the new
    # Explicit sharding-in-types mode, which requires per-op out_sharding
    # annotations; Auto is the mature path MaxText-class frameworks use.)
    axis_types = (jax.sharding.AxisType.Auto,) * len(AXIS_ORDER)
    if devices is None:
        devices = jax.devices()
        dp, sp, tp = plan.resolve(len(devices))
        return jax.make_mesh((dp, sp, tp), AXIS_ORDER, axis_types=axis_types)
    dp, sp, tp = plan.resolve(len(devices))
    arr = np.asarray(devices, dtype=object).reshape(dp, sp, tp)
    return Mesh(arr, AXIS_ORDER, axis_types=axis_types)


def default_compute_dtype() -> jnp.dtype:
    """bf16 on TPU (MXU-native), f32 elsewhere.

    The reference forces fp16 on GPU (``generate.py:53``); bf16 is the
    TPU-native equivalent — same memory footprint, MXU-native, and no loss
    scaling concerns.
    """
    platform = jax.default_backend()
    if platform == "cpu":
        return jnp.dtype("float32")
    return jnp.dtype("bfloat16")
