"""Device mesh construction and sharding policy.

TPU-native replacement for the reference's distributed runtime
(``src/llmss/server/models/utils/dist.py``): instead of torch.distributed
process groups (NCCL/Gloo/FakeGroup), we build a ``jax.sharding.Mesh`` over the
chips and let XLA compile collectives onto ICI/DCN. The reference's
``FakeGroup`` single-process debug path maps to a trivial 1-device mesh or a
virtual multi-device CPU mesh (``--xla_force_host_platform_device_count``).
"""

from llmss_tpu.parallel.mesh import (
    AXIS_DP,
    AXIS_SP,
    AXIS_TP,
    MeshPlan,
    default_compute_dtype,
    initialize_runtime,
    make_mesh,
)

__all__ = [
    "AXIS_DP",
    "AXIS_SP",
    "AXIS_TP",
    "MeshPlan",
    "default_compute_dtype",
    "initialize_runtime",
    "make_mesh",
]
