"""Published peaks of the chips the benchmark may run on, keyed by the exact
``device_kind`` JAX reports. A device that is not here is an error, not a
default (copied in spirit from ``llmss_tpu/utils/devtel.py: DEVICE_PEAKS``;
the benchmark keeps its own so that a later PR cannot move the yardstick)."""

from __future__ import annotations

# Google Cloud documentation, "TPU v5e" (system architecture): one chip has
# 197 TFLOP/s in bf16, 16 GB of HBM2e at 819 GB/s.
DEVICE_PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "cloud.google.com/tpu/docs/v5e (197 TFLOP/s bf16, "
                  "819 GB/s, 16 GB a chip)",
    },
}


def peaks_for(device_kind: str) -> dict:
    if device_kind not in DEVICE_PEAKS:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; "
            f"have {sorted(DEVICE_PEAKS)}"
        )
    return DEVICE_PEAKS[device_kind]
