"""Pluggable device cost model: virtual seconds for simulated work.

Two seeding paths, both ending in the same five knobs:

- **table**: explicit per-op seconds (``prefill_token_s``,
  ``decode_step_s``, ...) — what the migrated bench tools use so their
  receipts stay numerically comparable with their pre-sim runs.
- **devtel**: derived from the device-telemetry roofline (PR 15) — peak
  FLOPS / HBM bandwidth from :func:`devtel.device_peaks` (or a
  CostTable entry priced by XLA's ``cost_analysis``) pushed through
  :func:`devtel.roofline_seconds`, so sim time and real MFU/MBU
  accounting share one model. Peaks resolve deterministically (env
  overrides, else the device_kind table, whose "cpu" row serves CPU), which
  keeps devtel-seeded scenarios byte-replayable.

KV block accounting lives here too (``kv_blocks``): replicas charge and
release blocks through the invariant checker so the refcounts-balance-
at-drain invariant has one arithmetic to agree with.
"""

from __future__ import annotations

import math
from types import SimpleNamespace

# Default analytical shapes for devtel seeding: a ~1.2B-param decoder
# (the repo's flagship "1b2" dims) in bf16.
_DEFAULT_DIMS = dict(
    n_layers=22, n_heads=16, n_kv_heads=16, head_dim=128,
    max_position_embeddings=4096,
)
_DEFAULT_PARAMS = 1_200_000_000


class DeviceCostModel:
    """Virtual-time pricing for one replica's device."""

    __slots__ = (
        "prefill_token_s", "decode_step_s", "adopt_const_s",
        "kv_bytes_per_token", "wire_gbps", "bucket_compile_s",
        "prewarm_max_bucket", "block_size", "kv_blocks_total",
        "t1_fetch_const_s", "t1_gbps", "t2_fetch_const_s", "t2_gbps",
        "seeded_from",
    )

    def __init__(
        self,
        *,
        prefill_token_s: float = 50e-6,
        decode_step_s: float = 1.5e-3,
        adopt_const_s: float = 1e-3,
        kv_bytes_per_token: float = 2 * 20 * 16 * 128 * 2,
        wire_gbps: float = 819.0,
        bucket_compile_s: float = 2.5,
        prewarm_max_bucket: int = 128,
        block_size: int = 16,
        kv_blocks_total: int = 4096,
        t1_fetch_const_s: float = 0.2e-3,
        t1_gbps: float = 50.0,
        t2_fetch_const_s: float = 2e-3,
        t2_gbps: float = 10.0,
        seeded_from: str = "table",
    ):
        self.prefill_token_s = float(prefill_token_s)
        self.decode_step_s = float(decode_step_s)
        self.adopt_const_s = float(adopt_const_s)
        self.kv_bytes_per_token = float(kv_bytes_per_token)
        self.wire_gbps = float(wire_gbps)
        self.bucket_compile_s = float(bucket_compile_s)
        self.prewarm_max_bucket = int(prewarm_max_bucket)
        self.block_size = int(block_size)
        self.kv_blocks_total = int(kv_blocks_total)
        # KV tier fetch pricing (serve/kvstore.py's T1 host RAM / T2
        # fleet blob store): constant setup + KV bytes over the tier's
        # effective bandwidth. T1 is a host→device copy; T2 adds the
        # blob-store round trip — slower but still far cheaper than
        # re-prefilling the tokens it carries.
        self.t1_fetch_const_s = float(t1_fetch_const_s)
        self.t1_gbps = float(t1_gbps)
        self.t2_fetch_const_s = float(t2_fetch_const_s)
        self.t2_gbps = float(t2_gbps)
        self.seeded_from = seeded_from

    # -- seeding --------------------------------------------------------------

    @classmethod
    def from_devtel(
        cls,
        *,
        batch: int = 8,
        kv_len: int = 1024,
        param_count: int = _DEFAULT_PARAMS,
        kv_itemsize: int = 2,
        dims: dict | None = None,
        table=None,
        **overrides,
    ) -> "DeviceCostModel":
        """Seed per-op seconds from devtel's roofline.

        When ``table`` (a :class:`devtel.CostTable`) holds a decode-class
        entry priced from a real lowering, that entry's FLOPs/bytes win;
        otherwise the analytical :class:`devtel.EngineCostModel` prices
        the step. Either way the seconds come from
        :func:`devtel.roofline_seconds` against ``device_peaks()``.
        """
        from llmss_tpu.utils import devtel

        cfg = SimpleNamespace(**{**_DEFAULT_DIMS, **(dims or {})})
        param_bytes = param_count * kv_itemsize
        model = devtel.EngineCostModel(
            cfg, param_count, param_bytes, kv_itemsize=kv_itemsize,
        )
        peak_flops, peak_bw = devtel.device_peaks()
        source = "devtel:analytical"

        flops = nbytes = None
        if table is not None:
            for key, cost in sorted(
                table.export().items(), key=lambda kv: str(kv[0])
            ):
                kind = key[0] if isinstance(key, tuple) and key else key
                if kind in ("decode", "decode_group"):
                    flops, nbytes = cost["flops"], cost["hbm_bytes"]
                    source = f"devtel:{cost.get('source', 'cost_analysis')}"
                    break
        if flops is None:
            flops, nbytes = model.step_cost(batch, 1, kv_len)
        decode_step_s = devtel.roofline_seconds(
            flops, nbytes, peak_flops, peak_bw,
        )

        # Marginal prefill token: the same fused dispatch carrying ragged
        # prompt chunks, minus the pure-decode baseline.
        chunk = 256
        f2, b2 = model.step_cost(batch, 1, kv_len, prefill_tokens=chunk)
        f1, b1 = model.step_cost(batch, 1, kv_len)
        prefill_token_s = max(
            devtel.roofline_seconds(f2, b2, peak_flops, peak_bw)
            - devtel.roofline_seconds(f1, b1, peak_flops, peak_bw),
            1e-9,
        ) / chunk

        kw = dict(
            prefill_token_s=prefill_token_s,
            decode_step_s=decode_step_s,
            kv_bytes_per_token=model.kv_bytes_per_token,
            wire_gbps=peak_bw / 1e9,
            seeded_from=source,
        )
        kw.update(overrides)
        return cls(**kw)

    @classmethod
    def from_config(cls, cfg: dict | None) -> "DeviceCostModel":
        """Scenario-file entry point: ``{"kind": "table"|"devtel", ...}``
        (remaining keys are constructor / from_devtel overrides)."""
        cfg = dict(cfg or {})
        kind = cfg.pop("kind", "table")
        if kind == "devtel":
            return cls.from_devtel(**cfg)
        if kind != "table":
            raise ValueError(f"unknown cost model kind {kind!r}")
        return cls(**cfg)

    # -- pricing --------------------------------------------------------------

    def prefill_s(self, n_tokens: int) -> float:
        return n_tokens * self.prefill_token_s

    def step_s(self, batch: int, feeding_tokens: int = 0) -> float:
        """One fused decode step over ``batch`` rows, carrying
        ``feeding_tokens`` ragged prompt-chunk tokens."""
        if batch <= 0 and feeding_tokens <= 0:
            return 0.0
        return self.decode_step_s + feeding_tokens * self.prefill_token_s

    def adopt_s(self, n_tokens: int) -> float:
        """Decode-side handoff adoption: constant + KV bytes over the
        wire at ``wire_gbps``."""
        wire = (n_tokens * self.kv_bytes_per_token) / (self.wire_gbps * 1e9)
        return self.adopt_const_s + wire

    def handoff_bytes(self, n_tokens: int) -> int:
        return int(n_tokens * self.kv_bytes_per_token)

    def tier_fetch_s(self, n_tokens: int, tier: str) -> float:
        """Promotion cost: pull ``n_tokens`` of parked KV back onto the
        device from host RAM (``t1``) or the fleet blob store (``t2``)."""
        if tier == "t1":
            const, gbps = self.t1_fetch_const_s, self.t1_gbps
        elif tier == "t2":
            const, gbps = self.t2_fetch_const_s, self.t2_gbps
        else:
            raise ValueError(f"unknown KV tier {tier!r}")
        return const + (n_tokens * self.kv_bytes_per_token) / (gbps * 1e9)

    def kv_blocks(self, plen: int, max_new: int) -> int:
        return math.ceil((plen + max_new) / self.block_size)

    def describe(self) -> dict:
        return {
            "seeded_from": self.seeded_from,
            "prefill_token_s": self.prefill_token_s,
            "decode_step_s": self.decode_step_s,
            "adopt_const_s": self.adopt_const_s,
            "kv_bytes_per_token": self.kv_bytes_per_token,
            "wire_gbps": self.wire_gbps,
            "block_size": self.block_size,
            "kv_blocks_total": self.kv_blocks_total,
            "t1_fetch_const_s": self.t1_fetch_const_s,
            "t1_gbps": self.t1_gbps,
            "t2_fetch_const_s": self.t2_fetch_const_s,
            "t2_gbps": self.t2_gbps,
        }
