"""Pluggable device cost model: virtual seconds for simulated work.

A table of explicit per-op seconds (``prefill_token_s``,
``decode_step_s``, ...), set by the scenario file or the bench tool.

KV block accounting lives here too (``kv_blocks``): replicas charge and
release blocks through the invariant checker so the refcounts-balance-
at-drain invariant has one arithmetic to agree with.
"""

from __future__ import annotations

import math


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
    def from_config(cls, cfg: dict | None) -> "DeviceCostModel":
        """Scenario-file entry point: ``{"kind": "table", ...}`` (remaining
        keys are constructor overrides)."""
        cfg = dict(cfg or {})
        kind = cfg.pop("kind", "table")
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
