"""DecodeEngine: jitted prefill + decode steps and generation loops.

Replaces the reference's three decode loops (``generate.py:99-190`` cache and
no-cache paths, ``consumer_server.py:123-166``). Differences by design:

- **On-device sampling inside the jitted step**: the per-token chain
  logits→host→rank-0 sample→NCCL broadcast (``generate.py:109-144``) becomes
  a fused argmax/top-k/top-p/categorical on device; the host only reads the
  emitted token (streaming mode) or nothing at all (fused mode).
- **Two generation modes**: ``generate`` — a host-side loop around the jitted
  decode step (streaming, early-exit on EOS); ``generate_fused`` — the whole
  token loop as ``lax.scan`` inside one jit (zero host round-trips, the
  throughput path).
- **Static shapes with prompt bucketing**: prompts right-pad to a bucket
  length (compile-once-per-bucket), pads masked out of attention — fixing the
  reference's unmasked left-pad quirk (SURVEY.md §2.11.3).
- **Sliding-window overflow** (`generate.py:132-142`) is ring-buffer slot
  arithmetic (``slot = position % max_len``), not host-side trimming.
- **Donated cache buffers**: each step consumes and re-emits the cache with
  no reallocation (the reference re-allocates and calls
  ``torch.cuda.empty_cache()``, ``generate.py:187``).
"""

from __future__ import annotations

import dataclasses
import time
from functools import partial
from typing import TYPE_CHECKING, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from llmss_tpu.engine.cache import (
    KVCache, PagedKVCache, init_cache, init_paged_cache,
    paged_write_stacked, ssm_state_shapes,
)
from llmss_tpu.models.common import DecoderConfig
from llmss_tpu.ops.sampling import sample
from llmss_tpu.utils import devtel

if TYPE_CHECKING:  # a runtime import would be circular when the models
    # package is imported first (models.decoder -> engine.cache runs
    # engine/__init__ -> engine.engine -> models.decoder).
    from llmss_tpu.models.decoder import Params  # noqa: F401


class Prefix(NamedTuple):
    """A retained, device-resident KV segment for a shared prompt prefix
    (system prompt / earlier turns of a session). Built once with
    ``DecodeEngine.build_prefix``; admissions that start with these tokens
    seed their cache rows from it and prefill only the suffix — the
    prefix's prefill FLOPs and TTFT are paid once per prefix, not per
    request. Token-exact vs from-scratch on bf16 caches (absolute
    positions/counters); on int8 caches the stored bits are stable but
    reads pass through quantization, so exactness is not guaranteed. The
    reference has no analogue (it re-prefills every request from scratch,
    ``generate.py:99``)."""

    tokens: tuple[int, ...]  # the prefix token ids (host, for matching)
    k: jax.Array  # [L, P, Hkv, D] (or int8 when the engine is int8)
    v: jax.Array
    k_scale: jax.Array | None  # [L, P, Hkv] f32 iff int8
    v_scale: jax.Array | None

    @property
    def length(self) -> int:
        return len(self.tokens)


@dataclasses.dataclass
class GenerationParams:
    """Per-call generation controls (≙ reference CLI flags,
    ``generate.py:21-32``; correctness fixes per SURVEY.md §2.11.1)."""

    max_new_tokens: int = 20
    is_greedy: bool = True
    temperature: float = 1.0
    top_k: int = 0
    top_p: float = 1.0
    eos_token_id: int | None = None
    seed: int = 0

    def validate(self) -> None:
        # Range checks, parity with generate.py:37-40 — but raising, not
        # asserting: the engine path must reject bad params under
        # ``python -O`` too, same as the protocol path.
        if not self.is_greedy:
            if not self.temperature > 0.0:
                raise ValueError("temperature must be > 0")
            if not self.top_k >= 0:
                raise ValueError("top_k must be >= 0")
            if not 0.0 < self.top_p <= 1.0:
                raise ValueError("top_p must be in (0, 1]")
        if not self.max_new_tokens > 0:
            raise ValueError("max_new_tokens must be > 0")


def _bucket(n: int, cap: int) -> int:
    b = 16
    while b < n:
        b *= 2
    return min(b, cap)


class DecodeEngine:
    """Drives one model on one mesh with a fixed (batch, max_seq) envelope."""

    def __init__(
        self,
        cfg: DecoderConfig,
        params: Params,
        mesh,
        *,
        batch_size: int = 1,
        max_seq_len: int | None = None,
        kv_dtype: str | None = None,
        kv_layout: str = "dense",
        block_size: int = 16,
        kv_blocks: int | None = None,
    ):
        from llmss_tpu.utils.metrics import EngineMetrics

        self.cfg = cfg
        self.params = params
        self.mesh = mesh
        self.batch_size = batch_size
        self.max_seq_len = max_seq_len or cfg.max_position_embeddings
        # kv_layout="paged": rows address KV through per-row block tables
        # into a global block pool instead of owning a dense [T] ring —
        # same logical-slot contract, so every generate/serve path works
        # unchanged (models/decoder.py:_forward_paged, docs/paged-kv.md).
        # ``kv_blocks`` sizes the scheduler's shared pool (None = the
        # dense-equivalent batch*max_len/block_size); the engine's own
        # generate paths always use identity tables over a full pool.
        if kv_layout not in ("dense", "paged"):
            raise ValueError(
                f"kv_layout must be 'dense' or 'paged', got {kv_layout!r}"
            )
        if cfg.has_state and kv_layout != "paged":
            raise ValueError(
                f"model_type {cfg.model_type!r} holds a recurrent state, "
                "which lives in the paged cache only: pass "
                "kv_layout='paged' (docs/recurrent-state.md)"
            )
        if cfg.linear_attn is not None:
            from llmss_tpu.parallel.mesh import AXIS_TP

            tp = 1 if mesh is None else mesh.shape.get(AXIS_TP, 1)
            if cfg.n_heads % tp or cfg.pool_kv_heads % tp:
                raise ValueError(
                    f"tp={tp} does not divide the {cfg.n_heads} heads of "
                    f"model_type {cfg.model_type!r}'s attention layers and "
                    f"the {cfg.pool_kv_heads} its block pool holds (the "
                    "linear-attention mixer is replicated over tp: "
                    "docs/recurrent-state.md)"
                )
        if cfg.moe is not None and cfg.mla is None:
            from llmss_tpu.parallel.mesh import AXIS_TP

            if mesh is not None and mesh.shape.get(AXIS_TP, 1) > 1:
                raise ValueError(
                    f"model_type {cfg.model_type!r} has routed experts, "
                    "which are served at tp == 1 only: no mesh axis divides "
                    "the experts and nothing exchanges their tokens yet; "
                    "one chip's share of them is a configuration "
                    "(docs/recurrent-state.md, 'The experts' share')"
                )
        if cfg.indexer is not None:
            # The pool of indexer keys (docs/sparse-attention.md) is carried
            # by the paged layout, float32 beside keys and values in the
            # compute dtype, on one device; what is more than that is refused
            # by name, not served wrong.
            from llmss_tpu.parallel.mesh import AXIS_SP, AXIS_TP

            if kv_layout != "paged":
                raise ValueError(
                    f"model_type {cfg.model_type!r} selects what attention "
                    "reads by an indexer whose keys live in the paged cache "
                    "only: pass kv_layout='paged' (docs/sparse-attention.md)"
                )
            if kv_dtype == "int8":
                raise ValueError(
                    "an indexer's key pool is not carried beside an int8 "
                    "pool (kv_dtype='int8'): nothing measures the selection "
                    "over quantized keys and values "
                    "(docs/sparse-attention.md)"
                )
            if mesh is not None and (
                mesh.shape.get(AXIS_TP, 1) > 1 or mesh.shape.get(AXIS_SP, 1) > 1
            ):
                raise ValueError(
                    "a model with an indexer is served at tp == 1 and "
                    "sp == 1 only: its selection is a row's own, over its "
                    "whole context, and nothing shards the indexer's heads "
                    "or its pool yet (docs/sparse-attention.md)"
                )
        if cfg.mla is not None:
            # The latent pool (docs/latent-cache.md) is carried by the paged
            # layout in the compute dtype on one chip's worth of heads;
            # what is more than that is refused by name, not served wrong.
            from llmss_tpu.parallel.mesh import AXIS_TP

            if kv_layout != "paged":
                raise ValueError(
                    f"model_type {cfg.model_type!r} caches a latent pool, "
                    "which lives in the paged cache only: pass "
                    "kv_layout='paged' (docs/latent-cache.md)"
                )
            if kv_dtype == "int8":
                raise ValueError(
                    "the latent pool is not carried in int8 "
                    "(kv_dtype='int8'; docs/latent-cache.md)"
                )
            if mesh is not None and mesh.shape.get(AXIS_TP, 1) > 1:
                raise ValueError(
                    "the latent pool is served at tp == 1 only: nothing "
                    "divides the experts or measures the replicated latent "
                    "across chips yet (docs/latent-cache.md)"
                )
        self.kv_layout = kv_layout
        self.block_size = block_size
        self.kv_blocks = kv_blocks
        if kv_layout == "paged":
            from llmss_tpu.parallel.mesh import AXIS_SP

            if self.max_seq_len % block_size:
                raise ValueError(
                    f"kv_layout='paged' needs max_seq_len "
                    f"({self.max_seq_len}) divisible by block_size "
                    f"({block_size})"
                )
            if mesh is not None and AXIS_SP in mesh.shape and (
                mesh.shape[AXIS_SP] > 1
            ):
                raise ValueError(
                    "kv_layout='paged' does not support sp > 1 meshes "
                    "(the sequence axis is block-indirected per row)"
                )
        if (
            cfg.rope_original_max_positions is not None
            and cfg.rope_freq_factors_short is not None
        ):
            # LongRoPE: the rotary basis follows the context this engine
            # actually serves (models/phi3.py documents the contract) —
            # a 4k-context engine on a 128k checkpoint runs the short
            # factors, exactly as HF does for forwards within 4k.
            import dataclasses as _dc

            chosen = (
                cfg.rope_freq_factors_long
                if self.max_seq_len > cfg.rope_original_max_positions
                else cfg.rope_freq_factors_short
            )
            cfg = self.cfg = _dc.replace(cfg, rope_freq_factors=chosen)
        # kv_dtype="int8" stores the cache quantized (per-token-per-head
        # scales): half the HBM footprint → double the rows/context per
        # chip. On sp=1 meshes the dequant scales fold into the attention
        # contractions (no dequantized copy materializes,
        # ops/attention.py); sp>1 meshes pre-dequantize each layer before
        # the shard_map'd sequence-parallel attention (models/decoder.py).
        if kv_dtype == "int8":
            self._cache_dtype = jnp.int8
        else:
            self._cache_dtype = cfg.compute_dtype
        with devtel.setup_span("setup.engine"):
            self.metrics = EngineMetrics()
            self._ladder = self.bucket_ladder()
            self._canon_cache_memo: dict[tuple, KVCache | PagedKVCache] = {}

            # mesh is partial-bound (a compile-time constant, not a traced
            # arg): it enables the shard_map'd Pallas attention path inside
            # forward.
            self._prefill = jax.jit(
                partial(self._prefill_impl, cfg, mesh), donate_argnums=(2,),
            )
            self._decode = jax.jit(
                partial(self._decode_impl, cfg, mesh), donate_argnums=(2,),
                static_argnames=("t_bucket",),
            )
            # Grouped decode: n_chunks fused chunks in ONE program with ONE
            # packed device→host fetch for the whole group. Donates the token
            # and position carries as well as the cache — XLA reuses their
            # storage across every step of the group.
            self._decode_group = jax.jit(
                partial(self._decode_group_impl, cfg, mesh),
                donate_argnums=(1, 2, 3),
                static_argnames=("n_chunks", "n_steps", "t_bucket"),
            )
            # Ragged mixed prefill+decode group (chunked prefill): each scan
            # step advances decode rows by one token AND streams chunk-budget
            # slices of in-flight prompts through the same dispatch
            # (forward_ragged). Executable identity is keyed purely by the xs
            # shapes [n_chunks, B(, CB)] — no static args, no bucket ladder.
            self._ragged_group = jax.jit(
                partial(self._ragged_group_impl, cfg, mesh),
                donate_argnums=(1, 2, 3),
            )
            self._admit_merge = jax.jit(
                self._admit_merge_impl, donate_argnums=(0, 1)
            )
            self._seed = jax.jit(self._seed_impl, donate_argnums=(0,))

    # -- jitted bodies ------------------------------------------------------

    @staticmethod
    def _prefill_impl(
        cfg, mesh, params, ids, cache, prompt_lens, sample_args, start=None,
    ):
        """Prefill ``ids`` into the cache. ``start`` ([B] int32, optional)
        offsets every row's positions — the prefix-reuse path prefills only
        a request's *suffix* at positions ``[start, start + len)`` against
        a cache whose first ``start`` slots were seeded from a retained
        ``Prefix``; ``prompt_lens`` is then the suffix length. The default
        (start absent) is the ordinary from-zero prefill."""
        from llmss_tpu.models.decoder import forward

        B, S = ids.shape
        if cfg.indexer is not None:
            if start is not None:
                raise ValueError(
                    "prefix reuse is not carried for a model with an "
                    "indexer: a retained segment holds keys and values, not "
                    "the indexer's keys (docs/sparse-attention.md)"
                )
        if cfg.has_state:
            if start is not None:
                raise ValueError(
                    "prefix reuse is not carried for a model with a "
                    "recurrent state (docs/recurrent-state.md)"
                )
            if cache.state_rows is None:
                # A prompt starts from nothing, whatever the rows held. The
                # scheduler's admission view says which pool rows it stands
                # for; an engine-owned cache is a view onto its own rows.
                cache = cache._replace(
                    state_rows=jnp.arange(B, dtype=jnp.int32)
                )
        rel = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        off = jnp.zeros((B,), jnp.int32) if start is None else start
        positions = off[:, None] + rel
        valid = rel < prompt_lens[:, None]
        slots = positions % cache.max_len
        kv_pos = jnp.where(valid, positions, -1)
        logits, cache = forward(
            cfg, params, ids, positions, cache, slots,
            gather_idx=prompt_lens - 1, kv_write_positions=kv_pos, mesh=mesh,
        )
        # The sampled token sits at absolute position start + prompt_len —
        # that position is the per-row draw counter (ops/sampling.py:
        # stateless per-request randomness), so a prefix-reused request
        # draws exactly the tokens it would draw prefilled from scratch.
        tok = sample(logits[:, 0], counters=off + prompt_lens, **sample_args)
        return tok, logits[:, 0], cache

    @staticmethod
    def _seed_impl(cache, pk, pv, pks, pvs, plen):
        """Write a retained prefix segment into logical slots [0, Pb) of
        EVERY row of a (fresh) cache. The segment is BUCKET-padded
        (``build_prefix`` keeps the prefill bucket's shape): only slots
        below ``plen`` (traced, [] int32) record real positions — pad
        slots stay -1 so attention never sees them, and this one jit
        serves every prefix length in a bucket instead of compiling a
        bespoke scatter per length. Rows that go on to serve non-prefix
        work are simply overwritten by their own prefill; dummy admission
        rows ignore it entirely."""
        Pb = pk.shape[1]
        rel = jnp.arange(Pb, dtype=jnp.int32)
        pos_row = jnp.where(rel < plen, rel, -1)
        pos = cache.positions.at[:, :Pb].set(pos_row[None, :])
        if isinstance(cache, PagedKVCache):
            B = cache.block_tables.shape[0]
            slots = jnp.broadcast_to(rel, (B, Pb))

            def scatter(pool, seg):
                if pool is None:
                    return None
                new = jnp.broadcast_to(
                    seg[:, None], (seg.shape[0], B) + seg.shape[1:]
                )
                # Sentinel table entries drop the write — the scheduler
                # seeds through COW-masked tables whose SHARED prefix
                # blocks are sentineled out (docs/paged-kv.md).
                return paged_write_stacked(
                    pool, new, cache.block_tables, slots, cache.block_size
                )

            return cache._replace(
                k=scatter(cache.k, pk), v=scatter(cache.v, pv),
                positions=pos,
                k_scale=scatter(cache.k_scale, pks),
                v_scale=scatter(cache.v_scale, pvs),
            )
        return KVCache(
            k=cache.k.at[:, :, :Pb].set(pk[:, None]),
            v=cache.v.at[:, :, :Pb].set(pv[:, None]),
            positions=pos,
            k_scale=(
                cache.k_scale.at[:, :, :Pb].set(pks[:, None])
                if pks is not None else None
            ),
            v_scale=(
                cache.v_scale.at[:, :, :Pb].set(pvs[:, None])
                if pvs is not None else None
            ),
        )

    def seed_cache(self, cache, prefix: Prefix):
        """Seed a fresh cache's rows with ``prefix`` (jitted, donating)."""
        return self._seed(
            cache, prefix.k, prefix.v, prefix.k_scale, prefix.v_scale,
            jnp.asarray(prefix.length, jnp.int32),
        )

    def build_prefix(self, token_ids: list[int]) -> Prefix:
        """Prefill ``token_ids`` once and retain the resulting KV segment
        for reuse by later requests that start with these tokens (shared
        system prompt, earlier turns of a session). int8 engines store the
        prefix quantized — the seeded bits are identical on every reuse
        (storage bit-stability, models/decoder.py).

        The retained segment keeps the prefill BUCKET's padded length
        (pad slots carry no positions): construction rides the exact
        executables ``prewarm(prefix_prefill=True)`` already compiled and
        the seed scatter compiles once per bucket, not once per prefix
        length — this removed a one-time bespoke-shape compile per
        distinct prefix length."""
        if self.cfg.has_state:
            raise ValueError(
                "prefix reuse is not carried for a model with a recurrent "
                "state: a retained segment would need the state at its end "
                "(docs/recurrent-state.md)"
            )
        if self.cfg.indexer is not None:
            raise ValueError(
                "prefix reuse is not carried for a model with an indexer: a "
                "retained segment holds keys and values, not the indexer's "
                "keys (docs/sparse-attention.md)"
            )
        P = len(token_ids)
        if not 0 < P < self.max_seq_len:
            raise ValueError(
                f"prefix length {P} must be in (0, {self.max_seq_len})"
            )
        cache = self.new_cache(1)
        ids, lens = self._pad_prompts([list(token_ids)])
        Pb = ids.shape[1]
        sa = self._sample_args(GenerationParams(), 1)
        _, _, cache = self._prefill(
            self.params, jnp.asarray(ids), cache, jnp.asarray(lens), sa,
        )
        if isinstance(cache, PagedKVCache):
            # Row 0 of a fresh engine cache has the identity table: logical
            # slot s lives at pool[block s // bs, s % bs] — unfold the
            # first ceil(Pb/bs) blocks back into a dense [L, Pb] segment
            # (the Prefix stays layout-neutral; seeding re-scatters it
            # through whatever tables the target cache carries).
            bs = cache.block_size
            nb = -(-Pb // bs)

            def seg(pool):
                if pool is None:
                    return None
                v = pool[:, :nb]
                return v.reshape(
                    (v.shape[0], nb * bs) + v.shape[3:]
                )[:, :Pb]

            return Prefix(
                tokens=tuple(int(t) for t in token_ids),
                k=seg(cache.k), v=seg(cache.v),
                k_scale=seg(cache.k_scale), v_scale=seg(cache.v_scale),
            )
        return Prefix(
            tokens=tuple(int(t) for t in token_ids),
            k=cache.k[:, 0, :Pb],
            v=cache.v[:, 0, :Pb],
            k_scale=(
                cache.k_scale[:, 0, :Pb] if cache.k_scale is not None
                else None
            ),
            v_scale=(
                cache.v_scale[:, 0, :Pb] if cache.v_scale is not None
                else None
            ),
        )

    @staticmethod
    def split_prefix(
        prompts: list[list[int]], prefix: Prefix
    ) -> tuple[np.ndarray, list[list[int]]]:
        """Validate every prompt extends ``prefix`` and return (full
        lengths, suffixes). A prompt must be strictly longer than the
        prefix — the suffix prefill needs at least one token to produce
        the first logits."""
        P = prefix.length
        suffixes = []
        for p in prompts:
            if len(p) <= P or tuple(p[:P]) != prefix.tokens:
                raise ValueError(
                    "prompt does not extend the prefix (needs the prefix's "
                    f"{P} tokens plus at least one more)"
                )
            suffixes.append(list(p[P:]))
        lens = np.asarray([len(p) for p in prompts], np.int32)
        return lens, suffixes

    @staticmethod
    def _decode_impl(
        cfg, mesh, params, tokens, cache, cur_pos, sample_args,
        *, t_bucket: int | None = None,
    ):
        from llmss_tpu.models.decoder import forward

        # tokens [B], cur_pos [B] — position at which each token sits.
        positions = cur_pos[:, None]
        slots = positions % cache.max_len
        logits, cache = forward(
            cfg, params, tokens[:, None], positions, cache, slots,
            last_only=True, mesh=mesh, t_bucket=t_bucket,
        )
        tok = sample(logits[:, 0], counters=cur_pos + 1, **sample_args)
        return tok, logits[:, 0], cache

    @staticmethod
    def _admit_merge_impl(tokens, cur_pos, adm_tok, adm_lens, rows):
        """Merge an admission batch into the device-resident decode state:
        ``tokens[rows] = adm_tok`` (each row's prefill-sampled first token)
        and ``cur_pos[rows] = adm_lens``. ``rows`` is [P] int32 padded with
        a positive out-of-range sentinel (mode="drop"; negative would wrap
        — the r3 admission-sentinel bug). This is what lets the scheduler
        pipeline decode chunks without fetching tokens to the host: the
        next chunk reads the merged state directly (scheduler.py)."""
        return (
            tokens.at[rows].set(adm_tok, mode="drop"),
            cur_pos.at[rows].set(adm_lens, mode="drop"),
        )

    @staticmethod
    def _decode_step_body(cfg, mesh, params, sample_args, eos, t_bucket,
                          carry, _x=None):
        """One fused decode step — the body ``_decode_group`` scans."""
        from llmss_tpu.models.decoder import forward
        from llmss_tpu.ops.sampling import fold_step_outcome

        tokens, cache, cur_pos, done, poisoned = carry
        positions = cur_pos[:, None]
        # Done rows stop WRITING KV: their slot goes positive-OOB, and
        # every write site drops OOB indices. A dense done-row write
        # was merely wasted bandwidth (the row owns its ring); under
        # the paged layout a freed row's STALE device block table may
        # point at blocks the allocator already handed to another row
        # — or at shared prefix blocks, once its position wraps — so
        # the write must not land at all (docs/paged-kv.md).
        slots = jnp.where(
            done[:, None], cache.max_len, positions % cache.max_len
        )
        aux = {}
        logits, cache = forward(
            cfg, params, tokens[:, None], positions, cache, slots,
            last_only=True, mesh=mesh, t_bucket=t_bucket, aux=aux,
        )
        tok = sample(logits[:, 0], counters=cur_pos + 1, **sample_args)
        tok, done, poisoned = fold_step_outcome(
            logits[:, 0], tok, done, poisoned, eos
        )
        cur_pos = cur_pos + 1
        # the step's routing counts and selection counts; None without
        # routed experts, without an indexer
        return (tok, cache, cur_pos, done, poisoned), (
            tok, (aux.get("moe_counts"), aux.get("dsa_counts")),
        )

    @staticmethod
    def _decode_group_impl(
        cfg, mesh, params, tokens, cache, cur_pos, sample_args, done,
        eos, *, n_chunks: int, n_steps: int, t_bucket: int | None = None,
    ):
        """A GROUP of ``n_chunks`` fused decode chunks as one program: an
        outer ``lax.scan`` over a chunk's ``lax.scan`` of the step, with EOS/
        done and poison folded into the on-device carry so no host decision
        is needed between chunks. The host gets everything in ONE packed
        int32 transfer — ``n_chunks·B·n_steps`` tokens followed by
        ``n_chunks·B`` per-chunk poisoned flags (cumulative within the
        group, snapshotted after each chunk so the host can error a
        poisoned row at the same chunk granularity as the ungrouped
        path) — instead of one tokens + one poisoned fetch per chunk.

        Returns ``(packed [n_chunks·B·(n_steps+1)] int32, last_tok [B],
        cache, cur_pos, done)``; the carried token/position/cache outputs
        feed the next group's dispatch directly (device-resident state,
        donated in)."""
        body = partial(
            DecodeEngine._decode_step_body, cfg, mesh, params, sample_args,
            eos, t_bucket,
        )
        # The stacked ys MUST be pinned to a replicated sharding here:
        # GSPMD otherwise propagates an unreduced partial-sum layout from
        # the tp-sharded logits into the outer scan's stacked output, and
        # the host reads token values summed over the tp axis (observed:
        # every packed token exactly tp× its true value). The carry never
        # hits this — its sharding is pinned by the next iteration's
        # consumers — only the ys leave the loop unconstrained
        # (parallel/sharding.ys_pin documents the hazard; shardcheck's
        # partial-sum-leak rule gates it).
        from llmss_tpu.parallel.sharding import ys_pin

        pin = ys_pin(mesh)

        def chunk(carry, _):
            carry, (toks, counts) = jax.lax.scan(
                body, carry, None, length=n_steps
            )
            # Snapshot per-chunk: toks [n_steps, B] → [B, n_steps]; the
            # poison flags as of this chunk's end.
            return carry, (pin(toks.T), pin(carry[4]), counts)

        poisoned0 = jnp.zeros_like(done)
        carry, (toks, pois, counts) = jax.lax.scan(
            chunk, (tokens, cache, cur_pos, done, poisoned0), None,
            length=n_chunks,
        )
        tokens, cache, cur_pos, done, _ = carry
        packed = DecodeEngine._pack_group(toks, pois, counts)
        return packed, tokens, cache, cur_pos, done

    #: how many numbers each kind of count adds to a group's packed fetch
    COUNTS = (3, 4)

    @staticmethod
    def _pack_group(toks, pois, counts):
        """The ONE int32 vector a group sends to the host: its tokens, its
        per-chunk poison flags, and at the end the counts its steps left
        (``counts``: ``(moe, dsa)``, the steps' stacked counts, each None
        where the model has none), summed over the group's steps: for a
        model with routed experts three numbers, ``pairs``, ``experts_hit``
        and ``pairs_elsewhere`` (ops/moe.py: ``routed_experts``, summed over
        the expert layers), then for a model with an indexer four, ``scored``,
        ``kept``, ``dense_rows`` and ``rows`` (models/decoder.py:
        ``_forward_selected``)."""
        parts = [toks.reshape(-1), pois.astype(jnp.int32).reshape(-1)]
        for c, n in zip(counts, DecodeEngine.COUNTS):
            if c is not None:
                parts.append(jnp.sum(c.reshape(-1, n), axis=0))
        return jnp.concatenate(parts)


    @staticmethod
    def _ragged_step_body(cfg, mesh, params, sample_args, eos, carry, xs):
        """One ragged mixed prefill+decode step (chunked prefill,
        ISSUE 10): every row carries a CB-token query chunk of which
        ``q_lens[b]`` are live. Decode rows run with ``q_len == 1``,
        ``feed == False`` (the carried token is the input) and ``emit ==
        True`` — for them the positions/slots/counters arithmetic below
        reduces exactly to ``_decode_step_body``'s, so their token streams
        match the split decode path. Mid-prefill rows feed prompt slices
        (``feed == True``) and suppress sampling until the chunk that
        completes the prompt (``emit`` flips on): the token sampled there
        — at counter ``cur_pos + q_len`` = prompt length, the prefill
        counter — is the row's first token, exactly what the dedicated
        prefill program would have produced."""
        from llmss_tpu.models.decoder import forward_ragged
        from llmss_tpu.ops.sampling import fold_step_outcome

        tokens, cache, cur_pos, done, poisoned = carry
        ids, q_lens, feed, emit = xs
        CB = ids.shape[1]
        # Decode rows consume the device-resident carry token; prefill
        # rows consume the host-fed prompt slice.
        ids = ids.at[:, 0].set(jnp.where(feed, ids[:, 0], tokens))
        rel = jnp.arange(CB, dtype=jnp.int32)
        positions = cur_pos[:, None] + rel[None, :]
        valid = rel[None, :] < q_lens[:, None]
        live = valid & ~done[:, None]
        # Dead columns (chunk padding / done rows) write nowhere: slot
        # goes positive-OOB and position -1 — same containment as the
        # decode step's done-row handling (docs/paged-kv.md).
        slots = jnp.where(live, positions % cache.max_len, cache.max_len)
        kv_pos = jnp.where(live, positions, -1)
        aux = {}
        logits, cache = forward_ragged(
            cfg, params, ids, positions, cache, slots, q_lens,
            kv_write_positions=kv_pos, mesh=mesh, aux=aux,
        )
        tok = sample(logits[:, 0], counters=cur_pos + q_lens, **sample_args)
        tok, done2, poisoned = fold_step_outcome(
            logits[:, 0], tok, done, poisoned, eos
        )
        # Mid-prefill rows emit nothing this step: keep the carried token
        # and done state (a garbage mid-prompt sample must not EOS the
        # row). Poison is cumulative regardless — non-finite logits in
        # any chunk condemn the row.
        tok = jnp.where(emit, tok, tokens)
        done = jnp.where(emit, done2, done)
        cur_pos = cur_pos + q_lens
        return (tok, cache, cur_pos, done, poisoned), (
            tok, (aux.get("moe_counts"), aux.get("dsa_counts")),
        )

    @staticmethod
    def _ragged_group_impl(
        cfg, mesh, params, tokens, cache, cur_pos, sample_args, done,
        eos, ids_seq, qlens_seq, feed_seq, emit_seq,
    ):
        """A GROUP of ragged mixed steps as one program — the chunked-
        prefill twin of ``_decode_group_impl``. ``ids_seq`` [nc, B, CB],
        ``qlens_seq``/``feed_seq``/``emit_seq`` [nc, B] are host-planned
        per-step chunk schedules (which rows feed prompt slices, which
        decode). One packed int32 transfer returns ``nc·B`` tokens then
        ``nc·B`` cumulative poison snapshots — same layout as the decode
        group at ``n_steps == 1``, so the scheduler's group processing is
        shared. Returns ``(packed, last_tok, cache, cur_pos, done)``."""
        body = partial(
            DecodeEngine._ragged_step_body, cfg, mesh, params, sample_args,
            eos,
        )
        # Pin the stacked ys replicated — same GSPMD partial-sum hazard
        # as _decode_group_impl (parallel/sharding.ys_pin).
        from llmss_tpu.parallel.sharding import ys_pin

        pin = ys_pin(mesh)

        def step(carry, xs):
            carry, (tok, counts) = body(carry, xs)
            return carry, (pin(tok), pin(carry[4]), counts)

        poisoned0 = jnp.zeros_like(done)
        carry, (toks, pois, counts) = jax.lax.scan(
            step, (tokens, cache, cur_pos, done, poisoned0),
            (ids_seq, qlens_seq, feed_seq, emit_seq),
        )
        tokens, cache, cur_pos, done, _ = carry
        packed = DecodeEngine._pack_group(toks, pois, counts)
        return packed, tokens, cache, cur_pos, done

    # -- host API -----------------------------------------------------------

    def timed_prefill(self, prefill_fn, *args, batch: int):
        """Run a jitted prefill, recording prefill latency, TTFT, and the
        request count (one definition for all prefill sites: generate,
        generate_fused, and the continuous batcher's row admission)."""
        t0 = time.perf_counter()
        with self.metrics.prefill.time():
            out = prefill_fn(*args)
            out[0].block_until_ready()
        self.metrics.ttft.record(time.perf_counter() - t0)
        self.metrics.add_request(batch)
        return out

    def seq_buckets(self) -> list[int]:
        """Every prompt bucket _pad_prompts can produce for this engine."""
        out, b = [], 16
        while b < self.max_seq_len:
            out.append(b)
            b *= 2
        out.append(self.max_seq_len)
        return out

    def bucket_ladder(self) -> list[int]:
        """The static cache-read buckets decode executables compile for:
        multiples of ``max(32, max_seq_len/16)`` below max_seq_len — at
        most 15 entries, so the executable set stays bounded while the
        average over-read is ~one granule. ``LLMSS_BUCKETS=0`` disables
        bucketing (every decode reads the full ring)."""
        import os

        if os.environ.get("LLMSS_BUCKETS") == "0":
            return []
        g = max(32, -(-self.max_seq_len // (16 * 32)) * 32)  # round UP
        return list(range(g, self.max_seq_len, g))

    def _bucketable(self) -> bool:
        """Whether this engine's decode path can bucket cache reads at
        all: an sp>1 mesh reads the full cache by construction."""
        from llmss_tpu.parallel.mesh import AXIS_SP

        return not (
            self.mesh is not None and AXIS_SP in self.mesh.shape
            and self.mesh.shape[AXIS_SP] > 1
        )

    def decode_bucket(self, pos_bound: int) -> int | None:
        """Pick the cache-read bucket for a decode call whose rows' ring
        positions (current + steps in the call) are all < ``pos_bound``.
        Returns None — read the full ring — when no ladder entry covers it,
        when any row may have wrapped (pos_bound > max_seq_len), or on an
        sp>1 mesh (which reads the full cache by construction)."""
        if not self._ladder or pos_bound > self.max_seq_len:
            return None  # wrapped rows: full-ring semantics
        if not self._bucketable():
            return None
        for b in self._ladder:
            if b >= pos_bound:
                return b
        return None

    def prewarm_bucket_set(self) -> "list[int | None]":
        """Every ``t_bucket`` value the live decode path can pick — what a
        prewarm must compile. Skips the ladder when this engine's decode
        path can't bucket at all (an sp>1 mesh): every ladder value would
        compile a byte-identical full-ring program."""
        out: list[int | None] = [None]
        if self.decode_bucket(1) is not None:
            out += self._ladder
        return out

    def check_capacity(self, n_prompt_tokens: int, max_new_tokens: int):
        """Reject a request that cannot fit the ring: it would advance past
        max_seq_len mid-generation, wrap, and silently slide its own early
        context out of the window. The ONE capacity rule shared by every
        serving path (batch Worker and continuous batcher)."""
        if n_prompt_tokens + max_new_tokens > self.max_seq_len:
            raise ValueError(
                f"prompt ({n_prompt_tokens} tokens) + max_new_tokens "
                f"({max_new_tokens}) exceeds the engine's max_seq_len "
                f"({self.max_seq_len})"
            )

    def prewarm(
        self, batch: int, *, chunk_steps: tuple[int, ...] | int = (),
        buckets: bool = True, prefix_prefill: bool = False,
    ) -> int:
        """Compile every executable the serving path can hit at ``batch``:
        prefill for each seq bucket, the single-token decode step, and the
        fused chunk scans — each × every cache-read bucket when ``buckets``
        (the default; the live path picks buckets by row position, so all
        are reachable). Eats the multi-second XLA compiles at worker
        startup instead of on the first unlucky request. Returns the number
        of executables compiled.

        Each executable is compiled exactly ONCE: ``generate``/
        ``generate_fused`` (like the scheduler) re-wrap every carried state
        array with the engine's canonical shardings, so each executable has
        a single steady-state input signature.

        ``prefix_prefill`` additionally compiles each prefill bucket's
        prefix-reuse variant (the ``start``-offset signature): set it when
        this engine will serve ``generate(prefix=...)`` so the first
        prefix request doesn't eat the multi-second prefill compile
        mid-serve. (The per-prefix seed scatter still compiles on first
        use — its shape depends on the prefix length — but that's a
        sub-second scatter compile, not a model compile.)"""
        if isinstance(chunk_steps, int):
            chunk_steps = (chunk_steps,)
        if devtel.enabled():
            devtel.install_monitoring_hook()
            devtel.observer().watch_obj(self)
        with devtel.setup_span("setup.prewarm") as sp:
            n = self._prewarm(batch, chunk_steps, buckets, prefix_prefill)
            sp.set(executables=n)
        return n

    def _prewarm(self, batch, chunk_steps, buckets, prefix_prefill) -> int:
        warm = devtel.warm
        sa = self._sample_args(GenerationParams(), batch)
        n = 0
        for S in self.seq_buckets():
            cache = self.new_cache(batch)
            ids = jnp.zeros((batch, S), jnp.int32)
            lens = jnp.ones(batch, jnp.int32)
            tok, _, cache = warm(
                "prefill", {"P": batch, "S": S},
                self._prefill, self.params, ids, cache, lens, sa,
            )
            del cache
            n += 1
            if prefix_prefill:
                cache = self.new_cache(batch)
                tok, _, cache = warm(
                    "prefill", {"P": batch, "S": S, "prefix": True},
                    self._prefill, self.params, ids, cache, lens, sa,
                    jnp.zeros(batch, jnp.int32),
                )
                del cache
                n += 1
        tok = self.canon_vec(tok)
        bucket_set = self.prewarm_bucket_set() if buckets else [None]
        cache = self.canon_cache(self.new_cache(batch))
        cur = self.canon_vec(jnp.ones(batch, jnp.int32))
        for tb in bucket_set:
            _, _, c2 = warm(
                "decode", {"t_bucket": tb},
                self._decode, self.params, tok, cache, cur, sa, t_bucket=tb,
            )
            cache = self.canon_cache(c2)
            n += 1
        for k in chunk_steps:
            if k <= 1:
                continue
            done = self.canon_vec(jnp.zeros(batch, bool))
            eos = self.canon_vec(jnp.full(batch, -1, jnp.int32))
            for tb in bucket_set:
                # generate()'s chunked branch runs the grouped program at
                # n_chunks=1 — token/position carries are donated, so
                # rebind them from the outputs before the next compile.
                _, t2, c2, cur2, _ = warm(
                    "decode_group", {"chunks": 1, "k": k, "t_bucket": tb},
                    self._decode_group,
                    self.params, tok, cache, cur, sa, done, eos,
                    n_chunks=1, n_steps=k, t_bucket=tb,
                )
                cache = self.canon_cache(c2)
                tok = self.canon_vec(t2)
                cur = self.canon_vec(cur2)
                n += 1
        # Drain the device before returning: each prewarm call above also
        # DISPATCHED one execution, and the first execution of a program
        # can carry a program-load cost — left queued, that backlog would
        # land on the first real request as "TTFT" that is really
        # deferred prewarm work (its size is not measured on the current
        # machine).
        with devtel.setup_span("setup.prewarm.drain"):
            jax.block_until_ready(cache.positions)
            _ = int(jnp.zeros((), jnp.int32) + 1)
        del cache
        return n

    def new_cache(self, batch: int | None = None):
        if self.kv_layout == "paged":
            # Engine-owned generate paths use the dense-equivalent identity
            # layout (full pool, no allocator); the scheduler builds its
            # shared-pool cache via new_paged_cache directly.
            return self.new_paged_cache(batch)
        return init_cache(
            self.mesh,
            n_layers=self.cfg.n_layers,
            batch=batch or self.batch_size,
            max_len=self.max_seq_len,
            n_kv_heads=self.cfg.n_kv_heads,
            head_dim=self.cfg.head_dim,
            dtype=self._cache_dtype,
        )

    def new_paged_cache(
        self, batch: int | None = None, *,
        num_blocks: int | None = None, identity: bool = True,
    ) -> PagedKVCache:
        """Fresh paged cache. ``identity=True`` (engine generate paths)
        pre-maps row b to blocks [b*MB, (b+1)*MB) over a full pool;
        ``identity=False`` (scheduler) starts every table at the unmapped
        sentinel and sizes the pool to ``num_blocks`` (default: the
        engine's ``kv_blocks`` flag, else dense-equivalent)."""
        b = batch or self.batch_size
        if num_blocks is None and not identity:
            num_blocks = self.kv_blocks
        return init_paged_cache(
            self.mesh,
            n_layers=self.cfg.n_kv_layers,
            batch=b,
            max_len=self.max_seq_len,
            row=self.cfg.cache_row,
            dtype=self._cache_dtype,
            block_size=self.block_size,
            num_blocks=num_blocks,
            identity_tables=identity,
            state_shapes=ssm_state_shapes(self.cfg),
            state_layers=self.cfg.n_state_layers,
            index_dim=(
                self.cfg.indexer.pool_dim if self.cfg.indexer else None
            ),
        )

    # -- canonical state shardings ------------------------------------------
    #
    # jit-produced arrays carry GSPMD-inferred shardings whose PartitionSpec
    # representation is not a stable normal form: feeding one executable's
    # output to another can key a fresh compile even though the layout is
    # identical (round 3 worked around this by prewarming every executable
    # TWICE to cover the 2-cycle of representations). The scheduler instead
    # re-wraps every state array it carries across steps with the engine's
    # canonical shardings — ``jax.device_put`` to an equivalent sharding is
    # a metadata rewrap, not a copy — so each executable has exactly ONE
    # steady-state input signature and prewarm compiles it exactly once
    # (asserted by tests/test_serve.py::test_prewarm_covers_all_shapes).

    def _canon_cache_shardings(self, cache):
        # Memoized: canon_cache runs once per decoded token on the
        # single-step generate path. Dense shardings depend on the batch
        # (dp shards rows); paged ones only on the layout (the pool is
        # row-free) — the key carries both plus the cache type.
        paged = isinstance(cache, PagedKVCache)
        key = (paged, cache.block_tables.shape[0] if paged
               else cache.k.shape[1])
        hit = self._canon_cache_memo.get(key)
        if hit is not None:
            return hit
        from jax.sharding import NamedSharding

        from llmss_tpu.engine.cache import (
            cache_specs_for, paged_cache_specs_for,
        )

        if paged:
            specs = paged_cache_specs_for(
                self.mesh, row=self.cfg.cache_row, dtype=self._cache_dtype,
            )
            out = PagedKVCache(*[
                NamedSharding(self.mesh, s) if s is not None else None
                for s in specs
            ])
        else:
            specs = cache_specs_for(
                self.mesh, batch=cache.k.shape[1],
                max_len=self.max_seq_len,
                n_kv_heads=self.cfg.n_kv_heads, dtype=self._cache_dtype,
            )
            out = KVCache(*[
                NamedSharding(self.mesh, s) if s is not None else None
                for s in specs
            ])
        self._canon_cache_memo[key] = out
        return out

    def canon_cache(self, cache):
        """Re-wrap a (possibly jit-produced) cache with the same canonical
        shardings ``new_cache`` uses — layout-identical, so no data moves."""
        sh = self._canon_cache_shardings(cache)
        return type(cache)(*[
            jax.device_put(x, s) if x is not None else None
            for x, s in zip(cache, sh)
        ])

    def canon_vec(self, x: jax.Array) -> jax.Array:
        """Canonical (replicated) sharding for small per-row state vectors
        (tokens, positions) carried across scheduler steps."""
        from jax.sharding import NamedSharding, PartitionSpec

        return jax.device_put(x, NamedSharding(self.mesh, PartitionSpec()))

    def _sample_args(self, gens: "GenerationParams | list[GenerationParams]",
                     batch: int):
        if isinstance(gens, GenerationParams):
            gens = [gens] * batch
        return dict(
            seeds=jnp.asarray([g.seed for g in gens], jnp.int32),
            temperature=jnp.asarray(
                [g.temperature for g in gens], jnp.float32
            ),
            top_k=jnp.asarray([g.top_k for g in gens], jnp.int32),
            top_p=jnp.asarray([g.top_p for g in gens], jnp.float32),
            greedy=jnp.asarray([g.is_greedy for g in gens], bool),
        )

    def _pad_prompts(
        self, prompts: list[list[int]], pad_id: int = 0
    ) -> tuple[np.ndarray, np.ndarray]:
        lens = np.array([len(p) for p in prompts], np.int32)
        if lens.max() > self.max_seq_len:
            raise ValueError(
                f"prompt length {lens.max()} exceeds max_seq_len "
                f"{self.max_seq_len}"
            )
        S = _bucket(int(lens.max()), self.max_seq_len)
        ids = np.full((len(prompts), S), pad_id, np.int32)
        for i, p in enumerate(prompts):
            ids[i, : len(p)] = p
        return ids, lens

    def generate(
        self,
        prompts: list[list[int]],
        gen: GenerationParams | list[GenerationParams],
        *,
        on_token=None,
        on_increment=None,
        on_poisoned=None,
        cancel_poll=None,
        chunk_steps: int = 1,
        live_rows: int | None = None,
        prefix: "Prefix | None" = None,
    ) -> list[list[int]]:
        """Streaming host-loop generation (≙ generate.py:99-145 cache path).

        ``prefix``: a retained KV segment (``build_prefix``) every prompt
        must extend — its tokens are NOT re-prefilled: the cache rows are
        seeded from the segment and only each prompt's suffix runs through
        the model. On bf16 caches emitted tokens are identical to the
        from-scratch run (positions, masks, and sampling counters are all
        absolute); int8 caches are storage-bit-stable but the suffix reads
        the prefix through quantized KV, so tokens can differ from a
        from-scratch run at logit ties (models/decoder.py).

        ``gen`` may be a list with one entry per prompt: a batch can mix
        greedy/sampled requests with different warpers, lengths, and EOS ids
        (the serving path; the reference hard-codes one config per batch).
        ``on_token(step, tokens: np.ndarray)`` is called per step with the
        raw batch tokens; ``on_increment(row, new_tokens: list[int])`` is
        called only for tokens actually ACCEPTED into a row's output (EOS
        and post-completion fills excluded) — the serving layer streams
        from here with engine-owned completion semantics. Stops early when every row is done.
        ``on_poisoned(row)`` (optional) fires when a row's logits go
        non-finite mid-decode (``chunk_steps > 1`` path — the serving
        path): that row stops decoding with the tokens produced before the
        poison, co-batched rows are unaffected, and the caller should
        answer the row with an error rather than a truncated success.
        ``cancel_poll() -> iterable[int]`` (optional) is polled for row
        indices whose clients went away: those rows stop accumulating
        tokens and count as done.

        ``chunk_steps > 1`` runs that many fused decode steps per host
        round-trip (one dispatch + one token fetch per chunk instead of per
        token): the serving throughput lever — host-link latency amortizes
        across the chunk. Token *results* are identical; the trade is
        granularity: ``on_token``/``cancel_poll`` fire once per chunk, and
        a row reaching EOS mid-chunk stops contributing but the chunk still
        runs to its end on device (its extra steps produce discarded EOS
        fills — same cost the single-step path pays keeping done rows in
        the batch).

        ``live_rows`` marks how many leading rows are real requests when
        the caller padded the batch to its envelope (serving): metrics
        count only those, and only their tokens.
        """
        if chunk_steps < 1:
            raise ValueError(f"chunk_steps must be >= 1, got {chunk_steps}")
        B = len(prompts)
        gens = gen if isinstance(gen, list) else [gen] * B
        assert len(gens) == B
        for g in gens:
            g.validate()
        cache = self.new_cache(B)
        sample_args = self._sample_args(gens, B)

        if prefix is not None:
            full_lens, suffixes = self.split_prefix(prompts, prefix)
            if int(full_lens.max()) > self.max_seq_len:
                # Same guard _pad_prompts applies on the non-prefix path:
                # a prefix+suffix total past the ring would wrap the
                # suffix over the just-seeded prefix slots.
                raise ValueError(
                    f"prompt length {int(full_lens.max())} exceeds "
                    f"max_seq_len {self.max_seq_len}"
                )
            ids, suf_lens = self._pad_prompts(suffixes)
            if prefix.length + ids.shape[1] > self.max_seq_len:
                # The suffix prefill pads to a BUCKET, and every padded
                # column computes a slot (slot = position % max_len) even
                # though its kv position is masked to -1 — so a start +
                # bucket reaching past the ring wraps those writes over
                # the just-seeded prefix slots, destroying the reused KV.
                # The request itself fits (checked above); only the
                # bucket-padded suffix doesn't. Fall back to a from-scratch
                # prefill of the full prompts — identical tokens, just
                # without the prefix's FLOP savings.
                prefix = None
        if prefix is not None:
            cache = self.canon_cache(self.seed_cache(cache, prefix))
            start = jnp.full(B, prefix.length, jnp.int32)
            tok, _, cache = self.timed_prefill(
                self._prefill, self.params, jnp.asarray(ids), cache,
                jnp.asarray(suf_lens), sample_args, start,
                batch=live_rows or B,
            )
            lens = full_lens
        else:
            ids, lens = self._pad_prompts(prompts)
            tok, _, cache = self.timed_prefill(
                self._prefill, self.params, jnp.asarray(ids), cache,
                jnp.asarray(lens), sample_args, batch=live_rows or B,
            )
        # Carry canon-resharded state (like the scheduler): every decode
        # executable then has exactly one steady-state input signature, so
        # prewarm compiles each once and no mid-request compile can occur.
        tok = self.canon_vec(tok)
        cache = self.canon_cache(cache)
        eos = np.asarray(
            [g.eos_token_id if g.eos_token_id is not None else -1
             for g in gens]
        )
        max_new = np.asarray([g.max_new_tokens for g in gens])
        out = [[] for _ in range(B)]
        done = np.zeros(B, bool)
        cur_pos = self.canon_vec(jnp.asarray(lens))
        # Host-side upper bound on any row's ring position — drives the
        # cache-read bucket (decode cost follows live context, not ring
        # size).
        pos_hi = int(lens.max())
        total_steps = int(max_new.max())
        eos_dev = self.canon_vec(jnp.asarray(eos, jnp.int32))

        step = 0

        inc_buf: list[list[int]] = [[] for _ in range(B)]

        def flush_increments() -> None:
            # One on_increment per row per host round-trip (chunk): SSE /
            # broker push costs scale with chunks, not tokens.
            if on_increment is None:
                return
            for i in range(B):
                if inc_buf[i]:
                    on_increment(i, inc_buf[i])
                    inc_buf[i] = []

        def process(tok_np) -> bool:
            """Account one step's tokens; returns True when all rows done."""
            nonlocal step
            newly_done = (tok_np == eos) | (step >= max_new)
            for i in range(B):
                if not done[i] and not newly_done[i]:
                    out[i].append(int(tok_np[i]))
                    if on_increment is not None:
                        inc_buf[i].append(int(tok_np[i]))
                    if len(out[i]) == max_new[i]:
                        done[i] = True
            done[:] = done | newly_done
            if on_token is not None:
                on_token(step, tok_np)
            step += 1
            return bool(done.all())

        process(np.asarray(tok))
        flush_increments()
        while not done.all() and step < total_steps:
            if cancel_poll is not None:
                for i in cancel_poll():
                    done[i] = True
                if done.all():
                    break
            # Always run full chunks (never a remainder-sized one): a
            # distinct n_steps would compile a fresh executable mid-request.
            # Overshoot columns are discarded by process() — once step
            # reaches every row's max_new, all rows are done and the loop
            # exits.
            k = chunk_steps
            if k == 1:
                with self.metrics.decode_step.time():
                    tok, _, cache = self._decode(
                        self.params, tok, cache, cur_pos, sample_args,
                        t_bucket=self.decode_bucket(pos_hi + 1),
                    )
                    # Sync inside the timer: dispatch is async, so without
                    # this the stat would record ~µs dispatch overhead, not
                    # step latency. The loop reads the token next iteration
                    # anyway, so this costs nothing.
                    tok.block_until_ready()  # lint: ignore[host-sync-in-loop]
                tok = self.canon_vec(tok)
                cache = self.canon_cache(cache)
                cur_pos = cur_pos + 1
                pos_hi += 1
                # Deliberate per-step fetch: chunk_steps=1 IS the
                # token-granularity streaming mode; the sync is the product.
                process(np.asarray(tok))  # lint: ignore[host-sync-in-loop]
                flush_increments()
            else:
                t0 = time.perf_counter()
                tb = self.decode_bucket(pos_hi + k)
                packed, last_tok, cache, cur_pos, _ = self._decode_group(
                    self.params, tok, cache, cur_pos, sample_args,
                    self.canon_vec(jnp.asarray(done)), eos_dev,
                    n_chunks=1, n_steps=k, t_bucket=tb,
                )
                cache = self.canon_cache(cache)
                cur_pos = self.canon_vec(cur_pos)
                tok = self.canon_vec(last_tok)
                pos_hi += k
                self.metrics.host_dispatch.record(time.perf_counter() - t0)
                self.metrics.add_group()
                # ONE packed fetch per chunk BY DESIGN: tokens and poison
                # flags cross the host link in a single transfer (the
                # pipelined scheduler overlaps it with the next dispatch).
                with self.metrics.host_fetch.time():
                    flat = np.asarray(packed)  # lint: ignore[host-sync-in-loop]
                self.metrics.add_host_sync()
                chunk_np = flat[: B * k].reshape(B, k)
                # (a model with routed experts appends its two counts)
                poisoned_np = flat[B * k: B * (k + 1)].astype(bool)
                t1 = time.perf_counter()
                self.metrics.decode_step.record((t1 - t0) / k)
                t_cb = time.perf_counter()
                for col in range(k):
                    if process(chunk_np[:, col]):
                        break
                # Poisoned rows were forced done on device (EOS-filled from
                # the bad step on), so process() already stopped accepting
                # their tokens; surface the flag so the caller errors the
                # row instead of returning a silently truncated success.
                for i in range(B):
                    if poisoned_np[i] and not done[i]:
                        done[i] = True
                if on_poisoned is not None:
                    for i in np.flatnonzero(poisoned_np):
                        on_poisoned(int(i))
                flush_increments()
                self.metrics.host_callback.record(
                    time.perf_counter() - t_cb
                )
        self.metrics.add_tokens(
            sum(len(o) for o in out[: live_rows or B])
        )
        return out

    def generate_speculative(
        self, prompts: list[list[int]], gen: GenerationParams, *,
        gamma: int = 4, ngram: int = 3,
    ) -> list[list[int]]:
        """Greedy generation with prompt-lookup speculative decoding:
        exactly ``generate``'s tokens, 1..gamma+1 of them per forward /
        host round-trip (engine/speculative.py)."""
        from llmss_tpu.engine.speculative import generate_speculative

        return generate_speculative(
            self, prompts, gen, gamma=gamma, ngram=ngram
        )

    def generate_fused(
        self, prompts: list[list[int]], gen: GenerationParams
    ) -> list[list[int]]:
        """Whole-generation-on-device path: prefill + one fused scan jit.

        Zero per-token host round-trips — the TPU-native answer to the
        reference's per-token broadcast tax (``generate.py:144``).
        """
        gen.validate()
        B = len(prompts)
        ids, lens = self._pad_prompts(prompts)
        cache = self.new_cache(B)
        sample_args = self._sample_args(gen, B)

        tok, _, cache = self.timed_prefill(
            self._prefill, self.params, jnp.asarray(ids), cache,
            jnp.asarray(lens), sample_args, batch=B,
        )
        tok = self.canon_vec(tok)
        cache = self.canon_cache(cache)
        eos = jnp.int32(
            gen.eos_token_id if gen.eos_token_id is not None else -1
        )
        eos_dev = self.canon_vec(jnp.full(B, int(eos), jnp.int32))
        done = self.canon_vec(tok == eos_dev)
        # Read the prefill token BEFORE the grouped call: the token carry
        # is donated, so the buffer is dead once the program is enqueued.
        first = np.asarray(tok)[:, None]
        n_steps = gen.max_new_tokens - 1
        packed, _, cache, _, done = self._decode_group(
            self.params, tok, cache, self.canon_vec(jnp.asarray(lens)),
            sample_args, done, eos_dev, n_chunks=1, n_steps=n_steps,
            t_bucket=self.decode_bucket(int(lens.max()) + n_steps),
        )
        rest = np.asarray(packed)[: B * n_steps].reshape(B, n_steps)
        all_toks = np.concatenate([first, rest], axis=1)
        out = []
        for row in all_toks:
            stop = np.where(row == int(eos))[0]
            out.append(row[: stop[0]].tolist() if stop.size else row.tolist())
        self.metrics.add_tokens(sum(len(o) for o in out))
        return out
