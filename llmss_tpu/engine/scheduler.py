"""Continuous batching: iteration-level request scheduling, pipelined.

The reference serves one request at a time end-to-end
(``consumer_server.py:73`` ``batch_size = 1``, with a TODO admitting batching
is future work). This scheduler implements Orca-style continuous batching on
top of the static-shape engine: a persistent ``[L, B, T]`` ring cache whose
**rows** are the scheduling unit. New requests are prefilled into a scratch
cache and inserted into free rows between decode chunks; every chunk advances
all active rows with per-row sampling parameters; finished rows free for the
next waiting request — no request waits for an unrelated request to finish.

**The decode state lives on device and the host observes it one GROUP late.**
Round 3 fetched every chunk's tokens before dispatching the next chunk, so
each chunk paid a full device→host round-trip on the critical path (its
length is not measured on the current machine; on the round-3 machine the
serving layer reached 0.21 of roofline while the bare engine hit 0.65). Here:

- ``tokens``/``cur_pos`` are device arrays; the fused decode group feeds
  itself, so group N+1 is dispatched *before* group N's results are fetched
  and the fetch overlaps device compute instead of serializing behind it.
- While busy, ``group_chunks`` fused chunks run as ONE jitted program
  (``DecodeEngine._decode_group``): EOS/done and poison flags carry on
  device between the chunks, and the whole group's tokens + per-chunk
  poison flags cross the host link in a single packed int32 transfer —
  host syncs and dispatch overhead scale per group, not per chunk
  (docs/decode-loop.md).
- Admissions merge their first tokens into the device state with a jitted
  scatter (``DecodeEngine._admit_merge``) — the host never needs to see a
  token to keep the device advancing.
- The host processes group N's results (stream callbacks, EOS/max-token
  finishes, row frees) while group N+1 runs. Freeing and admission therefore
  lag one group — a freshly finished row keeps decoding discarded fills for
  one extra group, the same cost an idle row pays anyway.

Invariant tested in ``tests/test_continuous.py``: interleaved admission must
produce exactly the tokens the request would get alone (row isolation — the
causal mask is driven by per-row cache positions, so rows never see each
other; the one-group lag changes *when* the host learns tokens, never which
tokens the device computes), and grouped dispatch must emit bit-identical
token streams to the ungrouped path under EOS, poison, and admission churn.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from collections import deque
from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from llmss_tpu.engine.cache import (
    BlockAllocator, KVCache, PagedKVCache, export_blocks,
    export_dense_row, import_blocks, table_sentinel,
)
from llmss_tpu.engine.engine import DecodeEngine, GenerationParams, _bucket
from llmss_tpu.utils import devtel, trace


@dataclasses.dataclass
class _Row:
    req_id: str
    gen: GenerationParams
    out: list[int]
    # Called as done_cb(tokens) on completion, done_cb(tokens, True) when
    # the request was cancelled (tokens = what was produced before the
    # cancel) — so the serving layer can answer honestly instead of
    # disguising a cancelled request as a success.
    done_cb: Callable[..., None]
    # Optional per-increment hook: called with the NEW tokens after each
    # scheduler step that produced any (streaming delivery; granularity is
    # the decode chunk).
    stream_cb: Callable[[list[int]], None] | None = None
    emitted: int = 0
    # Row is active on device (its admission merge is dispatched) but the
    # host hasn't yet fetched its prefill-sampled first token.
    awaiting_first: bool = True
    t_submit: float = 0.0
    # Preemption rank (SLO_CLASS_RANK: 0 = interactive, highest). A
    # pending request with a strictly LOWER rank may evict this row when
    # admission is blocked; equal ranks never preempt (livelock).
    priority: int = 1
    # Tokens this row replays from a previous (preempted) run: ``out`` is
    # preloaded with them and finish thresholds shift by this count, so
    # the resumed stream continues exactly where the evicted one stopped.
    replayed: int = 0
    # The prompt's prefill is on the device queue (``prefill_dispatch`` is
    # recorded). False only between a chunked admission and the ragged
    # group that plans the prompt's first chunk.
    prefill_dispatched: bool = True


@dataclasses.dataclass
class _InFlightAdmission:
    """An admission whose prefill + insert + device-state merge are
    dispatched but whose first tokens have not been fetched. Rows are
    already active (the device decodes them from the next chunk on);
    ``resolve`` is host bookkeeping only."""

    entries: list  # [(row_idx, _Row)]
    tok: jax.Array  # [P] first sampled token per admission row (device)


@dataclasses.dataclass
class _InFlightGroup:
    """A dispatched decode GROUP (n_chunks fused chunks in one jitted
    program) whose packed results the host hasn't read yet."""

    # Flat int32 device array (copy_to_host_async issued):
    # ``n_chunks·rows·k`` tokens followed by ``n_chunks·rows`` per-chunk
    # poisoned flags — the group's ONE device→host transfer. Poisoned rows
    # were already forced done on device (EOS fills from the bad step on);
    # _process_group errors them out instead of reporting a success.
    packed: jax.Array
    n_chunks: int
    k: int  # steps per chunk
    # An admission's device work (prefill+insert+merge) ran between the
    # previous group and this one, so this group's fetch-to-fetch interval
    # is not a clean decode-only sample.
    has_admission: bool = False
    # Ragged mixed group (chunked prefill): for each row whose prompt
    # completed inside this group, the chunk index whose sampled token is
    # the request's FIRST token — admission bookkeeping happens at that
    # chunk in _process_group (chunked admissions never create an
    # _InFlightAdmission). Rows absent from the map either finished
    # streaming earlier or are still mid-prompt (skip their chunks).
    prefill_firsts: dict | None = None
    # Which program the group ran: the ``sched.dispatch`` span carries it.
    kind: str = "decode_group"
    # The batcher's running group number (from 0): on the loop track the
    # group's ``sched.dispatch``, ``sched.fetch_wait`` and ``sched.callback``
    # spans carry it, one iteration apart.
    no: int = 0


def select_preemption_victim(candidates, head_priority: int):
    """Pick the row to evict for a blocked head request, or ``None``.

    ``candidates`` is an iterable of ``(key, priority, emitted_tokens)``
    for the rows that are *evictable at all* (the caller applies its own
    structural filters — settled, refundable, not mid-prefill). Policy:
    only rows strictly outranked by the head (``priority >
    head_priority``) qualify; among those, evict the lowest class first,
    ties broken by FEWEST emitted tokens — the cheapest replay prefill.
    Exact ties keep the first candidate, so iteration order is part of
    the contract (dict order for the batcher, row order for the sim).

    Factored to module level so the fleet simulator preempts with the
    scheduler's REAL policy rather than a re-implementation; both
    ``ContinuousBatcher._maybe_preempt`` and ``sim.replica`` call this.
    """
    victim = None
    for key, priority, emitted in candidates:
        if priority <= head_priority:
            continue
        if victim is None or (priority, -emitted) > (victim[1], -victim[2]):
            victim = (key, priority, emitted)
    return None if victim is None else victim[0]


class ContinuousBatcher:
    def __init__(
        self, engine: DecodeEngine, *, rows: int = 8, chunk_steps: int = 1,
        chunk_steps_low: int | None = None, group_chunks: int = 1,
        prefill_only: bool = False, chunked_prefill: int | None = None,
    ):
        # chunk_steps > 1 advances all rows that many tokens per scheduler
        # step (one fused scan instead of per-token dispatch); combined
        # with the one-chunk-lag pipeline the host round-trip disappears
        # from the critical path entirely.
        #
        # The chunk is also the scheduling granularity: admission and
        # row-freeing happen once per chunk, so TTFT carries ~1.5 chunks
        # of latency. ``chunk_steps_low`` (default: half of chunk_steps)
        # is used while under 3/4 of the rows are busy — at low load the
        # chip has headroom and the shorter chunk halves perceived TTFT;
        # at saturation the full chunk keeps the host off the critical
        # path. Both sizes are prewarmed.
        #
        # ``group_chunks`` (K) dispatches K chunks as ONE jitted program
        # while busy (DecodeEngine._decode_group): on-device EOS/poison
        # carry between the chunks and the host gets one packed fetch per
        # GROUP — K× fewer host syncs and dispatches at saturation, at the
        # cost of admission/free granularity stretching to K chunks. At
        # low load the group collapses to (1 × chunk_steps_low) so TTFT
        # keeps the short-chunk latency. Token streams are bit-identical
        # to group_chunks=1 (docs/decode-loop.md).
        if chunk_steps < 1:
            raise ValueError(f"chunk_steps must be >= 1, got {chunk_steps}")
        if group_chunks < 1:
            raise ValueError(
                f"group_chunks must be >= 1, got {group_chunks}"
            )
        if prefill_only and engine.cfg.mla is not None:
            raise ValueError(
                "prefill_only (the KV hand-off) does not carry the latent "
                f"pool of model_type {engine.cfg.model_type!r}: its wire "
                "format names keys and values (docs/latent-cache.md)"
            )
        if prefill_only and engine.cfg.indexer is not None:
            raise ValueError(
                "prefill_only (the KV hand-off) does not carry the indexer's "
                f"key pool of model_type {engine.cfg.model_type!r}: its wire "
                "format names keys and values (docs/sparse-attention.md)"
            )
        if prefill_only and engine.cfg.has_state:
            # What does not carry a model's recurrent state refuses the
            # model, so that nothing runs and is silently wrong
            # (docs/recurrent-state.md).
            raise ValueError(
                "prefill_only (the KV hand-off) does not carry the "
                f"recurrent state of model_type {engine.cfg.model_type!r}"
            )
        self.engine = engine
        self.rows = rows
        self.chunk_steps = chunk_steps
        self.chunk_steps_low = (
            chunk_steps_low if chunk_steps_low is not None
            else max(1, chunk_steps // 2)
        )
        self.group_chunks = group_chunks
        # Paged KV: the scheduling capacity unit becomes the block pool,
        # not the row count — rows are admitted when free blocks cover
        # prompt + max_new (+ shared prefix blocks ride for free), and a
        # finished/cancelled row returns its blocks immediately. All the
        # paged bookkeeping below is worker-thread state (like ``active``);
        # only the BlockAllocator itself is cross-thread (metrics read it)
        # and carries its own lock.
        self._paged = engine.kv_layout == "paged"
        # Prefill-only mode (disaggregated serving, serve/handoff.py):
        # admission runs exactly as usual — seed + batched prefill into
        # pool blocks — but instead of decoding, _resolve_admission
        # EXPORTS each row's blocks through ``export_cb`` and frees the
        # row immediately. No decode group ever dispatches (active is
        # empty outside the admit->resolve window, so step() always takes
        # the direct admit path). Requests whose answer IS the first
        # token (max_new <= 1, or the prefill sampled EOS) are answered
        # locally through done_cb — bit-identical to a unified worker.
        # Paged-only: the block table is the transfer unit.
        if prefill_only and engine.kv_layout != "paged":
            raise ValueError("prefill_only requires kv_layout='paged'")
        self.prefill_only = prefill_only
        # Chunked prefill (docs/decode-loop.md): prompts admit WITHOUT a
        # dedicated prefill program — they stream through the ragged
        # mixed-batch dispatch (DecodeEngine._ragged_group) as extra query
        # rows, ``chunked_prefill`` tokens per step, alongside the decode
        # rows advancing one token each. The prefill bucket ladder and its
        # (P × S) prewarm grid die with the dedicated program, and a long
        # prompt admits across O(len/budget) *shared* steps instead of one
        # monolithic prefill that stalls every decode row for seconds.
        # Paged-only: admission is a table upload + positions merge (the
        # pool IS the scratch); the dense path would still need a row copy.
        if chunked_prefill is not None:
            if chunked_prefill < 1:
                raise ValueError(
                    f"chunked_prefill must be >= 1, got {chunked_prefill}"
                )
            if engine.kv_layout != "paged":
                raise ValueError(
                    "chunked_prefill requires kv_layout='paged'"
                )
        self.chunked_prefill = chunked_prefill
        self._chunked = chunked_prefill is not None
        # prompts that may feed at once: set below, once the pool exists
        self._feed_rows: int | None = None
        # row -> remaining prompt tokens to feed / total prompt length
        # (worker-thread state, like ``active``).
        self._inflight_prefill: dict[int, list[int]] = {}
        self._prefill_plen: dict[int, int] = {}
        # Called as export_cb(req_id, first_token, n_tokens, blocks) with
        # ``blocks`` the export_blocks() host-array dict; set by the
        # serving layer before submitting.
        self.export_cb: Callable[..., None] | None = None
        # Preemption hook: called as preempt_cb(req_id, tokens) when a
        # running row is evicted for a higher-priority pending request
        # (the serving layer stamps resume_tokens and refunds the request
        # to the broker). None disables preemption entirely — the check
        # never runs, keeping FIFO deployments at zero overhead.
        self.preempt_cb: Callable[[str, list[int]], None] | None = None
        # Tiered-KV hooks (serve/kvstore.py). ``demote_cb(prefix)``
        # receives each idle Prefix evicted from the pool — its blocks
        # are already freed (the Prefix owns its own arrays), so the
        # store encodes off-thread while admission proceeds.
        # ``park_cb(req_id, tokens, blocks)`` receives a finished session
        # turn's exported KV (see ``_maybe_park``). Both None by default:
        # without a store every eviction is a plain drop and no finish
        # exports — bit-identical to the pre-tiering batcher.
        self.demote_cb: Callable[..., None] | None = None
        self.park_cb: Callable[..., None] | None = None
        # req_id -> (token_ids, replayed): park interest registered by
        # the serving layer, which is the only holder of prompt ids (the
        # batcher's rows carry outputs, and adopted rows no ids at all).
        self._park_ids: dict[str, tuple] = {}  # guarded_by: self._lock
        if self._paged:
            mb = engine.max_seq_len // engine.block_size
            n_blocks = engine.kv_blocks or rows * mb
            with devtel.setup_span("setup.cache") as sp:
                self.cache = engine.new_paged_cache(
                    rows, num_blocks=n_blocks, identity=False
                )
                sp.set(bytes=devtel.tree_bytes(self.cache))
            self.allocator = BlockAllocator(n_blocks)
            self._sentinel = table_sentinel(n_blocks)
            self._host_tables = np.full((rows, mb), self._sentinel, np.int32)
            self._row_owned: dict[int, list[int]] = {}
            self._row_shared: dict[int, list[int]] = {}
            # row -> monotonic reserve time: block-seconds cost attribution
            # (blocks held x hold duration, charged at release).
            self._row_reserve_t: dict[int, float] = {}
            # id(prefix) -> (prefix, full-block ids); the registry holds
            # one allocator ref per block so an idle prefix survives until
            # evicted to admit new work.
            self._paged_prefixes: dict[int, tuple] = {}
            engine.metrics.set_kv_blocks(total=n_blocks, in_use=0)
            if engine.cfg.mla is not None:
                engine.metrics.set_latent_bytes_per_token(
                    self.cache.k.nbytes
                    // (self.cache.num_blocks * self.cache.block_size)
                )
            if self.cache.idx is not None:
                engine.metrics.set_index_bytes_per_token(
                    self.cache.idx.nbytes
                    // (self.cache.num_blocks * self.cache.block_size)
                )
                if self._chunked:
                    from llmss_tpu.models.decoder import feed_rows

                    self._feed_rows = feed_rows(
                        engine.cfg, self.cache, chunked_prefill
                    )
            if self.cache.ssm is not None:
                engine.metrics.set_state_pool(
                    self.cache.ssm.nbytes + self.cache.conv.nbytes,
                    state_layers=self.cache.ssm.shape[0],
                    kv_layers=self.cache.k.shape[0],
                )
                # Chunked admission runs no prefill program that could start
                # its rows from nothing, so it zeroes their state itself
                # (rows padded with the out-of-range sentinel: dropped).
                self._zero_state = jax.jit(
                    lambda ssm, conv, rows_: (
                        ssm.at[:, rows_].set(0, mode="drop"),
                        conv.at[:, rows_].set(0, mode="drop"),
                    ),
                    donate_argnums=(0, 1),
                )
            self._merge_positions = jax.jit(
                lambda big, sub, rows_: big.at[rows_].set(sub, mode="drop"),
                donate_argnums=(0,),
            )
            self._seed_blocks = jax.jit(
                self._seed_blocks_impl, donate_argnums=(0,)
            )
            # Decode-side adopt scatter (cache.import_blocks): block count
            # pads to a power of two (sentinel ids drop), so the compile
            # envelope is log2(max_blocks) programs.
            self._import_blocks = jax.jit(
                import_blocks, donate_argnums=(0,)
            )
        else:
            with devtel.setup_span("setup.cache") as sp:
                self.cache = engine.new_cache(rows)
                sp.set(bytes=devtel.tree_bytes(self.cache))
        self.pending: deque = deque()  # guarded_by: self._lock
        self.active: dict[int, _Row] = {}
        self._free = list(range(rows))  # guarded_by: self._lock
        # Host-side upper bound on each ACTIVE row's ring position — drives
        # the decode chunk's cache-read bucket (engine.decode_bucket): the
        # chunk reads only the live-context prefix of the ring, so decode
        # cost follows occupancy, not the provisioned max_seq_len. Freed
        # rows keep advancing on device past any bucket; their reads are
        # garbage nobody consumes and their writes stay within their own
        # row, so only active rows constrain the bucket.
        self._row_pos: dict[int, int] = {}
        # chunk -> how a step of that many tokens a row reads the paged pool
        # and how it updates a recurrent state (attn_read, state_update)
        self._attn_reads: dict[int, dict[str, str]] = {}
        # Device-resident decode state (see module docstring), carried in
        # the engine's canonical shardings so every executable keeps one
        # steady-state signature (DecodeEngine.canon_cache/canon_vec).
        self._tokens_dev = engine.canon_vec(jnp.zeros(rows, jnp.int32))
        self._cur_pos_dev = engine.canon_vec(jnp.zeros(rows, jnp.int32))
        self._step_count = 0  # groups dispatched: the next group's number
        self._cancelled: set[str] = set()  # guarded_by: self._lock
        self._inflight: _InFlightGroup | None = None
        self._pending_adm: _InFlightAdmission | None = None
        self._last_fetch_t: float | None = None
        self._devtel_last_t = float("-inf")
        self._lock = threading.Lock()

        cfg = engine.cfg
        self._insert = jax.jit(self._insert_impl, donate_argnums=(0,))
        self._prefill_row = jax.jit(
            partial(DecodeEngine._prefill_impl, cfg, engine.mesh),
            donate_argnums=(2,),
        )

    def _pad_row_idx(self, P: int, rows: list[int]) -> np.ndarray:
        """[P] scatter indices for an admission insert: real rows first,
        padding filled with a POSITIVE out-of-range sentinel (self.rows).
        mode="drop" only drops indices that are OOB *after* normalization,
        and JAX wraps negative indices first — a -1 sentinel would scatter
        the dummy row into live row rows-1, zeroing its KV."""
        idx = np.full(P, self.rows, np.int32)
        idx[: len(rows)] = rows
        return idx

    @staticmethod
    def _insert_impl(big: KVCache, small: KVCache, rows) -> KVCache:
        """Copy scratch-cache rows into the persistent cache at ``rows``
        ([P] int32; entries >= big rows are padding and dropped — the
        sentinel must be positive OOB, since negative indices wrap)."""
        return KVCache(
            k=big.k.at[:, rows].set(small.k, mode="drop"),
            v=big.v.at[:, rows].set(small.v, mode="drop"),
            positions=big.positions.at[rows].set(
                small.positions, mode="drop"
            ),
            k_scale=(
                big.k_scale.at[:, rows].set(small.k_scale, mode="drop")
                if big.k_scale is not None else None
            ),
            v_scale=(
                big.v_scale.at[:, rows].set(small.v_scale, mode="drop")
                if big.v_scale is not None else None
            ),
        )

    # -- paged-KV plumbing --------------------------------------------------

    @staticmethod
    def _seed_blocks_impl(cache: PagedKVCache, pk, pv, pks, pvs, block_ids):
        """Materialize a prefix's FULL blocks in the pool: the dense
        ``Prefix`` segment's first ``nf*bs`` tokens, reshaped block-wise
        and scattered at ``block_ids`` ([nf] int32). These blocks are
        immutable from here on — rows reference them via their tables and
        never write them (COW masks the seed's own writes elsewhere)."""
        bs = cache.block_size
        nf = block_ids.shape[0]

        def put(pool, seg):
            if pool is None:
                return None
            seg = seg[:, : nf * bs]
            r = seg.reshape((seg.shape[0], nf, bs) + seg.shape[2:])
            return pool.at[:, block_ids].set(r.astype(pool.dtype), mode="drop")

        return cache._replace(
            k=put(cache.k, pk), v=put(cache.v, pv),
            k_scale=put(cache.k_scale, pks), v_scale=put(cache.v_scale, pvs),
        )

    def _dev_tables(self, tables: np.ndarray) -> jax.Array:
        from jax.sharding import NamedSharding, PartitionSpec

        return jax.device_put(
            jnp.asarray(tables, jnp.int32),
            NamedSharding(self.engine.mesh, PartitionSpec()),
        )

    def _paged_scratch_view(
        self, P: int, tables: np.ndarray | None = None,
        row_idx: np.ndarray | None = None,
    ) -> PagedKVCache:
        """A P-row admission 'scratch cache' that SHARES the big pool:
        fresh per-view positions and the admitted rows' tables, but the
        same pool buffers — prefill writes land in place, so absorbing an
        admission is a positions merge + table upload, never a KV copy.
        The recurrent state pool (a model with a mixer) is shared the same
        way: ``row_idx`` ([P], padded with the out-of-range sentinel; None:
        all padding) names the pool rows the prefill writes its final
        state to."""
        eng = self.engine
        if tables is None:
            mb = eng.max_seq_len // eng.block_size
            tables = np.full((P, mb), self._sentinel, np.int32)
        state_rows = None
        if self.cache.ssm is not None:
            if row_idx is None:
                row_idx = self._pad_row_idx(P, [])
            state_rows = eng.canon_vec(jnp.asarray(row_idx, jnp.int32))
        return PagedKVCache(
            k=self.cache.k, v=self.cache.v,
            block_tables=self._dev_tables(tables),
            positions=eng.canon_vec(
                jnp.full((P, eng.max_seq_len), -1, jnp.int32)
            ),
            k_scale=self.cache.k_scale, v_scale=self.cache.v_scale,
            ssm=self.cache.ssm, conv=self.cache.conv,
            state_rows=state_rows, idx=self.cache.idx,
        )

    def _paged_absorb(self, view: PagedKVCache, row_idx: np.ndarray) -> None:
        """Fold a prefilled scratch view back into the big cache. The view's
        pool buffers ARE the big cache's (threaded through the seed/prefill
        donations), so only row positions scatter in and the host tables
        upload — this is also where freed rows' device tables go sentinel,
        cutting off their stale reads."""
        eng = self.engine
        view = eng.canon_cache(view)
        self.cache = eng.canon_cache(PagedKVCache(
            k=view.k, v=view.v,
            block_tables=self._dev_tables(self._host_tables),
            # canon_vec: the one signature prewarm compiled the merge for
            # (chunked admission and adopt upload theirs the same way) — a
            # view's own positions sharding would key a second executable
            # per admission size, compiled inside the first requests.
            positions=self._merge_positions(
                self.cache.positions, eng.canon_vec(view.positions),
                jnp.asarray(row_idx),
            ),
            k_scale=view.k_scale, v_scale=view.v_scale,
            # the admitted rows' state is already in the pool: the prefill
            # wrote it there (models/decoder.py: _layer_scan)
            ssm=view.ssm, conv=view.conv, idx=view.idx,
        ))

    def _zeroed_state(self, row_idx: np.ndarray) -> dict:
        """The state pool with rows ``row_idx`` at zero, as fields for
        ``cache._replace``; nothing for a model without one."""
        if self.cache.ssm is None:
            return {}
        ssm, conv = self._zero_state(
            self.cache.ssm, self.cache.conv, jnp.asarray(row_idx)
        )
        return {"ssm": ssm, "conv": conv}

    def _paged_evict_idle_prefixes(self, keep: int | None = None) -> int:
        """Reclaim prefix block sets no live row references (every block
        at the registry's own refcount of 1) — the paged admission's
        backstop when the pool runs dry. Returns sets evicted."""
        freed = 0
        demoted = 0
        for key, (_pfx, blocks) in list(self._paged_prefixes.items()):
            if key == keep or not blocks:
                continue
            if all(self.allocator.refcount(b) == 1 for b in blocks):
                self.allocator.free(blocks)
                del self._paged_prefixes[key]
                freed += 1
                if self.demote_cb is not None:
                    # Tiered KV: hand the Prefix down instead of dropping
                    # it. The blocks are already free — the store's encode
                    # reads the Prefix's OWN arrays, off this thread.
                    try:
                        self.demote_cb(_pfx)
                        demoted += 1
                    except Exception:  # noqa: BLE001 — a failed demote is a drop
                        pass
        if freed:
            self.allocator.record_evictions(freed)
            self.engine.metrics.add_kv_evictions(demoted, demoted=True)
            self.engine.metrics.add_kv_evictions(freed - demoted)
        return freed

    def _ensure_paged_prefix(self, prefix) -> list[int] | None:
        """Register a retained Prefix's FULL blocks in the pool (once per
        prefix object): allocate, scatter the dense segment in, and hold
        one ref per block so the set outlives its rows. Returns the block
        ids (possibly []), or None when the pool can't fit them even
        after evicting idle prefixes."""
        key = id(prefix)
        hit = self._paged_prefixes.get(key)
        if hit is not None:
            return hit[1]
        bs = self.engine.block_size
        nf = prefix.length // bs
        if nf == 0:
            self._paged_prefixes[key] = (prefix, [])
            return []
        blocks = self.allocator.alloc(nf)
        if blocks is None and self._paged_evict_idle_prefixes(keep=key):
            blocks = self.allocator.alloc(nf)
        if blocks is None:
            return None
        self.cache = self.engine.canon_cache(self._seed_blocks(
            self.cache, prefix.k, prefix.v, prefix.k_scale, prefix.v_scale,
            jnp.asarray(blocks, jnp.int32),
        ))
        self._paged_prefixes[key] = (prefix, blocks)
        return blocks

    def _paged_reserve(self, taken: list, rows: list[int], head_prefix):
        """Block-pool admission control: reserve each candidate row's
        blocks (``ceil((prompt + max_new)/bs)`` minus the prefix's shared
        full blocks, which are increfed instead of copied — the COW
        partial tail lands in the row's first owned block). Rows that
        don't fit requeue to the FRONT of the queue in order and their
        row slots go back — admission degrades to pool capacity, not row
        count. Returns the (items, rows) that did fit."""
        bs = self.engine.block_size
        shared: list[int] = []
        if head_prefix is not None:
            got = self._ensure_paged_prefix(head_prefix)
            if got is None:
                with self._lock:
                    for item, row in zip(reversed(taken), reversed(rows)):
                        self.pending.appendleft(item)
                        self._free.append(row)
                return [], []
            shared = got
        ns = len(shared)
        keep = id(head_prefix) if head_prefix is not None else None
        ok_items, ok_rows, failed = [], [], []
        for item, row in zip(taken, rows):
            ids, gen = item[1], item[2]
            need = -(-(len(ids) + gen.max_new_tokens) // bs) - ns
            if need + ns > self.allocator.num_blocks:
                # Bigger than the whole pool: requeueing would spin
                # forever. Answer it now (check_capacity bounds requests
                # by max_seq_len, not by a smaller kv_blocks setting).
                with self._lock:
                    self._free.append(row)
                self.engine.metrics.add_error(1)
                item[3]([], error=(
                    f"request needs {need + ns} KV blocks but the pool "
                    f"has {self.allocator.num_blocks}"
                ))
                continue
            owned = self.allocator.alloc(need)
            if owned is None and self._paged_evict_idle_prefixes(keep=keep):
                owned = self.allocator.alloc(need)
            if owned is None:
                failed.append((item, row))
                continue
            if shared:
                self.allocator.incref(shared)
            self._row_owned[row] = owned
            self._row_shared[row] = list(shared)
            self._row_reserve_t[row] = time.monotonic()
            self._host_tables[row, :] = self._sentinel
            self._host_tables[row, :ns] = shared
            self._host_tables[row, ns:ns + len(owned)] = owned
            ok_items.append(item)
            ok_rows.append(row)
        if failed:
            with self._lock:
                for item, row in reversed(failed):
                    self.pending.appendleft(item)
                    self._free.append(row)
        self.engine.metrics.set_kv_blocks(
            in_use=self.allocator.blocks_in_use
        )
        return ok_items, ok_rows

    def _paged_release_row(self, row: int) -> float:
        """Return a finished/cancelled row's blocks to the pool NOW (owned
        blocks free; shared prefix blocks decref). The device-side table
        stays stale until the next admission uploads tables — safe because
        done rows' KV writes are slot-suppressed on device
        (DecodeEngine._decode_step_body) and nobody reads a freed row.

        Returns the row's block-seconds (blocks held x hold duration) for
        per-request cost attribution; the cumulative also lands on the
        engine's ``kv_block_seconds`` counter."""
        if not self._paged:
            return 0.0
        owned = self._row_owned.pop(row, [])
        shared = self._row_shared.pop(row, [])
        self.allocator.free(owned)
        self.allocator.free(shared)
        self._host_tables[row, :] = self._sentinel
        held = 0.0
        t0 = self._row_reserve_t.pop(row, None)
        n_blocks = len(owned) + len(shared)
        if t0 is not None and n_blocks:
            held = (time.monotonic() - t0) * n_blocks
            self.engine.metrics.add_kv_block_seconds(held)
        self.engine.metrics.set_kv_blocks(
            in_use=self.allocator.blocks_in_use
        )
        return held

    def _prewarm_scratch(self, P: int):
        """Admission scratch for prewarm. Paged: an all-sentinel VIEW over
        the live pool (every write drops) — the pool's shape is baked into
        the prefill executable, so prewarming against a separately sized
        throwaway pool would compile the wrong program."""
        if self._paged:
            return self._paged_scratch_view(P)
        return self.engine.new_cache(P)

    def _prewarm_absorb_pools(self, scratch) -> None:
        """Paged prewarm threads the ONE pool through every donating
        prefill — rebind the big cache's pool leaves from the view after
        each call so the next view (and live serving) holds live buffers."""
        if not self._paged:
            return
        eng = self.engine
        scratch = eng.canon_cache(scratch)
        self.cache = eng.canon_cache(self.cache._replace(
            k=scratch.k, v=scratch.v,
            k_scale=scratch.k_scale, v_scale=scratch.v_scale,
            ssm=scratch.ssm, conv=scratch.conv, idx=scratch.idx,
        ))

    def prewarm(
        self, seq_buckets: list[int] | None = None,
        prefix_prefill: bool = False,
    ) -> int:
        """Compile every executable the scheduler can hit: admission
        prefill for each (admission-batch P, seq bucket S) pair, the row
        insert + device-state merge per P, and the decode chunk at the
        full row count — so no request ever eats a multi-second XLA
        compile mid-serve. ``seq_buckets`` narrows the prompt-length
        envelope when known (default: every bucket up to the engine's
        max_seq_len); ``prefix_prefill`` additionally compiles each
        bucket's prefix-reuse admission variant (the ``start``-offset
        signature) — set it when requests will carry a ``prefix``.
        Returns the number of executables compiled."""
        eng = self.engine
        if seq_buckets is None:
            seq_buckets = eng.seq_buckets()
        if devtel.enabled():
            devtel.install_monitoring_hook()
            # Watch both jit namespaces: the engine's grouped/ragged
            # programs AND the scheduler's own insert/prefill-row jits.
            devtel.observer().watch_obj(eng)
            devtel.observer().watch_obj(self)
        warm = devtel.warm
        Ps, p = [], 1
        while p < self.rows:
            Ps.append(p)
            p *= 2
        Ps.append(p)  # one above, for n == rows when rows isn't a pow2
        n_compiled = 0
        if self._chunked and prefix_prefill:
            # build_prefix still runs through the ENGINE's own _prefill jit
            # at batch=1 even under chunked prefill (prefix construction is
            # a one-off dense prefill, not an admission) — warm it per
            # bucket so the first prefix build doesn't compile mid-serve.
            sa1 = eng._sample_args(GenerationParams(), 1)
            for S in seq_buckets:
                c1 = eng.new_cache(1)
                _, _, c1 = warm(
                    "prefill", {"P": 1, "S": S}, eng._prefill,
                    eng.params, jnp.zeros((1, S), np.int32), c1,
                    jnp.ones(1, np.int32), sa1,
                )
                del c1
                n_compiled += 1
        for P in sorted(set(Ps)):
            sa = eng._sample_args(GenerationParams(), P)
            scratch = None
            tok = jnp.zeros(P, jnp.int32)
            # Chunked prefill KILLS the (P × S) admission-prefill grid:
            # prompts stream through the ragged dispatch, so no dedicated
            # prefill executable exists to warm — only the per-P positions
            # merge + device-state merge below, and the ragged combos
            # after the decode loop. The steady-state executable count
            # collapses to the two grouped-decode combos (× buckets) plus
            # the two ragged step counts (tests/test_ragged.py asserts).
            for S in seq_buckets if not self._chunked else []:
                scratch = self._prewarm_scratch(P)
                ids = jnp.zeros((P, S), np.int32)
                lens = jnp.ones(P, np.int32)
                tok, _, scratch = warm(
                    "prefill_row", {"P": P, "S": S}, self._prefill_row,
                    eng.params, ids, scratch, jnp.asarray(lens), sa,
                )
                self._prewarm_absorb_pools(scratch)
                n_compiled += 1
                if prefix_prefill:
                    scratch = self._prewarm_scratch(P)
                    tok, _, scratch = warm(
                        "prefill_row", {"P": P, "S": S, "prefix": True},
                        self._prefill_row,
                        eng.params, ids, scratch, jnp.asarray(lens), sa,
                        jnp.zeros(P, np.int32),
                    )
                    self._prewarm_absorb_pools(scratch)
                    n_compiled += 1
                    # build_prefix itself runs through the ENGINE's own
                    # _prefill jit at batch=1 — a separate jit object from
                    # _prefill_row — so the first prefix build would
                    # otherwise compile mid-serve.
                    c1 = eng.new_cache(1)
                    sa1 = eng._sample_args(GenerationParams(), 1)
                    _, _, c1 = warm(
                        "prefill", {"P": 1, "S": S}, eng._prefill,
                        eng.params, jnp.zeros((1, S), np.int32), c1,
                        jnp.ones(1, np.int32), sa1,
                    )
                    del c1
                    n_compiled += 1
            # Insert/absorb with all-dropped indices: compiles the P-shaped
            # scatter without touching live rows. Once — the live path
            # feeds it exactly these canonical shardings.
            if self._paged:
                state = {}
                if self._chunked and self.cache.ssm is not None:
                    ssm, conv = warm(
                        "zero_state", {"P": P}, self._zero_state,
                        self.cache.ssm, self.cache.conv,
                        jnp.asarray(self._pad_row_idx(P, [])),
                    )
                    state = {"ssm": ssm, "conv": conv}
                    n_compiled += 1
                self.cache = eng.canon_cache(self.cache._replace(
                    positions=warm(
                        "merge_positions", {"P": P}, self._merge_positions,
                        self.cache.positions,
                        eng.canon_vec(
                            jnp.full((P, eng.max_seq_len), -1, jnp.int32)
                        ),
                        jnp.asarray(self._pad_row_idx(P, [])),
                    ),
                    **state,
                ))
            else:
                scratch = eng.canon_cache(scratch)
                self.cache = eng.canon_cache(warm(
                    "insert", {"P": P}, self._insert,
                    self.cache, scratch,
                    jnp.asarray(self._pad_row_idx(P, [])),
                ))
            n_compiled += 1
            self._tokens_dev, self._cur_pos_dev = (
                eng.canon_vec(x) for x in warm(
                    "admit_merge", {"P": P}, eng._admit_merge,
                    self._tokens_dev, self._cur_pos_dev, eng.canon_vec(tok),
                    jnp.ones(P, jnp.int32),
                    jnp.asarray(self._pad_row_idx(P, [])),
                )
            )
            n_compiled += 1
        # Decode group at the full row count: both live (n_chunks, k)
        # combos — the busy full group and the low-load single short chunk
        # — × every cache-read bucket (the live path picks the bucket from
        # row positions, so all ladder entries are reachable).
        sa = eng._sample_args(GenerationParams(), self.rows)
        combos = sorted({
            (self.group_chunks, self.chunk_steps),
            (1, self.chunk_steps_low),
        })
        for nc, k in combos:
            for tb in eng.prewarm_bucket_set():
                _, last_tok, cache, cur_pos, _ = warm(
                    "decode_group", {"chunks": nc, "k": k, "t_bucket": tb},
                    eng._decode_group,
                    eng.params, self._tokens_dev, self.cache,
                    self._cur_pos_dev, sa,
                    jnp.ones(self.rows, bool),
                    jnp.full(self.rows, -1, np.int32),
                    n_chunks=nc, n_steps=k, t_bucket=tb,
                )
                self.cache = eng.canon_cache(cache)
                self._cur_pos_dev = eng.canon_vec(cur_pos)
                self._tokens_dev = eng.canon_vec(last_tok)
                n_compiled += 1
        if self._chunked:
            # The ragged mixed-batch programs — one per live step count
            # (busy and low-load). All-done dummy schedules: no KV writes
            # land (live = valid & ~done), but the executable for each
            # live xs shape [nc, rows, CB] compiles.
            CB = self.chunked_prefill
            for nc in sorted({
                self.group_chunks * self.chunk_steps, self.chunk_steps_low,
            }):
                _, last_tok, cache, cur_pos, _ = warm(
                    "ragged_group", {"chunks": nc, "k": 1},
                    eng._ragged_group,
                    eng.params, self._tokens_dev, self.cache,
                    self._cur_pos_dev, sa,
                    jnp.ones(self.rows, bool),
                    jnp.full(self.rows, -1, np.int32),
                    jnp.zeros((nc, self.rows, CB), jnp.int32),
                    jnp.ones((nc, self.rows), jnp.int32),
                    jnp.zeros((nc, self.rows), bool),
                    jnp.ones((nc, self.rows), bool),
                )
                self.cache = eng.canon_cache(cache)
                self._cur_pos_dev = eng.canon_vec(cur_pos)
                self._tokens_dev = eng.canon_vec(last_tok)
                n_compiled += 1
        # The prewarm decode ran with every row marked done/free, but its
        # cache writes still landed — reset positions so no ghost slots
        # survive into real serving. device_put with the original sharding:
        # an eager op could re-commit the array and key fresh compiles for
        # every executable that takes the cache.
        self.cache = self.cache._replace(
            positions=jax.device_put(
                jnp.full_like(self.cache.positions, -1),
                self.cache.positions.sharding,
            ),
        )
        self._cur_pos_dev = eng.canon_vec(jnp.zeros(self.rows, jnp.int32))
        self._tokens_dev = eng.canon_vec(jnp.zeros(self.rows, jnp.int32))
        # Drain the device queue before declaring warm: prewarm dispatched
        # one execution per compiled program, and a program's first run
        # can carry a load cost — queued up, that backlog would otherwise
        # land on the first real admission (engine.prewarm has the same
        # guard).
        with devtel.setup_span("setup.prewarm.drain"):
            jax.block_until_ready(self.cache.positions)
            _ = int(jnp.zeros((), jnp.int32) + 1)
        if devtel.enabled():
            # Every serving-path executable is compiled: from here on any
            # compile is a steady-state recompile — counted by the
            # observer and flagged on /slo.
            devtel.observer().mark_steady()
        return n_compiled

    # -- the loop track ------------------------------------------------------

    def loop_span(self, name: str, parent: int | None = None):
        """Open a span on the flight recorder's loop track
        (``utils/trace.py``). The same span is a
        ``jax.profiler.TraceAnnotation``, so a profile shows the loop's
        phases on the host plane beside the device ops, on the profiler's
        clock; and its seconds go to ``metrics.loop_spans`` when it closes.
        ``ContinuousWorker.run_once`` opens its spans here too. Tracing
        off: the one shared no-op span."""
        if not trace.enabled():
            return trace.NO_LOOP_SPAN
        return trace.loop_span(
            name, parent, self.engine.metrics.add_loop_span,
            jax.profiler.TraceAnnotation,
        )

    # -- submission ---------------------------------------------------------

    def submit(
        self,
        token_ids: list[int],
        gen: GenerationParams,
        done_cb: Callable[[list[int]], None],
        req_id: str = "",
        stream_cb: Callable[[list[int]], None] | None = None,
        prefix=None,  # engine.Prefix: token_ids must extend it
        priority: int = 1,
        replayed: int = 0,
    ) -> None:
        """Queue a request. ``prefix`` (from ``engine.build_prefix``) marks
        ``token_ids`` as extending a retained KV segment: admission seeds
        the row from the segment and prefills only the suffix — turn-2 of
        a session (or the Nth request sharing a system prompt) skips the
        shared prefill entirely, with identical tokens.

        ``priority`` is the SLO-class rank (0 = interactive, highest);
        ``replayed`` resumes a preempted request: the LAST ``replayed``
        entries of ``token_ids`` are its already-emitted tokens (prompt +
        resume tokens prefill as one prompt — sampling is stateless per
        (seed, position), so the continuation is identical to the
        unpreempted run), preloaded into the row's output so the stream
        picks up where it stopped and ``max_new_tokens`` counts only the
        REMAINING tokens."""
        gen.validate()
        if prefix is not None and self.engine.cfg.has_state:
            raise ValueError(
                "prefix reuse is not carried for a model with a recurrent "
                "state (docs/recurrent-state.md)"
            )
        if prefix is not None and self.engine.cfg.indexer is not None:
            raise ValueError(
                "prefix reuse is not carried for a model with an indexer: a "
                "retained segment holds keys and values, not the indexer's "
                "keys (docs/sparse-attention.md)"
            )
        if replayed and not 0 < replayed < len(token_ids):
            raise ValueError(
                f"replayed={replayed} must be in [0, len(token_ids))"
            )
        if prefix is not None:
            # Same contract split_prefix enforces; checked at submit time
            # so the error surfaces on the caller, not the worker thread.
            P = prefix.length
            if len(token_ids) <= P or tuple(token_ids[:P]) != prefix.tokens:
                raise ValueError(
                    "token_ids does not extend the prefix (needs its "
                    f"{P} tokens plus at least one more)"
                )
            if P + _bucket(
                len(token_ids) - P, self.engine.max_seq_len
            ) > self.engine.max_seq_len:
                # Ring-wrap guard: even this request's
                # own BUCKET-padded suffix would reach past the ring and
                # wrap over the seeded prefix slots — admit it without the
                # prefix (from-scratch prefill, identical tokens). Dropping
                # here also keeps it out of the prefix's admission group,
                # where a longer batchmate's bucket applies the same guard
                # batch-wide (_admit_dispatch).
                prefix = None
        # With chunked decode a near-capacity row would advance past
        # max_seq_len mid-chunk, wrap, and silently serve context-corrupted
        # tokens (the host can't see the wrap — the decode state is
        # device-resident).
        self.engine.check_capacity(len(token_ids), gen.max_new_tokens)
        with self._lock:
            self.pending.append(
                (req_id, list(token_ids), gen, done_cb, stream_cb,
                 time.perf_counter(), prefix, priority, replayed)
            )
            depth = len(self.pending)
        if req_id:
            trace.record(req_id, "sched_submit", queued=depth)

    # -- scheduling ---------------------------------------------------------

    def _admit_dispatch(
        self, loop: int | None = None,
    ) -> _InFlightAdmission | None:
        """Dispatch admission for every pending request that has a free
        row: ONE batched prefill + ONE row-scatter cache insert + ONE
        device-state merge, **no blocking fetch**. The rows become active
        immediately (the next decode chunk reads the merged device state);
        the host fetches the first tokens later, overlapped with that
        chunk (``_resolve_admission``).

        Must be called *after* the step's decode chunk is dispatched:
        device programs run in dispatch order, so the insert + merge land
        between this chunk and the next — the running chunk can't scribble
        on freshly inserted rows, and the next chunk sees them.

        The admission batch pads to a power of two (dummy rows) so the
        compile envelope stays (log₂ rows × log₂ seq buckets) executables.

        Prefix-sharing requests are admitted in their own batches (every
        row of one admission shares one retained ``Prefix``, matched by
        identity): the scratch cache is seeded from the segment and only
        the suffixes prefill. One admission takes the OLDEST request's
        whole group from anywhere in the queue (same-prefix entries may
        jump ahead of other groups by one admission — the other groups go
        in the next step's admission, one chunk later), so an interleaved
        queue still admits in O(#groups) steps, not O(#requests).
        """
        with self._lock:
            if not self.pending or not self._free:
                return None
            head_prefix = self.pending[0][6]
            free_n = len(self._free)
            if self._feed_rows is not None:
                # a model whose mixed step works only so many feeding rows
                # (models/decoder.py: feed_rows): the others wait here
                free_n = min(
                    free_n, self._feed_rows - len(self._inflight_prefill)
                )
                if free_n <= 0:
                    return None
            taken, rest = [], deque()
            while self.pending:
                item = self.pending.popleft()
                if len(taken) < free_n and item[6] is head_prefix:
                    taken.append(item)
                else:
                    rest.append(item)
            self.pending = rest
            rows = [self._free.pop() for _ in taken]
        with self.loop_span("sched.admit", loop) as sp:
            return self._admit_taken(taken, rows, head_prefix, sp)

    def _admit_taken(
        self, taken: list, rows: list[int], head_prefix, sp,
    ) -> _InFlightAdmission | None:
        """``_admit_dispatch`` past the queue: reserve, prefill, insert and
        merge for the requests ``taken`` on ``rows``, inside the
        ``sched.admit`` span ``sp``."""
        n = len(taken)
        if head_prefix is not None:
            # Ring-wrap guard: the suffix prefill pads to
            # the BATCH's bucket, and padded columns still compute slots
            # (slot = position % max_len) — a prefix start + bucket past
            # the ring would wrap those writes over the seeded prefix
            # slots. Decided BEFORE the paged reserve so the block
            # accounting matches the prefill actually dispatched. The batch
            # admits WITHOUT the prefix (from-scratch prefill of the full
            # prompts — always ring-safe since _bucket caps at
            # max_seq_len); identical tokens, only the prefix's FLOP
            # savings are lost.
            probe = _bucket(
                max(len(item[1]) - head_prefix.length for item in taken),
                self.engine.max_seq_len,
            )
            if head_prefix.length + probe > self.engine.max_seq_len:
                head_prefix = None

        if self._paged:
            # Second gate: row slots are necessary but not sufficient —
            # each row also needs blocks for prompt + max_new. Rows that
            # don't fit the pool went back to the queue inside.
            taken, rows = self._paged_reserve(taken, rows, head_prefix)
            if not taken:
                return None
            n = len(taken)

        P = 1
        while P < n:
            P *= 2
        if self._chunked:
            fed = self._admit_chunked(taken, rows, P, head_prefix)
            sp.set(admitted=n, prompt_tokens=fed, P=P)
            return None
        plen = head_prefix.length if head_prefix is not None else 0
        # With a prefix, only each request's suffix is padded/prefilled.
        suffixes = [item[1][plen:] for item in taken]
        S = _bucket(
            max(len(s) for s in suffixes), self.engine.max_seq_len,
        )
        fed = sum(len(s) for s in suffixes)
        sp.set(admitted=n, prompt_tokens=fed, P=P, S=S)
        padded = np.zeros((P, S), np.int32)
        lens = np.ones(P, np.int32)  # dummy rows prefill one pad token
        gens = []
        for i, s in enumerate(suffixes):
            padded[i, : len(s)] = s
            lens[i] = len(s)
            gens.append(taken[i][2])
        gens += [GenerationParams()] * (P - n)
        row_idx = self._pad_row_idx(P, rows)

        sample_args = self.engine._sample_args(gens, P)
        if self._paged:
            mb = self.engine.max_seq_len // self.engine.block_size
            sub_tables = np.full((P, mb), self._sentinel, np.int32)
            sub_tables[:n] = self._host_tables[rows]
            scratch = self._paged_scratch_view(P, sub_tables, row_idx)
            if head_prefix is not None:
                # Seed through COW-masked tables: the SHARED full blocks'
                # columns are sentineled out so the seed's writes to them
                # drop (they were materialized once by _seed_blocks); only
                # the partial tail lands, in each row's first OWNED block —
                # the copy-on-write copy (docs/paged-kv.md).
                ns = len(self._row_shared[rows[0]])
                seed_tables = sub_tables.copy()
                seed_tables[:, :ns] = self._sentinel
                seeded = self.engine.seed_cache(
                    scratch._replace(
                        block_tables=self._dev_tables(seed_tables)
                    ),
                    head_prefix,
                )
                scratch = self.engine.canon_cache(
                    seeded._replace(block_tables=scratch.block_tables)
                )
                tok, _, scratch = self._prefill_row(
                    self.engine.params, jnp.asarray(padded), scratch,
                    jnp.asarray(lens), sample_args,
                    jnp.full(P, plen, jnp.int32),
                )
            else:
                tok, _, scratch = self._prefill_row(
                    self.engine.params, jnp.asarray(padded), scratch,
                    jnp.asarray(lens), sample_args,
                )
            # The view's pool buffers ARE the big cache's (threaded through
            # the seed/prefill donations) — absorbing is a positions merge
            # + host-table upload, never a KV copy.
            self._paged_absorb(scratch, row_idx)
        elif head_prefix is not None:
            scratch = self.engine.canon_cache(
                self.engine.seed_cache(self.engine.new_cache(P), head_prefix)
            )
            tok, _, scratch = self._prefill_row(
                self.engine.params, jnp.asarray(padded), scratch,
                jnp.asarray(lens), sample_args,
                jnp.full(P, plen, jnp.int32),
            )
            scratch = self.engine.canon_cache(scratch)
            self.cache = self.engine.canon_cache(self._insert(
                self.cache, scratch, jnp.asarray(row_idx)
            ))
        else:
            tok, _, scratch = self._prefill_row(
                self.engine.params, jnp.asarray(padded),
                self.engine.new_cache(P), jnp.asarray(lens), sample_args,
            )
            scratch = self.engine.canon_cache(scratch)
            self.cache = self.engine.canon_cache(self._insert(
                self.cache, scratch, jnp.asarray(row_idx)
            ))
        self._tokens_dev, self._cur_pos_dev = (
            self.engine.canon_vec(x) for x in self.engine._admit_merge(
                self._tokens_dev, self._cur_pos_dev,
                self.engine.canon_vec(tok),
                jnp.asarray(lens + plen), jnp.asarray(row_idx),
            )
        )
        try:
            tok.copy_to_host_async()
        except AttributeError:  # older jax array types
            pass

        entries = []
        for i, (req_id, ids, gen, cb, scb, t_submit, _pfx, pri, rpl) in (
            enumerate(taken)
        ):
            r = _Row(
                req_id=req_id, gen=gen,
                # Resumed rows preload the replayed tokens (the prompt's
                # tail) so done_cb returns the full generation while
                # ``emitted`` keeps the stream from re-sending them.
                out=list(ids[len(ids) - rpl:]) if rpl else [],
                done_cb=cb, stream_cb=scb, awaiting_first=True,
                t_submit=t_submit, priority=pri, replayed=rpl,
                emitted=rpl,
            )
            self.active[rows[i]] = r
            self._row_pos[rows[i]] = len(ids)
            entries.append((rows[i], r))
            if req_id:
                # The seam between the wait for a row and the first-token
                # lag (prefill on the device + the pipelined fetch).
                trace.record(
                    req_id, "prefill_dispatch", row=rows[i],
                    tokens=len(ids), bucket=S, loop=sp.seq,
                )
        return _InFlightAdmission(entries=entries, tok=tok)

    def _admit_chunked(
        self, taken: list, rows: list[int], P: int, head_prefix,
    ) -> int:
        """Chunked-prefill admission: NO prefill program runs. The rows'
        blocks are already reserved (``_paged_reserve``) and their tables
        staged host-side; admission is one table upload, one positions
        merge (seeding prefix rows' shared-FULL-block positions, clearing
        everything else to -1), and one device-state merge pointing
        ``cur_pos`` at the feed start. The prompt itself streams through
        the next ragged groups, ``chunked_prefill`` tokens per step.

        Prefix rows resume after the shared full blocks (``start = ns·bs``)
        and re-feed the COW partial tail through the ragged steps — its KV
        lands in the row's first owned block, exactly where the dedicated
        prefill's copy-on-write would put it. Returns the prompt tokens
        left to feed."""
        eng = self.engine
        n = len(taken)
        row_idx = self._pad_row_idx(P, rows)
        ns = (
            len(self._row_shared[rows[0]]) if head_prefix is not None else 0
        )
        start = ns * eng.block_size
        sub = np.full((P, eng.max_seq_len), -1, np.int32)
        sub[:n, :start] = np.arange(start, dtype=np.int32)[None, :]
        self.cache = eng.canon_cache(self.cache._replace(
            block_tables=self._dev_tables(self._host_tables),
            positions=self._merge_positions(
                self.cache.positions, eng.canon_vec(jnp.asarray(sub)),
                jnp.asarray(row_idx),
            ),
            **self._zeroed_state(row_idx),
        ))
        starts = np.ones(P, np.int32)
        starts[:n] = start
        # Carry token 0 is never read: every planned chunk of these rows
        # feeds prompt slices until emit flips on.
        self._tokens_dev, self._cur_pos_dev = (
            eng.canon_vec(x) for x in eng._admit_merge(
                self._tokens_dev, self._cur_pos_dev,
                eng.canon_vec(jnp.zeros(P, jnp.int32)),
                jnp.asarray(starts), jnp.asarray(row_idx),
            )
        )
        for i, (req_id, ids, gen, cb, scb, t_submit, _pfx, pri, rpl) in (
            enumerate(taken)
        ):
            r = _Row(
                req_id=req_id, gen=gen,
                out=list(ids[len(ids) - rpl:]) if rpl else [],
                done_cb=cb, stream_cb=scb, awaiting_first=True,
                t_submit=t_submit, priority=pri, replayed=rpl,
                emitted=rpl, prefill_dispatched=False,
            )
            self.active[rows[i]] = r
            self._row_pos[rows[i]] = start
            self._inflight_prefill[rows[i]] = list(ids[start:])
            self._prefill_plen[rows[i]] = len(ids)
        return sum(len(item[1]) - start for item in taken)

    def _maybe_preempt(self, loop: int | None = None) -> int:
        """Evict the lowest-priority running row when the head pending
        request strictly outranks it and admission is blocked on rows or
        pool blocks. At most ONE eviction per step — the freed capacity
        feeds this same step's ``_admit_dispatch``, and bounding the hook
        keeps its host cost within the per-request overhead budget
        (tools/bench_priority.py measures the no-op path).

        The eviction mirrors ``_finish`` minus the terminal callback:
        flush what already streamed, release the row's blocks (owned free,
        COW prefix shares decref — exactly balancing the reserve's
        increfs), and hand the emitted tokens to ``preempt_cb`` for the
        broker refund. Tokens for this row still inside the in-flight
        group are discarded unseen; sampling is stateless per (seed,
        position), so the resume regenerates them identically."""
        cb = self.preempt_cb
        if cb is None or self.prefill_only:
            return 0
        with self._lock:
            if not self.pending:
                return 0
            head = self.pending[0]
            free_rows = len(self._free)
        head_pri = head[7]
        blocked = free_rows == 0
        if not blocked and self._paged:
            ids, gen = head[1], head[2]
            need = -(
                -(len(ids) + gen.max_new_tokens) // self.engine.block_size
            )
            blocked = need > self.allocator.free_blocks
        if not blocked:
            return 0
        candidates = [
            (row, r.priority, len(r.out))
            for row, r in self.active.items()
            # Only settled rows are evictable: a row awaiting its first
            # token (admission in flight, or prompt still streaming
            # through ragged chunks) has no resume point yet, and an
            # anonymous row can't be refunded to a broker.
            if r.req_id and not r.awaiting_first
            and row not in self._inflight_prefill
        ]
        row = select_preemption_victim(candidates, head_pri)
        if row is None:
            return 0
        r = self.active[row]
        with self.loop_span("sched.preempt", loop) as sp:
            sp.set(row=row)
            self._flush_stream(r)
            self.active.pop(row, None)
            self._row_pos.pop(row, None)
            self._prefill_plen.pop(row, None)
            self._paged_release_row(row)
            with self._lock:
                self._free.append(row)
            self.engine.metrics.add_preempted(1)
            trace.record(
                r.req_id, "evict", tokens=len(r.out), priority=r.priority,
                for_priority=head_pri, loop=sp.seq,
            )
            cb(r.req_id, list(r.out))
        return 1

    def _resolve_admission(
        self, adm: _InFlightAdmission | None, loop: int | None = None,
    ) -> int:
        """Host bookkeeping for a dispatched admission (fetch its first
        tokens — by now overlapped with at least one decode chunk)."""
        if adm is None:
            return 0
        # The prefill runs on the device behind the group just fetched, so
        # this fetch blocks: a wait for the device like a group's, under
        # the same name, told apart by ``admission``.
        with self.loop_span("sched.fetch_wait", loop) as sp:
            sp.set(admission=len(adm.entries))
            firsts = np.asarray(adm.tok)
        with self.loop_span("sched.resolve", loop) as sp:
            n = 0
            for i, (row, r) in enumerate(adm.entries):
                if self.active.get(row) is not r:
                    continue  # cancelled (and possibly re-admitted) meanwhile
                self._resolve_first(row, r, int(firsts[i]), sp.seq)
                n += 1
            sp.set(resolved=n)
        return n

    def _resolve_first(
        self, row: int, r: _Row, first: int, loop: int | None = None,
    ) -> None:
        """Host bookkeeping at a request's FIRST token — shared by the
        admission-prefill resolve and the ragged chunked path (there the
        first token arrives in the chunk that completed the prompt)."""
        now = time.perf_counter()
        # TTFT spans submit → resolve: queueing for a free row, the
        # admission prefill (or the chunked prompt streaming), AND the
        # decode work the admission deliberately overlapped — the time a
        # client actually waited for its first token. Resumed rows skip
        # both stats: their client saw its first token before the
        # preemption, and counting the re-admission would double-bill
        # requests_served.
        if not r.replayed:
            self.engine.metrics.ttft.record(now - r.t_submit)
            self.engine.metrics.add_request(1)
        if r.req_id:
            # "admit" (not "prefill"): its duration is submit→first
            # token — queue wait + prefill + overlapped chunk — while
            # the role worker's "prefill" span times only the export
            # call; distinct names keep phase sums from double-counting.
            trace.record(
                r.req_id, "admit", dur_s=now - r.t_submit, loop=loop,
            )
        r.awaiting_first = False
        eos = (
            r.gen.eos_token_id if r.gen.eos_token_id is not None else -1
        )
        if first == eos or r.gen.max_new_tokens == 0:
            self._finish(row, r)
            return
        if self.prefill_only and r.gen.max_new_tokens > 1:
            # Disaggregated prefill: export the row's blocks and free
            # it — the decode replica owns the request from here.
            # (max_new == 1 falls through: the first token IS the
            # answer, shipping KV for it would be pure overhead.)
            self._export_row(
                row, r, first, n_tokens=self._prefill_plen.get(row)
            )
            return
        r.out.append(first)
        self.engine.metrics.add_tokens(1)
        if len(r.out) >= r.gen.max_new_tokens + r.replayed:
            self._finish(row, r)
        else:
            # First token goes out now, not a full chunk later —
            # streaming's perceived TTFT is the point.
            self._flush_stream(r)

    def _export_row(
        self, row: int, r: _Row, first: int, n_tokens: int | None = None,
    ) -> None:
        """Prefill-only epilogue for one admitted row: copy its blocks to
        host (a pure pool read — COW-shared prefix blocks stay shared and
        refcounted for the NEXT request; ``export_blocks`` zeroes slot
        garbage past ``n_tokens``), free the row, then hand the payload
        to ``export_cb``. Freeing first means an export_cb that throws
        can't leak the row; the host copy is complete before the blocks
        return to the pool, so reuse can't corrupt it. ``n_tokens`` is the
        prompt length — passed explicitly on the chunked path, where
        ``_row_pos`` has already advanced past it by plan time."""
        if n_tokens is None:
            n_tokens = self._row_pos[row]
        bs = self.engine.block_size
        nb = -(-n_tokens // bs)
        blk_ids = self._host_tables[row, :nb].copy()
        blocks = export_blocks(self.cache, blk_ids, n_tokens)
        cb = self.export_cb
        self.active.pop(row, None)
        self._row_pos.pop(row, None)
        self._inflight_prefill.pop(row, None)
        self._prefill_plen.pop(row, None)
        self._paged_release_row(row)
        with self._lock:
            self._free.append(row)
        self.engine.metrics.add_tokens(1)
        if cb is not None:
            cb(r.req_id, first, n_tokens, blocks)

    def adopt(
        self,
        req_id: str,
        first_token: int,
        n_tokens: int,
        blocks: dict,
        gen: GenerationParams,
        done_cb: Callable[..., None],
        stream_cb: Callable[[list[int]], None] | None = None,
    ) -> bool:
        """Decode-side half of the KV handoff: install an imported
        prompt's blocks into a free row and decode from token ``n_tokens``
        on, WITHOUT a prefill pass. Returns False (record untouched) when
        no row or not enough pool blocks are free — the caller keeps the
        record and retries while touching its handoff lease.

        Bit-identity with a local prefill holds because every piece of
        decode-visible state is reconstructed exactly: the pool bytes are
        the exported ones (bf16/int8 round-trip is exact), positions are
        the same arange-mask a local admission produces, and sampling is
        stateless per (seed, position) so resuming at ``cur_pos =
        n_tokens`` with ``tokens = first_token`` continues the identical
        stream (tests/test_handoff.py).
        """
        if not self._paged:
            raise ValueError("adopt requires kv_layout='paged'")
        if self.engine.cfg.mla is not None:
            raise ValueError(
                "the KV hand-off does not carry a latent pool: its wire "
                "format names keys and values (docs/latent-cache.md)"
            )
        if self.engine.cfg.indexer is not None:
            raise ValueError(
                "the KV hand-off does not carry an indexer's key pool: its "
                "wire format names keys and values "
                "(docs/sparse-attention.md)"
            )
        if self.engine.cfg.has_state:
            raise ValueError(
                "the KV hand-off does not carry a recurrent state: a row "
                "adopted without it would decode from a wrong state "
                "(docs/recurrent-state.md)"
            )
        if self.prefill_only:
            raise ValueError("prefill-only batcher cannot adopt")
        gen.validate()
        self.engine.check_capacity(n_tokens, gen.max_new_tokens)
        eng = self.engine
        bs = eng.block_size
        nb = -(-n_tokens // bs)
        k_seg = blocks["k"]
        if k_seg is None or k_seg.shape[1] != nb:
            raise ValueError(
                f"payload has {None if k_seg is None else k_seg.shape[1]} "
                f"blocks, prompt of {n_tokens} tokens needs {nb}"
            )
        if k_seg.shape[2] != bs:
            raise ValueError(
                f"payload block_size {k_seg.shape[2]} != engine {bs}"
            )
        if bool(blocks.get("k_scale") is not None) != self.cache.quantized:
            raise ValueError(
                "payload quantization does not match the engine's pool"
            )
        # All validation done — now take a row and the blocks.
        with self._lock:
            if not self._free:
                return False
            row = self._free.pop()
        need = -(-(n_tokens + gen.max_new_tokens) // bs)
        owned = self.allocator.alloc(need)
        if owned is None and self._paged_evict_idle_prefixes():
            owned = self.allocator.alloc(need)
        if owned is None:
            with self._lock:
                self._free.append(row)
            return False
        self._row_owned[row] = owned
        self._row_shared[row] = []
        self._row_reserve_t[row] = time.monotonic()
        self._host_tables[row, :] = self._sentinel
        self._host_tables[row, :need] = owned
        eng.metrics.set_kv_blocks(in_use=self.allocator.blocks_in_use)

        # Import scatter, block count padded to a power of two (sentinel
        # ids drop) so the compile envelope stays log2(max_blocks).
        P2 = 1
        while P2 < nb:
            P2 *= 2
        ids = np.full(P2, self._sentinel, np.int32)
        ids[:nb] = owned[:nb]

        def padded(seg):
            if seg is None:
                return None
            seg = np.asarray(seg)
            if P2 == nb:
                return seg
            pad = np.zeros(
                (seg.shape[0], P2 - nb) + seg.shape[2:], seg.dtype
            )
            return np.concatenate([seg, pad], axis=1)

        cache = self._import_blocks(
            self.cache, padded(blocks["k"]), padded(blocks["v"]),
            padded(blocks.get("k_scale")), padded(blocks.get("v_scale")),
            jnp.asarray(ids),
        )
        # Positions: the same arange-under-n_tokens mask a local
        # admission's prefill writes; table upload cuts any stale mapping.
        sub = np.full((1, eng.max_seq_len), -1, np.int32)
        sub[0, :n_tokens] = np.arange(n_tokens, dtype=np.int32)
        cache = cache._replace(
            block_tables=self._dev_tables(self._host_tables),
            positions=self._merge_positions(
                cache.positions, eng.canon_vec(jnp.asarray(sub)),
                jnp.asarray([row], jnp.int32),
            ),
        )
        self.cache = eng.canon_cache(cache)
        # Device decode state: resume at cur_pos = n_tokens with the
        # prefill-sampled first token (the P=1 merge is prewarmed).
        self._tokens_dev, self._cur_pos_dev = (
            eng.canon_vec(x) for x in eng._admit_merge(
                self._tokens_dev, self._cur_pos_dev,
                eng.canon_vec(jnp.asarray([first_token], jnp.int32)),
                jnp.asarray([n_tokens], jnp.int32),
                jnp.asarray([row], jnp.int32),
            )
        )
        r = _Row(
            req_id=req_id, gen=gen, out=[first_token], done_cb=done_cb,
            stream_cb=stream_cb, awaiting_first=False,
            t_submit=time.perf_counter(),
        )
        self.active[row] = r
        self._row_pos[row] = n_tokens
        eng.metrics.add_request(1)
        eng.metrics.add_tokens(1)
        if req_id:
            trace.record(req_id, "adopt", n_tokens=n_tokens, row=row)
        if len(r.out) >= gen.max_new_tokens:
            self._finish(row, r)
        else:
            self._flush_stream(r)
        return True

    def request_park(
        self, req_id: str, token_ids, replayed: int = 0,
    ) -> None:
        """Register session-park interest for a request (thread-safe):
        when its row finishes served, ``park_cb`` receives the full token
        sequence (``token_ids`` + the non-replayed outputs) and the row's
        exported KV blocks. Idempotent; a no-op without ``park_cb``."""
        if self.engine.cfg.mla is not None:
            raise ValueError(
                "session parking does not carry a latent pool: the tiered "
                "store's blobs name keys and values (docs/latent-cache.md)"
            )
        if self.engine.cfg.indexer is not None:
            raise ValueError(
                "session parking does not carry an indexer's key pool: the "
                "tiered store's blobs name keys and values "
                "(docs/sparse-attention.md)"
            )
        if self.engine.cfg.has_state:
            raise ValueError(
                "session parking does not carry a recurrent state "
                "(docs/recurrent-state.md)"
            )
        with self._lock:
            self._park_ids[req_id] = (list(token_ids), int(replayed))

    def forget_park(self, req_id: str) -> None:
        """Withdraw park interest (submit/adopt failed after
        registration — the row will never reach ``_finish``)."""
        with self._lock:
            self._park_ids.pop(req_id, None)

    def _maybe_park(self, row: int, r: _Row, parked: tuple) -> None:
        """Export the finished row's KV for session parking
        (serve/kvstore.py). The device may still be running the in-flight
        group, which keeps advancing this row past its last sampled token
        — positions >= T-1 can be (re)written with garbage-continuation
        KV after this host-side finish. Only positions < T-1 are
        guaranteed stable, so the parked segment covers the first
        (T-1)//bs FULL blocks; and when the in-flight lag could ring-wrap
        into slot 0 (T-1 + group-lag past max_seq_len) parking is skipped
        outright — the low slots themselves would be hazardous. Parking
        is best-effort: any failure is a plain drop (the next turn
        re-prefills), never an error on the finished request."""
        ids, replayed = parked
        seq = list(ids) + [int(t) for t in r.out[replayed:]]
        T = len(seq)
        eng = self.engine
        bs = eng.block_size
        if T - 1 + self.group_chunks * self.chunk_steps > eng.max_seq_len:
            return
        nf = (T - 1) // bs
        if nf == 0:
            return
        try:
            if self._paged:
                blk = [int(b) for b in self._host_tables[row, :nf]]
                if any(b >= self._sentinel for b in blk):
                    return  # row shorter than its sequence claims
                blocks = export_blocks(self.cache, blk, nf * bs)
            else:
                blocks = export_dense_row(self.cache, row, nf * bs, bs)
            self.park_cb(r.req_id, seq[: nf * bs], blocks)
        except Exception:  # noqa: BLE001 — parking never fails a request
            pass

    def _finish(
        self, row: int, r: _Row, cancelled: bool = False,
        error: str | None = None,
    ) -> None:
        self.active.pop(row, None)
        self._row_pos.pop(row, None)
        self._inflight_prefill.pop(row, None)
        self._prefill_plen.pop(row, None)
        with self._lock:
            parked = self._park_ids.pop(r.req_id, None)
        if (
            parked is not None and self.park_cb is not None
            and error is None and not cancelled
        ):
            # Park BEFORE the release: the row's blocks must still be
            # this row's when the export reads them.
            self._maybe_park(row, r, parked)
        kv_block_s = self._paged_release_row(row)
        with self._lock:
            self._free.append(row)
        self._flush_stream(r)
        disposition = (
            "error" if error is not None
            else "cancelled" if cancelled else "served"
        )
        self.engine.metrics.add_finish(disposition)
        if r.req_id:
            trace.record(
                r.req_id, "finish", tokens=len(r.out),
                disposition=disposition,
                **(
                    {"kv_block_s": round(kv_block_s, 6)}
                    if kv_block_s else {}
                ),
            )
        if error is not None:
            # Keyword-only on the error path: existing 2-positional-arg
            # callbacks (tests, batch worker) never see it, and a callback
            # that doesn't accept it raising TypeError is the right
            # loud failure for a serving layer that can't report errors.
            r.done_cb(r.out, error=error)
        elif cancelled:
            r.done_cb(r.out, True)
        else:
            r.done_cb(r.out)

    @staticmethod
    def _flush_stream(r: _Row) -> None:
        if r.stream_cb is not None and len(r.out) > r.emitted:
            r.stream_cb(r.out[r.emitted:])
            r.emitted = len(r.out)

    def cancel(self, req_id: str) -> None:
        """Mark a request cancelled (thread-safe). The worker thread frees
        its row / drops it from the queue at the top of the next ``step()``
        — i.e. a cancelled request stops consuming decode steps within one
        step. Its ``done_cb`` fires with the tokens produced so far."""
        with self._lock:
            self._cancelled.add(req_id)

    def _process_cancellations(self) -> int:
        """Worker-thread half of ``cancel``: drop marked pending requests
        (their callbacks fire with ``cancelled=True`` so every submitted
        request gets exactly one response) and free marked active rows
        (admitted-but-unresolved rows are active too — their resolve
        notices the row changed hands and skips). Unmatched ids are
        discarded — the broker-side cancellation flag persists (TTL'd), so
        a cancel racing ahead of its request is re-delivered by the
        worker's ``check_cancelled`` once the request shows up."""
        with self._lock:
            if not self._cancelled:
                return 0
            ids, self._cancelled = self._cancelled, set()
            dropped = [p for p in self.pending if p[0] in ids]
            self.pending = deque(p for p in self.pending if p[0] not in ids)
        n = len(dropped)
        for item in dropped:
            item[3]([], True)
        for row, r in list(self.active.items()):
            if r.req_id in ids:
                self._finish(row, r, cancelled=True)
                n += 1
        if n:
            self.engine.metrics.add_cancelled(n)
        return n

    def live_ids(self) -> list[str]:
        """Every request id this batcher currently owns (pending or
        active, including admitted-but-unresolved rows) — what the worker
        polls cancellation flags for."""
        with self._lock:
            ids = [req_id for (req_id, *_r) in self.pending]
        ids += [r.req_id for r in self.active.values()]
        return ids

    def load_snapshot(self) -> dict:
        """Cheap load view for the fleet registry heartbeat: row/queue
        occupancy, KV-pool headroom, and the content hashes of the COW
        prefixes resident in the pool (the ``prefix_affinity`` routing
        signal). Host-side counters and host tables only — never touches
        a device array, so publishing it from a heartbeat thread can't
        force a device sync mid-decode."""
        from llmss_tpu.serve.protocol import prefix_hash

        with self._lock:
            pending = len(self.pending)
            free_slots = len(self._free)
        snap = {
            "rows": self.rows,
            "inflight_rows": self.rows - free_slots,
            "pending": pending,
            "free_slots": free_slots,
            "free_kv_blocks": None,
            "kv_blocks_total": None,
            "prefix_hashes": [],
        }
        if self._paged:
            snap["free_kv_blocks"] = self.allocator.free_blocks
            snap["kv_blocks_total"] = self.allocator.num_blocks
            snap["prefix_hashes"] = [
                prefix_hash(pfx.tokens)
                for pfx, _blocks in list(self._paged_prefixes.values())
            ]
        return snap

    def drain_all(self) -> list[str]:
        """Remove every pending and active request and return their ids —
        supervisor teardown: a restarting worker must error these out so no
        client waits forever on a request the new batcher never saw.

        Runs on the worker thread (the supervisor tears down from inside the
        crashed worker's loop), so touching ``self.active`` here doesn't race
        ``step()``; the queue and free-list stay lock-guarded.
        """
        with self._lock:
            ids = [req_id for (req_id, *_rest) in self.pending]
            self.pending.clear()
            self._park_ids.clear()
        self._inflight = None
        self._pending_adm = None
        self._last_fetch_t = None
        self._row_pos.clear()
        self._inflight_prefill.clear()
        self._prefill_plen.clear()
        for row in list(self.active):
            r = self.active.pop(row)
            ids.append(r.req_id)
            self._paged_release_row(row)
            with self._lock:
                self._free.append(row)
        return ids

    def drop_pending(self) -> list[str]:
        """Remove every PENDING (never-admitted) request and return its id
        WITHOUT firing callbacks — drain-deadline path: work the device
        never touched goes back to the broker queue for another worker
        (``release_requests``) instead of being answered with an error.
        Active rows are not touched; the caller aborts those separately."""
        with self._lock:
            ids = [req_id for (req_id, *_rest) in self.pending]
            self.pending.clear()
        return ids

    def _chunk_args(self):
        """Per-chunk host-side control arrays. ``done``/``eos``/sampling
        params come from the host's (one-chunk-lagged) view — a row that
        finished on device but not yet on host rides one extra chunk as a
        done row emitting discarded fills, the same cost an idle row pays.
        """
        done = np.ones(self.rows, bool)
        eos_arr = np.full(self.rows, -1, np.int32)
        gens = []
        for i in range(self.rows):
            r = self.active.get(i)
            gens.append(r.gen if r else GenerationParams())
            if r is not None:
                done[i] = False
                if r.gen.eos_token_id is not None:
                    eos_arr[i] = r.gen.eos_token_id
        sa = self.engine._sample_args(gens, self.rows)
        return done, eos_arr, sa

    def _process_group(
        self, group: _InFlightGroup, loop: int | None = None,
    ) -> int:
        """Fetch a group's packed results (ONE device→host transfer,
        overlapped with the next group already running on device) and
        apply host bookkeeping chunk by chunk: per-row token accounting,
        stream flushes, EOS / max-token finishes — the same per-chunk
        granularity as the ungrouped path, so a row that finishes (or
        poisons) in chunk c never has chunk c+1's fill tokens read as
        output."""
        with self.loop_span("sched.fetch_wait", loop) as sp:
            sp.set(group=group.no)
            with self.engine.metrics.host_fetch.time():
                flat = np.asarray(group.packed)  # the ONE blocking fetch
        self.engine.metrics.add_host_sync()
        with self.loop_span("sched.callback", loop) as sp:
            live = len(self.active)
            n = self._apply_group(group, flat, sp.seq)
            sp.set(
                group=group.no, tokens=n, finished=live - len(self.active),
                **self._count_moe(group, flat),
            )
        return n

    def _count_moe(self, group: _InFlightGroup, flat: np.ndarray) -> dict:
        """The counts at the end of a group's packed fetch (engine.py:
        ``_pack_group``), added to /metrics and returned as the attributes
        the group's ``sched.callback`` span carries. A model with routed
        experts: ``pairs``, ``experts_hit`` and ``pairs_elsewhere``
        (``loop.moe``). A model with an indexer, after them: ``dsa_scored``,
        ``dsa_kept``, ``dsa_dense_rows`` and ``dsa_rows`` (``loop.dsa``).
        Nothing for any other model."""
        cfg, out = self.engine.cfg, {}
        n_moe, n_dsa = DecodeEngine.COUNTS
        if cfg.indexer is not None:
            scored, kept, dense, rows = (int(n) for n in flat[-n_dsa:])
            flat = flat[:-n_dsa]
            self.engine.metrics.add_dsa(scored, kept, dense, rows)
            out.update(dsa_scored=scored, dsa_kept=kept,
                       dsa_dense_rows=dense, dsa_rows=rows)
        if cfg.moe is not None:
            pairs, hit, elsewhere = (int(n) for n in flat[-n_moe:])
            self.engine.metrics.add_moe(
                pairs, hit,
                (cfg.n_layers - cfg.n_lead_layers) * group.n_chunks * group.k,
                elsewhere,
            )
            out.update(pairs=pairs, experts_hit=hit,
                       pairs_elsewhere=elsewhere)
        return out

    def _apply_group(
        self, group: _InFlightGroup, flat: np.ndarray, loop: int | None,
    ) -> int:
        """The host's half of ``_process_group``, after the fetch (the
        ``sched.callback`` span): the fetched group applied chunk by chunk."""
        R, k, nc = self.rows, group.k, group.n_chunks
        toks_np = flat[: nc * R * k].reshape(nc, R, k)
        poisoned_np = flat[nc * R * k: nc * R * (k + 1)].reshape(
            nc, R
        ).astype(bool)
        now = time.perf_counter()
        if self._last_fetch_t is not None and not group.has_admission:
            # Fetch-to-fetch interval — but only for groups with no
            # admission dispatched in between: the admission's prefill +
            # insert + merge execute on device between the two groups and
            # would inflate the per-token decode stat.
            self.engine.metrics.decode_step.record(
                (now - self._last_fetch_t) / (nc * k)
            )
        self._last_fetch_t = now

        n = 0
        t_cb = time.perf_counter()
        firsts = group.prefill_firsts or {}
        for c in range(nc):
            for i in list(self.active):
                r = self.active[i]
                if r.awaiting_first:
                    first_c = firsts.get(i)
                    if first_c is None or c < first_c:
                        # Mid-prompt (or admitted after this group was
                        # dispatched): nothing to consume yet.
                        continue
                    # The chunk that completed this row's prompt — its
                    # sampled token is the request's FIRST token; admission
                    # bookkeeping happens here (chunked admissions never
                    # create an _InFlightAdmission). Poison first: a NaN
                    # anywhere in the prompt condemns the row before its
                    # garbage first token reads as a clean answer.
                    if poisoned_np[c, i]:
                        self.engine.metrics.add_poisoned(1)
                        self._finish(
                            i, r,
                            error="non-finite logits: row poisoned "
                                  "(NaN/inf in model output)",
                        )
                        continue
                    self._resolve_first(i, r, int(toks_np[c, i, 0]), loop)
                    continue
                if poisoned_np[c, i]:
                    # Checked BEFORE token processing: the device
                    # EOS-filled the poisoned row from the bad step on
                    # (with -1 when the row has no eos), so its chunk
                    # tokens would otherwise read as a clean early finish.
                    # Error the row with the tokens produced before the
                    # poison; co-batched rows are untouched (row isolation
                    # is positional — a NaN never crosses rows). The flags
                    # are cumulative within the group, so the row errors at
                    # its FIRST poisoned chunk and leaves ``active``.
                    self.engine.metrics.add_poisoned(1)
                    self._finish(
                        i, r,
                        error="non-finite logits: row poisoned "
                              "(NaN/inf in model output)",
                    )
                    continue
                eos = (
                    r.gen.eos_token_id
                    if r.gen.eos_token_id is not None else -1
                )
                finished = False
                for col in range(k):
                    t = int(toks_np[c, i, col])
                    if t == eos:
                        finished = True
                        break
                    r.out.append(t)
                    n += 1
                    if len(r.out) >= r.gen.max_new_tokens + r.replayed:
                        finished = True
                        break
                if finished:
                    self._finish(i, r)
                else:
                    self._flush_stream(r)
        self.engine.metrics.add_tokens(n)
        self.engine.metrics.host_callback.record(time.perf_counter() - t_cb)
        return n

    def _plan_ragged(self, n_steps: int, loop: int | None = None):
        """Host-side schedule for one ragged mixed group: every active row
        advances one token per step; rows with an in-flight prompt feed
        ``chunked_prefill``-token slices instead, sampling suppressed
        until the slice that completes the prompt (``emit`` flips on —
        that step's sample is the row's first token). A row whose prompt
        completes mid-group decodes normally for the remaining steps.
        Returns the xs arrays plus {row: step} first-token marks."""
        CB, R = self.chunked_prefill, self.rows
        ids = np.zeros((n_steps, R, CB), np.int32)
        qlens = np.ones((n_steps, R), np.int32)
        feed = np.zeros((n_steps, R), bool)
        emit = np.ones((n_steps, R), bool)
        firsts: dict[int, int] = {}
        fed = 0
        for s in range(n_steps):
            for row in list(self._inflight_prefill):
                rem = self._inflight_prefill[row]
                r = self.active[row]
                if not r.prefill_dispatched:
                    # The chunked path's seam between the wait for a row
                    # and the first-token lag: the prompt's first chunk.
                    r.prefill_dispatched = True
                    if r.req_id:
                        trace.record(
                            r.req_id, "prefill_dispatch", row=row,
                            tokens=self._prefill_plen[row], bucket=CB,
                            loop=loop,
                        )
                q = min(CB, len(rem))
                ids[s, row, :q] = rem[:q]
                del rem[:q]
                qlens[s, row] = q
                feed[s, row] = True
                emit[s, row] = not rem
                fed += q
                if not rem:
                    firsts[row] = s
                    del self._inflight_prefill[row]
        pre = int(feed.sum())
        self.engine.metrics.add_mixed_steps(
            steps=n_steps,
            decode_rows=n_steps * len(self.active) - pre,
            prefill_rows=pre, prefill_tokens=fed,
            budget_tokens=pre * CB,
        )
        return ids, qlens, feed, emit, firsts

    def _pool_read(self, chunk: int, t_bucket: int | None) -> dict:
        """What the group about to be dispatched reads of the paged pool, for
        its ``sched.dispatch`` span: ``models.decoder``'s ``attn_read`` (the
        read its program is traced with), ``attn_form`` (``kv.kernel``'s work
        on a chunk, or ``none``), ``index_read`` (an indexer's pool's, or
        ``none``) and ``state_update`` (a state's: ``xla`` without one);
        ``blocks_read``, the blocks its live rows hold at the group's first
        step (a read that stops at a row's length visits these), over
        ``blocks_ring``, the columns a read of every row's whole ring or read
        bucket visits: the share of the ring that holds anything."""
        from llmss_tpu.models import decoder as d

        how = self._attn_reads.get(chunk)
        if how is None:
            how = self._attn_reads[chunk] = {
                f.__name__: f(
                    self.engine.cfg, self.cache, self.engine.mesh, chunk
                )
                for f in (d.attn_read, d.attn_form, d.index_read, d.state_update)
            }
        bs, mb = self.cache.block_size, self.cache.max_blocks
        if t_bucket is not None:
            mb = min(-(-t_bucket // bs), mb)
        return dict(
            **how,
            blocks_read=sum(
                min(-(-n // bs), mb) for n in self._row_pos.values()
            ),
            blocks_ring=self.rows * mb,
        )

    def step(self, loop: int | None = None) -> int:
        """One scheduler iteration of the pipelined loop:

        1. dispatch decode group N+1 from the device-resident state — ONE
           jitted program covering ``group_chunks`` fused chunks while
           busy (a single chunk at low load) — the device never waits for
           the host;
        2. fetch + process group N's packed results, overlapped with group
           N+1 executing on device — this is where rows finish and free;
        3. resolve the admission dispatched last step (host bookkeeping —
           its merge already executed on device);
        4. dispatch admissions for the rows phase 2 just freed; their
           prefill + insert + merge land between group N+1 and N+2, so a
           finished row is back in service after exactly one idle group.

        Rows keep their exact solo tokens (row isolation is positional,
        and the device state never depends on host processing) — the
        pipeline only delays when the *host* learns them by one group.

        Each phase is a span on the loop track (``sched.plan``,
        ``sched.dispatch``, ``sched.fetch_wait``, ``sched.callback``,
        ``sched.resolve``, ``sched.preempt``, ``sched.admit``,
        ``sched.devtel``), a child of the worker's iteration span ``loop``.
        """
        ragged = t_bucket = None  # a mixed group has no read bucket
        with self.loop_span("sched.plan", loop) as sp:
            self._process_cancellations()
            if self.active:
                done, eos_arr, sa = self._chunk_args()
                busy = len(self.active) >= (3 * self.rows) // 4
                t0 = time.perf_counter()
                if self._chunked and self._inflight_prefill:
                    # Mixed batch: in-flight prompts stream through the
                    # ragged dispatch as chunk-budget query rows while
                    # decode rows advance one token per step. No t_bucket —
                    # the ragged executable's identity is keyed purely by
                    # the xs shapes, so exactly TWO programs exist (the busy
                    # and low-load step counts). The group never records
                    # decode_step (it is not a clean decode-only sample —
                    # has_admission covers that).
                    nc, k = (
                        self.group_chunks * self.chunk_steps if busy
                        else self.chunk_steps_low
                    ), 1
                    ragged = self._plan_ragged(nc, sp.seq)
                else:
                    # Busy → the full group of full chunks (host off the
                    # critical path); low load → one short chunk
                    # (admission/TTFT granularity). Exactly these two
                    # (n_chunks, n_steps) combos exist, so the executable
                    # envelope stays two programs per cache-read bucket —
                    # same count as the ungrouped two-chunk-size scheme.
                    nc, k = (
                        (self.group_chunks, self.chunk_steps) if busy
                        else (1, self.chunk_steps_low)
                    )
                    t_bucket = self.engine.decode_bucket(
                        max(self._row_pos.values(), default=0) + nc * k
                    )

        if not self.active:
            # Nothing running: drain the pipeline, then admit directly
            # (resolve immediately — nothing to overlap with; the merge
            # makes rows live for the next step's first group).
            if self._inflight is not None:
                group, self._inflight = self._inflight, None
                self._last_fetch_t = None
                n = self._process_group(group, loop)
                n += self._resolve_admission(self._pending_adm, loop)
                self._pending_adm = None
                return n
            if self._pending_adm is not None:
                adm, self._pending_adm = self._pending_adm, None
                return self._resolve_admission(adm, loop)
            adm = self._admit_dispatch(loop)
            if adm is None:
                return 0
            self._last_fetch_t = None
            return self._resolve_admission(adm, loop)

        with self.loop_span("sched.dispatch", loop) as sp:
            live = len(self.active)
            if self._paged and sp is not trace.NO_LOOP_SPAN:
                sp.set(**self._pool_read(
                    1 if ragged is None else ragged[0].shape[-1], t_bucket
                ))
            if ragged is not None:
                ids_seq, qlens_seq, feed_seq, emit_seq, firsts = ragged
                packed, last_tok, cache, cur_pos, _ = (
                    self.engine._ragged_group(
                        self.engine.params, self._tokens_dev, self.cache,
                        self._cur_pos_dev, sa, jnp.asarray(done),
                        jnp.asarray(eos_arr), jnp.asarray(ids_seq),
                        jnp.asarray(qlens_seq), jnp.asarray(feed_seq),
                        jnp.asarray(emit_seq),
                    )
                )
                adv = qlens_seq.sum(axis=0)
                for row in self._row_pos:
                    self._row_pos[row] += int(adv[row])
                group = _InFlightGroup(
                    packed=packed, n_chunks=nc, k=k, has_admission=True,
                    prefill_firsts=firsts,
                    kind="ragged_group",
                    no=self._step_count,
                )
            else:
                packed, last_tok, cache, cur_pos, _ = (
                    self.engine._decode_group(
                        self.engine.params, self._tokens_dev, self.cache,
                        self._cur_pos_dev, sa, jnp.asarray(done),
                        jnp.asarray(eos_arr),
                        n_chunks=nc, n_steps=k, t_bucket=t_bucket,
                    )
                )
                for row in self._row_pos:
                    self._row_pos[row] += nc * k
                # The admission dispatched LAST step sits between the
                # previous group and this one on the device queue, so this
                # group's fetch-to-fetch interval includes its
                # prefill+insert+merge time.
                group = _InFlightGroup(
                    packed=packed, n_chunks=nc, k=k,
                    has_admission=self._pending_adm is not None,
                    no=self._step_count,
                )
                sp.set(t_bucket=t_bucket)
            self.cache = self.engine.canon_cache(cache)
            self._cur_pos_dev = self.engine.canon_vec(cur_pos)
            self._tokens_dev = self.engine.canon_vec(last_tok)
            try:
                packed.copy_to_host_async()
            except AttributeError:
                pass
            sp.set(
                group=group.no, kind=group.kind, chunks=nc, k=k,
                rows_live=live, has_admission=group.has_admission,
            )
            self.engine.metrics.host_dispatch.record(
                time.perf_counter() - t0
            )
            self.engine.metrics.add_group(
                steps=nc * k,
                filtered=any(
                    not r.gen.is_greedy
                    and (r.gen.top_k > 0 or r.gen.top_p < 1.0)
                    for r in self.active.values()
                ),
            )

        prev, self._inflight = self._inflight, group
        n = 0
        if prev is not None:
            n = self._process_group(prev, loop)  # frees finished rows
        n += self._resolve_admission(self._pending_adm, loop)
        # Preemption sits between resolve and admit: an evicted row's slot
        # and blocks feed THIS step's admission, so a blocked interactive
        # request is running one group after its eviction decision.
        self._maybe_preempt(loop)
        # Admission takes the rows processing just freed; its device work
        # overlaps the in-flight group and lands before the next one.
        self._pending_adm = self._admit_dispatch(loop)
        self._step_count += 1
        if devtel.enabled():
            with self.loop_span("sched.devtel", loop):
                self._devtel_sample()
        return n

    def _devtel_sample(self) -> None:
        """Devtel sampling at a group boundary: counter tracks (throttled
        to 0.05 s) and the compile
        observer's ``_cache_size`` sweep (throttled to 0.5 s inside the
        observer). Host counters and host tables only — never a device
        sync (``memory_stats`` reads runtime-owned host counters)."""
        now = time.monotonic()
        rid = next(
            (r.req_id for r in self.active.values() if r.req_id), None,
        )
        devtel.observer().maybe_sample(rid)
        if now - self._devtel_last_t < 0.05:
            return
        self._devtel_last_t = now
        with self._lock:
            pending = len(self.pending)
            free_slots = len(self._free)
        prefill_rows = len(self._inflight_prefill)
        tracks = {
            "rows": {
                "decode": len(self.active) - prefill_rows,
                "prefill": prefill_rows,
                "free": free_slots,
            },
            "queue_depth": {"pending": pending},
        }
        if self._paged:
            alloc = self.allocator
            free = alloc.free_blocks
            tracks["kv_blocks"] = {
                "in_use": alloc.num_blocks - free, "free": free,
            }
            tracks["kv_fragmentation"] = {
                "largest_free_run": alloc.largest_free_run(), "free": free,
            }
        mem = devtel.device_memory_stats()
        if mem is not None:
            tracks["device_memory"] = mem
        devtel.record_counters(tracks, t=now)

    @property
    def idle(self) -> bool:
        with self._lock:
            return (
                not self.active and not self.pending
                and self._inflight is None and self._pending_adm is None
            )

    def run_until_idle(self) -> None:
        while not self.idle:
            self.step()

    def run_forever(self, stop: threading.Event, poll_s: float = 0.005):
        while not stop.is_set():
            if self.idle:
                time.sleep(poll_s)
                continue
            self.step()
