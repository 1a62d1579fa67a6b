"""Consumer: the model worker.

≙ reference ``consumer_server.py``: poll the broker, tokenize, run the
engine, respond. Structural upgrades over the reference (SURVEY.md §2.10,
§3.2):

- **Single controller**: the reference runs one process per GPU, fans the
  request out with ``broadcast_object_list`` (``consumer_server.py:108``) and
  every sampled token with ``dist.broadcast`` (``:165``); here one process
  drives the whole mesh — those collectives do not exist.
- **Batched**: drains up to ``batch_size`` queued requests per engine call
  (reference: ``batch_size = 1`` hard-coded, ``consumer_server.py:73``), with
  heterogeneous per-request sampling params.
- **Failure containment**: a failing batch produces per-request error
  responses and the worker keeps serving (the reference crashes).
"""

from __future__ import annotations

import gc
import logging
import threading
import time

from llmss_tpu.engine import DecodeEngine, GenerationParams
from llmss_tpu.serve.broker import Broker
from llmss_tpu.serve.handoff import (
    HandoffRecord,
    decode_blocks,
    encode_blocks,
    pick_decode_worker,
)
from llmss_tpu.serve.protocol import (
    SLO_CLASS_RANK,
    STATE_DRAINING,
    STATE_READY,
    GenerateRequest,
    GenerateResponse,
    prefix_hash,
)
from llmss_tpu.utils import devtel
from llmss_tpu.utils import metrics as metrics_mod
from llmss_tpu.utils import trace

logger = logging.getLogger("llmss_tpu.serve")


def worker_capabilities(worker_id: str, engine, role: str = "unified") -> dict:
    """Registration payload: identity + what this replica can serve.
    Tolerant of engine stand-ins (ScriptedEngine) that lack the attrs."""
    cfg = getattr(engine, "cfg", None)
    return {
        "worker_id": worker_id,
        "role": role,
        "model": getattr(cfg, "model_type", None) or type(engine).__name__,
        "kv_layout": getattr(engine, "kv_layout", None),
        "kv_blocks": getattr(engine, "kv_blocks", None),
        "max_seq_len": getattr(engine, "max_seq_len", None),
    }


def encode_request(tokenizer, req: GenerateRequest) -> list[int]:
    if req.token_ids is not None:
        return list(req.token_ids)
    if tokenizer is None:
        raise ValueError("no tokenizer configured; send token_ids")
    return tokenizer(req.prompt)["input_ids"]


def gen_params_from(tokenizer, req: GenerateRequest) -> GenerationParams:
    eos = tokenizer.eos_token_id if tokenizer is not None else None
    return GenerationParams(
        max_new_tokens=req.max_new_tokens,
        is_greedy=req.is_greedy,
        temperature=req.temperature,
        top_k=req.top_k,
        top_p=req.top_p,
        eos_token_id=eos,
        seed=req.seed,
    )


class Worker:
    def __init__(
        self,
        engine: DecodeEngine,
        broker: Broker,
        tokenizer=None,
        batch_size: int = 8,
        poll_timeout_s: float = 0.2,
        pad_batch: bool = True,
        chunk_steps: int = 8,
        worker_id: str | None = None,
        snapshot_interval_s: float = 1.0,
    ):
        self.engine = engine
        self.broker = broker
        self.tokenizer = tokenizer
        self.batch_size = batch_size
        self.poll_timeout_s = poll_timeout_s
        # Fleet identity: with a worker_id this worker registers in the
        # broker's worker registry, publishes periodic load snapshots, and
        # prefers its routed queue over the shared one. Without (default),
        # behavior is exactly the single-worker shared-queue stack.
        self.worker_id = worker_id
        self.role = "unified"  # batch workers always prefill + decode
        self.snapshot_interval_s = snapshot_interval_s
        self._last_snapshot_t = 0.0
        self._inflight_rows = 0
        # Decode steps per host round-trip (engine.generate chunking):
        # amortizes dispatch + token-fetch latency; cancellation latency
        # becomes one chunk instead of one step.
        self.chunk_steps = chunk_steps
        # Pad every live batch up to ``batch_size`` with inert rows so the
        # engine sees one batch shape: without this, each distinct queue
        # drain length compiles a fresh prefill+decode executable — repeated
        # multi-second stalls under bursty load. Batch rows run in parallel
        # on the chip, so the dummy rows are ~free.
        self.pad_batch = pad_batch
        # Lifecycle (supervisor drain contract): once draining, run_once
        # stops leasing — and since a batch worker holds requests only
        # INSIDE run_once, it is fully drained the moment the current batch
        # finishes.
        self.draining = False
        # Monotonic stamp of the last demonstrable worker progress (batch
        # boundaries + every decode chunk via cancel_poll). The supervisor
        # watchdog compares it against time.monotonic() from another thread;
        # the heartbeat converts it to wall clock only at publish time.
        self.last_progress_ts = 0.0
        if worker_id is not None:
            self.register()

    def register(self) -> None:
        """(Re-)announce this worker in the fleet registry — called at
        construction and safe to call again after a registry TTL expiry."""
        self.broker.register_worker(
            worker_capabilities(self.worker_id, self.engine, self.role)
        )
        self._publish_load()

    def load_snapshot(self) -> dict:
        """Registry heartbeat payload (host counters only). Carries the
        same ``heartbeat_ts``/``heartbeat_s`` contract as the supervisor
        block so ``evaluate_worker_health`` judges fleet entries too."""
        import time as _time

        return {
            "role": self.role,
            "state": STATE_DRAINING if self.draining else STATE_READY,
            "alive": True,
            "rows": self.batch_size,
            "inflight_rows": self._inflight_rows,
            "free_slots": self.batch_size - self._inflight_rows,
            "queue_depth": 0,  # batch worker holds nothing between batches
            "free_kv_blocks": None,
            "kv_blocks_total": None,
            "prefix_hashes": [],
            "heartbeat_s": self.snapshot_interval_s,
            # Cross-process staleness stamp: the router/producer compute
            # `time.time() - heartbeat_ts` in another process, and
            # monotonic epochs don't line up across processes.
            "heartbeat_ts": _time.time(),  # lint: ignore[wall-clock-timer]
            # Flight-recorder snapshot: rides the registry heartbeat so
            # the producer can stitch fleet-wide timelines (GET /trace).
            **(
                {"trace": trace.recorder().export(max_events=256)}
                if trace.enabled() else {}
            ),
            # Windowed SLO series ride the same heartbeat; the cached
            # export keeps repeat snapshots within a heartbeat cheap.
            **(
                {"series": metrics_mod.series().export(cache_s=1.0)}
                if trace.enabled() else {}
            ),
            # Device telemetry (compile forensics, counter tracks) rides
            # the same heartbeat.
            **({"devtel": devtel.export()} if devtel.enabled() else {}),
        }

    def _publish_load(self) -> None:
        if self.worker_id is not None:
            self._last_snapshot_t = time.monotonic()
            self.broker.publish_worker_load(
                self.worker_id, self.load_snapshot()
            )

    def _maybe_publish_load(self) -> None:
        if (
            self.worker_id is not None
            and time.monotonic() - self._last_snapshot_t
            >= self.snapshot_interval_s
        ):
            self._publish_load()

    def _pop(self, timeout: float = 0.0) -> GenerateRequest | None:
        if self.worker_id is None:
            return self.broker.pop_request(timeout=timeout)
        return self.broker.pop_request(
            timeout=timeout, worker_id=self.worker_id
        )

    def begin_drain(self) -> None:
        self.draining = True

    @property
    def drained(self) -> bool:
        return self.draining

    def prewarm(self) -> int:
        """Compile the worker's full executable envelope up front (every
        prompt bucket at the padded batch size + decode step/chunks) so the
        first request of any shape never stalls on a multi-second compile."""
        return self.engine.prewarm(
            self.batch_size, chunk_steps=self.chunk_steps
        )

    # -- request plumbing ---------------------------------------------------

    def _encode(self, req: GenerateRequest) -> list[int]:
        return encode_request(self.tokenizer, req)

    def _gen_params(self, req: GenerateRequest) -> GenerationParams:
        return gen_params_from(self.tokenizer, req)

    def _gather(self) -> list[GenerateRequest]:
        """Block briefly for one request, then drain the queue up to
        batch_size (the reference instead spins at batch_size=1,
        consumer_server.py:75-81)."""
        first = self._pop(timeout=self.poll_timeout_s)
        if first is None:
            return []
        batch = [first]
        while len(batch) < self.batch_size:
            nxt = self._pop()
            if nxt is None:
                break
            batch.append(nxt)
        return batch

    # -- serving loop -------------------------------------------------------

    def run_once(self) -> int:
        self.last_progress_ts = time.monotonic()
        self._maybe_publish_load()
        if self.draining:
            return 0  # stop leasing; nothing held between batches
        batch = self._gather()
        if not batch:
            return 0

        # Cancellation is a broker-side TTL flag (not a consumed queue):
        # check exactly the ids this worker holds — multi-worker safe, and
        # a cancel that raced ahead of its request still lands here.
        cancelled = self.broker.check_cancelled([r.id for r in batch])
        prompts, gens, ok = [], [], []
        for req in batch:
            if req.id in cancelled:
                self.engine.metrics.add_cancelled()
                self.broker.push_response(
                    GenerateResponse(id=req.id, error="cancelled")
                )
                continue
            if req.deadline_ts is not None and time.time() > req.deadline_ts:
                # Shed before prefill: the client's end-to-end deadline has
                # passed, so decoding would be work nobody collects.
                self.engine.metrics.add_expired()
                self.broker.push_response(
                    GenerateResponse(id=req.id, error="deadline exceeded")
                )
                continue
            try:
                req.validate()
                ids = self._encode(req)
                gp = self._gen_params(req)
                if req.resume_tokens:
                    # Resume after a preemption elsewhere in the fleet:
                    # prompt + already-emitted tokens prefill as ONE
                    # prompt and only the remainder decodes — sampling is
                    # stateless per (seed, position), so the continuation
                    # matches the unpreempted run exactly.
                    ids = ids + list(req.resume_tokens)
                    gp.max_new_tokens = (
                        req.max_new_tokens - len(req.resume_tokens)
                    )
                # Same ring-capacity rule as ContinuousBatcher.submit.
                self.engine.check_capacity(len(ids), gp.max_new_tokens)
                prompts.append(ids)
                gens.append(gp)
                ok.append(req)
            except Exception as e:  # noqa: BLE001 — per-request error surface
                self.broker.push_response(
                    GenerateResponse(id=req.id, error=str(e))
                )
        if not ok:
            return len(batch)

        n_live = len(prompts)
        if self.pad_batch and n_live < self.batch_size:
            pad = self.batch_size - n_live
            prompts = prompts + [[0]] * pad
            gens = gens + [
                GenerationParams(max_new_tokens=1, is_greedy=True)
            ] * pad

        mid_cancelled: set[str] = set()

        def cancel_poll():
            # Mid-batch cancellation: stop spending decode steps on rows
            # whose clients are gone. Stamping progress here (once per
            # decode chunk) is what keeps the watchdog and the supervisor
            # heartbeat truthful through a long batch — without it a
            # multi-thousand-token batch reads as a hung worker. Touching
            # the leases here keeps a long decode from being mistaken for
            # a dead worker (same cadence, one decode chunk).
            self.last_progress_ts = time.monotonic()
            self.broker.publish_metrics(self.engine.metrics.to_dict())
            self._maybe_publish_load()
            self.broker.touch_requests([r.id for r in ok])
            hits = self.broker.check_cancelled(
                [r.id for r in ok if r.id not in mid_cancelled]
            )
            if hits:
                self.engine.metrics.add_cancelled(len(hits))
                mid_cancelled.update(hits)
            return [i for i, r in enumerate(ok) if r.id in hits]

        def on_increment(row, new_toks):
            # True streaming from the batch worker: increments go out at
            # decode-chunk granularity, with engine-owned completion
            # semantics (EOS / max-token fills never leak).
            if row < n_live and ok[row].stream:
                self.broker.push_stream(ok[row].id, new_toks)

        poisoned_rows: set[int] = set()
        self._inflight_rows = n_live
        t_batch = time.monotonic()
        try:
            outs = self.engine.generate(
                prompts, gens, cancel_poll=cancel_poll,
                on_increment=on_increment,
                on_poisoned=poisoned_rows.add,
                chunk_steps=self.chunk_steps, live_rows=n_live,
            )[:n_live]
        except Exception as e:  # noqa: BLE001 — batch failure containment
            logger.exception("batch failed")
            self.engine.metrics.add_error(len(ok))
            for req in ok:
                self.broker.push_response(
                    GenerateResponse(id=req.id, error=f"engine error: {e}")
                )
            # Persistent failures must be visible to operators immediately,
            # not only after the next successful batch.
            self.broker.publish_metrics(self.engine.metrics.to_dict())
            return len(batch)
        finally:
            self._inflight_rows = 0

        # One batch generate == one decode phase for every live row; the
        # per-request event shares the batch duration (rows run in parallel).
        dur_batch = time.monotonic() - t_batch
        for req in ok:
            trace.record(
                req.id, "decode", trace_id=req.trace_id, dur_s=dur_batch,
                worker=self.worker_id, batch=n_live,
            )
        for row, (req, toks) in enumerate(zip(ok, outs)):
            if req.resume_tokens:
                # The replayed tokens belong to the answer: the client
                # sees one seamless stream across the preemption.
                toks = list(req.resume_tokens) + toks
            if row in poisoned_rows:
                # Per-row poison containment: this row's logits went
                # NaN/inf mid-decode. Only this row errors — batch-mates
                # keep their exact solo tokens (row isolation).
                self.engine.metrics.add_poisoned()
                self.broker.push_response(
                    GenerateResponse(
                        id=req.id,
                        error="non-finite logits: row poisoned "
                              "(NaN/inf in model output)",
                        token_ids=toks,
                    )
                )
                continue
            if req.id in mid_cancelled:
                # The client is by definition gone — an honest "cancelled"
                # error (with the partial tokens), not a fake success.
                self.broker.push_response(
                    GenerateResponse(
                        id=req.id, error="cancelled", token_ids=toks,
                    )
                )
                continue
            text = (
                self.tokenizer.decode(toks) if self.tokenizer is not None
                else None
            )
            self.broker.push_response(
                GenerateResponse(
                    id=req.id, prompt=req.prompt, continuation=text,
                    token_ids=toks,
                )
            )
        self.broker.publish_metrics(self.engine.metrics.to_dict())
        return len(batch)

    def run_forever(self, stop: threading.Event | None = None) -> None:
        while stop is None or not stop.is_set():
            self.run_once()


class ContinuousWorker:
    """Serving loop over the continuous batcher: requests are admitted into
    the running batch at token granularity.

    ``role`` selects this replica's half of the disaggregated
    prefill/decode split (docs/serving.md):

    - ``"unified"`` (default): prefill + decode interleaved, exactly the
      pre-disaggregation worker — single-worker deployments are
      bit-identical.
    - ``"prefill"``: the batcher runs prefill-only; each admitted request's
      KV blocks are exported, wrapped in a :class:`HandoffRecord`, and
      pushed onto the broker's handoff channel toward a decode replica.
      Requests whose answer IS the first token (``max_new_tokens <= 1`` or
      an immediate EOS) are answered locally — shipping KV for them would
      be pure overhead.
    - ``"decode"``: pops handoff records instead of raw requests, installs
      the imported blocks via ``ContinuousBatcher.adopt`` (no prefill
      pass), and decodes to completion. Records that arrive while all rows
      are busy wait in a local backlog whose handoff leases are renewed
      every ``run_once`` — never re-pushed, so no counter inflation and no
      loss window.
    """

    def __init__(
        self,
        engine: DecodeEngine,
        broker: Broker,
        tokenizer=None,
        rows: int = 8,
        poll_timeout_s: float = 0.02,
        chunk_steps: int = 8,
        chunk_steps_low: int | None = None,
        group_chunks: int = 1,
        worker_id: str | None = None,
        snapshot_interval_s: float = 1.0,
        role: str = "unified",
        chunked_prefill: int | None = None,
        kvstore=None,
    ):
        from collections import deque

        from llmss_tpu.engine.scheduler import ContinuousBatcher

        if role not in ("unified", "prefill", "decode"):
            raise ValueError(f"unknown worker role: {role!r}")
        if getattr(getattr(engine, "cfg", None), "mla", None) is not None and (
            role != "unified" or kvstore is not None
        ):
            raise ValueError(
                "a model with a latent pool is served by a unified worker "
                "without a tiered KV store: the hand-off's wire format and "
                "the store's blobs name keys and values "
                "(docs/latent-cache.md)"
            )
        if getattr(getattr(engine, "cfg", None), "indexer", None) is not None and (
            role != "unified" or kvstore is not None
        ):
            raise ValueError(
                "a model with an indexer is served by a unified worker "
                "without a tiered KV store: the hand-off's wire format and "
                "the store's blobs name keys and values, not the indexer's "
                "keys (docs/sparse-attention.md)"
            )
        if getattr(getattr(engine, "cfg", None), "has_state", False) and (
            role != "unified" or kvstore is not None
        ):
            # The hand-off's wire format and the tiered store's blobs hold
            # keys and values only (docs/recurrent-state.md).
            raise ValueError(
                "a model with a recurrent state is served by a unified "
                "worker without a tiered KV store: neither the prefill/"
                "decode hand-off nor session parking carries the state"
            )
        self.engine = engine
        self.broker = broker
        self.tokenizer = tokenizer
        self.role = role
        # Tiered KV store (serve/kvstore.py): None = pre-tiering behavior
        # (evictions drop, sessions re-prefill). With a store: pool/LRU
        # evictions DEMOTE, shared-prefix misses PROMOTE from T1/T2, and
        # finished session turns PARK for zero-re-prefill resume.
        self.kvstore = kvstore
        self.batcher = ContinuousBatcher(
            engine, rows=rows, chunk_steps=chunk_steps,
            chunk_steps_low=chunk_steps_low, group_chunks=group_chunks,
            prefill_only=(role == "prefill"),
            chunked_prefill=chunked_prefill,
        )
        # Prefill role: requests currently inside the batcher, keyed by id,
        # so the export callback can attach the ORIGINAL request (sampling
        # params, deadline, stream flag) to its HandoffRecord.
        self._handoff_reqs: dict[str, GenerateRequest] = {}
        if role == "prefill":
            self.batcher.export_cb = self._on_export
        # Every request currently inside the batcher, keyed by id: the
        # preemption hook stamps resume_tokens/preemptions onto the
        # ORIGINAL request object before refunding it to the broker.
        self._reqs: dict[str, GenerateRequest] = {}
        if role == "unified":
            # Preemption only makes sense where this worker both admits
            # from the request queue and decodes: a prefill replica's rows
            # live for one prefill, and a decode replica's requests arrive
            # as handoff records the request queue never redelivers.
            self.batcher.preempt_cb = self._on_preempt
        if kvstore is not None:
            self.batcher.demote_cb = self._on_demote
            self.batcher.park_cb = self._on_park
        # req_id -> session_id for requests whose finish should park
        # (set before submit/adopt, popped by the park hook / done_cb).
        self._park_sessions: dict[str, str] = {}
        # Decode role: popped-but-not-yet-adopted records (all rows busy).
        self._adopt_backlog: "deque" = deque()
        self.poll_timeout_s = poll_timeout_s
        self._publish_counter = 0
        self.draining = False
        self.last_progress_ts = 0.0
        # Retained prefix segments keyed by their token tuple (LRU):
        # requests carrying ``prefix_token_ids`` build the segment once
        # (engine.build_prefix) and every later request sharing it seeds
        # from device-resident KV instead of re-prefilling the prefix.
        self._prefixes: "dict[tuple, object]" = {}
        self.max_prefixes = 4
        # Fleet identity (see Worker): registry + load snapshots + routed
        # queue preference; None = pre-fleet single-worker behavior.
        self.worker_id = worker_id
        self.snapshot_interval_s = snapshot_interval_s
        self._last_snapshot_t = 0.0
        if worker_id is not None:
            self.register()

    def register(self) -> None:
        """(Re-)announce this worker in the fleet registry — called at
        construction and safe to call again after a registry TTL expiry."""
        self.broker.register_worker(
            worker_capabilities(self.worker_id, self.engine, self.role)
        )
        self._publish_load()

    def load_snapshot(self) -> dict:
        """Registry heartbeat: the batcher's host-side occupancy/KV view
        plus lifecycle and the resident prefix hashes from BOTH layers —
        the batcher's paged COW pool and this worker's dense prefix LRU
        (either one makes a prefix-affinity route a prefill hit)."""
        import time as _time

        snap = self.batcher.load_snapshot()
        hashes = set(snap.get("prefix_hashes") or [])
        hashes.update(prefix_hash(k) for k in self._prefixes)
        snap.update({
            "role": self.role,
            "state": STATE_DRAINING if self.draining else STATE_READY,
            "alive": True,
            # Backlogged handoff records are load this worker has already
            # committed to (their leases are ours) — routers should see it.
            "queue_depth": snap.get("pending", 0) + len(self._adopt_backlog),
            "prefix_hashes": sorted(hashes),
            # Per-tier KV residency + lifecycle counters (numeric leaves
            # only): the producer aggregates these fleet-wide and the
            # Prometheus renderer walks them into families as-is.
            **(
                {"kv_tiers": self.kvstore.stats()}
                if self.kvstore is not None else {}
            ),
            "heartbeat_s": self.snapshot_interval_s,
            # Cross-process staleness stamp (see Worker.load_snapshot).
            "heartbeat_ts": _time.time(),  # lint: ignore[wall-clock-timer]
            # Flight-recorder snapshot (see Worker.load_snapshot).
            **(
                {"trace": trace.recorder().export(max_events=256)}
                if trace.enabled() else {}
            ),
            # Windowed SLO series (see Worker.load_snapshot).
            **(
                {"series": metrics_mod.series().export(cache_s=1.0)}
                if trace.enabled() else {}
            ),
            # Device telemetry blob (see Worker.load_snapshot).
            **({"devtel": devtel.export()} if devtel.enabled() else {}),
        })
        if devtel.enabled():
            # Queue depths BY CLASS come from the broker, not the batcher
            # — sampled here at heartbeat cadence so the counter track
            # shows which class's queue a waiting request sat in.
            depths = getattr(self.broker, "queue_depths_by_class", None)
            if depths is not None:
                try:
                    by_class = {
                        str(k): int(v) for k, v in depths().items()
                    }
                except Exception:  # noqa: BLE001 — telemetry never gates serving
                    by_class = {}
                if by_class:
                    devtel.record_counters({"queue_by_class": by_class})
        return snap

    def _publish_load(self) -> None:
        if self.worker_id is not None:
            self._last_snapshot_t = time.monotonic()
            self.broker.publish_worker_load(
                self.worker_id, self.load_snapshot()
            )

    def _maybe_publish_load(self) -> None:
        if (
            self.worker_id is not None
            and time.monotonic() - self._last_snapshot_t
            >= self.snapshot_interval_s
        ):
            self._publish_load()

    def _pop(self, timeout: float = 0.0) -> GenerateRequest | None:
        if self.worker_id is None:
            return self.broker.pop_request(timeout=timeout)
        return self.broker.pop_request(
            timeout=timeout, worker_id=self.worker_id
        )

    def prewarm(
        self, seq_buckets: list[int] | None = None,
        prefix_prefill: bool = False,
    ) -> int:
        """Compile the batcher's full executable envelope up front
        (``seq_buckets`` narrows the prompt-length envelope when known;
        ``prefix_prefill`` adds the prefix-reuse admission variants).

        Tracing the step programs leaves hundreds of thousands of objects
        that live as long as the process; a full collection walks them all
        and stops the loop for longer than a short group runs. They are put
        out of the collector's reach here, once, so that a collection while
        serving walks what serving made."""
        with devtel.setup_span("setup.prewarm") as sp:
            n = self.batcher.prewarm(seq_buckets, prefix_prefill)
            with devtel.setup_span("setup.prewarm.gc"):
                gc.collect()
                gc.freeze()
            sp.set(executables=n)
        return n

    def _drain_broker(self, loop: int | None = None) -> int:
        n = 0
        while True:
            if self.batcher.idle and n == 0:
                # Nothing to run: block on the queue. A span of its own
                # (``loop.idle``), so that waiting is never read as host work.
                with self.batcher.loop_span("loop.idle", loop):
                    req = self._pop(timeout=self.poll_timeout_s)
            else:
                req = self._pop()
            if req is None:
                return n
            if (
                req.deadline_ts is not None
                and time.time() > req.deadline_ts
            ):
                # Shed before prefill (see Worker.run_once).
                self.engine.metrics.add_expired()
                self.broker.push_response(
                    GenerateResponse(id=req.id, error="deadline exceeded")
                )
                continue
            try:
                req.validate()
                ids = encode_request(self.tokenizer, req)
                gen = gen_params_from(self.tokenizer, req)
            except Exception as e:  # noqa: BLE001 — per-request error surface
                self.broker.push_response(
                    GenerateResponse(id=req.id, error=str(e))
                )
                continue

            cb = self._done_cb(req)

            stream_cb = None
            if req.stream:
                def stream_cb(new_toks, req=req):
                    self.broker.push_stream(req.id, new_toks)

            resume = list(req.resume_tokens or ())
            if resume:
                # Resume after preemption: prompt + already-emitted tokens
                # admit as one (chunked-prefill) prompt; the batcher
                # preloads the replayed tail into the row's output and
                # decodes only the remainder — sampling is stateless per
                # (seed, position), so greedy streams match the
                # unpreempted run token for token.
                ids = ids + resume
                gen.max_new_tokens = req.max_new_tokens - len(resume)
            try:
                prefix = (
                    self._get_prefix(req.prefix_token_ids)
                    if req.prefix_token_ids else None
                )
                if prefix is None and req.session_id and (
                    self.kvstore is not None
                ):
                    # Session resume: a prior turn parked this session's
                    # KV. If the parked tokens are a proper prefix of the
                    # new turn's prompt, seed from them — the earlier
                    # turns never re-prefill and the stream is
                    # bit-identical to the never-evicted run.
                    prefix = self._resume_session(req.session_id, ids)
                if self.role == "prefill":
                    # Must be registered BEFORE submit: a short request
                    # can resolve (and its done_cb clean this up) inside
                    # the submit -> next step() window.
                    self._handoff_reqs[req.id] = req
                self._reqs[req.id] = req
                if req.session_id and self.kvstore is not None and (
                    self.role != "prefill"
                ):
                    # Park interest BEFORE submit (a short request can
                    # finish inside the submit -> step window). Prefill
                    # role never parks: its rows end at export, and the
                    # decode side owns the finished KV.
                    self._park_sessions[req.id] = req.session_id
                    self.batcher.request_park(
                        req.id, ids, replayed=len(resume)
                    )
                self.batcher.submit(
                    ids, gen, cb, req_id=req.id, stream_cb=stream_cb,
                    prefix=prefix,
                    priority=SLO_CLASS_RANK.get(req.slo_class, 1),
                    replayed=len(resume),
                )
            except ValueError as e:  # e.g. prompt + max_new exceeds the ring
                self._handoff_reqs.pop(req.id, None)
                self._reqs.pop(req.id, None)
                self._park_sessions.pop(req.id, None)
                self.batcher.forget_park(req.id)
                self.broker.push_response(
                    GenerateResponse(id=req.id, error=str(e))
                )
                continue
            n += 1

    def _done_cb(self, req: GenerateRequest):
        """Completion closure for one request: turns the batcher's
        (tokens, cancelled, error) outcome into exactly one broker
        response. Shared by the submit path and the adopt path — on a
        decode replica ``push_response`` doubles as the handoff ack."""

        def cb(toks, cancelled=False, error=None):
            self._handoff_reqs.pop(req.id, None)
            self._reqs.pop(req.id, None)
            # The park hook (which runs before this) already consumed the
            # entry on the served path; this covers error/cancel paths.
            self._park_sessions.pop(req.id, None)
            if error is not None:
                # Row-level failure (e.g. poison containment): the
                # batcher finished this row with an error; batch-mates
                # are untouched.
                self.engine.metrics.add_error()
                self.broker.push_response(
                    GenerateResponse(id=req.id, error=error, token_ids=toks)
                )
                return
            if cancelled:
                # Honest response: the client timed out / went away;
                # partial tokens ride along, but this is not a success.
                self.broker.push_response(
                    GenerateResponse(
                        id=req.id, error="cancelled", token_ids=toks,
                    )
                )
                return
            text = (
                self.tokenizer.decode(toks)
                if self.tokenizer is not None else None
            )
            self.broker.push_response(
                GenerateResponse(
                    id=req.id, prompt=req.prompt, continuation=text,
                    token_ids=toks,
                )
            )

        return cb

    # -- preemption ---------------------------------------------------------

    def _on_preempt(self, rid: str, toks: list[int]) -> None:
        """Batcher eviction hook: stamp the emitted tokens onto the
        ORIGINAL request as its resume point and refund it to the broker
        (``preempt_requests`` — head-of-class-queue requeue, delivery
        attempt NOT consumed). The next worker to lease it replays the
        tokens as chunked prefill and continues the identical stream."""
        req = self._reqs.pop(rid, None)
        if req is None:
            return  # cancelled/finished concurrently — the row's gone
        req.resume_tokens = list(toks) if toks else None
        req.preemptions += 1
        self.broker.preempt_requests([req])

    # -- KV handoff: prefill side -------------------------------------------

    def _on_export(self, rid: str, first: int, n_tokens: int, blocks) -> None:
        """Batcher export callback (prefill role): serialize the row's
        blocks and push the record toward a decode replica. ``push_handoff``
        enqueues the record BEFORE settling the request lease, so a death
        anywhere in here re-prefills elsewhere — never loses the request."""
        req = self._handoff_reqs.pop(rid, None)
        if req is None:  # defensive: submit registered it before the batcher
            self.broker.push_response(
                GenerateResponse(id=rid, error="exported request lost")
            )
            return
        with trace.span(
            rid, "kv_export", trace_id=req.trace_id,
            worker=self.worker_id, n_tokens=n_tokens,
        ):
            payload = encode_blocks(
                blocks, req_id=rid, n_tokens=n_tokens,
                block_size=self.engine.block_size,
                trace_id=req.trace_id,
            )
        rec = HandoffRecord(
            req=req, first_token=first, n_tokens=n_tokens, payload=payload,
        )
        target = pick_decode_worker(
            self.broker.read_workers(), self.broker.handoff_depths()
        )
        if target is None:
            self.broker.push_handoff(rec)
        else:
            self.broker.push_handoff_to(target, rec)

    # -- KV handoff: decode side --------------------------------------------

    def _try_adopt(self, rec: HandoffRecord) -> bool:
        """Install one handoff record into a free row. Returns False ONLY
        when capacity-blocked (record untouched — caller holds it and
        renews its lease); terminal outcomes (deadline, corrupt payload,
        mismatched pool shape) consume the record and return True."""
        req = rec.req
        if req.deadline_ts is not None and time.time() > req.deadline_ts:
            # Shed before adopting: push_response acks the handoff lease.
            self.engine.metrics.add_expired()
            self.broker.push_response(
                GenerateResponse(id=req.id, error="deadline exceeded")
            )
            return True
        try:
            gen = gen_params_from(self.tokenizer, req)
            with trace.span(
                req.id, "kv_adopt", trace_id=req.trace_id,
                worker=self.worker_id, bytes=len(rec.payload),
            ):
                d = decode_blocks(rec.payload)
                blocks = {k: d[k] for k in ("k", "v", "k_scale", "v_scale")}
        except Exception as e:  # noqa: BLE001 — corrupt payload quarantine
            # fail_handoff re-queues the REQUEST (re-prefill makes a fresh
            # payload); repeat offenders hit the delivery-attempt cap and
            # dead-letter.
            self.broker.fail_handoff(rec, error=str(e))
            return True
        stream_cb = None
        if req.stream:
            def stream_cb(new_toks, req=req):
                self.broker.push_stream(req.id, new_toks)
        if req.session_id and self.kvstore is not None:
            # Adopted rows carry no prompt ids inside the batcher —
            # register them here so the finish hook can park the session
            # (withdrawn below if the adopt never takes a row).
            self._park_sessions[req.id] = req.session_id
            self.batcher.request_park(req.id, list(req.token_ids or []))
        try:
            ok = self.batcher.adopt(
                req.id, rec.first_token, rec.n_tokens, blocks, gen,
                self._done_cb(req), stream_cb=stream_cb,
            )
        except Exception as e:  # noqa: BLE001 — e.g. block_size mismatch
            self._park_sessions.pop(req.id, None)
            self.batcher.forget_park(req.id)
            self.broker.fail_handoff(rec, error=str(e))
            return True
        if not ok:
            self._park_sessions.pop(req.id, None)
            self.batcher.forget_park(req.id)
        return ok

    def _drain_handoffs(
        self, backlog_only: bool = False, loop: int | None = None,
    ) -> int:
        """Decode-role intake: adopt backlogged records first (FIFO — they
        were popped earlier), then pop new ones while rows are free. A
        capacity-blocked record goes to the backlog and stops the intake;
        its lease is renewed each run_once until a row frees. Never
        re-pushed: re-pushing would open a loss window and inflate the
        handoff counters."""
        n = 0
        while self._adopt_backlog and self._try_adopt(self._adopt_backlog[0]):
            self._adopt_backlog.popleft()
            n += 1
        if backlog_only:
            return n
        while not self._adopt_backlog:
            if self.batcher.idle and n == 0:
                with self.batcher.loop_span("loop.idle", loop):
                    rec = self.broker.pop_handoff(
                        timeout=self.poll_timeout_s,
                        worker_id=self.worker_id,
                    )
            else:
                rec = self.broker.pop_handoff(
                    timeout=0.0, worker_id=self.worker_id,
                )
            if rec is None:
                break
            if self._try_adopt(rec):
                n += 1
            else:
                self._adopt_backlog.append(rec)
                break
        return n

    def _get_prefix(self, prefix_ids: list[int]):
        """Retained prefix for these tokens, building (and LRU-evicting)
        on first use. Build cost is one prefill — paid once per distinct
        prefix, amortized over every request that shares it. With a
        tiered store, a local miss first tries PROMOTION (the blob a
        peer — or this worker's own eviction — demoted) before paying
        the prefill, and the LRU's evictions DEMOTE instead of drop."""
        key = tuple(prefix_ids)
        pfx = self._prefixes.pop(key, None)
        if pfx is None and self.kvstore is not None:
            with trace.span(
                "-", "kv_promote", worker=self.worker_id,
                n_tokens=len(prefix_ids),
            ):
                pfx = self.kvstore.fetch_prefix(
                    prefix_ids, max_seq_len=self.engine.max_seq_len,
                )
        if pfx is None:
            pfx = self.engine.build_prefix(list(prefix_ids))
        self._prefixes[key] = pfx  # most-recently-used at the end
        while len(self._prefixes) > self.max_prefixes:
            old = self._prefixes.pop(next(iter(self._prefixes)))
            self._on_demote(old)
        return pfx

    # -- KV tiering (serve/kvstore.py) ---------------------------------------

    def _on_demote(self, prefix) -> None:
        """Eviction hook (batcher pool + dense prefix LRU): hand the
        evicted ``Prefix`` to the store's async demote queue."""
        if self.kvstore is not None:
            self.kvstore.demote_prefix(prefix, self.engine.block_size)

    def _on_park(self, req_id: str, tokens, blocks) -> None:
        """Batcher finish hook: a session turn completed — park its
        exported KV under the session key for the next turn."""
        sid = self._park_sessions.pop(req_id, None)
        if sid is None or self.kvstore is None:
            return
        with trace.span(
            req_id, "kv_park", worker=self.worker_id,
            n_tokens=len(tokens),
        ):
            self.kvstore.park_session(
                sid, tokens, blocks, self.engine.block_size
            )

    def _resume_session(self, session_id: str, ids: list[int]):
        """Parked-KV resume: consume the session blob and rebuild a
        seedable ``Prefix`` when the parked tokens properly prefix the
        new turn's prompt; None (and the blob stays consumed only on a
        match) otherwise."""
        parked = self.kvstore.resume_session(session_id, token_ids=ids)
        if parked is None:
            return None
        tokens, blocks = parked
        from llmss_tpu.serve.kvstore import prefix_from_blocks

        with trace.span(
            "-", "kv_resume", worker=self.worker_id,
            n_tokens=len(tokens),
        ):
            pfx = prefix_from_blocks(
                tokens, blocks, max_seq_len=self.engine.max_seq_len,
            )
        self.kvstore.note_reprefill_avoided(len(tokens))
        return pfx

    def begin_drain(self) -> None:
        """Supervisor drain contract: stop leasing new requests; run_once
        keeps stepping (cancels, lease renewal, publishes included) until
        the active rows finish and ack."""
        self.draining = True

    @property
    def drained(self) -> bool:
        return (
            self.draining and self.batcher.idle
            and not self._adopt_backlog
        )

    def release_pending(self) -> int:
        """Drain-deadline fallback, half 1: requests this worker leased
        but never admitted go back to the broker queue for another worker
        — no error, no redelivery count against the request. (Half 2, the
        active rows, gets ``abort_inflight``.)"""
        ids = self.batcher.drop_pending()
        for rid in ids:
            self._reqs.pop(rid, None)
        if ids:
            self.broker.release_requests(ids)
        return len(ids)

    def run_once(self) -> int:
        """One iteration of the worker loop. On the loop track
        (utils/trace.py) it is one ``loop`` span whose children are
        ``loop.housekeep``, ``loop.drain`` (with ``loop.idle`` inside when
        the pop blocks), the batcher's ``sched.*`` spans and
        ``loop.publish``."""
        self.last_progress_ts = time.monotonic()
        span = self.batcher.loop_span
        with span("loop") as it:
            it.set(iteration=self._publish_counter)
            with span("loop.housekeep", it.seq) as sp:
                # Check the broker's TTL'd cancellation flags for exactly
                # the ids this batcher holds (pending, in-flight admission,
                # active): the flag persists until its request shows up, so
                # cancel-before-submit races land, and other workers' ids
                # are never swallowed.
                live = self.batcher.live_ids()
                sp.set(live=len(live))
                # Renew this worker's leases on everything it holds —
                # pending and active alike — so only a genuinely dead
                # worker's requests are redelivered, never a busy one's.
                self.broker.touch_requests(live)
                if self.role == "decode":
                    # Adopted rows and backlogged records are held under
                    # HANDOFF leases (their request leases were settled at
                    # push_handoff); renew those at the same cadence.
                    # Unknown ids are ignored.
                    self.broker.touch_handoffs(
                        live + [r.req.id for r in self._adopt_backlog]
                    )
                for rid in self.broker.check_cancelled(live):
                    # The batcher frees the row at the top of its next
                    # step; the request's done_cb fires with the tokens
                    # produced so far.
                    self.batcher.cancel(rid)
                self._maybe_publish_load()
            with span("loop.drain", it.seq) as sp:
                if self.role == "decode":
                    # Draining still adopts the backlog: those records are
                    # already this worker's responsibility (leased), and
                    # every adoption moves them toward their exactly-one
                    # terminal response.
                    n = self._drain_handoffs(
                        backlog_only=self.draining, loop=sp.seq,
                    )
                else:
                    n = 0 if self.draining else self._drain_broker(sp.seq)
                sp.set(popped=n)
            self.batcher.step(it.seq)
            self._publish_counter += 1
            # Every 16 iterations even when idle: with chunked steps (~0.3 s
            # each under load) a sparser cadence would let the supervisor
            # heartbeat go stale mid-serve (producer /health flips at
            # 3× heartbeat_s).
            if n or self._publish_counter % 16 == 0:
                with span("loop.publish", it.seq):
                    self.broker.publish_metrics(self.engine.metrics.to_dict())
        return n

    def abort_inflight(self, reason: str) -> int:
        """Error out every admitted-but-unfinished request (supervisor
        teardown contract: every request gets a response, even across a
        worker restart). Backlogged handoff records are returned via
        ``fail_handoff`` — their requests re-queue for a fresh prefill on
        a surviving replica instead of waiting out the lease timeout."""
        while self._adopt_backlog:
            self.broker.fail_handoff(
                self._adopt_backlog.popleft(),
                error=f"worker restarted: {reason}",
            )
        ids = self.batcher.drain_all()
        for rid in ids:
            self._reqs.pop(rid, None)
            self.broker.push_response(
                GenerateResponse(id=rid, error=f"worker restarted: {reason}")
            )
        return len(ids)

    def run_forever(self, stop: threading.Event | None = None) -> None:
        while stop is None or not stop.is_set():
            self.run_once()


def main(argv=None):
    import argparse

    parser = argparse.ArgumentParser("llmss-consumer")
    parser.add_argument("--pretrained_model_path", required=True)
    parser.add_argument("--batch_size", type=int, default=8)
    parser.add_argument(
        "--continuous", action="store_true",
        help="continuous batching (token-level admission) instead of "
             "batch-at-a-time",
    )
    parser.add_argument("--max_seq_len", type=int, default=None)
    parser.add_argument(
        "--chunk_steps", type=int, default=8,
        help="decode steps per host round-trip (1 = per-token streaming "
             "granularity; higher amortizes host-link latency)",
    )
    parser.add_argument(
        "--group_chunks", type=int, default=1,
        help="continuous batching only: fused decode chunks dispatched as "
             "ONE jitted program while busy — host syncs and dispatch "
             "overhead scale per group instead of per chunk, at the cost "
             "of admission granularity stretching to group_chunks x "
             "chunk_steps tokens (docs/decode-loop.md)",
    )
    parser.add_argument("--tp", type=int, default=None)
    parser.add_argument("--dp", type=int, default=1)
    parser.add_argument(
        "--sp", type=int, default=1,
        help="sequence-parallel axis (sp-sharded KV cache: context scales "
             "with chips)",
    )
    parser.add_argument("--dtype", type=str, default=None)
    parser.add_argument(
        "--kv_dtype", type=str, default=None, choices=[None, "int8"],
        help="int8 = quantized KV cache (double the rows per chip)",
    )
    parser.add_argument("--redis_host", default="localhost")
    parser.add_argument("--redis_port", type=int, default=6379)
    parser.add_argument(
        "--lease_s", type=float, default=60.0,
        help="request lease visibility timeout: an un-acked lease older "
             "than this is redelivered to another worker (workers renew "
             "leases every decode chunk)",
    )
    parser.add_argument(
        "--max_delivery_attempts", type=int, default=3,
        help="deliveries before a request is dead-lettered instead of "
             "redelivered (poison-request quarantine)",
    )
    parser.add_argument(
        "--chunked_prefill", type=int, default=None,
        help="continuous batching only: admit prompts by streaming them "
             "through the ragged mixed-batch dispatch, this many tokens "
             "per step, instead of a dedicated bucketed prefill program — "
             "long prompts stop stalling decode rows and the prefill "
             "prewarm grid disappears (docs/decode-loop.md). Requires "
             "--kv_layout paged",
    )
    parser.add_argument(
        "--role", choices=["unified", "prefill", "decode"],
        default="unified",
        help="disaggregated serving role (docs/serving.md): 'prefill' "
             "exports each request's KV blocks to the handoff channel "
             "after prefill; 'decode' adopts handed-off blocks and decodes "
             "them; 'unified' (default) does both — bit-identical to "
             "pre-disaggregation single-worker serving. prefill/decode "
             "require --continuous and --kv_layout paged",
    )
    parser.add_argument(
        "--kv_layout", choices=["dense", "paged"], default="dense",
        help="KV cache layout: 'paged' enables the block pool (COW "
             "prefixes, KV handoff); 'dense' is the contiguous ring",
    )
    parser.add_argument(
        "--worker_id", default=None,
        help="fleet identity (no ':' allowed): register in the broker's "
             "worker registry, publish load snapshots, and serve this "
             "worker's routed queue before the shared one; omit for "
             "plain single-worker shared-queue serving",
    )
    parser.add_argument(
        "--snapshot_interval_s", type=float, default=1.0,
        help="load-snapshot publish cadence when --worker_id is set "
             "(routers treat a worker as stale after 3x this)",
    )
    parser.add_argument(
        "--kv_tier_host_mb", type=float, default=None,
        help="enable the tiered KV store (docs/paged-kv.md 'KV tiers') "
             "with this many MB of host RAM as tier T1; the broker's "
             "Redis doubles as the fleet-wide T2 blob store. Evicted "
             "prefixes demote instead of dropping, shared-prefix misses "
             "promote from the tiers, and multi-turn sessions park their "
             "KV between turns (zero re-prefill on resume). Requires "
             "--continuous",
    )
    parser.add_argument(
        "--supervise", action="store_true",
        help="run under the crash-restart supervisor (heartbeats + capped "
             "exponential backoff)",
    )
    parser.add_argument("--max_restarts", type=int, default=None)
    parser.add_argument(
        "--step_timeout_s", type=float, default=None,
        help="watchdog: a decode step with no progress for this long is "
             "escalated as a crash (supervised mode; default: disabled)",
    )
    parser.add_argument(
        "--drain_timeout_s", type=float, default=30.0,
        help="SIGTERM drain deadline: past it, never-started requests are "
             "released back to the queue and active rows abort with an "
             "error instead of pinning the shutdown",
    )
    args = parser.parse_args(argv)
    if args.role != "unified":
        if not args.continuous:
            parser.error("--role prefill/decode requires --continuous")
        if args.kv_layout != "paged":
            parser.error("--role prefill/decode requires --kv_layout paged")
    if args.chunked_prefill is not None:
        if not args.continuous:
            parser.error("--chunked_prefill requires --continuous")
        if args.kv_layout != "paged":
            parser.error("--chunked_prefill requires --kv_layout paged")
    if args.kv_tier_host_mb is not None and not args.continuous:
        parser.error("--kv_tier_host_mb requires --continuous")

    from transformers import AutoTokenizer

    from llmss_tpu.models.registry import load_model
    from llmss_tpu.parallel import (
        MeshPlan, default_compute_dtype, initialize_runtime, make_mesh,
    )
    from llmss_tpu.serve.broker import RedisBroker

    initialize_runtime()
    mesh = make_mesh(MeshPlan(dp=args.dp, sp=args.sp, tp=args.tp))
    dtype = args.dtype or str(default_compute_dtype())
    cfg, params = load_model(args.pretrained_model_path, mesh, dtype=dtype)
    engine = DecodeEngine(
        cfg, params, mesh, kv_dtype=args.kv_dtype,
        kv_layout=args.kv_layout,
        max_seq_len=args.max_seq_len or cfg.max_position_embeddings,
    )
    tokenizer = AutoTokenizer.from_pretrained(args.pretrained_model_path)
    broker = RedisBroker(
        args.redis_host, args.redis_port, lease_s=args.lease_s,
        max_delivery_attempts=args.max_delivery_attempts,
        # Fleet id doubles as the lease identity so routed queues, lease
        # attribution, and failover all line up on one name.
        worker_id=args.worker_id,
    )

    kvstore = None
    if args.kv_tier_host_mb is not None:
        from llmss_tpu.serve.kvstore import (
            HostKVStore, RedisBlobStore, TieredKVStore,
        )

        kvstore = TieredKVStore(
            host=HostKVStore(
                cap_bytes=int(args.kv_tier_host_mb * 1024 * 1024)
            ),
            # The broker's (retry-wrapped) client doubles as T2; the
            # ":kv:" key segment keeps the blob family clear of every
            # broker key family under the same queue namespace.
            blob=RedisBlobStore(broker._r, namespace="pqueue"),
        )

    def make_worker():
        if args.continuous:
            w = ContinuousWorker(
                engine, broker, tokenizer, rows=args.batch_size,
                chunk_steps=args.chunk_steps,
                group_chunks=args.group_chunks,
                worker_id=args.worker_id,
                snapshot_interval_s=args.snapshot_interval_s,
                role=args.role,
                chunked_prefill=args.chunked_prefill,
                kvstore=kvstore,
            )
        else:
            w = Worker(
                engine, broker, tokenizer, batch_size=args.batch_size,
                chunk_steps=args.chunk_steps, worker_id=args.worker_id,
                snapshot_interval_s=args.snapshot_interval_s,
            )
        # Inside the factory so supervised restarts (fresh batcher, fresh
        # jit wrappers) also come up fully compiled.
        t0 = time.monotonic()
        n = w.prewarm()
        logger.info(
            "prewarmed %d executables in %.0fs", n, time.monotonic() - t0
        )
        return w

    print(
        "consumer serving"
        + (" (continuous batching)" if args.continuous else "")
        + (f" (role={args.role})" if args.role != "unified" else "")
        + (" (supervised)" if args.supervise else "")
    )
    import signal

    if args.supervise:
        from llmss_tpu.serve.supervisor import Supervisor

        sup = Supervisor(
            make_worker, broker, max_restarts=args.max_restarts,
            step_timeout_s=args.step_timeout_s,
            drain_timeout_s=args.drain_timeout_s,
        )

        def _on_sigterm(signum, frame):
            # First SIGTERM: graceful drain, refused if this is the last
            # routable replica of its role (drain_blocked advisory).
            # Second SIGTERM: the operator means it — force teardown.
            if sup.drain(force=sup.draining or _sig_seen["n"] > 0):
                logger.info("SIGTERM: draining (deadline %.0fs)",
                            args.drain_timeout_s)
            else:
                logger.warning(
                    "SIGTERM: drain blocked (last routable replica); "
                    "send SIGTERM again to force teardown"
                )
            _sig_seen["n"] += 1

        _sig_seen = {"n": 0}

        signal.signal(signal.SIGTERM, _on_sigterm)
        sup.run()
    else:
        w = make_worker()

        def _on_sigterm(signum, frame):
            logger.info("SIGTERM: draining (unsupervised)")
            w.begin_drain()

        signal.signal(signal.SIGTERM, _on_sigterm)
        while not (w.draining and w.drained):
            w.run_once()


if __name__ == "__main__":
    main()
