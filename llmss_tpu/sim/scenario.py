"""Scenario files + the FleetSim engine that runs them.

A scenario is a JSON document (``format: "llmss-scenario/1"``,
docs/simulator.md) describing one deterministic run: broker parameters,
fleet shape, device cost model, workload (synthetic arrival process or
an ``llmss-workload/1`` capture from ``/trace/export_workload``), and a
fault schedule. :class:`FleetSim` instantiates the REAL serving stack —
``InProcBroker`` or ``RedisBroker``-over-``FakeRedis``, the fleet
``Router`` + ``BrownoutController``, the handoff channel, the
scheduler's preemption policy — under a virtual clock, pumps the
workload through :class:`~llmss_tpu.sim.replica.SimReplica` actors,
fires the fault schedule, and asserts the full invariant catalog at
drain.

Determinism rules (docs/simulator.md): one ``random.Random(seed)``
drives every stochastic choice in a fixed order; the event loop breaks
time ties by insertion order; no wall-clock value can leak into the run
(the virtual clock owns ``time.monotonic``/``time.time`` while
installed, and reports contain only virtual-time quantities). Same
scenario + same seed ⇒ byte-identical report.
"""

from __future__ import annotations

import collections
import json
import random

from llmss_tpu.serve.broker import InProcBroker, RedisBroker
from llmss_tpu.serve.chaos import POISON_TOKEN, FakeRedis
from llmss_tpu.serve.fleet import BrownoutController, Router
from llmss_tpu.serve.protocol import (
    SLO_CLASSES,
    GenerateRequest,
)
from llmss_tpu.sim.clock import VirtualClock
from llmss_tpu.sim.cost import DeviceCostModel
from llmss_tpu.sim.faults import FaultPlane
from llmss_tpu.sim.invariants import InvariantChecker
from llmss_tpu.sim.loop import EventLoop
from llmss_tpu.sim.replica import SimReplica, SimTierStore
from llmss_tpu.utils import trace

SCENARIO_FORMAT = "llmss-scenario/1"

_ROLE_PREFIX = {"unified": "u", "prefill": "p", "decode": "d"}


def load_scenario(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        spec = json.load(f)
    fmt = spec.get("format")
    if fmt != SCENARIO_FORMAT:
        raise ValueError(
            f"{path}: format {fmt!r}, expected {SCENARIO_FORMAT!r}"
        )
    return spec


def _percentile(sorted_vals: list[float], q: float) -> float:
    """Nearest-rank percentile over an already-sorted list (no numpy on
    the hot path; deterministic for byte-identical reports)."""
    if not sorted_vals:
        return 0.0
    i = min(len(sorted_vals) - 1, max(0, int(q * len(sorted_vals))))
    return sorted_vals[i]


class FleetSim:
    """One scenario run over the real serving stack on a virtual clock."""

    def __init__(self, spec: dict, *, n_requests: int | None = None,
                 duration_s: float | None = None, seed: int | None = None):
        fmt = spec.get("format", SCENARIO_FORMAT)
        if fmt != SCENARIO_FORMAT:
            raise ValueError(f"unsupported scenario format {fmt!r}")
        self.spec = spec
        self.name = spec.get("name", "scenario")
        self.seed = int(spec.get("seed", 0) if seed is None else seed)
        self.rng = random.Random(self.seed)
        self.duration_s = (
            duration_s if duration_s is not None else spec.get("duration_s")
        )

        self.clock = VirtualClock()
        self.loop = EventLoop(self.clock)
        self.cost = DeviceCostModel.from_config(spec.get("cost_model"))
        self.broker = self._build_broker(spec.get("broker") or {})
        wl = dict(spec.get("workload") or {})
        if n_requests is not None:
            wl["requests"] = n_requests
        self.workload = wl
        self.checker = InvariantChecker(
            check_payloads=bool(wl.get("check_payloads", True)),
        )
        self.checker.attach(self.broker)
        self._attach_collector(self.broker)
        self.faults = FaultPlane()
        self.counters: dict[str, int] = collections.defaultdict(int)

        fleet = spec.get("fleet") or {}
        # Fleet-shared tiered KV store (``fleet.kv_tiering`` block,
        # serve/kvstore.py's sim twin). Built BEFORE the replicas — they
        # bind ``sim.tier_store`` at construction. ``enabled: false``
        # keeps the block in the scenario but runs the per-worker-LRU
        # baseline, which is how the tiering bench builds its arms.
        kt = fleet.get("kv_tiering") or {}
        self.tier_store: SimTierStore | None = None
        if kt and kt.get("enabled", True):
            self.tier_store = SimTierStore(
                t1_cap_tokens=int(kt.get("t1_cap_tokens", 4096)),
                checker=self.checker,
            )
            self.checker.attach_tier_store(self.tier_store)
        self.replicas: list[SimReplica] = []
        self.by_wid: dict[str, SimReplica] = {}
        # Provisioned-replica gauge for the autoscale bench's chip-hours
        # metric (SimReplica._mark_up/_mark_down drive it).
        self._alive_now = 0
        self._peak_alive = 0
        self._build_fleet(fleet)
        # "shared" is the null policy: requests go to the shared queue
        # and any non-decode replica pops them — the baseline arm the
        # router benches compare against.
        policy = fleet.get("router_policy", "least_loaded")
        self.router = None if policy == "shared" else Router(
            self.broker,
            policy=policy,
            failover_check_s=float(fleet.get("failover_check_s", 1.0)),
        )
        self.ctrl = self._build_brownout(fleet.get("brownout"))
        # Fleet controller (serve/controller.py): scenario-driven
        # autoscaling over the REAL reconciler. Telemetry internals are
        # initialized even without a controller block so the fault plane
        # can reference them unconditionally.
        self._util_prev: dict[str, tuple[float, float]] = {}
        self._last_telemetry: dict | None = None
        self._telemetry_stale_until = 0.0
        self._telemetry_min_dt = 0.5
        self._ctrl_ttft_target = 0.5
        self._ctrl_seq = 1
        self._zombie_controllers: list = []
        self._ctrl_cfg = fleet.get("controller")
        self.controller = (
            self._build_controller(self._ctrl_cfg)
            if self._ctrl_cfg else None
        )
        self.poison_respawn_s = float(spec.get("poison_respawn_s", 0.5))
        self.tick_s = float(spec.get("control_tick_s", 0.25))

        # Virtual-time latency accounting (successes only).
        self._submit_t: dict[str, float] = {}
        self._first_t: dict[str, float] = {}
        self._ttft: list[float] = []
        self._e2e: list[float] = []
        self._interactive_ttft = collections.deque(maxlen=64)
        self._tokens_out = 0
        self._done = 0
        self._arrivals_done = False
        self._end_t = 0.0

        # Optional metric planes (scenario "metrics" block). step_gaps
        # collects one inter-token gap per decoding row per fused step —
        # the cadence-variance measurement the PD/ragged benches assert
        # on; leave it off for big storms (one float per token).
        m = spec.get("metrics") or {}
        self.step_gaps: list[float] | None = (
            [] if m.get("step_gaps") else None
        )
        self.per_class = bool(m.get("per_class"))
        self._cls_ttft: dict[str, list[float]] = collections.defaultdict(list)
        self._cls_e2e: dict[str, list[float]] = collections.defaultdict(list)
        self._cls_offered: dict[str, int] = collections.defaultdict(int)
        self._cls_done: dict[str, int] = collections.defaultdict(int)
        self._cls_shed: dict[str, int] = collections.defaultdict(int)
        # Hook: map a request to its accounting class. Defaults to the
        # request's slo_class; benches that neutralize broker priority
        # (the FIFO arm submits everything as one class) install a
        # side-table classifier so per-class stats keep the true class.
        self.classify = None

    # -- construction ---------------------------------------------------------

    def _build_broker(self, b: dict):
        self._broker_kind = b.get("kind", "inproc")
        self._broker_kw = dict(
            lease_s=float(b.get("lease_s", 2.0)),
            max_delivery_attempts=int(b.get("max_delivery_attempts", 5)),
            worker_ttl_s=float(b.get("worker_ttl_s", 30.0)),
        )
        if self._broker_kind == "inproc":
            return InProcBroker(
                response_ttl_s=float(b.get("response_ttl_s", 60.0)),
                **self._broker_kw,
            )
        if self._broker_kind == "fakeredis":
            self._redis_client = FakeRedis()
            return RedisBroker(
                client=self._redis_client, worker_id="sim-router",
                **self._broker_kw,
            )
        raise ValueError(f"unknown broker kind {self._broker_kind!r}")

    def broker_for(self, wid: str):
        """A replica's broker view. InProc: the one shared instance.
        Redis: a per-worker RedisBroker over the shared (Fake)Redis,
        like each real consumer process owns — lease keys embed the
        worker identity and ``pop_request`` adopts the caller's id into
        the instance, so replicas must not share one object. Every view
        gets the checker + collector wrap so responses pushed (or
        dispositioned by a reaper) through ANY view are observed."""
        if self._broker_kind == "inproc":
            return self.broker
        view = RedisBroker(
            client=self._redis_client, worker_id=wid, **self._broker_kw,
        )
        self.checker.attach(view)
        self._attach_collector(view)
        return view

    def _build_fleet(self, fleet: dict) -> None:
        groups = fleet.get("replicas") or [{"count": 4, "role": "unified"}]
        # Per-role wid counters + group templates persist past
        # construction: controller spawns continue the numbering and
        # clone the role's first group's knobs.
        self._role_idx: dict[str, int] = collections.defaultdict(int)
        self._role_groups: dict[str, dict] = {}
        for g in groups:
            role = g.get("role", "unified")
            self._role_groups.setdefault(role, g)
            for _ in range(int(g.get("count", 1))):
                wid = self._next_wid(role)
                self.checker.note_worker(wid)
                self._make_replica(wid, role, g)

    def _next_wid(self, role: str) -> str:
        wid = f"sim-{_ROLE_PREFIX[role]}{self._role_idx[role]:02d}"
        self._role_idx[role] += 1
        return wid

    def _make_replica(self, wid: str, role: str, g: dict) -> SimReplica:
        r = SimReplica(
            self, wid, role=role,
            rows=int(g.get("rows", 8)),
            chunk_tokens=int(g.get("chunk_tokens", 16)),
            prefill_chunk=int(g.get("prefill_chunk", 64)),
            admit_burst=int(g.get("admit_burst", 4)),
            heartbeat_s=float(g.get("heartbeat_s", 0.5)),
            prefill_mode=g.get("prefill_mode", "chunked"),
            prefix_lru_slots=int(g.get("prefix_lru_slots", 0)),
            preempt=bool(g.get("preempt", True)),
            sized_handoff_payload=bool(
                g.get("sized_handoff_payload", False)
            ),
        )
        self.replicas.append(r)
        self.by_wid[wid] = r
        return r

    def _build_brownout(self, b: dict | None):
        if not b:
            return None
        target = float(b.get("ttft_target_s", 0.5))
        burn_mode = b.get("burn", "mean")
        slo_target = float(b.get("slo_target", 0.95))

        def read_burn() -> float:
            window = self._interactive_ttft
            if not window:
                return 0.0
            if burn_mode == "attainment":
                # SLO burn rate: fraction of the error budget
                # (1 - slo_target) consumed over the sliding window —
                # the bench_priority ladder driver.
                att = sum(1 for v in window if v <= target) / len(window)
                return (1.0 - att) / max(1.0 - slo_target, 1e-9)
            return sum(window) / len(window) / target

        return BrownoutController(
            read_burn,
            high=float(b.get("high", 2.0)),
            low=float(b.get("low", 1.0)),
            dwell_s=float(b.get("dwell_s", 5.0)),
            check_s=float(b.get("check_s", 1.0)),
            batch_max_new_cap=int(b.get("batch_max_new_cap", 64)),
        )

    def _build_controller(self, c: dict):
        """The REAL reconciling controller (serve/controller.py) wired
        to sim actuators: spawns continue the role's wid numbering and
        clone the role's group knobs; retires drive the replica drain
        lifecycle. Invariant hooks fire on every actuation so the
        checker — not the controller's own guards — is what certifies
        no-duplicate-spawn / drain-before-retire / floor."""
        from llmss_tpu.serve.controller import FleetController

        roles = sorted({r.role for r in self.replicas}) or ["unified"]
        cold = float(c.get("cold_start_s", 2.0))
        self._ctrl_ttft_target = float(c.get("ttft_target_s", 0.5))
        self._telemetry_min_dt = float(c.get("telemetry_min_dt_s", 0.5))
        floor = c.get("floor", 1)
        floor_map = (
            {r: int(floor.get(r, 1)) for r in roles}
            if isinstance(floor, dict)
            else {r: int(floor) for r in roles}
        )

        def spawn(role: str) -> str:
            wid = self._next_wid(role)
            self.checker.on_controller_spawn(wid)
            r = self._make_replica(wid, role, self._role_groups.get(role, {}))
            self.counters["ctrl_spawns"] += 1
            r.spawn(cold_start_s=cold)
            return wid

        def retire(wid: str) -> None:
            r = self.by_wid.get(wid)
            if r is None:
                return
            remaining = sum(
                1 for o in self.replicas
                if o.role == r.role and o.alive and not o.draining
            ) - 1
            self.checker.on_fleet_retire(
                r.role, remaining, floor_map.get(r.role, 1),
            )
            self.checker.on_controller_drain(wid)
            self.counters["ctrl_retires"] += 1
            r.retire()

        ctrl = FleetController(
            self.broker,
            spawn=spawn, retire=retire,
            read_telemetry=self._read_telemetry,
            roles=roles,
            floor=c.get("floor", 1),
            ceiling=c.get("ceiling", 8),
            check_s=float(c.get("check_s", 1.0)),
            cooldown_s=float(c.get("cooldown_s", 5.0)),
            dwell_s=float(c.get("dwell_s", 3.0)),
            cold_start_s=cold,
            burn_headroom_s=float(c.get("burn_headroom_s", 10.0)),
            scale_up_burn=float(c.get("scale_up_burn", 1.5)),
            scale_down_burn=float(c.get("scale_down_burn", 0.5)),
            backlog_high=float(c.get("backlog_high", 8.0)),
            backlog_low=float(c.get("backlog_low", 1.0)),
            util_high=float(c.get("util_high", 0.85)),
            util_low=float(c.get("util_low", 0.35)),
            telemetry_max_age_s=float(c.get("telemetry_max_age_s", 5.0)),
            reshape=bool(c.get("reshape", True)),
            controller_id=f"sim-ctrl-{self._ctrl_seq}",
        )
        self._ctrl_seq += 1
        return ctrl

    def _read_telemetry(self) -> dict | None:
        """The controller's signal snapshot: interactive TTFT burn (the
        same sliding window the brownout ladder reads), total queue +
        handoff backlog, and per-role mean utilization from windowed
        busy-seconds deltas (a live producer has no such source:
        ``controller.producer_telemetry``). Snapshots are memoized for a minimum window
        so repeated reads within one control interval see one coherent
        sample; a telemetry_stall fault freezes the last snapshot, whose
        aging ``ts`` is exactly what the controller's staleness gate
        watches."""
        now = self.clock.now
        if now < self._telemetry_stale_until:
            return self._last_telemetry
        last = self._last_telemetry
        if last is not None and now - last["ts"] < self._telemetry_min_dt:
            return last
        util_sum: dict[str, float] = {}
        util_n: dict[str, int] = {}
        for r in self.replicas:
            if not (r.alive or r.spawning):
                self._util_prev.pop(r.wid, None)
                continue
            t0, b0 = self._util_prev.get(r.wid, (now, r.busy_s))
            dt = now - t0
            u = min(1.0, (r.busy_s - b0) / dt) if dt > 0 else 0.0
            self._util_prev[r.wid] = (now, r.busy_s)
            util_sum[r.role] = util_sum.get(r.role, 0.0) + u
            util_n[r.role] = util_n.get(r.role, 0) + 1
        window = self._interactive_ttft
        burn = (
            sum(window) / len(window) / self._ctrl_ttft_target
            if window else 0.0
        )
        self._last_telemetry = {
            "ts": now,
            "burn": round(burn, 9),
            "queue_depth": self.broker.queue_depth()
            + sum(self.broker.routed_depths().values()),
            "handoff_depth": self.broker.handoff_depth()
            + sum(self.broker.handoff_depths().values()),
            "util": {
                role: round(util_sum[role] / util_n[role], 9)
                for role in sorted(util_sum)
            },
        }
        return self._last_telemetry

    def _restart_controller(self) -> None:
        """Crash recovery: a BRAND NEW controller instance (no memory of
        its predecessor) takes a fresh epoch and reconciles from the
        registry — the zero-duplicate-spawn path under test."""
        self.counters["controller_restarts"] += 1
        ctrl = self._build_controller(self._ctrl_cfg)
        ctrl.start()
        self.controller = ctrl
        self._wire_escalation()

    def _wire_escalation(self) -> None:
        """Brownout may escalate (shed harder) only when the controller
        says scaling cannot respond in time; with no controller (never
        configured, or crashed and not yet restarted) the ladder is
        ungated — shedding is the only protection left."""
        if self.ctrl is None:
            return
        c = self.controller
        self.ctrl.escalate_ok = (
            None if c is None
            else (lambda: c.escalation_allowed(self.clock.now))
        )

    # -- hooks SimReplica calls (provisioning gauge) --------------------------

    def on_replica_up(self) -> None:
        self._alive_now += 1
        self._peak_alive = max(self._peak_alive, self._alive_now)

    def on_replica_down(self) -> None:
        self._alive_now -= 1

    def _attach_collector(self, broker) -> None:
        """Pop every settled response out of the broker's buffer the
        instant it lands (the checker wrapper already observed it).
        Nobody in the sim blocks on wait_response, and push_response's
        TTL prune scans its whole buffer — keeping the buffer empty is
        what keeps a million-request storm O(1) per response."""
        inner = broker.push_response

        def wrapped(resp):
            inner(resp)
            broker.wait_response(resp.id, timeout=0.0)

        broker.push_response = wrapped

    # -- hooks SimReplica calls -----------------------------------------------

    def has_work(self, replica: SimReplica) -> bool:
        if replica.role == "decode":
            return (
                self.broker.handoff_depth() > 0
                or self.broker.handoff_depths().get(replica.wid, 0) > 0
            )
        return (
            self.broker.queue_depth() > 0
            or self.broker.routed_depths().get(replica.wid, 0) > 0
        )

    def record_first_token(self, req, t: float) -> None:
        self._first_t[req.id] = t

    def _class_of(self, req) -> str:
        return self.classify(req) if self.classify else req.slo_class

    def record_done(self, req, t_done: float, n_tokens: int) -> None:
        sub = self._submit_t.pop(req.id, None)
        first = self._first_t.pop(req.id, None)
        cls = self._class_of(req) if self.per_class else None
        if sub is not None:
            if first is not None:
                ttft = first - sub
                self._ttft.append(ttft)
                if req.slo_class == "interactive":
                    self._interactive_ttft.append(ttft)
                if cls is not None:
                    self._cls_ttft[cls].append(ttft)
            self._e2e.append(t_done - sub)
            if cls is not None:
                self._cls_e2e[cls].append(t_done - sub)
        if cls is not None:
            self._cls_done[cls] += 1
        self._tokens_out += n_tokens
        self._done += 1
        self._end_t = max(self._end_t, t_done)

    def on_handoff_pushed(self, target: str | None) -> None:
        r = self.by_wid.get(target) if target else None
        if r is not None:
            r.nudge()
            return
        for r in self.replicas:
            if r.role == "decode":
                r.nudge()

    # -- workload -------------------------------------------------------------

    def _install_workload(self) -> None:
        wl = self.workload
        kind = wl.get("kind", "synthetic")
        if kind == "synthetic":
            self._install_synthetic(wl)
        elif kind == "workload-file":
            self._install_workload_file(wl)
        elif kind == "trace":
            self._install_trace(wl)
        else:
            raise ValueError(f"unknown workload kind {kind!r}")

    def _install_synthetic(self, wl: dict) -> None:
        n = int(wl.get("requests", 1000))
        rate = float(wl.get("rate_rps", 500.0))
        arrival = wl.get("arrival", "poisson")
        p_lo, p_hi = wl.get("prompt_len", [4, 32])
        m_lo, m_hi = wl.get("max_new", [4, 32])
        classes = wl.get(
            "classes", {"interactive": 0.2, "standard": 0.6, "batch": 0.2}
        )
        cdf: list[tuple[float, str]] = []
        acc = 0.0
        for c in SLO_CLASSES:  # fixed order — determinism
            if c in classes:
                acc += float(classes[c])
                cdf.append((acc, c))
        deadlines = wl.get("deadline_s") or {}
        poison_every = int(wl.get("poison_every", 0))
        sessions = int(wl.get("sessions", 0))
        # ``session_turns: true`` makes session traffic STRUCTURALLY
        # multi-turn: each session request after its first carries the
        # whole earlier conversation (prompt + generated tokens) as
        # prompt history, the way real chat history accretes — what
        # exercises session parking/resume. RNG call order is unchanged,
        # so legacy scenarios without the flag stay byte-identical.
        session_turns = bool(wl.get("session_turns", False))
        # Fraction of traffic that is session (chat) traffic when
        # ``sessions`` is set; the rest is one-shot. Only consulted when
        # present, so legacy scenarios consume the RNG identically.
        session_p = wl.get("session_p")
        sess_len: dict[str, int] = {}
        sess_turn: dict[str, int] = {}
        # Shared-prefix population (``prefixes: {count, len}``): one-shot
        # requests draw one of ``count`` system prompts and carry it as a
        # prefix_token_ids reuse hint — the traffic that exercises the
        # per-worker prefix LRU and, through it, the KV tier store.
        pcfg = wl.get("prefixes") or {}
        npfx = int(pcfg.get("count", 0))
        pfx_tokens = [
            [
                self.rng.randrange(1, 50_000)
                for _ in range(int(pcfg.get("len", 32)))
            ]
            for _ in range(npfx)
        ]
        # Diurnal shaping: piecewise-constant rate multipliers
        # [[t_s, mult], ...] — rate_rps is the baseline, each breakpoint
        # rescales it from t_s on. Draw COUNT is unchanged (the
        # expovariate just gets a different rate), so profiled and flat
        # runs consume the RNG identically.
        prof = sorted(
            (float(t), float(m)) for t, m in (wl.get("rate_profile") or ())
        ) or None
        # Heavy tail: with probability p a request's max_new multiplies
        # by ``mult`` (capped) — the occasional long generation that
        # makes diurnal autoscaling hard.
        ht = wl.get("heavy_tail")
        rng = self.rng

        def rate_at(t: float) -> float:
            m = 1.0
            if prof:
                for ts, mult in prof:
                    if t >= ts:
                        m = mult
                    else:
                        break
            return max(rate * m, 1e-6)

        def make(i: int) -> GenerateRequest:
            plen = rng.randint(int(p_lo), int(p_hi))
            ids = [rng.randrange(1, 50_000) for _ in range(plen)]
            u = rng.random() * acc
            slo = next((c for a, c in cdf if u <= a), cdf[-1][1])
            mnew = rng.randint(int(m_lo), int(m_hi))
            if ht is not None and rng.random() < float(ht.get("p", 0.05)):
                mnew = min(
                    int(mnew * float(ht.get("mult", 8.0))),
                    int(ht.get("cap", 512)),
                )
            req = GenerateRequest(
                token_ids=ids,
                max_new_tokens=mnew,
                slo_class=slo,
                id=f"s{i:08d}",
            )
            if sessions and (
                session_p is None or rng.random() < float(session_p)
            ):
                sid = f"sess-{rng.randrange(sessions):05d}"
                req.session_id = sid
                if session_turns:
                    t = sess_turn.get(sid, 0)
                    req.turn = t
                    sess_turn[sid] = t + 1
                    hist = sess_len.get(sid, 0)
                    if hist:
                        # History token VALUES are inert in the sim
                        # (payload checks key on the last prompt token);
                        # only the length — the re-prefill a resume can
                        # skip — matters.
                        req.token_ids = [1] * hist + req.token_ids
                    sess_len[sid] = len(req.token_ids) + mnew
            if npfx and not req.session_id:
                # One-shot request under a shared system prompt: the
                # prefix rides in front of the drawn prompt body, with
                # the reuse hint the routers/schedulers key on.
                pref = pfx_tokens[rng.randrange(npfx)]
                req.token_ids = list(pref) + req.token_ids
                req.prefix_token_ids = list(pref)
            d = deadlines.get(slo)
            poison = poison_every and (i + 1) % poison_every == 0
            if poison:
                # Genuine poison: crashes every replica that prefills
                # it. No deadline — exhausting delivery attempts into
                # the DLQ is the outcome under test.
                req.token_ids[-1] = POISON_TOKEN
                self.checker.poison_ids.add(req.id)
            elif d is not None:
                req.deadline_ts = self.clock.time() + float(d)
            return req

        def pump(i: int):
            self._submit(make(i))
            if i + 1 < n:
                r_now = rate_at(self.clock.now)
                if arrival == "uniform":
                    dt = 1.0 / r_now
                else:
                    dt = rng.expovariate(r_now)
                self.loop.call_after(dt, lambda: pump(i + 1))
            else:
                self._arrivals_done = True

        if n > 0:
            self.loop.call_at(self.clock.now, lambda: pump(0))
        else:
            self._arrivals_done = True

    def _install_workload_file(self, wl: dict) -> None:
        """Native replay of an ``llmss-workload/1`` capture (PR 11's
        ``/trace/export_workload``): arrivals, prompt/output lengths,
        SLO classes, and (when captured) session ids replay verbatim;
        token values are synthesized deterministically from the seed."""
        path = wl["path"]
        with open(path, encoding="utf-8") as f:
            doc = json.load(f)
        if doc.get("format") != "llmss-workload/1":
            raise ValueError(
                f"{path}: not an llmss-workload/1 file "
                f"(format={doc.get('format')!r})"
            )
        speedup = float(wl.get("speedup", 1.0))
        rows = doc.get("requests") or []
        rng = self.rng

        def make(i: int) -> GenerateRequest:
            row = rows[i]
            plen = max(1, int(row.get("prompt_len") or 8))
            req = GenerateRequest(
                token_ids=[rng.randrange(1, 50_000) for _ in range(plen)],
                max_new_tokens=max(1, int(row.get("max_new_tokens") or 16)),
                slo_class=row.get("slo_class") or "standard",
                id=row.get("req_id") or f"w{i:08d}",
            )
            sess = row.get("session_id")
            if sess:
                req.session_id = sess
            return req

        def pump(i: int):
            self._submit(make(i))
            if i + 1 < len(rows):
                now_off = float(rows[i].get("arrival_s") or 0.0)
                nxt = float(rows[i + 1].get("arrival_s") or 0.0)
                self.loop.call_after(
                    max(0.0, (nxt - now_off) / speedup),
                    lambda: pump(i + 1),
                )
            else:
                self._arrivals_done = True

        if rows:
            self.loop.call_at(self.clock.now, lambda: pump(0))
        else:
            self._arrivals_done = True

    def _install_trace(self, wl: dict) -> None:
        """Explicit inline trace: ``rows`` is a list of request dicts
        (``arrival_s``, ``prompt_len`` or ``token_ids``, ``max_new``,
        optional ``slo_class``/``prefix_token_ids``/``deadline_s``/
        ``session_id``/``id``) — the bench tools' deterministic traces,
        carried inside the scenario instead of a separate capture file."""
        rows = sorted(
            wl.get("rows") or [],
            key=lambda r: float(r.get("arrival_s", 0.0)),
        )
        rng = self.rng

        def make(i: int) -> GenerateRequest:
            row = rows[i]
            ids = row.get("token_ids")
            if ids is None:
                plen = max(1, int(row.get("prompt_len") or 8))
                ids = [rng.randrange(1, 50_000) for _ in range(plen)]
            req = GenerateRequest(
                token_ids=list(ids),
                max_new_tokens=max(1, int(row.get("max_new") or 16)),
                slo_class=row.get("slo_class") or "standard",
                id=str(row.get("id") or f"t{i:08d}"),
            )
            pref = row.get("prefix_token_ids")
            if pref:
                req.prefix_token_ids = list(pref)
            if row.get("session_id"):
                req.session_id = str(row["session_id"])
            d = row.get("deadline_s")
            if d is not None:
                req.deadline_ts = self.clock.time() + float(d)
            return req

        def pump(i: int):
            self._submit(make(i))
            if i + 1 < len(rows):
                now_off = float(rows[i].get("arrival_s", 0.0))
                nxt = float(rows[i + 1].get("arrival_s", 0.0))
                self.loop.call_after(max(0.0, nxt - now_off),
                                     lambda: pump(i + 1))
            else:
                self._arrivals_done = True

        if rows:
            self.loop.call_at(
                self.clock.now + float(rows[0].get("arrival_s", 0.0)),
                lambda: pump(0),
            )
        else:
            self._arrivals_done = True

    def _submit(self, req: GenerateRequest) -> None:
        now = self.clock.now
        self.counters["submitted"] += 1
        if self.per_class:
            self._cls_offered[self._class_of(req)] += 1
        if self.ctrl is not None:
            ok, _retry = self.ctrl.admit(req)
            if not ok:
                self.counters["shed"] += 1
                if self.per_class:
                    self._cls_shed[self._class_of(req)] += 1
                self.checker.on_shed(req)
                return
        self.checker.on_submit(req, now)
        self._submit_t[req.id] = now
        if self.router is None:
            self.broker.push_request(req)
            wid = None
        else:
            wid = self.router.submit(req)
        target = self.by_wid.get(wid) if wid else None
        if target is not None:
            target.nudge()
        else:
            for r in self.replicas:
                if r.role != "decode":
                    r.nudge()

    # -- fault schedule -------------------------------------------------------

    def _install_faults(self) -> None:
        for f in self.spec.get("faults", ()):
            times = [float(f.get("at_s", 0.0))]
            every = f.get("repeat_every_s")
            if every:
                if not self.duration_s:
                    raise ValueError(
                        "repeat_every_s requires scenario duration_s"
                    )
                t = times[0] + float(every)
                while t < self.duration_s:
                    times.append(t)
                    t += float(every)
            for t in times:
                self._install_fault(dict(f), t)

    def _pick_replicas(self, count, role: str | None,
                       alive_only: bool) -> list[SimReplica]:
        pool = [
            r for r in self.replicas
            if (role in (None, "any") or r.role == role)
            and (not alive_only or r.alive)
        ]
        if count in (None, "*"):
            return pool
        return self.rng.sample(pool, min(int(count), len(pool)))

    def _install_fault(self, f: dict, at_s: float) -> None:
        kind = f["kind"]
        role = f.get("role")
        if kind == "kill_wave":
            count = int(f.get("count", 1))
            respawn = f.get("respawn_after_s", 2.0)
            respawn = None if respawn is None else float(respawn)
            stagger = float(f.get("stagger_s", 0.0))

            def fire_kill():
                victims = self._pick_replicas(count, role, alive_only=True)
                for i, r in enumerate(victims):
                    self.loop.call_after(
                        i * stagger,
                        lambda r=r: r.kill(respawn_after_s=respawn),
                    )

            self.loop.call_at(at_s, fire_kill)
        elif kind == "partition":
            dur = float(f.get("duration_s", 1.0))
            for r in self._pick_replicas(
                f.get("targets", 1), role, alive_only=False,
            ):
                self.faults.add_partition(r.wid, at_s, at_s + dur)
                self.counters["partitions"] += 1
        elif kind == "latency_spike":
            dur = float(f.get("duration_s", 1.0))
            extra = float(f.get("extra_s", 0.05))
            targets = f.get("targets", "*")
            if targets == "*":
                self.faults.add_latency("*", at_s, at_s + dur, extra)
                self.counters["latency_spikes"] += 1
            else:
                for r in self._pick_replicas(targets, role, False):
                    self.faults.add_latency(r.wid, at_s, at_s + dur, extra)
                    self.counters["latency_spikes"] += 1
        elif kind == "heartbeat_stall":
            dur = float(f.get("duration_s", 5.0))
            count = int(f.get("count", 1))

            def fire_stall():
                for r in self._pick_replicas(count, role, alive_only=True):
                    r.stall(dur)
                    self.counters["heartbeat_stalls"] += 1

            self.loop.call_at(at_s, fire_stall)
        elif kind == "handoff_storm":
            # Handoff-mid-kill: kill prefill/decode replicas while
            # records are in flight — exports die unsent (lease rot →
            # redelivery) and adopted records die with their importer
            # (handoff lease rot → re-prefill).
            count = int(f.get("count", 2))
            respawn = float(f.get("respawn_after_s", 2.0))

            def fire_storm():
                pool = [
                    r for r in self.replicas
                    if r.alive and r.role in ("prefill", "decode")
                ]
                for r in self.rng.sample(pool, min(count, len(pool))):
                    r.kill(respawn_after_s=respawn)

            self.loop.call_at(at_s, fire_storm)
        elif kind == "controller_crash":
            # Kill the fleet controller. Default: it simply stops ticking
            # (a true crash) and a BRAND NEW instance restarts after
            # ``restart_after_s`` (None = never), reconciling from the
            # registry. ``zombie: true`` keeps the dead controller
            # ticking alongside its successor — a partitioned leader
            # that still thinks it leads — so every actuation it plans
            # must die at the epoch fence.
            restart_after = f.get("restart_after_s", 2.0)
            zombie = bool(f.get("zombie", False))

            def fire_crash():
                old = self.controller
                if old is None:
                    return
                self.counters["controller_crashes"] += 1
                if zombie:
                    self._zombie_controllers.append(old)
                self.controller = None
                self._wire_escalation()
                if restart_after is not None:
                    self.loop.call_after(
                        float(restart_after), self._restart_controller,
                    )

            self.loop.call_at(at_s, fire_crash)
        elif kind == "telemetry_stall":
            # Freeze the telemetry snapshot: reads keep returning the
            # last payload with its aging ``ts`` (or None if nothing was
            # ever sampled). The controller's staleness gate must hold
            # position for the whole window.
            dur = float(f.get("duration_s", 5.0))

            def fire_tstall():
                self._telemetry_stale_until = max(
                    self._telemetry_stale_until, self.clock.now + dur,
                )
                self.counters["telemetry_stalls"] += 1

            self.loop.call_at(at_s, fire_tstall)
        else:
            raise ValueError(f"unknown fault kind {kind!r}")

    # -- control plane + drain ------------------------------------------------

    def _control_tick(self) -> None:
        self.broker.reap_expired()
        if self.router is not None:
            self.router.check_failover()
        if self.ctrl is not None:
            self.ctrl.tick()
        if self.controller is not None:
            self.controller.tick(now=self.clock.now)
        for z in self._zombie_controllers:
            # A fenced zombie may tick forever; every action it plans
            # must be a no-op (asserted via its ``fenced`` counter).
            z.tick(now=self.clock.now)
        for r in self.replicas:
            if r.alive and r._idle and self.has_work(r):
                r.nudge()
        if (
            self._arrivals_done and self.checker.pending == 0
            and self._quiesced()
        ):
            self.loop.stop()
            return
        self.loop.call_after(self.tick_s, self._control_tick)

    def _quiesced(self) -> bool:
        """True when no replica holds any row.

        Even with every request terminal, a replica resuming from a
        partition or heartbeat stall may still hold rows whose leases
        were reaped and redelivered while it was away.  Its fence check
        drops them (releasing their KV blocks) on its next cycle —
        stopping the loop before that cycle runs would strand the
        charged blocks and misreport them as an accounting leak.
        """
        return all(
            not r.active and not r.pending
            and not r._to_finish and not r._to_export
            for r in self.replicas
        )

    # -- run ------------------------------------------------------------------

    def run(self) -> dict:
        was_tracing = trace.enabled()
        trace.set_enabled(False)
        try:
            with self.clock.installed():
                if self.ctrl is not None:
                    # Built on the REAL clock in __init__ — re-anchor its
                    # history epoch to virtual t=0 so transition ``at_s``
                    # stamps are virtual-time (deterministic) quantities.
                    self.ctrl._since = 0.0
                if self.controller is not None:
                    self.controller.start()
                    self._wire_escalation()
                for r in self.replicas:
                    r.start()
                self._install_faults()
                self._install_workload()
                self.loop.call_after(self.tick_s, self._control_tick)
                self.loop.run(until_s=self.duration_s)
                self.checker.assert_ok(self.broker)
        finally:
            trace.set_enabled(was_tracing)
        return self._report()

    def _report(self) -> dict:
        ttft = sorted(self._ttft)
        e2e = sorted(self._e2e)
        span = self._end_t or self.clock.now
        stats = self.checker.stats()
        delivery = self.broker.delivery_stats()
        out = {
            "scenario": self.name,
            "format": SCENARIO_FORMAT,
            "seed": self.seed,
            "virtual_s": round(self.clock.now, 6),
            "requests": {
                "submitted": self.counters["submitted"],
                **stats,
            },
            "latency_ms": {
                "ttft_p50": round(_percentile(ttft, 0.50) * 1e3, 6),
                "ttft_p95": round(_percentile(ttft, 0.95) * 1e3, 6),
                "ttft_p99": round(_percentile(ttft, 0.99) * 1e3, 6),
                "e2e_p50": round(_percentile(e2e, 0.50) * 1e3, 6),
                "e2e_p95": round(_percentile(e2e, 0.95) * 1e3, 6),
            },
            "throughput": {
                "tokens_out": self._tokens_out,
                "tokens_per_s": round(self._tokens_out / span, 6)
                if span > 0 else 0.0,
                "requests_per_s": round(self._done / span, 6)
                if span > 0 else 0.0,
            },
            "faults": {
                k: self.counters[k] for k in sorted(self.counters)
                if k not in ("submitted", "shed")
            },
            "delivery": {
                k: delivery[k] for k in sorted(delivery)
                if isinstance(delivery[k], (int, float))
            },
            "brownout": (
                self.ctrl.state()["state"] if self.ctrl is not None else None
            ),
            "invariants": {
                "checked": True,
                "violations": 0,
                "pending_at_drain": self.checker.pending,
            },
            "cost_model": self.cost.describe(),
        }
        if self.tier_store is not None:
            c = self.counters
            attaches = (
                c["prefix_hits"] + c["prefix_tier_hits"] + c["prefix_misses"]
            )
            out["kv_tiers"] = {
                **self.tier_store.stats(),
                "prefix_hits_local": c["prefix_hits"],
                "prefix_hits_tier": c["prefix_tier_hits"],
                "prefix_misses": c["prefix_misses"],
                # Hit rate counting BOTH tiers as hits — the fleet-wide
                # number the tiering bench compares against the
                # per-worker-LRU baseline's local-only rate.
                "fleet_prefix_hit_rate": round(
                    (c["prefix_hits"] + c["prefix_tier_hits"]) / attaches, 6,
                ) if attaches else None,
                "tier_demotes": c["tier_demotes"],
                "sessions_parked": c["sessions_parked"],
                "sessions_resumed": c["sessions_resumed"],
                "reprefill_tokens_avoided": c["reprefill_tokens_avoided"],
            }
        if self.per_class:
            slo_targets = (self.spec.get("metrics") or {}).get(
                "ttft_slo_s"
            ) or {}
            out["classes"] = {
                cls: {
                    "offered": self._cls_offered[cls],
                    "completed": self._cls_done[cls],
                    "shed": self._cls_shed[cls],
                    "ttft_p50_ms": round(_percentile(
                        sorted(self._cls_ttft[cls]), 0.50) * 1e3, 6),
                    "ttft_p95_ms": round(_percentile(
                        sorted(self._cls_ttft[cls]), 0.95) * 1e3, 6),
                    "ttft_p99_ms": round(_percentile(
                        sorted(self._cls_ttft[cls]), 0.99) * 1e3, 6),
                }
                for cls in sorted(self._cls_offered)
            }
            # Per-class TTFT SLO attainment (metrics.ttft_slo_s targets):
            # fraction of completed requests under the class's target —
            # the equal-or-better bar the autoscale bench holds both
            # arms to.
            for cls, entry in out["classes"].items():
                t = slo_targets.get(cls)
                if t is None:
                    continue
                vals = self._cls_ttft[cls]
                entry["ttft_attainment"] = (
                    round(sum(1 for v in vals if v <= float(t)) / len(vals), 6)
                    if vals else None
                )
        if self._ctrl_cfg is not None:
            now = self.clock.now
            fenced = sum(
                z.counters["fenced"] for z in self._zombie_controllers
            )
            out["fleet"] = {
                "replicas_end": sum(1 for r in self.replicas if r.alive),
                "peak_alive": self._peak_alive,
                "replica_seconds": round(
                    sum(r.alive_seconds(now) for r in self.replicas), 6,
                ),
                "spawns": self.counters["ctrl_spawns"],
                "retires": self.counters["ctrl_retires"],
                "zombie_fenced": fenced,
                "controller": (
                    self.controller.state()
                    if self.controller is not None else None
                ),
                "brownout": (
                    self.ctrl.state() if self.ctrl is not None else None
                ),
            }
        return out


def run_scenario(spec_or_path, *, n_requests: int | None = None,
                 duration_s: float | None = None,
                 seed: int | None = None) -> dict:
    """Load (if given a path), run, invariant-check, and report."""
    spec = (
        load_scenario(spec_or_path)
        if isinstance(spec_or_path, str) else spec_or_path
    )
    sim = FleetSim(
        spec, n_requests=n_requests, duration_s=duration_s, seed=seed,
    )
    return sim.run()
