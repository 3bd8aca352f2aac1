"""FleetSim: a serving fleet as a discrete-event system.

The simulator wires the REAL control-plane code — the
:class:`~dynamo_tpu.planner.planner.Planner` (driven mode, virtual
clock) and the REAL :class:`~dynamo_tpu.http.admission.AdmissionController`
(token bucket on the virtual clock) — to modeled workers
(:mod:`dynamo_tpu.sim.worker`), a prefill server pool, and a
:class:`~dynamo_tpu.sim.faults.SimFaultDriver` interpreting PR-5
FaultPlans at simulated timestamps. Scaling policy, admission limits,
the degradation ladder, and self-healing reconciliation thereby become
tier-1-testable artifacts: ≥100k requests replay in seconds, and two
runs at the same seed are bit-identical.

Request lifecycle::

    arrival ──http.request faults──> admission (429?) ──> prefill pool
        ──> decode placement (slots + KV blocks; least-loaded)
        ──> analytic finish at output_tokens × itl(occupancy)
        ──> SLO scoring (TTFT + ITL vs targets) → rolling window

The one modeling approximation: a request keeps the inter-token latency
of the occupancy it was admitted into (no per-token re-evaluation) —
cheap enough for million-request what-ifs, load-sensitive enough that
fleet sizing moves attainment the way the bench data says it should.
"""

from __future__ import annotations

import random
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Optional

from dynamo_tpu.faults.plan import FaultPlan
from dynamo_tpu.http.admission import AdmissionConfig, AdmissionController
from dynamo_tpu.planner.degradation import LadderPolicy
from dynamo_tpu.planner.planner import Planner, PlannerConfig
from dynamo_tpu.sim.core import SimClock, SimLoop, drive
from dynamo_tpu.sim.faults import SimFaultDriver
from dynamo_tpu.sim.traces import SimRequest
from dynamo_tpu.sim.worker import SimWorker, WorkerProfile


@dataclass
class SimConfig:
    initial_decode: int = 2
    initial_prefill: int = 1
    # SLO targets every finished request is scored against
    slo_ttft_ms: float = 2000.0
    slo_itl_ms: float = 60.0
    slo_window: int = 512
    heartbeat_interval_s: float = 1.0
    metric_interval_s: float = 5.0
    drain_s: float = 120.0
    # admission (level-0 baseline; the degradation ladder tightens it)
    max_queue_depth: int = 400
    max_kv_usage: float = 0.98
    retry_after_s: float = 1.0
    probe_rate_per_s: float = 1.0
    probe_burst: float = 2.0
    spec_enabled: bool = True
    # mid-stream migration (docs/robustness.md "Mid-stream migration"):
    # on by default to match the live routers — a worker kill re-queues
    # its in-flight streams as resumes (re-prefill of prompt+emitted,
    # then the remaining tokens) instead of scoring them lost. Resumes
    # bypass admission, exactly like the live plane. False restores the
    # PR-5 every-death-is-lost behavior.
    migration: bool = True
    # fraction of resumes landing on a cache-hot target (fleet-wide
    # prefix reuse / a prior placement of the same prefix): those pay
    # the cheap onboard rate instead of a full re-prefill. Drawn from a
    # per-resume seeded stream so replays stay bit-identical.
    resume_cache_hot_frac: float = 0.0
    # graceful drain (docs/robustness.md "Graceful drain & rolling
    # restarts"): a worker.drain fault hands every active stream off at
    # a step boundary — zero lost finish to synthesize, and because the
    # departing worker pre-publishes its KV catalog entries the resume
    # pays only the handoff latency plus an onboard-rate re-prefill
    # (vs a kill's full recompute). drain_proactive additionally routes
    # planner scale-downs through the migrating drain instead of the
    # stop-admitting-and-wait removal (off by default so existing
    # seeded runs stay bit-identical).
    drain_handoff_s: float = 0.05
    drain_proactive: bool = False
    # reactive-path detection latency: a KILLED worker's streams are
    # only re-dispatched once the router notices the death (stream
    # error + failover backoff) — the asymmetry the drain protocol
    # removes. 0 (default) keeps the pre-drain instantaneous-requeue
    # model, so existing seeded runs stay bit-identical.
    kill_detect_s: float = 0.0
    # injected stalls multiply decode latency by this until they lapse
    stall_factor: float = 4.0
    # ladder tightening: level>=1 scales the admission caps, level 3
    # clamps the queue to a shallow shed line
    degrade_queue_factor: float = 0.5
    degrade_kv_factor: float = 0.95
    shed_queue_depth: int = 32
    # fleet KV fabric (kvbm/fabric.py) modeled at prefix-family
    # granularity: the first prefill of a prefix_id publishes it to the
    # fleet catalog (G2 somewhere in the fleet); later requests of the
    # family fetch the shared head at the fabric rate instead of
    # recomputing it. Watermark pressure demotes least-popular families
    # — hot ones to the shared bucket (slower fetch, survives), cold
    # ones out of the fabric entirely (a fleet-wide miss; their home is
    # a single worker's private disk). The planner's "demote cold KV"
    # rung scales fabric_host_prefixes via LadderPolicy.
    fabric: bool = False
    fabric_host_prefixes: int = 6  # G2 capacity, in prefix families
    fabric_hot_min_hits: int = 2
    # fetch rates: peer host tier ≫ shared bucket, both ≫ the 20k tok/s
    # prefill recompute they replace, both ≪ the 200k tok/s local onboard
    fabric_peer_fetch_tok_s: float = 60_000.0
    fabric_bucket_fetch_tok_s: float = 30_000.0
    worker: WorkerProfile = field(default_factory=WorkerProfile)


@dataclass
class _InFlight:
    req: SimRequest
    frontend_delay: float = 0.0
    worker: int = -1
    ttft: float = 0.0
    itl: float = 0.0
    # mid-stream migration state: tokens delivered before the last
    # worker death, how many times this stream resumed, whether the
    # current resume found a cache-hot target, and when the current
    # decode segment started emitting
    emitted: int = 0
    resumed_n: int = 0
    resume_hot: bool = False
    decode_start_t: float = 0.0


class SimConnector:
    """The planner's connector, backed by the simulated fleet. Decode
    adds honor the worker profile's provisioning delay (the ack is
    immediate, capacity arrives ``spawn_delay_s`` later — exactly the
    window reconciliation must not mistake for a second loss)."""

    def __init__(self, fleet: "FleetSim"):
        self.fleet = fleet

    async def add_component(self, component: str) -> bool:
        f = self.fleet
        if component == f.prefill_component:
            f.prefill_servers += 1
            f._drain_prefill()
            return True
        f.pending_spawns += 1
        f.loop.after(f.config.worker.spawn_delay_s, f._spawn_worker)
        return True

    async def remove_component(self, component: str) -> bool:
        f = self.fleet
        if component == f.prefill_component:
            if f.prefill_servers <= 0:
                return False
            f.prefill_servers -= 1
            return True
        # drain the least-loaded worker (ties: newest first)
        candidates = [
            w for w in f.workers.values() if not w.draining
        ]
        if not candidates:
            return False
        victim = min(candidates, key=lambda w: (w.occupancy, -w.wid))
        victim.draining = True
        if victim.occupancy == 0:
            f._remove_worker(victim.wid)
        return True

    async def drain_component(self, component: str) -> bool:
        """The planner's graceful scale-down. With ``drain_proactive``
        the victim migrates its active streams through the drain
        protocol (zero lost tokens, onboard-rate resumes); off (the
        default) it falls back to remove_component's stop-admitting-
        and-wait behavior so existing seeded runs stay bit-identical."""
        f = self.fleet
        if component == f.prefill_component or not f.config.drain_proactive:
            return await self.remove_component(component)
        candidates = [w for w in f.workers.values() if not w.draining]
        if not candidates:
            return False
        victim = min(candidates, key=lambda w: (w.occupancy, -w.wid))
        f._drain_worker(victim.wid)
        return True


class FleetSim:
    def __init__(
        self,
        trace: list[SimRequest],
        config: Optional[SimConfig] = None,
        plan: Optional[FaultPlan] = None,
    ):
        self.config = config or SimConfig()
        self.trace = trace
        self.loop = SimLoop()
        self.clock = SimClock(self.loop)
        self.faults = SimFaultDriver(plan)
        self.workers: dict[int, SimWorker] = {}
        self._next_wid = 0
        self.pending_spawns = 0
        self.prefill_servers = self.config.initial_prefill
        self.prefill_component = "prefill"
        self._prefill_busy = 0
        self._prefill_queue: deque[_InFlight] = deque()
        self._decode_queue: deque[_InFlight] = deque()
        self._inflight: dict[int, _InFlight] = {}
        self._base_admission = AdmissionConfig(
            max_queue_depth=self.config.max_queue_depth,
            max_kv_usage=self.config.max_kv_usage,
            retry_after_s=self.config.retry_after_s,
            probe_rate_per_s=self.config.probe_rate_per_s,
            probe_burst=self.config.probe_burst,
        )
        self.admission = AdmissionController(
            AdmissionConfig(**vars(self._base_admission)),
            load_fn=self._load_snapshot,
            clock=self.clock.monotonic,
        )
        self.spec_enabled = self.config.spec_enabled
        self.ladder = LadderPolicy(
            queue_factor=self.config.degrade_queue_factor,
            kv_factor=self.config.degrade_kv_factor,
            shed_queue_depth=self.config.shed_queue_depth,
        )
        self.planner: Optional[Planner] = None
        # scoreboard
        self._outcomes: deque = deque(maxlen=max(1, self.config.slo_window))
        self.arrived = 0
        self.shed = 0
        self.failed_frontend = 0
        self.killed_inflight = 0  # in-flight streams hit by a kill
        self.resumed = 0          # of those, mid-stream (≥1 token) resumes
        self.resumed_hot = 0      # resumes onto a cache-hot target
        self.refailed = 0         # pre-first-token kills replayed as failover
        self.lost_inflight = 0    # of those, dropped (migration off)
        self.completed = 0
        self.met = 0
        self.goodput_tokens = 0
        self.workers_killed = 0
        self.workers_drained = 0  # planned departures (drain protocol)
        self.drained_inflight = 0  # streams handed off by drains
        self.workers_spawned = 0
        self.step_errors = 0
        self.degradation_level = 0
        # fleet KV fabric scoreboard (prefix_id -> tier/hits/last_touch)
        self._fabric: dict[int, dict[str, Any]] = {}
        self._fabric_scale = 1.0
        self.prefix_requests = 0         # prefill passes carrying a prefix
        self.fleet_hits_host = 0
        self.fleet_hits_bucket = 0
        self.fleet_publishes = 0
        self.fleet_demoted_bucket = 0
        self.fleet_demoted_dropped = 0
        self.fleet_fetched_tokens = 0
        self.reprefill_tokens_avoided = 0
        self.prefilled_tokens = 0        # tokens recomputed at prefill rate
        self.timeline: list[dict[str, Any]] = []
        self.horizon = (trace[-1].t if trace else 0.0) + self.config.drain_s
        self._next_adjust_t = 0.0

    # -- public API ---------------------------------------------------------

    def attach_planner(self, pconfig: Optional[PlannerConfig] = None) -> Planner:
        """Create the driven-mode Planner wired to this fleet: sim
        clock, sim connector, fleet degradation hooks. Planner intent
        starts at the fleet's initial sizes."""
        self.planner = Planner(
            store=None,
            component=None,
            connector=SimConnector(self),
            config=pconfig,
            decode_workers=self.config.initial_decode,
            prefill_workers=self.config.initial_prefill,
            clock=self.clock,
            degradation=self,
        )
        self.prefill_component = self.planner.config.prefill_component
        self._next_adjust_t = self.planner.config.adjustment_interval_s
        return self.planner

    def run(self) -> dict[str, Any]:
        for _ in range(self.config.initial_decode):
            self._spawn_worker(initial=True)
        if self.trace:
            self.loop.at(self.trace[0].t, self._on_arrival, 0)
        self.loop.after(self.config.heartbeat_interval_s, self._heartbeat)
        self.loop.after(self.config.metric_interval_s, self._metric_tick)
        # recurring chains self-terminate past the horizon; whatever
        # remains afterwards is finish events — drain them all
        self.loop.run()
        return self.result()

    # -- degradation ladder (planner DegradationHooks) ----------------------

    def set_level(self, level: int) -> None:
        """Apply a planner rung through the SAME LadderPolicy math live
        serving uses (planner/degradation.py): level 1+ tightens
        admission so queued work stays meetable, level 2+ gives KV back
        by turning draft staging off, level 3 clamps to the shed line."""
        self.degradation_level = level
        cfg = self.admission.config
        base = self._base_admission
        cfg.max_queue_depth, cfg.max_kv_usage = self.ladder.admission_caps(
            base.max_queue_depth, base.max_kv_usage, level
        )
        self.spec_enabled = self.ladder.spec_enabled(
            self.config.spec_enabled, level
        )
        # the "demote cold KV" rung: tighten the fabric's G2 watermark
        # through the SAME LadderPolicy math ServingDegradation applies
        # to a live FleetKvFabric, and demote immediately
        self._fabric_scale = self.ladder.fabric_pressure_scale(level)
        if self.config.fabric:
            self._fabric_enforce()

    # -- load + snapshots ---------------------------------------------------

    def _load_snapshot(self):
        from dynamo_tpu.http.admission import LoadSnapshot

        alive = list(self.workers.values())
        kv = (
            sum(w.kv_usage for w in alive) / len(alive) if alive else 0.0
        )
        return LoadSnapshot(
            queue_depth=len(self._prefill_queue) + len(self._decode_queue),
            active_slots=sum(w.occupancy for w in alive),
            total_slots=sum(w.profile.batch_slots for w in alive),
            kv_usage=kv,
        )

    @property
    def attainment(self) -> float:
        if not self._outcomes:
            return 1.0
        return sum(self._outcomes) / len(self._outcomes)

    def snapshot(self) -> dict[str, float]:
        """The planner-facing view — same keys as Planner.collect().
        ``decode_workers_reporting`` counts only alive workers, exactly
        like the live plane (a provisioning pod publishes no metrics
        until the model is loaded), so the planner's spawn-grace credits
        are genuinely exercised: a replacement it just ordered stays
        invisible for ``spawn_delay_s`` and must not be mistaken for a
        second loss."""
        alive = list(self.workers.values())
        kv = (
            sum(w.kv_usage for w in alive) / len(alive) if alive else 1.0
        )
        depth = float(len(self._prefill_queue))
        return {
            "kv_load_mean": kv,
            "decode_workers_reporting": float(len(self.workers)),
            "prefill_queue_depth": depth,
            "prefill_queue_per_worker": depth / max(1, self.prefill_capacity),
            "slo_attainment_mean": self.attainment,
            "goodput_tokens_total": float(self.goodput_tokens),
            "degradation_level": float(self.degradation_level),
            "ts": self.clock.time(),
        }

    # -- workers ------------------------------------------------------------

    def _spawn_worker(self, initial: bool = False) -> None:
        if not initial:
            self.pending_spawns = max(0, self.pending_spawns - 1)
        wid = self._next_wid
        self._next_wid += 1
        self.workers[wid] = SimWorker(wid, self.config.worker)
        self.workers_spawned += 1
        self._drain_decode()

    def _remove_worker(self, wid: int) -> None:
        self.workers.pop(wid, None)

    def _kill_worker(self, wid: int) -> None:
        w = self.workers.pop(wid, None)
        if w is None:
            return
        self.workers_killed += 1
        now = self.loop.now
        requeued = False
        for rid in list(w.active):
            rec = self._inflight.get(rid)
            if rec is None:
                continue
            self.killed_inflight += 1
            if not self.config.migration:
                # PR-5 behavior: the stream is gone — a hard SLO miss,
                # scored so attainment feels the outage
                self._inflight.pop(rid, None)
                self.lost_inflight += 1
                self._outcomes.append(False)
                continue
            # mid-stream migration (mirrors the live routers): tokens
            # already delivered stay delivered; the request re-prefills
            # prompt+emitted elsewhere (cheap onboard when the target is
            # cache-hot) and decodes the remainder. The migration gap
            # lands in the stream's mean ITL at finish time. Resumes
            # re-enter the prefill queue directly — they already paid
            # for admission, exactly like the live bypass.
            seg = 0
            if rec.itl > 0 and now > rec.decode_start_t:
                seg = int((now - rec.decode_start_t) / rec.itl)
            remaining_before = rec.req.output_tokens - rec.emitted
            rec.emitted += max(0, min(seg, remaining_before - 1))
            if rec.emitted > 0:
                # a true mid-stream resume — books like the live
                # plane's dynamo_midstream_resumes_total{ok}
                rec.resumed_n += 1
                self.resumed += 1
            else:
                # the kill landed before this request's FIRST token —
                # the live plane replays it from scratch
                # (pre-first-token failover, FAILOVER_RETRIES), so
                # resumed_n stays 0, the re-placement recomputes its
                # TTFT, and it is NOT counted as a resume
                self.refailed += 1
            rec.worker = -1  # invalidates the pending finish event
            if rec.emitted > 0:
                rng = random.Random(f"resume:{rid}:{rec.resumed_n}")
                rec.resume_hot = (
                    rng.random() < self.config.resume_cache_hot_frac
                )
                if rec.resume_hot:
                    self.resumed_hot += 1
            else:
                # failover replays pay a full re-prefill, like live
                rec.resume_hot = False
            if self.config.kill_detect_s > 0:
                self.loop.after(
                    self.config.kill_detect_s, self._requeue_resume, rec
                )
            else:
                self._prefill_queue.append(rec)
                requeued = True
        if requeued:
            self._drain_prefill()

    def _drain_worker(self, wid: int) -> None:
        """Graceful counterpart of ``_kill_worker``: the worker hands
        every active stream off at a step boundary. Delivered tokens
        stay delivered (same commit-log math as a kill, but nothing to
        synthesize), and because the departing worker pre-publishes its
        KV catalog entries the resume always rides the onboard rate —
        the kill path's full recompute is exactly the cost this
        protocol exists to avoid. Each resume re-enters prefill after
        ``drain_handoff_s`` (flag publish + MIGRATE + re-dispatch)."""
        w = self.workers.pop(wid, None)
        if w is None:
            return
        self.workers_drained += 1
        now = self.loop.now
        for rid in list(w.active):
            rec = self._inflight.get(rid)
            if rec is None:
                continue
            self.drained_inflight += 1
            seg = 0
            if rec.itl > 0 and now > rec.decode_start_t:
                seg = int((now - rec.decode_start_t) / rec.itl)
            remaining_before = rec.req.output_tokens - rec.emitted
            rec.emitted += max(0, min(seg, remaining_before - 1))
            if rec.emitted > 0:
                rec.resumed_n += 1
                self.resumed += 1
                self.resumed_hot += 1
            else:
                # drained before the first token: replayed from scratch
                # (TTFT recomputes), like the live pre-first-token path
                self.refailed += 1
            rec.worker = -1  # invalidates the pending finish event
            rec.resume_hot = True
            self.loop.after(
                self.config.drain_handoff_s, self._requeue_resume, rec
            )

    def _requeue_resume(self, rec: _InFlight) -> None:
        if rec.req.rid not in self._inflight:
            return
        self._prefill_queue.append(rec)
        self._drain_prefill()

    # -- request lifecycle --------------------------------------------------

    def _on_arrival(self, index: int) -> None:
        req = self.trace[index]
        if index + 1 < len(self.trace):
            self.loop.at(self.trace[index + 1].t, self._on_arrival, index + 1)
        self.arrived += 1
        frontend_delay = 0.0
        for rule in self.faults.due(
            self.loop.now, "http.request", rid=f"sim-{req.rid}"
        ):
            if rule.kind in ("error", "drop"):
                self.failed_frontend += 1
                return
            if rule.kind in ("delay", "stall"):
                frontend_delay += rule.delay_s
        if self.admission.check() is not None:
            self.shed += 1
            # sheds are SLO misses in the rolling window (mirrors the
            # live AdmissionController's on_shed -> SloTracker.note_shed):
            # scoring only admitted traffic would let the planner read
            # ~1.0 attainment while the frontend 429s the overload away,
            # and the SLO-breach scale-up would never fire
            self._outcomes.append(False)
            return
        rec = _InFlight(req=req, frontend_delay=frontend_delay)
        self._inflight[req.rid] = rec
        self._prefill_queue.append(rec)
        self._drain_prefill()

    @property
    def prefill_capacity(self) -> int:
        """Concurrent prefills: the dedicated pool, or — at zero prefill
        workers (aggregated mode) — the decode workers prefill locally."""
        return self.prefill_servers or max(1, len(self.workers))

    def _drain_prefill(self) -> None:
        while self._prefill_queue and self._prefill_busy < self.prefill_capacity:
            rec = self._prefill_queue.popleft()
            self._prefill_busy += 1
            # the frontend fault delay applies once (the first pass);
            # resumes re-prefill prompt + delivered tokens, at onboard
            # speed when the placement is cache-hot
            delay, rec.frontend_delay = rec.frontend_delay, 0.0
            self.loop.after(
                self._prefill_duration(rec) + delay,
                self._on_prefill_done, rec,
            )

    def _prefill_duration(self, rec: _InFlight) -> float:
        """Seconds this prefill pass occupies a prefill slot, split
        between fabric fetch (the shared head, when the fleet catalog
        hits) and recompute (everything else). Also the fabric's
        publish/touch point — this is where a live KVBM's pump lands
        blocks in G2 and prefetch pulls them from peers."""
        w = self.config.worker
        tokens = rec.req.prompt_tokens + rec.emitted
        if rec.resume_hot:
            # cache-hot resume: the whole re-prefill rides the local
            # onboard path (no recompute, no fabric round trip)
            return tokens / w.onboard_tok_s
        if not self.config.fabric or rec.req.prefix_id < 0:
            self.prefilled_tokens += tokens
            return tokens / w.prefill_tok_s
        pid = rec.req.prefix_id
        ptoks = min(rec.req.prefix_tokens, tokens)
        self.prefix_requests += 1
        now = self.loop.now
        entry = self._fabric.get(pid)
        if entry is None:
            # first sighting fleet-wide: pay the full prefill once, then
            # publish the family to the catalog (G2 on this placement)
            self._fabric[pid] = {"tier": "host", "hits": 1, "last": now}
            self.fleet_publishes += 1
            self._fabric_enforce()
            self.prefilled_tokens += tokens
            return tokens / w.prefill_tok_s
        entry["hits"] += 1
        entry["last"] = now
        if entry["tier"] == "host":
            self.fleet_hits_host += 1
            fetch_rate = self.config.fabric_peer_fetch_tok_s
        else:
            self.fleet_hits_bucket += 1
            fetch_rate = self.config.fabric_bucket_fetch_tok_s
            # a bucket hit promotes the family back into G2 (the live
            # onboard inserts fetched blocks into the host tier)
            entry["tier"] = "host"
            self._fabric_enforce()
        self.fleet_fetched_tokens += ptoks
        self.reprefill_tokens_avoided += ptoks
        rest = tokens - ptoks
        self.prefilled_tokens += rest
        return ptoks / fetch_rate + rest / w.prefill_tok_s

    def _fabric_enforce(self) -> None:
        """Watermark pressure at prefix-family granularity: when more
        families sit in G2 than the (ladder-scaled) capacity, demote
        popularity-weighted victims — least-hit first, stalest breaking
        ties. Hot families go to the shared bucket (still fleet-
        fetchable, slower); cold ones leave the fabric (their only copy
        is one worker's private disk — a fleet-wide miss)."""
        cap = max(1, int(self.config.fabric_host_prefixes
                         * self._fabric_scale))
        host = [(pid, e) for pid, e in self._fabric.items()
                if e["tier"] == "host"]
        excess = len(host) - cap
        if excess <= 0:
            return
        host.sort(key=lambda pe: (pe[1]["hits"], pe[1]["last"], pe[0]))
        for pid, e in host[:excess]:
            if e["hits"] >= self.config.fabric_hot_min_hits:
                e["tier"] = "bucket"
                self.fleet_demoted_bucket += 1
            else:
                del self._fabric[pid]
                self.fleet_demoted_dropped += 1

    def _on_prefill_done(self, rec: _InFlight) -> None:
        self._prefill_busy = max(0, self._prefill_busy - 1)
        self._drain_prefill()
        if rec.req.rid not in self._inflight:
            return  # lost to a kill while prefilling (worker-agnostic)
        if not self._try_place(rec):
            self._decode_queue.append(rec)

    def _try_place(self, rec: _InFlight) -> bool:
        blocks = self.config.worker.blocks_for(
            rec.req.prompt_tokens, rec.req.output_tokens, self.spec_enabled
        )
        candidates = [
            w for w in self.workers.values() if w.can_admit(blocks)
        ]
        if not candidates:
            return False
        worker = min(candidates, key=lambda w: (w.kv_usage, w.occupancy, w.wid))
        worker.admit(rec.req.rid, blocks)
        now = self.loop.now
        rec.worker = worker.wid
        if rec.resumed_n == 0:
            # a resume's first token already streamed before the kill:
            # its TTFT stands; only the original placement sets it
            rec.ttft = now - rec.req.t + self.config.worker.first_step_s
        rec.itl = worker.itl_s(now, self.spec_enabled)
        rec.decode_start_t = now + self.config.worker.first_step_s
        remaining = rec.req.output_tokens - rec.emitted
        self.loop.after(
            self.config.worker.first_step_s + remaining * rec.itl,
            self._on_finish, rec.req.rid, worker.wid,
        )
        return True

    def _on_finish(self, rid: int, wid: int) -> None:
        # get-then-pop: a STALE finish event (superseded by a kill that
        # migrated this request elsewhere) must not evict the live
        # record — only the finish from the request's current worker
        # consumes it
        rec = self._inflight.get(rid)
        if rec is None or rec.worker != wid:
            return  # superseded by a kill
        self._inflight.pop(rid, None)
        worker = self.workers.get(wid)
        if worker is not None and rid in worker.active:
            worker.release(rid)
            if worker.draining and worker.occupancy == 0:
                self._remove_worker(wid)
        itl = rec.itl
        if rec.resumed_n:
            # the migration gap (re-prefill + queue wait) lands in the
            # stream's mean inter-token latency, exactly as the live
            # SLO tracker (mean decode ITL) would observe it
            first_token_t = rec.req.t + rec.ttft
            itl = (self.loop.now - first_token_t) / max(
                1, rec.req.output_tokens
            )
        met = (
            rec.ttft * 1e3 <= self.config.slo_ttft_ms
            and itl * 1e3 <= self.config.slo_itl_ms
        )
        self._outcomes.append(met)
        self.completed += 1
        if met:
            self.met += 1
            self.goodput_tokens += rec.req.output_tokens
        self._drain_decode()

    def _drain_decode(self) -> None:
        while self._decode_queue:
            if not self._try_place(self._decode_queue[0]):
                return
            self._decode_queue.popleft()

    # -- recurring chains ---------------------------------------------------

    def _heartbeat(self) -> None:
        now = self.loop.now
        for wid in sorted(self.workers):
            worker = self.workers.get(wid)
            if worker is None:
                continue
            for rule in self.faults.due(now, "engine.step", worker=f"w{wid}"):
                if rule.kind in ("stall", "delay"):
                    worker.slow_until = now + rule.delay_s
                    worker.slow_factor = self.config.stall_factor
                elif rule.kind == "error":
                    self.step_errors += 1  # quarantine absorbs it
            for rule in self.faults.due(
                now, "worker.liveness", worker=f"w{wid}"
            ):
                if rule.kind == "kill":
                    self._kill_worker(wid)
            # planned departure: any rule at worker.drain runs the
            # graceful protocol on this worker (the kill-vs-drain A/B
            # fires the same schedule at both points and diffs the dip)
            for rule in self.faults.due(now, "worker.drain", worker=f"w{wid}"):
                self._drain_worker(wid)
        if now + self.config.heartbeat_interval_s <= self.horizon:
            self.loop.after(self.config.heartbeat_interval_s, self._heartbeat)

    def _metric_tick(self) -> None:
        snap = self.snapshot()
        self.timeline.append(snap)
        if self.planner is not None and self.loop.now >= self._next_adjust_t:
            drive(self.planner.make_adjustments(snap))
            self._next_adjust_t = (
                self.loop.now + self.planner.config.adjustment_interval_s
            )
        if self.loop.now + self.config.metric_interval_s <= self.horizon:
            self.loop.after(self.config.metric_interval_s, self._metric_tick)

    # -- results ------------------------------------------------------------

    def result(self) -> dict[str, Any]:
        # _inflight spans arrival -> finish/kill, so prefill- and
        # decode-queued requests are already in it; adding queue lengths
        # would double-count anything still queued at sim end
        unfinished = len(self._inflight)
        return {
            "requests": self.arrived,
            "completed": self.completed,
            "met": self.met,
            "shed": self.shed,
            "failed_frontend": self.failed_frontend,
            "killed_inflight": self.killed_inflight,
            "resumed": self.resumed,
            "resumed_hot": self.resumed_hot,
            "refailed": self.refailed,
            "lost_inflight": self.lost_inflight,
            "unfinished": unfinished,
            # of ADMITTED work (the Tail-at-Scale contract: what you
            # accept, you serve well)
            "slo_attainment": (
                self.met / self.completed if self.completed else 1.0
            ),
            # of OFFERED load: shed, frontend-failed, and killed
            # requests all count as misses, so a policy cannot score
            # 1.0 by rejecting the traffic
            "slo_attainment_offered": (
                self.met / self.arrived if self.arrived else 1.0
            ),
            "final_window_attainment": self.attainment,
            "goodput_tokens": self.goodput_tokens,
            "goodput_tok_s": self.goodput_tokens / max(1e-9, self.loop.now),
            "workers_spawned": self.workers_spawned,
            "workers_killed": self.workers_killed,
            "workers_drained": self.workers_drained,
            "drained_inflight": self.drained_inflight,
            "step_errors": self.step_errors,
            "faults_fired": len(self.faults.fired),
            "degradation_level": self.degradation_level,
            "decode_workers_final": len(self.workers),
            "prefill_servers_final": self.prefill_servers,
            # fleet KV fabric A/B surface (tests/test_kv_fabric.py):
            # prefilled_tokens is the recompute bill — with the fabric
            # on, every fleet hit moves its shared head from this figure
            # into fleet_fetched_tokens
            "fabric": {
                "enabled": self.config.fabric,
                "prefix_requests": self.prefix_requests,
                "fleet_hits": self.fleet_hits_host + self.fleet_hits_bucket,
                "fleet_hits_host": self.fleet_hits_host,
                "fleet_hits_bucket": self.fleet_hits_bucket,
                "fleet_hit_rate": (
                    (self.fleet_hits_host + self.fleet_hits_bucket)
                    / self.prefix_requests if self.prefix_requests else 0.0
                ),
                "publishes": self.fleet_publishes,
                "demoted_bucket": self.fleet_demoted_bucket,
                "demoted_dropped": self.fleet_demoted_dropped,
                "fleet_fetched_tokens": self.fleet_fetched_tokens,
                "reprefill_tokens_avoided": self.reprefill_tokens_avoided,
                "prefilled_tokens": self.prefilled_tokens,
            },
            "planner": (
                {
                    "decode_intent": self.planner.decode_workers,
                    "prefill_intent": self.planner.prefill_workers,
                    "replacements": self.planner.replacements_total,
                    "degradation_level": self.planner.degradation_level,
                }
                if self.planner is not None
                else None
            ),
            "sim_end_s": self.loop.now,
            "timeline": self.timeline,
        }
