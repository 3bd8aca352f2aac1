"""KV-aware worker selection: metrics aggregation + cost function.

Analogue of the reference's scheduler (reference:
lib/llm/src/kv_router/scheduler.rs:88-337 — DefaultWorkerSelector:
``logit = 2*overlap − gpu_cache_usage − normalized_waiting``, random
tie-break; lib/llm/src/kv_router/{metrics_aggregator.rs,scoring.rs}).
"""

from __future__ import annotations

import asyncio
import logging
import random
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Optional

from dynamo_tpu.kv_router.indexer import KvIndexer, OverlapScores
from dynamo_tpu.kv_router.protocols import ForwardPassMetrics, KvHitRateEvent
from dynamo_tpu.tokens import hash_sequence

log = logging.getLogger("dynamo_tpu.kv_router.scheduler")

# selector: (overlaps, metrics by worker, candidate ids) -> worker id
Selector = Callable[[OverlapScores, dict[int, ForwardPassMetrics], list[int]], int]


def default_selector(
    overlaps: OverlapScores,
    metrics: dict[int, ForwardPassMetrics],
    candidates: list[int],
) -> int:
    """reference: scheduler.rs DefaultWorkerSelector."""
    max_waiting = max(
        (metrics[w].num_requests_waiting for w in candidates if w in metrics),
        default=0,
    )
    best_ids: list[int] = []
    best_logit = float("-inf")
    for wid in candidates:
        m = metrics.get(wid, ForwardPassMetrics(worker_id=wid))
        overlap = overlaps.scores.get(wid, 0)
        waiting_norm = (
            m.num_requests_waiting / max_waiting if max_waiting > 0 else 0.0
        )
        logit = 2.0 * overlap - m.gpu_cache_usage_perc - waiting_norm
        if logit > best_logit:
            best_logit, best_ids = logit, [wid]
        elif logit == best_logit:
            best_ids.append(wid)
    return random.choice(best_ids)


class KvMetricsAggregator:
    """Holds the latest ForwardPassMetrics per worker, fed by pub/sub
    (reference: metrics_aggregator.rs; transport differs — the reference
    scrapes NATS service stats, we subscribe to a metrics subject)."""

    def __init__(self, stale_after_s: float = 10.0):
        self.metrics: dict[int, ForwardPassMetrics] = {}
        self._updated: dict[int, float] = {}
        self.stale_after_s = stale_after_s
        self._task: Optional[asyncio.Task] = None

    def update(self, m: ForwardPassMetrics) -> None:
        self.metrics[m.worker_id] = m
        self._updated[m.worker_id] = time.monotonic()

    def last_update(self, worker_id: int) -> float:
        """monotonic timestamp of the worker's latest snapshot (0 = never)."""
        return self._updated.get(worker_id, 0.0)

    def fresh_metrics(self) -> dict[int, ForwardPassMetrics]:
        now = time.monotonic()
        return {
            w: m
            for w, m in self.metrics.items()
            if now - self._updated.get(w, 0) < self.stale_after_s
        }

    def remove_worker(self, worker_id: int) -> None:
        self.metrics.pop(worker_id, None)
        self._updated.pop(worker_id, None)

    def start_consuming(self, subscriber) -> None:
        async def pump() -> None:
            try:
                async for _subject, payload in subscriber:
                    try:
                        self.update(ForwardPassMetrics.model_validate(payload))
                    except Exception:
                        log.exception("bad metrics payload")
            except asyncio.CancelledError:
                raise
            except Exception:
                log.exception("metrics subscription died; snapshot is frozen")

        self._task = asyncio.get_running_loop().create_task(pump())

    async def close(self) -> None:
        if self._task is not None:
            self._task.cancel()


@dataclass
class SchedulingDecision:
    worker_id: int
    overlap_blocks: int
    total_blocks: int
    # the in-flight charge this decision placed (note_dispatch's return):
    # pass it back to note_done so completion releases THIS request's
    # charge, not some later request's (ADVICE r5: anonymous pops under
    # bursts released the wrong entry)
    dispatch_token: float = 0.0
    # leading blocks fetchable from the fleet KV fabric (peer host tier
    # or shared bucket) — 0 when no catalog is attached. Informational:
    # the logit already counted them at the discounted fetch weight.
    fleet_blocks: int = 0

    @property
    def prefix_hit_rate(self) -> float:
        return self.overlap_blocks / self.total_blocks if self.total_blocks else 0.0


class KvScheduler:
    """indexer + metrics + selector → routing decisions
    (reference: kv_router.rs KvRouter.schedule)."""

    def __init__(
        self,
        indexer: KvIndexer,
        aggregator: KvMetricsAggregator,
        selector: Selector = default_selector,
        on_hit_rate: Optional[Callable[[KvHitRateEvent], None]] = None,
        fleet_catalog: Optional[Any] = None,
    ):
        self.indexer = indexer
        self.aggregator = aggregator
        self.selector = selector
        self.on_hit_rate = on_hit_rate
        # fleet KV fabric catalog (kvbm/fabric.py FleetPrefixCatalog, or
        # anything with match_prefix(seq_hashes) -> int): blocks any
        # candidate can fetch from a peer's host tier / the shared
        # bucket instead of recomputing. Counted at fleet_hit_weight.
        self.fleet_catalog = fleet_catalog
        # optimistic in-flight accounting: published metrics lag by a
        # publish interval, so a BURST of concurrent no-overlap requests
        # would all see identical zero-load snapshots and (modulo the
        # random tie-break) pile onto few workers. Every schedule()
        # charges its decision as one waiting request, and the charge expires as
        # soon as the worker publishes a metrics snapshot NEWER than
        # the dispatch (the snapshot then reflects the request itself —
        # keeping the charge would double-count it for the whole
        # stream) or after a TTL backstop when no metrics flow at all.
        # Decision-only callers (the standalone `schedule` endpoint)
        # are covered because the charge lives here, not in the proxy.
        self.inflight: dict[int, list[float]] = {}
        self.inflight_ttl_s = 5.0

    def note_dispatch(self, worker_id: int) -> float:
        """Charge one in-flight dispatch; returns the charge's token
        (its monotonic timestamp). Keep it and hand it to note_done —
        an anonymous release under a burst would pop the OLDEST entry,
        i.e. release a later request's still-live charge."""
        token = time.monotonic()
        self.inflight.setdefault(worker_id, []).append(token)
        return token

    def note_done(self, worker_id: int, token: Optional[float] = None) -> None:
        """Optional early release (proxy paths that observe stream
        completion); expiry handles callers that never report back.
        ``token`` (note_dispatch's return) releases that SPECIFIC charge
        — a no-op if it already expired or was consumed by a newer
        metrics snapshot. token=None keeps the legacy oldest-entry pop
        for callers that didn't record one."""
        entries = self.inflight.get(worker_id)
        if not entries:
            return
        if token is None:
            entries.pop(0)
        else:
            try:
                entries.remove(token)
            except ValueError:
                return  # already expired / released by a fresher snapshot
        if not entries:
            self.inflight.pop(worker_id, None)

    def _active_inflight(self, worker_id: int) -> int:
        entries = self.inflight.get(worker_id)
        if not entries:
            return 0
        now = time.monotonic()
        seen_at = self.aggregator.last_update(worker_id)
        live = [
            t for t in entries
            if t > seen_at and now - t < self.inflight_ttl_s
        ]
        if live:
            self.inflight[worker_id] = live
        else:
            self.inflight.pop(worker_id, None)
        return len(live)

    # how much harder prefix overlap weighs for a migration resume: a
    # resume's token_ids carry the tokens already streamed, so a worker
    # holding that prefix turns the re-prefill into a cheap onboard —
    # worth crossing a load gradient for (docs/robustness.md
    # "Mid-stream migration"). Applied by scaling the overlap scores the
    # selector sees, so custom selectors keep their 3-arg signature.
    resume_overlap_boost: float = 2.0

    # discount for fleet-fetchable blocks in the overlap term: a fetch
    # from a peer's host tier / the shared bucket is far cheaper than
    # recompute but dearer than a local (G1/G2) hit. Fleet blocks count
    # for every candidate (any worker can fetch them), which NARROWS the
    # local-overlap worker's advantage to 2*(1-w)*blocks of logit — the
    # router stops thrash-pinning a loaded worker for a prefix the whole
    # fleet can onboard. Must stay < 1.0: a fleet hit must never score
    # at local weight, including under the resume boost (the boost
    # multiplies AFTER this discount, so a resume racing a demotion sees
    # boost*w*blocks, not boost*blocks).
    fleet_hit_weight: float = 0.35

    def _fleet_match(self, token_ids: list[int]) -> int:
        """Leading blocks fetchable from the fleet fabric (catalog
        membership only — no network). Never raises into routing."""
        if self.fleet_catalog is None:
            return 0
        try:
            _, seq_hashes = hash_sequence(
                list(token_ids), self.indexer.block_size
            )
            return int(self.fleet_catalog.match_prefix(seq_hashes))
        except Exception:
            log.exception("fleet catalog match failed; scoring local-only")
            return 0

    def schedule(
        self, token_ids: list[int], candidates: list[int],
        resume: bool = False,
        draining: Optional[set[int]] = None,
    ) -> SchedulingDecision:
        if not candidates:
            raise RuntimeError("no candidate workers")
        overlaps = self.indexer.find_matches_for_request(token_ids)
        true_overlaps = overlaps
        fleet_blocks = self._fleet_match(token_ids)
        if draining:
            # DRAINING workers never take fresh placement (defensive:
            # the router's candidate list already excludes them; fall
            # back only if that empties the set entirely)...
            healthy = [w for w in candidates if w not in draining]
            candidates = healthy or candidates
            # ...but their indexed prefixes don't vanish: the drain
            # publishes/retiers them into the fleet catalog before the
            # handoff, so count them as FLEET overlap (fetchable by any
            # candidate at fleet_hit_weight) rather than local — even
            # when the catalog refresh hasn't landed yet
            drain_local = max(
                (overlaps.scores.get(w, 0) for w in draining), default=0
            )
            if drain_local > fleet_blocks:
                fleet_blocks = drain_local
        if fleet_blocks or (resume and overlaps.scores):
            boost = self.resume_overlap_boost if resume else 1.0
            # effective overlap per candidate: local blocks at full
            # weight + the fleet-fetchable extension at fetch weight.
            # The resume boost scales the COMBINED score, so the fleet
            # contribution stays discounted (satellite guarantee: a
            # resume whose prefix was just demoted off every device
            # scores boost*fleet_hit_weight*blocks, never at local
            # weight as if the blocks were still resident).
            # OverlapScores is sparse (absent = 0): only workers with a
            # non-zero effective overlap get an entry, so a resume with
            # no fleet catalog scores exactly as before.
            scores = {}
            for w in set(candidates) | set(overlaps.scores):
                local = overlaps.scores.get(w, 0)
                eff = local + self.fleet_hit_weight * max(
                    0, fleet_blocks - local
                )
                if eff:
                    scores[w] = boost * eff
            overlaps = OverlapScores(
                scores=scores,
                total_blocks=overlaps.total_blocks,
            )
        fresh = self.aggregator.fresh_metrics()
        # prefer workers with a live health signal: if SOME candidates have
        # fresh metrics, a candidate without them is stale (hung publisher /
        # dead worker) — don't reward it with a default zero-load score
        with_fresh = [w for w in candidates if w in fresh]
        if with_fresh:
            candidates = with_fresh
        metrics = fresh
        if self.inflight:
            charges = {w: self._active_inflight(w) for w in candidates}
            metrics = {
                w: m.model_copy(update={
                    "num_requests_waiting": m.num_requests_waiting
                    + charges.get(w, 0)
                })
                for w, m in fresh.items()
            }
            for w, n in charges.items():
                if n > 0 and w not in metrics:
                    metrics[w] = ForwardPassMetrics(
                        worker_id=w, num_requests_waiting=n
                    )
        wid = self.selector(overlaps, metrics, candidates)
        token = self.note_dispatch(wid)
        # decision + hit-rate event report the TRUE (unboosted) overlap
        decision = SchedulingDecision(
            worker_id=wid,
            overlap_blocks=true_overlaps.scores.get(wid, 0),
            total_blocks=true_overlaps.total_blocks,
            dispatch_token=token,
            fleet_blocks=fleet_blocks,
        )
        if self.on_hit_rate is not None:
            self.on_hit_rate(
                KvHitRateEvent(
                    worker_id=wid,
                    isl_blocks=decision.total_blocks,
                    overlap_blocks=decision.overlap_blocks,
                )
            )
        return decision
