"""End-to-end request autopsy: tail-sampled per-request timelines.

The serving stack's telemetry is rich but siloed — spans land in an
opt-in ``DYN_TRACE_FILE``, the flight recorder is step-centric, the
hostplane ledger keeps stage EMAs, and migration/guided/kv-fabric
outcomes each live in their own counters.
This module is the join layer: every request accumulates ONE compact
in-memory record keyed by the ``X-Request-Id``/``Context.id`` that
already rides the wire ctx frame, assembled from four sources:

- **frontend stages** — the ``HostCostLedger`` row handed over at
  ``finish()`` (preprocess/admission/dispatch/prime/ttfb, chunk counts);
- **router decisions** — worker chosen, overlap/fleet-block score,
  failover/resume re-dials (:func:`note_router`, stamped by both
  routers' dial closures);
- **engine segments** — queue-wait, prefill, decode, TTFT, spec accept
  totals, preemptions, guided flag, published by the engine at finish
  (:func:`publish_segment`). A worker process has no active record, so
  its segments park in a bounded pending table; the endpoint server
  pops them (:func:`take_pending`) and ships them to the caller on a
  ``{t:"seg"}`` wire frame, where :func:`merge_pending` folds them into
  the frontend's record — a migrated request's autopsy therefore shows
  BOTH workers' segments and the splice point;
- **fleet events** — migration splice (both worker ids), kv-fabric
  prefetch hit/miss, fault firings, deadline/shed outcomes
  (:func:`note_event`).

Retention is tail-based (the scrape-safe shape): a bounded table holds
every in-flight request; at finish a record is kept as an **exemplar**
only if it was flagged (SLO miss, migrated/aborted, faulted, shed,
rejected, error) or its total/TTFB sits at or above the rolling
window's p99 — everything else is dropped. Per-request cost is O(1)
amortized: bounded lists, p99 thresholds recomputed every
``GAUGE_EVERY`` finishes, no per-chunk work.

Surfaces: ``/debug/requests`` (exemplar index) + ``/debug/request/{rid}``
on the HTTP frontend and the metrics service via the fourth
:class:`ProviderRegistry` instance, and ``dynamo-tpu autopsy <rid>``
(ASCII waterfall with a wall-clock coverage check).
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Any, Callable, Optional

from dynamo_tpu.telemetry.instruments import (
    AUTOPSY_EXEMPLARS,
    AUTOPSY_REQUESTS,
    AUTOPSY_SEGMENTS,
)

# hard bounds on everything a request can accumulate (dynalint DL007
# discipline): a pathological stream must not grow its record unboundedly
MAX_EVENTS = 48
MAX_ROUTER = 16
MAX_SEGMENTS = 8

# recompute the p99 retention thresholds every N finishes (the same
# amortization discipline as the hostplane ledger)
GAUGE_EVERY = 32

# below this many finished requests in the rolling window the p99 is
# noise — retain everything while the tail estimate warms up (the
# exemplar ring is bounded, so warm-up retention cannot leak)
MIN_WINDOW = 32

# flags that force exemplar retention regardless of latency
_RETAIN_FLAGS = frozenset(
    {"slo_miss", "migrated", "aborted", "faulted", "shed", "rejected",
     "error", "deadline"}
)


def _percentile(sorted_vals: list[float], p: float) -> float:
    """Nearest-rank percentile over an already-sorted list."""
    if not sorted_vals:
        return 0.0
    k = max(0, min(len(sorted_vals) - 1, int(round(p * (len(sorted_vals) - 1)))))
    return sorted_vals[k]


class _RequestRecord:
    """Mutable in-flight autopsy record (internal to the collector)."""

    __slots__ = (
        "rid", "endpoint", "t_start", "t_start_wall", "trace_id",
        "flags", "events", "router", "segments",
    )

    def __init__(self, rid: str, endpoint: str, t: float, wall: float):
        self.rid = rid
        self.endpoint = endpoint
        self.t_start = t
        self.t_start_wall = wall
        self.trace_id: Optional[str] = None
        self.flags: set[str] = set()
        self.events: list[dict] = []
        self.router: list[dict] = []
        self.segments: list[dict] = []


class AutopsyCollector:
    """Per-request timeline assembly + tail-based exemplar retention.

    Thread-safety matches the other ledgers: stamped from the event
    loop AND the engine thread, read from arbitrary threads (debug
    endpoints) — one lock, all accesses take it. Every table is
    bounded: the active map (FIFO-evicted past ``max_active``), the
    pending cross-process table, the exemplar ring, and the rolling
    latency window.
    """

    def __init__(
        self,
        max_active: int = 8192,
        max_exemplars: int = 256,
        window: int = 512,
        max_pending: int = 1024,
        clock: Callable[[], float] = time.monotonic,
        wall: Callable[[], float] = time.time,
    ):
        self._clock = clock
        self._wall = wall
        self._lock = threading.Lock()
        self._active: dict[str, _RequestRecord] = {}
        self._active_order: deque = deque()
        self._max_active = max_active
        # worker-side segments/events for rids with no active record
        # here (they belong to a frontend in another process); popped by
        # the endpoint server and shipped over the wire
        self._pending: dict[str, dict] = {}
        self._pending_order: deque = deque()
        self._max_pending = max_pending
        self._exemplars: deque = deque(maxlen=max(1, max_exemplars))
        # rolling (total_ms, ttfb_ms) window feeding the p99 thresholds
        self._window: deque = deque(maxlen=max(MIN_WINDOW, window))
        self._finished = 0
        self._retained = 0
        self._dropped = 0
        self._p99_total_ms = 0.0
        self._p99_ttfb_ms = 0.0

    # -- request lifecycle (frontend process) -----------------------------
    def begin(self, rid: str, endpoint: str) -> None:
        now, wall = self._clock(), self._wall()
        with self._lock:
            if rid in self._active:
                return
            while len(self._active) >= self._max_active and self._active_order:
                self._active.pop(self._active_order.popleft(), None)
            self._active[rid] = _RequestRecord(rid, endpoint, now, wall)
            self._active_order.append(rid)

    def set_trace(self, rid: str, trace_id: Optional[str]) -> None:
        if not trace_id:
            return
        with self._lock:
            rec = self._active.get(rid)
            if rec is not None:
                rec.trace_id = trace_id

    def note_event(
        self, rid: str, kind: str, flag: Optional[str] = None, **fields
    ) -> None:
        """Append one timeline event. Active record → straight in;
        unknown rid (worker process) → the pending table, to ride the
        wire with this worker's segments."""
        now = self._clock()
        with self._lock:
            rec = self._active.get(rid)
            if rec is not None:
                if len(rec.events) < MAX_EVENTS:
                    ev = {"t_ms": round((now - rec.t_start) * 1e3, 3),
                          "kind": kind}
                    ev.update(fields)
                    rec.events.append(ev)
                if flag:
                    rec.flags.add(flag)
                return
            pend = self._pending_locked(rid)
            if pend is not None and len(pend["events"]) < MAX_EVENTS:
                ev = {"kind": kind}
                ev.update(fields)
                if flag:
                    ev["flag"] = flag
                pend["events"].append(ev)

    def note_router(
        self,
        rid: str,
        worker_id: int,
        overlap_blocks: int = 0,
        total_blocks: int = 0,
        fleet_blocks: int = 0,
        resume: bool = False,
        mode: str = "kv",
    ) -> None:
        """One routing decision (dial). Repeat calls record failover /
        resume re-dials in order."""
        now = self._clock()
        with self._lock:
            rec = self._active.get(rid)
            if rec is None or len(rec.router) >= MAX_ROUTER:
                return
            rec.router.append({
                "t_ms": round((now - rec.t_start) * 1e3, 3),
                "worker": f"{worker_id:x}",
                "mode": mode,
                "overlap_blocks": overlap_blocks,
                "total_blocks": total_blocks,
                "fleet_blocks": fleet_blocks,
                "resume": resume,
            })

    # -- segments (engine / disagg side; any process) ---------------------
    def publish_segment(self, rid: str, segment: dict) -> None:
        """Attach one execution segment (engine finish, remote-prefill
        wait, synthesized dead-worker stub) to the request's record —
        directly when the record lives here, via the pending table when
        the frontend is another process."""
        with self._lock:
            rec = self._active.get(rid)
            if rec is not None:
                if len(rec.segments) < MAX_SEGMENTS:
                    rec.segments.append(dict(segment))
                    AUTOPSY_SEGMENTS.labels(
                        str(segment.get("source", "engine"))
                    ).inc()
                return
            pend = self._pending_locked(rid)
            if pend is not None and len(pend["segments"]) < MAX_SEGMENTS:
                pend["segments"].append(dict(segment))
                AUTOPSY_SEGMENTS.labels(
                    str(segment.get("source", "engine"))
                ).inc()

    def _pending_locked(self, rid: str) -> Optional[dict]:
        pend = self._pending.get(rid)
        if pend is None:
            while (
                len(self._pending) >= self._max_pending
                and self._pending_order
            ):
                self._pending.pop(self._pending_order.popleft(), None)
            pend = {"segments": [], "events": []}
            self._pending[rid] = pend
            self._pending_order.append(rid)
        return pend

    def take_pending(self, rid: str) -> Optional[dict]:
        """Pop the worker-side payload for ``rid`` (segments + events)
        so the endpoint server can ship it to the caller; None when
        this process accumulated nothing for the rid."""
        with self._lock:
            pend = self._pending.pop(rid, None)
            if pend is not None:
                try:
                    self._pending_order.remove(rid)
                except ValueError:
                    pass
            return pend

    def merge_pending(self, rid: str, payload: Optional[dict]) -> None:
        """Fold a worker's shipped payload (a ``take_pending`` dict off
        the wire) into the local record for ``rid`` — or park it in the
        local pending table when the record lives yet another hop up
        (disagg decode worker relaying to the frontend)."""
        if not isinstance(payload, dict):
            return
        for seg in payload.get("segments") or []:
            if isinstance(seg, dict):
                self.publish_segment(rid, seg)
        for ev in payload.get("events") or []:
            if isinstance(ev, dict):
                ev = dict(ev)
                kind = str(ev.pop("kind", "event"))
                flag = ev.pop("flag", None)
                ev.pop("t_ms", None)  # worker-relative; meaningless here
                self.note_event(rid, kind, flag=flag, **ev)

    # -- finish + retention ------------------------------------------------
    def finish(
        self, rid: str, status: str = "200", host: Optional[dict] = None
    ) -> Optional[dict]:
        """Close the record: merge any local pending payload, derive
        flags from segments/status, decide retention, and (for
        exemplars) move the assembled record into the ring. Idempotent
        — the first call wins. Returns the assembled record when it was
        retained."""
        pend = self.take_pending(rid)
        now = self._clock()
        with self._lock:
            rec = self._active.pop(rid, None)
            if rec is None:
                return None
            try:
                self._active_order.remove(rid)
            except ValueError:
                pass
            total_ms = round((now - rec.t_start) * 1e3, 3)
        if pend is not None:
            # merge outside the pop so bounded-append logic is shared;
            # the record is gone from _active, so fold manually below
            for seg in pend.get("segments") or []:
                if len(rec.segments) < MAX_SEGMENTS and isinstance(seg, dict):
                    rec.segments.append(dict(seg))
            for ev in pend.get("events") or []:
                if len(rec.events) < MAX_EVENTS and isinstance(ev, dict):
                    ev = dict(ev)
                    flag = ev.pop("flag", None)
                    if flag:
                        rec.flags.add(str(flag))
                    rec.events.append(ev)
        ttfb_ms = None
        if host:
            ttfb_ms = host.get("ttfb_ms")
        # flags derived from the assembled segments + terminal status
        for seg in rec.segments:
            if seg.get("slo_miss"):
                rec.flags.add("slo_miss")
            fr = str(seg.get("finish_reason") or "")
            if fr == "timeout":
                rec.flags.add("deadline")
            elif fr == "error":
                rec.flags.add("error")
        if status not in ("200", "499"):
            rec.flags.add("error")
        with self._lock:
            self._finished += 1
            if self._finished % GAUGE_EVERY == 0:
                totals = sorted(t for t, _ in self._window)
                ttfbs = sorted(
                    t for _, t in self._window if t is not None
                )
                self._p99_total_ms = _percentile(totals, 0.99)
                self._p99_ttfb_ms = _percentile(ttfbs, 0.99)
            slow = (
                len(self._window) < MIN_WINDOW
                or total_ms >= self._p99_total_ms
                or (
                    ttfb_ms is not None
                    and self._p99_ttfb_ms > 0
                    and ttfb_ms >= self._p99_ttfb_ms
                )
            )
            self._window.append((total_ms, ttfb_ms))
            retain = bool(rec.flags & _RETAIN_FLAGS) or slow
            if not retain:
                self._dropped += 1
        if not retain:
            AUTOPSY_REQUESTS.labels("dropped").inc()
            return None
        row = {
            "rid": rec.rid,
            "endpoint": rec.endpoint,
            "status": status,
            "ts": rec.t_start_wall,
            "total_ms": total_ms,
            "ttfb_ms": ttfb_ms,
            "flags": sorted(rec.flags),
            "retained": (
                "flag" if rec.flags & _RETAIN_FLAGS else "tail_p99"
            ),
            "host": host,
            "router": rec.router,
            "events": rec.events,
            "segments": rec.segments,
            "trace_id": rec.trace_id,
            "finished": True,
        }
        with self._lock:
            self._retained += 1
            self._exemplars.append(row)
            n = len(self._exemplars)
        AUTOPSY_REQUESTS.labels("retained").inc()
        AUTOPSY_EXEMPLARS.set(float(n))
        return row

    # -- introspection -----------------------------------------------------
    def get(self, rid: str) -> Optional[dict]:
        """The request's record: in-flight (partial, ``finished:
        False``) or a retained exemplar. None = never seen or dropped
        at finish."""
        now = self._clock()
        with self._lock:
            rec = self._active.get(rid)
            if rec is not None:
                return {
                    "rid": rec.rid,
                    "endpoint": rec.endpoint,
                    "status": None,
                    "ts": rec.t_start_wall,
                    "total_ms": round((now - rec.t_start) * 1e3, 3),
                    "ttfb_ms": None,
                    "flags": sorted(rec.flags),
                    "host": None,
                    "router": list(rec.router),
                    "events": list(rec.events),
                    "segments": list(rec.segments),
                    "trace_id": rec.trace_id,
                    "finished": False,
                }
            for row in reversed(self._exemplars):
                if row["rid"] == rid:
                    return dict(row)
        return None

    def index(self) -> list[dict]:
        """The exemplar index (newest first): one summary line per
        retained record — what ``/debug/requests`` serves and the
        ``top`` SLOW column counts."""
        with self._lock:
            rows = list(self._exemplars)
        return [
            {
                "rid": r["rid"],
                "endpoint": r["endpoint"],
                "status": r["status"],
                "total_ms": r["total_ms"],
                "ttfb_ms": r["ttfb_ms"],
                "flags": r["flags"],
                "segments": len(r["segments"]),
                "ts": r["ts"],
            }
            for r in reversed(rows)
        ]

    def snapshot(self) -> dict:
        with self._lock:
            out = {
                "requests_total": self._finished,
                "retained_total": self._retained,
                "dropped_total": self._dropped,
                "active": len(self._active),
                "pending": len(self._pending),
                "p99_total_ms": round(self._p99_total_ms, 3),
                "p99_ttfb_ms": round(self._p99_ttfb_ms, 3),
            }
        out["exemplars"] = self.index()
        return out


# ---------------------------------------------------------------------------
# process-global collector + module-level note_* functions (downstream
# layers — routers, engine, migration, faults, fabric — only know the
# request id, exactly like hostplane.note_stage)
# ---------------------------------------------------------------------------
COLLECTOR = AutopsyCollector()


def begin_request(rid: Optional[str], endpoint: str) -> None:
    if rid:
        COLLECTOR.begin(rid, endpoint)


def set_trace(rid: Optional[str], trace_id: Optional[str]) -> None:
    if rid:
        COLLECTOR.set_trace(rid, trace_id)


def note_event(
    rid: Optional[str], kind: str, flag: Optional[str] = None, **fields
) -> None:
    if rid:
        COLLECTOR.note_event(rid, kind, flag=flag, **fields)


def note_router(rid: Optional[str], worker_id: int, **fields) -> None:
    if rid:
        COLLECTOR.note_router(rid, worker_id, **fields)


def publish_segment(rid: Optional[str], segment: dict) -> None:
    if rid:
        COLLECTOR.publish_segment(rid, segment)


def take_pending(rid: Optional[str]) -> Optional[dict]:
    return COLLECTOR.take_pending(rid) if rid else None


def merge_pending(rid: Optional[str], payload: Optional[dict]) -> None:
    if rid:
        COLLECTOR.merge_pending(rid, payload)


def finish_request(
    rid: Optional[str], status: str = "200", host: Optional[dict] = None
) -> Optional[dict]:
    if rid:
        return COLLECTOR.finish(rid, status, host=host)
    return None


def get_record(rid: Optional[str]) -> Optional[dict]:
    return COLLECTOR.get(rid) if rid else None


def exemplar_index() -> list[dict]:
    return COLLECTOR.index()


# ---------------------------------------------------------------------------
# onboard context: the KVBM onboard hook is (hashes, blocks) -> int with
# no request identity, so the scheduler parks the admitting sequence's
# rid in a thread-local around the call and the fleet fabric's prefetch
# reads it back — same engine thread, synchronous call chain
# ---------------------------------------------------------------------------
_TLS = threading.local()


def set_onboard_rid(rid: Optional[str]) -> None:
    _TLS.rid = rid


def current_onboard_rid() -> Optional[str]:
    return getattr(_TLS, "rid", None)


# ---------------------------------------------------------------------------
# /debug/requests provider registry — the SAME machinery as
# /debug/state and /debug/hostplane: third instance
# ---------------------------------------------------------------------------
from dynamo_tpu.telemetry.debug import ProviderRegistry  # noqa: E402

_AUTOPSY_PROVIDERS = ProviderRegistry("autopsy")
_AUTOPSY_PROVIDERS.register("collector", COLLECTOR.snapshot)


def register_autopsy_provider(name: str, fn: Callable[[], dict]) -> None:
    _AUTOPSY_PROVIDERS.register(name, fn)


def unregister_autopsy_provider(
    name: str, fn: Optional[Callable[[], dict]] = None
) -> None:
    _AUTOPSY_PROVIDERS.unregister(name, fn)


def collect_autopsy() -> dict:
    """One JSON-able snapshot for ``/debug/requests`` — a provider that
    raises degrades to an error stanza (introspection must keep working
    exactly when things are broken)."""
    return _AUTOPSY_PROVIDERS.collect()


def waterfall(record: dict) -> dict:
    """Derive the waterfall rows + wall-clock coverage check from an
    assembled record: sequential host stages, the streaming span, and
    the unattributed gap must together explain the end-to-end latency
    (the CLI renders this; tests assert the coverage bound).

    Shared here (not in the CLI) so the coverage math has one
    implementation for the renderer and the acceptance tests."""
    total_ms = float(record.get("total_ms") or 0.0)
    host = record.get("host") or {}
    stages_ms: dict[str, Any] = dict(host.get("stages_ms") or {})
    ttfb_ms = record.get("ttfb_ms")
    rows: list[dict] = []
    t = 0.0
    for name in ("preprocess", "admission", "dispatch", "prime",
                 "tool_parser"):
        dur = stages_ms.pop(name, None)
        if dur is None:
            continue
        rows.append({"name": name, "start_ms": round(t, 3),
                     "dur_ms": float(dur)})
        t += float(dur)
    for name, dur in stages_ms.items():  # any future stage names
        rows.append({"name": name, "start_ms": round(t, 3),
                     "dur_ms": float(dur)})
        t += float(dur)
    staged = t
    if ttfb_ms is not None and total_ms > 0:
        gap = max(0.0, float(ttfb_ms) - staged)
        if gap > 0:
            rows.append({"name": "(host gap)", "start_ms": round(staged, 3),
                         "dur_ms": round(gap, 3)})
        stream = max(0.0, total_ms - float(ttfb_ms))
        rows.append({"name": "stream", "start_ms": float(ttfb_ms),
                     "dur_ms": round(stream, 3)})
        explained = staged + gap + stream
    else:
        gap = max(0.0, total_ms - staged)
        if gap > 0:
            rows.append({"name": "(unattributed)",
                         "start_ms": round(staged, 3),
                         "dur_ms": round(gap, 3)})
        explained = staged + gap
    coverage = explained / total_ms if total_ms > 0 else 1.0
    return {
        "rows": rows,
        "total_ms": total_ms,
        "explained_ms": round(explained, 3),
        "coverage": round(coverage, 4),
        # the acceptance bound: stages + gaps explain the end-to-end
        # wall time to within 10%
        "covered": abs(explained - total_ms) <= 0.10 * max(total_ms, 1e-9),
    }
