"""Host data-plane observability: event-loop lag + per-stream cost.

The engine side has the step loop's phase clocks (telemetry/spans.py
``StepClock``, in ``/debug/state``) answering "where does the step's
time go"; this module is their twin for the *frontend host plane* —
the single-process asyncio loop that parses requests, sheds load,
primes first chunks, and serializes SSE deltas, and that will saturate
long before the chips do (ROADMAP item 4). Nothing here should be
invisible before PR 18 shards it.

Three pieces, all surfaced at ``/debug/hostplane`` (HTTP frontend and
metrics service) via the same :class:`ProviderRegistry` machinery as
``/debug/state``:

- :class:`LoopLagMonitor` — a self-timing heartbeat task per event
  loop: sleeps a fixed interval and measures how late the loop woke it
  (p50/p99/max over a bounded window). A wake later than the stall
  threshold trips the flight-recorder/black-box path with reason
  ``loop_stall`` (exactly one bundle per holdoff window, the same
  rate-limit discipline as the engine's anomaly capture). Also keeps
  an asyncio task census (active tasks by name family) and arms
  ``loop.slow_callback_duration`` so debug-mode slow-callback logs
  name the offending handler.
- :class:`HostCostLedger` — per-request stamps for every host stage
  (preprocess, admission, router dispatch, first-chunk priming,
  per-chunk SSE serialize+write as an EMA, tool-parser time,
  write-backpressure drain waits), rolled into ``dynamo_http_*``
  histograms/gauges. ``dynamo_http_time_to_first_token_seconds``
  (frontend TTFB) minus the ``prime`` stamp (the engine-side wait for
  the first chunk) is the frontend's added latency — the
  TTFB-vs-engine-TTFT split that tells host stall from chip stall.
- the ``/debug/hostplane`` provider registry
  (``register_hostplane_provider`` / ``collect_hostplane``).

Operator view: docs/observability.md "Host data plane".
"""

from __future__ import annotations

import asyncio
import contextlib
import logging
import re
import threading
import time
from collections import deque
from typing import Callable, Optional

from dynamo_tpu.utils.clock import SYSTEM, Clock

from dynamo_tpu.telemetry.instruments import (
    HTTP_DRAIN_WAIT,
    HTTP_FIRST_CHUNK_WAIT,
    HTTP_HOST_STAGE,
    HTTP_LOOP_LAG,
    HTTP_LOOP_LAG_MAX,
    HTTP_LOOP_LAG_P99,
    HTTP_LOOP_STALLS,
    HTTP_OPEN_STREAMS,
    HTTP_SSE_WRITE_EMA,
)

log = logging.getLogger("dynamo_tpu.telemetry.hostplane")

# ledger stage names (the bounded label set of dynamo_http_host_stage_seconds)
STAGES = ("preprocess", "admission", "dispatch", "prime", "tool_parser")

# refresh the derived gauges every N heartbeats / finished requests
GAUGE_EVERY = 32


def _percentile(sorted_vals: list[float], p: float) -> float:
    """Nearest-rank percentile over an already-sorted list."""
    if not sorted_vals:
        return 0.0
    k = max(0, min(len(sorted_vals) - 1, int(round(p * (len(sorted_vals) - 1)))))
    return sorted_vals[k]


_TASK_FAMILY_RE = re.compile(r"[-_]?\d+$")


def task_census(loop: Optional[asyncio.AbstractEventLoop] = None,
                max_families: int = 32) -> dict[str, int]:
    """Active asyncio tasks grouped by name family (``Task-17`` →
    ``Task``, ``metrics-hit-pump`` stays itself): the "what is this
    loop running" answer without a debugger. Bounded to the
    ``max_families`` largest families so a task-name bug cannot bloat
    the snapshot."""
    try:
        tasks = asyncio.all_tasks(loop)
    except RuntimeError:
        return {}
    fams: dict[str, int] = {}
    for t in tasks:
        name = _TASK_FAMILY_RE.sub("", t.get_name() or "") or "unnamed"
        fams[name] = fams.get(name, 0) + 1
    if len(fams) > max_families:
        top = sorted(fams.items(), key=lambda kv: (-kv[1], kv[0]))
        rest = sum(n for _, n in top[max_families:])
        fams = dict(top[:max_families])
        fams["_other"] = rest
    return fams


class LoopLagMonitor:
    """Self-timing heartbeat: measures how late the event loop runs a
    task that asked to wake every ``interval_s``.

    Lag is THE summary statistic for a cooperative loop — every await
    in every handler waits at least this long beyond its nominal wake
    time, so lag p99 bounds the scheduling tax on all concurrent
    streams. A single wake later than ``stall_s`` means some callback
    held the loop synchronously for that span; the watchdog dumps the
    flight-recorder ring and triggers a black-box bundle with reason
    ``loop_stall`` (once per ``holdoff_s`` — the same flap-proofing as
    the engine's anomaly capture).

    ``note_lag`` is the pure core (injectable-clock unit tests call it
    directly); ``start()`` spawns the heartbeat task on the running
    loop and arms ``loop.slow_callback_duration`` so asyncio's
    debug-mode slow-callback log names the offending handler.
    """

    def __init__(
        self,
        interval_s: float = 0.1,
        window: int = 1024,
        stall_s: float = 0.05,
        holdoff_s: float = 60.0,
        recorder=None,
        blackbox=None,
        clock: Optional[Clock] = None,
        slow_callback_s: float = 0.1,
    ):
        self.interval_s = interval_s
        self.stall_s = stall_s
        self.holdoff_s = holdoff_s
        self.recorder = recorder
        self.blackbox = blackbox
        self.slow_callback_s = slow_callback_s
        # injectable Clock (utils/clock.py): the heartbeat loop and the
        # stall holdoff both run on it, so tests (and simulated runs)
        # drive the monitor on virtual time
        self.clock: Clock = clock or SYSTEM
        self._lock = threading.Lock()
        # bounded lag window (dynalint DL007 discipline)
        self._window: deque = deque(maxlen=max(2, window))
        self._task: Optional[asyncio.Task] = None
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        self._beats = 0
        self._stalls = 0
        self._last_stall: float = -float("inf")
        self._last_lag_s = 0.0
        self._summary: dict = {"p50_ms": 0.0, "p99_ms": 0.0, "max_ms": 0.0}

    # -- pure core (unit-testable with an injected clock) ------------------
    def note_lag(self, lag_s: float) -> Optional[str]:
        """Record one heartbeat's measured lag; returns the black-box
        bundle dir when the stall watchdog fired (None otherwise)."""
        lag_s = max(0.0, lag_s)
        fired: Optional[str] = None
        with self._lock:
            self._beats += 1
            self._window.append(lag_s)
            self._last_lag_s = lag_s
            beats = self._beats
        HTTP_LOOP_LAG.observe(lag_s)
        if lag_s >= self.stall_s:
            fired = self._stall(lag_s)
        if beats % GAUGE_EVERY == 0:
            self._refresh_gauges()
        return fired

    def _stall(self, lag_s: float) -> Optional[str]:
        now = self.clock.monotonic()
        with self._lock:
            self._stalls += 1
            if now - self._last_stall < self.holdoff_s:
                return None  # one bundle per window, not one per beat
            self._last_stall = now
        HTTP_LOOP_STALLS.inc()
        log.warning(
            "event-loop stall: heartbeat woke %.1f ms late "
            "(threshold %.1f ms)", lag_s * 1e3, self.stall_s * 1e3,
        )
        if self.recorder is not None:
            self.recorder.record(
                "loop_stall", lag_s, lag_ms=round(lag_s * 1e3, 3),
                stall_threshold_ms=round(self.stall_s * 1e3, 3),
            )
            self.recorder.dump(reason="loop_stall")
        if self.blackbox is not None:
            return self.blackbox.trigger("loop_stall")
        return None

    def _refresh_gauges(self) -> None:
        with self._lock:
            vals = sorted(self._window)
        p50 = _percentile(vals, 0.50)
        p99 = _percentile(vals, 0.99)
        mx = vals[-1] if vals else 0.0
        HTTP_LOOP_LAG_P99.set(p99)
        HTTP_LOOP_LAG_MAX.set(mx)
        with self._lock:
            self._summary = {
                "p50_ms": round(p50 * 1e3, 3),
                "p99_ms": round(p99 * 1e3, 3),
                "max_ms": round(mx * 1e3, 3),
            }

    # -- heartbeat lifecycle ----------------------------------------------
    async def _heartbeat(self) -> None:
        while True:
            before = self.clock.monotonic()
            await self.clock.sleep(self.interval_s)
            # the sleep returned late by exactly the loop's scheduling
            # lag: every other coroutine on this loop waited at least
            # as long past ITS wake time
            self.note_lag(
                self.clock.monotonic() - before - self.interval_s
            )

    def start(self) -> None:
        """Spawn the heartbeat on the running loop (idempotent)."""
        if self._task is not None and not self._task.done():
            return
        from dynamo_tpu.utils.tasks import spawn

        self._loop = asyncio.get_running_loop()
        # debug-mode slow-callback log threshold: harmless when debug
        # is off, names the offending handler when it is on
        self._loop.slow_callback_duration = self.slow_callback_s
        self._task = spawn(self._heartbeat(), name="hostplane-heartbeat")

    async def stop(self) -> None:
        if self._task is not None:
            self._task.cancel()
            with contextlib.suppress(asyncio.CancelledError):
                await self._task
            self._task = None

    def snapshot(self) -> dict:
        self._refresh_gauges()
        with self._lock:
            out = {
                "interval_ms": round(self.interval_s * 1e3, 3),
                "stall_threshold_ms": round(self.stall_s * 1e3, 3),
                "beats": self._beats,
                "stalls": self._stalls,
                "last_lag_ms": round(self._last_lag_s * 1e3, 3),
                "lag": dict(self._summary),
                "running": self._task is not None and not self._task.done(),
                "slow_callback_ms": round(self.slow_callback_s * 1e3, 1),
            }
        out["tasks"] = task_census(self._loop)
        if self.blackbox is not None:
            out["blackbox"] = self.blackbox.stats()
        if self.recorder is not None:
            out["flight_recorder"] = self.recorder.stats()
        return out


class _RequestCost:
    """Mutable per-request stamp record (internal to the ledger)."""

    __slots__ = (
        "rid", "endpoint", "stream", "t_start", "stages", "chunks",
        "bytes", "write_ema_s", "drain_waits", "drain_wait_s", "ttfb_s",
    )

    def __init__(self, rid: str, endpoint: str, stream: bool, t: float):
        self.rid = rid
        self.endpoint = endpoint
        self.stream = stream
        self.t_start = t
        self.stages: dict[str, float] = {}
        self.chunks = 0
        self.bytes = 0
        self.write_ema_s = 0.0
        self.drain_waits = 0
        self.drain_wait_s = 0.0
        self.ttfb_s: Optional[float] = None


class HostCostLedger:
    """Per-request host-cost stamps → bounded window + instruments.

    One record per in-flight request, stamped by the HTTP handler
    (parse/validate, admission, dispatch, first-chunk priming, SSE
    chunk serialize+write, drain waits) and by downstream stages that
    only know the request id (the preprocessor's tool parser, the
    router's instance pick) via :func:`note_stage`. ``finish()`` rolls
    the record into the histograms and the rolling window the
    ``/debug/hostplane`` snapshot reads.

    Thread-safety: stamped from the event loop, read from arbitrary
    threads (debug endpoints) — one lock, all accesses take it. Both the
    active table and the finished window are bounded (DL007).
    """

    def __init__(
        self,
        window: int = 512,
        max_active: int = 8192,
        ema_alpha: float = 0.2,
        drain_threshold_s: float = 0.001,
        clock: Callable[[], float] = time.monotonic,
    ):
        self._clock = clock
        self._lock = threading.Lock()
        self._active: dict[str, _RequestCost] = {}
        self._active_order: deque = deque()
        self._max_active = max_active
        self._ema_alpha = ema_alpha
        self._drain_threshold_s = drain_threshold_s
        self._window: deque = deque(maxlen=max(1, window))
        self._finished = 0
        self._streams_open = 0
        self._streams_total = 0
        self._chunks_total = 0
        self._write_ema_s = 0.0
        self._summary_cache: dict = {}

    # -- request lifecycle -------------------------------------------------
    def begin(self, rid: str, endpoint: str, stream: bool = False) -> None:
        now = self._clock()
        with self._lock:
            if rid in self._active:
                return
            # bound the active table: a handler path that never reaches
            # finish() (crash before the finally) must not leak records
            while len(self._active) >= self._max_active and self._active_order:
                self._active.pop(self._active_order.popleft(), None)
            self._active[rid] = _RequestCost(rid, endpoint, stream, now)
            self._active_order.append(rid)
            self._summary_cache = {}

    def stage(self, rid: str, name: str, seconds: float) -> None:
        """Accumulate ``seconds`` into the request's ``name`` stamp
        (repeat calls add — tool-parser time arrives per delta)."""
        with self._lock:
            rec = self._active.get(rid)
            if rec is None:
                return
            rec.stages[name] = rec.stages.get(name, 0.0) + seconds
        if name in STAGES:
            HTTP_HOST_STAGE.labels(name).observe(seconds)
        if name == "prime":
            HTTP_FIRST_CHUNK_WAIT.observe(seconds)

    def mark_stream(self, rid: str) -> None:
        """The request committed to an SSE response (stream opened)."""
        with self._lock:
            rec = self._active.get(rid)
            if rec is not None and not rec.stream:
                rec.stream = True
            self._streams_open += 1
            self._streams_total += 1
            open_now = self._streams_open
            self._summary_cache = {}
        HTTP_OPEN_STREAMS.set(float(open_now))

    def chunk(self, rid: str, serialize_s: float, write_s: float,
              nbytes: int = 0) -> None:
        """One SSE chunk's serialize + write cost. The EMA (not a
        per-chunk series) is the scrape-safe shape: thousands of
        streams × hundreds of chunks must not mint samples."""
        total = serialize_s + write_s
        with self._lock:
            rec = self._active.get(rid)
            if rec is not None:
                rec.chunks += 1
                rec.bytes += nbytes
                rec.write_ema_s = (
                    total if rec.chunks == 1
                    else rec.write_ema_s
                    + self._ema_alpha * (total - rec.write_ema_s)
                )
                if rec.ttfb_s is None:
                    rec.ttfb_s = self._clock() - rec.t_start
                if write_s >= self._drain_threshold_s:
                    # the write awaited transport drain: backpressure
                    rec.drain_waits += 1
                    rec.drain_wait_s += write_s
            self._chunks_total += 1
            self._write_ema_s = (
                total if self._chunks_total == 1
                else self._write_ema_s
                + self._ema_alpha * (total - self._write_ema_s)
            )
            ema = self._write_ema_s
            n = self._chunks_total
        if n % GAUGE_EVERY == 0:
            HTTP_SSE_WRITE_EMA.set(ema)

    def finish(self, rid: str, status: str = "200") -> Optional[dict]:
        """Close the request's ledger entry; returns the finished row
        (None on a repeat call) so the autopsy plane can adopt the
        frontend stages without re-deriving them."""
        with self._lock:
            rec = self._active.pop(rid, None)
            if rec is None:
                return None
            try:
                self._active_order.remove(rid)
            except ValueError:
                pass
            now = self._clock()
            was_stream = rec.stream
            if was_stream:
                self._streams_open = max(0, self._streams_open - 1)
            open_now = self._streams_open
            self._finished += 1
            row = {
                "rid": rec.rid,
                "endpoint": rec.endpoint,
                "stream": was_stream,
                "status": status,
                "total_ms": round((now - rec.t_start) * 1e3, 3),
                "stages_ms": {
                    k: round(v * 1e3, 3) for k, v in rec.stages.items()
                },
                "chunks": rec.chunks,
                "bytes": rec.bytes,
                "write_ema_us": round(rec.write_ema_s * 1e6, 1),
                "drain_waits": rec.drain_waits,
                "drain_wait_ms": round(rec.drain_wait_s * 1e3, 3),
                "ttfb_ms": (
                    round(rec.ttfb_s * 1e3, 3)
                    if rec.ttfb_s is not None else None
                ),
            }
            # host-side overhead of the first byte: TTFB minus the wait
            # for the engine's first chunk — the frontend's own share
            prime = rec.stages.get("prime")
            if rec.ttfb_s is not None and prime is not None:
                row["host_ttfb_ms"] = round(
                    max(0.0, rec.ttfb_s - prime) * 1e3, 3
                )
            self._window.append(row)
            # every lifecycle edge invalidates (summary() recomputes
            # lazily on the next scrape): /debug/hostplane and the
            # `top` STRM/RPS columns must never read counts staler
            # than the requests they describe
            self._summary_cache = {}
        if was_stream:
            HTTP_OPEN_STREAMS.set(float(open_now))
            HTTP_DRAIN_WAIT.observe(rec.drain_wait_s)
        return row

    # -- introspection -----------------------------------------------------
    def summary(self) -> dict:
        """Rolling-window means (cheap; cached between refreshes)."""
        with self._lock:
            if self._summary_cache:
                return dict(self._summary_cache)
            rows = list(self._window)
            out = {
                "requests_total": self._finished,
                "streams_total": self._streams_total,
                "streams_open": self._streams_open,
                "active": len(self._active),
                "chunks_total": self._chunks_total,
                "sse_write_ema_us": round(self._write_ema_s * 1e6, 1),
            }
        if rows:
            out["window"] = {
                "requests": len(rows),
                "total_ms_mean": round(
                    sum(r["total_ms"] for r in rows) / len(rows), 3
                ),
                "stage_ms_mean": {
                    s: round(
                        sum(r["stages_ms"].get(s, 0.0) for r in rows)
                        / len(rows), 3,
                    )
                    for s in STAGES
                    if any(s in r["stages_ms"] for r in rows)
                },
                "drain_wait_ms_mean": round(
                    sum(r["drain_wait_ms"] for r in rows) / len(rows), 3
                ),
            }
            ttfbs = [r["ttfb_ms"] for r in rows if r.get("ttfb_ms") is not None]
            primes = [
                r["stages_ms"]["prime"] for r in rows
                if "prime" in r["stages_ms"]
            ]
            if ttfbs:
                out["window"]["ttfb_ms_mean"] = round(
                    sum(ttfbs) / len(ttfbs), 3
                )
            if primes:
                # the split operators read: TTFB − engine first-chunk
                # wait = the host plane's own contribution
                out["window"]["engine_first_chunk_ms_mean"] = round(
                    sum(primes) / len(primes), 3
                )
        with self._lock:
            self._summary_cache = dict(out)
        return out

    def snapshot(self, recent: int = 8) -> dict:
        out = self.summary()
        with self._lock:
            out["recent"] = list(self._window)[-max(0, recent):]
        return out


# ---------------------------------------------------------------------------
# process-global ledger + note_stage (downstream stages — the
# preprocessor's tool parser, the router's dispatch pick — only know
# the request id, so they stamp through the module singleton exactly
# like instruments are process-global)
# ---------------------------------------------------------------------------
LEDGER = HostCostLedger()


def note_stage(rid: Optional[str], stage: str, seconds: float) -> None:
    """Stamp ``seconds`` of host work onto the live request ``rid``
    (no-op when the id has no active ledger record — engines run
    outside a frontend too)."""
    if rid:
        LEDGER.stage(rid, stage, seconds)


# ---------------------------------------------------------------------------
# /debug/hostplane provider registry — the SAME machinery as
# /debug/state, second instance
# ---------------------------------------------------------------------------
from dynamo_tpu.telemetry.debug import ProviderRegistry  # noqa: E402

_HOSTPLANE_PROVIDERS = ProviderRegistry("hostplane")


def register_hostplane_provider(name: str, fn: Callable[[], dict]) -> None:
    _HOSTPLANE_PROVIDERS.register(name, fn)


def unregister_hostplane_provider(
    name: str, fn: Optional[Callable[[], dict]] = None
) -> None:
    _HOSTPLANE_PROVIDERS.unregister(name, fn)


def collect_hostplane() -> dict:
    """One JSON-able snapshot for ``/debug/hostplane`` — a provider
    that raises degrades to an error stanza (introspection must keep
    working exactly when things are broken)."""
    return _HOSTPLANE_PROVIDERS.collect()
