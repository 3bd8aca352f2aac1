"""Continuous decode perf attribution: where every wall second went.

"Where does the time a decode step spends beyond its byte-bound floor
go?" had to be answered offline, by reading raw flight-recorder phase
stamps. This module makes the answer a *live time series*: an always-on per-step
ledger (``AttributionLedger``) decomposes the engine's decode timeline
into named loss buckets, rolls them into windowed gauges
(``dynamo_step_time_frac{component}``, ``dynamo_roofline_frac``,
``dynamo_tokens_lost_per_s{component}``), and a black-box recorder
(``BlackBox``) bundles full forensic state into one timestamped dump
dir when an anomaly trips — so a roofline regression is caught, named,
and preserved while it happens instead of reconstructed a week later.

## The decomposition

Each engine step record covers the engine-thread interval since the
previous record (the decode timeline is continuous under load;
``note_idle`` breaks it when the engine parks with no work, so waiting
for traffic is load, not loss). The interval partitions EXACTLY — the
buckets sum to the interval by construction — using the measured phase
stamps the flight recorder already carries plus the roofline byte model
(telemetry/roofline.py) as the device-compute split prior:

- **serial step** (``overlapped=False``): the harvest block IS the
  device executing (``sync_ms`` ≈ device compute + transfer), so the
  interval splits ``plan`` → ``dispatch`` → device compute (the sync
  span, split attention/MLP/LM-head/sampling by byte prior) →
  ``queue_wait`` (the emit/bookkeeping/drain residual). ``idle_gap``
  and ``sync`` read 0: in the serial loop the device-idle time *is*
  the exposed host time already named by plan/queue_wait.
- **overlapped step** (``overlapped=True``, the decode/window
  pipelines): the device is presumed busy except the measured
  ``idle_gap_ms`` (telemetry/overlap.py — a host-observable lower
  bound, exact in the serial loop). The idle gap is the loss; it is
  attributed ``plan`` → ``dispatch`` → ``queue_wait`` (residual host
  work: emit, drain, scheduler bookkeeping) against the measured host
  spans. ``sync`` is the residual harvest block (near zero when the
  pipeline is healthy), and everything else is device compute, split
  by the byte prior.

``roofline_frac`` is achieved tok/s over the byte-bound ceiling at the
live geometry (telemetry/roofline.py).
``tokens_lost_per_s{component}`` distributes the gap to the ceiling
over the loss buckets proportionally to their *excess* time (host
buckets count whole; device buckets count time beyond their byte-bound
ideal), so the gap to the ceiling is a first-class per-component series.

## Threading

``note_step``/``note_idle`` are engine-thread only (they mirror
``_record_step``); snapshots are read from the event loop and debug
endpoints, so the window mutates behind a lock. Everything is bounded:
the window is a ``deque(maxlen=...)`` (dynalint DL007) and gauge
refreshes run every ``GAUGE_EVERY`` steps.
"""

from __future__ import annotations

import json
import logging
import os
import tempfile
import threading
import time
from collections import deque
from typing import Callable, Optional

from dynamo_tpu.telemetry.instruments import (
    BLACKBOX_DUMPS,
    ROOFLINE_FRAC,
    STEP_TIME_FRAC,
    TOKENS_LOST_PER_S,
)
from dynamo_tpu.telemetry.roofline import PHASES, RooflineModel

log = logging.getLogger("dynamo_tpu.telemetry.attribution")

# host-side loss buckets + the device-phase split; every step's
# fractions over BUCKETS sum to 1.0 by construction
HOST_BUCKETS = ("queue_wait", "plan", "dispatch", "sync", "idle_gap")
BUCKETS = HOST_BUCKETS + PHASES

# step kinds that are decode work (the roofline is a *decode* ceiling;
# prefill records stay in the timeline/fracs but not the ceiling math)
DECODE_KINDS = frozenset({"decode", "window_pure", "window_mixed", "spec"})

GAUGE_EVERY = 32  # steps between windowed-gauge refreshes


def _alloc(budget: float, *wants: float) -> list[float]:
    """Greedy sequential allocation: give each ``want`` up to what is
    left of ``budget``; the last element returned is the residual."""
    out = []
    rem = max(0.0, budget)
    for w in wants:
        take = min(max(0.0, w), rem)
        out.append(take)
        rem -= take
    out.append(rem)
    return out


class AttributionLedger:
    def __init__(
        self,
        roofline: Optional[RooflineModel] = None,
        window: int = 512,
        clock: Callable[[], float] = time.monotonic,
        anomaly_band: Optional[float] = None,
        anomaly_check_every: int = 64,
    ):
        self.roofline = roofline
        self._clock = clock
        self._lock = threading.Lock()
        self._window: deque = deque(maxlen=max(8, window))
        self._last_note: Optional[float] = None
        self.steps_noted = 0
        # anomaly band: current short-window roofline_frac below
        # band × trailing EMA trips a black-box capture. Defaults off
        # until enough checks have seeded the trailing estimate.
        if anomaly_band is None:
            try:
                anomaly_band = float(os.environ.get("DYN_ATTR_BAND", "0.5"))
            except ValueError:
                anomaly_band = 0.5
        self.anomaly_band = anomaly_band
        self._check_every = max(1, anomaly_check_every)
        self._since_check = 0
        self._trailing_frac: Optional[float] = None
        self._trailing_checks = 0
        self._since_gauges = 0
        # last rolled-up summary, refreshed with the gauges: the cheap
        # read for per-request paths (engine.stats() feeds admission
        # control on every HTTP request — it must not pay an O(window)
        # pass per call). Whole-dict swap: atomic under the GIL.
        self._last_summary: Optional[dict] = None

    def configure(self, roofline: RooflineModel) -> None:
        """Install the byte model once the engine knows its geometry
        (model config + quant + kv dtype resolve during init)."""
        self.roofline = roofline

    # -- engine-thread recording -------------------------------------------
    def note_idle(self) -> None:
        """The engine parked with NO work: break the timeline so the
        wait for the next request is load, not an attribution bucket."""
        self._last_note = None

    def note_step(
        self,
        kind: str,
        duration_s: float,
        *,
        batch: int = 0,
        tokens: int = 0,
        context_tokens: int = 0,
        plan_ms: float = 0.0,
        dispatch_ms: float = 0.0,
        sync_ms: float = 0.0,
        idle_gap_ms: float = 0.0,
        overlapped: bool = False,
    ) -> Optional[str]:
        """Account one engine step; returns an anomaly reason string
        when the roofline-band monitor trips (None otherwise)."""
        now = self._clock()
        interval = (
            now - self._last_note
            if self._last_note is not None
            else max(duration_s, 0.0)
        )
        self._last_note = now
        interval = max(interval, 1e-9)
        plan_s = max(0.0, plan_ms) / 1e3
        disp_s = max(0.0, dispatch_ms) / 1e3
        sync_s = max(0.0, sync_ms) / 1e3
        idle_s = max(0.0, idle_gap_ms) / 1e3

        b = dict.fromkeys(BUCKETS, 0.0)
        if overlapped:
            # device presumed busy except the measured idle gap; the
            # gap is the loss, attributed to the host spans that caused
            # it — plan first, dispatch next, the unexplained remainder
            # stays idle_gap (the host did *something* untimed: emit,
            # drain, scheduler bookkeeping)
            idle = min(idle_s, interval)
            b["plan"], b["dispatch"], b["idle_gap"] = _alloc(
                idle, plan_s, disp_s
            )
            b["sync"] = min(sync_s, interval - idle)
            device = max(0.0, interval - idle - b["sync"])
        else:
            # serial loop: plan and dispatch serialize ahead of the
            # harvest block, which is the device executing; the tail is
            # host emit/bookkeeping (queue_wait). idle_gap would double
            # count the plan/emit time and stays 0.
            plan_b, disp_b, rest = _alloc(interval, plan_s, disp_s)
            b["plan"], b["dispatch"] = plan_b, disp_b
            device = min(sync_s, rest)
            b["queue_wait"] = rest - device
        if self.roofline is not None and device > 0.0:
            frac = self.roofline.phase_fractions(
                max(batch, 1), max(context_tokens, 0)
            )
            for ph in PHASES:
                b[ph] = device * frac[ph]
        else:
            # no byte model (engine still initializing): park device
            # time under attention so the partition stays exact
            b["attention"] = device

        ideal_s = 0.0
        if (
            self.roofline is not None
            and kind in DECODE_KINDS
            and tokens > 0
            and batch > 0
        ):
            ideal_s = (
                tokens / batch
            ) * self.roofline.ideal_step_s(batch, context_tokens)
        rec = {
            "kind": kind,
            "interval_s": interval,
            "tokens": int(tokens),
            "batch": int(batch),
            "context_tokens": int(context_tokens),
            "ideal_s": ideal_s,
            "buckets": b,
        }
        with self._lock:
            self._window.append(rec)
            self.steps_noted += 1
        self._since_gauges += 1
        if self._since_gauges >= GAUGE_EVERY:
            self._since_gauges = 0
            self._refresh_gauges()
        return self._maybe_anomaly()

    # -- anomaly band -------------------------------------------------------
    def _maybe_anomaly(self) -> Optional[str]:
        self._since_check += 1
        if self._since_check < self._check_every:
            return None
        self._since_check = 0
        cur = self._short_roofline_frac()
        if cur is None:
            return None
        prev, self._trailing_checks = self._trailing_frac, self._trailing_checks + 1
        # EMA updates every check — including the anomalous one, so a
        # sustained regression becomes the new normal instead of
        # re-dumping forever (BlackBox rate-limits the burst anyway)
        self._trailing_frac = (
            cur if prev is None else 0.7 * prev + 0.3 * cur
        )
        if (
            prev is not None
            and self._trailing_checks > 3
            and prev > 1e-4
            and cur < self.anomaly_band * prev
        ):
            return (
                f"roofline_drop:frac={cur:.4f}<"
                f"{self.anomaly_band:.2f}x{prev:.4f}"
            )
        return None

    def _short_roofline_frac(self) -> Optional[float]:
        """Roofline frac over the most recent ``check_every`` decode
        records (the anomaly monitor's short window)."""
        with self._lock:
            recent = list(self._window)[-self._check_every:]
        ideal = sum(r["ideal_s"] for r in recent if r["kind"] in DECODE_KINDS)
        span = sum(
            r["interval_s"] for r in recent if r["kind"] in DECODE_KINDS
        )
        if span <= 0.0 or ideal <= 0.0:
            return None
        return ideal / span

    # -- windows / gauges / snapshots --------------------------------------
    def window_summary(self) -> dict:
        """Roll the window up: per-bucket time fractions, achieved and
        ceiling tok/s, roofline_frac, per-bucket tokens lost per second,
        and the top loss bucket."""
        with self._lock:
            recs = list(self._window)
        total = sum(r["interval_s"] for r in recs)
        out: dict = {
            "steps": len(recs),
            "span_s": round(total, 6),
            "frac": dict.fromkeys(BUCKETS, 0.0),
            "achieved_tok_s": 0.0,
            "decode_tok_s": 0.0,
            "roofline_tok_s": 0.0,
            "roofline_frac": None,
            "tokens_lost_per_s": dict.fromkeys(BUCKETS, 0.0),
            "top_loss_bucket": "",
        }
        if not recs or total <= 0.0:
            return out
        sums = dict.fromkeys(BUCKETS, 0.0)
        for r in recs:
            for k, v in r["buckets"].items():
                sums[k] += v
        out["frac"] = {k: round(v / total, 6) for k, v in sums.items()}
        tokens = sum(r["tokens"] for r in recs)
        out["achieved_tok_s"] = round(tokens / total, 3)
        dec = [r for r in recs if r["kind"] in DECODE_KINDS and r["ideal_s"] > 0]
        ideal = sum(r["ideal_s"] for r in dec)
        dec_tokens = sum(r["tokens"] for r in dec)
        dec_span = sum(r["interval_s"] for r in dec)
        if ideal > 0.0 and dec_tokens > 0 and dec_span > 0.0:
            out["roofline_tok_s"] = round(dec_tokens / ideal, 3)
            # DECODE-window ratio: decode tok/s over the decode
            # ceiling (= ideal/span). The roofline is a decode
            # ceiling, so prefill intervals must not dilute the frac —
            # a traffic-mix shift toward long prompts is not a decode
            # regression.
            out["decode_tok_s"] = round(dec_tokens / dec_span, 3)
            out["roofline_frac"] = round(ideal / dec_span, 6)
            # loss attribution: host buckets lose their whole span,
            # device phases only their time beyond the byte-bound ideal
            loss_time = dict.fromkeys(BUCKETS, 0.0)
            for r in dec:
                pf = (
                    self.roofline.phase_fractions(
                        max(r["batch"], 1), r["context_tokens"]
                    )
                    if self.roofline is not None
                    else {}
                )
                for k, v in r["buckets"].items():
                    if k in PHASES:
                        loss_time[k] += max(
                            0.0, v - r["ideal_s"] * pf.get(k, 0.0)
                        )
                    else:
                        loss_time[k] += v
            lost_tok_s = max(
                0.0, out["roofline_tok_s"] - dec_tokens / max(dec_span, 1e-9)
            )
            lt = sum(loss_time.values())
            if lt > 0.0 and lost_tok_s > 0.0:
                out["tokens_lost_per_s"] = {
                    k: round(lost_tok_s * v / lt, 3)
                    for k, v in loss_time.items()
                }
                out["top_loss_bucket"] = max(
                    loss_time, key=loss_time.get
                )
        if not out["top_loss_bucket"]:
            # no ceiling yet: the biggest non-device bucket still names
            # where host time goes
            host = {k: out["frac"][k] for k in HOST_BUCKETS}
            if any(v > 0 for v in host.values()):
                out["top_loss_bucket"] = max(host, key=host.get)
        return out

    def summary_cached(self) -> dict:
        """The last gauge-refresh's window summary (recomputed every
        GAUGE_EVERY steps); computes once when nothing has rolled up
        yet. Per-request readers use this; snapshot endpoints roll a
        fresh window."""
        w = self._last_summary
        if w is None:
            w = self.window_summary()
            self._last_summary = w  # dynalint: handoff=idempotent cache fill — whole-dict swap is atomic under the GIL, any thread's computed summary is valid
        return w

    def _refresh_gauges(self) -> None:
        w = self.window_summary()
        self._last_summary = w
        for k in BUCKETS:
            STEP_TIME_FRAC.labels(k).set(w["frac"][k])
            TOKENS_LOST_PER_S.labels(k).set(w["tokens_lost_per_s"][k])
        if w["roofline_frac"] is not None:
            ROOFLINE_FRAC.set(w["roofline_frac"])

    def refresh_gauges(self) -> None:
        """Public refresh for snapshot paths (the engine's per-step
        refresh is sampled every GAUGE_EVERY steps)."""
        self._refresh_gauges()

    def snapshot(self, recent: int = 8) -> dict:
        """JSON-able state for /debug/attribution and /debug/state."""
        with self._lock:
            tail = list(self._window)[-max(0, recent):]
        return {
            "configured": self.roofline is not None,
            "steps_noted": self.steps_noted,
            "anomaly_band": self.anomaly_band,
            "trailing_roofline_frac": self._trailing_frac,
            "window": self.window_summary(),
            "recent": [
                {
                    "kind": r["kind"],
                    "interval_ms": round(r["interval_s"] * 1e3, 3),
                    "tokens": r["tokens"],
                    "batch": r["batch"],
                    "buckets_ms": {
                        k: round(v * 1e3, 3)
                        for k, v in r["buckets"].items()
                        if v > 0.0
                    },
                }
                for r in tail
            ],
        }


# ---------------------------------------------------------------------------
# Black-box capture: one timestamped dir with everything an incident needs
# ---------------------------------------------------------------------------
class BlackBox:
    """Anomaly-triggered forensic bundle. One ``trigger(reason)`` writes
    a ``dynamo_blackbox_<pid>_<seq>/`` dir containing:

    - ``meta.json`` — reason, timestamps, pid;
    - ``attribution.json`` — the ledger window + recent per-step rows;
    - ``flight.jsonl`` — the flight recorder's ring (snapshotted
      directly: the recorder's own rate limiter must not starve the
      black box, and vice versa);
    - ``state.json`` — the full ``/debug/state`` snapshot;
    - ``profile/`` — optional short ``jax.profiler`` capture
      (``DYN_BLACKBOX_PROFILE_MS``; 0 = off — it blocks the calling
      thread for the capture span, so it is opt-in).

    Rate-limited (``min_interval_s``, default ``DYN_BLACKBOX_INTERVAL_S``
    or 60 s) and disk-capped (``max_dumps`` dirs, oldest pruned) so a
    flapping anomaly produces exactly one bundle per window, not a
    disk-write loop. Dumps count in
    ``dynamo_blackbox_dumps_total{reason}``.

    Threading: ``trigger()`` runs on the ENGINE thread (it is called
    from ``_record_step``), so it only *snapshots* — in-memory dict
    builds over bounded structures — and hands serialization + disk
    I/O (+ the optional profiler capture) to a background writer
    thread. A slow or networked disk must not stall every in-flight
    request's next token exactly during the incident being captured.
    ``flush()`` joins the writer (tests, shutdown paths).
    """

    def __init__(
        self,
        recorder=None,
        ledger: Optional[AttributionLedger] = None,
        dump_dir: str = "",
        min_interval_s: Optional[float] = None,
        max_dumps: int = 8,
        clock: Callable[[], float] = time.monotonic,
        profile_ms: Optional[int] = None,
    ):
        self.recorder = recorder
        self.ledger = ledger
        self.dump_dir = (
            dump_dir
            or os.environ.get("DYN_BLACKBOX_DIR")
            or os.environ.get("DYN_FLIGHT_DIR")
            or tempfile.gettempdir()
        )
        if min_interval_s is None:
            try:
                min_interval_s = float(
                    os.environ.get("DYN_BLACKBOX_INTERVAL_S", "60")
                )
            except ValueError:
                min_interval_s = 60.0
        self.min_interval_s = min_interval_s
        if profile_ms is None:
            try:
                profile_ms = int(
                    os.environ.get("DYN_BLACKBOX_PROFILE_MS", "0")
                )
            except ValueError:
                profile_ms = 0
        self.profile_ms = max(0, profile_ms)
        self._clock = clock
        self._lock = threading.Lock()
        self._last: float = -float("inf")
        self._seq = 0
        self._dirs: deque = deque(maxlen=max(1, max_dumps))
        self._writer: Optional[threading.Thread] = None
        self.dumps_written = 0
        self.last_dump_dir: Optional[str] = None
        self.triggers_suppressed = 0

    def trigger(self, reason: str) -> Optional[str]:
        """Snapshot one bundle and enqueue its write (or None when
        rate-limited). Returns the bundle dir the writer is filling."""
        now = self._clock()
        with self._lock:
            if now - self._last < self.min_interval_s:
                self.triggers_suppressed += 1
                return None
            self._last = now
            self._seq += 1
            seq = self._seq
        d = os.path.join(
            self.dump_dir, f"dynamo_blackbox_{os.getpid()}_{seq:03d}"
        )
        # SNAPSHOT on the calling (engine) thread: bounded in-memory
        # dict builds only — the ring is <= capacity records, the
        # ledger window <= 512 rows
        files: dict[str, object] = {
            "meta.json": {
                "blackbox_dump": True,
                "reason": reason,
                "ts": time.time(),
                "pid": os.getpid(),
            },
        }
        if self.ledger is not None:
            files["attribution.json"] = self.ledger.snapshot(recent=64)
        if self.recorder is not None:
            files["flight.jsonl"] = [
                {
                    "flight_recorder_dump": True,
                    "reason": f"blackbox:{reason}",
                    "ts": time.time(),
                    "pid": os.getpid(),
                },
                *self.recorder.snapshot(self.recorder.capacity),
            ]
        try:
            # full introspection snapshot — imported lazily to keep the
            # module dependency-light for unit tests
            from dynamo_tpu.telemetry.debug import collect_debug_state

            files["state.json"] = collect_debug_state()
        except Exception:
            log.exception("black-box state snapshot failed")
        writer = threading.Thread(
            target=self._write_bundle, args=(d, files, reason, now),
            name="blackbox-writer", daemon=True,
        )
        with self._lock:
            self._writer = writer
        writer.start()
        return d

    def flush(self, timeout: float = 10.0) -> None:
        """Join the in-flight bundle write (tests/shutdown)."""
        with self._lock:
            writer = self._writer
        if writer is not None:
            writer.join(timeout)

    def _write_bundle(
        self, d: str, files: dict, reason: str, armed_at: float
    ) -> None:
        """Serialize + write one snapshotted bundle — background thread
        (plus the optional blocking profiler capture)."""
        try:
            os.makedirs(d, exist_ok=True)
            for name, payload in files.items():
                with open(os.path.join(d, name), "w") as f:
                    if name.endswith(".jsonl"):
                        for rec in payload:  # type: ignore[union-attr]
                            f.write(json.dumps(rec) + "\n")
                    else:
                        json.dump(payload, f, default=str)
            if self.profile_ms > 0:
                self._capture_profile(os.path.join(d, "profile"))
        except OSError:
            log.exception("black-box dump to %s failed", d)
            with self._lock:
                if self._last == armed_at:
                    # nothing persisted: the next trigger should retry
                    self._last = -float("inf")
            return
        evict: Optional[str] = None
        with self._lock:
            self.dumps_written += 1
            self.last_dump_dir = d
            if len(self._dirs) == self._dirs.maxlen:
                evict = self._dirs[0]
            self._dirs.append(d)
        if evict is not None:
            _rmtree_quiet(evict)
        BLACKBOX_DUMPS.labels(reason.split(":", 1)[0]).inc()
        log.warning("black-box bundle written to %s (%s)", d, reason)

    def _capture_profile(self, out_dir: str) -> None:
        """Blocking jax.profiler capture — opt-in and short; a failure
        (a ``/debug/profile`` capture holding the one profiler among
        them) degrades to a bundle without the profile."""
        from dynamo_tpu.telemetry.debug import profile_blocking

        try:
            profile_blocking(self.profile_ms, out_dir)
        except Exception:
            log.exception("black-box profiler capture failed")

    def stats(self) -> dict:
        with self._lock:
            return {
                "dumps": self.dumps_written,
                "last_dump_dir": self.last_dump_dir,
                "suppressed": self.triggers_suppressed,
                "min_interval_s": self.min_interval_s,
                "dump_dir": self.dump_dir,
                "profile_ms": self.profile_ms,
            }


def _rmtree_quiet(path: str) -> None:
    import shutil

    try:
        shutil.rmtree(path)
    except OSError:
        pass  # already gone / external cleanup: cap still holds


# ---------------------------------------------------------------------------
# /debug/attribution provider registry — the SAME machinery as
# /debug/state (telemetry/debug.py ProviderRegistry), second instance
# ---------------------------------------------------------------------------
from dynamo_tpu.telemetry.debug import ProviderRegistry  # noqa: E402

_ATTR_PROVIDERS = ProviderRegistry("attribution")


def register_attribution_provider(name: str, fn: Callable[[], dict]) -> None:
    _ATTR_PROVIDERS.register(name, fn)


def unregister_attribution_provider(
    name: str, fn: Optional[Callable[[], dict]] = None
) -> None:
    _ATTR_PROVIDERS.unregister(name, fn)


def collect_attribution() -> dict:
    """One JSON-able snapshot for ``/debug/attribution`` — a provider
    that raises degrades to an error stanza (introspection must keep
    working exactly when things are broken)."""
    return _ATTR_PROVIDERS.collect()
