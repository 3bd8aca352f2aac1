"""Black-box capture: one timestamped dir with everything an incident
needs.

When an anomaly trips (a slow-step or idle-gap watchdog dump, a
serve-phase compile or implicit transfer, an SLO miss, a frontend loop
stall) ``BlackBox`` bundles the flight recorder's ring and the full
``/debug/state`` snapshot into one dump dir, so the incident is
preserved while it happens instead of reconstructed a week later.
"""

from __future__ import annotations

import json
import logging
import os
import shutil
import tempfile
import threading
import time
from collections import deque
from typing import Callable, Optional

from dynamo_tpu.telemetry.instruments import BLACKBOX_DUMPS

log = logging.getLogger("dynamo_tpu.telemetry.blackbox")


class BlackBox:
    """Anomaly-triggered forensic bundle. One ``trigger(reason)`` writes
    a ``dynamo_blackbox_<pid>_<seq>/`` dir containing:

    - ``meta.json`` — reason, timestamps, pid;
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
        dump_dir: str = "",
        min_interval_s: Optional[float] = None,
        max_dumps: int = 8,
        clock: Callable[[], float] = time.monotonic,
        profile_ms: Optional[int] = None,
    ):
        self.recorder = recorder
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
        # dict builds only — the ring is <= capacity records
        files: dict[str, object] = {
            "meta.json": {
                "blackbox_dump": True,
                "reason": reason,
                "ts": time.time(),
                "pid": os.getpid(),
            },
        }
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
    try:
        shutil.rmtree(path)
    except OSError:
        pass  # already gone / external cleanup: cap still holds
