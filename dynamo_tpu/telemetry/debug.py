"""Live-introspection plumbing behind the ``/debug/*`` endpoints.

A process-global registry of *debug-state providers*: any subsystem
that can describe "what am I doing right now" registers a zero-arg
callable returning a JSON-able dict (the engine registers its
scheduler/KV-pool/flight-recorder snapshot; a metrics service registers
its aggregator view). ``collect_debug_state()`` assembles one snapshot
— a provider that raises contributes an ``{"error": ...}`` stanza
instead of breaking the endpoint (introspection must keep working
exactly when things are broken).

``capture_profile()`` backs ``/debug/profile?ms=N``: an on-demand
``jax.profiler`` capture written where TensorBoard/Perfetto can load it
(the profiler emits ``plugins/profile/*/trace.json.gz`` under the
output dir — load it at https://ui.perfetto.dev). One capture at a
time per process, whoever asks (``profile_blocking`` is the one function
that starts and stops the profiler; the black box's capture goes
through it too); a second request gets a busy error. The profiler runs
in a worker thread: ``stop_trace`` serialises the whole trace, seconds
during which the event loop must keep serving.

Beside each trace a capture writes ``program_spans.json``: the request
spans the process holds in memory (telemetry/spans.py ``SpanBuffer``),
the two clocks at ``start_trace``'s return and ``stop_trace``'s call
(``start`` / ``stop``), the program's cumulative counts at those two
instants (``program counts`` providers: the engine's steps dispatched by
kind, prompt tokens admitted and served from cache, preemptions), the two
clocks again at ``stop_trace``'s RETURN (``end``: the seconds it spends
serialising the trace are not untraced time), and ``history``: the
host-side counts a step loop noted once a second since the process began
(``note_counts``; the newest ``HISTORY_LEN`` entries), capture or no
capture. A process that took a capture writes the file again when it
exits cleanly, so the copy a reader finds after shutdown covers every
request the process finished and every second it served.
"""

from __future__ import annotations

import asyncio
import atexit
import json
import logging
import os
import tempfile
import threading
import time
from collections import deque
from typing import Callable, Optional

from dynamo_tpu.telemetry import spans

log = logging.getLogger("dynamo_tpu.telemetry.debug")

class ProviderRegistry:
    """Named zero-arg snapshot providers behind one lock — the shape
    ``/debug/state``, ``/debug/hostplane`` and ``/debug/requests`` share
    (one implementation so fixes to the identity-checked unregister or
    the error-stanza collect can't drift between them).

    Cross-thread contract (dynalint DL103 vocabulary, docs/
    static_analysis.md): written from the event loop (engines
    registering at launch) AND read/written from arbitrary threads
    (debug endpoints, shutdown paths) — the lock is the declared
    handoff; every access takes it.
    """

    def __init__(self, what: str):
        self._what = what
        self._providers: dict[str, Callable[[], dict]] = {}
        self._lock = threading.Lock()

    def register(self, name: str, fn: Callable[[], dict]) -> None:
        """Register (or replace) a named snapshot provider."""
        with self._lock:
            self._providers[name] = fn

    def unregister(
        self, name: str, fn: Optional[Callable[[], dict]] = None
    ) -> None:
        """Remove a provider; with ``fn`` given, only if it is still
        the registered one (an engine shutting down must not yank a
        newer engine's registration)."""
        with self._lock:
            # == (not `is`): bound methods are fresh objects per
            # attribute access but compare equal for the same
            # instance+function
            if fn is None or self._providers.get(name) == fn:
                self._providers.pop(name, None)

    def names(self) -> list[str]:
        with self._lock:
            return sorted(self._providers)

    def collect(self) -> dict:
        """One JSON-able snapshot across every registered provider."""
        with self._lock:
            providers = dict(self._providers)
        out: dict = {"ts": time.time(), "pid": os.getpid()}
        for name, fn in sorted(providers.items()):
            try:
                out[name] = fn()
            except Exception as exc:
                # the snapshot reads live structures without stopping
                # the world — a torn read must degrade to an error
                # stanza, not a 500 on the one endpoint you need
                # during an incident
                log.exception("%s provider %r failed", self._what, name)
                out[name] = {"error": f"{type(exc).__name__}: {exc}"}
        return out


_DEBUG_PROVIDERS = ProviderRegistry("debug")
# cumulative counts read at a capture's two edges (program_spans.json)
_COUNT_PROVIDERS = ProviderRegistry("program counts")

# one jax.profiler capture at a time (the profiler itself is global)
_profile_lock = threading.Lock()
_profile_seq = 0
# the newest capture's program_spans.json: path, and what was read at
# the capture's edges — rewritten with the spans finished since, at exit
_last_capture: Optional[dict] = None

MAX_PROFILE_MS = 30_000
PROGRAM_SPANS_FILE = "program_spans.json"

# the count history: ``{"monotonic_ns", "counts": {provider: counts}}``,
# one entry a second a step loop (17 minutes of one engine). Appended by
# the loop's own thread and copied by whoever writes the span file: a
# deque's append and its copy are each one step under the interpreter
# lock, so the history takes no lock of its own and none the event loop
# takes.
HISTORY_LEN = 1024
_history: deque = deque(maxlen=HISTORY_LEN)


def register_debug_provider(name: str, fn: Callable[[], dict]) -> None:
    _DEBUG_PROVIDERS.register(name, fn)


def unregister_debug_provider(
    name: str, fn: Optional[Callable[[], dict]] = None
) -> None:
    _DEBUG_PROVIDERS.unregister(name, fn)


def debug_provider_names() -> list[str]:
    return _DEBUG_PROVIDERS.names()


def collect_debug_state() -> dict:
    return _DEBUG_PROVIDERS.collect()


def register_count_provider(name: str, fn: Callable[[], dict]) -> None:
    _COUNT_PROVIDERS.register(name, fn)


def unregister_count_provider(
    name: str, fn: Optional[Callable[[], dict]] = None
) -> None:
    _COUNT_PROVIDERS.unregister(name, fn)


def note_counts(name: str, counts: dict, monotonic_ns: int) -> None:
    """One entry of the count history: ``counts`` are numbers the caller
    has WITHOUT WAITING (a step loop between two steps: host-side counts,
    and a family's device counts as LAST READ at a capture's edge — no
    device read, no lock)."""
    _history.append({"monotonic_ns": monotonic_ns, "counts": {name: counts}})


def count_history() -> list[dict]:
    return list(_history)


def _edge() -> dict:
    """Both clocks and the program's cumulative counts, now."""
    return {"monotonic_ns": time.monotonic_ns(), "time_ns": time.time_ns(),
            "counts": _COUNT_PROVIDERS.collect()}


def write_program_spans(written: str) -> Optional[str]:
    """(Re)write the newest capture's ``program_spans.json`` (atomic
    replace) with the spans in memory now; the path, or None when this
    process took no capture."""
    cap = _last_capture
    if cap is None:
        return None
    buffer = spans.get_tracer().buffer
    kept, dropped = buffer.snapshot() if buffer is not None else ([], 0)
    doc = {"written": written, "pid": os.getpid(), "spans": kept,
           "dropped": dropped, "start": cap["start"], "stop": cap["stop"],
           "end": cap["end"], "history": count_history()}
    tmp = cap["path"] + ".tmp"
    with open(tmp, "w") as f:
        json.dump(doc, f, default=str)
    os.replace(tmp, cap["path"])
    return cap["path"]


def _write_program_spans_at_exit() -> None:
    try:
        write_program_spans("shutdown")
    except OSError:  # the directory may be gone; exiting anyway
        log.debug("program_spans.json not rewritten at exit", exc_info=True)


def profile_blocking(ms: int, out_dir: str = "") -> dict:
    """One profiler session, blocking: ``start_trace``, ``ms``
    milliseconds, ``stop_trace``, ``program_spans.json``. Call from a
    thread that may block for seconds. Raises RuntimeError when a
    capture is already running."""
    global _profile_seq, _last_capture
    ms = max(1, min(int(ms), MAX_PROFILE_MS))
    if not _profile_lock.acquire(blocking=False):
        raise RuntimeError("a profile capture is already running")
    try:
        import jax

        _profile_seq += 1
        d = out_dir or os.path.join(
            os.environ.get("DYN_PROFILE_DIR") or tempfile.gettempdir(),
            f"dynamo_profile_{os.getpid()}_{_profile_seq:03d}",
        )
        os.makedirs(d, exist_ok=True)
        jax.profiler.start_trace(d)
        try:
            started = _edge()
            spans.set_capture_live(True)
            time.sleep(ms / 1000.0)
        finally:
            spans.set_capture_live(False)
            stopped = _edge()
            jax.profiler.stop_trace()
        ended = {"monotonic_ns": time.monotonic_ns(), "time_ns": time.time_ns()}
        if _last_capture is None:
            atexit.register(_write_program_spans_at_exit)
        _last_capture = {"path": os.path.join(d, PROGRAM_SPANS_FILE),
                         "start": started, "stop": stopped, "end": ended}
        write_program_spans("capture_end")
        log.info("profiler capture (%d ms) -> %s", ms, d)
        return {"trace_dir": d, "duration_ms": ms}
    finally:
        _profile_lock.release()


async def capture_profile(ms: int, out_dir: str = "") -> dict:
    """Run ``jax.profiler`` for ``ms`` milliseconds off the event loop;
    returns ``{"trace_dir", "duration_ms"}`` (raises RuntimeError when a
    capture is already running or the profiler is unavailable)."""
    return await asyncio.to_thread(profile_blocking, ms, out_dir)
