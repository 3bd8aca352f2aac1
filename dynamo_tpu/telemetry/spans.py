"""Spans + trace-context propagation for the disaggregated serving path.

Analogue of the reference's ``tracing``-subscriber spans (reference:
lib/runtime/src/logging.rs span layers): every request produces ONE
connected trace through HTTP frontend → preprocessor → router → worker
→ engine → disagg prefill → KV transfer, joined by a ``trace_id`` that
rides the existing transport (runtime/service.py ``ctx`` wire dict and
disagg/protocols.py ``RemotePrefillRequest.trace``).

Design constraints (ISSUE 2 acceptance: bench throughput within noise):

- **No exporter ⇒ near-zero cost.** ``Tracer.enabled`` is a plain bool
  checked before any span allocation; the disabled path returns the
  shared ``NULL_SPAN`` singleton whose methods are no-ops.
- **Dependency-free.** Stdlib only; JSONL lines are plain dicts.
- **Thread-safe export.** The engine step thread and the asyncio loop
  both finish spans; exporters serialize behind one lock.

Timing model: every span carries TWO starts taken at the same instant.
``start`` is wall-clock (``time.time()``) so spans from different
processes on one machine order/nest correctly; ``start_mono_ns`` is
``time.monotonic_ns()`` (CLOCK_MONOTONIC, one per host), the clock a load
generator's window and the profiler capture's edges are stamped on
(``program_spans.json``, telemetry/debug.py). ``duration_s`` is measured
on the monotonic clock so it never goes negative under NTP slew.
``Tracer.record()`` builds a span from explicit timestamps for code that
only learns span boundaries after the fact (the engine emits
queue-wait/prefill/decode spans at finish time from the scheduler's
monotonic stamps, passed as they are).

Sinks, all behind ``Tracer._export``: the JSONL file (``DYN_TRACE_FILE``)
and the in-memory ``SpanBuffer`` every serving process keeps
(``Tracer.keep_in_memory``; cli ``run``), which a profiler capture writes
beside its trace. Per-STEP phases are not spans of this stream: they are
counted always and annotated inside a capture, through ``step_span``
(bottom of this file).

Env knobs:
  DYN_TRACE_FILE    append finished spans as JSONL here (enables tracing)
  DYN_TRACE_SAMPLE  root-trace sampling fraction in [0, 1] (default 1.0);
                    a propagated inbound context is always recorded — the
                    head made the sampling decision for the whole trace
"""

from __future__ import annotations

import json
import logging
import os
import random
import threading
import time
import uuid
from collections import deque
from typing import Any, Optional

log = logging.getLogger("dynamo_tpu.telemetry")


def new_trace_id() -> str:
    return uuid.uuid4().hex  # 32 hex chars (128-bit), W3C-sized


def new_span_id() -> str:
    return uuid.uuid4().hex[:16]  # 16 hex chars (64-bit)


class Span:
    """One timed operation. Create via ``Tracer.span()``; finish with
    ``end()`` or a ``with`` block. Attributes must be scalar-ish (they
    land in JSONL verbatim)."""

    __slots__ = (
        "name", "trace_id", "span_id", "parent_id", "start",
        "start_mono_ns", "duration_s", "attrs", "_tracer", "_ended",
    )

    def __init__(
        self,
        tracer: "Tracer",
        name: str,
        trace_id: str,
        parent_id: Optional[str],
        attrs: Optional[dict] = None,
    ):
        self._tracer = tracer
        self.name = name
        self.trace_id = trace_id
        self.span_id = new_span_id()
        self.parent_id = parent_id
        self.start = time.time()
        self.start_mono_ns = time.monotonic_ns()
        self.duration_s: Optional[float] = None
        self.attrs: dict = dict(attrs) if attrs else {}
        self._ended = False

    # -- recording ---------------------------------------------------------
    def set_attr(self, key: str, value: Any) -> None:
        self.attrs[key] = value

    def end(self) -> None:
        if self._ended:
            return
        self._ended = True
        self.duration_s = (time.monotonic_ns() - self.start_mono_ns) / 1e9
        self._tracer._export(self)

    # -- propagation -------------------------------------------------------
    def trace_context(self) -> dict:
        """The dict that rides the wire to link downstream spans."""
        return {"trace_id": self.trace_id, "span_id": self.span_id}

    def to_dict(self) -> dict:
        out = {
            "name": self.name,
            "trace_id": self.trace_id,
            "span_id": self.span_id,
            "start": self.start,
            "start_mono_ns": self.start_mono_ns,
            "duration_s": self.duration_s,
        }
        if self.parent_id:
            out["parent_id"] = self.parent_id
        if self.attrs:
            out["attrs"] = self.attrs
        return out

    def __enter__(self) -> "Span":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        if exc_type is not None and "error" not in self.attrs:
            self.attrs["error"] = exc_type.__name__
        self.end()


class _NullSpan:
    """Shared no-op span: the disabled-tracing fast path. Carries no
    identity, exports nothing, propagates nothing."""

    __slots__ = ()
    name = ""
    trace_id = ""
    span_id = ""
    parent_id = None
    duration_s = None
    attrs: dict = {}

    def set_attr(self, key: str, value: Any) -> None:
        pass

    def end(self) -> None:
        pass

    def trace_context(self) -> Optional[dict]:
        return None

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        pass

    def __bool__(self) -> bool:
        return False


NULL_SPAN = _NullSpan()


class JsonlSpanExporter:
    """One JSON object per finished span, appended to a file. The file
    handle opens lazily (first span) so merely constructing a tracer
    never touches the filesystem."""

    def __init__(self, path: str):
        self.path = path
        self._lock = threading.Lock()
        self._fh = None

    def export(self, span: Span) -> None:
        line = json.dumps(span.to_dict()) + "\n"
        with self._lock:
            if self._fh is None:
                self._fh = open(self.path, "a")
            self._fh.write(line)
            self._fh.flush()  # spans must survive SIGTERM'd fleets

    def close(self) -> None:
        with self._lock:
            if self._fh is not None:
                self._fh.close()
                self._fh = None


class SpanBuffer:
    """The in-memory sink: the newest ``capacity`` finished spans, and a
    count of those that fell off the far end. What a profiler capture
    writes beside its trace (telemetry/debug.py)."""

    CAPACITY = 16_384

    def __init__(self, capacity: int = CAPACITY):
        self._spans: deque = deque(maxlen=capacity)
        self._lock = threading.Lock()
        self.dropped = 0

    def export(self, span: Span) -> None:
        with self._lock:
            if len(self._spans) == self._spans.maxlen:
                self.dropped += 1
            self._spans.append(span)

    def snapshot(self) -> tuple[list[dict], int]:
        """(the buffered spans as dicts, oldest first; spans dropped)."""
        with self._lock:
            spans, dropped = list(self._spans), self.dropped
        return [s.to_dict() for s in spans], dropped


class Tracer:
    """Process-local span factory + exporter fan-out.

    ``enabled`` is the cheap gate callers may consult before computing
    span attributes; ``span()`` itself also degrades to ``NULL_SPAN``
    when disabled, so un-gated call sites stay correct (just marginally
    less cheap).
    """

    def __init__(self, sample: Optional[float] = None):
        self._exporters: list = []
        self._lock = threading.Lock()
        if sample is None:
            try:
                sample = float(os.environ.get("DYN_TRACE_SAMPLE", "1.0"))
            except ValueError:
                sample = 1.0
        self.sample = min(1.0, max(0.0, sample))
        self.buffer: Optional[SpanBuffer] = None

    @property
    def enabled(self) -> bool:
        return bool(self._exporters)

    def keep_in_memory(self) -> SpanBuffer:
        """Attach the in-memory sink (once; later calls return it)."""
        with self._lock:
            if self.buffer is None:
                self.buffer = SpanBuffer()
                self._exporters.append(self.buffer)
            return self.buffer

    def add_exporter(self, exporter: Any) -> None:
        with self._lock:
            self._exporters.append(exporter)

    def remove_exporter(self, exporter: Any) -> None:
        with self._lock:
            if exporter in self._exporters:
                self._exporters.remove(exporter)

    # -- span creation -----------------------------------------------------
    def span(
        self,
        name: str,
        parent: Any = None,
        attrs: Optional[dict] = None,
    ):
        """Start a span.

        ``parent`` may be a ``Span``, a trace-context dict
        (``{"trace_id", "span_id"}``), anything exposing
        ``trace_context()`` (e.g. runtime ``Context``), or None for a
        new root. Roots are subject to sampling; spans continuing an
        inbound context are always recorded (the head sampled for the
        whole trace), and an inbound ``{"sampled": False}`` mark —
        the head's negative decision — suppresses the span here too
        rather than starting an orphan root.
        """
        if not self._exporters:
            return NULL_SPAN
        ctx = _as_trace_context(parent)
        if ctx is _SAMPLED_OUT:
            return NULL_SPAN
        if ctx is None:
            if self.sample < 1.0 and random.random() >= self.sample:
                return NULL_SPAN
            return Span(self, name, new_trace_id(), None, attrs)
        return Span(self, name, ctx["trace_id"], ctx.get("span_id"), attrs)

    def record(
        self,
        name: str,
        start: Optional[float] = None,
        duration_s: float = 0.0,
        parent: Any = None,
        attrs: Optional[dict] = None,
        start_mono: Optional[float] = None,
    ) -> Optional[str]:
        """Record a span whose boundaries are already known: a start on
        either clock (``start_mono``: ``time.monotonic()`` seconds, the
        stamp engine code holds, kept as given; ``start``: wall clock)
        and a duration. The other clock's start is the same instant,
        carried over by the two clocks' present offset. Returns its
        span_id, or None when tracing is disabled/unsampled."""
        if not self._exporters:
            return None
        ctx = _as_trace_context(parent)
        if ctx is _SAMPLED_OUT:
            return None
        if ctx is None and self.sample < 1.0 and random.random() >= self.sample:
            return None
        span = Span.__new__(Span)
        span._tracer = self
        span.name = name
        span.trace_id = ctx["trace_id"] if ctx else new_trace_id()
        span.span_id = new_span_id()
        span.parent_id = ctx.get("span_id") if ctx else None
        if start_mono is not None:
            span.start_mono_ns = int(start_mono * 1e9)
            if start is None:
                start = time.time() - (time.monotonic() - start_mono)
        elif start is not None:
            span.start_mono_ns = time.monotonic_ns() - int(
                (time.time() - start) * 1e9
            )
        else:
            raise ValueError("record() needs start or start_mono")
        span.start = start
        span.duration_s = max(0.0, duration_s)
        span.attrs = dict(attrs) if attrs else {}
        span._ended = True
        self._export(span)
        return span.span_id

    def _export(self, span: Span) -> None:
        for exporter in self._exporters:
            try:
                exporter.export(span)
            except Exception:  # a broken sink must not fail the request
                log.exception("span exporter failed")


# sentinel: the trace head explicitly sampled this request OUT
_SAMPLED_OUT: dict = {"sampled": False}


def _as_trace_context(parent: Any) -> Optional[dict]:
    if parent is None:
        return None
    if isinstance(parent, Span):
        return parent.trace_context()
    if isinstance(parent, _NullSpan):
        return None
    if isinstance(parent, dict):
        ctx = parent
    else:
        tc = getattr(parent, "trace_context", None)
        if not callable(tc):
            return None
        ctx = tc()
    if not ctx:
        return None
    if ctx.get("sampled") is False:
        return _SAMPLED_OUT
    return ctx if ctx.get("trace_id") else None


# -- process-global tracer (≈ tracing's global subscriber) ------------------
_TRACER: Optional[Tracer] = None
_TRACER_LOCK = threading.Lock()


def get_tracer() -> Tracer:
    """The process tracer. First call wires the ``DYN_TRACE_FILE`` JSONL
    exporter if the env var is set; without it the tracer stays disabled
    (every span is ``NULL_SPAN``)."""
    global _TRACER
    if _TRACER is None:
        with _TRACER_LOCK:
            if _TRACER is None:
                tracer = Tracer()
                path = os.environ.get("DYN_TRACE_FILE")
                if path:
                    tracer.add_exporter(JsonlSpanExporter(path))
                _TRACER = tracer
    return _TRACER


def reset_tracer() -> None:
    """Drop the global tracer (tests re-read DYN_TRACE_FILE)."""
    global _TRACER
    with _TRACER_LOCK:
        _TRACER = None


def propagation_context(span: Any, inbound: Any = None) -> Optional[dict]:
    """The trace dict to ship downstream from a boundary — the ONE
    implementation of the propagation rules every traced hop needs:

    - a real local span → its context (downstream nests under it);
    - a NULL local span with an inbound context → the inbound dict
      passed through verbatim (a hop without its own exporter must not
      break continuity; an inbound ``{"sampled": False}`` mark keeps
      propagating);
    - a NULL local span, no inbound, local tracer enabled → we are the
      trace head and sampling dropped the root: propagate the explicit
      negative mark so downstream tracers stay quiet;
    - tracing disabled everywhere → None (no decision was made).

    ``inbound`` may be a trace dict, a runtime ``Context``, or anything
    exposing ``trace_context()``.
    """
    ctx = span.trace_context() if span is not None else None
    if ctx:
        return ctx
    if inbound is not None:
        if isinstance(inbound, dict):
            in_ctx = inbound
        else:
            tc = getattr(inbound, "trace_context", None)
            in_ctx = tc() if callable(tc) else None
        if in_ctx:
            return in_ctx
    if get_tracer().enabled:
        return {"sampled": False}
    return None


# -- step phases: counted always, annotated inside a capture ------------------
# The engine's step loops mark their phases (``dyn.step.plan`` / ``pack`` /
# ``dispatch`` / ``harvest`` / ``emit`` / ``record`` / ``wait``) with
# ``step_span``. A phase is per STEP, not per request, so it never enters
# the span stream above. It is clocked ALWAYS: the thread's ``StepClock``
# keeps one preallocated ``StepPhase`` a name, which reads the monotonic
# clock on entry and exit and the thread's CPU clock on exit and adds to
# its cumulative ``wall_ns`` / ``cpu_ns`` / ``calls`` (phases do not nest,
# so nothing is allocated a step). Inside a capture a phase ALSO enters a
# ``jax.profiler.TraceAnnotation`` of its name and attributes, which lands
# in the capture's ``.xplane.pb`` on the thread that made it, on the clock
# of the device's own lines; telemetry/debug.py turns that switch around
# each capture. docs/observability.md "Step phases".
PHASE_PREFIX = "dyn.step."
PHASES = ("plan", "pack", "dispatch", "harvest", "emit", "record", "wait")
# the phases in which the engine thread WORKS (``harvest`` waits for the
# device, ``wait`` for a request): their wall less their thread CPU is
# time the thread stood there without running
HOST_WORK = ("plan", "pack", "dispatch", "emit", "record")
HISTORY_TICK_NS = 1_000_000_000
_capture_live = False
_annotation: Any = None
_monotonic_ns = time.monotonic_ns
_thread_time_ns = time.thread_time_ns


def set_capture_live(live: bool) -> None:
    """Called by the one function that starts and stops the profiler
    (telemetry/debug.py ``profile_blocking``)."""
    global _capture_live, _annotation
    if live and _annotation is None:
        # lazily: a frontend-only process never imports JAX for this
        from jax.profiler import TraceAnnotation

        _annotation = TraceAnnotation
    _capture_live = live


class StepPhase:
    """One phase of a step loop, entered with ``with``; owned by one
    thread's ``StepClock``. ``last_ns`` is the wall of the newest pass:
    the flight recorder's ``plan_ms`` / ``dispatch_ms`` / ``sync_ms`` are
    read from it, so a step has ONE clocking."""

    __slots__ = ("name", "attrs", "wall_ns", "cpu_ns", "calls", "last_ns",
                 "_clock", "_t0", "_ann")

    def __init__(self, clock: "StepClock", name: str):
        self._clock = clock
        self.name = name
        self.attrs: dict = {}
        self.wall_ns = self.cpu_ns = self.calls = self.last_ns = 0
        self._t0 = 0
        self._ann: Any = None

    @property
    def ms(self) -> float:
        """The newest pass, as the flight recorder spells a duration."""
        return round(self.last_ns / 1e6, 3)

    def __enter__(self) -> "StepPhase":
        if _capture_live:
            self._ann = _annotation(self.name, **self.attrs)
            self._ann.__enter__()
        self._t0 = _monotonic_ns()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        # ONE read of the thread's CPU clock a pass (a syscall: 0.3 us on
        # a bare kernel, 6 us where syscalls are sandboxed): the phases
        # tile the loop, so this pass's CPU is the clock's growth since
        # the pass before ended (what ran between the two, under no phase,
        # is charged here: unphased_ns says how little that is)
        clock = self._clock
        cpu = _thread_time_ns()
        self.cpu_ns += cpu - clock._cpu_mark
        clock._cpu_mark = cpu
        self.last_ns = _monotonic_ns() - self._t0
        self.wall_ns += self.last_ns
        self.calls += 1
        ann = self._ann
        if ann is not None:
            self._ann = None
            ann.__exit__(exc_type, exc, tb)


class _DispatchPhase(StepPhase):
    """``dispatch`` also counts by ``kind`` and closes the period of the
    dispatch before it. ``drained`` is what the device answered just
    before this one (set by the engine, read once)."""

    __slots__ = ("drained",)

    def __init__(self, clock: "StepClock", name: str):
        super().__init__(clock, name)
        self.drained = False

    def __enter__(self) -> "StepPhase":
        StepPhase.__enter__(self)
        self._clock._note_dispatch(
            self.attrs.get("kind") or "step", self._t0, self.drained)
        self.drained = False
        return self


class _TickPhase(StepPhase):
    """``record`` and ``wait`` end with the once-a-second history tick."""

    __slots__ = ()

    def __exit__(self, exc_type, exc, tb) -> None:
        StepPhase.__exit__(self, exc_type, exc, tb)
        now = self._t0 + self.last_ns
        if now - self._clock.ticked_ns >= HISTORY_TICK_NS:
            self._clock.tick(now)


class StepClock:
    """What one step-loop thread counts, cumulatively and always:

    - ``phases[name]``: ``wall_ns`` / ``cpu_ns`` / ``calls`` of every
      ``dyn.step.*`` phase;
    - ``loop_wall_ns``: the loop's wall up to the newest ``lap()`` or
      tick; ``unphased_ns`` = that less every phase's wall AT THAT
      INSTANT = what no phase covered (the tiling as a number; a
      pipeline stays inside one lap for many steps, so between ticks
      the pair is up to a second old, and always consistent);
    - ``dispatches[kind]``, and ``period_ns[kind]``: at each dispatch the
      wall since the one before it, less the ``wait`` between them, added
      under the EARLIER one's kind;
    - ``dispatches_device_drained``: dispatches issued to a device whose
      queue had run dry (not the first after ``note_idle()``: no work is
      not starvation);
    - the thread's, the event loop's and the process's CPU clocks as of
      the newest tick.

    Written by its own thread alone (plain ints: a reader on another
    thread sees values a step apart, never torn ones); ``on_tick`` is
    called on that thread once a second from ``record`` / ``wait``."""

    def __init__(self) -> None:
        self.phases: dict[str, StepPhase] = {}
        for name in PHASES:
            kind = (_DispatchPhase if name == "dispatch" else
                    _TickPhase if name in ("record", "wait") else StepPhase)
            self.phases[name] = kind(self, PHASE_PREFIX + name)
        self._by_name = {p.name: p for p in self.phases.values()}
        self._wait = self.phases["wait"]
        self.loop_wall_ns = 0
        self.unphased_ns = 0
        self._lap_ns = 0
        self.dispatches: dict[str, int] = {}
        self.period_ns: dict[str, int] = {}
        self.dispatches_device_drained = 0
        self._last_kind: Optional[str] = None
        self._last_dispatch_ns = 0
        self._wait_ns_then = 0
        self._idled = False
        # the owning thread's CPU clock when its newest phase ended
        # (bind_step_clock / step_clock set it on that thread)
        self._cpu_mark = 0
        self.cpu_ns: dict[str, int] = {}
        self.ticked_ns = 0
        self.on_tick: Optional[Any] = None

    def phase(self, name: str) -> StepPhase:
        phase = self._by_name.get(name)
        if phase is None:  # a name outside the seven: clocked all the same
            phase = self._by_name[name] = StepPhase(self, name)
            self.phases[name.removeprefix(PHASE_PREFIX)] = phase
        return phase

    def lap(self) -> None:
        """The loop's wall runs up to here (each iteration's top)."""
        self._lap(_monotonic_ns())

    def _lap(self, now: int) -> None:
        # called between two phases: every phase's wall is whole
        if self._lap_ns:
            self.loop_wall_ns += now - self._lap_ns
            self.unphased_ns = self.loop_wall_ns - sum(
                p.wall_ns for p in self.phases.values())
        self._lap_ns = now

    def note_idle(self) -> None:
        """The loop found no work: the next dispatch meets a device that
        ran dry for want of requests, not of a host."""
        self._idled = True

    def _note_dispatch(self, kind: str, now: int, drained: bool) -> None:
        wait = self._wait
        if self._last_kind is not None:
            self.period_ns[self._last_kind] = (
                self.period_ns.get(self._last_kind, 0)
                + now - self._last_dispatch_ns
                - (wait.wall_ns - self._wait_ns_then))
        if drained and not self._idled:
            self.dispatches_device_drained += 1
        self._idled = False
        self.dispatches[kind] = self.dispatches.get(kind, 0) + 1
        self._last_kind, self._last_dispatch_ns = kind, now
        self._wait_ns_then = wait.wall_ns

    def tick(self, now: int) -> None:
        """Once a second, on the owning thread as a phase ends: note the
        CPU clocks (this thread's as that phase just read it) and hand the
        counts to ``on_tick`` (the count history)."""
        self.ticked_ns = now
        self._lap(now)
        self.cpu_ns["engine"] = self._cpu_mark
        self.cpu_ns["process"] = time.process_time_ns()
        loop = loop_thread_cpu_ns()
        if loop is not None:
            self.cpu_ns["loop"] = loop
        if self.on_tick is not None:
            self.on_tick(now)

    def counts(self) -> dict:
        """Every count above, JSON-able."""
        phases = {
            name: {"wall_ns": p.wall_ns, "cpu_ns": p.cpu_ns, "calls": p.calls}
            for name, p in list(self.phases.items())
        }
        work = [phases[n] for n in HOST_WORK]
        return {
            "step_phases": phases,
            "loop_wall_ns": self.loop_wall_ns,
            "unphased_ns": self.unphased_ns,
            "offcpu_ns": sum(p["wall_ns"] - p["cpu_ns"] for p in work),
            "dispatches": dict(self.dispatches),
            "period_ns": dict(self.period_ns),
            "dispatches_device_drained": self.dispatches_device_drained,
            "cpu_ns": dict(self.cpu_ns),
        }


_bound = threading.local()


def step_clock() -> StepClock:
    """The calling thread's clock (made at its first phase)."""
    clock = getattr(_bound, "clock", None)
    if clock is None:
        clock = StepClock()
        bind_step_clock(clock)
    return clock


def bind_step_clock(clock: Optional[StepClock]) -> None:
    """Make ``clock`` the calling thread's (an engine binds its own at
    the top of its step loop); None unbinds."""
    _bound.clock = clock
    if clock is not None:
        clock._cpu_mark = _thread_time_ns()


def step_span(name: str, **attrs: Any) -> StepPhase:
    """Context manager for one phase of an engine step: clocked always,
    and inside a capture also a trace annotation with ``attrs`` (scalars
    shown beside the event in the trace viewer)."""
    clock = getattr(_bound, "clock", None) or step_clock()
    phase = clock._by_name.get(name) or clock.phase(name)
    phase.attrs = attrs
    return phase


# -- the event loop's CPU clock, readable from the engine thread --------------
_loop_cpu_clock: Optional[int] = None


def note_loop_thread() -> None:
    """Call ON the asyncio loop's thread (http/service.py ``start``, the
    engine's ``launch``): keeps that thread's CPU-time clock id, which any
    thread may read. Nothing where the platform has no such clock."""
    global _loop_cpu_clock
    getcpuclockid = getattr(time, "pthread_getcpuclockid", None)
    if getcpuclockid is not None and hasattr(time, "clock_gettime_ns"):
        _loop_cpu_clock = getcpuclockid(threading.get_ident())


def loop_thread_cpu_ns() -> Optional[int]:
    """CPU ns the noted loop thread has used, or None (not noted, no such
    clock here, or the thread is gone)."""
    if _loop_cpu_clock is None:
        return None
    try:
        return time.clock_gettime_ns(_loop_cpu_clock)
    except OSError:
        return None
