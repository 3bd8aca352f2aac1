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
beside its trace. Per-STEP phases are not spans of this stream: they go
to the profiler only, through ``step_span`` (bottom of this file).

Env knobs:
  DYN_TRACE_FILE    append finished spans as JSONL here (enables tracing)
  DYN_TRACE_SAMPLE  root-trace sampling fraction in [0, 1] (default 1.0);
                    a propagated inbound context is always recorded — the
                    head made the sampling decision for the whole trace
"""

from __future__ import annotations

import contextlib
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


# -- step phases on the profiler's clock ------------------------------------
# The engine's step loops mark their phases (``dyn.step.plan`` / ``pack`` /
# ``dispatch`` / ``harvest`` / ``emit`` / ``record`` / ``wait``) with
# ``step_span``. A phase is per STEP, not per request, so it never enters
# the span stream above: it is a ``jax.profiler.TraceAnnotation`` and
# lands in a capture's ``.xplane.pb`` on the thread that made it, on the
# clock of the device's own lines. telemetry/debug.py turns the switch
# around each capture; with no capture live a phase costs one global read.
_NO_PHASE = contextlib.nullcontext()
_capture_live = False
_annotation: Any = None


def set_capture_live(live: bool) -> None:
    """Called by the one function that starts and stops the profiler
    (telemetry/debug.py ``profile_blocking``)."""
    global _capture_live, _annotation
    if live and _annotation is None:
        # lazily: a frontend-only process never imports JAX for this
        from jax.profiler import TraceAnnotation

        _annotation = TraceAnnotation
    _capture_live = live


def step_span(name: str, **attrs: Any):
    """Context manager for one phase of an engine step; ``attrs`` are
    scalars shown beside the event in the trace viewer."""
    if not _capture_live:
        return _NO_PHASE
    return _annotation(name, **attrs)
