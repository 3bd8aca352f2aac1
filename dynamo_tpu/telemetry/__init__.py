"""dynamo_tpu.telemetry — dependency-free tracing, metrics, and live
introspection.

Four pieces (docs/observability.md is the operator-facing guide):

- **Spans** (spans.py): ``get_tracer().span("name", parent=ctx)`` with
  trace-context propagation over the existing transport. Sinks:
  ``DYN_TRACE_FILE`` (JSONL; ``dynamo-tpu trace export`` renders
  Perfetto/chrome://tracing flame graphs, export.py) and the in-memory
  buffer of every serving process, written beside a profiler capture
  (``program_spans.json``, debug.py). ``step_span`` marks the engine's
  step phases: counted always by the step loop's ``StepClock`` (wall,
  thread CPU, calls; periods and dispatches by kind), annotated on the
  profiler's own clock inside a capture.
- **Metrics** (metrics.py): one process registry of labeled counters/
  gauges/histograms with Prometheus text exposition and cardinality
  guard rails; the serving stack's catalog lives in instruments.py.
- **Live introspection** (debug.py, recorder.py, blackbox.py, hbm.py):
  the ``/debug/state``/``/debug/profile`` provider registry, the
  engine's step flight recorder with slow-step watchdog dumps, the
  anomaly-triggered black-box bundle, and HBM memory accounting.
  ``dynamo-tpu top`` renders the fleet view.
- **SLO/goodput** (slo.py): per-request TTFT/ITL vs configured targets
  → ``dynamo_slo_attainment``/``dynamo_goodput_tokens_total``, riding
  the worker load feed for the Planner.
"""

from dynamo_tpu.telemetry.metrics import (  # noqa: F401
    Counter,
    Gauge,
    Histogram,
    Metric,
    Registry,
    REGISTRY,
    check_scrape_safety,
    escape_label_value,
)
from dynamo_tpu.telemetry.debug import (  # noqa: F401
    capture_profile,
    collect_debug_state,
    debug_provider_names,
    profile_blocking,
    register_count_provider,
    register_debug_provider,
    unregister_count_provider,
    unregister_debug_provider,
)
from dynamo_tpu.telemetry.blackbox import BlackBox  # noqa: F401
from dynamo_tpu.telemetry.hbm import HbmAccountant, tree_bytes  # noqa: F401
from dynamo_tpu.telemetry.hostplane import (  # noqa: F401
    HostCostLedger,
    LoopLagMonitor,
    collect_hostplane,
    note_stage,
    register_hostplane_provider,
    task_census,
    unregister_hostplane_provider,
)
from dynamo_tpu.telemetry.overlap import OverlapTracker  # noqa: F401
from dynamo_tpu.telemetry.recorder import FlightRecorder  # noqa: F401
from dynamo_tpu.telemetry.slo import SloConfig, SloTracker  # noqa: F401
from dynamo_tpu.telemetry.spans import (  # noqa: F401
    NULL_SPAN,
    JsonlSpanExporter,
    Span,
    SpanBuffer,
    Tracer,
    get_tracer,
    new_span_id,
    new_trace_id,
    propagation_context,
    reset_tracer,
    step_span,
)
