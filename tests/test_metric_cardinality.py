"""Cardinality gate (ISSUE 2 satellite): walk every metric the serving
stack registers and fail the build if the surface could become
scrape-unsafe — per-request identifier labels, absurd series bounds, or
missing help text. Importing the layer modules below is what populates
the process registry, so a new instrument anywhere in the stack is
automatically in scope."""

import importlib

import pytest

from dynamo_tpu.telemetry import REGISTRY, check_scrape_safety
from dynamo_tpu.telemetry.metrics import (
    DEFAULT_MAX_SERIES,
    FORBIDDEN_LABEL_NAMES,
    Registry,
)

# every module that declares or touches process-global instruments
_INSTRUMENTED_MODULES = [
    "dynamo_tpu.telemetry.instruments",
    "dynamo_tpu.telemetry.recorder",
    "dynamo_tpu.telemetry.slo",
    "dynamo_tpu.telemetry.hbm",
    "dynamo_tpu.telemetry.blackbox",
    "dynamo_tpu.telemetry.hostplane",
    "dynamo_tpu.telemetry.autopsy",
    "dynamo_tpu.http.service",
    "dynamo_tpu.metrics.service",
    "dynamo_tpu.disagg.worker",
    "dynamo_tpu.disagg.transfer",
    "dynamo_tpu.engine.scheduler",
    "dynamo_tpu.kvbm.manager",
    "dynamo_tpu.planner.planner",
]

# the ISSUE 4 observability surface: these series must exist in the
# process registry (catalog drift fails here, not in a dashboard)
_REQUIRED_SERIES = [
    "dynamo_slo_attainment",
    "dynamo_goodput_tokens_total",
    "dynamo_slo_requests_total",
    "dynamo_request_ttft_seconds",
    "dynamo_request_itl_seconds",
    "dynamo_engine_slow_steps_total",
    "dynamo_flight_recorder_dumps_total",
    "dynamo_kv_pool_blocks_active",
    "dynamo_kv_pool_blocks_total",
    "dynamo_kv_pool_cached_free_blocks",
    "dynamo_hbm_weight_bytes",
    "dynamo_hbm_kv_pool_bytes",
    "dynamo_hbm_bytes_in_use",
    "dynamo_hbm_bytes_limit",
    "dynamo_hbm_peak_bytes",
    # ISSUE 6: the self-healing planner surface
    "dynamo_planner_scale_events_total",
    "dynamo_planner_replacements_total",
    "dynamo_planner_degradation_level",
    "dynamo_planner_connector_failures_total",
    # ISSUE 10: the black box (telemetry/blackbox.py)
    "dynamo_blackbox_dumps_total",
    # ISSUE 12: the overlapped spec pipeline surface
    "dynamo_spec_draft_hidden_frac",
    "dynamo_spec_accept_rate",
    "dynamo_spec_proposed_tokens_total",
    "dynamo_spec_accepted_tokens_total",
    # ISSUE 13: the serve-phase compile fence (DYN_COMPILE_FENCE)
    "dynamo_compile_fence_events_total",
    # ISSUE 16: the serve-phase transfer fence (DYN_TRANSFER_FENCE)
    "dynamo_transfer_fence_events_total",
    # ISSUE 14: mid-stream migration (docs/robustness.md)
    "dynamo_midstream_resumes_total",
    "dynamo_midstream_resume_seconds",
    "dynamo_midstream_aborts_total",
    "dynamo_failover_retries_total",
    # ISSUE 15: guided decoding / tool calls (docs/guided_decoding.md)
    "dynamo_guided_compile_seconds",
    "dynamo_guided_cache_events_total",
    "dynamo_guided_requests_total",
    "dynamo_tool_call_streams_total",
    # ISSUE 17: the host data plane (telemetry/hostplane.py)
    "dynamo_http_loop_lag_seconds",
    "dynamo_http_loop_lag_p99_seconds",
    "dynamo_http_loop_lag_max_seconds",
    "dynamo_http_loop_stalls_total",
    "dynamo_http_open_streams",
    "dynamo_http_host_stage_seconds",
    "dynamo_http_first_chunk_wait_seconds",
    "dynamo_http_sse_write_ema_seconds",
    "dynamo_http_drain_wait_seconds",
    # ISSUE 18: the fleet KV fabric (kvbm/fabric.py, docs/kvbm.md)
    "dynamo_kvbm_remote_timeout_total",
    "dynamo_kvbm_fleet_hits_total",
    "dynamo_kvbm_fleet_fetched_blocks_total",
    "dynamo_kvbm_fleet_fetch_seconds",
    "dynamo_kvbm_fleet_demoted_blocks_total",
    "dynamo_kvbm_fleet_catalog_entries",
    "dynamo_kvbm_fleet_dangling_total",
    # ISSUE 19: request autopsy (telemetry/autopsy.py) — request-bounded
    # counters only; the per-request detail lives in the exemplar ring,
    # never as labeled series
    "dynamo_autopsy_requests_total",
    "dynamo_autopsy_exemplars",
    "dynamo_autopsy_segments_total",
    # ISSUE 20: graceful drain (runtime/drain.py, docs/robustness.md)
    "dynamo_worker_drains_total",
    "dynamo_drain_handoff_seconds",
    "dynamo_drain_streams_migrated_total",
]


def _load_all() -> None:
    for mod in _INSTRUMENTED_MODULES:
        importlib.import_module(mod)


def test_process_registry_is_scrape_safe():
    _load_all()
    check_scrape_safety(REGISTRY)


def test_every_instrument_has_bounded_labels():
    _load_all()
    for m in REGISTRY.metrics():
        # denylist enforced at declaration; belt-and-braces here
        assert not (set(m.label_names) & FORBIDDEN_LABEL_NAMES), m.name
        assert m.max_series <= DEFAULT_MAX_SERIES, (
            f"{m.name}: raise the gate bound deliberately if a metric "
            f"really needs more than {DEFAULT_MAX_SERIES} series"
        )
        assert m.help, m.name


def test_metrics_service_registry_is_scrape_safe():
    """The aggregation service builds a per-instance registry; its
    declarations must pass the same gate (constructed without a
    component — declaration happens in __init__ before any I/O)."""
    from dynamo_tpu.metrics.service import MetricsService

    svc = MetricsService(component=None, host="127.0.0.1", port=0)  # type: ignore[arg-type]
    check_scrape_safety(svc.registry)


def test_observability_series_are_registered():
    _load_all()
    missing = [n for n in _REQUIRED_SERIES if REGISTRY.get(n) is None]
    assert not missing, f"catalog drifted: {missing}"
    # bounded label sets on the labeled ones
    assert REGISTRY.get("dynamo_slo_requests_total").label_names == (
        "outcome",
    )
    assert REGISTRY.get("dynamo_engine_slow_steps_total").label_names == (
        "kind",
    )
    assert REGISTRY.get(
        "dynamo_flight_recorder_dumps_total"
    ).label_names == ("reason",)
    assert REGISTRY.get(
        "dynamo_planner_scale_events_total"
    ).label_names == ("component", "direction")
    assert REGISTRY.get(
        "dynamo_planner_replacements_total"
    ).label_names == ("component",)
    assert REGISTRY.get("dynamo_blackbox_dumps_total").label_names == (
        "reason",
    )
    # migration outcomes key on the bounded {ok, failed} result set
    assert REGISTRY.get("dynamo_midstream_resumes_total").label_names == (
        "result",
    )
    assert REGISTRY.get("dynamo_midstream_resume_seconds").label_names == ()
    # guided decoding keys on the bounded spec-kind / result / mode sets
    assert REGISTRY.get("dynamo_guided_compile_seconds").label_names == (
        "kind",
    )
    assert REGISTRY.get(
        "dynamo_guided_cache_events_total"
    ).label_names == ("result",)
    assert REGISTRY.get("dynamo_guided_requests_total").label_names == (
        "kind",
    )
    assert REGISTRY.get("dynamo_tool_call_streams_total").label_names == (
        "mode",
    )
    # the host-stage histogram keys on the fixed ledger stage set
    assert REGISTRY.get("dynamo_http_host_stage_seconds").label_names == (
        "stage",
    )
    assert REGISTRY.get("dynamo_http_loop_lag_seconds").label_names == ()
    assert REGISTRY.get("dynamo_http_loop_stalls_total").label_names == ()
    assert REGISTRY.get("dynamo_http_open_streams").label_names == ()
    # fleet fabric: hit source and demotion destination are fixed enums
    assert REGISTRY.get("dynamo_kvbm_fleet_hits_total").label_names == (
        "source",
    )
    assert REGISTRY.get(
        "dynamo_kvbm_fleet_demoted_blocks_total"
    ).label_names == ("dest",)
    assert REGISTRY.get("dynamo_kvbm_remote_timeout_total").label_names == (
        "op",
    )
    assert REGISTRY.get(
        "dynamo_kvbm_fleet_catalog_entries"
    ).label_names == ()
    # autopsy: retention outcome and segment source are fixed enums;
    # the rid itself must never become a label (gate below enforces)
    assert REGISTRY.get("dynamo_autopsy_requests_total").label_names == (
        "outcome",
    )
    assert REGISTRY.get("dynamo_autopsy_exemplars").label_names == ()
    assert REGISTRY.get("dynamo_autopsy_segments_total").label_names == (
        "source",
    )


def test_metric_catalog_docs_match_registry():
    """docs/observability.md's catalog table IS the documentation
    contract for the metric surface: every series the process registers
    must have a row, and every row must name a live series.  Catalog
    rot was a review nit before this test; now it's a tier-1 failure
    in both directions (ISSUE 13 satellite)."""
    import re
    from pathlib import Path

    _load_all()
    registered = {m.name for m in REGISTRY.metrics()}
    docs = (
        Path(__file__).resolve().parents[1] / "docs" / "observability.md"
    ).read_text()
    documented = {
        m.group(1)
        for m in re.finditer(r"^\|\s*`(dynamo_[a-z0-9_]+)`", docs, re.M)
    }
    undocumented = sorted(registered - documented)
    assert not undocumented, (
        "series registered but missing from docs/observability.md's "
        f"catalog table: {undocumented}"
    )
    ghosts = sorted(documented - registered)
    assert not ghosts, (
        "docs/observability.md catalog rows naming no registered "
        f"series: {ghosts}"
    )


def test_gate_catches_a_request_id_label():
    r = Registry()
    with pytest.raises(ValueError):
        r.counter("bad_total", "h", labels=("request_id",))
