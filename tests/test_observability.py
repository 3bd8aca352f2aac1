"""Live introspection (ISSUE 4): flight recorder ring/dump semantics,
SLO attainment math, HBM accounting fallback, the /debug/state + /debug/profile endpoints, the
`dynamo-tpu top` fleet view, and the e2e acceptance path — a slow
request produces a JSONL flight dump whose offending step carries
per-phase latency, while /debug/state and /metrics agree on KV-pool
occupancy for the same moment."""

import asyncio
import io
import json
import os
import time

import aiohttp
import pytest

from dynamo_tpu.telemetry import debug as tdebug
from dynamo_tpu.telemetry.hbm import HbmAccountant, tree_bytes
from dynamo_tpu.telemetry.recorder import FlightRecorder
from dynamo_tpu.telemetry.slo import SloConfig, SloTracker

from tests.prom_parser import parse as prom_parse

MODEL_DIR = os.path.join(os.path.dirname(__file__), "data", "tiny_llama_model")


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------
def test_flight_recorder_ring_is_bounded():
    rec = FlightRecorder(capacity=8)
    for i in range(50):
        rec.record("decode", 0.001, batch=i)
    snap = rec.snapshot(100)
    assert len(snap) == 8  # deque(maxlen=8): old entries fell off
    assert [r["batch"] for r in snap] == list(range(42, 50))
    assert rec.steps_recorded == 50


def test_flight_recorder_slow_step_dumps(tmp_path):
    rec = FlightRecorder(
        capacity=16, slow_step_s=0.010, dump_dir=str(tmp_path),
        min_dump_interval_s=0.0,
    )
    for _ in range(5):
        assert rec.record("decode", 0.001, batch=4) is None  # under threshold
    path = rec.record(
        "prefill", 0.050, batch=2, dispatch_ms=48.0, sync_ms=1.5,
        plan_ms=0.3,
    )
    assert path is not None and os.path.exists(path)
    lines = [json.loads(x) for x in open(path).read().splitlines()]
    header, records = lines[0], lines[1:]
    assert header["flight_recorder_dump"] is True
    assert header["reason"] == "slow_step:prefill"
    assert len(records) == 6
    slow = [r for r in records if r.get("slow")]
    assert len(slow) == 1
    # the offending step carries its per-phase latency breakdown
    assert slow[0]["kind"] == "prefill"
    assert slow[0]["duration_ms"] == pytest.approx(50.0)
    assert slow[0]["dispatch_ms"] == 48.0
    assert slow[0]["sync_ms"] == 1.5
    assert slow[0]["plan_ms"] == 0.3
    assert slow[0]["slow_threshold_ms"] == pytest.approx(10.0)


def test_flight_recorder_dumps_are_rate_limited(tmp_path):
    now = [0.0]
    rec = FlightRecorder(
        capacity=4, slow_step_s=0.001, dump_dir=str(tmp_path),
        min_dump_interval_s=30.0, clock=lambda: now[0],
    )
    assert rec.record("decode", 0.5) is not None
    assert rec.record("decode", 0.5) is None  # suppressed: too soon
    assert rec.slow_steps == 2  # still counted as slow
    now[0] = 31.0
    assert rec.record("decode", 0.5) is not None  # window elapsed
    assert rec.dumps_written == 2


def test_flight_recorder_failed_dump_does_not_arm_rate_limit(tmp_path):
    rec = FlightRecorder(
        capacity=4, slow_step_s=0.001,
        dump_dir=os.path.join(str(tmp_path), "missing", "dir"),
        min_dump_interval_s=3600.0,
    )
    assert rec.record("decode", 0.5) is None  # write failed (no dir)
    rec.dump_dir = str(tmp_path)
    # a failed dump persisted nothing, so the next trigger must not be
    # suppressed by the rate limiter
    assert rec.record("decode", 0.5) is not None


def test_flight_recorder_caps_on_disk_dump_files(tmp_path):
    rec = FlightRecorder(
        capacity=4, slow_step_s=0.001, dump_dir=str(tmp_path),
        min_dump_interval_s=0.0, max_dump_files=3,
    )
    paths = [rec.record("decode", 0.5) for _ in range(5)]
    assert all(paths)
    on_disk = sorted(
        p for p in os.listdir(tmp_path) if p.startswith("dynamo_flight_")
    )
    # dumps 1 and 2 were unlinked when 4 and 5 landed: disk is bounded
    assert len(on_disk) == 3
    assert on_disk == [os.path.basename(p) for p in paths[-3:]]


def test_flight_recorder_slow_request_dump(tmp_path):
    rec = FlightRecorder(
        capacity=8, dump_dir=str(tmp_path), min_dump_interval_s=0.0,
    )
    rec.record("decode", 0.001)
    path = rec.note_slow_request("req-9", ttft_ms=812.0, tokens=30)
    assert path is not None
    lines = [json.loads(x) for x in open(path).read().splitlines()]
    assert lines[0]["reason"] == "slow_request:req-9"
    marker = [r for r in lines[1:] if r.get("kind") == "slow_request"]
    assert marker and marker[0]["request_id"] == "req-9"
    assert marker[0]["ttft_ms"] == 812.0


# ---------------------------------------------------------------------------
# SLO attainment / goodput math
# ---------------------------------------------------------------------------
def test_slo_attainment_math():
    t = SloTracker(SloConfig(ttft_ms=100.0, itl_ms=10.0), window=16)
    assert t.attainment == 1.0  # nothing observed yet
    assert t.observe(0.050, 0.005, completion_tokens=10) is True
    assert t.observe(0.200, 0.005, completion_tokens=10) is False  # ttft miss
    assert t.observe(0.050, 0.020, completion_tokens=10) is False  # itl miss
    assert t.observe(0.050, None, completion_tokens=5) is True  # no itl: n/a
    assert t.attainment == pytest.approx(2 / 4)
    assert t.goodput_tokens == 15  # only SLO-met requests count
    s = t.stats()
    assert s["requests_seen"] == 4 and s["requests_met"] == 2
    assert s["targets"] == {"ttft_ms": 100.0, "itl_ms": 10.0}


def test_slo_rolling_window_forgets_old_outcomes():
    t = SloTracker(SloConfig(ttft_ms=100.0), window=4)
    for _ in range(4):
        t.observe(1.0, None)  # all miss
    assert t.attainment == 0.0
    for _ in range(4):
        t.observe(0.01, None)  # all meet: misses roll out of the window
    assert t.attainment == 1.0


def test_aggregate_slo_shared_rollup():
    from dynamo_tpu.telemetry.slo import aggregate_slo

    class W:
        def __init__(self, enabled, attain, goodput):
            self.slo_enabled = enabled
            self.slo_attainment = attain
            self.goodput_tokens_total = goodput

    attainment, goodput = aggregate_slo([
        W(True, 0.5, 100), W(True, 1.0, 300),
        W(False, 1.0, 0),  # target-less: excluded from the mean
    ])
    assert attainment == 0.75 and goodput == 400
    assert aggregate_slo([]) == (1.0, 0.0)
    assert aggregate_slo([W(False, 1.0, 50)]) == (1.0, 50.0)


async def test_errored_requests_do_not_score_slo(tmp_path):
    """ERROR finishes must not count as goodput or attainment: a fleet
    in an error loop reporting 'healthy' would invert the Planner
    signal."""
    from dynamo_tpu.engine.engine import JaxEngine

    engine = await JaxEngine.launch(_engine_cfg(
        slo_ttft_ms=60_000.0, flight_dump_dir=str(tmp_path),
    ))
    try:
        await engine.wait_for_state(lambda e: e.scheduler is not None)

        def always_boom(*a, **kw):
            raise RuntimeError("persistent failure")

        engine._run_device_step = always_boom
        engine._dispatch_mixed = always_boom
        engine._dispatch_multi_step = always_boom
        out = await _gen(engine, range(1, 12), request_id="err")
        assert out == []
        assert engine.slo.requests_seen == 0
        assert engine.slo.goodput_tokens == 0
        assert engine.slo.attainment == 1.0
    finally:
        await engine.shutdown()


def test_slo_disabled_records_but_does_not_score():
    t = SloTracker(SloConfig())
    assert not t.config.enabled
    assert t.observe(99.0, 99.0, completion_tokens=100) is True
    assert t.attainment == 1.0
    assert t.goodput_tokens == 0
    assert t.requests_seen == 0


# ---------------------------------------------------------------------------
# HBM accounting
# ---------------------------------------------------------------------------
def test_hbm_accountant_portable_fallback():
    acct = HbmAccountant(device=None)
    acct.set_static(weight_bytes=1000, kv_pool_bytes=500)
    snap = acct.refresh()
    assert snap["source"] == "accounted"
    assert snap["weight_bytes"] == 1000
    assert snap["kv_pool_bytes"] == 500
    assert snap["bytes_in_use"] == 1500
    assert snap["peak_bytes_in_use"] == 1500
    acct.set_static(weight_bytes=100, kv_pool_bytes=50)
    snap2 = acct.refresh()
    assert snap2["bytes_in_use"] == 150
    assert snap2["peak_bytes_in_use"] == 1500  # watermark held


def test_tree_bytes_counts_nested_arrays():
    import numpy as np

    tree = {"a": np.zeros((4, 4), np.float32),
            "kv": (np.zeros(8, np.int8), np.zeros(2, np.float32))}
    assert tree_bytes(tree) == 64 + 8 + 8


# ---------------------------------------------------------------------------
# debug provider registry
# ---------------------------------------------------------------------------
def test_debug_provider_registry_isolation():
    def good():
        return {"x": 1}

    def bad():
        raise RuntimeError("torn read")

    tdebug.register_debug_provider("t_good", good)
    tdebug.register_debug_provider("t_bad", bad)
    try:
        state = tdebug.collect_debug_state()
        assert state["t_good"] == {"x": 1}
        # a raising provider degrades to an error stanza, not a crash
        assert "RuntimeError" in state["t_bad"]["error"]
        assert "ts" in state and "pid" in state
    finally:
        tdebug.unregister_debug_provider("t_good")
        tdebug.unregister_debug_provider("t_bad")
    assert "t_good" not in tdebug.debug_provider_names()


def test_debug_provider_unregister_checks_identity():
    tdebug.register_debug_provider("t_ident", lambda: {"v": 2})
    try:
        # a DIFFERENT provider under the same name must not be yanked
        tdebug.unregister_debug_provider("t_ident", lambda: {"v": 3})
        assert "t_ident" in tdebug.debug_provider_names()
    finally:
        tdebug.unregister_debug_provider("t_ident")


# ---------------------------------------------------------------------------
# /debug endpoints on the HTTP frontend
# ---------------------------------------------------------------------------
async def _start_frontend():
    from dynamo_tpu.http.service import HttpService, ModelManager

    service = HttpService(ModelManager(), host="127.0.0.1", port=0)
    await service.start()
    return service, f"http://127.0.0.1:{service.port}"


async def test_debug_state_endpoint_schema():
    tdebug.register_debug_provider("t_worker", lambda: {"busy": True})
    service, base = await _start_frontend()
    try:
        async with aiohttp.ClientSession() as s:
            async with s.get(f"{base}/debug/state") as r:
                assert r.status == 200
                state = await r.json()
        assert state["t_worker"] == {"busy": True}
        assert state["frontend"]["models"] == []
        assert state["frontend"]["port"] == service.port
    finally:
        tdebug.unregister_debug_provider("t_worker")
        await service.stop()


@pytest.mark.parametrize("which", ["frontend", "metrics-service"])
async def test_debug_attribution_is_gone_and_state_answers(which):
    """PR 48 took ``/debug/attribution`` from both services (no modelled
    roofline is kept); ``/debug/state`` is where an operator reads the
    step loop, on either port."""
    from aiohttp import web

    from dynamo_tpu.http.service import HttpService
    from dynamo_tpu.metrics.service import MetricsService

    if which == "frontend":
        app = HttpService().app
    else:
        app = MetricsService(component=None, host="127.0.0.1", port=0).build_app()  # type: ignore[arg-type]
    tdebug.register_debug_provider(
        "engine", lambda: {"step_phases": {"record": {"calls": 1}}})
    runner = web.AppRunner(app)
    await runner.setup()
    site = web.TCPSite(runner, "127.0.0.1", 0)
    await site.start()
    base = "http://127.0.0.1:%d" % site._server.sockets[0].getsockname()[1]
    try:
        async with aiohttp.ClientSession() as s:
            async with s.get(f"{base}/debug/attribution") as r:
                assert r.status == 404
            async with s.get(f"{base}/debug/state") as r:
                assert r.status == 200
                state = await r.json()
        assert state["engine"]["step_phases"]["record"]["calls"] == 1
    finally:
        tdebug.unregister_debug_provider("engine")
        await runner.cleanup()


async def test_debug_profile_endpoint(tmp_path, monkeypatch):
    monkeypatch.setenv("DYN_PROFILE_DIR", str(tmp_path))
    service, base = await _start_frontend()
    try:
        async with aiohttp.ClientSession() as s:
            async with s.get(f"{base}/debug/profile?ms=50") as r:
                assert r.status == 200, await r.text()
                body = await r.json()
            assert body["duration_ms"] == 50
            assert os.path.isdir(body["trace_dir"])
            assert body["trace_dir"].startswith(str(tmp_path))
            async with s.get(f"{base}/debug/profile?ms=nope") as r:
                assert r.status == 400
    finally:
        await service.stop()


# ---------------------------------------------------------------------------
# e2e acceptance: slow request -> flight dump; /debug/state vs /metrics
# ---------------------------------------------------------------------------
def _engine_cfg(**kw):
    from dynamo_tpu.engine.config import EngineConfig

    defaults = dict(
        model_path=MODEL_DIR, model_name="tiny", random_weights=True,
        num_blocks=128, block_size=8, max_batch_size=8,
        prefill_chunk_size=32, max_model_len=256,
    )
    defaults.update(kw)
    return EngineConfig(**defaults)


async def _gen(engine, prompt, max_tokens=8, request_id="r"):
    from dynamo_tpu.protocols.common import (
        PreprocessedRequest,
        SamplingOptions,
        StopConditions,
    )
    from dynamo_tpu.runtime.engine import Context

    req = PreprocessedRequest(
        request_id=request_id, token_ids=list(prompt),
        sampling=SamplingOptions(use_greedy=True),
        stop=StopConditions(max_tokens=max_tokens),
    )
    out = []
    async for item in engine.as_async_engine().generate(req, Context()):
        out.extend(item.token_ids)
    return out


async def test_e2e_slow_step_dump_and_consistent_kv_occupancy(tmp_path):
    """The acceptance bar: an injected device-step delay trips the
    slow-step watchdog, the dump contains the offending step WITH its
    per-phase latency, and /debug/state + /metrics agree on KV-pool
    occupancy for the same moment."""
    from dynamo_tpu.engine.engine import JaxEngine

    engine = await JaxEngine.launch(_engine_cfg(
        slow_step_ms=40.0,
        flight_dump_dir=str(tmp_path),
        slo_ttft_ms=10_000.0,  # generous: CPU test backend
    ))
    service = None
    try:
        # inject a delay into every synced device step
        orig = engine._run_device_step

        def slow_step(arrays, sampling, **kw):
            time.sleep(0.08)
            return orig(arrays, sampling, **kw)

        engine._run_device_step = slow_step
        toks = await _gen(engine, range(1, 20), request_id="slowreq")
        assert len(toks) == 8
        engine._run_device_step = orig

        # -- flight dump: offending step + per-phase latency ------------
        dumps = sorted(
            p for p in os.listdir(tmp_path) if p.startswith("dynamo_flight_")
        )
        assert dumps, "slow steps produced no flight-recorder dump"
        lines = [
            json.loads(x)
            for x in open(os.path.join(tmp_path, dumps[0])).read().splitlines()
        ]
        assert lines[0]["reason"].startswith("slow_step:")
        slow_recs = [r for r in lines[1:] if r.get("slow")]
        assert slow_recs, "dump lacks the offending step"
        off = slow_recs[0]
        assert off["duration_ms"] > 40.0
        assert "dispatch_ms" in off  # per-phase latency present
        assert "plan_ms" in off
        assert off["queue_depth"] >= 0 and "batch" in off

        # -- /debug/state vs /metrics occupancy -------------------------
        service, base = await _start_frontend()
        async with aiohttp.ClientSession() as s:
            async with s.get(f"{base}/debug/state") as r:
                assert r.status == 200
                state = await r.json()
            async with s.get(f"{base}/metrics") as r:
                metrics_text = await r.text()
        eng = state["engine"]
        pool = eng["kv_pool"]
        fams = prom_parse(metrics_text)
        active = fams["dynamo_kv_pool_blocks_active"].samples[
            ("dynamo_kv_pool_blocks_active", ())
        ]
        total = fams["dynamo_kv_pool_blocks_total"].samples[
            ("dynamo_kv_pool_blocks_total", ())
        ]
        assert pool["active_blocks"] == active
        assert pool["total_blocks"] == total == 127
        assert pool["active_blocks"] + pool["free_blocks"] == total
        # the engine snapshot carries the rest of the introspection
        # surface the CLI renders
        assert eng["scheduler"]["running"] == 0
        assert eng["hbm"]["kv_pool_bytes"] > 0
        assert eng["slo"]["enabled"] is True
        assert eng["slo"]["requests_seen"] >= 1
        assert eng["recent_steps"], "flight recorder tail missing"
        assert eng["load"]["goodput_tokens_total"] >= 8
        # SLO histograms made it into the exposition machinery
        assert fams["dynamo_request_ttft_seconds"].type == "histogram"
        assert fams["dynamo_slo_attainment"].samples[
            ("dynamo_slo_attainment", ())
        ] == 1.0
    finally:
        if service is not None:
            await service.stop()
        await engine.shutdown()


async def test_slo_miss_scores_and_dumps(tmp_path):
    """An impossible ITL target: the request misses, attainment drops,
    and the request watchdog dumps the ring."""
    from dynamo_tpu.engine.engine import JaxEngine

    engine = await JaxEngine.launch(_engine_cfg(
        slo_ttft_ms=100_000.0, slo_itl_ms=0.0001,
        flight_dump_dir=str(tmp_path),
    ))
    try:
        await _gen(engine, range(1, 16), request_id="misser")
        assert engine.slo.attainment < 1.0
        assert engine.slo.goodput_tokens == 0
        dumps = [
            p for p in os.listdir(tmp_path) if p.startswith("dynamo_flight_")
        ]
        assert dumps, "SLO miss did not trip the request watchdog"
    finally:
        await engine.shutdown()


# ---------------------------------------------------------------------------
# dynamo-tpu top
# ---------------------------------------------------------------------------
async def test_top_renders_fleet_frame():
    from dynamo_tpu.cli.top import run_top

    tokens = [1000]

    def fake_engine():
        tokens[0] += 500
        return {
            "model": "tiny-model",
            "max_batch_size": 8,
            "tokens_generated_total": tokens[0],
            "scheduler": {"running": 3, "queue_depth": 2, "preemptions": 1},
            "kv_pool": {"usage": 0.25, "active_blocks": 32,
                        "total_blocks": 128},
            "slo": {"enabled": True, "attainment": 0.875},
            "hbm": {"bytes_in_use": 2 * 1024 * 1024},
            "flight_recorder": {"slow_steps": 4},
            "load": {"goodput_tokens_total": 0},  # no SLO targets: tok/s
            # must NOT come from goodput
        }

    tdebug.register_debug_provider("engine", fake_engine)
    service, base = await _start_frontend()
    try:
        buf = io.StringIO()
        rc = await run_top([base], interval=0.01, iterations=2,
                           clear=False, out=buf)
        assert rc == 0
        text = buf.getvalue()
        assert "WORKER" in text and "tiny-model" in text
        assert "25.0%" in text  # kv usage
        assert "87.5%" in text  # slo attainment
        assert "2.0MB" in text  # hbm
        # second frame derives a NONZERO rate from generated-token
        # deltas even though goodput is 0 (no SLO targets configured)
        frames = text.split("dynamo-tpu top")
        assert "       -" in frames[1]  # first frame: no delta yet
        import re

        rates = re.findall(r" (\d+\.\d)\b", frames[2])
        assert any(float(x) > 0 for x in rates), frames[2]
    finally:
        tdebug.unregister_debug_provider("engine")
        await service.stop()


async def test_top_raw_mode_and_dead_worker():
    from dynamo_tpu.cli.top import run_top

    buf = io.StringIO()
    # unroutable port: every worker erroring is exit code 1
    rc = await run_top(["http://127.0.0.1:1"], interval=0.01,
                       iterations=1, raw=True, out=buf)
    assert rc == 1
    row = json.loads(buf.getvalue())
    assert "error" in row["http://127.0.0.1:1"]


def test_top_cli_parser_wiring():
    from dynamo_tpu.cli.main import build_parser

    args = build_parser().parse_args(
        ["top", "http://h:1", "--once", "--raw", "--interval", "0.5"]
    )
    assert args.command == "top"
    assert args.urls == ["http://h:1"]
    assert args.once and args.raw and args.interval == 0.5
    run_args = build_parser().parse_args(
        ["run", "--slo-ttft-ms", "500", "--slo-itl-ms", "40",
         "--slow-step-ms", "250", "--flight-recorder-steps", "128"]
    )
    assert run_args.slo_ttft_ms == 500.0
    assert run_args.slo_itl_ms == 40.0
    assert run_args.slow_step_ms == 250.0
    assert run_args.flight_recorder_steps == 128
    from dynamo_tpu.engine.config import load_engine_config

    cfg = load_engine_config(run_args)
    assert cfg.slo_ttft_ms == 500.0 and cfg.slow_step_ms == 250.0
    assert cfg.flight_recorder_steps == 128


# ---------------------------------------------------------------------------
# black box (telemetry/blackbox.py): units, then the e2e fault stall
# ---------------------------------------------------------------------------
def test_blackbox_bundle_contents_and_rate_limit(tmp_path):
    from dynamo_tpu.telemetry.blackbox import BlackBox

    rec = FlightRecorder(capacity=8)
    rec.record("decode", 0.001, batch=4)
    now = [0.0]
    bb = BlackBox(
        recorder=rec, dump_dir=str(tmp_path),
        min_interval_s=60.0, clock=lambda: now[0], profile_ms=0,
    )
    d = bb.trigger("watchdog:decode")
    assert d is not None
    bb.flush()  # snapshot is sync; the file write is a background thread
    assert os.path.isdir(d)
    meta = json.load(open(os.path.join(d, "meta.json")))
    assert meta["reason"] == "watchdog:decode"
    assert sorted(os.listdir(d)) == ["flight.jsonl", "meta.json", "state.json"]
    flight = [
        json.loads(x)
        for x in open(os.path.join(d, "flight.jsonl")).read().splitlines()
    ]
    assert flight[0]["flight_recorder_dump"] is True
    assert flight[1]["kind"] == "decode"
    assert os.path.exists(os.path.join(d, "state.json"))
    # second trigger inside the window: suppressed
    assert bb.trigger("watchdog:decode") is None
    assert bb.stats()["dumps"] == 1 and bb.stats()["suppressed"] == 1
    now[0] = 61.0
    assert bb.trigger("manual") is not None
    bb.flush()
    assert bb.stats()["dumps"] == 2


async def test_e2e_stall_fires_exactly_one_blackbox(tmp_path, monkeypatch):
    """An injected engine.step stall (DYN_FAULTS) trips the slow-step
    watchdog; the black box bundles recorder tail + /debug/state
    exactly ONCE per rate-limit window despite repeated stalls."""
    from dynamo_tpu import faults
    from dynamo_tpu.engine.engine import JaxEngine

    monkeypatch.setenv("DYN_BLACKBOX_INTERVAL_S", "3600")
    injector = faults.activate(faults.parse_plan(
        "seed=7;engine.step:delay=0.06@p=1.0"
    ))
    engine = await JaxEngine.launch(_engine_cfg(
        slow_step_ms=30.0, flight_dump_dir=str(tmp_path),
    ))
    try:
        await _gen(engine, range(1, 16), max_tokens=12, request_id="stall")
        assert injector.stats()["fired_total"] > 3  # repeated stalls
        engine.blackbox.flush()
        bundles = [
            p for p in os.listdir(tmp_path)
            if p.startswith("dynamo_blackbox_")
        ]
        assert len(bundles) == 1, bundles
        d = os.path.join(str(tmp_path), bundles[0])
        meta = json.load(open(os.path.join(d, "meta.json")))
        assert meta["reason"].startswith("watchdog:")
        # recorder tail + engine state both present (acceptance)
        flight = open(os.path.join(d, "flight.jsonl")).read().splitlines()
        assert len(flight) >= 2
        state = json.load(open(os.path.join(d, "state.json")))
        assert "scheduler" in state["engine"]
        assert not os.path.exists(os.path.join(d, "attribution.json"))
        assert engine.blackbox.stats()["dumps"] == 1
        assert engine.blackbox.stats()["suppressed"] >= 0
    finally:
        faults.deactivate()
        await engine.shutdown()


# ---------------------------------------------------------------------------
# top: tok/s absence marker
# ---------------------------------------------------------------------------
async def test_top_counter_reset_renders_absence_not_zero():
    """A worker restart rewinds tokens_generated_total; the rate must
    render `-` (no delta), not clamp to a fabricated 0.0."""
    from dynamo_tpu.cli.top import _engine_row

    prev = {"engine": {"tokens_generated_total": 10_000}}
    cur = {"engine": {"tokens_generated_total": 50}}  # restarted worker
    row = _engine_row("u", cur, prev, now=10.0, prev_ts=8.0)
    assert row["tok_s"] is None
    ok = _engine_row(
        "u", {"engine": {"tokens_generated_total": 150}},
        {"engine": {"tokens_generated_total": 50}}, now=12.0, prev_ts=10.0,
    )
    assert ok["tok_s"] == pytest.approx(50.0)


# ---------------------------------------------------------------------------
# metrics service rollup
# ---------------------------------------------------------------------------
def test_metrics_service_rolls_up_slo_signals():
    from dynamo_tpu.kv_router.protocols import ForwardPassMetrics
    from dynamo_tpu.metrics.service import MetricsService

    svc = MetricsService(component=None, host="127.0.0.1", port=0)  # type: ignore[arg-type]
    svc.aggregator.update(ForwardPassMetrics(
        worker_id=1, slo_enabled=True, slo_attainment=0.5,
        goodput_tokens_total=100,
    ))
    svc.aggregator.update(ForwardPassMetrics(
        worker_id=2, slo_enabled=True, slo_attainment=1.0,
        goodput_tokens_total=300,
    ))
    # a target-less worker reports the default 1.0 — it must NOT
    # dilute the fleet attainment mean
    svc.aggregator.update(ForwardPassMetrics(worker_id=3))
    fams = prom_parse(svc.render())
    assert fams["llm_slo_attainment"].samples[
        ("llm_slo_attainment", ())
    ] == 0.75
    assert fams["llm_goodput_tokens"].samples[
        ("llm_goodput_tokens", ())
    ] == 400


async def test_metrics_service_rolls_up_a_parent_commit_workers_payload():
    """Rolling restart: a worker of the commit before PR 48 still sends
    ``roofline_frac`` / ``top_loss_bucket`` on the load feed. The
    subscription the router and the metrics service share drops the two
    keys (a refused payload would only be logged, and the worker lost
    to both) and the rollup counts the worker."""
    from dynamo_tpu.kv_router.protocols import ForwardPassMetrics
    from dynamo_tpu.metrics.service import MetricsService

    old_worker = {
        "worker_id": 7, "request_active_slots": 3, "request_total_slots": 8,
        "kv_active_blocks": 40, "kv_total_blocks": 100,
        "num_requests_waiting": 2, "gpu_cache_usage_perc": 0.4,
        "gpu_prefix_cache_hit_rate": 0.0, "slo_enabled": True,
        "slo_attainment": 0.5, "goodput_tokens_total": 10,
        "roofline_frac": 0.31, "top_loss_bucket": "idle_gap",
    }
    new_worker = ForwardPassMetrics(worker_id=8, kv_total_blocks=100)
    assert "roofline_frac" not in new_worker.model_dump()

    async def feed():
        yield "load_metrics", old_worker
        yield "load_metrics", new_worker.model_dump()

    svc = MetricsService(component=None, host="127.0.0.1", port=0)  # type: ignore[arg-type]
    svc.aggregator.start_consuming(feed())
    await svc.aggregator._task
    got = svc.aggregator.fresh_metrics()
    assert sorted(got) == [7, 8]
    assert set(got[7].model_dump()) == set(new_worker.model_dump())
    fams = prom_parse(svc.render())
    assert "llm_roofline_frac" not in fams
    assert fams["llm_workers_reporting"].samples[
        ("llm_workers_reporting", ())
    ] == 2
    assert fams["llm_kv_blocks_active"].samples[
        ("llm_kv_blocks_active", ())
    ] == 40
    assert fams["llm_slo_attainment"].samples[
        ("llm_slo_attainment", ())
    ] == 0.5
