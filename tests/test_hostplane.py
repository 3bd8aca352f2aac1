"""Host data-plane observability (ISSUE 17): the event-loop lag
monitor, the per-stream host-cost ledger, the /debug/hostplane
surface, and the `top` host columns —
docs/observability.md "Host data plane"."""

import asyncio
import json
import os
import time
from typing import Any, AsyncIterator

import aiohttp

from dynamo_tpu.http.service import HttpService, ModelManager
from dynamo_tpu.protocols.common import FinishReason
from dynamo_tpu.protocols.openai import ChatCompletionRequest, ChatDeltaGenerator
from dynamo_tpu.runtime.engine import AsyncEngine, Context, EngineStream
from dynamo_tpu.telemetry import REGISTRY
from dynamo_tpu.telemetry.blackbox import BlackBox
from dynamo_tpu.telemetry.hostplane import (
    LEDGER,
    STAGES,
    HostCostLedger,
    LoopLagMonitor,
    collect_hostplane,
    note_stage,
    register_hostplane_provider,
    task_census,
    unregister_hostplane_provider,
)
from dynamo_tpu.telemetry.recorder import FlightRecorder

from tests.prom_parser import parse as prom_parse

MODEL_DIR = os.path.join(os.path.dirname(__file__), "data", "tiny_llama_model")


# ---------------------------------------------------------------------------
# LoopLagMonitor units (injectable clock — no real sleeping)
# ---------------------------------------------------------------------------
class FakeClock:
    """utils/clock.Clock implementation on virtual time; ``sleep``
    returns immediately, advancing by the requested span plus the
    injected per-sleep lag (one event-loop yield keeps the heartbeat
    cooperative instead of spinning)."""

    def __init__(self):
        self.t = 100.0
        self.extra_lag = 0.0

    def monotonic(self) -> float:
        return self.t

    async def sleep(self, seconds: float) -> None:
        self.t += seconds + self.extra_lag
        await asyncio.sleep(0)


def test_note_lag_window_and_percentiles():
    clk = FakeClock()
    mon = LoopLagMonitor(interval_s=0.01, window=64, clock=clk)
    for i in range(100):
        mon.note_lag(0.001 * (i % 10))
    snap = mon.snapshot()
    assert snap["beats"] == 100
    # window bounded: only the last 64 lags back the summary
    assert snap["lag"]["max_ms"] == 9.0
    assert 0.0 <= snap["lag"]["p50_ms"] <= 9.0
    assert snap["lag"]["p50_ms"] <= snap["lag"]["p99_ms"] <= 9.0
    assert snap["last_lag_ms"] == 9.0
    assert snap["stalls"] == 0 and snap["running"] is False


def test_note_lag_negative_clamped():
    mon = LoopLagMonitor(interval_s=0.01, clock=FakeClock())
    mon.note_lag(-0.5)  # clock jitter must not mint negative lag
    assert mon.snapshot()["lag"]["max_ms"] == 0.0
    mon.note_lag(0.02)
    snap = mon.snapshot()
    assert snap["beats"] == 2 and snap["lag"]["max_ms"] == 20.0


def test_stall_fires_exactly_one_bundle_per_holdoff(tmp_path):
    clk = FakeClock()
    rec = FlightRecorder(
        capacity=16, dump_dir=str(tmp_path), min_dump_interval_s=0.0
    )
    bb = BlackBox(
        recorder=rec, dump_dir=str(tmp_path), min_interval_s=0.0
    )
    mon = LoopLagMonitor(
        interval_s=0.01, stall_s=0.05, holdoff_s=60.0,
        recorder=rec, blackbox=bb, clock=clk,
    )
    d1 = mon.note_lag(0.08)  # stall -> bundle
    d2 = mon.note_lag(0.09)  # still inside the holdoff -> suppressed
    assert d1 is not None and d2 is None
    bb.flush()
    assert bb.stats()["dumps"] == 1
    with open(os.path.join(d1, "meta.json")) as f:
        assert json.load(f)["reason"] == "loop_stall"
    snap = mon.snapshot()
    assert snap["stalls"] == 2  # every stall counts, one bundle fires
    assert snap["blackbox"]["dumps"] == 1
    # the flight ring carries the loop_stall record
    kinds = [r["kind"] for r in rec.snapshot(16)]
    assert "loop_stall" in kinds
    # advancing the virtual clock past the holdoff re-arms the watchdog
    clk.t += 61.0
    d3 = mon.note_lag(0.07)
    assert d3 is not None
    bb.flush()
    assert bb.stats()["dumps"] == 2


async def test_heartbeat_measures_injected_lag_on_virtual_time():
    clk = FakeClock()
    clk.extra_lag = 0.25
    mon = LoopLagMonitor(interval_s=0.01, clock=clk)
    mon.start()
    mon.start()  # idempotent: one heartbeat task, not two
    try:
        for _ in range(20):
            await asyncio.sleep(0)
        snap = mon.snapshot()
        assert snap["running"] is True
        assert snap["beats"] >= 1
        # every virtual sleep returned exactly extra_lag late
        assert snap["last_lag_ms"] == 250.0
        assert snap["tasks"].get("hostplane-heartbeat") == 1
    finally:
        await mon.stop()
    assert mon.snapshot()["running"] is False


def test_task_census_groups_name_families():
    async def run():
        async def idle():
            await asyncio.sleep(10)

        tasks = [
            asyncio.ensure_future(idle(), loop=asyncio.get_running_loop())
            for _ in range(3)
        ]
        for i, t in enumerate(tasks):
            t.set_name(f"sse-pump-{i}")
        await asyncio.sleep(0)
        fams = task_census()
        for t in tasks:
            t.cancel()
        return fams

    fams = asyncio.run(run())
    assert fams["sse-pump"] == 3


# ---------------------------------------------------------------------------
# HostCostLedger units (manual clock)
# ---------------------------------------------------------------------------
def test_ledger_stamps_all_stages_and_ttfb_split():
    t = [1000.0]
    led = HostCostLedger(clock=lambda: t[0])
    led.begin("r1", "chat")
    for s in STAGES:
        led.stage("r1", s, 0.010)
    led.stage("r1", "tool_parser", 0.005)  # repeat calls accumulate
    led.mark_stream("r1")
    assert led.summary()["streams_open"] == 1
    t[0] += 0.1  # first chunk lands 100 ms after begin
    led.chunk("r1", serialize_s=0.001, write_s=0.002, nbytes=64)
    led.chunk("r1", serialize_s=0.001, write_s=0.0001, nbytes=64)
    led.finish("r1", "200")
    led.finish("r1", "200")  # idempotent: one row, not two
    snap = led.snapshot(recent=4)
    assert snap["requests_total"] == 1
    assert snap["streams_open"] == 0 and snap["streams_total"] == 1
    assert snap["chunks_total"] == 2
    rows = snap["recent"]
    assert len(rows) == 1
    row = rows[0]
    assert row["stream"] is True and row["status"] == "200"
    assert set(row["stages_ms"]) == set(STAGES)
    assert row["stages_ms"]["tool_parser"] == 15.0  # 10 + 5 accumulated
    assert row["chunks"] == 2 and row["bytes"] == 128
    # one write (2 ms) crossed the 1 ms drain threshold
    assert row["drain_waits"] == 1
    assert row["drain_wait_ms"] == 2.0
    assert row["ttfb_ms"] == 100.0
    # host TTFB = TTFB minus the engine's first-chunk wait (prime)
    assert row["host_ttfb_ms"] == 90.0
    assert snap["window"]["stage_ms_mean"]["prime"] == 10.0
    assert snap["window"]["engine_first_chunk_ms_mean"] == 10.0


def test_ledger_bounds_active_table_and_ignores_unknown_rids():
    led = HostCostLedger(max_active=4)
    for i in range(10):
        led.begin(f"r{i}", "chat")
    assert led.summary()["active"] <= 4
    led.stage("nope", "prime", 1.0)  # unknown rid: no-op, no crash
    led.chunk("nope", 0.1, 0.1)
    led.finish("nope")
    note_stage(None, "prime", 1.0)  # rid-less engines stamp nowhere


def test_note_stage_routes_to_global_ledger():
    rid = "hostplane-note-stage-test"
    LEDGER.begin(rid, "chat")
    try:
        note_stage(rid, "dispatch", 0.004)
        note_stage(rid, "dispatch", 0.002)
    finally:
        LEDGER.finish(rid, "200")
    row = next(
        r for r in LEDGER.snapshot(recent=64)["recent"] if r["rid"] == rid
    )
    assert row["stages_ms"]["dispatch"] == 6.0


# ---------------------------------------------------------------------------
# /debug/hostplane provider registry
# ---------------------------------------------------------------------------
def test_collect_hostplane_providers_and_error_stanza():
    register_hostplane_provider("t_ok", lambda: {"x": 1})

    def boom():
        raise RuntimeError("torn")

    register_hostplane_provider("t_bad", boom)
    try:
        snap = collect_hostplane()
        assert snap["t_ok"] == {"x": 1}
        assert "RuntimeError" in snap["t_bad"]["error"]
        assert "ts" in snap and "pid" in snap
    finally:
        unregister_hostplane_provider("t_ok")
        unregister_hostplane_provider("t_bad")


# ---------------------------------------------------------------------------
# e2e through the real HttpService (CounterEngine pattern,
# tests/test_http_service.py)
# ---------------------------------------------------------------------------
class CounterEngine(AsyncEngine):
    def __init__(self, n: int = 3, delay: float = 0.0, block_s: float = 0.0):
        self.n = n
        self.delay = delay
        self.block_s = block_s

    async def _gen(self, request: Any, ctx: Context) -> AsyncIterator[Any]:
        assert isinstance(request, ChatCompletionRequest)
        gen = ChatDeltaGenerator(model=request.model)
        if self.block_s:
            time.sleep(self.block_s)  # deliberate sync loop stall
        for i in range(self.n):
            if self.delay:
                await asyncio.sleep(self.delay)
            yield gen.text_chunk(f"w{i} ")
        yield gen.finish_chunk(FinishReason.STOP)

    def generate(self, request: Any, context: Context) -> EngineStream:
        return self._gen(request, context)


async def _start_service(engine, **kw) -> tuple[HttpService, str]:
    manager = ModelManager()
    manager.add_chat_model("foo", engine)
    service = HttpService(manager, host="127.0.0.1", port=0, **kw)
    await service.start()
    return service, f"http://127.0.0.1:{service.port}"


def _recent_rows(hp: dict) -> list:
    return hp["frontend"]["ledger"]["recent"]


async def test_ledger_rows_nonstream_and_stream_e2e():
    from dynamo_tpu.http.admission import AdmissionConfig, AdmissionController

    # permissive admission (unknown load admits) so the admission
    # stage + stanza are live without shedding anything
    service, base = await _start_service(
        CounterEngine(n=3),
        admission=AdmissionController(AdmissionConfig(), load_fn=lambda: None),
    )
    try:
        async with aiohttp.ClientSession() as s:
            payload = {
                "model": "foo",
                "messages": [{"role": "user", "content": "hi"}],
            }
            async with s.post(f"{base}/v1/chat/completions", json=payload) as r:
                assert r.status == 200
                await r.json()
            async with s.get(f"{base}/debug/hostplane") as r:
                hp = await r.json()
            row = _recent_rows(hp)[-1]
            assert row["stream"] is False and row["status"] == "200"
            # the non-stream path stamps every frontend-visible stage
            # (prime is streaming-only: it times the first SSE chunk)
            for stage in ("preprocess", "admission", "dispatch"):
                assert stage in row["stages_ms"], row["stages_ms"]
            assert row["chunks"] == 0 and row["ttfb_ms"] is None

            async with s.post(
                f"{base}/v1/chat/completions",
                json=dict(payload, stream=True),
            ) as r:
                assert r.status == 200
                async for _ in r.content:
                    pass
            async with s.get(f"{base}/debug/hostplane") as r:
                hp = await r.json()
            row = _recent_rows(hp)[-1]
            assert row["stream"] is True
            for stage in ("preprocess", "admission", "dispatch", "prime"):
                assert stage in row["stages_ms"], row["stages_ms"]
            # chunks counted, TTFB recorded, and the split resolves
            assert row["chunks"] > 0 and row["bytes"] > 0
            assert row["ttfb_ms"] is not None
            assert "host_ttfb_ms" in row
            assert row["host_ttfb_ms"] <= row["ttfb_ms"]
            # loop + admission stanzas ride the same payload
            assert hp["frontend"]["loop"]["running"] is True
            assert hp["frontend"]["admission"]["checks_total"] >= 2
            assert "check_ema_us" in hp["frontend"]["admission"]
    finally:
        await service.stop()


async def test_debug_hostplane_agrees_with_metrics():
    service, base = await _start_service(CounterEngine(n=4, delay=0.2))
    try:
        async with aiohttp.ClientSession() as s:
            payload = {
                "model": "foo",
                "messages": [{"role": "user", "content": "hi"}],
                "stream": True,
            }

            async def drain():
                async with s.post(
                    f"{base}/v1/chat/completions", json=payload
                ) as r:
                    async for _ in r.content:
                        pass

            task = asyncio.ensure_future(drain())
            await asyncio.sleep(0.3)  # mid-stream: the stream is OPEN
            async with s.get(f"{base}/debug/hostplane") as r:
                hp = await r.json()
            async with s.get(f"{base}/metrics") as r:
                fams = prom_parse(await r.text())
            open_streams = hp["frontend"]["ledger"]["streams_open"]
            assert open_streams >= 1
            assert fams["dynamo_http_open_streams"].samples[
                ("dynamo_http_open_streams", ())
            ] == open_streams
            # stall agreement: one induced stall moves the snapshot
            # counter and the counter series in lockstep
            stalls_before = hp["frontend"]["loop"]["stalls"]
            metric_before = fams["dynamo_http_loop_stalls_total"].samples[
                ("dynamo_http_loop_stalls_total", ())
            ]
            service.lag_monitor.note_lag(0.06)
            async with s.get(f"{base}/debug/hostplane") as r:
                hp2 = await r.json()
            async with s.get(f"{base}/metrics") as r:
                fams2 = prom_parse(await r.text())
            assert hp2["frontend"]["loop"]["stalls"] == stalls_before + 1
            assert fams2["dynamo_http_loop_stalls_total"].samples[
                ("dynamo_http_loop_stalls_total", ())
            ] == metric_before + 1
            # lag histogram + gauges exist on the scrape surface
            for fam in (
                "dynamo_http_loop_lag_seconds",
                "dynamo_http_loop_lag_p99_seconds",
                "dynamo_http_host_stage_seconds",
                "dynamo_http_sse_write_ema_seconds",
            ):
                assert fam in fams2, fam
            await task
    finally:
        await service.stop()


async def test_induced_sync_stall_dumps_exactly_one_bundle(tmp_path):
    """The acceptance drill: a handler that blocks the loop for 120 ms
    produces exactly ONE loop_stall black-box bundle, visible in
    /debug/hostplane."""
    rec = FlightRecorder(
        capacity=32, dump_dir=str(tmp_path), min_dump_interval_s=0.0
    )
    bb = BlackBox(recorder=rec, dump_dir=str(tmp_path), min_interval_s=0.0)
    monitor = LoopLagMonitor(
        interval_s=0.01, stall_s=0.05, holdoff_s=60.0,
        recorder=rec, blackbox=bb,
    )
    service, base = await _start_service(
        CounterEngine(n=1, block_s=0.12), lag_monitor=monitor
    )
    try:
        async with aiohttp.ClientSession() as s:
            payload = {
                "model": "foo",
                "messages": [{"role": "user", "content": "hi"}],
            }
            for _ in range(2):  # two stalls, one holdoff window
                async with s.post(
                    f"{base}/v1/chat/completions", json=payload
                ) as r:
                    assert r.status == 200
                    await r.json()
                await asyncio.sleep(0.05)  # let the heartbeat catch up
            bb.flush()
            async with s.get(f"{base}/debug/hostplane") as r:
                hp = await r.json()
        loop_snap = hp["frontend"]["loop"]
        assert loop_snap["stalls"] >= 1
        assert loop_snap["blackbox"]["dumps"] == 1
        bundle = loop_snap["blackbox"]["last_dump_dir"]
        with open(os.path.join(bundle, "meta.json")) as f:
            assert json.load(f)["reason"] == "loop_stall"
        # the ring inside the bundle carries the stall record
        flight = open(os.path.join(bundle, "flight.jsonl")).read()
        assert "loop_stall" in flight
    finally:
        await service.stop()


async def test_tool_parser_stamp_rides_note_stage():
    """The preprocessor's backward pass stamps tool_parser time onto
    the live ledger record by request id (Context.child preserves it)."""
    from dynamo_tpu.preprocessor.preprocessor import (
        OpenAIPreprocessor,
        _ReqState,
    )
    from dynamo_tpu.protocols.common import LLMEngineOutput
    from dynamo_tpu.tokenizer import Tokenizer

    pre = OpenAIPreprocessor(
        Tokenizer.from_file(MODEL_DIR), formatter=None, model_name="tiny"
    )
    state = _ReqState(
        kind="chat", model="tiny", request_id="r", prompt_tokens=3,
        include_usage=True, logprobs=False, tool_mode="forced",
        tool_name="get_weather",
    )

    async def stream():
        for t in ['{"city": ', '"Oslo"}']:
            yield LLMEngineOutput(request_id="r", token_ids=[1], text=t)
        yield LLMEngineOutput(
            request_id="r", finish_reason=FinishReason.STOP,
            prompt_tokens=3, completion_tokens=2,
        )

    rid = "hostplane-toolcall-test"
    LEDGER.begin(rid, "chat")
    try:
        chunks = [
            c async for c in pre.backward(stream(), state, Context(id=rid))
        ]
        assert chunks
    finally:
        LEDGER.finish(rid, "200")
    row = next(
        r for r in LEDGER.snapshot(recent=64)["recent"] if r["rid"] == rid
    )
    assert "tool_parser" in row["stages_ms"]


# ---------------------------------------------------------------------------
# `dynamo-tpu top` host columns
# ---------------------------------------------------------------------------
def _hp_payload(total: int, streams: int = 2, p99: float = 3.5) -> dict:
    return {
        "frontend": {
            "loop": {"lag": {"p50_ms": 1.0, "p99_ms": p99, "max_ms": 9.0}},
            "ledger": {"requests_total": total, "streams_open": streams},
        }
    }


def test_top_hostplane_cols_rules():
    from dynamo_tpu.cli.top import _hostplane_cols

    # no payload at all: every column renders the absence marker
    cols = _hostplane_cols(None, None, now=10.0, prev_ts=5.0)
    assert cols == {"loop_lag_p99_ms": None, "streams_open": None, "rps": None}
    # first poll: lag + streams resolve, RPS needs a prior sample
    cols = _hostplane_cols(_hp_payload(100), None, now=10.0, prev_ts=None)
    assert cols["loop_lag_p99_ms"] == 3.5
    assert cols["streams_open"] == 2
    assert cols["rps"] is None
    # second poll: RPS from the counter delta over the poll gap
    cols = _hostplane_cols(
        _hp_payload(150), _hp_payload(100), now=15.0, prev_ts=10.0
    )
    assert cols["rps"] == 10.0
    # counter rewind (frontend restart) and zero gap both render `-`
    assert _hostplane_cols(
        _hp_payload(50), _hp_payload(100), now=15.0, prev_ts=10.0
    )["rps"] is None
    assert _hostplane_cols(
        _hp_payload(150), _hp_payload(100), now=10.0, prev_ts=10.0
    )["rps"] is None


async def test_top_fetch_hostplane_live_and_down():
    from dynamo_tpu.cli.top import fetch_hostplane

    service, base = await _start_service(CounterEngine())
    try:
        async with aiohttp.ClientSession() as s:
            hp = await fetch_hostplane(s, base)
            assert hp is not None and "frontend" in hp
            # a dead endpoint degrades to None (columns render `-`)
            assert await fetch_hostplane(s, "http://127.0.0.1:9") is None
    finally:
        await service.stop()


def test_top_header_renders_host_columns():
    from dynamo_tpu.cli import top as top_mod

    assert "LAG99" in top_mod.HEADER
    assert "STRM" in top_mod.HEADER
    assert "RPS" in top_mod.HEADER
