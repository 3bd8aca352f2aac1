"""The span stream a profiler capture writes out (ISSUE 25): the bounded
in-memory sink, spans on two clocks, the engine's request spans tiling
submit -> finish, step phases on the profiler's clock, the
``program_spans.json`` beside a capture, and the capture staying off the
event loop and exclusive."""

import asyncio
import glob
import json
import os
import threading
import time

import pytest

from dynamo_tpu.telemetry import (
    SpanBuffer,
    Tracer,
    get_tracer,
    reset_tracer,
    step_span,
)
from dynamo_tpu.telemetry import debug as tdebug
from dynamo_tpu.telemetry import spans as tspans

from tests.test_observability import _engine_cfg, _gen


@pytest.fixture
def buffered_tracer():
    reset_tracer()
    tracer = get_tracer()
    tracer.keep_in_memory()
    yield tracer
    reset_tracer()


# ---------------------------------------------------------------------------
# the sink and the clocks
# ---------------------------------------------------------------------------
def test_span_buffer_is_bounded_and_counts_what_it_drops():
    t = Tracer()
    assert t.buffer is None and not t.enabled
    buf = t.keep_in_memory()
    assert t.keep_in_memory() is buf and t.enabled
    assert buf._spans.maxlen == SpanBuffer.CAPACITY == 16_384
    small = SpanBuffer(capacity=4)
    t.add_exporter(small)
    for i in range(10):
        t.span(f"s{i}").end()
    kept, dropped = small.snapshot()
    assert [s["name"] for s in kept] == ["s6", "s7", "s8", "s9"]
    assert dropped == 6
    assert buf.snapshot()[1] == 0 and len(buf.snapshot()[0]) == 10


def test_span_carries_both_clocks():
    t = Tracer()
    buf = t.keep_in_memory()
    wall0, mono0 = time.time(), time.monotonic_ns()
    with t.span("x"):
        time.sleep(0.01)
    wall1, mono1 = time.time(), time.monotonic_ns()
    (s,), _ = buf.snapshot()
    assert wall0 <= s["start"] <= wall1
    assert mono0 <= s["start_mono_ns"] <= mono1
    assert 0.01 <= s["duration_s"] <= (mono1 - mono0) / 1e9
    # the two starts are one instant: their offset is the clocks' offset
    offset = wall0 - mono0 / 1e9
    assert s["start"] - s["start_mono_ns"] / 1e9 == pytest.approx(offset, abs=0.05)


def test_record_keeps_the_callers_monotonic_stamp():
    t = Tracer()
    buf = t.keep_in_memory()
    stamp = time.monotonic() - 1.5  # what a scheduler stamped 1.5 s ago
    t.record("engine.prefill", start_mono=stamp, duration_s=0.25)
    t.record("legacy", start=time.time() - 2.0, duration_s=0.5)
    (a, b), _ = buf.snapshot()
    assert a["start_mono_ns"] == int(stamp * 1e9) and a["duration_s"] == 0.25
    assert a["start"] == pytest.approx(time.time() - 1.5, abs=0.05)
    assert b["start_mono_ns"] == pytest.approx(
        time.monotonic_ns() - 2.0e9, abs=5e7)
    with pytest.raises(ValueError):
        t.record("neither", duration_s=0.1)


# ---------------------------------------------------------------------------
# the engine's request spans
# ---------------------------------------------------------------------------
async def test_engine_spans_tile_submit_to_finish(buffered_tracer):
    from dynamo_tpu.engine.engine import JaxEngine

    engine = await JaxEngine.launch(_engine_cfg())
    try:
        prompt = list(range(1, 71))  # 70 tokens: chunks of 32, 32, 6
        before = time.monotonic_ns()
        await _gen(engine, prompt, max_tokens=6, request_id="first")
        await _gen(engine, prompt, max_tokens=6, request_id="again")
        after = time.monotonic_ns()
        counts = engine.program_counts()
    finally:
        await engine.shutdown()
    kept, dropped = buffered_tracer.buffer.snapshot()
    assert dropped == 0
    by_trace: dict = {}
    for s in kept:
        by_trace.setdefault(s["trace_id"], {})[s["name"]] = s
    assert len(by_trace) == 2
    first, again = sorted(
        by_trace.values(), key=lambda t: t["engine.queue_wait"]["start_mono_ns"])
    for spans in (first, again):
        q, p, d = (spans[f"engine.{n}"] for n in ("queue_wait", "prefill", "decode"))
        assert before <= q["start_mono_ns"]
        # no gap and no overlap: each span starts where the last one ended
        # (a microsecond: the stamps are float seconds)
        assert q["start_mono_ns"] + q["duration_s"] * 1e9 == pytest.approx(
            p["start_mono_ns"], abs=1e3)
        assert p["start_mono_ns"] + p["duration_s"] * 1e9 == pytest.approx(
            d["start_mono_ns"], abs=1e3)
        assert d["start_mono_ns"] + d["duration_s"] * 1e9 <= after
        assert q["attrs"]["waiting"] == 0
        assert p["attrs"]["prompt_tokens"] == 70
        assert d["attrs"]["tokens"] == 6
        # the server's own TTFT: submit to the first token, which the
        # step that finished the prompt sampled
        ttft_ns = d["attrs"]["ttft_ms"] * 1e6
        assert ttft_ns >= q["duration_s"] * 1e9 + p["duration_s"] * 1e9 - 1e3
        assert q["start_mono_ns"] + ttft_ns <= d["start_mono_ns"] + d["duration_s"] * 1e9
    assert first["engine.prefill"]["attrs"]["cached_tokens"] == 0
    assert first["engine.prefill"]["attrs"]["chunks"] == 3
    # the second request finds the first one's full pages (8 tokens each)
    cached = again["engine.prefill"]["attrs"]["cached_tokens"]
    assert 0 < cached < 70 and cached % 8 == 0
    assert again["engine.prefill"]["attrs"]["chunks"] < 3
    # the counts a capture reads at its edges say the same
    assert counts["prompt_tokens"] == 140
    assert counts["cached_prompt_tokens"] == cached
    assert counts["preemptions"] == 0
    assert counts["steps"]["prefill"] == 3 + again["engine.prefill"]["attrs"]["chunks"]
    assert counts["steps"]["decode"] >= 10
    # every prefill dispatch counted: the tokens its chunks held, and the
    # rows x tokens of the rectangle it ran (never fewer)
    assert counts["prefill_tokens_real"] == 140 - cached
    assert counts["prefill_tokens_padded"] >= counts["prefill_tokens_real"]
    # one request at a time: each arrived at an empty engine, so admission
    # never weighed one against the page reserve
    assert [counts[k] for k in (
        "admit_blocked_reserve", "admit_reserve_peak_pages",
        "admit_reserve_sum_pages")] == [0] * 3


# ---------------------------------------------------------------------------
# step phases and the file beside a capture
# ---------------------------------------------------------------------------
def test_step_span_annotates_nothing_without_a_capture(monkeypatch):
    """Outside a capture a phase is clocked (ISSUE 40) and makes no
    ``TraceAnnotation``: the profiler is not touched."""
    made = []
    monkeypatch.setattr(tspans, "_annotation", lambda *a, **kw: made.append(a))
    assert not tspans._capture_live
    phase = step_span("dyn.step.plan", kind="decode")
    assert phase is step_span("dyn.step.plan")  # one object a name a thread
    calls = phase.calls
    with phase:
        pass
    assert made == [] and phase.calls == calls + 1


def test_a_capture_holds_step_spans_and_writes_the_span_file(
        tmp_path, buffered_tracer):
    from jax.profiler import ProfileData

    tdebug.register_count_provider("t_counts", lambda: {"steps": {"decode": 7}})
    buffered_tracer.span("before.capture").end()
    done = threading.Event()

    def engine_thread():
        while not done.is_set():
            with step_span("dyn.step.dispatch", kind="decode", rows=8, tokens=8):
                time.sleep(0.002)

    worker = threading.Thread(target=engine_thread)
    worker.start()
    try:
        before = time.monotonic_ns()
        out = tdebug.profile_blocking(150, str(tmp_path / "cap"))
        after = time.monotonic_ns()
    finally:
        done.set()
        worker.join(10)
        tdebug.unregister_count_provider("t_counts")
    assert not worker.is_alive() and not tspans._capture_live
    (pb,) = glob.glob(os.path.join(
        out["trace_dir"], "plugins", "profile", "*", "*.xplane.pb"))
    names = [ev.name for plane in ProfileData.from_file(pb).planes
             for line in plane.lines for ev in line.events]
    assert names.count("dyn.step.dispatch") >= 10
    # python frames stay on: the benchmark reads the capture's edges there
    assert any(n.endswith(" start_trace") for n in names)
    assert any(n.endswith(" stop_trace") for n in names)

    path = os.path.join(out["trace_dir"], tdebug.PROGRAM_SPANS_FILE)
    with open(path) as f:
        doc = json.load(f)
    assert doc["written"] == "capture_end" and doc["dropped"] == 0
    assert [s["name"] for s in doc["spans"]] == ["before.capture"]
    assert before < doc["start"]["monotonic_ns"] < doc["stop"]["monotonic_ns"] < after
    assert doc["stop"]["monotonic_ns"] - doc["start"]["monotonic_ns"] >= 150e6
    assert doc["start"]["time_ns"] < doc["stop"]["time_ns"]
    for edge in ("start", "stop"):
        assert doc[edge]["counts"]["t_counts"] == {"steps": {"decode": 7}}

    # a clean shutdown writes the file again, with what finished since
    buffered_tracer.span("after.capture").end()
    assert tdebug.write_program_spans("shutdown") == path
    with open(path) as f:
        again = json.load(f)
    assert again["written"] == "shutdown"
    assert [s["name"] for s in again["spans"]] == ["before.capture", "after.capture"]
    assert again["start"] == doc["start"] and again["stop"] == doc["stop"]
    assert not os.path.exists(path + ".tmp")


# ---------------------------------------------------------------------------
# the capture and the event loop; one capture at a time
# ---------------------------------------------------------------------------
@pytest.fixture
def stub_profiler(monkeypatch):
    """A profiler whose ``stop_trace`` blocks as a real one does while it
    writes a large trace."""
    import jax

    calls = {"start": 0, "stop": 0, "stop_s": 0.0}

    def start_trace(d, *a, **kw):
        calls["start"] += 1

    def stop_trace():
        calls["stop"] += 1
        time.sleep(calls["stop_s"])

    monkeypatch.setattr(jax.profiler, "start_trace", start_trace)
    monkeypatch.setattr(jax.profiler, "stop_trace", stop_trace)
    return calls


async def test_a_blocking_stop_trace_leaves_the_event_loop_alone(
        tmp_path, stub_profiler):
    stub_profiler["stop_s"] = 2.0
    worst = 0.0

    async def heartbeat():
        nonlocal worst
        while True:
            t = time.monotonic()
            await asyncio.sleep(0.01)
            worst = max(worst, time.monotonic() - t - 0.01)

    beat = asyncio.ensure_future(heartbeat())
    t0 = time.monotonic()
    out = await tdebug.capture_profile(20, str(tmp_path / "cap"))
    took = time.monotonic() - t0
    beat.cancel()
    assert out["duration_ms"] == 20 and took >= 2.0
    assert stub_profiler == {"start": 1, "stop": 1, "stop_s": 2.0}
    assert worst < 0.1, f"the loop was held for {worst:.3f} s"


async def test_black_box_and_debug_profile_captures_cannot_overlap(
        tmp_path, stub_profiler, caplog):
    from dynamo_tpu.telemetry.blackbox import BlackBox

    box = BlackBox(dump_dir=str(tmp_path), profile_ms=50)
    first = asyncio.ensure_future(
        tdebug.capture_profile(400, str(tmp_path / "cap")))
    while stub_profiler["start"] == 0:
        await asyncio.sleep(0.005)
    # the one profiler is taken: a second /debug/profile is refused ...
    with pytest.raises(RuntimeError, match="already running"):
        await tdebug.capture_profile(10, str(tmp_path / "second"))
    # ... and the black box, through the same function, writes its bundle
    # without a profile instead of starting a second session
    with caplog.at_level("ERROR"):
        await asyncio.to_thread(box._capture_profile, str(tmp_path / "bb"))
    assert "black-box profiler capture failed" in caplog.text
    assert stub_profiler["start"] == 1 and stub_profiler["stop"] == 0
    await first
    # afterwards the black box gets its turn
    await asyncio.to_thread(box._capture_profile, str(tmp_path / "bb"))
    assert stub_profiler["start"] == 2 and stub_profiler["stop"] == 2
    assert os.path.exists(os.path.join(tmp_path, "bb", tdebug.PROGRAM_SPANS_FILE))
