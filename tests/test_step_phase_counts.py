"""The step loop's one clock (ISSUE 40): every ``dyn.step.*`` phase
counted always (wall and thread CPU), the loop's wall tiled by them,
periods and dispatches by kind, the device asked whether it had run dry,
the three CPU clocks, and the once-a-second count history that
``program_spans.json`` carries — on the tiny CPU engine, in each of the
five step loops."""

import asyncio
import json
import os
import threading
import time

import numpy as np
import pytest

from dynamo_tpu.telemetry import debug as tdebug
from dynamo_tpu.telemetry import spans as tspans
from dynamo_tpu.telemetry import step_span

from tests.test_observability import _engine_cfg, _gen

SEVEN = set(tspans.PHASES)
PROMPTS = [[1, 2, 3, 4, 5, 6, 1, 2, 3, 4, 5, 6, 1, 2, 3],
           [2, 9, 2, 9, 2, 9, 2], list(range(30, 41))]
# the five step loops, by the configuration that selects each
LOOPS = {
    "serial": dict(overlap=False),
    "overlapped-decode": dict(),
    "window": dict(decode_steps=4),
    "speculative": dict(spec_decode="ngram", spec_tokens=4),
    "multimodal": dict(),
}


async def _launch(loop: str, **kw):
    from dynamo_tpu.engine.engine import JaxEngine

    if loop != "multimodal":
        return await JaxEngine.launch(_engine_cfg(**LOOPS[loop], **kw))
    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.models.config import ModelConfig

    mc = ModelConfig(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=128,
    )
    cfg = EngineConfig(
        model_path="", model_name="vlm-test", random_weights=True,
        num_blocks=32, block_size=4, max_batch_size=4,
        enable_prefix_caching=False, **kw,
    )
    return await JaxEngine.launch(cfg, model_config=mc)


async def _drive(engine, loop: str, max_tokens: int = 12) -> None:
    """A deterministic little load: three greedy requests at once (or,
    for the multimodal loop, two requests that inject embeddings)."""
    if loop != "multimodal":
        await asyncio.gather(*[
            _gen(engine, p, max_tokens=max_tokens, request_id=f"r{i}")
            for i, p in enumerate(PROMPTS)])
        return
    from dynamo_tpu.multimodal.embeds import pack_segments
    from dynamo_tpu.protocols.common import (
        PreprocessedRequest,
        SamplingOptions,
        StopConditions,
    )
    from dynamo_tpu.runtime.engine import Context

    async def one(seed: int) -> None:
        embeds = np.random.default_rng(seed).standard_normal(
            (6, 32)).astype(np.float32)
        req = PreprocessedRequest(
            request_id=f"mm-{seed}", token_ids=[5, 6] + [0] * 6 + [7],
            sampling=SamplingOptions(use_greedy=True),
            stop=StopConditions(max_tokens=max_tokens, ignore_eos=True),
            mm_embeds=pack_segments([(2, embeds)]),
        )
        async for _ in engine.as_async_engine().generate(req, Context()):
            pass

    await asyncio.gather(one(1), one(2))


async def _until_idle(engine) -> None:
    """The loop back in its idle ``wait`` (so every phase has ended)."""
    wait = engine.step_clock.phases["wait"]
    seen = wait.calls
    for _ in range(400):
        if wait.calls >= seen + 2:
            return
        await asyncio.sleep(0.01)
    raise AssertionError("the engine never went idle")


def _flat(counts: dict, prefix: str = "") -> dict:
    out: dict = {}
    for k, v in counts.items():
        if isinstance(v, dict):
            out.update(_flat(v, f"{prefix}{k}."))
        else:
            out[prefix + k] = v
    return out


# ---------------------------------------------------------------------------
# every loop passes the seven names, and they tile it
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("loop", list(LOOPS))
async def test_each_loop_passes_the_seven_phases_and_they_tile_it(loop):
    engine = await _launch(loop)
    try:
        seen = [engine.step_clock.counts()]
        for _ in range(3):
            await _drive(engine, loop)
            await _until_idle(engine)
            # a tick brings the loop's wall up to the same instant as the
            # phases' (a snapshot between two laps lacks the open
            # iteration)
            await engine.acall_on_thread(
                lambda: engine.step_clock.tick(time.monotonic_ns()))
            seen.append(engine.step_clock.counts())
        after = seen[-1]
        state = engine.debug_state()
    finally:
        await engine.shutdown()
    phases = after["step_phases"]
    assert set(phases) == SEVEN
    for name, p in phases.items():
        assert p["calls"] > 0, f"the {loop} loop never passed {name}"
        assert p["cpu_ns"] >= 0 and p["wall_ns"] > 0
    # one CPU-clock read a pass: a phase's CPU is the thread's since the
    # phase before ended, so together they are the loop's CPU
    assert sum(p["cpu_ns"] for p in phases.values()) <= after["loop_wall_ns"]
    # what no phase covered, as a share of the loop's wall: a piece of
    # the loop under no phase shows in every drive, a thread that lost
    # the CPU between two phases (six loaded test workers) in one
    shares = [(b["unphased_ns"] - a["unphased_ns"])
              / (b["loop_wall_ns"] - a["loop_wall_ns"])
              for a, b in zip(seen, seen[1:])]
    assert 0 <= min(shares) < 0.03 and after["unphased_ns"] >= 0, shares
    # cumulative: nothing ever decreases
    for a, b in zip(seen, seen[1:]):
        fa, fb = _flat(a), _flat(b)
        assert [k for k, v in fa.items()
                if fb[k] < v and k != "offcpu_ns"] == [], (fa, fb)
    # per dispatch, by kind: the clock and the program's step counts agree
    assert after["dispatches"] == engine.program_counts()["steps"]
    assert sum(after["dispatches"].values()) > 4
    assert all(after["period_ns"][k] > 0 for k in after["period_ns"])
    assert set(after["period_ns"]) <= set(after["dispatches"])
    assert 0 <= after["dispatches_device_drained"] <= sum(
        after["dispatches"].values())
    # off-CPU: the host-work phases' wall less their CPU
    assert after["offcpu_ns"] == sum(
        phases[n]["wall_ns"] - phases[n]["cpu_ns"] for n in tspans.HOST_WORK)
    # /debug/state shows the same stanza
    assert set(state["step_phases"]) == SEVEN
    assert state["dispatches"] == after["dispatches"]


async def test_the_serial_loop_meets_a_drained_device_at_every_dispatch():
    """Serial: each dispatch follows the harvest of the one before, so the
    device's queue is dry every time — except after an idle wait, which is
    no work and not starvation."""
    engine = await _launch("serial")
    try:
        await _gen(engine, PROMPTS[0], max_tokens=10)
        await _until_idle(engine)
        c = engine.step_clock.counts()
    finally:
        await engine.shutdown()
    total = sum(c["dispatches"].values())
    assert c["dispatches"]["decode"] >= 9
    # the one prefill dispatch follows the idle wait: not counted
    assert c["dispatches_device_drained"] == total - 1


# ---------------------------------------------------------------------------
# annotations only inside a capture, under the same names
# ---------------------------------------------------------------------------
class _FakeAnnotation:
    made: list = []

    def __init__(self, name, **attrs):
        _FakeAnnotation.made.append((name, attrs))

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return None


async def test_annotations_only_inside_a_capture_under_the_same_names(
        monkeypatch):
    monkeypatch.setattr(tspans, "_annotation", _FakeAnnotation)
    _FakeAnnotation.made = []
    engine = await _launch("overlapped-decode")
    try:
        await _drive(engine, "overlapped-decode")
        await _until_idle(engine)
        assert _FakeAnnotation.made == []  # no capture: none constructed
        calls = {n: p.calls for n, p in engine.step_clock.phases.items()}
        tspans.set_capture_live(True)
        try:
            await _drive(engine, "overlapped-decode")
            await _until_idle(engine)
        finally:
            tspans.set_capture_live(False)
        made = list(_FakeAnnotation.made)
        await _drive(engine, "overlapped-decode")
        assert _FakeAnnotation.made == made  # and none after it
    finally:
        await engine.shutdown()
    assert {n for n, _ in made} == {tspans.PHASE_PREFIX + n for n in SEVEN}
    attrs = [a for n, a in made if n == "dyn.step.dispatch"]
    assert attrs and all(set(a) == {"kind", "rows", "tokens"} for a in attrs)
    assert {a["kind"] for a in attrs} == {"prefill", "decode"}
    # the capture's phases were clocked too
    assert all(engine.step_clock.phases[n].calls > c for n, c in calls.items())


def test_a_phase_belongs_to_the_thread_that_enters_it():
    mine = tspans.step_clock()
    other: list = []

    def worker():
        with step_span("dyn.step.plan"):
            pass
        other.append(tspans.step_clock())

    calls = mine.phases["plan"].calls
    t = threading.Thread(target=worker)
    t.start()
    t.join(10)
    assert not t.is_alive() and other[0] is not mine
    assert other[0].phases["plan"].calls == 1
    assert mine.phases["plan"].calls == calls
    clock = tspans.StepClock()
    tspans.bind_step_clock(clock)
    try:
        assert step_span("dyn.step.emit") is clock.phases["emit"]
    finally:
        tspans.bind_step_clock(mine)


def test_the_period_leaves_out_the_wait_between_two_dispatches():
    clock = tspans.StepClock()
    tspans.bind_step_clock(clock)
    try:
        with step_span("dyn.step.dispatch", kind="prefill", rows=1, tokens=8):
            pass
        t0 = clock._last_dispatch_ns
        with step_span("dyn.step.wait"):
            time.sleep(0.03)
        clock.note_idle()
        phase = step_span("dyn.step.dispatch", kind="decode", rows=1, tokens=1)
        phase.drained = True
        with phase:
            pass
        t1 = clock._last_dispatch_ns
        phase = step_span("dyn.step.dispatch", kind="decode", rows=1, tokens=1)
        phase.drained = True
        with phase:
            pass
    finally:
        tspans.bind_step_clock(None)
    c = clock.counts()
    assert c["dispatches"] == {"prefill": 1, "decode": 2}
    # the first period is the prefill's, without the 30 ms of wait
    assert c["period_ns"]["prefill"] == t1 - t0 - clock.phases["wait"].wall_ns
    assert c["period_ns"]["prefill"] < 20e6 < clock.phases["wait"].wall_ns
    assert c["period_ns"]["decode"] == clock._last_dispatch_ns - t1
    # drained after an idle wait is not starvation; the next one is
    assert c["dispatches_device_drained"] == 1


# ---------------------------------------------------------------------------
# one clock: the recorder's stamps ARE the phases' elapsed
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("loop", ["serial", "overlapped-decode", "speculative"])
async def test_the_recorders_stamps_are_the_phases_elapsed(loop, monkeypatch):
    passes: dict = {}
    plain_exit = tspans.StepPhase.__exit__

    def logging_exit(self, *exc):
        plain_exit(self, *exc)
        passes.setdefault(self.name[len(tspans.PHASE_PREFIX):], []).append(
            self.last_ns)

    monkeypatch.setattr(tspans.StepPhase, "__exit__", logging_exit)
    engine = await _launch(loop, overlap=False) if loop == "speculative" \
        else await _launch(loop)
    try:
        await _drive(engine, loop)
        await _until_idle(engine)
        steps = engine.recorder.snapshot(64)
    finally:
        await engine.shutdown()
    ms = {name: {round(ns / 1e6, 3) for ns in took}
          for name, took in passes.items()}
    kinds = {r["kind"] for r in steps}
    assert {"spec"} <= kinds if loop == "speculative" else {"decode"} <= kinds
    for r in steps:
        if "dispatch_ms" in r:
            assert r["dispatch_ms"] in ms["dispatch"], r
        if "sync_ms" in r:
            assert r["sync_ms"] in ms["harvest"], r
        if loop == "serial" and "plan_ms" in r:
            assert r["plan_ms"] in ms["plan"], r
        if "draft_ms" in r:
            assert r["draft_ms"] in ms["plan"], r
        if "verify_ms" in r:  # its dispatch pass + its harvest pass
            assert any(abs(r["verify_ms"] - d - h) < 2e-3
                       for d in ms["dispatch"] for h in ms["harvest"]), r
    assert any("dispatch_ms" in r or "verify_ms" in r for r in steps)


async def test_the_step_loops_hold_no_second_clocking():
    """``plan_ms`` / ``dispatch_ms`` / ``sync_ms`` / ``draft_ms`` /
    ``verify_ms`` come from the phase objects: no ``time.monotonic()``
    difference is rounded into one of them any more. And no ledger
    beside the clock re-derives a split of the step from them (the
    modelled roofline went in PR 48)."""
    import inspect
    import re

    from dynamo_tpu.engine import engine as eng

    src = inspect.getsource(eng.JaxEngine)
    stamped = re.findall(
        r"(plan_ms|dispatch_ms|sync_ms|draft_ms|verify_ms)\"?\s*[=:]\s*"
        r"[^\n]*time\.monotonic\(\)", src)
    assert stamped == []
    assert "_capture_live" not in inspect.getsource(tspans.step_span)
    assert "_capture_live" not in inspect.getsource(tspans.StepClock.phase)
    record = inspect.signature(eng.JaxEngine._record_step).parameters
    assert not {"tokens", "overlapped"} & set(record)
    engine = await _launch("overlapped-decode")
    try:
        await _drive(engine, "overlapped-decode", max_tokens=4)
        assert not hasattr(engine, "attribution")
        state = engine.debug_state()
        assert "attribution" not in state
        assert {"step_phases", "blackbox", "recent_steps"} <= set(state)
    finally:
        await engine.shutdown()


async def test_the_record_phase_reads_no_row_of_the_batch(monkeypatch):
    """``dyn.step.record`` costs the same at any batch: with 8 rows
    running, no pass of it reads a row's ``num_computed`` (the attribution
    ledger summed it over every running row on every dispatch)."""
    from dynamo_tpu.engine.scheduler import Sequence

    reads = {"record": 0, "elsewhere": 0}
    rows: list = []
    recording: list = []

    def read(self):
        reads["record" if recording else "elsewhere"] += 1
        return self.__dict__.get("_num_computed", 0)

    def write(self, v):
        self.__dict__["_num_computed"] = v

    monkeypatch.setattr(Sequence, "num_computed", property(read, write))
    plain_enter = tspans.StepPhase.__enter__
    plain_exit = tspans.StepPhase.__exit__
    name = tspans.PHASE_PREFIX + "record"

    def enter(self):
        if self.name == name:
            recording.append(True)
            rows.append(engine.scheduler.num_running)
        return plain_enter(self)

    def leave(self, *exc):
        plain_exit(self, *exc)
        if self.name == name:
            recording.clear()

    monkeypatch.setattr(tspans.StepPhase, "__enter__", enter)
    monkeypatch.setattr(tspans.StepPhase, "__exit__", leave)
    engine = await _launch("overlapped-decode")
    try:
        await asyncio.gather(*[
            _gen(engine, range(1 + i, 12 + i), max_tokens=16,
                 request_id=f"row{i}")
            for i in range(8)])
        await _until_idle(engine)
    finally:
        await engine.shutdown()
    assert max(rows) == 8, rows
    assert reads["elsewhere"] > 0  # the property does count
    assert reads["record"] == 0


# ---------------------------------------------------------------------------
# the three CPU clocks and the count history
# ---------------------------------------------------------------------------
async def test_the_history_ticks_once_a_second_with_host_counts_only(
        monkeypatch):
    from tests.kimi_tiny import tiny_kimi
    from tests.test_kimi_linear_engine import engine_config

    from dynamo_tpu.engine.engine import JaxEngine

    tick = 60_000_000
    monkeypatch.setattr(tspans, "HISTORY_TICK_NS", tick)
    engine = await JaxEngine.launch(engine_config(), model_config=tiny_kimi())
    name = engine._debug_name
    began = time.monotonic_ns()
    device_reads: list = []
    plain = engine._read_family_counts
    engine._read_family_counts = lambda: device_reads.append(1) or plain()
    try:
        await _gen(engine, PROMPTS[0], max_tokens=8)
        served = time.monotonic_ns()
        await asyncio.sleep(0.5)
        mine = [e for e in tdebug.count_history()
                if name in e["counts"] and e["monotonic_ns"] >= began]
        assert device_reads == []  # the tick read no device array ...
        edge = engine.program_counts()  # ... a capture's edge does
        assert device_reads == [1]
    finally:
        await engine.shutdown()
    assert len(mine) >= 4
    stamps = [e["monotonic_ns"] for e in mine]
    assert all(b - a >= tick for a, b in zip(stamps, stamps[1:]))
    # an idle loop ticks too, no more than a wait's timeout (50 ms) late
    # (a step that compiles ends no phase for seconds: only idle is held)
    idle = [t for t in stamps if t >= served]
    assert len(idle) >= 3
    assert all(b - a < tick + 0.2e9 for a, b in zip(idle, idle[1:]))
    flat = [_flat(e["counts"][name]) for e in mine]
    # (``offcpu_ns`` is a difference of two clocks, not a count: the CPU
    # a phase is charged runs from the end of the phase before it)
    for a, b in zip(flat, flat[1:]):
        assert [k for k, v in a.items()
                if b[k] < v and k != "offcpu_ns"] == []
    newest = mine[-1]["counts"][name]
    family = set(edge) - set(newest)
    assert family and all(k.startswith("moe_") or "recurrent" in k
                          for k in family), family
    for key in ("step_phases", "unphased_ns", "loop_wall_ns", "offcpu_ns",
                "dispatches", "period_ns", "dispatches_device_drained",
                "steps", "decode_dispatches", "decode_dispatches_chained",
                "prefill_tokens_real", "prefill_tokens_padded",
                "admit_blocked_reserve", "admit_reserve_peak_pages",
                "admit_reserve_sum_pages",
                "state_slot_steps_used"):
        assert key in newest, key
    cpu = newest["cpu_ns"]
    assert 0 < cpu["engine"] <= cpu["process"]
    if hasattr(time, "pthread_getcpuclockid"):
        assert 0 < cpu["loop"] <= cpu["process"]
        assert cpu["loop"] == pytest.approx(time.thread_time_ns(), rel=0.5)
    json.dumps(mine)  # plain numbers all the way down


def test_the_history_is_a_ring():
    before = tdebug.count_history()
    try:
        for i in range(tdebug.HISTORY_LEN + 7):
            tdebug.note_counts("ring", {"i": i}, i)
        kept = tdebug.count_history()
        assert len(kept) == tdebug.HISTORY_LEN == 1024
        assert kept[0]["counts"]["ring"]["i"] == 7
        assert kept[-1] == {"monotonic_ns": tdebug.HISTORY_LEN + 6,
                            "counts": {"ring": {"i": tdebug.HISTORY_LEN + 6}}}
    finally:
        tdebug._history.clear()
        tdebug._history.extend(before)


async def test_the_span_file_carries_the_history_and_the_captures_end(
        tmp_path, monkeypatch):
    import jax

    monkeypatch.setattr(tspans, "HISTORY_TICK_NS", 40_000_000)
    monkeypatch.setattr(jax.profiler, "start_trace", lambda d, *a, **kw: None)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: time.sleep(0.08))
    engine = await _launch("overlapped-decode")
    name = engine._debug_name
    try:
        await _drive(engine, "overlapped-decode")
        out = await tdebug.capture_profile(60, str(tmp_path / "cap"))
        path = os.path.join(out["trace_dir"], tdebug.PROGRAM_SPANS_FILE)
        with open(path) as f:
            doc = json.load(f)
        await _drive(engine, "overlapped-decode")
        await asyncio.sleep(0.15)
    finally:
        await engine.shutdown()
    assert doc["written"] == "capture_end"
    # `stop` is stop_trace's call, `end` its return: 80 ms apart here
    assert doc["end"]["monotonic_ns"] - doc["stop"]["monotonic_ns"] >= 80e6
    assert doc["end"]["time_ns"] > doc["stop"]["time_ns"]
    assert doc["start"]["monotonic_ns"] < doc["stop"]["monotonic_ns"]
    inside = [e for e in doc["history"] if name in e["counts"]]
    assert inside and inside[-1]["monotonic_ns"] <= doc["end"]["monotonic_ns"]
    # the edges still carry every count they carried, and the new ones
    for edge in ("start", "stop"):
        counts = doc[edge]["counts"][name]
        assert counts["steps"]["decode"] > 0 and "step_phases" in counts
    # the engine is gone; the shutdown copy still holds what it noted,
    # the seconds after the capture included
    assert tdebug.write_program_spans("shutdown") == path
    with open(path) as f:
        again = json.load(f)
    assert again["written"] == "shutdown" and again["end"] == doc["end"]
    later = [e for e in again["history"] if name in e["counts"]
             and e["monotonic_ns"] > doc["end"]["monotonic_ns"]]
    assert len(later) >= 2
    assert len(again["history"]) > len(doc["history"]) \
        or len(again["history"]) == tdebug.HISTORY_LEN


# ---------------------------------------------------------------------------
# the decode pipeline's in-line admissions and finishes (ISSUE 43)
# ---------------------------------------------------------------------------
INLINE_COUNTS = ("prefill_dispatches_inline", "finishes_inline")


async def test_in_line_counts_show_everywhere_and_the_phases_still_tile(
        tmp_path, monkeypatch):
    """Arrivals that the pipeline prefills behind a step in flight, and
    finishes it does not flush for: the three counts are in
    ``/debug/state``, in the count history and at a capture's edges;
    the in-line ``prefill`` dispatches are the clock's ``dispatches.prefill``
    / ``period_ns.prefill`` like any other; and the phases still tile
    the loop's wall."""
    import jax

    monkeypatch.setattr(tspans, "HISTORY_TICK_NS", 40_000_000)
    monkeypatch.setattr(jax.profiler, "start_trace", lambda d, *a, **kw: None)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    engine = await _launch("overlapped-decode")
    name = engine._debug_name

    async def tick() -> dict:
        await _until_idle(engine)
        await engine.acall_on_thread(
            lambda: engine.step_clock.tick(time.monotonic_ns()))
        return engine.step_clock.counts()

    try:
        began = time.monotonic_ns()
        seen = [await tick()]
        for r in range(3):
            chained = engine._decode_dispatches[1]
            long_ = asyncio.ensure_future(_gen(
                engine, PROMPTS[0], max_tokens=48, request_id=f"long{r}"))
            await engine.wait_for_state(
                lambda e: e._decode_dispatches[1] >= chained + 3)
            for k in (1, 2):  # each arrives, and ends, while long_ decodes
                await _gen(engine, PROMPTS[k], max_tokens=5,
                           request_id=f"short{r}-{k}")
            await long_
            seen.append(await tick())
        out = await tdebug.capture_profile(60, str(tmp_path / "cap"))
        with open(os.path.join(out["trace_dir"],
                               tdebug.PROGRAM_SPANS_FILE)) as f:
            doc = json.load(f)
        state = engine.debug_state()
        counts = engine.program_counts()
    finally:
        await engine.shutdown()
    # six arrivals behind a step in flight, six finishes beside a row
    # that went on; nothing emptied the pipeline but the ticks above
    assert counts["prefill_dispatches_inline"] == 6
    assert counts["finishes_inline"] == 6
    assert set(counts["pipeline_drains"]) == {
        "unpredicted_finish", "admission", "blocks", "irregular", "control",
        "speculation"}
    assert sum(counts["pipeline_drains"].values()) == 0
    for key in (*INLINE_COUNTS, "pipeline_drains"):
        assert state["overlap"][key] == counts[key]
        for edge in ("start", "stop"):
            assert doc[edge]["counts"][name][key] == counts[key]
    mine = [e["counts"][name] for e in doc["history"]
            if name in e["counts"] and e["monotonic_ns"] >= began]
    assert len(mine) >= 4
    for key in INLINE_COUNTS:
        grown = [c[key] for c in mine]
        assert grown == sorted(grown) and grown[-1] == counts[key]
    assert all(sum(c["pipeline_drains"].values()) == 0 for c in mine)
    # an in-line prefill is a ``prefill`` dispatch of the one clock
    after = seen[-1]
    assert after["dispatches"] == counts["steps"]
    assert after["dispatches"]["prefill"] == 9  # three ways in, six in line
    assert after["period_ns"]["prefill"] > 0
    shares = [(b["unphased_ns"] - a["unphased_ns"])
              / (b["loop_wall_ns"] - a["loop_wall_ns"])
              for a, b in zip(seen, seen[1:])]
    assert 0 <= min(shares) < 0.03, shares
