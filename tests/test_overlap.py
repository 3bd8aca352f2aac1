"""Overlapped decode pipeline (docs/performance.md): bit-identity vs
the serial loop, late-stop rollback, preemption/block-pressure safety,
the cohort-graduation window entry, and the OverlapTracker /
flight-recorder idle-gap plumbing. CPU-runnable tier-1, like
tests/test_spec.py."""

import asyncio
import os

import numpy as np
import pytest

from dynamo_tpu.protocols.common import (
    FinishReason,
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.runtime.engine import Context
from dynamo_tpu.telemetry.overlap import OverlapTracker

MODEL_DIR = os.path.join(os.path.dirname(__file__), "data", "tiny_llama_model")


# ---------------------------------------------------------------------------
# OverlapTracker units (fake clock)
# ---------------------------------------------------------------------------


class _Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def test_tracker_counts_idle_gap_only_when_queue_empty():
    clk = _Clock()
    tr = OverlapTracker(clock=clk)
    assert tr.note_dispatch() == 0.0  # no completion anchor yet
    clk.t = 1.0
    tr.note_complete()
    clk.t = 1.5
    # queue empty + anchored: the 0.5 s host-side span is device idle
    assert tr.note_dispatch() == pytest.approx(0.5)
    # second dispatch while one is in flight: device has queued work
    clk.t = 1.6
    assert tr.note_dispatch() == 0.0
    clk.t = 2.0
    tr.note_complete()  # oldest harvested; one still in flight
    clk.t = 3.0
    assert tr.note_dispatch() == 0.0  # nonempty queue -> no idle
    s = tr.stats()
    assert s["steps_dispatched"] == 4
    assert s["idle_events"] == 1
    assert s["idle_gap_s_total"] == pytest.approx(0.5)
    assert s["max_idle_gap_ms"] == pytest.approx(500.0)


def test_tracker_all_prior_retirement_and_idle_reset():
    clk = _Clock()
    tr = OverlapTracker(clock=clk)
    tr.note_dispatch()
    tr.note_dispatch()  # e.g. sync=False prefill + synced step
    clk.t = 1.0
    tr.note_complete(all_prior=True)  # the newest sync retires both
    assert tr.inflight == 0
    # note_idle drops the anchor: a no-work wait is not device idleness
    tr.note_idle()
    clk.t = 10.0
    assert tr.note_dispatch() == 0.0
    # reset forgets a poisoned queue (aborted dispatch)
    tr.note_dispatch()
    tr.reset()
    assert tr.inflight == 0


def test_recorder_idle_gap_watchdog_dumps(tmp_path):
    from dynamo_tpu.telemetry.recorder import FlightRecorder

    clk = _Clock()
    rec = FlightRecorder(
        capacity=8, slow_step_s=10.0, dump_dir=str(tmp_path),
        idle_gap_slow_s=0.05, clock=clk,
    )
    # fast step, small gap: no dump
    assert rec.record("decode", 0.001, idle_gap_ms=1.0) is None
    clk.t = 100.0  # past the dump rate limit
    path = rec.record("decode", 0.001, idle_gap_ms=80.0)
    assert path is not None and os.path.exists(path)
    with open(path) as f:
        lines = f.read().splitlines()
    assert '"reason": "idle_gap:decode"' in lines[0]
    assert any('"slow_idle_gap": true' in ln for ln in lines[1:])


# ---------------------------------------------------------------------------
# Engine: overlap vs serial bit-identity
# ---------------------------------------------------------------------------


def _engine_config(**kw):
    from dynamo_tpu.engine.config import EngineConfig

    base = dict(
        model_path=MODEL_DIR, model_name="tiny", random_weights=True,
        num_blocks=64, block_size=8, max_batch_size=4,
        prefill_chunk_size=32, max_model_len=128,
    )
    base.update(kw)
    return EngineConfig(**base)


async def _generate(engine, prompt_ids, max_tokens=8, request_id="r",
                    temperature=None, seed=None, context=None):
    sampling = (
        SamplingOptions(use_greedy=True)
        if temperature is None
        else SamplingOptions(temperature=temperature, seed=seed)
    )
    req = PreprocessedRequest(
        request_id=request_id,
        token_ids=list(prompt_ids),
        sampling=sampling,
        stop=StopConditions(max_tokens=max_tokens, ignore_eos=True),
    )
    out = []
    final = None
    async for item in engine.as_async_engine().generate(
        req, context or Context()
    ):
        out.extend(item.token_ids)
        if item.is_final:
            final = item
    return out, final


PROMPTS = [list(range(1, 12)), list(range(5, 21)), [7, 7, 3, 9, 1, 2]]


async def _decode_all(engine, max_tokens=9, temperature=None, seed=7):
    outs = await asyncio.gather(*[
        _generate(engine, p, max_tokens=max_tokens, request_id=f"r{i}",
                  temperature=temperature, seed=seed)
        for i, p in enumerate(PROMPTS)
    ])
    return [o[0] for o in outs]


async def test_overlap_greedy_bit_identical_vs_serial():
    """THE acceptance criterion: overlap on vs --no-overlap produce the
    same greedy tokens, token for token, at decode_steps=1 — and the
    overlap engine actually pipelined (dispatched with a step still in
    flight at least once). The sampled path must match too: the seed
    stream is identical, only offset by the in-flight lag."""
    from dynamo_tpu.engine.engine import JaxEngine

    eng = await JaxEngine.launch(_engine_config(overlap=True))
    try:
        over = await _decode_all(eng)
        over_sampled = await _decode_all(eng, temperature=0.8)
        assert eng.overlap.steps_dispatched > 0
        dbg = eng.debug_state()["overlap"]
        assert dbg["enabled"] is True
    finally:
        await eng.shutdown()

    eng = await JaxEngine.launch(_engine_config(overlap=False))
    try:
        serial = await _decode_all(eng)
        serial_sampled = await _decode_all(eng, temperature=0.8)
        assert eng.debug_state()["overlap"]["enabled"] is False
    finally:
        await eng.shutdown()
    assert over == serial
    assert over_sampled == serial_sampled
    assert all(len(o) == 9 for o in over)


async def test_overlap_window_graduation_bit_identical():
    """decode_steps > 1: the cohort-graduation entry (prefill dispatch
    chaining first tokens on device into the first window) must not
    change greedy output vs the serial prefill -> window boundary."""
    from dynamo_tpu.engine.engine import JaxEngine

    eng = await JaxEngine.launch(_engine_config(decode_steps=4, overlap=True))
    try:
        over = await _decode_all(eng, max_tokens=11)
    finally:
        await eng.shutdown()
    eng = await JaxEngine.launch(_engine_config(decode_steps=4, overlap=False))
    try:
        serial = await _decode_all(eng, max_tokens=11)
    finally:
        await eng.shutdown()
    assert over == serial
    assert all(len(o) == 11 for o in over)


async def test_overlap_late_stop_discards_inflight_tokens():
    """Late-detected stop: a cancellation that lands while a step is in
    flight must terminate the stream with nothing extra emitted after
    the cancel is observed, free every block, and leave the prefix
    cache clean — a fresh continuation through the same engine matches
    a fresh engine's (post-stop tokens were never content-addressed)."""
    from dynamo_tpu.engine.engine import JaxEngine
    from dynamo_tpu.runtime.engine import Context

    eng = await JaxEngine.launch(_engine_config(overlap=True))
    try:
        free0 = eng.allocator.num_free
        ctx = Context()
        req = PreprocessedRequest(
            request_id="late-stop",
            token_ids=PROMPTS[0],
            sampling=SamplingOptions(use_greedy=True),
            stop=StopConditions(max_tokens=64, ignore_eos=True),
        )
        stream = eng.as_async_engine().generate(req, ctx)
        got = []
        async for item in stream:
            got.extend(item.token_ids)
            if len(got) >= 2:
                # the backend's stop-string detection cancels exactly
                # like this: via the context, one step late
                ctx.stop_generating()
                break
        # the engine reaps the cancelled sequence and frees its blocks
        await eng.wait_for_state(
            lambda e: not e.scheduler.running and not e.scheduler.waiting
            and not e.scheduler.prefilling
        )
        await eng.wait_for_state(
            lambda e: e.allocator.num_free == free0
        )
        # prefix-cache integrity: continuing prompt+got through the warm
        # cache matches a fresh engine (nothing past the stop committed)
        cont_warm, _ = await _generate(
            eng, PROMPTS[0] + got, max_tokens=4, request_id="cont"
        )
    finally:
        await eng.shutdown()
    fresh = await JaxEngine.launch(_engine_config(overlap=False))
    try:
        cont_fresh, _ = await _generate(
            fresh, PROMPTS[0] + got, max_tokens=4, request_id="cont2"
        )
    finally:
        await fresh.shutdown()
    assert cont_warm == cont_fresh


async def test_overlap_under_block_pressure_matches_roomy_engine():
    """Block exhaustion mid-pipeline: plan_pipelined_decode never
    preempts with a step in flight — it drains back to the serial
    planner, which preempts safely. Output under pressure (preemption +
    recompute) must equal a roomy engine's greedy output."""
    from dynamo_tpu.engine.engine import JaxEngine

    prompts = [list(range(1, 14)), list(range(3, 17)), list(range(2, 13))]

    async def run(num_blocks):
        eng = await JaxEngine.launch(
            _engine_config(overlap=True, num_blocks=num_blocks)
        )
        try:
            outs = await asyncio.gather(*[
                _generate(eng, p, max_tokens=16, request_id=f"p{i}")
                for i, p in enumerate(prompts)
            ])
            return [o[0] for o in outs], eng.scheduler.preemptions
        finally:
            await eng.shutdown()

    # 13 usable blocks of 8 tokens: the three sequences need ~12 at
    # their ends, so growth collides mid-decode and someone recomputes
    tight, _ = await run(14)
    roomy, roomy_preempt = await run(64)
    assert roomy_preempt == 0
    assert tight == roomy
    assert all(len(t) == 16 for t in tight)


async def test_overlap_records_phase_stamps():
    """The flight recorder's decode records carry the overlap phase
    stamps (overlap_ms / idle_gap_ms / sync_ms) so the win is
    measurable, not asserted — and /debug/state exposes the tracker."""
    from dynamo_tpu.engine.engine import JaxEngine

    eng = await JaxEngine.launch(_engine_config(overlap=True))
    try:
        await _generate(eng, PROMPTS[0], max_tokens=6)
        recs = [r for r in eng.recorder.snapshot(64) if r["kind"] == "decode"]
        assert recs, "no decode records"
        piped = [r for r in recs if "overlap_ms" in r]
        assert piped, "no pipelined decode records"
        assert all("sync_ms" in r for r in piped)
        assert any("idle_gap_ms" in r for r in recs)
        dbg = eng.debug_state()["overlap"]
        assert dbg["steps_dispatched"] > 0
    finally:
        await eng.shutdown()


# ---------------------------------------------------------------------------
# A waiting queue that only a finish can move (Scheduler.admission_work)
# ---------------------------------------------------------------------------

# r0 and r1 run long; r2 and r3 cannot be placed until one of them ends
_LONG = [list(range(1, 12)), list(range(30, 41))]
_BLOCKED = [list(range(60, 80)), list(range(100, 120))]


def _spy(eng) -> dict:
    """Engine-thread stamps, counted in device dispatches: when each
    request entered ``waiting``, was admitted, and finished."""
    sched = eng.scheduler
    log = {"intake": {}, "admit": {}, "finish": {}}
    add, admit, fin = sched.add_request, sched._admit, sched.on_finish

    def add_request(seq):
        log["intake"].setdefault(seq.request_id, eng.overlap.steps_dispatched)
        add(seq)

    def _admit():
        before = {id(s) for s in sched.prefilling}
        admit()
        for s in sched.prefilling:
            if id(s) not in before:
                log["admit"][s.request_id] = eng.overlap.steps_dispatched

    def on_finish(seq, reason):
        log["finish"][seq.request_id] = (eng.overlap.steps_dispatched, reason)
        fin(seq, reason)

    sched.add_request, sched._admit, sched.on_finish = add_request, _admit, on_finish
    return log


async def _launch_blocking(cause: str, overlap: bool):
    """An engine in which two long requests leave no room for a third,
    for ``cause``: the page reserve (22 pages of 8 tokens: when r0 ends
    at 75 tokens it holds 10, r1 is as long by then, and a third row
    beside them — one that outlives r0 — holds 3 however late it came
    in; once r0 is gone, r1's 14 at its end and the 7 the third row has
    grown to by then fit),
    the batch rows, or the state slots (kimi tiny; the test holds one
    of three slots, so the rows are not what runs out)."""
    from dynamo_tpu.engine.engine import JaxEngine

    if cause == "slots":
        from tests.test_kimi_linear_engine import launch

        eng, _ = await launch(max_batch_size=3, overlap=overlap)
        await eng.acall_on_thread(eng.scheduler.state_slots.acquire)
        return eng
    kw = dict(num_blocks=23) if cause == "reserve" else dict(max_batch_size=2)
    return await JaxEngine.launch(_engine_config(overlap=overlap, **kw))


async def _run_blocking(cause: str, overlap: bool, watch=None):
    """r0..r3 through ``_launch_blocking``'s engine; ``watch(eng)`` runs
    beside them. Returns (tokens of each stream, stamps, the engine's
    counts)."""
    eng = await _launch_blocking(cause, overlap)
    try:
        log = _spy(eng)
        # the reserve lets a short answer in beside the long ones (it is
        # over before they have grown): the one it holds back is longer
        budgets = [64, 96, 70 if cause == "reserve" else 8, 8]
        streams = asyncio.gather(*[
            _generate(eng, p, max_tokens=n, request_id=f"r{i}")
            for i, (p, n) in enumerate(zip(_LONG + _BLOCKED, budgets))
        ])
        if watch is not None:
            await watch(eng)
        outs = await streams
        counts = dict(eng.program_counts(), **{
            "overlap": eng.debug_state()["overlap"],
            "kv_preemptions": eng.scheduler.preemptions,
        })
        return [o[0] for o in outs], log, counts
    finally:
        await eng.shutdown()


@pytest.mark.parametrize("cause", ["reserve", "batch", "slots"])
async def test_blocked_queue_keeps_chaining_and_moves_at_first_finish(cause):
    """Whatever keeps the head of ``waiting`` out (reserve, batch rows,
    state slots), the pipeline keeps chaining while it waits; the first
    finish admits it, in FIFO order and with no preemption; and every
    stream's tokens are the serial engine's."""

    async def chained_while_waiting(eng):
        # all four were submitted before the first decode step, so every
        # chained dispatch so far was issued with r2 and r3 waiting
        await eng.wait_for_state(
            lambda e: len(e.scheduler.waiting) == 2
            and e._decode_dispatches[1] >= 5
        )

    over, log, counts = await _run_blocking(cause, True, chained_while_waiting)
    serial, serial_log, serial_counts = await _run_blocking(cause, False)
    assert over == serial
    assert [len(o) for o in over] == [
        64, 96, 70 if cause == "reserve" else 8, 8]
    for lg, c in ((log, counts), (serial_log, serial_counts)):
        assert list(lg["admit"]) == ["r0", "r1", "r2", "r3"]  # FIFO
        # r2 waited, and went in at r0's finish: no dispatch in between
        assert lg["intake"]["r2"] < lg["admit"]["r2"] == lg["finish"]["r0"][0]
        finishes = {step for step, _ in lg["finish"].values()}
        assert lg["admit"]["r3"] in finishes
        assert c["kv_preemptions"] == 0 and c["preemptions"] == 0
        # the reserve's own counts name the cause: it stopped admission,
        # and never asked for more pages than the sum would have
        assert (c["admit_blocked_reserve"] > 0) == (cause == "reserve")
        assert (0 < c["admit_reserve_peak_pages"]
                <= c["admit_reserve_sum_pages"])
    d, chained = counts["decode_dispatches"], counts["decode_dispatches_chained"]
    assert counts["overlap"]["decode_dispatches"] == d
    assert counts["overlap"]["decode_dispatches_chained"] == chained
    # an unchained dispatch is one entry into the pipeline: after an
    # admission's prefill or a finish, never once per step
    assert 0 < d - chained <= 10 and d > 90
    assert serial_counts["decode_dispatches"] == 0


@pytest.mark.parametrize("how", ["cancelled", "deadline"])
async def test_blocked_request_leaves_the_queue_within_a_step(how):
    """A waiting request that is cancelled, or whose deadline passes,
    while the pipeline runs over a blocked queue is reaped before another
    step is dispatched — as when every waiting request drained it."""
    import time

    from dynamo_tpu.engine.engine import JaxEngine

    # 30 pages: the two long ones need 28 at their ends, and a third
    # row as long as they are would need 14 more beside them
    eng = await JaxEngine.launch(_engine_config(overlap=True, num_blocks=31))
    try:
        log = _spy(eng)
        streams = asyncio.gather(*[
            _generate(eng, p, max_tokens=n, request_id=f"r{i}")
            for i, (p, n) in enumerate(zip(_LONG + _BLOCKED, [100] * 4))
        ])
        await eng.wait_for_state(
            lambda e: len(e.scheduler.waiting) == 2
            and e._decode_dispatches[1] >= 2
        )
        head, behind = list(eng.scheduler.waiting)
        at = eng.overlap.steps_dispatched + 4
        victim = head if how == "cancelled" else behind

        def trigger() -> bool:
            # the engine thread asks this of the head at every step: the
            # event lands exactly when ``at`` programs have been dispatched
            if eng.overlap.steps_dispatched < at:
                return False
            if how == "deadline":
                behind.deadline = time.monotonic() - 1.0
                return False
            return True

        head.is_cancelled = trigger
        await eng.wait_for_state(lambda e: victim.request_id in log["finish"])
        step, reason = log["finish"][victim.request_id]
        assert step == at
        assert reason == (FinishReason.CANCELLED if how == "cancelled"
                          else FinishReason.TIMEOUT)
        assert victim not in eng.scheduler.waiting
        assert "r0" not in log["finish"] and "r1" not in log["finish"]
        chained = eng._decode_dispatches[1]
        # the queue is blocked again behind its new head: chaining goes on
        await eng.wait_for_state(
            lambda e: len(e.scheduler.waiting) == 1
            and e._decode_dispatches[1] >= chained + 5
        )
        head.is_cancelled = lambda: False
        outs = await streams
        assert [len(o[0]) for o in outs] == [
            100, 100, *((0, 100) if how == "cancelled" else (100, 0))]
        assert eng.scheduler.preemptions == 0
    finally:
        await eng.shutdown()


async def test_arrival_into_an_empty_queue_drains_the_pipeline():
    """Nobody has tried to place a request that arrives into an empty
    queue: the pipeline drains for it before the next dispatch, and it is
    admitted at once — exactly as before the blocked-queue rule."""
    from dynamo_tpu.engine.engine import JaxEngine

    async def run(overlap):
        eng = await JaxEngine.launch(_engine_config(overlap=overlap))
        try:
            log = _spy(eng)
            first = asyncio.ensure_future(
                _generate(eng, _LONG[0], max_tokens=80, request_id="r0"))
            await eng.wait_for_state(
                lambda e: e.overlap.steps_dispatched >= 8
                and (not overlap or e._decode_dispatches[1] >= 5))
            late = await _generate(eng, _BLOCKED[0], max_tokens=8,
                                   request_id="r1")
            return (await first)[0], late[0], log
        finally:
            await eng.shutdown()

    over0, over1, log = await run(True)
    serial0, serial1, _ = await run(False)
    assert (over0, over1) == (serial0, serial1)
    assert log["admit"]["r1"] == log["intake"]["r1"] < log["finish"]["r0"][0]
