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
    # both loops count the rows of the buckets their decode dispatches ran
    # and the padding among them: the same streams took the same rows
    for c in (counts, serial_counts):
        rows, padded = c["decode_rows_dispatched"], c["decode_rows_padded"]
        assert rows >= c["steps"]["decode"] > 0 and 0 <= padded < rows
    assert (counts["decode_rows_dispatched"] - counts["decode_rows_padded"]
            == serial_counts["decode_rows_dispatched"]
            - serial_counts["decode_rows_padded"])


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


async def test_arrival_into_an_empty_queue_is_admitted_before_the_next_dispatch():
    """Nobody has tried to place a request that arrives into an empty
    queue: it is admitted before another program is dispatched — by the
    pipeline itself, in line (ISSUE 43; until then the pipeline drained
    for it) — and its tokens are the serial loop's."""
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


# ---------------------------------------------------------------------------
# The pipeline admits and finishes in line (ISSUE 43)
# ---------------------------------------------------------------------------


def _inline_counts(eng) -> dict:
    c = eng.program_counts()
    return {
        "unchained": c["decode_dispatches"] - c["decode_dispatches_chained"],
        "prefill_inline": c["prefill_dispatches_inline"],
        "prefill": c["steps"].get("prefill", 0),
        "finishes_inline": c["finishes_inline"],
        "drains": dict(c["pipeline_drains"]),
        "preemptions": c["preemptions"],
    }


async def _launch_family(family: str, overlap: bool, **kw):
    """A tiny engine of ``family``: the dense llama (``dense``; with
    ``prefix`` its clients share a two-page prompt head, so admissions hit
    the prefix cache) or kimi with its state plane (``slots``: three
    slots, so every admission after the third takes one a finish gave
    back)."""
    from dynamo_tpu.engine.engine import JaxEngine

    if family == "slots":
        from tests.test_kimi_linear_engine import launch

        eng, _ = await launch(max_batch_size=3, overlap=overlap, **kw)
        return eng
    return await JaxEngine.launch(_engine_config(overlap=overlap, **kw))


async def _closed_loop(eng, family: str, clients=3, requests=4,
                       temperature=None):
    """``clients`` closed-loop clients, each sending its next request as
    soon as the last one ended — by ``max_tokens``, as the benchmark's
    closed cells do. Returns every stream's tokens, in order."""
    head = list(range(40, 56)) if family == "prefix" else []

    async def client(c: int) -> list:
        outs = []
        for k in range(requests):
            n = 5 + (7 * c + 3 * k) % 14
            prompt = head + [(11 * c + 5 * k + j) % 90 + 1 for j in range(n)]
            toks, _ = await _generate(
                eng, prompt, max_tokens=4 + (c + 2 * k) % 9,
                request_id=f"c{c}-{k}", temperature=temperature, seed=7 + c,
            )
            outs.append(toks)
        return outs

    return await asyncio.gather(*[client(c) for c in range(clients)])


@pytest.mark.parametrize("family", ["dense", "slots", "prefix"])
async def test_closed_loop_in_line_matches_the_serial_loop(family):
    """Finishes by ``max_tokens`` with an arrival after each: the
    pipeline takes both in line (it is entered once, and hardly ever
    again), and every stream's tokens are the serial loop's — on the
    dense path (sampled too: a graduated row's seed offset is its lag),
    with a state slot that a finish gave back and the next admission
    took, and with admissions that hit the prefix cache."""
    slots_taken: list[int] = []

    async def run(overlap):
        eng = await _launch_family(family, overlap)
        try:
            sched = eng.scheduler
            if family == "slots":
                acquire = sched.state_slots.acquire

                def spy():
                    slots_taken.append(acquire())
                    return slots_taken[-1]

                sched.state_slots.acquire = spy
            outs = [await _closed_loop(eng, family)]
            if family == "dense":
                outs.append(await _closed_loop(eng, family, temperature=0.8))
            return outs, _inline_counts(eng), sched.prefix_hits
        finally:
            await eng.shutdown()

    over, counts, hits = await run(True)
    n_slots = len(slots_taken)
    serial, serial_counts, _ = await run(False)
    assert over == serial
    assert serial_counts["prefill_inline"] == serial_counts["finishes_inline"] == 0
    # nearly every admission went in behind a step in flight, and nearly
    # every finish left the pipeline running: what is not in line is the
    # way in (the first prefill on an idle engine) and the way out (the
    # last rows' finish, with nothing left in flight)
    # (how nearly is a matter of timing: whenever every client is
    # between two requests at once, the pipeline runs out of rows)
    assert counts["prefill_inline"] * 2 >= counts["prefill"] > 0
    assert counts["finishes_inline"] >= 4
    # no way back in but behind a prefill on an engine with no row left
    assert counts["unchained"] <= counts["prefill"] - counts["prefill_inline"]
    assert not any(counts["drains"].values()), counts["drains"]
    assert counts["preemptions"] == 0
    if family == "slots":
        # three slots, twelve admissions: each after the third took a
        # slot that a finish had just given back, with steps in flight
        assert n_slots == 12 and set(slots_taken[:n_slots]) == {1, 2, 3}
    if family == "prefix":
        assert hits >= 6


async def test_predicted_finish_leaves_the_pipeline_running():
    """A row that ends by ``max_tokens`` beside one that goes on: its
    pages are freed at the harvest (``finishes_inline``), and the
    pipeline is neither flushed nor entered again — every later decode
    dispatch is chained onto a step in flight."""
    from dynamo_tpu.engine.engine import JaxEngine

    eng = await JaxEngine.launch(_engine_config(overlap=True))
    try:
        free0 = eng.allocator.num_free
        long_ = asyncio.ensure_future(
            _generate(eng, _LONG[0], max_tokens=60, request_id="long"))
        short, _ = await _generate(eng, _LONG[1], max_tokens=6,
                                   request_id="short")
        # (no engine-thread call here: a control call is a drain)
        await eng.wait_for_state(lambda e: e._inline["finishes_inline"] == 1)
        running_then = [s.request_id for s in eng.scheduler.running]
        free_then = eng.allocator.num_free
        assert len(short) == 6 and len((await long_)[0]) == 60
        counts = _inline_counts(eng)
    finally:
        await eng.shutdown()
    # the short row's pages came back while the long one decoded on
    assert running_then == ["long"] and free_then > free0 - 11
    assert counts["finishes_inline"] == 1
    assert counts["unchained"] == 1  # one way in, and no way back in
    assert not any(counts["drains"].values())


@pytest.mark.parametrize("how", ["stop_token", "cancelled", "deadline"])
async def test_unforeseen_stop_still_flushes(how):
    """A stop the planner could not foresee — the backend's stop-token
    (EOS) detection, a cancellation, a deadline — ends a row of a step
    in flight: the pipeline flushes (``pipeline_drains.unpredicted_finish``),
    the serial planner reaps, the other row's tokens are untouched."""
    import time

    from dynamo_tpu.engine.engine import JaxEngine

    async def run(overlap, eos=None):
        eng = await JaxEngine.launch(_engine_config(overlap=overlap))
        try:
            other = asyncio.ensure_future(
                _generate(eng, _LONG[0], max_tokens=40, request_id="other"))
            ctx = Context()
            req = PreprocessedRequest(
                request_id="stopped", token_ids=_LONG[1],
                sampling=SamplingOptions(use_greedy=True),
                stop=StopConditions(max_tokens=64, ignore_eos=eos is None),
            )
            stream = eng.as_async_engine().generate(req, ctx)
            if how == "stop_token" and eos is not None:
                from dynamo_tpu.backend import Backend
                from dynamo_tpu.tokenizer import Tokenizer

                backend = Backend(Tokenizer.from_file(MODEL_DIR),
                                  eos_token_ids=[eos])
                _, state = await backend.forward(req, ctx)
                stream = backend.backward(stream, state, ctx)
            got = []
            async for item in stream:
                got.extend(item.token_ids)
                if len(got) < 3 or item.is_final:
                    continue
                if how == "cancelled":
                    ctx.stop_generating()
                    break
                for seq in list(eng.scheduler.running):
                    if how == "deadline" and seq.request_id == "stopped":
                        seq.deadline = time.monotonic() - 1.0
            await eng.wait_for_state(
                lambda e: all(s.request_id != "stopped"
                              for s in e.scheduler.running))
            return got, (await other)[0], _inline_counts(eng)
        finally:
            await eng.shutdown()

    eos = None
    if how == "stop_token":
        # the fourth greedy token of the stream becomes the model's EOS
        eos = (await run(False))[0][3]
    got, other, counts = await run(True, eos)
    _, serial_other, _ = await run(False, eos)
    assert other == serial_other and len(other) == 40
    assert 3 <= len(got) < 64
    assert counts["drains"]["unpredicted_finish"] >= 1
    assert counts["finishes_inline"] == 0


@pytest.mark.parametrize("how", ["opted_out_row_ends", "suspension_lifts"])
async def test_pipeline_gives_the_batch_back_once_it_could_speculate(how):
    """The plain pipeline outlives the plan that chose it. With a drafter
    configured, ``_route`` sends a batch here only while a row of it opted
    out of speculation, or while speculation is suspended; when the
    opted-out row has ended (by ``max_tokens``: in line, no flush) or the
    suspension lifts, the pipeline empties itself
    (``pipeline_drains.speculation``) and the next plan goes down the
    spec path — it does not wait for an unrelated drain."""
    from dynamo_tpu.engine.engine import JaxEngine
    from dynamo_tpu.planner.degradation import ServingDegradation

    prompt = [1, 2, 3, 4, 5, 6, 1, 2, 3, 4, 5, 6, 1, 2, 3]  # drafts hit

    async def generate(eng, rid, prompt_ids, max_tokens, speculative=None,
                       at_token=None):
        req = PreprocessedRequest(
            request_id=rid, token_ids=list(prompt_ids),
            sampling=SamplingOptions(use_greedy=True),
            stop=StopConditions(max_tokens=max_tokens, ignore_eos=True),
            speculative=speculative,
        )
        out = []
        async for item in eng.as_async_engine().generate(req, Context()):
            out.extend(item.token_ids)
            if at_token and len(out) >= at_token[0]:
                at_token[1]()
                at_token = None
        return out

    eng = await JaxEngine.launch(
        _engine_config(overlap=True, spec_decode="ngram", spec_tokens=4))
    try:
        if how == "opted_out_row_ends":
            plain = asyncio.ensure_future(
                generate(eng, "plain", _LONG[1], 6, speculative=False))
            await eng.wait_for_state(
                lambda e: [s.request_id for s in e.scheduler.running]
                == ["plain"])
            spec = asyncio.ensure_future(generate(eng, "spec", prompt, 60))
            assert len(await plain) == 6
            steps_then = eng.spec_pipeline_steps
        else:
            rungs = ServingDegradation(engine=eng)
            rungs.set_level(2)
            steps_then = 0
            spec = asyncio.ensure_future(generate(
                eng, "spec", prompt, 60,
                at_token=(5, lambda: rungs.set_level(0))))
        got = await spec
        counts = _inline_counts(eng)
        assert eng.spec_pipeline_steps > steps_then
    finally:
        await eng.shutdown()
    assert len(got) == 60
    assert counts["drains"]["speculation"] == 1
    if how == "opted_out_row_ends":
        assert counts["finishes_inline"] == 1
    assert not counts["drains"]["unpredicted_finish"]


def _bare_scheduler(pages: int, **kw):
    from dynamo_tpu.engine.allocator import BlockAllocator
    from dynamo_tpu.engine.scheduler import Scheduler

    return Scheduler(BlockAllocator(pages + 1, 8), 8, max_batch_size=4,
                     prefill_chunk_size=32, **kw)


def _bare_seq(rid: str, n_prompt: int, max_tokens: int):
    from tests.test_admission_timeline import _seq

    return _seq(range(1, n_prompt + 1), 8, max_tokens, rid)


def _prefill_all(sched):
    """Serial prefill steps until every placeable prompt has its first
    token (5)."""
    while (plan := sched.plan()).kind == "prefill":
        for work in plan.prefill_batch:
            sched.complete_prefill_chunk(work)
            if work.is_last_chunk:
                sched.append_token(work.seq, 5)


def test_in_line_planners_drain_on_exhaustion_and_never_preempt():
    """No page for the next step: ``plan_pipelined_decode`` gives the
    pages it took back and refuses (``blocks``), and the in-line
    admission leaves a head it cannot place where it is — neither
    preempts, whatever is in flight."""
    sched = _bare_scheduler(pages=16)
    a, b = _bare_seq("a", 15, 40), _bare_seq("b", 15, 40)
    sched.add_request(a)
    sched.add_request(b)
    _prefill_all(sched)
    assert sched.running == [a, b]
    for _ in range(7):
        sched.append_token(a, 7)
        sched.append_token(b, 7)
    while sched.allocator.num_free > 1:  # somebody else's pages
        sched.allocator.allocate_block()
    # both rows hold three pages and sit at 23 tokens: the step after
    # the one in flight writes position 24, on a fourth page each — and
    # one page is free
    lag = {id(a): 1, id(b): 1}
    tables = [list(a.block_table), list(b.block_table)]
    assert [len(t) for t in tables] == [3, 3]
    assert sched.plan_pipelined_decode([a, b], lag) == (None, "blocks")
    assert [a.block_table, b.block_table] == tables
    assert sched.allocator.num_free == 1  # what a took was given back
    c = _bare_seq("c", 9, 8)
    sched.add_request(c)
    assert sched.admission_work()
    assert sched.plan_pipelined_admission(lag) == ([], "")
    assert list(sched.waiting) == [c] and not sched.admission_work()
    assert sched.preemptions == 0 and sched.running == [a, b]


def test_in_line_admission_takes_what_a_predicted_finish_freed():
    """The planner's own sequence of events, without an engine: row a
    is left out of the step planned behind its last one (``lag`` says
    it ends there); its finish at the harvest frees its pages with that
    step in flight; the head that was blocked is admitted by the next
    in-line plan, its chunk planned, and once that is dispatched the
    following decode step holds b from the host and c from the prefill's
    column."""
    from dynamo_tpu.engine.scheduler import SeqState

    sched = _bare_scheduler(pages=8)
    a, b = _bare_seq("a", 15, 2), _bare_seq("b", 15, 40)
    sched.add_request(a)
    sched.add_request(b)
    _prefill_all(sched)
    c = _bare_seq("c", 20, 8)
    sched.add_request(c)
    sched.plan()  # c cannot be placed: the reserve keeps b's growth
    assert not sched.admission_work() and sched.admit_blocked_reserve == 1
    lag = {id(a): 1, id(b): 1}  # step N in flight over [a, b]
    nxt, _ = sched.plan_pipelined_decode([a, b], lag)  # step N+1
    assert nxt["seqs"] == [b] and nxt["src_idx"][0] == 1
    sched.append_token(a, 7)  # harvest of N: a reaches max_tokens
    sched.append_token(b, 7)
    sched.finish(a, sched.should_finish(a))
    lag = {id(b): 1}  # N+1 in flight
    assert sched.admission_work()  # the finish forgot the blocked head
    works, _ = sched.plan_pipelined_admission(lag)
    assert [w.seq for w in works] == [c] and works[0].is_last_chunk
    assert c.state == SeqState.PREFILL and sched.preemptions == 0
    # P dispatched behind N+1, N+1 harvested: b is level with the host
    sched.append_token(b, 9)
    lag = {id(c): 1}
    # c's chunk is in flight
    assert sched.plan_pipelined_admission(lag) == ([], "")
    nxt, _ = sched.plan_pipelined_decode([a, b, c], lag, {id(c): 0})
    assert nxt["seqs"] == [b, c]
    assert nxt["src_idx"][:2].tolist() == [-1, 0]
    assert nxt["arrays"]["tokens"][0, 0] == 9
    assert nxt["arrays"]["context_lens"][:2].tolist() == [18, 21]
    assert nxt["offsets"] == [0, 1]
    # a row that lags and is not in the newest column has to wait
    assert sched.plan_pipelined_decode(
        [b, c], {id(b): 1, id(c): 1}, {id(c): 0}
    ) == (None, "wait")


async def test_multi_chunk_prompt_runs_its_chunks_in_order_in_line():
    """A prompt of three chunks that arrives while another row decodes:
    its chunks are dispatched back to back, in order, each behind a step
    in flight, and the decode step after the last holds both rows — the
    serial loop's order, and its tokens."""
    from dynamo_tpu.engine.engine import JaxEngine

    prompt = [(3 * j) % 90 + 1 for j in range(80)]  # chunks of 32, 32, 16

    async def run(overlap):
        eng = await JaxEngine.launch(_engine_config(overlap=overlap))
        try:
            dispatched = []
            inner = eng._dispatch_device_step

            def spy(arrays, sampling, **kw):
                ctx = arrays["context_lens"]
                dispatched.append((
                    "prefill" if arrays["tokens"].shape[1] > 1 else "decode",
                    int(ctx.max()), int((ctx > 0).sum()),
                ))
                return inner(arrays, sampling, **kw)

            eng._dispatch_device_step = spy
            first = asyncio.ensure_future(
                _generate(eng, _LONG[0], max_tokens=50, request_id="r0"))
            await eng.wait_for_state(
                lambda e: e.overlap.steps_dispatched >= 6
                and (not overlap or e._decode_dispatches[1] >= 4))
            late, _ = await _generate(eng, prompt, max_tokens=6,
                                      request_id="r1")
            return (await first)[0], late, dispatched, _inline_counts(eng)
        finally:
            await eng.shutdown()

    over0, over1, order, counts = await run(True)
    serial0, serial1, serial_order, _ = await run(False)
    assert (over0, over1) == (serial0, serial1)
    for seen in (order, serial_order):
        at = seen.index(("prefill", 32, 1))
        # the chunks end at 32, 64 and 80 tokens of context, nothing
        # between them, and the next step decodes two rows
        assert [s[:2] for s in seen[at:at + 3]] == [
            ("prefill", 32), ("prefill", 64), ("prefill", 80)]
        assert seen[at + 3][0] == "decode" and seen[at + 3][2] == 2
    assert counts["prefill_inline"] == 3 and counts["unchained"] == 1
    assert not any(counts["drains"].values())


@pytest.fixture
def fatal_fence():
    from dynamo_tpu.utils import compile_fence

    compile_fence.set_mode("fatal")
    compile_fence.reset()
    yield compile_fence
    compile_fence.set_mode(None)
    compile_fence.reset()


async def test_in_line_admission_compiles_nothing_after_prewarm(
    tmp_path, fatal_fence
):
    """Under the FATAL compile fence, after prewarm: an in-line admission
    at each decode bucket (4 and 8 rows here), of one prompt (the
    single-row rectangles) and of a burst (the eight-row ones) — the
    packed harvest of a prefill batch and the join of its column into
    each bucket were warmed beside the chain gathers."""
    from dynamo_tpu.engine.engine import JaxEngine

    eng = await JaxEngine.launch(_engine_config(
        overlap=True, prewarm=True, max_batch_size=8, num_blocks=128,
        flight_dump_dir=str(tmp_path),
    ))
    try:
        assert fatal_fence.stats()["events_total"] == 0  # prewarm sanctioned
        assert sorted({eng.scheduler.decode_batch_small,
                       eng.scheduler.decode_batch_pad}) == [4, 8]
        streams = [asyncio.ensure_future(_generate(
            eng, _LONG[i], max_tokens=70, request_id=f"l{i}")) for i in (0, 1)]
        await eng.wait_for_state(lambda e: e._decode_dispatches[1] >= 3)
        # one arrival into the 4-row bucket, then a burst that fills it
        # and goes on into the 8-row one, then one arrival there
        for wave in ([20], [9, 14, 30], [12]):
            before = eng._inline["prefill_dispatches_inline"]
            streams += [asyncio.ensure_future(_generate(
                eng, list(range(3, 3 + n)), max_tokens=40,
                request_id=f"w{n}")) for n in wave]
            await eng.wait_for_state(
                lambda e: e._inline["prefill_dispatches_inline"] > before
                and not e.scheduler.waiting and not e.scheduler.prefilling
                and e._decode_dispatches[1] >= 3)
        outs = await asyncio.gather(*streams)
        assert [len(o[0]) for o in outs] == [70, 70, 40, 40, 40, 40, 40]
        recs = [r for r in eng.recorder.snapshot(512)
                if r["kind"] == "serve_compile"]
        assert recs == [], recs
        assert fatal_fence.stats()["events_total"] == 0
        counts = _inline_counts(eng)
        assert counts["prefill_inline"] >= 3
        assert not any(counts["drains"].values())
    finally:
        await eng.shutdown()
