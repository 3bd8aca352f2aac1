"""Mixed prefill+decode batching: a straggler's prefill rides the fused
decode window's dispatch instead of stalling decode for a dedicated
full-weight pass (reference: vLLM's mixed continuous-batching scheduler,
container/deps/vllm/vllm_v0.8.4-dynamo-kv-disagg-patch.patch :535,
docs/architecture.md:55-68).

Correctness bar: greedy outputs must be IDENTICAL whether a request's
prefill ran mixed or dedicated (paged attention only ever reads a
sequence's own pages)."""

import asyncio
import os

import numpy as np
import pytest

from dynamo_tpu.engine.allocator import BlockAllocator
from dynamo_tpu.engine.config import EngineConfig
from dynamo_tpu.engine.scheduler import Scheduler, SeqState, Sequence
from dynamo_tpu.protocols.common import (
    FinishReason,
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.runtime.engine import Context
from dynamo_tpu.tokens import TokenBlockSequence

MODEL_DIR = os.path.join(os.path.dirname(__file__), "data", "tiny_llama_model")


def _mk_seq(tokens, block_size=4, max_tokens=8, request_id="r"):
    return Sequence(
        request=PreprocessedRequest(
            request_id=request_id,
            token_ids=list(tokens),
            stop=StopConditions(max_tokens=max_tokens),
        ),
        tokens=TokenBlockSequence(list(tokens), block_size=block_size),
    )


# ---------------------------------------------------------------------------
# Scheduler planning
# ---------------------------------------------------------------------------


def test_scheduler_emits_mixed_plan():
    alloc = BlockAllocator(256, 4)
    sched = Scheduler(alloc, 4, max_batch_size=8, prefill_chunk_size=64)
    sched.mixed_prefill_rows = 4
    sched.mixed_prefill_len = 32
    # get one sequence decoding
    a = _mk_seq(list(range(10)), request_id="a")
    sched.add_request(a)
    plan = sched.plan()
    assert plan.kind == "prefill"
    sched.complete_prefill_chunk(plan.prefill)
    assert sched.num_running == 1
    # a straggler arrives while decode has work -> mixed plan with both
    b = _mk_seq(list(range(5, 25)), request_id="b")
    sched.add_request(b)
    plan = sched.plan()
    assert plan.kind == "mixed"
    assert [w.seq.request_id for w in plan.prefill_batch] == ["b"]
    assert [s.request_id for s in plan.decode_seqs] == ["a"]
    # chunk capped to the rectangle length
    assert len(plan.prefill.tokens) <= 32


def test_scheduler_mixed_backlog_falls_back_to_dedicated_prefill():
    alloc = BlockAllocator(1024, 4)
    sched = Scheduler(alloc, 4, max_batch_size=16, prefill_chunk_size=512)
    sched.mixed_prefill_rows = 2
    sched.mixed_prefill_len = 16  # tiny rectangle: capacity 32, thresh 64
    a = _mk_seq(list(range(8)), request_id="a")
    sched.add_request(a)
    sched.complete_prefill_chunk(sched.plan().prefill)
    # a long prompt exceeding 2x rectangle capacity -> dedicated prefill
    b = _mk_seq(list(range(200)), request_id="b")
    sched.add_request(b)
    plan = sched.plan()
    assert plan.kind == "prefill"
    assert len(plan.prefill.tokens) > 16  # full chunking, not the rect


def test_scheduler_wide_rect_at_low_occupancy():
    """A long prompt with few decoders swaps the mixed rectangle for
    the wide variant (same token budget, fewer rows) so it stops
    trickling at mixed_prefill_len per window; high decode occupancy
    keeps the narrow rectangle's extra rows."""
    alloc = BlockAllocator(2048, 4)
    sched = Scheduler(alloc, 4, max_batch_size=16, prefill_chunk_size=512)
    sched.mixed_prefill_rows = 4
    sched.mixed_prefill_len = 32
    sched.mixed_prefill_wide_rows = 1
    sched.mixed_prefill_wide_len = 128
    sched.mixed_wide_max_running = 4
    a = _mk_seq(list(range(8)), request_id="a")
    sched.add_request(a)
    sched.complete_prefill_chunk(sched.plan().prefill)
    # long prompt (backlog > narrow len), 1 decoder -> wide rect
    b = _mk_seq(list(range(200)), request_id="b")
    sched.add_request(b)
    plan = sched.plan()
    assert plan.kind == "mixed"
    assert plan.rect == (1, 128)
    assert len(plan.prefill.tokens) == 128  # wide chunk, not 32
    # drain b's prefill; then raise decode occupancy past the ceiling
    while True:
        p = sched.plan()
        if p.kind != "mixed" or not p.prefill_batch:
            break
        for w in p.prefill_batch:
            sched.complete_prefill_chunk(w)
    for i in range(5):
        s = _mk_seq(list(range(6)), request_id=f"d{i}")
        sched.add_request(s)
        p = sched.plan()
        for w in p.prefill_batch:
            sched.complete_prefill_chunk(w)
    assert sched.num_running >= 5
    c = _mk_seq(list(range(200)), request_id="c")
    sched.add_request(c)
    plan = sched.plan()
    assert plan.kind == "mixed"
    assert plan.rect == (4, 32)  # narrow: occupancy above the ceiling


def test_scheduler_mixed_disabled_keeps_either_or():
    alloc = BlockAllocator(256, 4)
    sched = Scheduler(alloc, 4, max_batch_size=8, prefill_chunk_size=64)
    assert sched.mixed_prefill_rows == 0  # default off at scheduler level
    a = _mk_seq(list(range(10)), request_id="a")
    sched.add_request(a)
    sched.complete_prefill_chunk(sched.plan().prefill)
    b = _mk_seq(list(range(5, 25)), request_id="b")
    sched.add_request(b)
    assert sched.plan().kind == "prefill"


def test_cohort_takes_dedicated_prefill_not_trickle():
    """A cohort (more prompts than rectangle rows, whole backlog within
    one prefill budget) takes a dedicated batched step even when decode
    occupancy is high — trickling it 'rows' per window staggers the
    population into partial-width waves (measured: B=64 closed batch
    924 vs 2181 tok/s)."""
    alloc = BlockAllocator(4096, 4)
    sched = Scheduler(
        alloc, 4, max_batch_size=64, prefill_chunk_size=64,
        max_prefill_tokens=512,
    )
    sched.mixed_prefill_rows = 4
    sched.mixed_prefill_len = 32
    for i in range(16):
        s = _mk_seq(list(range(8)), request_id=f"r{i}")
        sched.add_request(s)
        p = sched.plan()
        for w in p.prefill_batch:
            sched.complete_prefill_chunk(w)
    assert sched.num_running == 16
    # cohort: 12 prompts x 20 tokens = 240 <= 512 budget, count > rows.
    # CRITICAL test geometry: 240 is also <= the mixed-gate bound
    # 2*rows*rlen (256) and running(16) >= prefilling(12), so the
    # PRE-cohort gate trickled exactly this through the 4-row
    # rectangle — the assertion below fails without the cohort gate.
    for i in range(12):
        sched.add_request(
            _mk_seq([200 + i] + list(range(300, 319)), request_id=f"c{i}")
        )
    plan = sched.plan()
    assert plan.kind == "prefill", "cohort must take the dedicated step"
    assert len(plan.prefill_batch) > sched.mixed_prefill_rows
    # a straggler (single prompt) still rides the mixed rectangle
    while sched.prefilling:
        p = sched.plan()
        if not p.prefill_batch:
            break
        for w in p.prefill_batch:
            sched.complete_prefill_chunk(w)
    sched.add_request(_mk_seq(list(range(400, 420)), request_id="s"))
    assert sched.plan().kind == "mixed"


@pytest.mark.parametrize("a_budget, b_prompt, b_waits", [
    # A ends at 32 tokens (8 blocks) and B, 36 + 4 tokens (10 blocks),
    # ends while A is inside the three windows of slack of its own end:
    # 18 > 15, B must WAIT (no reserve would admit it and later preempt
    # a running sequence)
    (12, 36, True),
    # A ends at 48 tokens (12 blocks), B at 12 + 4 (4 blocks): the sum of
    # their growth wants 16 > 15, but when B ends A has at most 20 + 3 +
    # 12 tokens (9 blocks) — 13 at the peak, and A's 12 alone afterwards:
    # B goes in beside A at once
    (28, 12, False),
], ids=["ends_overlap_waits", "short_answer_fits"])
def test_admission_reserves_population_growth(a_budget, b_prompt, b_waits):
    """Admission must leave free what the population needs at the WORST
    INSTANT of its future — every row run to its end, its pages given
    back when it gets there (Scheduler._growth_reserve): without the
    reserve, a freed block is instantly eaten by the next waiting prompt
    and decode growth preempts a running sequence — a recompute cascade
    under closed-loop pressure (observed as the ISL-3000 c=64 collapse).
    Either way nobody is ever preempted."""
    alloc = BlockAllocator(16, 4)  # 15 usable
    sched = Scheduler(alloc, 4, max_batch_size=8, prefill_chunk_size=64)
    sched.decode_lookahead = 4
    a = _mk_seq(list(range(20)), max_tokens=a_budget, request_id="a")
    sched.add_request(a)
    plan = sched.plan()
    assert plan.kind == "prefill"
    for w in plan.prefill_batch:
        sched.complete_prefill_chunk(w)
    assert sched.num_running == 1
    # B's prompt is DISTINCT from A's (a shared prefix would be charged
    # only for its fresh tail)
    b = _mk_seq(list(range(100, 100 + b_prompt)), max_tokens=4,
                request_id="b")
    sched.add_request(b)
    plan = sched.plan()
    assert plan.kind == ("decode" if b_waits else "prefill")
    assert len(sched.waiting) == b_waits
    assert sched.admit_blocked_reserve == b_waits
    assert sched.admit_reserve_peak_pages <= sched.admit_reserve_sum_pages
    for w in plan.prefill_batch:
        sched.complete_prefill_chunk(w)
    # A decodes to completion without ever being preempted
    while a.state == SeqState.RUNNING:
        for s in sched.plan().decode_seqs:
            for _ in range(sched._seq_lookahead(s)):
                sched.append_token(s, 1)
            r = sched.should_finish(s)
            if r is not None:
                sched.finish(s, r)
    assert sched.preemptions == 0
    if b_waits:
        # A's blocks freed -> B admits now
        plan = sched.plan()
        assert plan.kind == "prefill"
        assert plan.prefill.seq.request_id == "b"
    else:
        assert b.state == SeqState.FINISHED


# ---------------------------------------------------------------------------
# Engine end-to-end
# ---------------------------------------------------------------------------


def _engine_config(**kw) -> EngineConfig:
    defaults = dict(
        model_path=MODEL_DIR,
        model_name="tiny",
        random_weights=True,
        num_blocks=128,
        block_size=8,
        max_batch_size=8,
        prefill_chunk_size=32,
        max_model_len=256,
        decode_steps=4,
        mixed_prefill_rows=2,
        mixed_prefill_len=16,
    )
    defaults.update(kw)
    return EngineConfig(**defaults)


async def _generate(engine, prompt_ids, max_tokens=8, request_id="r"):
    adapter = engine.as_async_engine()
    req = PreprocessedRequest(
        request_id=request_id,
        token_ids=list(prompt_ids),
        sampling=SamplingOptions(use_greedy=True),
        stop=StopConditions(max_tokens=max_tokens),
    )
    out = []
    final = None
    async for item in adapter.generate(req, Context()):
        out.extend(item.token_ids)
        if item.is_final:
            final = item
    return out, final


async def test_mixed_engine_straggler_rides_mixed_window():
    """A straggler arriving while another request decodes must ride a
    mixed window (not stall decode with a dedicated pass), and greedy
    outputs must match a mixed-off engine run of the same prompts."""
    from dynamo_tpu.engine.engine import JaxEngine

    prompts = [list(range(1, 14 + 3 * i)) for i in range(3)]

    async def run(mixed: bool):
        engine = await JaxEngine.launch(
            _engine_config(mixed_prefill_rows=2 if mixed else 0)
        )
        n_mixed = 0
        if mixed:
            orig = engine._dispatch_mixed

            def counting(*a, **kw):
                nonlocal n_mixed
                n_mixed += 1
                return orig(*a, **kw)

            engine._dispatch_mixed = counting
        try:
            adapter = engine.as_async_engine()

            async def consume(req, out: list):
                async for item in adapter.generate(req, Context()):
                    out.extend(item.token_ids)

            # A decodes a LONG generation...
            a_out: list = []
            a_req = PreprocessedRequest(
                request_id="a", token_ids=prompts[0],
                sampling=SamplingOptions(use_greedy=True),
                stop=StopConditions(max_tokens=120),
            )
            a_task = asyncio.create_task(consume(a_req, a_out))
            while len(a_out) < 8:  # guaranteed mid-decode
                await asyncio.sleep(0.01)
            # ...when stragglers B and C arrive: their prefills must
            # ride the decode window's dispatch
            b = await _generate(engine, prompts[1], max_tokens=24,
                                request_id="b")
            c = await _generate(engine, prompts[2], max_tokens=24,
                                request_id="c")
            await a_task
            assert len(a_out) == 120
            return a_out, b[0], c[0], n_mixed
        finally:
            await engine.shutdown()

    a1, b1, c1, n_mixed = await run(True)
    a2, b2, c2, _ = await run(False)
    assert n_mixed > 0, "stragglers never took the mixed path"
    assert (a1, b1, c1) == (a2, b2, c2)


async def test_pipelined_mixed_chain_matches_dedicated():
    """Continuous staggered arrivals with long generations force CHAINS
    of pipelined mixed windows (prefill graduation chained on device);
    greedy outputs must still match the mixed-off engine exactly."""
    from dynamo_tpu.engine.engine import JaxEngine

    prompts = [list(range(1, 10 + 2 * i)) for i in range(6)]

    async def run(mixed: bool):
        engine = await JaxEngine.launch(
            _engine_config(
                mixed_prefill_rows=2 if mixed else 0, max_batch_size=8
            )
        )
        try:
            async def staggered(i: int):
                await asyncio.sleep(0.1 * i)
                return await _generate(
                    engine, prompts[i], max_tokens=24, request_id=f"pl{i}"
                )

            results = await asyncio.gather(*[staggered(i) for i in range(6)])
            for toks, fin in results:
                assert len(toks) == 24, fin
            return [r[0] for r in results]
        finally:
            await engine.shutdown()

    mixed_out = await run(True)
    dedicated_out = await run(False)
    assert mixed_out == dedicated_out


async def test_wide_rect_engine_matches_narrow_only():
    """A long prompt arriving while one request decodes takes the WIDE
    mixed rectangle (fewer windows to first token); greedy outputs must
    match an engine with the wide variant disabled. (Static shapes
    bucket the narrow len 16 up to 128, so the wide variant here must
    be 256 to differ.)"""
    from dynamo_tpu.engine.engine import JaxEngine

    long_prompt = list(np.random.RandomState(7).randint(1, 250, size=180))

    async def run(wide_len: int):
        # prefill_chunk_size must cover the wide len: the engine clamps
        # the wide rectangle to one chunk (longer would pad dead tokens)
        engine = await JaxEngine.launch(
            _engine_config(
                mixed_prefill_rows=2, mixed_prefill_len=16,
                mixed_prefill_wide_len=wide_len, num_blocks=256,
                prefill_chunk_size=256,
            )
        )
        wide_rects = 0
        orig = engine._dispatch_mixed

        def counting(*a, **kw):
            nonlocal wide_rects
            r = kw.get("rect")
            if r is not None and r[1] > engine.config.mixed_prefill_len:
                wide_rects += 1
            return orig(*a, **kw)

        engine._dispatch_mixed = counting
        try:
            adapter = engine.as_async_engine()
            a_out: list = []

            async def consume(req, out: list):
                async for item in adapter.generate(req, Context()):
                    out.extend(item.token_ids)

            a_req = PreprocessedRequest(
                request_id="a", token_ids=list(range(1, 12)),
                sampling=SamplingOptions(use_greedy=True),
                stop=StopConditions(max_tokens=80),
            )
            a_task = asyncio.create_task(consume(a_req, a_out))
            while len(a_out) < 4:
                await asyncio.sleep(0.01)
            b = await _generate(engine, long_prompt, max_tokens=16,
                                request_id="b")
            await a_task
            return a_out, b[0], wide_rects
        finally:
            await engine.shutdown()

    a1, b1, n_wide = await run(256)
    a2, b2, n_off = await run(0)
    assert n_wide > 0, "long prompt never took the wide rectangle"
    assert n_off == 0
    assert (a1, b1) == (a2, b2)


async def test_mixed_engine_long_prompt_and_pressure():
    """Long prompts (multi-chunk through the rectangle) and more
    requests than decode slots still finish correctly under mixed."""
    from dynamo_tpu.engine.engine import JaxEngine

    engine = await JaxEngine.launch(
        _engine_config(max_batch_size=4, num_blocks=64)
    )
    try:
        first, _ = await _generate(
            engine, list(range(1, 10)), max_tokens=30, request_id="warm"
        )
        assert len(first) == 30
        # now pile on while nothing decodes vs while decoding
        tasks = [
            _generate(engine, list(range(1, 60)), max_tokens=6,
                      request_id=f"p{i}")
            for i in range(6)
        ]
        results = await asyncio.gather(*tasks)
        for toks, fin in results:
            assert len(toks) == 6
        # determinism: same long prompt solo matches its batched run
        solo, _ = await _generate(
            engine, list(range(1, 60)), max_tokens=6, request_id="solo"
        )
        assert solo == results[0][0]
    finally:
        await engine.shutdown()


def test_admission_gate_ignores_actively_shared_prefix():
    """The growth-reserve admission gate charges only what admission
    takes from the FREE pool: a prompt whose prefix blocks are pinned
    by running sequences admits even when free blocks < total prompt
    blocks (shared-prefix workloads must not stall on phantom need)."""
    alloc = BlockAllocator(16, 4)
    sched = Scheduler(alloc, 4, max_batch_size=8, prefill_chunk_size=64)
    sched.decode_lookahead = 1
    # A: 40-token prompt = 10 blocks, pinned and running
    a = _mk_seq(list(range(40)), max_tokens=2, request_id="a")
    sched.add_request(a)
    plan = sched.plan()
    while plan.kind == "prefill":
        for w in plan.prefill_batch:
            sched.complete_prefill_chunk(w)
        plan = sched.plan()
    assert sched.num_running == 1
    assert alloc.num_free < 10  # free pool cannot hold the prompt fresh
    # B: SAME 40-token prompt + 4 extra tokens = 11 blocks total, but
    # 10 are actively shared with A -> only ~1-2 fresh needed
    b = _mk_seq(list(range(40)) + [99, 98, 97, 96], max_tokens=2,
                request_id="b")
    sched.add_request(b)
    plan = sched.plan()
    assert plan.kind in ("prefill", "mixed")
    assert any(
        w.seq.request_id == "b"
        for w in plan.prefill_batch
    ), "shared-prefix prompt was not admitted"


def test_mid_decode_bucket_selection():
    """Wide-pad engines get a mid decode bucket: a half-occupancy
    population decodes in [pad/2]-padded windows instead of the full
    pad (measured ~11% at c=32 on a max_batch=64 engine)."""
    alloc = BlockAllocator(4096, 4)
    sched = Scheduler(alloc, 4, max_batch_size=64)
    sched.decode_batch_small = 4
    sched.decode_batch_mid = 32
    sched.decode_batch_pad = 64
    assert sched._decode_batch(3) == 4
    assert sched._decode_batch(4) == 4
    assert sched._decode_batch(5) == 32
    assert sched._decode_batch(32) == 32
    assert sched._decode_batch(33) == 64
    assert sched._decode_batch(64) == 64


async def test_mid_decode_bucket_override_semantics():
    """Explicit decode_batch_mid rounds DOWN to a real bucket strictly
    between the small bucket and the pad; 0 disables the auto mid; out
    of range values are ignored (never a no-op mid == pad or dead
    mid <= small)."""
    from dynamo_tpu.engine.engine import JaxEngine

    async def launch(**kw):
        return await JaxEngine.launch(_engine_config(
            max_batch_size=64, num_blocks=512, **kw
        ))

    if True:
        e = await launch(decode_batch_mid=48)
        try:
            assert e.scheduler.decode_batch_mid == 32  # rounds DOWN
        finally:
            await e.shutdown()
        e = await launch(decode_batch_mid=0)
        try:
            assert e.scheduler.decode_batch_mid is None  # 0 disables auto
        finally:
            await e.shutdown()
        e = await launch(decode_batch_mid=2)
        try:
            assert e.scheduler.decode_batch_mid is None  # below small
        finally:
            await e.shutdown()
        e = await launch()  # auto: pad 64 -> mid 32
        try:
            assert e.scheduler.decode_batch_mid == 32
        finally:
            await e.shutdown()

