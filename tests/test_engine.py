"""JAX engine tests on the virtual CPU backend.

The load-bearing test is prefill+decode ≡ one-shot forward: running a
sequence incrementally through the paged cache must produce the same
logits/greedy tokens as processing it in a single pass.
"""

import asyncio
import os

import numpy as np
import pytest

from dynamo_tpu.engine.allocator import BlockAllocator, NoBlocksError
from dynamo_tpu.engine.config import EngineConfig
from dynamo_tpu.engine.scheduler import Scheduler, Sequence
from dynamo_tpu.protocols.common import (
    FinishReason,
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.runtime.engine import Context
from dynamo_tpu.tokens import TokenBlockSequence

MODEL_DIR = os.path.join(os.path.dirname(__file__), "data", "tiny_llama_model")


# ---------------------------------------------------------------------------
# Allocator
# ---------------------------------------------------------------------------


def test_allocator_basic_and_prefix_reuse():
    events = []
    alloc = BlockAllocator(8, 4, on_event=lambda op, h, b: events.append((op, h)))
    hashes = [101, 102, 103]
    blocks, cached = alloc.allocate_prefix(hashes)
    assert cached == 0 and len(blocks) == 3
    for b, h in zip(blocks, hashes):
        alloc.commit_block(b, h)
    assert [e[0] for e in events] == ["stored"] * 3
    # a second sequence with the same prefix reuses all three
    blocks2, cached2 = alloc.allocate_prefix(hashes)
    assert cached2 == 3 and blocks2 == blocks
    assert alloc.match_prefix([101, 102, 999]) == 2
    alloc.free_sequence(blocks)
    alloc.free_sequence(blocks2)
    # still cached after free (inactive pool keeps content)
    blocks3, cached3 = alloc.allocate_prefix(hashes)
    assert cached3 == 3
    alloc.free_sequence(blocks3)


def test_allocator_eviction_lru_and_events():
    events = []
    alloc = BlockAllocator(4, 4, on_event=lambda op, h, b: events.append((op, h[0])))
    b1, _ = alloc.allocate_prefix([1, 2, 3])
    for b, h in zip(b1, [1, 2, 3]):
        alloc.commit_block(b, h)
    alloc.free_sequence(b1)
    # allocating new content evicts the LRU cached blocks and emits removals
    b2, cached = alloc.allocate_prefix([7, 8])
    assert cached == 0
    removed = [h for op, h in events if op == "removed"]
    assert len(removed) == 2
    assert alloc.match_prefix([1]) == (1 if 1 not in removed else 0)


def test_allocator_capacity_rollback():
    alloc = BlockAllocator(4, 4)  # 3 usable
    blocks, _ = alloc.allocate_prefix([1, 2])
    with pytest.raises(NoBlocksError):
        alloc.allocate_prefix([9, 10])  # needs 2, only 1 free
    assert alloc.num_free == 1  # rollback left state intact
    alloc.free_sequence(blocks)
    assert alloc.num_free == 3


# ---------------------------------------------------------------------------
# Scheduler
# ---------------------------------------------------------------------------


def _mk_seq(tokens, block_size=4, max_tokens=None, request_id="r"):
    return Sequence(
        request=PreprocessedRequest(
            request_id=request_id,
            token_ids=list(tokens),
            stop=StopConditions(max_tokens=max_tokens),
        ),
        tokens=TokenBlockSequence(list(tokens), block_size=block_size),
    )


def test_scheduler_admission_and_chunked_prefill():
    alloc = BlockAllocator(64, 4)
    sched = Scheduler(alloc, 4, max_batch_size=4, prefill_chunk_size=8)
    seq = _mk_seq(list(range(20)))
    sched.add_request(seq)
    # chunk 1: 8 tokens
    plan = sched.plan()
    assert plan.kind == "prefill" and len(plan.prefill.tokens) == 8
    assert plan.prefill.start_pos == 0 and not plan.prefill.is_last_chunk
    sched.complete_prefill_chunk(plan.prefill)
    # chunk 2
    plan = sched.plan()
    assert plan.prefill.start_pos == 8 and len(plan.prefill.tokens) == 8
    sched.complete_prefill_chunk(plan.prefill)
    # chunk 3 (final, 4 tokens)
    plan = sched.plan()
    assert plan.prefill.is_last_chunk and len(plan.prefill.tokens) == 4
    sched.complete_prefill_chunk(plan.prefill)
    assert sched.num_running == 1
    plan = sched.plan()
    assert plan.kind == "decode" and plan.decode_seqs == [seq]


def test_scheduler_decode_arrays_shapes():
    alloc = BlockAllocator(64, 4)
    sched = Scheduler(alloc, 4, max_batch_size=8)
    seqs = []
    for i in range(3):
        s = _mk_seq(list(range(5 + i)), request_id=f"r{i}")
        sched.add_request(s)
        seqs.append(s)
    while sched.prefilling or sched.waiting:
        plan = sched.plan()
        assert plan.kind == "prefill"
        sched.complete_prefill_chunk(plan.prefill)
    plan = sched.plan()
    arrays = sched.build_decode_arrays(plan.decode_seqs)
    assert arrays["tokens"].shape[0] == 4  # bucket of 3 -> 4
    assert arrays["block_tables"].shape[1] % sched.TABLE_BUCKET == 0
    # slot mapping points at the last token's slot
    s0 = plan.decode_seqs[0]
    pos = s0.total_len - 1
    assert arrays["slot_mapping"][0] == s0.block_table[pos // 4] * 4 + pos % 4


def test_scheduler_preemption_frees_blocks():
    # 8 usable; neither row states its end, so admission reserves each
    # one decode window (a 4th block) and no more: both go in at once
    alloc = BlockAllocator(9, 4)
    sched = Scheduler(alloc, 4, max_batch_size=4)
    a = _mk_seq(list(range(12)), request_id="a")  # 3 blocks
    b = _mk_seq(list(range(12)), request_id="b")  # 3 blocks
    sched.add_request(a)
    sched.add_request(b)
    while sched.prefilling or sched.waiting:
        plan = sched.plan()
        if plan.kind != "prefill":
            break
        sched.complete_prefill_chunk(plan.prefill)
    assert sched.num_running == 2
    # both grow a 4th block (2 free -> 0); at 17 tokens a needs a 5th
    # -> b (younger) gets preempted when pool is exhausted
    for seq in (a, b):
        sched.append_token(seq, 1)  # fills to 13 tokens
    for _ in range(4):
        plan = sched.plan()
        if plan.kind != "decode":
            break
        for s in plan.decode_seqs:
            sched.append_token(s, 1)
        if sched.waiting:
            break
    # the OLDER sequence keeps running; the younger one is the preemption
    # victim (vLLM recompute policy)
    assert a.state.value == "running"
    assert b.state.value == "waiting"
    assert b.block_table == []  # its blocks were freed


def _blocked_scheduler(cause):
    """One running sequence ``a`` and a request ``b`` that _admit cannot
    place, for ``cause``; returns (sched, a, b)."""
    from dynamo_tpu.engine.allocator import StateSlots

    # 9 usable pages of 4 tokens; a: 8-token prompt, 24 more to come
    # (8 pages at its end), and b as long: their ends do not fit together
    alloc = BlockAllocator(10, 4)
    sched = Scheduler(alloc, 4, max_batch_size=1 if cause == "batch" else 4)
    if cause == "slots":
        sched.state_slots = StateSlots(2)  # one usable slot
    budget = 24 if cause == "reserve" else 4
    a = _mk_seq(list(range(8)), max_tokens=budget, request_id="a")
    sched.add_request(a)
    plan = sched.plan()
    sched.complete_prefill_chunk(plan.prefill)
    assert sched.running == [a] and not sched.admission_work()
    b = _mk_seq(list(range(100, 108)), max_tokens=budget, request_id="b")
    sched.add_request(b)
    # an arrival into an EMPTY queue has not been tried: work
    assert sched.admission_work()
    assert sched.plan().kind == "decode"
    assert list(sched.waiting) == [b] and sched.preemptions == 0
    return sched, a, b


@pytest.mark.parametrize("cause", ["reserve", "batch", "slots"])
def test_admission_work_forgets_blocked_head_on_every_release(cause):
    """admission_work(): a head that _admit could not place is no work
    for the serial planner — whatever blocked it — until something is
    freed; arrivals BEHIND it change nothing; a finish makes it work
    again and the head is admitted first (FIFO)."""
    sched, a, b = _blocked_scheduler(cause)
    assert not sched.admission_work()
    c = _mk_seq(list(range(200, 208)), max_tokens=4, request_id="c")
    sched.add_request(c)  # queues behind the blocked head
    assert not sched.admission_work()
    # decode growth takes pages from pool and reserve alike: still blocked
    for _ in range(3):
        sched.append_token(a, 1)
        assert sched.plan().kind == "decode"
        assert not sched.admission_work()
    sched.running.remove(a)
    sched.finish(a, FinishReason.LENGTH)
    assert sched.admission_work()
    plan = sched.plan()
    assert plan.kind == "prefill" and plan.prefill.seq is b
    assert sched.preemptions == 0


@pytest.mark.parametrize("event", ["cancel_head", "cancel_behind", "deadline",
                                   "preempt", "head_shares_page"])
def test_admission_work_sees_what_can_move_a_blocked_queue(event):
    """The events other than a finish after which plan() has something
    to do for a blocked queue: a waiting request cancelled or past its
    deadline (reaped within a step), a preemption (the victim is the new
    head), and a running row committing a page of the head's own prompt
    (one page fewer to take from the free pool)."""
    import time

    sched, a, b = _blocked_scheduler("reserve")
    c = _mk_seq(list(range(200, 208)), max_tokens=24, request_id="c")
    sched.add_request(c)
    assert not sched.admission_work()
    if event == "cancel_head":
        b.is_cancelled = lambda: True
    elif event == "cancel_behind":
        c.is_cancelled = lambda: True
    elif event == "deadline":
        b.deadline = time.monotonic() - 1.0
    elif event == "preempt":
        sched._preempt(a)
        assert sched.waiting[0] is a
    elif event == "head_shares_page":
        # b's prompt is a's prompt and the 4 tokens a generates next
        sched.waiting.clear()
        b = _mk_seq(list(range(8)) + [1, 1, 1, 1, 9], max_tokens=24,
                    request_id="b2")
        sched.add_request(b)
        sched.plan()
        assert list(sched.waiting) == [b] and not sched.admission_work()
        for _ in range(4):
            sched.append_token(a, 1)
            assert not sched.admission_work()  # page 3 of a is not full yet
        sched.append_token(a, 1)  # ...now it is computed, and committed
    assert sched.admission_work()
    sched.plan()
    if event in ("cancel_head", "deadline"):
        # reaped; c is the head now, tried, and blocked in its turn
        assert b.state.value == "finished" and list(sched.waiting) == [c]
        assert not sched.admission_work()
    elif event == "cancel_behind":
        assert c.state.value == "finished" and list(sched.waiting) == [b]
        assert not sched.admission_work()


# ---------------------------------------------------------------------------
# Model correctness: incremental == one-shot
# ---------------------------------------------------------------------------


def test_paged_forward_incremental_matches_oneshot():
    import jax.numpy as jnp

    from dynamo_tpu.models import ModelConfig
    from dynamo_tpu.models.llama import forward, init_cache, init_params

    cfg = ModelConfig.from_dir(MODEL_DIR)
    cfg.num_hidden_layers = 2
    params = init_params(cfg, seed=0)
    bs = 4
    prompt = list(range(1, 11))  # 10 tokens

    def run_oneshot(tokens):
        k, v = init_cache(cfg, 16, bs, dtype=jnp.float32)
        T = len(tokens)
        n_blocks = -(-T // bs)
        tables = np.zeros((1, 8), np.int32)
        tables[0, :n_blocks] = np.arange(1, n_blocks + 1)
        slots = np.zeros((T,), np.int32)
        for j in range(T):
            slots[j] = tables[0, j // bs] * bs + j % bs
        logits, _, _ = forward(
            cfg, params, k, v,
            np.asarray([tokens], np.int32),
            np.arange(T, dtype=np.int32)[None, :],
            slots, tables,
            np.asarray([T], np.int32),
            np.asarray([T - 1], np.int32),
            bs,
        )
        return np.asarray(logits[0])

    # incremental: prefill prompt, then decode 4 tokens greedily
    k, v = init_cache(cfg, 16, bs, dtype=jnp.float32)
    tables = np.zeros((1, 8), np.int32)
    seq_tokens = list(prompt)
    n_blocks = -(-len(seq_tokens) // bs)
    tables[0, :n_blocks] = np.arange(1, n_blocks + 1)
    slots = np.zeros((len(prompt),), np.int32)
    for j in range(len(prompt)):
        slots[j] = tables[0, j // bs] * bs + j % bs
    logits, k, v = forward(
        cfg, params, k, v,
        np.asarray([prompt], np.int32),
        np.arange(len(prompt), dtype=np.int32)[None, :],
        slots, tables,
        np.asarray([len(prompt)], np.int32),
        np.asarray([len(prompt) - 1], np.int32),
        bs,
    )
    for _ in range(4):
        nxt = int(np.argmax(np.asarray(logits)[0]))
        # one-shot over the full sequence must agree on the next prediction
        oneshot_logits = run_oneshot(seq_tokens)
        assert int(np.argmax(oneshot_logits)) == nxt
        np.testing.assert_allclose(
            np.asarray(logits)[0], oneshot_logits, rtol=2e-2, atol=2e-2
        )
        seq_tokens.append(nxt)
        pos = len(seq_tokens) - 1
        n_blocks = -(-len(seq_tokens) // bs)
        tables[0, :n_blocks] = np.arange(1, n_blocks + 1)
        slot = np.asarray([tables[0, pos // bs] * bs + pos % bs], np.int32)
        logits, k, v = forward(
            cfg, params, k, v,
            np.asarray([[nxt]], np.int32),
            np.asarray([[pos]], np.int32),
            slot, tables,
            np.asarray([len(seq_tokens)], np.int32),
            np.asarray([0], np.int32),
            bs,
        )


# ---------------------------------------------------------------------------
# Engine end-to-end (async, CPU)
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
    )
    defaults.update(kw)
    return EngineConfig(**defaults)


async def _generate(engine, prompt_ids, max_tokens=8, greedy=True, request_id="r"):
    from dynamo_tpu.protocols.common import SamplingOptions

    adapter = engine.as_async_engine()
    req = PreprocessedRequest(
        request_id=request_id,
        token_ids=list(prompt_ids),
        sampling=SamplingOptions(use_greedy=greedy),
        stop=StopConditions(max_tokens=max_tokens),
    )
    out = []
    final = None
    async for item in adapter.generate(req, Context()):
        out.extend(item.token_ids)
        if item.is_final:
            final = item
    return out, final


async def test_engine_greedy_determinism_and_prefix_cache():
    from dynamo_tpu.engine.engine import JaxEngine

    engine = await JaxEngine.launch(_engine_config())
    try:
        prompt = list(range(1, 40))
        toks1, fin1 = await _generate(engine, prompt, request_id="r1")
        assert len(toks1) == 8
        assert fin1.finish_reason == FinishReason.LENGTH
        assert fin1.completion_tokens == 8
        # same prompt again: identical greedy continuation + prefix-cache hit
        toks2, _ = await _generate(engine, prompt, request_id="r2")
        assert toks2 == toks1
        stats = engine.stats()
        assert stats.gpu_prefix_cache_hit_rate > 0.0
        assert stats.kv_total_blocks == 127
    finally:
        await engine.shutdown()


async def test_engine_concurrent_batching():
    from dynamo_tpu.engine.engine import JaxEngine

    engine = await JaxEngine.launch(_engine_config())
    try:
        prompts = [list(range(1, 10 + i)) for i in range(5)]
        results = await asyncio.gather(
            *[
                _generate(engine, p, max_tokens=6, request_id=f"c{i}")
                for i, p in enumerate(prompts)
            ]
        )
        for toks, fin in results:
            assert len(toks) == 6
            assert fin.finish_reason == FinishReason.LENGTH
        # determinism under batching: re-run one prompt alone and compare
        solo, _ = await _generate(engine, prompts[0], max_tokens=6, request_id="solo")
        assert solo == results[0][0]
    finally:
        await engine.shutdown()


async def test_engine_cancellation_frees_blocks():
    from dynamo_tpu.engine.engine import JaxEngine

    engine = await JaxEngine.launch(_engine_config())
    try:
        adapter = engine.as_async_engine()
        ctx = Context()
        req = PreprocessedRequest(
            request_id="cancel-me",
            token_ids=list(range(1, 30)),
            sampling=SamplingOptions(use_greedy=True),
            stop=StopConditions(max_tokens=200),
        )
        got = 0
        async for item in adapter.generate(req, ctx):
            if item.token_ids:
                got += 1
            if got == 3:
                ctx.stop_generating()
        await asyncio.sleep(0.3)
        assert engine.allocator.num_free == engine.allocator.num_blocks - 1
    finally:
        await engine.shutdown()


async def test_multi_step_decode_matches_single_step():
    """decode_steps=4 must produce token-identical greedy output to
    decode_steps=1 (max_tokens not divisible by the window, so the tail
    of the last fused window is discarded), and frees all blocks."""
    from dynamo_tpu.engine.engine import JaxEngine

    async def run(steps: int):
        engine = await JaxEngine.launch(_engine_config(decode_steps=steps))
        try:
            prompt = list(range(1, 30))
            toks, fin = await _generate(engine, prompt, max_tokens=6,
                                        request_id=f"ms{steps}")
            assert fin.finish_reason == FinishReason.LENGTH
            assert fin.completion_tokens == 6
            # concurrent batch under multi-step
            results = await asyncio.gather(*[
                _generate(engine, list(range(1, 12 + i)), max_tokens=7,
                          request_id=f"msb{steps}-{i}")
                for i in range(3)
            ])
            # all sequences finished: only cached (committed) blocks may
            # remain referenced; nothing should leak as active-unfreed
            assert engine.scheduler is not None
            assert not engine.scheduler.running
            return toks, [r[0] for r in results]
        finally:
            await engine.shutdown()

    t1, b1 = await run(1)
    t4, b4 = await run(4)
    assert t1 == t4
    assert b1 == b4


def test_prefill_batch_admits_free_rows_under_pinned_buckets():
    """Rows whose admission leaves the padded BxT rectangle unchanged
    are free and must be admitted even past the area budget (pinned
    batch buckets would otherwise degrade batched prefill to one real
    row per full-size dispatch)."""
    alloc = BlockAllocator(256, 4)
    sched = Scheduler(alloc, 4, max_batch_size=8, prefill_chunk_size=16,
                      max_prefill_tokens=16)
    # bench-style pinning: a set with one row count
    sched.prefill_rects = [(8, t) for t in Scheduler.CHUNK_BUCKETS]
    for i in range(4):
        sched.add_request(_mk_seq(list(range(1, 17)), request_id=f"p{i}"))
    plan = sched.plan()
    # area = 8 (pinned B) * 16 (T bucket) = 128 > budget 16, but
    # every extra row is free: all 4 must batch into one step
    assert plan.kind == "prefill"
    assert len(plan.prefill_batch) == 4
    arrays = sched.build_prefill_batch_arrays(plan.prefill_batch)
    assert arrays["tokens"].shape == (8, 16)


async def test_multi_step_with_pipeline_parallelism():
    """Fused multi-step decode composes with pp stage rotation: output
    must match the plain single-device single-step engine."""
    from dynamo_tpu.engine.engine import JaxEngine
    from dynamo_tpu.models.config import ModelConfig

    mc = ModelConfig(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        num_hidden_layers=4, num_attention_heads=8, num_key_value_heads=4,
        max_position_embeddings=128,
    )

    async def run(pp: int, steps: int) -> list[int]:
        engine = await JaxEngine.launch(
            EngineConfig(
                model_path="", model_name="ppms", random_weights=True,
                num_blocks=32, block_size=4, max_batch_size=4,
                pipeline_parallel_size=pp, tensor_parallel_size=2 if pp > 1 else 1,
                decode_steps=steps, kv_cache_dtype="float32",
            ),
            model_config=mc,
        )
        try:
            toks, fin = await _generate(
                engine, list(range(1, 14)), max_tokens=6, request_id="x"
            )
            assert fin.completion_tokens == 6
            return toks
        finally:
            await engine.shutdown()

    base = await run(1, 1)
    assert await run(2, 4) == base


async def test_multi_step_surplus_does_not_corrupt_full_width_table():
    """A sequence whose block table exactly fills the bucketed width at
    the last fused window used to have surplus-step KV writes clipped
    onto its LAST REAL block (take_along_axis clips out-of-range table
    indices) — corrupting a block that prefix caching then serves to
    later requests. Surplus writes must go to the garbage block instead.

    Geometry: block_size=4, TABLE_BUCKET=8 -> width 8 = 32 slots.
    prompt 26 + max_tokens 6 = 32 tokens exactly; decode_steps=4 leaves
    2 surplus steps in the final window that would write at positions
    32,33 -> table column 8,9 -> clipped to column 7 (a real block)."""
    from dynamo_tpu.engine.engine import JaxEngine

    engine = await JaxEngine.launch(
        _engine_config(block_size=4, decode_steps=4, num_blocks=64)
    )
    try:
        prompt = list(range(1, 27))  # 26 tokens
        toks, fin = await _generate(engine, prompt, max_tokens=6,
                                    request_id="full-width")
        assert fin.completion_tokens == 6
        # continue from the full 32-token history: the last block is a
        # prefix-cache hit and must hold uncorrupted KV
        full = prompt + toks
        cont_cached, _ = await _generate(engine, full, max_tokens=4,
                                         request_id="reuse")
    finally:
        await engine.shutdown()

    # ground truth: a fresh single-step engine over the same history
    engine2 = await JaxEngine.launch(
        _engine_config(block_size=4, decode_steps=1, num_blocks=64)
    )
    try:
        cont_fresh, _ = await _generate(engine2, full, max_tokens=4,
                                        request_id="fresh")
    finally:
        await engine2.shutdown()
    assert cont_cached == cont_fresh


async def test_pipelined_decode_with_mid_stream_arrival():
    """The pipelined decode path must flush cleanly when a new request
    arrives mid-generation (the next window is already in flight when
    the scheduler sees the newcomer), and outputs must stay identical
    to solo runs."""
    from dynamo_tpu.engine.engine import JaxEngine

    engine = await JaxEngine.launch(
        _engine_config(decode_steps=4, max_batch_size=4, num_blocks=96)
    )
    try:
        p1 = list(range(1, 30))
        p2 = list(range(5, 40))

        async def delayed_second():
            await asyncio.sleep(0.25)  # lands mid-way through p1's decode
            return await _generate(engine, p2, max_tokens=12, request_id="mid2")

        (t1, f1), (t2, f2) = await asyncio.gather(
            _generate(engine, p1, max_tokens=24, request_id="mid1"),
            delayed_second(),
        )
        assert f1.completion_tokens == 24 and len(t1) == 24
        assert f2.completion_tokens == 12 and len(t2) == 12
        # identical to unpipelined solo reruns (prefix cache warm now,
        # but greedy continuations must not change)
        s1, _ = await _generate(engine, p1, max_tokens=24, request_id="solo1")
        s2, _ = await _generate(engine, p2, max_tokens=12, request_id="solo2")
        assert s1 == t1 and s2 == t2
        assert not engine.scheduler.running
    finally:
        await engine.shutdown()


async def test_multi_step_under_block_pressure():
    """Fused windows + tight block pool: preemption/recompute must keep
    outputs correct and leak no blocks."""
    from dynamo_tpu.engine.engine import JaxEngine

    engine = await JaxEngine.launch(
        _engine_config(num_blocks=24, decode_steps=4, max_batch_size=4)
    )
    try:
        prompts = [list(range(1, 14 + 3 * i)) for i in range(4)]
        results = await asyncio.gather(*[
            _generate(engine, p, max_tokens=10, request_id=f"bp{i}")
            for i, p in enumerate(prompts)
        ])
        for toks, fin in results:
            assert fin.finish_reason == FinishReason.LENGTH
            assert len(toks) == 10
        # solo rerun of each prompt matches (recompute preemption must
        # not corrupt KV)
        for i, p in enumerate(prompts):
            solo, _ = await _generate(engine, p, max_tokens=10,
                                      request_id=f"solo{i}")
            assert solo == results[i][0], f"prompt {i} diverged"
        assert not engine.scheduler.running
    finally:
        await engine.shutdown()


