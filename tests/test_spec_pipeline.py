"""Overlapped speculative decoding (docs/speculative_decoding.md,
pipelined section): spec (PR 3) composed with the decode pipeline's
double-buffering (PR 7).

The load-bearing properties:
- spec+overlap output is BIT-IDENTICAL to serial spec (--no-overlap) —
  greedy AND seeded-sampled (the sampled realization depends on the
  proposal stream, so this pins that pre-draft/repair reproduces the
  serial drafts byte-for-byte) — and greedy rows additionally match a
  plain non-speculative engine;
- the incremental per-sequence n-gram index proposes EXACTLY what the
  from-scratch windowed scan proposes, across appends, unwinds and
  speculative suffixes;
- late-detected stops discard in-flight spec tokens (blocks freed,
  prefix cache clean), and zero-proposal steps fall back without
  deadlock.

CPU-runnable tier-1, like tests/test_spec.py and tests/test_overlap.py.
"""

import asyncio
import os
import random

import numpy as np
import pytest

from dynamo_tpu.engine.allocator import BlockAllocator
from dynamo_tpu.engine.config import EngineConfig
from dynamo_tpu.engine.scheduler import Scheduler, Sequence
from dynamo_tpu.protocols.common import (
    FinishReason,
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.runtime.engine import Context
from dynamo_tpu.spec import NgramDrafter
from dynamo_tpu.tokens import TokenBlockSequence

MODEL_DIR = os.path.join(os.path.dirname(__file__), "data", "tiny_llama_model")


# ---------------------------------------------------------------------------
# Incremental n-gram index == from-scratch build (satellite)
# ---------------------------------------------------------------------------


def test_ngram_index_matches_scratch_fuzz():
    """The exactness contract: across random append/unwind/propose
    sequences (small vocab to force gram collisions, windows small
    enough to roll), the incremental index proposes byte-identically to
    the from-scratch windowed scan — including speculative suffixes
    (the pipeline's pre-draft/repair contexts)."""
    rng = random.Random(12)
    for trial in range(40):
        vocab = rng.choice([3, 4, 8])
        window = rng.choice([6, 16, 64])
        d = NgramDrafter(
            max_ngram=rng.choice([2, 3, 4]), min_ngram=1, max_window=window
        )
        toks = [rng.randrange(vocab) for _ in range(rng.randrange(0, 40))]
        idx = d.make_index(toks[-window:], len(toks))
        for _ in range(40):
            op = rng.random()
            if op < 0.55:
                new = [rng.randrange(vocab) for _ in range(rng.randrange(1, 6))]
                toks += new
                idx.extend(new)
            elif op < 0.7 and toks:
                # unwind/truncation: the engine rebuilds from the tail
                n = rng.randrange(1, min(5, len(toks)) + 1)
                toks = toks[:-n]
                idx = d.make_index(toks[-window:], len(toks))
            sfx = [rng.randrange(vocab) for _ in range(rng.randrange(0, 6))]
            k = rng.randrange(1, 6)
            want = d.propose((toks[-window:] + sfx)[-window:], k)
            got = idx.propose(k, sfx)
            assert got == want, (trial, toks, sfx, k, want, got)


def test_ngram_index_compaction_keeps_answers():
    """Long generations compact the retained token list to the window;
    proposals before and after compaction match the scratch scan."""
    d = NgramDrafter(max_ngram=3, max_window=16)
    toks = []
    idx = d.make_index([], 0)
    rng = random.Random(5)
    for _ in range(20):  # 20 × 5 tokens ≫ 2 × window → several compactions
        new = [rng.randrange(4) for _ in range(5)]
        toks += new
        idx.extend(new)
        assert idx.propose(4) == d.propose(toks[-16:], 4)
    assert len(idx.tokens) <= 2 * 16


# ---------------------------------------------------------------------------
# plan_pipelined_spec geometry / rollback (scheduler units)
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


def test_plan_pipelined_spec_lag_shifts_geometry():
    from dynamo_tpu.engine.scheduler import SeqState

    alloc = BlockAllocator(64, 4)
    sched = Scheduler(alloc, 4, max_batch_size=8)
    seq = _mk_seq(list(range(6)), request_id="a")
    seq.state = SeqState.RUNNING
    seq.block_table = [alloc.allocate_block() for _ in range(2)]
    # the just-harvested step emitted 2 tokens (lag) not yet appended;
    # the repaired drafts for the next step are [21, 22]
    plan = sched.plan_pipelined_spec([(seq, 2, [21, 22])], S=4)
    assert plan is not None
    a = plan["arrays"]
    # carry sits at (total_len + lag) - 1 = 7; drafts follow
    assert a["positions"][0].tolist() == [7, 8, 9, 10]
    assert a["tokens"][0, 1:3].tolist() == [21, 22]
    assert a["tokens"][0, 0] == 0  # placeholder: device chain fills it
    assert a["context_lens"][0] == 6 + 2 + 2
    assert a["draft_lens"][0] == 2
    assert plan["offsets"] == [2]  # seed offset = lag
    # blocks grew to cover total+lag+k = 10 tokens -> 3 blocks
    assert len(seq.block_table) == 3
    # the carry slot resolves through the block table at position 7
    assert a["slot_mapping"][0] == seq.block_table[1] * 4 + 3


def test_plan_pipelined_spec_excludes_predicted_finishes_and_rolls_back():
    from dynamo_tpu.engine.scheduler import SeqState

    alloc = BlockAllocator(8, 4)  # 7 usable
    sched = Scheduler(alloc, 4, max_batch_size=8)
    done = _mk_seq(list(range(4)), max_tokens=2, request_id="done")
    done.state = SeqState.RUNNING
    done.generated = 1
    done.block_table = [alloc.allocate_block()]
    live = _mk_seq(list(range(4)), request_id="live")
    live.state = SeqState.RUNNING
    live.block_table = [alloc.allocate_block()]
    # `done` finishes inside its lag (generated 1 + lag 1 == max 2):
    # not a row of the next step
    plan = sched.plan_pipelined_spec(
        [(done, 1, [9]), (live, 1, [9, 9])], S=4
    )
    assert plan is not None
    assert [s.request_id for s, _ in plan["works"]] == ["live"]
    assert plan["src_idx"][0] == 1  # chains from the PREVIOUS row index
    # cancellation flushes (returns None)
    live.is_cancelled = lambda: True
    assert sched.plan_pipelined_spec([(live, 1, [9])], S=4) is None
    live.is_cancelled = None
    # block exhaustion rolls back and flushes
    free0 = alloc.num_free
    while alloc.num_free:
        alloc.allocate_block()
    big = _mk_seq(list(range(4)), request_id="big")
    big.state = SeqState.RUNNING
    big.block_table = [1]
    blocks0 = len(big.block_table)
    assert sched.plan_pipelined_spec([(big, 1, [7, 7, 7])], S=4) is None
    assert len(big.block_table) == blocks0  # rollback left no growth


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
        spec_decode="ngram",
        spec_tokens=4,
    )
    defaults.update(kw)
    return EngineConfig(**defaults)


async def _generate(engine, prompt_ids, max_tokens=8, request_id="r",
                    temperature=None, seed=7, context=None):
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


# a prompt whose greedy continuation reuses its own structure, so the
# n-gram drafter proposes (and the pre-draft can hit); the other two
# exercise partial/no self-similarity in the same batch
SPEC_PROMPT = [1, 2, 3, 4, 5, 6, 1, 2, 3, 4, 5, 6, 1, 2, 3]
PROMPTS = [SPEC_PROMPT, [2, 9, 2, 9, 2, 9, 2], list(range(30, 41))]


async def _decode_all(engine, max_tokens=11, temperature=None, seed=7):
    outs = await asyncio.gather(*[
        _generate(engine, p, max_tokens=max_tokens, request_id=f"r{i}",
                  temperature=temperature, seed=seed)
        for i, p in enumerate(PROMPTS)
    ])
    return [o[0] for o in outs]


async def test_spec_overlap_bit_identical_vs_serial_spec():
    """THE acceptance criterion (ISSUE 12): spec+overlap greedy AND
    seeded-sampled output bit-identical to serial spec (--no-overlap),
    token for token — and the pipeline actually engaged (pipelined spec
    steps recorded, proposals made). Greedy output additionally matches
    a plain non-speculative engine (spec never changes greedy output).
    """
    from dynamo_tpu.engine.engine import JaxEngine

    eng = await JaxEngine.launch(_engine_config(overlap=True))
    try:
        over = await _decode_all(eng)
        over_sampled = await _decode_all(eng, temperature=0.8)
        assert eng.spec_pipeline_steps > 0, "pipeline never engaged"
        assert eng.spec_proposed_total > 0
        dbg = eng.debug_state()["spec"]
        assert dbg["pipelined"] is True
        assert dbg["predraft_hits"] + dbg["predraft_misses"] > 0
        # the draft time hidden under the device's step is counted, and
        # every pipelined step left a "spec" row in the recorder
        assert dbg["draft_hidden_s"] >= 0.0
        assert 0.0 <= dbg["draft_hidden_frac"] <= 1.0
        assert any(r["kind"] == "spec" for r in eng.recorder.snapshot(64))
        from dynamo_tpu.telemetry import REGISTRY

        assert "dynamo_spec_draft_hidden_frac" in REGISTRY.render()
    finally:
        await eng.shutdown()

    eng = await JaxEngine.launch(_engine_config(overlap=False))
    try:
        serial = await _decode_all(eng)
        serial_sampled = await _decode_all(eng, temperature=0.8)
        assert eng.spec_pipeline_steps == 0
        assert eng.spec_proposed_total > 0
    finally:
        await eng.shutdown()
    assert over == serial
    assert over_sampled == serial_sampled
    assert all(len(o) == 11 for o in over)

    # greedy rows also match plain non-speculative greedy
    plain = await JaxEngine.launch(_engine_config(spec_decode=""))
    try:
        base = await _decode_all(plain)
    finally:
        await plain.shutdown()
    assert over == base


async def test_spec_pipeline_late_stop_discards_inflight_tokens():
    """Late-detected stop (cancel/deadline): tokens sampled past the
    stop are DISCARDED at emit — never appended, never content-
    addressed — blocks are freed, and a continuation through the warm
    prefix cache matches a fresh engine's."""
    from dynamo_tpu.engine.engine import JaxEngine

    eng = await JaxEngine.launch(_engine_config(overlap=True))
    try:
        free0 = eng.allocator.num_free
        ctx = Context()
        req = PreprocessedRequest(
            request_id="late-stop",
            token_ids=SPEC_PROMPT,
            sampling=SamplingOptions(use_greedy=True),
            stop=StopConditions(max_tokens=64, ignore_eos=True),
        )
        got = []
        async for item in eng.as_async_engine().generate(req, ctx):
            got.extend(item.token_ids)
            if len(got) >= 2:
                ctx.stop_generating()  # a stop-string detection's shape
                break
        await eng.wait_for_state(
            lambda e: not e.scheduler.running and not e.scheduler.waiting
            and not e.scheduler.prefilling
        )
        await eng.wait_for_state(lambda e: e.allocator.num_free == free0)
        cont_warm, _ = await _generate(
            eng, SPEC_PROMPT + got, max_tokens=4, request_id="cont"
        )
    finally:
        await eng.shutdown()
    fresh = await JaxEngine.launch(_engine_config(spec_decode=""))
    try:
        cont_fresh, _ = await _generate(
            fresh, SPEC_PROMPT + got, max_tokens=4, request_id="cont2"
        )
    finally:
        await fresh.shutdown()
    assert cont_warm == cont_fresh


async def test_spec_pipeline_zero_proposal_falls_back_without_deadlock():
    """Prompts with no self-similarity produce zero proposals: the
    pipeline must fall back to the plain step (serial, one step) and
    keep serving — no deadlock, full token counts, and speculation
    re-engages when a proposal-rich request arrives."""
    from dynamo_tpu.engine.engine import JaxEngine

    eng = await JaxEngine.launch(_engine_config(overlap=True))
    try:
        toks, fin = await _generate(eng, list(range(40, 51)),
                                    max_tokens=6, request_id="noprop")
        assert len(toks) == 6 and fin.completion_tokens == 6
        # proposal-rich follow-up: the spec pipeline engages after the
        # zero-proposal episode
        toks, fin = await _generate(eng, SPEC_PROMPT, max_tokens=9,
                                    request_id="rich")
        assert len(toks) == 9
        assert eng.spec_pipeline_steps > 0
        assert not eng.scheduler.running
    finally:
        await eng.shutdown()


async def test_spec_pipeline_respects_block_pressure():
    """Block exhaustion mid-pipeline flushes to the serial spec step
    (which shrinks draft runs instead of preempting): output under
    pressure equals a roomy engine's greedy output."""
    from dynamo_tpu.engine.engine import JaxEngine

    async def run(num_blocks):
        eng = await JaxEngine.launch(
            _engine_config(overlap=True, num_blocks=num_blocks)
        )
        try:
            outs = await asyncio.gather(*[
                _generate(eng, p, max_tokens=10, request_id=f"p{i}")
                for i, p in enumerate(PROMPTS[:2])
            ])
            return [o[0] for o in outs]
        finally:
            await eng.shutdown()

    tight = await run(10)
    roomy = await run(64)
    assert tight == roomy
    assert all(len(t) == 10 for t in tight)
