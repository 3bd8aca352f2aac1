"""Speculative decoding tests (dynamo_tpu/spec).

The load-bearing properties:
- greedy speculative output is BIT-IDENTICAL to greedy non-speculative
  output on the tiny model (acceptance criterion of the subsystem);
- seeded statistical check that rejection sampling preserves the target
  distribution reference_sample_numpy/softmax describes;
- rollback bookkeeping: staged drafts never leak into host token state,
  blocks, or the prefix cache.
"""

import asyncio
import os

import numpy as np
import pytest

from dynamo_tpu.engine.allocator import BlockAllocator
from dynamo_tpu.engine.config import EngineConfig
from dynamo_tpu.engine.sampling import SamplingBatch, reference_sample_numpy
from dynamo_tpu.engine.scheduler import Scheduler, Sequence
from dynamo_tpu.protocols.common import (
    FinishReason,
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.runtime.engine import Context
from dynamo_tpu.spec import BigramTableDrafter, NgramDrafter, build_drafter
from dynamo_tpu.tokens import TokenBlockSequence

MODEL_DIR = os.path.join(os.path.dirname(__file__), "data", "tiny_llama_model")


# ---------------------------------------------------------------------------
# Drafters
# ---------------------------------------------------------------------------


def test_ngram_drafter_prompt_lookup():
    d = NgramDrafter(max_ngram=3)
    hist = [1, 2, 3, 4, 5, 6, 1, 2, 3]
    # trailing [1,2,3] matched at the start; continuation follows it
    assert d.propose(hist, 4) == [4, 5, 6, 1]
    assert d.propose(hist, 2) == [4, 5]
    # no earlier occurrence -> no proposal
    assert d.propose([7, 8, 9], 3) == []
    # k=0 and tiny histories are no-ops
    assert d.propose(hist, 0) == []
    assert d.propose([1], 3) == []


def test_ngram_drafter_prefers_longest_and_most_recent_match():
    d = NgramDrafter(max_ngram=3)
    # [2,3] occurs twice; the trailing trigram [1,2,3] only at index 0
    hist = [1, 2, 3, 9, 2, 3, 7, 1, 2, 3]
    assert d.propose(hist, 1) == [9]  # trigram match wins over bigram
    # drop to bigrams: most RECENT earlier [2,3] is at index 4 -> 7
    assert NgramDrafter(max_ngram=2).propose(hist, 1) == [7]


def test_ngram_drafter_window_bounds_scan():
    """The matcher reads only the trailing ``max_window`` tokens (the
    engine materializes exactly that tail via tail_tokens): matches
    older than the window are invisible."""
    hist = [1, 2, 3, 4, 5] + [9] * 50 + [1, 2, 3]
    assert NgramDrafter(max_ngram=3).propose(hist, 2) == [4, 5]
    small = NgramDrafter(max_ngram=3, max_window=8)
    assert small.window == 8
    # engine-side windowing: the drafter only ever sees the tail
    assert small.propose(hist[-8:], 2) == []


def test_tail_tokens_walks_blocks_from_the_end():
    seq = TokenBlockSequence(list(range(10)), block_size=4)
    assert seq.tail_tokens(3) == [7, 8, 9]
    assert seq.tail_tokens(6) == [4, 5, 6, 7, 8, 9]  # crosses a block
    assert seq.tail_tokens(100) == list(range(10))
    assert seq.tail_tokens(0) == []
    assert seq.last_token() == 9


def test_bigram_drafter_table_and_files(tmp_path):
    b = BigramTableDrafter.from_corpus([1, 2, 3, 1, 2, 3, 1, 2], 10)
    assert b.propose([9, 1], 3) == [2, 3, 1]
    assert b.propose([7], 2) == []  # no entry for 7
    assert b.propose([], 2) == []
    # json round trip
    import json

    p = tmp_path / "bigram.json"
    p.write_text(json.dumps({"1": 2, "2": 3}))
    j = BigramTableDrafter.from_file(str(p))
    assert j.propose([1], 3) == [2, 3]
    # npz round trip
    pz = tmp_path / "bigram.npz"
    np.savez(pz, next=b.table)
    assert BigramTableDrafter.from_file(str(pz)).propose([9, 1], 3) == [2, 3, 1]


def test_build_drafter_specs(tmp_path):
    assert isinstance(build_drafter("ngram"), NgramDrafter)
    assert build_drafter("ngram:5").max_ngram == 5
    with pytest.raises(ValueError):
        build_drafter("bigram")  # needs a path
    with pytest.raises(ValueError):
        build_drafter("medusa")


# ---------------------------------------------------------------------------
# Rejection sampling: distribution preservation (seeded, statistical)
# ---------------------------------------------------------------------------


def _verify(logits, tokens, draft_lens, opts, seeds):
    import jax.numpy as jnp

    from dynamo_tpu.spec.verify import verify_tokens

    sb = SamplingBatch.from_options(opts, seeds)
    t, lp, n = verify_tokens(
        jnp.asarray(logits), jnp.asarray(np.asarray(tokens, np.int32)),
        jnp.asarray(np.asarray(draft_lens, np.int32)), sb.arrays,
    )
    return np.asarray(t), np.asarray(lp), np.asarray(n), sb


def test_spec_rejection_preserves_target_distribution():
    """P(emit x at position j) must equal the target softmax regardless
    of what the drafter proposed — N independent seeded verifies over
    the same logits, frequencies vs reference_sample_numpy's transform."""
    V, S, K = 13, 4, 3
    rng = np.random.default_rng(42)
    base = (rng.normal(size=(S, V)) * 1.5).astype(np.float32)
    # draft 0 = a high-probability token (so the conditional position-1
    # sample survives often); draft 1 deliberately unlikely
    p_row0 = np.exp(base[0] - base[0].max())
    d0 = int(np.argmax(p_row0))
    drafts = [d0, int(np.argmin(p_row0)), 3]
    N = 4000
    logits = np.broadcast_to(base, (N, S, V)).astype(np.float32)
    tokens = np.zeros((N, S), np.int32)
    tokens[:, 1:] = drafts
    opts = [SamplingOptions(temperature=1.0)] * N
    t, _, n, sb = _verify(logits, tokens, [K] * N, opts, list(range(N)))

    # position 0 marginal == softmax of the reference transform
    ref = reference_sample_numpy(base[0], sb.arrays, 0)
    p0 = np.exp(ref - ref.max())
    p0 /= p0.sum()
    freq0 = np.bincount(t[:, 0], minlength=V) / N
    assert np.abs(freq0 - p0).max() < 0.03, (freq0, p0)

    # conditioned on draft 0 accepted, position 1 marginal == its target
    # (acceptance happens with prob p0(d0) ≈ 0.2 here — enough samples
    # for a 4-sigma band at this vocab size)
    m = n > 1
    assert m.sum() > 500
    p1 = np.exp(base[1].astype(np.float64) - base[1].max())
    p1 /= p1.sum()
    freq1 = np.bincount(t[m, 1], minlength=V) / m.sum()
    assert np.abs(freq1 - p1).max() < 0.07, (freq1, p1)


def test_spec_verify_respects_topk_filter():
    """With top_k the emitted token must come from the SAME keep set
    sample() filters to — never a token outside the top-k slice."""
    V, S = 17, 3
    rng = np.random.default_rng(7)
    base = (rng.normal(size=(S, V)) * 2).astype(np.float32)
    N = 512
    logits = np.broadcast_to(base, (N, S, V)).astype(np.float32)
    topk = 3
    keep0 = set(np.argsort(base[0])[-topk:].tolist())
    tokens = np.zeros((N, S), np.int32)
    tokens[:, 1] = int(np.argsort(base[0])[0])  # draft OUTSIDE the keep set
    tokens[:, 2] = 1
    opts = [SamplingOptions(temperature=1.0, top_k=topk)] * N
    t, _, n, _ = _verify(logits, tokens, [S - 1] * N, opts, list(range(N)))
    # the out-of-set draft must always be rejected, and the replacement
    # drawn from the keep set
    assert (n >= 1).all()
    assert set(t[:, 0].tolist()) <= keep0
    assert (t[:, 0] != tokens[0, 1]).all()


def test_spec_verify_greedy_rows_and_zero_drafts():
    V, S = 9, 4
    rng = np.random.default_rng(3)
    logits = (rng.normal(size=(2, S, V)) * 3).astype(np.float32)
    gt = np.argmax(logits, axis=-1)
    tokens = np.zeros((2, S), np.int32)
    tokens[0, 1:] = gt[0, :3]  # perfect drafts -> full accept + bonus
    opts = [SamplingOptions(use_greedy=True)] * 2
    t, lp, n, _ = _verify(logits, tokens, [3, 0], opts, [1, 2])
    assert n[0] == 4 and (t[0] == gt[0]).all()
    # zero drafts = plain greedy decode of one token
    assert n[1] == 1 and t[1, 0] == gt[1, 0]
    # emitted logprobs are log_softmax of the raw logits at the chosen ids
    lsm = logits[0, 0] - np.log(np.exp(logits[0, 0]).sum())
    np.testing.assert_allclose(lp[0, 0], lsm[t[0, 0]], rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# Scheduler bookkeeping: staging, rollback, block accounting
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


def test_reserve_spec_tokens_allocates_and_shrinks():
    alloc = BlockAllocator(8, 4)  # 7 usable
    sched = Scheduler(alloc, 4, max_batch_size=4)
    seq = _mk_seq(list(range(7)))  # 7 tokens -> 2 blocks
    seq.block_table = [alloc.allocate_block(), alloc.allocate_block()]
    # 3 drafts need a 3rd block (7+3=10 tokens -> 3 blocks); 5 free
    k = sched.reserve_spec_tokens(seq, [11, 12, 13])
    assert k == 3 and len(seq.block_table) == 3
    assert seq.total_len == 10  # drafts staged into token state
    seq.tokens.unwind(k)
    assert seq.total_len == 7
    # exhaust the pool: a seq at a block boundary gets 0 drafts
    while alloc.num_free:
        alloc.allocate_block()
    seq2 = _mk_seq(list(range(4)), request_id="r2")
    seq2.block_table = [1]  # exactly full block
    assert sched.reserve_spec_tokens(seq2, [5, 6]) == 0
    assert seq2.total_len == 4  # nothing staged
    # a seq with slack in its last block keeps what fits
    seq3 = _mk_seq(list(range(6)), request_id="r3")
    seq3.block_table = [2, 3]  # covers 8 slots, 2 spare
    assert sched.reserve_spec_tokens(seq3, [7, 8, 9]) == 2
    assert seq3.total_len == 8


def test_build_spec_arrays_geometry():
    alloc = BlockAllocator(64, 4)
    sched = Scheduler(alloc, 4, max_batch_size=8)
    seq = _mk_seq(list(range(6)), request_id="a")
    seq.block_table = [alloc.allocate_block() for _ in range(2)]
    k = sched.reserve_spec_tokens(seq, [21, 22])
    assert k == 2
    arrays = sched.build_spec_arrays([(seq, [5, 21, 22])], S=4)
    B, S = arrays["tokens"].shape
    assert S == 4 and B == sched._decode_batch(1)
    # row = [last committed token, d0, d1, pad]
    assert arrays["tokens"][0, :3].tolist() == [5, 21, 22]
    # positions contiguous from the carry token, pads included
    assert arrays["positions"][0].tolist() == [5, 6, 7, 8]
    assert arrays["context_lens"][0] == 8
    assert arrays["draft_lens"][0] == 2
    # real slots resolve through the block table; the pad writes to the
    # reserved garbage slot 0
    bt = seq.block_table
    assert arrays["slot_mapping"][0] == bt[1] * 4 + 1
    assert arrays["slot_mapping"][3] == 0
    seq.tokens.unwind(k)


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


async def _generate(engine, prompt_ids, max_tokens=8, request_id="r",
                    speculative=None, temperature=None):
    sampling = (
        SamplingOptions(use_greedy=True)
        if temperature is None
        else SamplingOptions(temperature=temperature, seed=7)
    )
    req = PreprocessedRequest(
        request_id=request_id,
        token_ids=list(prompt_ids),
        sampling=sampling,
        stop=StopConditions(max_tokens=max_tokens),
        speculative=speculative,
    )
    out = []
    final = None
    async for item in engine.as_async_engine().generate(req, Context()):
        out.extend(item.token_ids)
        if item.is_final:
            final = item
    return out, final


# a prompt whose greedy continuation reuses its own structure: the
# n-gram drafter then actually proposes (and a wrong-draft path is
# still exercised whenever the model diverges from the lookup)
SPEC_PROMPT = [1, 2, 3, 4, 5, 6, 1, 2, 3, 4, 5, 6, 1, 2, 3]


async def test_engine_greedy_spec_bit_identical():
    """THE acceptance criterion: greedy speculative == greedy plain,
    token for token, including an odd max_tokens (bonus-token clamping)
    — and the drafter must have actually proposed something. The plain
    reference runs on the SAME engine via the per-request opt-out,
    which diverts to the literal non-speculative decode path (same
    kernels, same state; greedy continuation through the warm prefix
    cache is pinned identical by test_engine.py). Piggybacks the
    temperature-sampled completion and the /metrics exposition checks
    (tier-1 budget: engine launches are the expensive part here)."""
    from dynamo_tpu.engine.engine import JaxEngine
    from dynamo_tpu.telemetry import REGISTRY

    engine = await JaxEngine.launch(
        _engine_config(spec_decode="ngram", spec_tokens=4)
    )
    try:
        # admission's page reserve reads its rate and its slack from the
        # engine's own configuration (Scheduler._growth_reserve)
        assert engine.scheduler.spec_tokens == 4
        assert engine.scheduler.dispatches_ahead == engine.PIPELINE_DEPTH + 1
        spec, fs = await _generate(engine, SPEC_PROMPT, max_tokens=13,
                                   request_id="spec")
        assert fs.finish_reason == FinishReason.LENGTH
        assert fs.completion_tokens == 13 == len(spec)
        assert engine.spec_proposed_total > 0
        # per-request opt-out = the plain decode path: same output
        base, _ = await _generate(engine, SPEC_PROMPT, max_tokens=13,
                                  request_id="off", speculative=False)
        assert spec == base
        # temperature sampling rides the verify step too (distribution
        # correctness is the statistical test's job; here: exact token
        # accounting and clean teardown)
        toks, fin = await _generate(engine, SPEC_PROMPT, max_tokens=10,
                                    request_id="sampled", temperature=0.8)
        assert len(toks) == 10 and fin.completion_tokens == 10
        # a prompt with no self-similarity: zero-proposal steps fall
        # back to the plain decode step and serving still completes
        toks, fin = await _generate(engine, list(range(40, 51)),
                                    max_tokens=6, request_id="noprop")
        assert len(toks) == 6 and fin.completion_tokens == 6
        # all blocks returned (drafted blocks uncommitted + freed)
        assert not engine.scheduler.running
    finally:
        await engine.shutdown()
    # accept-rate and proposed/accepted instruments appear on /metrics
    text = REGISTRY.render()
    assert 'dynamo_spec_proposed_tokens_total{drafter="ngram"}' in text
    assert "dynamo_spec_accept_rate" in text
    assert "dynamo_spec_step_seconds" in text


@pytest.mark.slow
async def test_engine_spec_concurrent_and_prefix_cache_intact():
    """Speculative KV writes for rejected drafts must never poison the
    prefix cache: continuing from a previously-generated history through
    the cache must match a fresh engine's continuation."""
    from dynamo_tpu.engine.engine import JaxEngine

    engine = await JaxEngine.launch(
        _engine_config(spec_decode="ngram", spec_tokens=3, block_size=4)
    )
    try:
        prompts = [SPEC_PROMPT, list(range(2, 12)), [3, 3, 3, 3, 3, 3, 3]]
        results = await asyncio.gather(*[
            _generate(engine, p, max_tokens=8, request_id=f"c{i}")
            for i, p in enumerate(prompts)
        ])
        for toks, fin in results:
            assert len(toks) == 8 and fin.finish_reason == FinishReason.LENGTH
        # reuse the full first history through the warm prefix cache
        full = prompts[0] + results[0][0]
        cont_cached, _ = await _generate(engine, full, max_tokens=4,
                                         request_id="reuse")
    finally:
        await engine.shutdown()
    fresh = await JaxEngine.launch(_engine_config(block_size=4))
    try:
        cont_fresh, _ = await _generate(fresh, full, max_tokens=4,
                                        request_id="fresh")
    finally:
        await fresh.shutdown()
    assert cont_cached == cont_fresh


def test_spec_divert_policy():
    """ANY opted-out request diverts its whole batch: the opt-out
    contract is the literal plain-decode path (T==1 kernel, sample()'s
    RNG stream), which the verify step only approximates."""
    from dynamo_tpu.engine.engine import JaxEngine

    engine = JaxEngine(_engine_config(spec_decode="ngram"))
    engine._drafter = NgramDrafter()

    def seq(greedy, spec):
        return Sequence(
            request=PreprocessedRequest(
                request_id="x", token_ids=[1, 2],
                sampling=SamplingOptions(
                    use_greedy=greedy,
                    temperature=None if greedy else 0.9,
                ),
                speculative=spec,
            ),
            tokens=TokenBlockSequence([1, 2], block_size=4),
        )

    spec_on = seq(True, None)
    assert not engine._spec_divert([spec_on, seq(False, None)])
    assert engine._spec_divert([spec_on, seq(True, False)])
    assert engine._spec_divert([spec_on, seq(False, False)])
    assert engine._spec_divert([seq(True, False)])


async def test_spec_config_rejects_fused_windows_and_bad_k():
    from dynamo_tpu.engine.engine import JaxEngine

    with pytest.raises(ValueError, match="decode_steps"):
        await JaxEngine.launch(
            _engine_config(spec_decode="ngram", decode_steps=4)
        )
    with pytest.raises(ValueError, match="spec_tokens"):
        await JaxEngine.launch(
            _engine_config(spec_decode="ngram", spec_tokens=0)
        )


# ---------------------------------------------------------------------------
# KV-router satellite: token-specific in-flight release
# ---------------------------------------------------------------------------


def test_kv_scheduler_note_done_releases_specific_charge():
    from dynamo_tpu.kv_router.indexer import KvIndexer
    from dynamo_tpu.kv_router.scheduler import KvMetricsAggregator, KvScheduler

    sched = KvScheduler(KvIndexer(block_size=4), KvMetricsAggregator())
    t1 = sched.note_dispatch(7)
    t2 = sched.note_dispatch(7)
    # releasing the SECOND charge must keep the first alive
    sched.note_done(7, t2)
    assert sched.inflight[7] == [t1]
    # double-release of the same token is a no-op
    sched.note_done(7, t2)
    assert sched.inflight[7] == [t1]
    sched.note_done(7, t1)
    assert 7 not in sched.inflight
    # unknown worker is a no-op
    sched.note_done(99, 1.0)
    # schedule() hands the token back on the decision
    sched.aggregator.update(
        __import__(
            "dynamo_tpu.kv_router.protocols", fromlist=["ForwardPassMetrics"]
        ).ForwardPassMetrics(worker_id=1)
    )
    d = sched.schedule([1, 2, 3, 4], [1])
    assert d.dispatch_token > 0
    assert sched.inflight[1] == [d.dispatch_token]
    sched.note_done(1, d.dispatch_token)
    assert 1 not in sched.inflight
