"""``mimo_v2_flash`` through the ENGINE on the CPU: what the served path
returns — chosen ids and their logprobs, chunked prefill then decode
through BOTH page planes — against the plain reference's full forward
pass, in float32 so that they meet to rounding; the window plane
released between a prompt's chunks and as decode advances, equal to a
run that releases nothing; what the engine gives a family with a
released plane (a second allocator, no prefix cache, both planes in
``/debug/state`` and the counts); and the two planes' bookkeeping under
any sequence of admit / chunk / decode / preempt / cancel / finish, with
no device."""

import asyncio
import random

import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine.allocator import BlockAllocator, NoBlocksError, WindowPlane
from dynamo_tpu.engine.config import EngineConfig
from dynamo_tpu.engine.scheduler import Scheduler, SeqState, Sequence
from dynamo_tpu.models import mimo_v2_flash as mm
from dynamo_tpu.models.reference import mimo_v2_flash as ref
from dynamo_tpu.protocols.common import (
    FinishReason,
    PreprocessedRequest,
    StopConditions,
)
from dynamo_tpu.tokens import TokenBlockSequence
from tests.mimo_v2_flash_tiny import tiny_mimo
from tests.test_kimi_linear_engine import generate

TOL = 2e-4   # float32 end to end: differences are summation order


def engine_config(**kw) -> EngineConfig:
    defaults = dict(
        model_name="tiny-mimo", random_weights=True, seed=5, num_blocks=64,
        block_size=8, max_batch_size=4, prefill_chunk_size=16,
        max_model_len=160, kv_cache_dtype="float32", static_shapes=False,
    )
    defaults.update(kw)
    return EngineConfig(**defaults)


async def launch(cfg=None, **kw):
    """An engine whose parameters are the seeded draw in float32."""
    from dynamo_tpu.engine.engine import JaxEngine

    cfg = cfg or tiny_mimo()
    engine = await JaxEngine.launch(engine_config(**kw), model_config=cfg)
    params = mm.init_params(cfg, seed=5, dtype=jnp.float32)
    await engine.acall_on_thread(lambda: setattr(engine, "params", params))
    return engine, params


def reference_logprobs(cfg, params, prompt, chosen):
    seq = np.asarray([list(prompt) + list(chosen)], np.int32)
    logits = np.asarray(ref.forward(cfg, params, jnp.asarray(seq)))[0]
    at = np.arange(len(prompt) - 1, len(seq[0]) - 1)
    top = logits[at].max(-1, keepdims=True)
    lp = logits[at] - top - np.log(np.exp(logits[at] - top).sum(-1, keepdims=True))
    return lp[np.arange(len(at)), np.asarray(chosen)], logits[at].argmax(-1)


def assert_matches(cfg, params, prompt, toks, lps):
    want_lp, want_id = reference_logprobs(cfg, params, prompt, toks)
    assert toks == want_id.tolist()
    np.testing.assert_allclose(lps, want_lp, atol=TOL)


def planes_empty(engine) -> bool:
    sched = engine.scheduler
    return (sched.window_plane.num_used == 0
            and sched.allocator.num_free == sched.allocator.num_blocks - 1)


PROMPTS = {
    "one_chunk": list(range(3, 14)),                 # 11 tokens < chunk 16
    "chunk_edge": list(range(20, 36)),               # exactly one chunk
    "three_chunks": [(7 * i) % 251 for i in range(41)],   # 16 + 16 + 9
    "single_token": [9],
}


@pytest.mark.parametrize("name", sorted(PROMPTS))
async def test_prefill_then_decode_matches_reference(name):
    """Window 12 over pages of 8: a later chunk attends the earlier
    chunks' keys in both planes, the window plane's through its live
    columns alone; 30 decoded tokens release window pages as they go."""
    cfg = tiny_mimo()
    engine, params = await launch(cfg)
    try:
        toks, lps = await generate(engine, PROMPTS[name], 30, name)
        assert len(toks) == 30
        assert_matches(cfg, params, PROMPTS[name], toks, lps)
        assert engine.scheduler.window_plane.released_total > 0
        assert planes_empty(engine)
    finally:
        await engine.shutdown()


@pytest.mark.parametrize("decode_steps", [1, 3])
async def test_batched_rows_of_unequal_length(decode_steps):
    """With ``decode_steps`` > 1 a fused window writes several tokens a
    row a dispatch: released only behind the window of the row's applied
    length, and a masked step's write goes to the garbage slot of BOTH
    planes."""
    cfg = tiny_mimo()
    engine, params = await launch(cfg, decode_steps=decode_steps)
    try:
        names = sorted(PROMPTS)
        got = await asyncio.gather(*[
            generate(engine, PROMPTS[n], 20, n) for n in names])
        for n, (toks, lps) in zip(names, got):
            assert_matches(cfg, params, PROMPTS[n], toks, lps)
        assert planes_empty(engine)
    finally:
        await engine.shutdown()


@pytest.mark.parametrize("window,block_size", [(8, 4), (24, 16), (16, 16)])
async def test_window_and_page_are_independent(window, block_size):
    cfg = tiny_mimo(sliding_window=window, sliding_window_size=window,
                    attention_chunk_size=window)
    engine, params = await launch(cfg, block_size=block_size)
    try:
        prompt = [(5 * i) % 247 + 3 for i in range(50)]
        toks, lps = await generate(engine, prompt, 25, "w")
        assert_matches(cfg, params, prompt, toks, lps)
        plane = engine.scheduler.window_plane
        assert (plane.window, plane.block_size) == (window, block_size)
        assert plane.released_total > 0 and planes_empty(engine)
    finally:
        await engine.shutdown()


async def test_released_between_chunks_and_during_decode_equals_never_released(
        monkeypatch):
    """A prompt that crosses three prefill chunks, its window pages
    handed back between them, and a decode long enough to hand back
    more: ids and logprobs EQUAL a run in which nothing is released."""
    cfg = tiny_mimo()
    prompt = PROMPTS["three_chunks"]
    engine, params = await launch(cfg)
    try:
        toks, lps = await generate(engine, prompt, 40, "released")
        plane = engine.scheduler.window_plane
        # 41 + 40 tokens = 11 pages of 8; the window's keys span 3
        assert plane.released_total >= 7
    finally:
        await engine.shutdown()
    monkeypatch.setattr(WindowPlane, "release_behind", lambda *a: 0)
    monkeypatch.setattr(WindowPlane, "first_live", lambda *a: 0)
    engine, params = await launch(cfg)
    try:
        kept, kept_lps = await generate(engine, prompt, 40, "kept")
        assert engine.scheduler.window_plane.released_total == 0
        assert planes_empty(engine)
    finally:
        await engine.shutdown()
    assert toks == kept
    np.testing.assert_array_equal(np.asarray(lps), np.asarray(kept_lps))
    assert_matches(cfg, params, prompt, toks, lps)


async def test_the_engine_gives_two_planes_and_no_prefix_cache():
    """Page ownership, recurrent state and a released plane are three
    questions: this family answers yes, no and yes."""
    cfg = tiny_mimo()
    assert cfg.owns_pages and not cfg.has_recurrent_state
    assert cfg.released_window == 12
    engine, _ = await launch(cfg)
    try:
        sched = engine.scheduler
        assert sched.state_slots is None and sched.table_extra == 0
        assert not sched.allocator.enable_prefix_caching
        plane = sched.window_plane
        # 4 rows x span(1 + 3 dispatches ahead) + a prefill batch + a chunk
        assert plane.span_pages(4) == 3 and plane.span_pages(16) == 5
        assert plane.num_blocks == 1 + 4 * 3 + (4 * 16) // 8 + 5
        assert sched.table_width_of(8) == 16 and sched._table_width(3) == 16
        assert set(engine.v_cache) == {"counts"}
        assert engine.k_cache["full_k"].shape == (2, 64 * 8 * 2, 128)
        assert engine.k_cache["win_v"].shape == (2, plane.num_blocks * 8 * 4, 16)
        await generate(engine, PROMPTS["three_chunks"], 12, "a")
        state = engine.debug_state()
        assert "state_plane" not in state and "page_plane" not in state
        planes = state["page_planes"]
        assert planes["full"] == {
            "bytes": 2 * 64 * 8 * 2 * (128 + 16) * 4,
            "pages_total": 63, "pages_in_use": 0}
        assert planes["window"]["pages_total"] == plane.num_blocks - 1
        assert planes["window"]["pages_in_use"] == 0
        assert planes["window"]["bytes"] \
            == 2 * plane.num_blocks * 8 * 4 * (128 + 16) * 4
        assert planes["window_pages_released_total"] == plane.released_total > 0
        counts = engine.program_counts()
        assert counts["window_pages_released_total"] == plane.released_total
        assert counts["window_pages_in_use"] == 0
        assert counts["window_page_steps"] > 0 and counts["window_row_steps"] > 0
        # a handful a row, whatever the row's length
        assert counts["window_page_steps"] / counts["window_row_steps"] <= 5
        assert (sched.prefix_queries, sched.prefix_hits) == (1, 0)
    finally:
        await engine.shutdown()


async def test_the_prefill_span_carries_the_pages_its_chunks_released():
    from dynamo_tpu.telemetry import get_tracer, reset_tracer

    reset_tracer()
    buf = get_tracer().keep_in_memory()
    engine, _ = await launch(tiny_mimo())
    try:
        await generate(engine, PROMPTS["three_chunks"], 3, "s")
        spans, _ = buf.snapshot()
        (prefill,) = [s for s in spans if s["name"] == "engine.prefill"]
        # chunks end at 16, 32, 41: a query at 16 / 32 / 41 reads keys
        # from 5 / 21 / 30 on, so columns 0; 1; 2 go, one a chunk
        assert prefill["attrs"]["chunks"] == 3
        assert prefill["attrs"]["window_pages_released"] == 3
    finally:
        await engine.shutdown()
        reset_tracer()


async def test_attention_counts_are_pairs_and_keys_by_position(monkeypatch):
    monkeypatch.setattr(mm, "PAIR_UNIT", 1)
    cfg = tiny_mimo()
    engine, _ = await launch(cfg)
    try:
        await generate(engine, PROMPTS["three_chunks"], 3, "p")
        c = engine.program_counts()
        w = 12
        assert c["attn_full_pairs"] == 2 * (41 * 42 // 2)
        assert c["attn_window_pairs"] == 2 * sum(min(p + 1, w) for p in range(41))
        # two decode steps read contexts 42 and 43 (the third token needs none)
        assert c["attn_full_decode_keys"] == 2 * (42 + 43)
        assert c["attn_window_decode_keys"] == 2 * 2 * w
        assert c["attn_full_prefill_calls"] == c["attn_window_prefill_calls"] == 6
        assert c["attn_full_decode_calls"] == c["attn_window_decode_calls"] == 4
        assert c["moe_layer_calls"] == 3 * (3 + 2)
    finally:
        await engine.shutdown()


async def test_preempted_row_resumes_and_frees_both_planes():
    cfg = tiny_mimo()
    engine, params = await launch(cfg)
    try:
        sched = engine.scheduler
        hit = []

        async def preempt_once(n_tokens):
            if n_tokens == 4 and not hit:
                def do():
                    victim = next(s for s in sched.running
                                  if s.request_id == "victim")
                    sched._preempt(victim)
                    hit.append((list(victim.window_table),
                                list(victim.block_table)))
                await engine.acall_on_thread(do)

        (toks, lps), (toks2, lps2) = await asyncio.gather(
            generate(engine, PROMPTS["three_chunks"], 10, "victim",
                     on_token=preempt_once),
            generate(engine, PROMPTS["one_chunk"], 10, "bystander"))
        assert hit == [([], [])] and sched.preemptions == 1
        assert sched.prefix_hits == 0          # recomputed, not reused
        assert_matches(cfg, params, PROMPTS["three_chunks"], toks, lps)
        assert_matches(cfg, params, PROMPTS["one_chunk"], toks2, lps2)
        assert planes_empty(engine)
    finally:
        await engine.shutdown()


async def test_cancellation_leaves_neither_plane_with_a_page():
    from dynamo_tpu.runtime.engine import Context

    cfg = tiny_mimo()
    engine, _ = await launch(cfg)
    try:
        ctx = Context()

        async def stop_soon(n_tokens):
            if n_tokens == 3:
                ctx.stop_generating()

        toks, _ = await generate(engine, PROMPTS["three_chunks"], 60, "c",
                                 ctx=ctx, on_token=stop_soon)
        assert 3 <= len(toks) < 60
        for _ in range(200):
            if planes_empty(engine) and not engine.scheduler.has_work:
                break
            await asyncio.sleep(0.01)
        assert planes_empty(engine)
    finally:
        await engine.shutdown()


REFUSED = {
    "tp": dict(tensor_parallel_size=2),
    "ep": dict(expert_parallel_size=2),
    "pp": dict(pipeline_parallel_size=2),
    "dp": dict(data_parallel_size=2),
    "spec": dict(spec_decode="ngram"),
    "kvbm": dict(host_kv_blocks=8),
    "int8_cache": dict(kv_cache_dtype="int8"),
}


@pytest.mark.parametrize("what", sorted(REFUSED))
async def test_unsupported_combinations_raise_at_start_up(what):
    from dynamo_tpu.engine.engine import JaxEngine

    with pytest.raises(ValueError, match="mimo_v2_flash"):
        await JaxEngine.launch(engine_config(**REFUSED[what]),
                               model_config=tiny_mimo())


async def test_a_checkpoint_kv_transfer_and_injected_embeddings_are_refused(tmp_path):
    from dynamo_tpu.models import loader

    with pytest.raises(NotImplementedError, match="mimo_v2_flash"):
        loader.resolve_model(str(tmp_path), model_config=tiny_mimo(),
                             random_weights=False)
    engine, params = await launch()
    try:
        with pytest.raises(NotImplementedError, match="lays its pages out"):
            await engine.export_kv_blocks([1, 2])
        with pytest.raises(NotImplementedError, match="lays its pages out"):
            await engine.import_kv_blocks([1], np.zeros((1,)))
    finally:
        await engine.shutdown()
    cfg = tiny_mimo()
    pages, counts = mm.init_cache(cfg, 4, 8, dtype=jnp.float32)
    z = np.zeros((1, 1), np.int32)
    with pytest.raises(NotImplementedError, match="injected embeddings"):
        mm.forward(cfg, params, pages, counts, z, z, z.reshape(-1),
                   np.zeros((1, 4), np.int32), np.ones((1,), np.int32),
                   np.zeros((1,), np.int32), 8,
                   extra_embeds=jnp.zeros((1, 1, 64)))


# -- the two planes' bookkeeping, with no device ---------------------------------
def test_window_plane_covers_releases_and_frees():
    plane = WindowPlane(8, 4, 6)              # 7 usable pages of 4, window 6
    assert (plane.num_free, plane.num_used) == (7, 0)
    assert [plane.first_live(p) for p in (0, 5, 6, 9, 13)] == [0, 0, 0, 1, 2]
    # window - 1 + tokens positions, wherever they start
    assert [plane.span_pages(t) for t in (1, 2, 4, 8)] == [3, 3, 3, 4]
    table: list[int] = []
    plane.cover(table, 3, 0)                  # a chunk of 12 tokens from 0
    assert len(table) == 3 and all(table) and plane.num_used == 3
    assert plane.release_behind(table, 12) == 1          # keys 7.. : column 0 goes
    assert table[0] == 0 and plane.released_total == 1
    plane.cover(table, 5, 12)                 # the next chunk: columns 1-4
    assert table[0] == 0 and all(table[1:]) and plane.num_used == 4
    assert plane.release_behind(table, 12) == 0          # nothing twice
    with pytest.raises(NoBlocksError):
        plane.cover(table, 12, 12)            # 7 more than the 3 free
    assert plane.num_used == 4 and len(table) == 12      # nothing taken
    del table[5:]
    plane.free_row(table)
    assert table == [] and plane.num_free == 7
    with pytest.raises(ValueError):
        WindowPlane(1, 4, 6)
    with pytest.raises(ValueError):
        WindowPlane(4, 4, 0)


def _seq(n_prompt, bs, max_tokens, rid):
    tokens = [(3 * i + 1) % 250 for i in range(n_prompt)]
    return Sequence(
        request=PreprocessedRequest(
            request_id=rid, token_ids=tokens,
            stop=StopConditions(max_tokens=max_tokens)),
        tokens=TokenBlockSequence(tokens, block_size=bs))


def two_plane_scheduler(window, bs, window_pages, full_pages=200, rows=6,
                        chunk=16, lookahead=1):
    sched = Scheduler(BlockAllocator(full_pages, bs, enable_prefix_caching=False),
                      bs, max_batch_size=rows, prefill_chunk_size=chunk,
                      max_model_len=400, max_prefill_tokens=2 * chunk)
    sched.window_plane = WindowPlane(window_pages, bs, window)
    sched.decode_lookahead = lookahead
    return sched


@pytest.mark.parametrize("window,bs", [(8, 4), (12, 8), (24, 16), (16, 16),
                                       (5, 16), (24, 4)])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_any_sequence_of_events_returns_both_planes_whole(window, bs, seed):
    """Admit, prefill in chunks, decode, and at random preempt, cancel
    and finish early: every step's tables are built as the engine builds
    them; no allocation of the window plane ever fails (admission
    reserved it), a row never holds more window pages than the bound
    admission reserved for it, a live column always holds a page and a
    dead one none, and at the end both planes are whole."""
    rng = random.Random(seed)
    probe = WindowPlane(2, bs, window)
    lookahead = rng.choice([1, 3])
    ahead = 1 + 3 * lookahead
    # room for three rows at their bound: admission has to say no at times
    pages = 1 + 2 * probe.span_pages(16) + probe.span_pages(ahead)
    sched = two_plane_scheduler(window, bs, pages, lookahead=lookahead)
    plane, alloc = sched.window_plane, sched.allocator
    cancelled: set[str] = set()
    seqs = []
    for i in range(14):
        s = _seq(rng.randint(1, 70), bs, rng.randint(1, 60), f"r{i}")
        s.is_cancelled = lambda rid=s.request_id: rid in cancelled
        seqs.append(s)
    pending = list(seqs)
    worst = 0

    def check_rows():
        nonlocal worst
        for pool in (sched.running, sched.prefilling):
            for s in pool:
                held = plane.held(s.window_table)
                assert held <= sched.window_row_bound(s), (
                    s.request_id, held, sched.window_row_bound(s))
                worst = max(worst, held)
                first = plane.first_live(s.num_computed)
                assert not any(s.window_table[:first])
        for s in sched.waiting:
            assert s.window_table == [] and s.block_table == []

    steps = 0
    while pending or sched.has_work:
        steps += 1
        assert steps < 5000
        for _ in range(rng.randint(0, 2)):
            if pending:
                sched.add_request(pending.pop())
        plan = sched.plan()
        if plan.prefill_batch:
            arrays = sched.build_prefill_batch_arrays(plan.prefill_batch)
            half = arrays["block_tables"].shape[1] // 2
            for i, w in enumerate(plan.prefill_batch):
                lo = plane.first_live(w.start_pos)
                hi = (w.start_pos + len(w.tokens) - 1) // bs
                row = arrays["block_tables"][i, half:]
                assert all(row[lo: hi + 1]) and not any(row[:lo])
                assert not any(row[hi + 1:])
            check_rows()
            for w in plan.prefill_batch:
                sched.complete_prefill_chunk(w)
                if w.is_last_chunk:
                    sched.append_token(w.seq, 7)
                    if sched.should_finish(w.seq):
                        sched.finish(w.seq, FinishReason.LENGTH)
        if plan.decode_seqs:
            arrays = sched.build_decode_arrays(plan.decode_seqs)
            half = arrays["block_tables"].shape[1] // 2
            for i, s in enumerate(plan.decode_seqs):
                pos = s.total_len - 1
                row = arrays["block_tables"][i, half:]
                assert all(row[plane.first_live(pos): pos // bs + 1])
                assert row[pos // bs] == s.window_table[pos // bs]
            check_rows()
            for s in plan.decode_seqs:
                for _ in range(sched._seq_lookahead(s)):
                    sched.append_token(s, 7)
                    reason = sched.should_finish(s)
                    if reason is None and rng.random() < 0.02:
                        reason = FinishReason.STOP
                    if reason is not None:
                        sched.finish(s, reason)
                        break
        check_rows()
        if sched.running and rng.random() < 0.08:
            sched._preempt(rng.choice(sched.running))
        live = [s for pool in (sched.running, sched.prefilling, sched.waiting)
                for s in pool]
        if live and rng.random() < 0.06:
            cancelled.add(rng.choice(live).request_id)
    assert all(s.state == SeqState.FINISHED for s in seqs)
    assert plane.num_used == 0 and plane.num_free == plane.num_blocks - 1
    assert alloc.num_free == alloc.num_blocks - 1
    assert plane.released_total > 0 and worst >= 2
    assert sched.admit_blocked_window >= 0


def test_admission_counts_the_window_plane_and_waits_for_it():
    """Three long prompts, a window plane that holds two rows' bounds:
    the third waits (counted) until a row finishes, and nobody's window
    allocation fails meanwhile."""
    bs, window = 8, 12
    probe = WindowPlane(2, bs, window)
    chunk_bound = probe.span_pages(16)
    sched = two_plane_scheduler(window, bs, 1 + 2 * chunk_bound)
    a, b, c = (_seq(40, bs, 4, r) for r in "abc")
    for s in (a, b, c):
        sched.add_request(s)
    sched._admit()
    assert [s.state for s in (a, b, c)] == [
        SeqState.PREFILL, SeqState.PREFILL, SeqState.WAITING]
    assert sched.admit_blocked_window == 1
    assert sched.window_row_bound(a) == chunk_bound == 5
    works = sched._plan_prefill_batch()
    sched.build_prefill_batch_arrays(works)
    assert sched.window_plane.num_used == 2 * 2      # 16 tokens: 2 columns each
    for w in works:
        sched.complete_prefill_chunk(w)
    sched._admit()
    assert c.state == SeqState.WAITING               # still owed to a and b
    sched.prefilling.remove(a)
    sched.finish(a, FinishReason.CANCELLED)
    sched._admit()
    assert c.state == SeqState.PREFILL
    # a running row's bound is the window and the dispatches ahead, no more
    b.state = SeqState.RUNNING
    assert sched.window_row_bound(b) == probe.span_pages(1 + 3) == 3


def test_tables_carry_the_window_planes_columns_in_their_second_half():
    sched = two_plane_scheduler(12, 8, 40)
    seqs = [_seq(10, 8, 4, "a"), _seq(30, 8, 4, "b")]
    for s in seqs:
        sched.add_request(s)
    sched._admit()
    works = sched._plan_prefill_batch()
    tables = sched.build_prefill_batch_arrays(works)["block_tables"]
    assert tables.shape[1] == sched._table_width(4) == 16
    for i, w in enumerate(works):
        n = len(w.seq.block_table)
        assert tables[i, :n].tolist() == w.seq.block_table
        cols = (len(w.tokens) - 1) // 8 + 1
        assert tables[i, 8: 8 + cols].tolist() == w.seq.window_table[:cols]
        assert all(tables[i, 8: 8 + cols]) and not any(tables[i, 8 + cols:])
    assert (tables[len(works):] == 0).all()
    wide = sched.widen_tables(tables, 24)
    assert wide.shape[1] == 24
    assert (wide[:, :8] == tables[:, :8]).all() and (wide[:, 8:12] == 0).all()
    assert (wide[:, 12:20] == tables[:, 8:]).all() and (wide[:, 20:] == 0).all()
    # a model without the plane: tables as they always were
    plain = Scheduler(BlockAllocator(32, 8), 8, max_batch_size=4)
    assert plain.table_width_of(8) == 8 and plain._table_width(3) == 8
