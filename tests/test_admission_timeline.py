"""Admission's page reserve (Scheduler._growth_reserve): the peak of the
population's worst-case page timeline, not the sum of every row's
growth. The real Scheduler and BlockAllocator, stepped through ``plan``
/ ``complete_prefill_chunk`` / ``append_token`` with no model: whatever
the pool's size, no row is ever preempted and no allocation ever fails,
and the timeline never asks for more than the sum of every row's growth,
the candidate's included."""

import random

import numpy as np
import pytest

from dynamo_tpu.engine.allocator import BlockAllocator, NoBlocksError
from dynamo_tpu.engine.scheduler import Scheduler, Sequence, _TimelineRow
from dynamo_tpu.protocols.common import (
    FinishReason,
    PreprocessedRequest,
    StopConditions,
)
from dynamo_tpu.tokens import TokenBlockSequence


def _seq(tokens, bs, max_tokens, rid):
    return Sequence(
        request=PreprocessedRequest(
            request_id=rid, token_ids=list(tokens),
            stop=StopConditions(max_tokens=max_tokens),
        ),
        tokens=TokenBlockSequence(list(tokens), block_size=bs),
    )


class SumReserve(Scheduler):
    """The sum of every row's growth to its end, the candidate's
    included: the timeline's bound if no row ever gave a page back."""

    def _growth_reserve(self, rows, newly_shared):
        total = super()._growth_reserve(rows, 0)[1]
        return total, total


class SumUntilPR41(Scheduler):
    """Admission's arithmetic as it stood until PR 41: the free pool
    must hold the candidate's PROMPT plus every admitted row's growth —
    the candidate's own growth is reserved only from the next admission
    on, so in a crowded pool the newest row can still run out."""

    def _growth_reserve(self, rows, newly_shared):
        bs = self.block_size
        grow = sum(max(0, -(-r.end // bs) - r.held) for r in rows[:-1])
        return rows[-1].alone + grow, super()._growth_reserve(rows, 0)[1]


class Loop:
    """Steps a scheduler the way the engine does, with no device: a
    prefill step computes its chunks (the last one samples the row's
    first token), a decode step gives every row its window — or, under
    speculation, stages a draft run and keeps 1 to k + 1 tokens of it.
    ``stop_at`` ends a row early (EOS) at that many generated tokens."""

    def __init__(self, sched, seed=0, stop_at=None):
        self.sched = sched
        self.rng = random.Random(seed)
        self.stop_at = stop_at or {}
        self.pace = {}  # speculation: tokens a row keeps of every step
        self.failed_allocations = 0
        self.finished = []
        self.rows_by_step = []
        self.peak_pages = 0
        alloc = sched.allocator
        for name in ("allocate_block", "allocate_prefix"):
            setattr(alloc, name, self._counting(getattr(alloc, name)))
        checked = sched._growth_reserve

        def never_above_the_sum(rows, newly_shared):
            peak, total = checked(rows, newly_shared)
            assert 0 <= peak <= total
            return peak, total

        sched._growth_reserve = never_above_the_sum

    def _counting(self, fn):
        def call(*a):
            try:
                return fn(*a)
            except NoBlocksError:
                self.failed_allocations += 1
                raise
        return call

    def _advance(self, seq, n):
        sched = self.sched
        for _ in range(n):
            sched.append_token(seq, 7)
            reason = sched.should_finish(seq)
            if reason is None and seq.generated >= self.stop_at.get(
                seq.request_id, 1 << 30
            ):
                reason = FinishReason.STOP
            if reason is not None:
                sched.finish(seq, reason)
                self.finished.append(seq)
                return

    def step(self):
        sched = self.sched
        plan = sched.plan()
        for w in plan.prefill_batch:
            sched.complete_prefill_chunk(w)
            if w.is_last_chunk:
                self._advance(w.seq, 1)
        for seq in plan.decode_seqs:
            if sched.spec_tokens:
                k = sched.spec_tokens
                if seq.max_new_tokens is not None:  # engine._spec_budget
                    k = min(k, max(0, seq.max_new_tokens - seq.generated - 1))
                k = sched.reserve_spec_tokens(seq, [9] * k)
                seq.tokens.unwind(k)
                kept = self.pace.get(seq.request_id) or self.rng.randint(1, 4)
                self._advance(seq, min(kept, k + 1))
            else:
                self._advance(seq, sched._seq_lookahead(seq))
        if plan.decode_seqs:
            self.rows_by_step.append(len(plan.decode_seqs))
        used = sched.allocator.num_blocks - 1 - sched.allocator.num_free
        self.peak_pages = max(self.peak_pages, used)
        return plan

    def drain(self, limit=20000):
        steps = 0
        while self.sched.has_work:
            self.step()
            steps += 1
            assert steps < limit, "the population never drained"
        return steps

    def assert_never_short(self):
        assert self.sched.preemptions == 0
        assert self.failed_allocations == 0
        assert self.sched.allocator.num_free == self.sched.allocator.num_blocks - 1


def _sched(cls, pages, bs, lookahead=1, depth=2, **kw):
    sched = cls(BlockAllocator(pages + 1, bs), bs, **kw)
    sched.decode_lookahead = lookahead
    sched.dispatches_ahead = depth + 1  # as JaxEngine sets it
    return sched


# ---------------------------------------------------------------------------
# property: random populations, every row to its end
# ---------------------------------------------------------------------------


def _population(seed, bs, lookahead):
    """Requests as (arrival step, sequence) and the early stops: prompts
    that cross a 32-token prefill chunk, groups sharing a prefix of whole
    pages, rows without ``max_tokens`` (they stop within the one decode
    window that is reserved for them), rows that stop before their
    ``max_tokens``."""
    rng = random.Random(seed)
    prefixes = [
        [rng.randrange(1, 999) for _ in range(bs * rng.randint(1, 4))]
        for _ in range(3)
    ]
    arrivals, stop_at = [], {}
    for i in range(40):
        rid = f"r{i}"
        body = [rng.randrange(1000, 9999) for _ in range(rng.randint(3, 70))]
        prompt = (rng.choice(prefixes) if rng.random() < 0.5 else []) + body
        kind = rng.random()
        if kind < 0.15:
            budget = None
            stop_at[rid] = rng.randint(1, lookahead)
        else:
            budget = rng.randint(1, 90)
            if kind < 0.35:
                stop_at[rid] = rng.randint(1, budget)
        arrivals.append((rng.randint(0, 120), _seq(prompt, bs, budget, rid)))
    return sorted(arrivals, key=lambda a: a[0]), stop_at


def _run_population(
    cls, seed, pages, lookahead, spec_tokens=0, max_model_len=None
):
    bs = 8
    sched = _sched(
        cls, pages, bs, lookahead, max_batch_size=16, prefill_chunk_size=32,
        max_model_len=max_model_len,
    )
    sched.spec_tokens = spec_tokens
    arrivals, stop_at = _population(seed, bs, lookahead)
    loop = Loop(sched, seed, stop_at)
    steps = 0
    while arrivals or sched.has_work:
        while arrivals and arrivals[0][0] <= steps:
            sched.add_request(arrivals.pop(0)[1])
        loop.step()
        steps += 1
        assert steps < 20000
    assert len(loop.finished) == 40
    return loop, steps


@pytest.mark.parametrize("lookahead", [1, 4])
@pytest.mark.parametrize("seed", range(6))
def test_no_row_is_ever_short_of_pages(seed, lookahead):
    """At a pool the population crowds, every row runs to its end (or
    stops before it) with no preemption and no failed allocation — and
    with more rows a step, in fewer steps and with fewer stops at the
    reserve than under the sum."""
    pages = 48
    loop, steps = _run_population(Scheduler, seed, pages, lookahead)
    loop.assert_never_short()
    assert loop.peak_pages <= pages
    old, old_steps = _run_population(SumReserve, seed, pages, lookahead)
    old.assert_never_short()
    assert steps < old_steps
    assert np.mean(loop.rows_by_step) > np.mean(old.rows_by_step)
    assert (
        0 < loop.sched.admit_blocked_reserve < old.sched.admit_blocked_reserve
    )


def test_the_candidates_own_growth_is_part_of_its_check():
    """Until PR 41 a candidate's growth was reserved only from the NEXT
    admission on: in the same crowded pools that arithmetic runs the
    newest row out of pages (a preemption: its whole prompt prefilled
    again). The timeline counts the candidate in, and never does."""
    short = 0
    for seed in range(6):
        old, _ = _run_population(SumUntilPR41, seed, 32, 1)
        short += old.sched.preemptions
        new, _ = _run_population(Scheduler, seed, 32, 1)
        new.assert_never_short()
    assert short > 0


@pytest.mark.parametrize("pages", [30, 40, 56, 80])
def test_no_row_is_ever_short_of_pages_under_speculation(pages):
    """(d) rows advance by 1 to ``spec_tokens + 1`` tokens a step, each
    at its own pace: the rate-bounded timeline still holds, at every
    pool size."""
    loop, _ = _run_population(Scheduler, 11, pages, 1, spec_tokens=3)
    loop.assert_never_short()


@pytest.mark.parametrize("lookahead, spec_tokens", [(1, 0), (4, 0), (1, 3)])
def test_no_row_is_ever_short_of_pages_under_a_max_model_len(
    lookahead, spec_tokens
):
    """A row's stated end is ``max_tokens`` or ``max_model_len``,
    whichever comes first (prompts here reach 102 tokens, answers 90:
    every second row is cut at 120). The planners clamp a row's last
    window to the first and not to the second, and the timeline leaves
    that window's pages to the row."""
    for seed in range(6):
        # at 30 pages a timeline that cut such a row's pages at
        # max_model_len itself comes short (seeds 2, 3, 5)
        loop, _ = _run_population(
            Scheduler, seed, 30, lookahead, spec_tokens, max_model_len=120
        )
        loop.assert_never_short()
        assert any(s.total_len >= 120 for s in loop.finished)


@pytest.mark.parametrize("lookahead", [1, 4])
def test_a_rows_end_is_capped_at_max_model_len(lookahead):
    """``max_tokens`` far past ``max_model_len`` reserves to
    ``max_model_len`` (and the last window's surplus), so the row is
    admitted beside another where ``max_tokens`` alone would not fit."""
    sched = _sched(
        Scheduler, 14, BS, lookahead, max_batch_size=8, max_model_len=100
    )
    loop = Loop(sched)
    _prefill(loop, _seq(_ids(1000, 40), BS, 10000, "a"))
    (row,) = sched._timeline_rows()
    assert (row.length, row.left, row.end) == (41, 59, 99 + lookahead)
    sched.add_request(_seq(_ids(2000, 40), BS, 10000, "b"))
    loop.step()
    assert not sched.waiting  # two rows of 7 pages each at their ends
    loop.drain()
    loop.assert_never_short()
    assert [s.total_len for s in loop.finished] == [100, 100]


# ---------------------------------------------------------------------------
# (a)-(d): three rows with staggered ends, each case where a plainer
# timeline would be wrong
# ---------------------------------------------------------------------------

BS = 16


def _prefill(loop, *seqs):
    """Each of ``seqs`` admitted and prefilled, one after another (so a
    later one finds the earlier one's pages in the prefix cache)."""
    for s in seqs:
        loop.sched.add_request(s)
        while loop.sched.waiting or loop.sched.prefilling:
            loop.step()


def _ids(start, n):
    return list(range(start, start + n))


def _staggered(loop, shift, a_prompt=None, b_prompt=None):
    """A ends after 20 tokens, B after 240; the candidate C, 150 tokens
    of answer, ends while B is two thirds of its way: the sum reserves
    all three ends at once, the timeline B's length at C's end.
    ``shift`` moves B against the page boundaries."""
    a = _seq(a_prompt or _ids(1000, 40), BS, 20, "a")
    b = _seq(b_prompt or _ids(2000, 50 + shift), BS, 240, "b")
    _prefill(loop, a, b)
    assert {"a", "b"} <= {r.request_id for r in loop.sched.running}
    return _seq(_ids(3000, 30), BS, 150, "c")


def _shared_prefix(loop, shift):
    """(a) A and B share four prompt pages: A's finish gives back only
    the pages that are its own."""
    system = _ids(1, 4 * BS)
    cand = _staggered(
        loop, shift, system + _ids(1000, 5), system + _ids(2000, 10 + shift)
    )
    b = next(r for r in loop.sched.running if r.request_id == "b")
    assert b.num_cached_prompt == 4 * BS
    assert loop.sched.allocator.held_alone(b.block_table) == len(b.block_table) - 4
    return cand


def _no_stated_end(loop, shift):
    """(b) U has no ``max_tokens`` and stops inside its first window."""
    u = _seq(_ids(4000, 15), BS, None, "u")
    loop.stop_at["u"] = 1 + loop.sched.decode_lookahead
    loop.sched.add_request(u)
    return _staggered(loop, shift)


def _chunked_candidate(loop, shift):
    """(c) mixed batching: C's 40-token prompt prefills in three
    16-token chunks, one a window, while A and B decode between them."""
    loop.sched.mixed_prefill_rows = 2
    loop.sched.mixed_prefill_len = 16
    cand = _staggered(loop, shift)
    return _seq(_ids(3000, 40), BS, 150, "c")


def _own_pace(loop, shift):
    """(d) speculation: B keeps 2 tokens of every step and C 1, so B is
    near its end — and still alive — when C reaches its own."""
    loop.sched.spec_tokens = 3
    loop.pace = {"b": 2, "c": 1}
    return _staggered(loop, shift)


def _during_reserve(sched, **attrs):
    """``_growth_reserve`` with these attributes of the scheduler set
    otherwise while it runs: a timeline blind to one of its terms."""
    inner = sched._growth_reserve

    def blind(rows, newly_shared):
        saved = {k: getattr(sched, k) for k in attrs}
        for k, v in attrs.items():
            setattr(sched, k, v)
        try:
            return inner(rows, newly_shared)
        finally:
            for k, v in saved.items():
                setattr(sched, k, v)

    sched._growth_reserve = blind


def _blind_to_sharing(sched):
    alloc = sched.allocator
    pinned_prefix = alloc.pinned_prefix
    alloc.held_alone = len
    alloc.pinned_prefix = lambda hashes: (pinned_prefix(hashes)[0], 0)


# name: (the running rows and the candidate, decode_lookahead, what makes
# the timeline blind to the case's term)
CASES = {
    "a_shared_prefix_pages_are_not_given_back": (
        _shared_prefix, 1, _blind_to_sharing,
    ),
    "b_no_max_tokens_reserves_one_window": (_no_stated_end, 2, None),
    "c_slack_covers_the_window_lookahead": (
        _staggered, 4,
        lambda sched: _during_reserve(sched, dispatches_ahead=0),
    ),
    "c_slack_covers_rows_trailing_in_chunked_prefill": (
        _chunked_candidate, 4,
        lambda sched: _during_reserve(sched, mixed_prefill_rows=0),
    ),
    "d_rows_at_their_own_pace_under_speculation": (
        _own_pace, 1, lambda sched: _during_reserve(sched, spec_tokens=0),
    ),
}
BLIND = [name for name, case in CASES.items() if case[2] is not None]
POOLS = range(23, 44)
SHIFTS = (0, 4, 8, 12)


def _run_case(cls, name, pages, shift=0, blind=False):
    build, lookahead, blinder = CASES[name]
    sched = _sched(
        cls, pages, BS, lookahead, max_batch_size=8, prefill_chunk_size=64
    )
    loop = Loop(sched, shift)
    cand = build(loop, shift)
    if blind:
        blinder(sched)
    sched.add_request(cand)
    loop.step()
    admitted_at_once = not sched.waiting
    loop.drain()
    assert {s.request_id for s in loop.finished} >= {"a", "b", "c"}
    return loop, admitted_at_once


@pytest.mark.parametrize("name", CASES)
def test_reserve_holds_at_every_pool_size(name):
    """From a pool that holds little more than the longest row up to one
    that holds all three at their ends: never a preemption, never a
    failed allocation; the candidate goes in at once in a smaller pool
    than the sum asked for, and in every larger one."""
    at_once, old_at_once = [], []
    for pages in POOLS:
        for shift in SHIFTS:
            loop, now = _run_case(Scheduler, name, pages, shift)
            loop.assert_never_short()
        if now:
            at_once.append(pages)
        if _run_case(SumReserve, name, pages, shift)[1]:
            old_at_once.append(pages)
    assert at_once and old_at_once
    assert at_once[0] < old_at_once[0]
    assert at_once == list(range(at_once[0], POOLS[-1] + 1))


@pytest.mark.parametrize("name", BLIND)
def test_reserve_without_the_cases_term_comes_short(name):
    """The same rows under a timeline blind to the case's term: in some
    pool it admits the candidate where the whole bound makes it wait,
    and a row then runs out of pages — the case exercises its term."""
    short = []
    for pages in POOLS:
        for shift in SHIFTS:
            loop, _ = _run_case(Scheduler, name, pages, shift, blind=True)
            if loop.sched.preemptions or loop.failed_allocations:
                short.append((pages, shift))
    assert short, "the blind timeline never came short"


def test_a_row_without_max_tokens_reserves_one_window_as_the_sum_did():
    """(b) such a row never finishes on the timeline: whatever the other
    rows' ends, it is charged the pages of one ``decode_lookahead``
    beyond its length — what the sum charged it."""
    sched = _sched(Scheduler, 64, BS, 2, max_batch_size=8)
    loop = Loop(sched)
    u = _seq(_ids(4000, 15), BS, None, "u")
    _prefill(loop, u)  # 16 tokens, one page: the window needs a second
    b = _seq(_ids(2000, 50), BS, 240, "b")
    _prefill(loop, b)
    rows = sched._timeline_rows()
    assert rows[0] == (16, sched._NEVER, 18, 1, 1, 0)
    cand = sched._timeline_row(_seq(_ids(3000, 30), BS, 150, "c"), 2, 2)
    with_u = sched._growth_reserve(rows + [cand], 0)
    without = sched._growth_reserve(rows[1:] + [cand], 0)
    # ``now`` holds u's page in the first and not in the second
    assert with_u[0] == without[0] + 1 and with_u[1] == without[1] + 1


@pytest.mark.parametrize("depth", [2, 3])
def test_pipelined_planner_lag_is_inside_the_slack(depth):
    """(c) the decode pipeline plans a step with up to ``depth - 1`` in
    flight (``DYN_PIPELINE_DEPTH``; the engine hands the scheduler its
    depth) and allocates ``total_len + lag + 1``: at the tightest pool
    that admits the third row, that planner never runs out either."""
    for pages in POOLS:
        sched = _sched(
            Scheduler, pages, BS, depth=depth, max_batch_size=8,
            prefill_chunk_size=64,
        )
        loop = Loop(sched)
        sched.add_request(_staggered(loop, pages % BS))
        while sched.has_work:
            plan = loop.step()
            if plan.kind != "decode":
                continue
            for ahead in range(1, depth):
                rows = list(sched.running)
                lag = {id(s): ahead for s in rows}
                nxt, _ = sched.plan_pipelined_decode(rows, lag)
                assert nxt is not None or all(
                    s.max_new_tokens - s.generated <= ahead for s in rows
                )
        loop.assert_never_short()


def test_a_bare_scheduler_assumes_the_engines_default_depth():
    """The engine hands the scheduler its own ``PIPELINE_DEPTH``
    (tests/test_spec.py reads it off a launched engine); a scheduler
    nobody configured assumes the default one."""
    from dynamo_tpu.engine.engine import JaxEngine

    assert Scheduler(BlockAllocator(4, BS), BS).dispatches_ahead == (
        JaxEngine.PIPELINE_DEPTH + 1
    )


# ---------------------------------------------------------------------------
# the numpy pass against the plain loop over EVERY instant
# ---------------------------------------------------------------------------


def _reserve_by_loop(sched, rows, newly_shared):
    """``_growth_reserve`` as a loop over every future instant, not only
    those just before a finish, in Python integers."""
    bs = sched.block_size
    rate = sched.spec_tokens + 1
    slack = sched.dispatches_ahead * max(sched.decode_lookahead, rate)
    if sched.mixed_prefill_rows > 0:
        slack += sched.decode_lookahead * sum(
            -(-r.unprefilled // sched.mixed_prefill_len) for r in rows
        )

    def own(row, tokens):
        return max(row.alone, -(-tokens // bs) - (row.held - row.alone))

    now = sum(r.alone for r in rows[:-1])
    total = sum(own(r, r.end) for r in rows) - now
    last = max([r.left for r in rows if r.left != sched._NEVER], default=1)
    peak = max(
        sum(own(r, min(r.end, r.length + rate * t + slack))
            for r in rows if r.left > t)
        for t in range(last)
    )
    return min(peak + newly_shared - now, total), total


@pytest.mark.parametrize("lookahead, spec_tokens, mixed_rows", [
    (1, 0, 0), (4, 0, 0), (4, 0, 2), (1, 3, 0),
], ids=["plain", "windows", "mixed", "speculation"])
def test_vectorised_reserve_equals_the_plain_loop(
    lookahead, spec_tokens, mixed_rows
):
    bs = 16
    sched = _sched(Scheduler, 64, bs, lookahead)
    sched.spec_tokens = spec_tokens
    sched.mixed_prefill_rows, sched.mixed_prefill_len = mixed_rows, 32
    rng = random.Random(lookahead * 100 + spec_tokens)
    for _ in range(200):
        rows = []
        for _ in range(rng.randint(2, 12)):
            length = rng.randint(1, 300)
            held = -(-length // bs) + rng.randint(0, 1)
            alone = rng.randint(0, held)
            unprefilled = rng.choice([0, 0, rng.randint(1, length)])
            if rng.random() < 0.15:
                rows.append(_TimelineRow(
                    length, sched._NEVER, length + lookahead, held, alone,
                    unprefilled,
                ))
            else:
                left = rng.randint(1, 200)
                rows.append(_TimelineRow(
                    length, left, length + left, held, alone, unprefilled
                ))
        shared = rng.randint(0, 3)
        assert sched._growth_reserve(rows, shared) == _reserve_by_loop(
            sched, rows, shared
        )


# ---------------------------------------------------------------------------
# read with steps in flight (the decode pipeline's in-line admission)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("depth", [2, 3])
def test_reserve_read_with_steps_in_flight_never_asks_less(depth):
    """``_admit`` inside the decode pipeline sees rows up to ``depth - 1``
    tokens behind the pages they hold (sampled, not yet applied), beside
    a candidate it is level with. What it then keeps free is never less
    than what the same population needs once those tokens are applied
    and nothing is in flight but the step being planned (one dispatch
    ahead) — ``slack`` is what covers the lag — and at most a page a row
    more."""
    bs = 16
    piped = _sched(Scheduler, 64, bs, depth=depth)
    level = _sched(Scheduler, 64, bs, depth=0)  # dispatches_ahead == 1
    rng = random.Random(depth)
    over = []
    for _ in range(1500):
        applied, seen = [], []
        for _ in range(rng.randint(1, 12)):
            lag = rng.randint(0, depth - 1)
            left = rng.randint(lag + 1, 200)
            length = rng.randint(lag + 1, 300)
            # the planner took the pages of its in-flight tokens' slots
            held = -(-(length + 1) // bs) + rng.randint(0, 1)
            alone = rng.randint(0, held)
            applied.append(
                _TimelineRow(length, left, length + left, held, alone, 0))
            seen.append(_TimelineRow(
                length - lag, left + lag, length + left, held, alone, 0))
        prompt, answer = rng.randint(1, 300), rng.randint(1, 200)
        pages = -(-prompt // bs)
        cand = _TimelineRow(
            prompt, answer, prompt + answer, pages, rng.randint(0, pages),
            prompt)
        shared = rng.randint(0, 2)
        asked = piped._growth_reserve(seen + [cand], shared)
        needed = level._growth_reserve(applied + [cand], shared)
        assert asked[0] >= needed[0] and asked[1] == needed[1]
        over.append(asked[0] - needed[0])
        assert over[-1] <= len(seen) + 1
    assert max(over) > 0  # the bound is a bound, not an identity


# ---------------------------------------------------------------------------
# the benchmark's closed loop (perf/traffic/decode-heavy.json)
# ---------------------------------------------------------------------------


def _closed_loop(cls, seed, clients=48, pages=455, steps=6000):
    bs = 128
    rng = random.Random(seed)
    sched = _sched(
        cls, pages, bs, max_batch_size=64, prefill_chunk_size=1024
    )
    loop = Loop(sched)
    made = [0]

    def request(phase=1.0):
        made[0] += 1
        prompt = [rng.randrange(1, 30000) for _ in range(rng.randint(128, 512))]
        answer = max(16, int(rng.randint(768, 1280) * phase))
        sched.add_request(_seq(prompt, bs, answer, f"r{made[0]}"))

    for c in range(clients):
        request((c + 1) / clients)  # first answers phased, as the mix does
    for _ in range(steps):
        done = len(loop.finished)
        loop.step()
        for _ in range(len(loop.finished) - done):
            request()
    return loop


@pytest.mark.parametrize("seed", [20240925, 7])
def test_decode_heavy_runs_all_of_its_clients(seed):
    """48 closed-loop clients, prompts 128-512, answers 768-1 280, 455
    pages of 128 tokens: the sum held the population near 41 (PERF.md
    section 4); the timeline runs all 48, with pages to spare and no
    preemption."""
    loop = _closed_loop(Scheduler, seed)
    rows = loop.rows_by_step[1000:]
    assert min(rows) >= 47 and np.mean(rows) > 47.9
    assert loop.sched.preemptions == 0 and loop.failed_allocations == 0
    assert 340 <= loop.peak_pages <= 455
    assert loop.sched.admit_blocked_reserve == 0
    s = loop.sched
    assert 0 < s.admit_reserve_peak_pages < s.admit_reserve_sum_pages / 4
    old = _closed_loop(SumUntilPR41, seed)
    assert 39 < np.mean(old.rows_by_step[1000:]) < 43
    assert old.sched.admit_blocked_reserve > 0
