"""Continuous-batching scheduler: admission, chunked prefill, decode batches.

The reference relies on vLLM's scheduler for this (reference: SURVEY.md §1
L3); here it is native and shaped for XLA's compilation model:

- every device step has **bucketed static shapes** (batch, chunk length,
  block-table width are rounded up to a small set of sizes) so the jitted
  step function compiles a handful of variants and then never recompiles;
- prefill is **chunked** (prefill_chunk_size) so long prompts can't starve
  decode; one prefill chunk or one decode batch per engine step;
- admission is capacity-checked against the block allocator — a prompt
  goes in only if the pool holds the population at the worst instant of
  its future (_growth_reserve) — with vLLM-style recompute preemption
  behind it: if decode still can't grow a sequence, the youngest
  sequence is rolled back to the waiting queue and its blocks freed.

Pure host-side logic — fully unit-testable without a device.
"""

from __future__ import annotations

import enum
import logging
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Callable, Container, NamedTuple, Optional

import numpy as np

from dynamo_tpu.engine.allocator import (
    BlockAllocator,
    NoBlocksError,
    StateSlots,
    WindowPlane,
)
from dynamo_tpu.protocols.common import FinishReason, PreprocessedRequest
from dynamo_tpu.telemetry import autopsy
from dynamo_tpu.telemetry.instruments import (
    DEADLINE_EXPIRED,
    ENGINE_PREEMPTIONS,
    ENGINE_QUEUE_WAIT,
)
from dynamo_tpu.tokens import TokenBlockSequence

log = logging.getLogger("dynamo_tpu.engine.scheduler")


from dynamo_tpu.utils.bucketing import next_bucket  # noqa: F401 (re-export)


class SeqState(str, enum.Enum):
    WAITING = "waiting"
    PREFILL = "prefill"
    RUNNING = "running"
    FINISHED = "finished"


@dataclass
class Sequence:
    request: PreprocessedRequest
    tokens: TokenBlockSequence
    state: SeqState = SeqState.WAITING
    block_table: list[int] = field(default_factory=list)
    # the row's pages of the window plane by absolute column, 0 where
    # released or not yet held (allocator.WindowPlane; models whose
    # window layers free behind the window)
    window_table: list[int] = field(default_factory=list)
    # of them, how many its prefill chunks handed back (engine.prefill span)
    window_pages_released: int = 0
    # slot of the state plane while admitted (0 = none; recurrent models)
    state_slot: int = 0
    num_computed: int = 0  # tokens whose KV is in cache
    num_cached_prompt: int = 0  # prefix-cache hit length (tokens)
    committed_blocks: int = 0  # prefix of block_table already content-addressed
    generated: int = 0
    arrival: int = 0
    # engine-facing hooks
    emit: Optional[Callable] = None  # called with LLMEngineOutput-shaped dicts
    is_cancelled: Optional[Callable[[], bool]] = None
    finish_reason: Optional[FinishReason] = None
    # request deadline (monotonic instant; 0.0 = none): expired
    # sequences are reaped in plan() — queue, prefill, and decode alike
    # — so their KV blocks free instead of burning further steps
    deadline: float = 0.0
    # multimodal: [(token offset, embeds[n, D])] to inject during prefill
    mm_segments: list = field(default_factory=list)
    # generated-token counts for frequency/presence/repetition penalties
    # (only maintained when the request's sampling options need them)
    gen_counts: dict = field(default_factory=dict)
    # cached distinct prompt ids for the repetition penalty (immutable;
    # computed once — np.unique over a long prompt must not sit on the
    # per-step host path)
    prompt_unique: Optional[Any] = None
    # per-sequence drafter state (spec/drafter.py NgramIndex): the
    # engine keeps the incremental n-gram index here so the per-step
    # proposal is a hashed lookup instead of an O(window) re-scan;
    # rebuilt whenever the sequence shrinks (unwind/truncation)
    drafter_state: Optional[Any] = None
    # per-sequence guided-decoding cursor (guided/automaton.GuidedState,
    # docs/guided_decoding.md): advanced in append_token as tokens
    # COMMIT — staged speculative drafts are unwound before verified
    # tokens re-append, so the automaton only ever sees committed tokens
    guided_state: Optional[Any] = None
    # request-lifecycle stamps (telemetry), all monotonic; the engine
    # emits queue-wait/prefill/decode spans from these at finish time
    # (engine.py _emit_finish)
    t_submit: float = 0.0  # engine.submit() (monotonic)
    t_admit: float = 0.0  # first admission into prefilling
    t_prefill_done: float = 0.0  # last prompt chunk computed
    t_first_token: float = 0.0  # first generated token appended (TTFT)
    waiting_at_intake: int = 0  # requests queued ahead when this one arrived
    prefill_chunks: int = 0  # prefill programs this prompt rode
    # propagated trace context ({"trace_id", "span_id"}) or None
    trace: Optional[dict] = None
    # the CALLER's request id (Context.id — the frontend's autopsy key),
    # distinct from request.request_id (the preprocessor's cmpl-… id):
    # engine-side autopsy segments/events must key on this or the
    # endpoint server's take_pending(ctx.id) never finds them
    autopsy_rid: str = ""
    # SLO + autopsy finalization must run BEFORE the last token item is
    # emitted (consumers abandon the stream at max_tokens, ahead of the
    # finish-marked item) — this guard keeps the early call and the
    # on_finish hook from double-counting
    observability_done: bool = False

    @property
    def request_id(self) -> str:
        return self.request.request_id

    @property
    def total_len(self) -> int:
        return len(self.tokens)

    @property
    def max_new_tokens(self) -> Optional[int]:
        return self.request.stop.max_tokens

    def blocks_needed(self, for_len: int, block_size: int) -> int:
        return (for_len + block_size - 1) // block_size


@dataclass
class PrefillWork:
    """One chunk of prompt to run this step."""

    seq: Sequence
    tokens: np.ndarray  # [t] token ids for this chunk
    start_pos: int  # absolute position of tokens[0]
    is_last_chunk: bool


@dataclass
class StepPlan:
    """What the engine should run this step.

    kind "mixed" carries BOTH a bounded prefill batch and the decode
    batch: the engine fuses them into one dispatch (prefill rectangle +
    K-step decode window) so a straggler's prefill no longer costs a
    dedicated full-weight pass while decode stalls — the serving-layer
    half of continuous batching (reference: vLLM's mixed scheduler,
    container/deps/vllm/...-patch :535, docs/architecture.md:55-68).
    """

    kind: str  # "prefill" | "decode" | "mixed" | "idle"
    prefill_batch: list[PrefillWork] = field(default_factory=list)
    decode_seqs: list[Sequence] = field(default_factory=list)
    # mixed plans: the [rows, len] prefill rectangle this window was
    # planned against (narrow or wide — engine pads to exactly this)
    rect: Optional[tuple[int, int]] = None

    @property
    def prefill(self) -> Optional[PrefillWork]:
        """First prefill work item (derived — cannot drift from the batch)."""
        return self.prefill_batch[0] if self.prefill_batch else None


def prefill_rectangles(
    rows: list[int], tokens: list[int], budget: int, chunk_size: int,
    single_row_tokens: tuple[int, ...] = (),
) -> list[tuple[int, int]]:
    """The ``(rows, tokens)`` prefill rectangles a step can name, sorted:
    of the two ladders' product those no longer than the length that
    holds ``chunk_size`` (no chunk is longer), at the smallest row count
    whatever the area (a lone chunk must run) and at the others where
    ``rows x tokens`` fits ``budget`` (the planner grows a batch no
    further) — and ``single_row_tokens`` at the smallest row count
    alone. Each is a whole-model program for whoever warms the set, and
    nobody warms a shape outside it."""
    top = next_bucket(chunk_size, tokens)
    rects = {
        (r, t) for t in tokens for r in rows
        if r == rows[0] or r * t <= budget
    } | {(rows[0], t) for t in single_row_tokens}
    return sorted(rt for rt in rects if rt[1] <= top)


def mixed_rect_of(
    rects: list[tuple[int, int]], rows: int, length: int, cap: int
) -> Optional[tuple[int, int]]:
    """The mixed window's fixed rectangle for a requested ``rows`` x
    ``length``: a member of ``rects`` — the row count that holds
    ``rows`` (else the largest), AT it the length that holds ``length``
    (else its longest), then a shorter length while one alone passes
    ``cap`` and fewer rows of that length while the area does. None
    where nothing fits."""
    counts = sorted({r for r, _ in rects})
    rows = next((r for r in counts if r >= rows), counts[-1])
    lens = sorted(t for r, t in rects if r == rows)
    length = next((t for t in lens if t >= length), lens[-1])
    while length > cap and length > lens[0]:
        length = lens[lens.index(length) - 1]
    fewer = [r for r in counts if r <= rows and (r, length) in rects]
    while rows * length > cap and rows > fewer[0]:
        rows = fewer[fewer.index(rows) - 1]
    return (rows, length) if rows * length <= cap else None


def _area(rect: Optional[tuple[int, int]]) -> int:
    return rect[0] * rect[1] if rect else 0


def _cancelled(seq: "Sequence") -> bool:
    return bool(seq.is_cancelled and seq.is_cancelled())


def _expired(seq: "Sequence", now: float) -> bool:
    return bool(seq.deadline) and now >= seq.deadline


def seq_gone(seq: "Sequence", now: float) -> bool:
    """Cancelled, or past its deadline at ``now`` (monotonic)."""
    return _cancelled(seq) or _expired(seq, now)


class _TimelineRow(NamedTuple):
    """A row of the page timeline (Scheduler._growth_reserve)."""

    length: int  # tokens it has
    left: int  # tokens it may still generate (_NEVER: no stated end)
    end: int  # the length its pages can reach
    held: int  # pages it holds
    alone: int  # of those, pages nobody else pins
    unprefilled: int  # prompt tokens still to prefill


def _ladder_to(buckets: list[int], top: int) -> list[int]:
    """``buckets``, doubled on past its end until it holds ``top``."""
    out = list(buckets)
    while out[-1] < top:
        out.append(out[-1] * 2)
    return out


class Scheduler:
    def __init__(
        self,
        allocator: BlockAllocator,
        block_size: int,
        max_batch_size: int = 64,
        prefill_chunk_size: int = 1024,
        max_model_len: Optional[int] = None,
        max_prefill_tokens: Optional[int] = None,
    ):
        self.allocator = allocator
        self.block_size = block_size
        self.max_batch_size = max_batch_size
        self.prefill_chunk_size = prefill_chunk_size
        self.max_model_len = max_model_len
        # total token budget for one BATCHED prefill step (several
        # sequences' chunks fused into one dispatch); per-seq chunks
        # still cap at prefill_chunk_size
        self.max_prefill_tokens = max_prefill_tokens or prefill_chunk_size
        self.waiting: deque[Sequence] = deque()
        self.prefilling: deque[Sequence] = deque()
        self.running: list[Sequence] = []
        # fused multi-step decode: how many tokens one device step emits
        # (engine sets this from EngineConfig.decode_steps); block
        # allocation must cover the whole window up front
        self.decode_lookahead = 1
        # speculative decoding: the longest draft run a verify step
        # checks (engine sets it from EngineConfig.spec_tokens when a
        # drafter is configured) — a row then advances by 1 to
        # spec_tokens + 1 tokens a step (_growth_reserve's rate)
        self.spec_tokens = 0
        # dispatches whose pages a row may hold beyond its applied
        # length: those the engine's pipelines keep in flight and the
        # one being planned (engine sets it from its PIPELINE_DEPTH;
        # _growth_reserve's slack)
        self.dispatches_ahead = 3
        # mixed prefill+decode: when decode has work AND prefill chunks
        # are pending, emit a "mixed" plan whose prefill batch fits the
        # engine's fixed [mixed_prefill_rows, mixed_prefill_len]
        # rectangle (0 rows = mixed planning off)
        self.mixed_prefill_rows = 0
        self.mixed_prefill_len = 256
        # adaptive wide rectangle (engine sets these; 0 rows = off):
        # at low decode occupancy the mixed window swaps to
        # [wide_rows, wide_len] — same token budget, fewer rows — so a
        # long prompt stops trickling at mixed_prefill_len per window
        self.mixed_prefill_wide_rows = 0
        self.mixed_prefill_wide_len = 0
        self.mixed_wide_max_running: Optional[int] = None
        # static serving shapes (engine sets these): every jit variant
        # costs a whole-model compile at start-up, and
        # composition-dependent buckets compile MID-SERVE. Padding the
        # decode batch to one fixed size and the block-table width to
        # the max_model_len cap makes the decode/mixed dispatch ONE
        # compiled shape — padded rows are ctx=0 no-ops the Pallas
        # kernel skips, and decode is weight-read-bound so the extra
        # rows are ~free. Coarse prefill buckets bound that path's
        # variant count too.
        self.decode_batch_pad: Optional[int] = None
        # optional SMALL decode bucket below the pad (e.g. 4): low
        # concurrency decodes in a lighter window at the cost of a few
        # extra prewarmed variants
        self.decode_batch_small: Optional[int] = None
        # optional MID bucket between small and pad (engine sets pad/2
        # for wide pads): a max_batch=64 engine otherwise pads a
        # 32-deep population to 64 rows (~11% measured at c=32)
        self.decode_batch_mid: Optional[int] = None
        self.table_width_pad: Optional[int] = None
        # THE set of prefill shapes, sorted: the planner grows a batch
        # only into a member, the builder pads to the smallest member
        # that covers the step, and every start-up loop compiles exactly
        # these (engine._prewarm). Here what the two class ladders'
        # product leaves reachable; a static-shape engine replaces it
        # with the few rectangles of its coarser ladders (engine.py).
        self.prefill_rects: list[tuple[int, int]] = prefill_rectangles(
            _ladder_to(self.BATCH_BUCKETS, max_batch_size),
            _ladder_to(self.CHUNK_BUCKETS, prefill_chunk_size),
            self.max_prefill_tokens, prefill_chunk_size,
        )
        self._arrival = 0
        # invoked on every finish (incl. cancellations reaped inside plan())
        self.on_finish: Optional[Callable[[Sequence, FinishReason], None]] = None
        # KVBM hook: (remaining_hashes, their_device_blocks) -> n onboarded
        # from host/disk tiers (dynamo_tpu/kvbm/manager.py onboard())
        self.onboard: Optional[Callable[[list[int], list[int]], int]] = None
        # prefix-cache stats (one query per admitted request)
        self.prefix_queries = 0
        self.prefix_hits = 0
        # the same at token level: prompt tokens admitted, and those of
        # them the cache already held (program_spans.json counts)
        self.prompt_tokens_admitted = 0
        self.prompt_tokens_cached = 0
        # recompute-preemption count (observability: healthy serving
        # should sit at ~0 — see _growth_reserve)
        self.preemptions = 0
        # what admission's page reserve did (cumulative; program_spans.json
        # counts): passes of _admit that stopped at it with rows to
        # spare, and over every check the pages it asked to be free —
        # the timeline's peak — beside the sum of every row's growth
        # (_growth_reserve)
        self.admit_blocked_reserve = 0
        self.admit_reserve_peak_pages = 0
        self.admit_reserve_sum_pages = 0
        # models with recurrent layers (engine sets it): a slot of the
        # state plane per admitted sequence, taken at admission and
        # given back at finish, abort and preemption. Every block table
        # then carries the row's slot as its LAST column.
        self.state_slots: Optional[StateSlots] = None
        # models whose window layers release their pages behind the
        # window (engine sets it): a second page plane, filled a column
        # at a time as a row's steps are built (_fill_table), released as
        # the row advances (_release_window), counted by admission
        # (_window_admits), freed whole at finish, abort and preemption.
        # Every block table then carries the row's window-plane columns
        # after its full-plane columns, as many again.
        self.window_plane: Optional[WindowPlane] = None
        # passes of _admit that stopped at the window plane's reserve
        self.admit_blocked_window = 0
        # the head of ``waiting`` that the last _admit could not place
        # (page reserve, max_batch_size, state slots, no blocks). It
        # stays unplaceable until something is freed, so finish() and
        # _preempt() forget it; a new head (the old one reaped, an
        # arrival into an empty queue, a preempted victim put first) is
        # simply not this one. Read by admission_work().
        self._blocked_head: Optional[Sequence] = None

    # -- intake -----------------------------------------------------------
    def add_request(self, seq: Sequence) -> None:
        seq.arrival = self._arrival
        self._arrival += 1
        seq.waiting_at_intake = len(self.waiting)
        self.waiting.append(seq)

    @property
    def num_waiting(self) -> int:
        return len(self.waiting) + len(self.prefilling)

    @property
    def num_running(self) -> int:
        return len(self.running)

    @property
    def has_work(self) -> bool:
        return bool(self.waiting or self.prefilling or self.running)

    def admission_work(self) -> bool:
        """Is there admission or prefill work a planner could do now?
        The overlapped decode pipeline (engine._decode_pipeline) asks
        before every dispatch and, on yes, admits and prefills IN LINE
        (``plan_pipelined_admission``); the spec pipeline, which never
        admits, drains back to plan() instead. Yes is not merely
        "somebody waits": _admit looks at waiting[0] only, and a head
        it could not place stays unplaceable until something is freed
        — a finish or a preemption, both of which forget it
        (``_blocked_head``), inside the pipeline or out of it; decode
        growth only takes pages. So a saturated server, whose queue
        only a finish can move, keeps pipelining; arrivals queue
        behind the blocked head and change nothing. A prefilling
        sequence counts as work even with its last chunk in flight
        (the in-line planner then finds nothing to plan and says so),
        and so does a cancelled or expired waiting request: either
        planner reaps it within a step."""
        if self.prefilling:
            return True
        if not self.waiting:
            return False
        if self.waiting[0] is not self._blocked_head:
            return True
        now = time.monotonic()
        return any(seq_gone(seq, now) for seq in self.waiting)

    # -- planning ---------------------------------------------------------
    def plan(self) -> StepPlan:
        self._reap_cancelled()
        self._admit()
        backlog = self._prefill_backlog() if self.prefilling else 0
        rows, rlen = self._mixed_rect(backlog=backlog)
        # a COHORT (more prompts than rectangle rows, whole backlog
        # fits one dedicated step) takes the dedicated step: trickling
        # it 'rows' per window staggers the population into waves that
        # decode at partial width for their whole lifetime, while one
        # dedicated dispatch costs the decoders ~a quarter-window
        # (measured at B=64/128-token prompts: 924 vs 1505+ tok/s)
        cohort = (
            len(self.prefilling) > rows
            and backlog <= self.max_prefill_tokens
        )
        if (
            self.prefilling
            and self.running
            and rows > 0
            and not cohort
            and backlog <= 2 * rows * rlen
            and (
                len(self.prefilling) <= rows
                or len(self.running) >= len(self.prefilling)
            )
        ):
            # mixed step: prefill rides the decode window's dispatch,
            # bounded to the chosen rectangle (narrow, or wide at low
            # decode occupancy — _mixed_rect). Large backlogs
            # (cold-start bursts, long prompts) and prefill-heavy
            # moments (a synchronized cohort with few decoders — the
            # rectangle would RAMP the batch 8 rows per window while
            # decode runs near-empty) fall through to the dedicated
            # batched-prefill step below.
            works = self._plan_prefill_batch(
                budget=rows * rlen,
                max_seqs=rows,
                max_chunk_len=rlen,
            )
            decode = self._plan_decode()
            if works and decode:
                return StepPlan(
                    kind="mixed", prefill_batch=works, decode_seqs=decode,
                    rect=(rows, rlen),
                )
            if works:
                return StepPlan(kind="prefill", prefill_batch=works)
            if decode:
                return StepPlan(kind="decode", decode_seqs=decode)
            return StepPlan(kind="idle")
        if self.prefilling:
            works = self._plan_prefill_batch()
            if works:
                return StepPlan(kind="prefill", prefill_batch=works)
        if self.running:
            return StepPlan(kind="decode", decode_seqs=self._plan_decode())
        return StepPlan(kind="idle")

    def _prefill_backlog(self) -> int:
        """TRUE pending prompt tokens across prefilling sequences — NOT
        chunk-capped: a single long prompt must trip the dedicated-
        prefill fallback rather than trickle through the mixed
        rectangle at mixed_prefill_len tokens per decode window."""
        return sum(
            max(1, s.total_len - s.num_computed) for s in self.prefilling
        )

    def _mixed_rect(
        self,
        n_running: Optional[int] = None,
        prefill_seqs: Optional[list[Sequence]] = None,
        backlog: Optional[int] = None,
    ) -> tuple[int, int]:
        """The mixed window's prefill rectangle for a given population
        (defaults: the scheduler's current one; plan_pipelined_mixed
        passes the NEXT window's): the wide [wide_rows, wide_len]
        variant when decode occupancy is low, few prompts are
        prefilling, and at least one needs more than a narrow chunk —
        a long prompt then prefills in backlog/wide_len windows instead
        of backlog/len, while decode keeps riding along (dedicated
        prefill instead starves it). Otherwise the narrow rectangle's extra rows
        graduate more stragglers per window."""
        if n_running is None:
            n_running = len(self.running)
        if prefill_seqs is None:
            prefill_seqs = self.prefilling
        if backlog is None:
            backlog = sum(
                max(1, s.total_len - s.num_computed) for s in prefill_seqs
            )
        if (
            self.mixed_prefill_wide_rows > 0
            and (
                self.mixed_wide_max_running is None
                or n_running <= self.mixed_wide_max_running
            )
            and len(prefill_seqs) <= self.mixed_prefill_wide_rows
            and backlog > self.mixed_prefill_len
        ):
            return self.mixed_prefill_wide_rows, self.mixed_prefill_wide_len
        return self.mixed_prefill_rows, self.mixed_prefill_len

    def _reap_cancelled(self, queued_only: bool = False) -> None:
        """Remove cancelled AND deadline-expired sequences from every
        pool. finish() frees their KV blocks, so an expired request
        costs nothing past the step that notices it. ``queued_only``:
        from ``waiting`` alone, whose sequences hold nothing a step in
        flight could write (plan_pipelined_admission)."""
        now = time.monotonic()
        pools = ((self.waiting, "queue"), (self.prefilling, "prefill"))
        for pool, stage in pools[:1] if queued_only else pools:
            for seq in list(pool):
                if _cancelled(seq):
                    pool.remove(seq)
                    self.finish(seq, FinishReason.CANCELLED)
                elif _expired(seq, now):
                    pool.remove(seq)
                    DEADLINE_EXPIRED.labels(stage).inc()
                    log.warning(
                        "request %s deadline expired in %s; cancelling",
                        seq.request_id, stage,
                    )
                    self.finish(seq, FinishReason.TIMEOUT)
        if queued_only:
            return
        for seq in list(self.running):
            if _cancelled(seq):
                self.running.remove(seq)
                self.finish(seq, FinishReason.CANCELLED)
            elif _expired(seq, now):
                self.running.remove(seq)
                DEADLINE_EXPIRED.labels("decode").inc()
                log.warning(
                    "request %s deadline expired mid-decode; cancelling",
                    seq.request_id,
                )
                self.finish(seq, FinishReason.TIMEOUT)

    # tokens left to a row that states no end
    _NEVER = 1 << 40

    def _timeline_rows(self) -> list[_TimelineRow]:
        """The admitted population as ``_growth_reserve`` reads it."""
        alloc = self.allocator
        return [
            self._timeline_row(
                seq, len(seq.block_table), alloc.held_alone(seq.block_table)
            )
            for pool in (self.running, self.prefilling) for seq in pool
        ]

    def _window(self) -> int:
        """The most tokens one dispatch adds to a row: a fused window,
        or a draft run and the token its verify step samples."""
        return max(self.decode_lookahead, self.spec_tokens + 1)

    def _timeline_row(
        self, seq: Sequence, held: int, alone: int
    ) -> _TimelineRow:
        length = seq.total_len
        unprefilled = (
            0 if seq.state == SeqState.RUNNING
            else max(1, length - seq.num_computed)
        )
        if seq.max_new_tokens is None:
            # no stated end: never finishes on the timeline, and holds
            # one decode window beyond what it has
            return _TimelineRow(
                length, self._NEVER, length + self.decode_lookahead,
                held, alone, unprefilled,
            )
        left = max(1, seq.max_new_tokens - seq.generated)
        end = length + left
        if self.max_model_len and end > self.max_model_len:
            # should_finish ends it at max_model_len whatever it asked
            # for; the planners clamp a row's last window to max_tokens
            # and not to this, so that window's pages may be held whole
            left = max(1, self.max_model_len - length)
            end = length + left - 1 + self._window()
        return _TimelineRow(length, left, end, held, alone, unprefilled)

    def _growth_reserve(
        self, rows: list[_TimelineRow], newly_shared: int
    ) -> tuple[int, int]:
        """Pages that must be free NOW for ``rows`` — the admitted
        population and, last, the candidate with nothing allocated yet —
        never to run out, as ``(peak, total)``.

        ``total`` is every row's growth to its stated end (``max_tokens``
        or ``max_model_len``, whichever comes first; one decode window
        where none is stated), added up — the pool's need only if no
        row finished before the last one had reached its end. Until
        PR 41 admission kept that sum free, less the candidate's own
        growth, which it reserved only from the next admission on: in a
        pool of rows with like ends the timeline admits one row fewer
        than that did, the row that could then run out. ``peak``
        is the need if every row runs to its stated end AND gives its
        pages back when it gets there: the most the population holds at
        any future instant, less what it holds now. The rows of a decode
        batch advance together, so after ``t`` more tokens the rows
        alive are those with more than ``t`` left, each holding the
        pages of ``length + t + slack`` tokens (never past its end);
        occupancy only rises between two finishes, so the peak lies just
        before one of them: at most one instant a row, all evaluated at
        once.

        It is still a worst-case guarantee — a row that stops early
        (EOS, a stop string, a cancel, a deadline) only lowers every
        later instant — given what the scheduler can observe:

        - a finish gives back only pages nobody else pins: a prefix
          page with ``ref_count > 1`` is counted as never returned, and
          so (``newly_shared``) is one the candidate is about to pin
          beside its one holder;
        - ``slack`` is what the planners allocate ahead of a row's
          applied length: ``dispatches_ahead`` dispatches (what the
          engine's pipelines keep in flight and the one being planned)
          of up to ``decode_lookahead`` (or a draft run's
          ``spec_tokens + 1``) tokens each, and under mixed batching the windows the running
          rows decode while the rows still in chunked prefill catch up;
        - under speculation rows advance by 1 to ``spec_tokens + 1``
          tokens a step, each at its own pace: after ``t`` steps a live
          row has at most ``rate * t`` more tokens and may still be
          alive if it has more than ``t`` left. ``rate`` 1 is the exact
          timeline; as it grows the bound tends to ``total``, which it
          never exceeds.

        Why anything is reserved at all: without it, blocks freed by a
        preemption are instantly consumed by the next waiting prompt,
        and the following decode window preempts again — a recompute
        cascade in which every admission costs a running request its
        entire prompt's prefill windows (observed as a c=64 ISL-3000
        collapse to 35 out tok/s with ~9-minute TTFT outliers; 20
        preemptions per 120 s even in healthy runs)."""
        bs = self.block_size
        length, left, end, held, alone, unprefilled = np.array(
            rows, np.int64
        ).T
        shared = held - alone

        def own(tokens):
            # pages of its own a row holds then: never fewer than now
            return np.maximum(alone, -(-tokens // bs) - shared)

        now = int(alone[:-1].sum())  # the candidate holds nothing yet
        total = int(own(end).sum()) - now
        rate = self.spec_tokens + 1
        slack = self.dispatches_ahead * self._window()
        if self.mixed_prefill_rows > 0:
            # a window a chunk still to prefill, the candidate's included
            chunks = -(-unprefilled // self.mixed_prefill_len)
            slack += self.decode_lookahead * int(chunks.sum())
        ts = np.unique(np.append(left[left < self._NEVER] - 1, 0))[:, None]
        at = own(np.minimum(end, length + rate * ts + slack))
        peak = int(np.where(left > ts, at, 0).sum(axis=1).max())
        return min(peak + newly_shared - now, total), total

    def _admit(self) -> None:
        # the population's timeline rows (built lazily, grown by each
        # admission), and the pages this pass's admissions came to share
        # with the one row that held them
        rows, shared = None, 0
        while self.waiting and (
            len(self.running) + len(self.prefilling) < self.max_batch_size
        ):
            seq = self.waiting[0]
            if self.max_model_len and seq.total_len >= self.max_model_len:
                self.waiting.popleft()
                self.finish(seq, FinishReason.ERROR)
                continue
            seq_hashes = seq.tokens.sequence_hashes()
            n_prompt_blocks = seq.blocks_needed(seq.total_len, self.block_size)
            # the prompt takes from the free pool only what no sequence
            # holds already: actively-shared prefix blocks are pinned
            pinned, alone = self.allocator.pinned_prefix(
                seq_hashes[:n_prompt_blocks]
            )
            need = free_need = n_prompt_blocks - pinned
            if rows is None:
                rows = self._timeline_rows()
            rows.append(self._timeline_row(seq, n_prompt_blocks, free_need))
            weighed = len(rows) > 1
            if weighed:
                # somebody runs: their worst case and this prompt's have
                # to fit the pool together (alone, a prompt goes in
                # whatever its end: waiting would free nothing)
                need, total = self._growth_reserve(rows, shared + alone)
                self.admit_reserve_peak_pages += need
                self.admit_reserve_sum_pages += total
            if self.allocator.num_free < need:
                self.admit_blocked_reserve += weighed
                break  # backpressure: the population's growth comes first
            if self.state_slots is not None and not self.state_slots.num_free:
                break  # every state slot is held: wait for a finish
            if self.window_plane is not None and not self._window_admits(seq):
                self.admit_blocked_window += 1
                break  # the admitted rows' window pages come first
            try:
                complete = seq_hashes[: n_prompt_blocks]
                blocks, cached = self.allocator.allocate_prefix(complete)
                if self.onboard is not None and cached < len(complete):
                    # the onboard hook is (hashes, blocks) -> n with no
                    # request identity — park the admitting seq's rid in
                    # the autopsy thread-local so the fleet fabric's
                    # prefetch (same thread, synchronous chain) can
                    # stamp its hit/miss onto this request's record
                    autopsy.set_onboard_rid(
                        seq.autopsy_rid or seq.request_id
                    )
                    try:
                        n_on = self.onboard(
                            complete[cached:], blocks[cached : len(complete)]
                        )
                    finally:
                        autopsy.set_onboard_rid(None)
                    for i in range(n_on):
                        self.allocator.commit_block(
                            blocks[cached + i], complete[cached + i]
                        )
                    cached += n_on
                extra = n_prompt_blocks - len(complete)
                try:
                    for _ in range(max(0, extra)):
                        blocks.append(self.allocator.allocate_block())
                except NoBlocksError:
                    # roll back the whole allocation (reused pins + fresh
                    # + onboarded blocks) or they leak with a permanent ref
                    self.allocator.free_sequence(blocks)
                    raise
            except NoBlocksError:
                break  # backpressure: try again next step
            self.waiting.popleft()
            shared += alone
            if seq.t_admit == 0.0:
                # first admission only: a preempted-and-readmitted seq
                # keeps its original queue-wait measurement
                seq.t_admit = time.monotonic()
                if seq.t_submit:
                    ENGINE_QUEUE_WAIT.observe(seq.t_admit - seq.t_submit)
            seq.block_table = blocks
            if self.state_slots is not None:
                seq.state_slot = self.state_slots.acquire()
            seq.num_cached_prompt = cached * self.block_size
            seq.num_computed = seq.num_cached_prompt
            seq.committed_blocks = cached  # reused blocks are already addressed
            seq.state = SeqState.PREFILL
            self.prefilling.append(seq)
            # prefix-cache stats: one query per admitted request
            self.prefix_queries += 1
            if cached > 0:
                self.prefix_hits += 1
            self.prompt_tokens_admitted += seq.total_len
            self.prompt_tokens_cached += seq.num_cached_prompt
        # the loop ends with a request still waiting only where it could
        # not place the head (the while's batch cap, or one of the breaks)
        self._blocked_head = self.waiting[0] if self.waiting else None

    # -- the window plane ------------------------------------------------
    def window_row_bound(self, seq: Sequence) -> int:
        """The most window-plane pages ``seq`` holds from now on: the
        window's keys and what one dispatch adds ahead of them — its next
        prefill chunk while it has prompt left, afterwards the tokens
        the decode planners allocate ahead of its applied length
        (``dispatches_ahead`` dispatches of a window each, and the token
        in hand). Never its length."""
        ahead = 1 + self.dispatches_ahead * self._window()
        if seq.state != SeqState.RUNNING:
            left = seq.total_len - seq.num_computed
            ahead = max(ahead, min(max(1, left), self.prefill_chunk_size))
        return self.window_plane.span_pages(ahead)

    def _window_admits(self, seq: Sequence) -> bool:
        """Does the window plane hold ``seq``'s bound beside what every
        admitted row may still take up to its own? (Alone, a prompt goes
        in whatever: the plane is sized to hold a row, and waiting would
        free nothing.)"""
        plane = self.window_plane
        owed = sum(
            max(0, self.window_row_bound(s) - plane.held(s.window_table))
            for pool in (self.running, self.prefilling) for s in pool
        )
        return plane.num_free - owed >= self.window_row_bound(seq) or not (
            self.running or self.prefilling)

    def _release_window(self, seq: Sequence) -> int:
        """Hand back ``seq``'s window-plane pages that no query from
        position ``num_computed`` on can read; returns how many."""
        if self.window_plane is None:
            return 0
        return self.window_plane.release_behind(
            seq.window_table, seq.num_computed)

    def _free_window(self, seq: Sequence) -> None:
        if self.window_plane is not None:
            self.window_plane.free_row(seq.window_table)

    def _plan_prefill_batch(
        self,
        budget: Optional[int] = None,
        max_seqs: Optional[int] = None,
        max_chunk_len: Optional[int] = None,
        skip: Container[int] = (),
    ) -> list[PrefillWork]:
        """One chunk from each of several prefilling sequences, fused
        into a single step (total tokens bounded by max_prefill_tokens)
        — continuous batching's batched-prefill half. ``max_chunk_len``
        additionally caps each row's chunk (the mixed-step rectangle,
        which the caller sized to hold ``max_seqs`` such rows: none is
        turned away for its area). ``skip``: ids of sequences to pass
        over (their last chunk is in flight, plan_pipelined_admission)."""
        budget = budget if budget is not None else self.max_prefill_tokens
        max_seqs = max_seqs if max_seqs is not None else self.max_batch_size
        works: list[PrefillWork] = []
        max_chunk = 0
        rect: Optional[tuple[int, int]] = None
        for seq in self.prefilling:
            if len(works) >= max_seqs:
                break
            if id(seq) in skip:
                continue
            prompt = seq.tokens.all_tokens()
            start = seq.num_computed
            remaining = len(prompt) - start
            if remaining <= 0:
                # fully cached prompt: recompute the last token so we
                # have its logits to sample from
                start = max(0, len(prompt) - 1)
                remaining = len(prompt) - start
            chunk = min(remaining, self.prefill_chunk_size, budget)
            if max_chunk_len is not None:
                chunk = min(chunk, max_chunk_len)
            # the dispatch cost is the PADDED B×T rectangle (every row
            # pads to the longest chunk's bucket), so the budget bounds
            # that area, not the sum of real tokens — one long chunk
            # plus many short ones must not inflate into a huge step
            new_max = max(max_chunk, chunk)
            if max_chunk_len is None:
                grown = self.prefill_rect(len(works) + 1, new_max)
                # a row whose admission leaves the padded rectangle
                # unchanged is free — only reject when it actually GROWS
                # the dispatch past the budget, or when no rectangle
                # holds one row more (two 300-token chunks under static
                # shapes: two 1 x 512 steps, not eight padded rows of
                # 1 024)
                if works and (
                    grown is None
                    or _area(grown) > max(budget, _area(rect))
                ):
                    break
                rect = grown
            tokens = np.asarray(prompt[start : start + chunk], dtype=np.int32)
            works.append(
                PrefillWork(
                    seq=seq,
                    tokens=tokens,
                    start_pos=start,
                    is_last_chunk=(start + chunk >= len(prompt)),
                )
            )
            max_chunk = new_max
        return works

    def complete_prefill_chunk(self, work: PrefillWork) -> None:
        seq = work.seq
        seq.num_computed = work.start_pos + len(work.tokens)
        seq.prefill_chunks += 1
        seq.window_pages_released += self._release_window(seq)
        self._commit_full_blocks(seq)
        if work.is_last_chunk:
            self.prefilling.remove(seq)
            seq.state = SeqState.RUNNING
            if seq.t_prefill_done == 0.0:
                seq.t_prefill_done = time.monotonic()
            self.running.append(seq)

    def _seq_lookahead(self, seq: Sequence, lag: int = 0) -> int:
        """Fused-decode window steps this sequence can actually keep:
        clamped to its remaining-token budget (less ``lag`` tokens that
        in-flight windows have sampled and the host has not applied). Near max_tokens the
        window's surplus is discarded, and allocating blocks for it would
        trigger phantom preemptions under pressure. Block allocation
        (_plan_decode) and the device-side KV-write mask
        (build_decode_arrays' valid_steps) MUST use the same value — if
        writes outrun allocation they land in another sequence's
        possibly-shared block."""
        lookahead = self.decode_lookahead
        if seq.max_new_tokens is not None:
            lookahead = min(
                lookahead, max(1, seq.max_new_tokens - seq.generated - lag)
            )
        return lookahead

    def _plan_decode(self) -> list[Sequence]:
        """Ensure each running seq has a slot for its next token; on block
        exhaustion preempt the YOUNGEST running sequence (possibly the
        requester itself) back to waiting — recompute preemption."""
        batch = sorted(self.running, key=lambda s: s.arrival)[: self.max_batch_size]
        safe: list[Sequence] = []
        for seq in batch:
            if seq.state != SeqState.RUNNING:
                continue  # preempted earlier in this pass
            lookahead = self._seq_lookahead(seq)
            needed_blocks = seq.blocks_needed(
                seq.total_len + lookahead, self.block_size
            )
            while (
                seq.state == SeqState.RUNNING
                and len(seq.block_table) < needed_blocks
            ):
                try:
                    seq.block_table.append(self.allocator.allocate_block())
                except NoBlocksError:
                    if not self.running:
                        break
                    victim = max(self.running, key=lambda s: s.arrival)
                    self._preempt(victim)
                    if victim is seq:
                        break
            if seq.state == SeqState.RUNNING:
                safe.append(seq)
        return safe

    def plan_pipelined_admission(
        self, lag: dict
    ) -> tuple[Optional[list[PrefillWork]], str]:
        """What ``plan()`` does before a prefill step — reap, admit,
        choose the chunks, with the same arguments and so the same
        priority of prefill chunks over decode — while the decode
        pipeline has steps in flight (engine._decode_pipeline calls it
        where ``admission_work()`` says yes). Returns ``(works, "")``,
        the chunks of the next prefill dispatch: ``[]`` when there is
        nothing to prefill now (the head cannot be placed, or every
        prefilling sequence's last chunk is in flight: those are in
        ``lag``, one token each); or ``(None, why)`` to drain the
        pipeline: ``"unpredicted_finish"`` — a sequence in
        prefill is cancelled or past its deadline, and only ``plan()``,
        with nothing in flight, may free pages a dispatched chunk
        writes; ``"admission"`` — pages come in from another tier
        (``onboard``) by a copy into the cache that is not the
        pipeline's to order, and that tier is pumped between two plans.

        Why admitting here is safe: ``_admit`` only takes from the free
        pool and from pages a running row pins, and it never preempts.
        A page is free because nobody writes it: a row that finished in
        line is a row of no step in flight (the engine flushes for every
        other finish before it comes back here), and a new row's prefill
        is dispatched after every step in flight, on a device that runs
        its programs in order. ``_growth_reserve`` reads rows up to
        ``lag`` tokens behind the pages they hold; its ``slack``
        (``dispatches_ahead``) is what covers that, so it asks for at
        most a page a row more than it needs and never less."""
        now = time.monotonic()
        if any(seq_gone(seq, now) for seq in self.prefilling):
            return None, "unpredicted_finish"
        if self.onboard is not None:
            return None, "admission"
        self._reap_cancelled(queued_only=True)
        self._admit()
        return self._plan_prefill_batch(skip=lag), ""

    def plan_pipelined_decode(
        self, seqs: list[Sequence], lag: dict,
        column: Optional[dict[int, int]] = None,
    ) -> tuple[Optional[dict], str]:
        """Plan the NEXT single-token decode step while steps are in
        flight (the decode_steps == 1 overlapped pipeline,
        engine._decode_pipeline / docs/performance.md).

        ``seqs`` is the decode population as of the newest dispatch;
        ``lag`` maps id(seq) -> tokens sampled by in-flight steps but
        not yet applied to host state (one per decode step, and one for
        a prompt whose last chunk is in flight). ``column`` maps
        id(seq) -> the row's index in the NEWEST dispatch's sampled
        token column, from which the engine gathers its input token on
        the device; by default the newest dispatch is a decode step
        over ``seqs`` in their order. A row the column does not hold
        must be one the host is level with (no lag): its token goes in
        from the host.

        Sequences that FINISH inside the in-flight lag — max_tokens
        reached, max_model_len hit, or block-table cap — are simply not
        rows of the next step, mirroring ``should_finish`` one step
        ahead so a predicted finish never leaves an in-flight step
        writing KV into blocks a harvest-time ``finish()`` just freed;
        one that already has finished that way, with nothing of its own
        in flight, is passed over. Returns ``(None, why)`` on anything
        else: ``"unpredicted_finish"`` (a
        cancellation, a deadline, a row in a state no step in flight
        foresaw), ``"blocks"`` (pool exhausted — this path NEVER
        preempts: a preemption would free blocks an in-flight step
        still writes; the outer serial plan() handles pressure with
        nothing in flight), ``"wait"`` (a lagging row's token is not in
        the newest column: plan again after the next harvest) or
        ``"done"`` (no row is left).

        Else returns the plan and ``""``. The plan is {"seqs", "arrays",
        "src_idx", "offsets", "vmap"}: the next step's rows, its decode
        arrays (the token column holds the host's tokens — the engine
        overrides it on device from the column via ``src_idx``, -1 where
        the host's token stands), per-row seed offsets (= lags), and the
        one token each row will add.
        """
        now = time.monotonic()
        if column is None:
            column = {id(s): j for j, s in enumerate(seqs)}
        survivors: list[Sequence] = []
        for seq in seqs:
            gl = lag.get(id(seq), 0)
            if seq.state == SeqState.FINISHED and not gl:
                continue  # finished in line, at an earlier harvest
            if seq.state != SeqState.RUNNING and not (
                seq.state == SeqState.PREFILL and gl and id(seq) in column
            ):
                return None, "unpredicted_finish"
            if seq_gone(seq, now):
                return None, "unpredicted_finish"
            if gl and id(seq) not in column:
                return None, "wait"
            if (
                seq.max_new_tokens is not None
                and seq.max_new_tokens - seq.generated <= gl
            ):
                continue  # finishes inside the in-flight step
            if self.max_model_len and seq.total_len + gl >= self.max_model_len:
                continue
            if len(seq.block_table) >= self.allocator.num_blocks - 1:
                continue  # should_finish's can't-grow-further clause
            survivors.append(seq)
        if not survivors:
            return None, "done"
        bs = self.block_size
        # block growth for the next step's KV write (the in-flight
        # token's slot) — no preemption; rollback on exhaustion
        added: list[Sequence] = []
        ok = True
        for seq in survivors:
            needed = seq.blocks_needed(
                seq.total_len + lag.get(id(seq), 0) + 1, bs
            )
            while len(seq.block_table) < needed:
                try:
                    seq.block_table.append(self.allocator.allocate_block())
                    added.append(seq)
                except NoBlocksError:
                    ok = False
                    break
            if not ok:
                break
        if not ok:
            for seq in reversed(added):
                self.allocator.free_sequence([seq.block_table.pop()])
            return None, "blocks"
        n = len(survivors)
        B = self._decode_batch(n)
        max_blocks = max(len(s.block_table) for s in survivors)
        width = self._table_width(max_blocks)
        tokens = np.zeros((B, 1), np.int32)
        positions = np.zeros((B, 1), np.int32)
        slot_mapping = np.zeros((B,), np.int32)
        tables = np.zeros((B, width), np.int32)
        ctx = np.zeros((B,), np.int32)
        src_idx = np.zeros((B,), np.int32)
        offsets = [0] * n
        vmap: dict[int, int] = {}
        for i, s in enumerate(survivors):
            gl = lag.get(id(s), 0)
            src_idx[i] = column.get(id(s), -1)
            if src_idx[i] < 0:
                tokens[i, 0] = s.tokens.tail_tokens(1)[0]
            pos = s.total_len - 1 + gl
            positions[i, 0] = pos
            slot_mapping[i] = s.block_table[pos // bs] * bs + pos % bs
            self._fill_table(tables, i, s)
            ctx[i] = s.total_len + gl
            offsets[i] = gl
            vmap[id(s)] = 1
        arrays = {
            "tokens": tokens,
            "positions": positions,
            "slot_mapping": slot_mapping,
            "block_tables": tables,
            "context_lens": ctx,
            "last_token_idx": np.zeros((B,), np.int32),
        }
        return {
            "seqs": survivors,
            "arrays": arrays,
            "src_idx": src_idx,
            "offsets": offsets,
            "vmap": vmap,
        }, ""

    def plan_pipelined_mixed(
        self, seqs: list[Sequence], works: list[PrefillWork], lag: dict,
        grad_base: Optional[int] = None,
    ) -> Optional[dict]:
        """Plan the NEXT window while one or more windows are in flight.

        ``lag`` maps id(seq) -> tokens generated by in-flight windows
        but not yet applied to host state (decode rows contribute their
        valid steps per window; a last-chunk prefill contributes its
        one sampled token). The newest in-flight window is decoding for
        ``seqs`` AND prefilling ``works``; last-chunk works GRADUATE to
        decode rows of the next window (their first sampled token is
        device-resident in that window's outputs — the engine chains it
        via an on-device gather, indexed by ``src_idx``: row j of the
        newest decode batch -> j, graduated work r -> grad_base + r,
        where ``grad_base`` defaults to the newest window's padded
        decode width; a prefill-only in-flight entry — the cohort
        dispatch the overlapped window pipeline chains its first window
        off — passes 0, its token vector being the prefill rows alone).
        Returns None (flush the pipeline) whenever anything irregular
        appears: a non-final chunk, cancellations, budget inside the
        in-flight windows, batch overflow, or block exhaustion (never
        preempts here).

        Returns {"seqs", "works2", "arrays", "src_idx", "offsets",
        "vmap"}: the next window's decode seqs (old + graduated), its
        prefill works, the decode arrays (tokens are placeholders), the
        token-source gather index, per-row seed offsets (= lags), and
        the valid-step counts this window will add per sequence (the
        engine folds them into ``lag`` on dispatch).
        """
        if self.waiting:
            self._admit()
        now = time.monotonic()
        for w in works:
            if not w.is_last_chunk:
                return None
            if seq_gone(w.seq, now):
                return None
        survivors: list[Sequence] = []
        for seq in seqs:
            if seq.state != SeqState.RUNNING:
                return None
            if seq_gone(seq, now):
                return None
            if (
                seq.max_new_tokens is not None
                and seq.max_new_tokens - seq.generated <= lag.get(id(seq), 0)
            ):
                # finishes INSIDE an in-flight window: simply not a
                # row of the next one (its blocks are freed at sync,
                # which the next window never touches) — refusing to
                # pipeline here would block the chain whenever ANY
                # sequence nears its budget, i.e. almost always
                continue
            survivors.append(seq)
        graduated = [w.seq for w in works]
        grad_row = {id(w.seq): r for r, w in enumerate(works)}
        old_row = {id(s): j for j, s in enumerate(seqs)}
        next_seqs = survivors + graduated
        if not next_seqs or len(next_seqs) > self.max_batch_size:
            return None
        # the steps of the next window each row keeps
        vmap = {
            id(s): self._seq_lookahead(s, lag.get(id(s), 0)) for s in next_seqs
        }
        # block allocation for the whole next window (no preemption on
        # this path; rollback on exhaustion) — for the steps a row keeps
        # and no further, so no row ever holds pages past its stated
        # end (_growth_reserve counts on it). lag covers a graduated
        # row's in-flight sampled token, so one formula serves all.
        added: list[Sequence] = []
        ok = True
        for seq in next_seqs:
            needed = seq.blocks_needed(
                seq.total_len + lag.get(id(seq), 0) + vmap[id(seq)],
                self.block_size,
            )
            while len(seq.block_table) < needed:
                try:
                    seq.block_table.append(self.allocator.allocate_block())
                    added.append(seq)
                except NoBlocksError:
                    ok = False
                    break
            if not ok:
                break
        if not ok:
            for seq in reversed(added):
                self.allocator.free_sequence([seq.block_table.pop()])
            return None
        # next window's prefill rows: pending chunks excluding the
        # in-flight works' seqs
        works2: list[PrefillWork] = []
        rows, rlen = self.mixed_prefill_rows, self.mixed_prefill_len
        if self.mixed_prefill_rows > 0:
            busy = set(id(s) for s in graduated)
            avail = [s for s in self.prefilling if id(s) not in busy]
            # adaptive rect for the NEXT window: its decode population
            # is next_seqs (not self.running, which lags the pipeline)
            avail_backlog = sum(
                max(1, s.total_len - s.num_computed) for s in avail
            )
            rows, rlen = self._mixed_rect(
                n_running=len(next_seqs), prefill_seqs=avail,
                backlog=avail_backlog,
            )
            if len(avail) > rows and (
                len(next_seqs) < len(avail)
                or avail_backlog <= self.max_prefill_tokens
            ):
                # prefill-heavy or a one-dispatch COHORT: break the
                # chain so the outer plan can run a dedicated batched
                # prefill instead of ramping the batch 'rows' per
                # window (a trickled cohort decodes at partial width
                # for its whole lifetime — see plan()'s cohort gate)
                for seq in reversed(added):
                    self.allocator.free_sequence([seq.block_table.pop()])
                return None
            saved = self.prefilling
            self.prefilling = deque(avail)
            try:
                works2 = self._plan_prefill_batch(
                    budget=rows * rlen,
                    max_seqs=rows,
                    max_chunk_len=rlen,
                )
            finally:
                self.prefilling = saved

        bs = self.block_size
        n = len(next_seqs)
        B = self._decode_batch(n)
        max_blocks = max(len(s.block_table) for s in next_seqs)
        width = self._table_width(max_blocks)
        positions = np.zeros((B, 1), np.int32)
        tables = np.zeros((B, width), np.int32)
        ctx = np.zeros((B,), np.int32)
        valid_steps = np.zeros((B,), np.int32)
        src_idx = np.zeros((B,), np.int32)
        offsets = [0] * n
        if grad_base is None:
            grad_base = self._decode_batch(len(seqs)) if seqs else 0
        for i, s in enumerate(next_seqs):
            gen_after = lag.get(id(s), 0)
            if id(s) in grad_row:
                src_idx[i] = grad_base + grad_row[id(s)]
            else:
                src_idx[i] = old_row[id(s)]
            # the sampled-but-unapplied tokens occupy slots up to
            # total_len - 1 + lag; the next window starts there
            positions[i, 0] = s.total_len - 1 + gen_after
            self._fill_table(tables, i, s)
            ctx[i] = s.total_len + gen_after
            valid_steps[i] = vmap[id(s)]
            offsets[i] = gen_after
        arrays = {
            "tokens": np.zeros((B, 1), np.int32),  # device chain overrides
            "positions": positions,
            "block_tables": tables,
            "context_lens": ctx,
            "valid_steps": valid_steps,
        }
        return {
            "seqs": next_seqs,
            "works2": works2,
            "arrays": arrays,
            "src_idx": src_idx,
            "offsets": offsets,
            "vmap": vmap,
            "rect": (rows, rlen),
        }

    # -- speculative decoding (dynamo_tpu/spec) ---------------------------
    def reserve_spec_tokens(self, seq: Sequence, drafts: list[int]) -> int:
        """Stage up to ``len(drafts)`` draft tokens for one verify step:
        allocate the blocks their KV writes need (positions
        [total_len-1, total_len-1+k) — the verify forward writes every
        draft's KV speculatively), then append the kept drafts to the
        sequence's token state so array building sees them. The engine
        UNWINDS the appended drafts after the device sync
        (TokenBlockSequence.unwind) and re-appends only the accepted
        prefix through append_token — so block content-addressing
        (committed_blocks / _commit_full_blocks) never sees unverified
        draft tokens: num_computed is untouched here, and a block is
        only committed once real appended tokens cover it.

        Never preempts (speculation is an optimization): on block
        exhaustion the draft count shrinks to what the sequence's
        current table already covers. Returns the kept draft count.
        """
        k = len(drafts)
        bs = self.block_size
        while k > 0:
            needed = seq.blocks_needed(seq.total_len + k, bs)
            try:
                while len(seq.block_table) < needed:
                    seq.block_table.append(self.allocator.allocate_block())
                break
            except NoBlocksError:
                # keep what fits in the blocks already held — blocks
                # speculatively appended above stay on the table (plain
                # growth the sequence will need anyway) but are never
                # committed/content-addressed until real tokens fill them
                k = min(k, len(seq.block_table) * bs - seq.total_len)
        if k > 0:
            seq.tokens.extend(drafts[:k])
        return max(0, k)

    def _fill_spec_row(
        self, arrays: dict[str, np.ndarray], i: int, seq: Sequence,
        base: int, k: int, S: int,
    ) -> None:
        """One verify-step row's tensor geometry — THE shared layout
        for both spec planners (serial ``build_spec_arrays`` over
        staged drafts, pipelined ``plan_pipelined_spec`` over explicit
        lags): positions contiguous from the carry token at ``base``,
        the k+1 real slots resolved through the block table (row pads
        write the reserved garbage slot 0), ``context_lens`` = real
        tokens including drafts (= base+1+k). The pipelined path's
        bit-identity-to-serial contract depends on the two callers
        producing identical rows for identical states, so the layout
        lives here and nowhere else."""
        bs = self.block_size
        arrays["positions"][i, :] = np.arange(base, base + S)
        for j in range(k + 1):
            pos = base + j
            arrays["slot_mapping"][i * S + j] = (
                seq.block_table[pos // bs] * bs + pos % bs
            )
        self._fill_table(arrays["block_tables"], i, seq)
        arrays["context_lens"][i] = base + 1 + k
        arrays["draft_lens"][i] = k

    def build_spec_arrays(
        self, works: list[tuple[Sequence, list[int]]], S: int
    ) -> dict[str, np.ndarray]:
        """Verify-step tensors for [(seq, row_tokens)] rows, where
        ``row_tokens`` is the CONTIGUOUS run [last committed token,
        draft_0, ..., draft_{k-1}] (the engine already holds these —
        re-materializing each sequence's full history here would put a
        second O(context) copy on the per-step host path), padded to the
        fixed width ``S`` (= spec_tokens+1 — one compiled shape). Call
        AFTER reserve_spec_tokens (seq.total_len includes the staged
        drafts). Row-internal pads keep contiguous positions (the Pallas
        prefill kernel derives per-token positions from positions[:, 0])
        but write to the reserved garbage slot 0; context_lens covers
        only real tokens, so attention never reads a pad's KV."""
        n = len(works)
        B = self._decode_batch(n)
        max_blocks = max(len(s.block_table) for s, _ in works)
        width = self._table_width(max_blocks)
        arrays = {
            "tokens": np.zeros((B, S), np.int32),
            "positions": np.zeros((B, S), np.int32),
            "slot_mapping": np.zeros((B * S,), np.int32),
            "block_tables": np.zeros((B, width), np.int32),
            "context_lens": np.zeros((B,), np.int32),
            "draft_lens": np.zeros((B,), np.int32),
            "last_token_idx": np.zeros((B,), np.int32),
        }
        for i, (seq, row) in enumerate(works):
            k = len(row) - 1
            # carry position: total_len here INCLUDES the staged drafts
            base = seq.total_len - k - 1
            arrays["tokens"][i, : k + 1] = row
            self._fill_spec_row(arrays, i, seq, base, k, S)
        return arrays

    def plan_pipelined_spec(
        self, entries: list, S: int
    ) -> Optional[dict]:
        """Plan the NEXT speculative verify step while the PREVIOUS
        one's emitted tokens are not yet applied to host state (the
        overlapped spec pipeline, engine._spec_pipeline /
        docs/speculative_decoding.md).

        ``entries`` is the previous step's row list as
        ``(seq, lag, drafts)``: ``lag`` = tokens that step emitted for
        the row (EXACT — the spec pipeline plans between harvest and
        emit, so unlike ``plan_pipelined_decode`` the in-flight token
        count is known, 1..K+1), ``drafts`` = the repaired proposals
        for the next step. Same discipline as the other pipelined
        planners: sequences that FINISH inside the lag (max_tokens,
        max_model_len, block-table cap — ``should_finish`` mirrored one
        emit ahead) are simply not rows of the next step; anything
        irregular (cancellation, deadline expiry, a non-RUNNING state,
        block exhaustion) returns None — flush to the serial planner,
        which admits/preempts/reaps with nothing in flight. This path
        NEVER preempts. Block growth reserves the row's in-flight
        tokens plus its draft run (``total_len + lag + k`` — the same
        coverage ``reserve_spec_tokens`` gives the serial step), with
        rollback on ``NoBlocksError``. Drafts are clamped to the
        remaining ``max_tokens`` budget exactly as the serial draft
        loop clamps them (bit-identity of the proposal stream).

        Returns {"works", "arrays", "src_idx", "offsets"}: ``works`` =
        (seq, kept_drafts) rows of the next step; ``arrays`` = the
        verify-step tensors, with token column 0 a placeholder — the
        engine chains each row's carry token ON DEVICE from the
        previous step's packed output (``chain_spec``), gathered by
        ``src_idx`` (= the row's index in ``entries``); ``offsets`` =
        per-row seed offsets (= lags). Unlike the serial path, nothing
        is staged into ``seq.tokens`` — array geometry comes from the
        explicit (lag, drafts) and host token state stays clean for the
        overlapped emit/bookkeeping.
        """
        now = time.monotonic()
        survivors: list[tuple[int, Sequence, int, list[int]]] = []
        for row, (seq, gl, drafts) in enumerate(entries):
            if seq.state != SeqState.RUNNING:
                return None
            if seq_gone(seq, now):
                return None
            if (
                seq.max_new_tokens is not None
                and seq.max_new_tokens - seq.generated <= gl
            ):
                continue  # finishes inside the in-flight emit
            if self.max_model_len and seq.total_len + gl >= self.max_model_len:
                continue
            if len(seq.block_table) >= self.allocator.num_blocks - 1:
                continue  # should_finish's can't-grow-further clause
            k = len(drafts)
            if seq.max_new_tokens is not None:
                # leave room for the verify step's guaranteed +1 token
                # (the serial draft loop's budget clamp, shifted by lag)
                k = min(
                    k, max(0, seq.max_new_tokens - seq.generated - gl - 1)
                )
            survivors.append((row, seq, gl, drafts[: min(k, S - 1)]))
        if not survivors:
            return None
        bs = self.block_size
        added: list[Sequence] = []
        ok = True
        for _, seq, gl, drafts in survivors:
            needed = seq.blocks_needed(seq.total_len + gl + len(drafts), bs)
            while len(seq.block_table) < needed:
                try:
                    seq.block_table.append(self.allocator.allocate_block())
                    added.append(seq)
                except NoBlocksError:
                    ok = False
                    break
            if not ok:
                break
        if not ok:
            for seq in reversed(added):
                self.allocator.free_sequence([seq.block_table.pop()])
            return None
        n = len(survivors)
        B = self._decode_batch(n)
        max_blocks = max(len(s.block_table) for _, s, _, _ in survivors)
        width = self._table_width(max_blocks)
        arrays = {
            # tokens column 0 = placeholder (device chain fills it)
            "tokens": np.zeros((B, S), np.int32),
            "positions": np.zeros((B, S), np.int32),
            "slot_mapping": np.zeros((B * S,), np.int32),
            "block_tables": np.zeros((B, width), np.int32),
            "context_lens": np.zeros((B,), np.int32),
            "draft_lens": np.zeros((B,), np.int32),
            "last_token_idx": np.zeros((B,), np.int32),
        }
        src_idx = np.zeros((B,), np.int32)
        offsets = [0] * n
        works: list[tuple[Sequence, list[int]]] = []
        for i, (row, seq, gl, drafts) in enumerate(survivors):
            k = len(drafts)
            # carry position: total_len + lag - 1 (the emit has not yet
            # applied; same row a serial plan would build post-emit)
            base = seq.total_len + gl - 1
            if k:
                arrays["tokens"][i, 1 : k + 1] = drafts
            self._fill_spec_row(arrays, i, seq, base, k, S)
            src_idx[i] = row
            offsets[i] = gl
            works.append((seq, drafts))
        return {
            "works": works,
            "arrays": arrays,
            "src_idx": src_idx,
            "offsets": offsets,
        }

    def _preempt(self, victim: Sequence) -> None:
        self.preemptions += 1
        self._blocked_head = None  # pages, a row and a slot come free
        ENGINE_PREEMPTIONS.inc()
        log.warning("preempting %s (recompute)", victim.request_id)
        self.running.remove(victim)
        self.allocator.free_sequence(victim.block_table)
        victim.block_table = []
        self._free_window(victim)
        self._release_state(victim)
        victim.num_computed = 0
        victim.num_cached_prompt = 0
        victim.committed_blocks = 0
        victim.state = SeqState.WAITING
        self.waiting.appendleft(victim)

    # -- post-step bookkeeping -------------------------------------------
    def append_token(self, seq: Sequence, token: int) -> None:
        seq.tokens.append(int(token))
        seq.generated += 1
        if seq.t_first_token == 0.0:
            # TTFT stamp (telemetry/slo.py): every emit path — plain
            # step, fused window, spec verify — funnels through here
            seq.t_first_token = time.monotonic()
        if seq.request.sampling.needs_penalties:
            seq.gen_counts[int(token)] = seq.gen_counts.get(int(token), 0) + 1
        if seq.guided_state is not None:
            # every emit path — plain step, spec verify — funnels
            # through here, so the automaton cursor tracks exactly the
            # committed token stream (guided requires decode_steps == 1;
            # fused windows never carry guided sequences)
            seq.guided_state.advance(int(token))
        # the just-sampled token's KV is NOT in the cache yet — it only gets
        # written when it is fed as input on the next step. Counting it as
        # computed would let _commit_full_blocks content-address a block
        # whose last slot holds garbage, poisoning the prefix cache.
        seq.num_computed = seq.total_len - 1
        self._release_window(seq)
        self._commit_full_blocks(seq)

    def _commit_full_blocks(self, seq: Sequence) -> None:
        """Content-address newly completed, fully-computed blocks."""
        hashes = seq.tokens.sequence_hashes()
        n_complete_computed = min(
            seq.num_computed // self.block_size, len(seq.block_table), len(hashes)
        )
        for i in range(seq.committed_blocks, n_complete_computed):
            self.allocator.commit_block(seq.block_table[i], hashes[i])
            if (
                self._blocked_head is not None
                and hashes[i] in self._blocked_head.tokens.sequence_hashes()
            ):
                # a page of the blocked head's own prompt is now held
                # by a running row: admitting it costs one page less
                # (allocator.pinned_prefix), so it has to be tried again
                self._blocked_head = None
        seq.committed_blocks = max(seq.committed_blocks, n_complete_computed)

    def should_finish(self, seq: Sequence) -> Optional[FinishReason]:
        if seq.max_new_tokens is not None and seq.generated >= seq.max_new_tokens:
            return FinishReason.LENGTH
        if self.max_model_len and seq.total_len >= self.max_model_len:
            return FinishReason.LENGTH
        if len(seq.block_table) >= (
            self.allocator.num_blocks - 1
        ):  # can't possibly grow further
            return FinishReason.LENGTH
        return None

    def finish(self, seq: Sequence, reason: FinishReason) -> None:
        if seq.state == SeqState.FINISHED:
            return
        seq.state = SeqState.FINISHED
        seq.finish_reason = reason
        self._blocked_head = None  # pages, a row and a slot come free
        if seq in self.running:
            self.running.remove(seq)
        if seq.block_table:
            self.allocator.free_sequence(seq.block_table)
            seq.block_table = []
        self._free_window(seq)
        self._release_state(seq)
        if self.on_finish is not None:
            self.on_finish(seq, reason)

    # -- step-tensor construction (static-shaped, bucketed) ---------------
    BATCH_BUCKETS = [1, 2, 4, 8, 16, 32, 64, 128, 256]
    CHUNK_BUCKETS = [16, 32, 64, 128, 256, 512, 1024, 2048, 4096]
    # static shapes, where every rectangle is compiled at start-up:
    # chunk lengths at every row count the budget allows, and the one a
    # single row has besides — between 256 and 1 024, which prompts of
    # 257-512 tokens would otherwise pay four-fold for. One row because
    # the static row ladder is coarse: 8 x 512 would be one more program
    # for steps traffic almost never forms, and two waiting chunks of
    # that length run as two single-row steps either way.
    STATIC_CHUNK_TOKENS = [128, 256, 1024, 4096]
    STATIC_SINGLE_ROW_TOKENS = (512,)
    TABLE_BUCKET = 8  # block-table width rounded to multiples of this

    def _release_state(self, seq: Sequence) -> None:
        if self.state_slots is not None and seq.state_slot:
            self.state_slots.release(seq.state_slot)
            seq.state_slot = 0

    @property
    def table_extra(self) -> int:
        """Columns of a block table beyond its pages that do not grow
        with them: the state slot."""
        return self.table_width_of(0)

    def table_width_of(self, pages: int) -> int:
        """Columns of a block table that holds ``pages`` page columns:
        one more for the state slot where the model keeps recurrent
        state, as many again for the window plane's columns where it
        has one."""
        if self.window_plane is not None:
            return 2 * pages
        return pages + (0 if self.state_slots is None else 1)

    def _table_pages(self, width: int) -> int:
        """``table_width_of``, backwards."""
        if self.window_plane is not None:
            return width // 2
        return width - (0 if self.state_slots is None else 1)

    def _table_width(self, max_blocks: int) -> int:
        """Block-table width for a step: the fixed serving cap when set
        (one compiled shape), bucketed otherwise — growing past the cap
        degrades to a wider bucket rather than corrupting tables. With
        state slots or a window plane, the columns beyond the pages
        (``table_width_of``, ``_fill_table``)."""
        w = max(
            self.TABLE_BUCKET,
            -(-max_blocks // self.TABLE_BUCKET) * self.TABLE_BUCKET,
        )
        if self.table_width_pad is not None and w <= self.table_width_pad:
            w = self.table_width_pad
        return self.table_width_of(w)

    def _fill_table(
        self, tables: np.ndarray, i: int, seq: Sequence,
        cols: Optional[int] = None,
    ) -> None:
        """Row ``i``: the sequence's pages; in the last column its state
        slot where the model keeps recurrent state; in the table's second
        half its window-plane pages where the model has that plane —
        made to hold, now, the first ``cols`` columns that the window of
        the row's next query can read (by default as many as the full
        plane holds: what the decode planners allocated ahead; a prefill
        chunk names its own end, the prompt's later pages being held in
        the full plane alone)."""
        tables[i, : len(seq.block_table)] = seq.block_table
        if self.state_slots is not None:
            tables[i, -1] = seq.state_slot
        if self.window_plane is not None:
            self.window_plane.cover(
                seq.window_table,
                len(seq.block_table) if cols is None else cols,
                seq.num_computed,
            )
            half = tables.shape[1] // 2
            tables[i, half: half + len(seq.window_table)] = seq.window_table

    def widen_tables(self, tables: np.ndarray, width: int) -> np.ndarray:
        """``tables`` padded to ``width`` columns (the state-slot column
        stays the last, the window plane's columns the second half)."""
        w0 = tables.shape[1]
        if w0 >= width:
            return tables
        out = np.zeros((tables.shape[0], width), np.int32)
        pages = self._table_pages(w0)
        out[:, :pages] = tables[:, :pages]
        if self.window_plane is not None:
            out[:, width // 2: width // 2 + pages] = tables[:, pages:]
        elif self.state_slots is not None:
            out[:, -1] = tables[:, -1]
        return out

    def _decode_batch(self, n: int) -> int:
        if (
            self.decode_batch_small is not None
            and n <= self.decode_batch_small
        ):
            return self.decode_batch_small
        if self.decode_batch_mid is not None and n <= self.decode_batch_mid:
            return self.decode_batch_mid
        b = next_bucket(n, self.BATCH_BUCKETS)
        if self.decode_batch_pad is not None and b <= self.decode_batch_pad:
            return self.decode_batch_pad
        return b

    def prefill_rect(
        self, rows: int, tokens: int,
        within: Optional[tuple[int, int]] = None,
    ) -> Optional[tuple[int, int]]:
        """The smallest-area rectangle of ``prefill_rects`` that covers
        ``rows`` chunks of up to ``tokens`` tokens (and, for a mixed
        window, lies inside ``within``), or None. Ties go to fewer
        rows."""
        fits = [
            r for r in self.prefill_rects
            if r[0] >= rows and r[1] >= tokens
            and (within is None or (r[0] <= within[0] and r[1] <= within[1]))
        ]
        return min(fits, key=_area) if fits else None

    def build_prefill_batch_arrays(
        self, works: list[PrefillWork],
        within: Optional[tuple[int, int]] = None,
    ) -> dict[str, np.ndarray]:
        """Fuse several sequences' prefill chunks into one [B, T] step,
        padded to ``prefill_rect`` (pads write to the garbage slot 0 like
        decode pads). ``within``: the mixed window's rectangle the
        arrays are padded out to afterwards."""
        bs = self.block_size
        n = len(works)
        longest = max(len(w.tokens) for w in works)
        rect = self.prefill_rect(n, longest, within)
        if rect is None:
            # the planner grows a batch only into rectangles that exist
            raise ValueError(
                f"no prefill rectangle holds {n} x {longest} tokens "
                f"(within {within}): {self.prefill_rects}"
            )
        B, T = rect
        max_blocks = max(len(w.seq.block_table) for w in works)
        width = self._table_width(max_blocks)
        tokens = np.zeros((B, T), np.int32)
        positions = np.zeros((B, T), np.int32)
        slot_mapping = np.zeros((B * T,), np.int32)
        tables = np.zeros((B, width), np.int32)
        ctx = np.zeros((B,), np.int32)
        last_idx = np.zeros((B,), np.int32)
        mm_extra = None
        mm_mask = None
        for i, w in enumerate(works):
            t = len(w.tokens)
            tokens[i, :t] = w.tokens
            positions[i, :t] = np.arange(w.start_pos, w.start_pos + t)
            for j in range(t):
                pos = w.start_pos + j
                slot_mapping[i * T + j] = (
                    w.seq.block_table[pos // bs] * bs + pos % bs
                )
            self._fill_table(
                tables, i, w.seq, cols=(w.start_pos + t - 1) // bs + 1)
            ctx[i] = w.start_pos + t
            last_idx[i] = t - 1
            mm = self._mm_chunk_arrays(w.seq, w.start_pos, t, T)
            if mm is not None:
                if mm_extra is None:
                    D = mm["extra_embeds"].shape[-1]
                    mm_extra = np.zeros((B, T, D), np.float32)
                    mm_mask = np.zeros((B, T), bool)
                mm_extra[i] = mm["extra_embeds"][0]
                mm_mask[i] = mm["embeds_mask"][0]
        arrays = {
            "tokens": tokens,
            "positions": positions,
            "slot_mapping": slot_mapping,
            "block_tables": tables,
            "context_lens": ctx,
            "last_token_idx": last_idx,
        }
        if mm_extra is not None:
            arrays["extra_embeds"] = mm_extra
            arrays["embeds_mask"] = mm_mask
        return arrays

    @staticmethod
    def _mm_chunk_arrays(
        seq: Sequence, start: int, t: int, T: int
    ) -> Optional[dict[str, np.ndarray]]:
        """Embedding-injection arrays for the chunk [start, start+t), or
        None if no multimodal segment overlaps it (models/llama.py
        forward(extra_embeds=, embeds_mask=))."""
        if not seq.mm_segments:
            return None
        end = start + t
        D = seq.mm_segments[0][1].shape[-1]
        extra = np.zeros((1, T, D), np.float32)
        mask = np.zeros((1, T), bool)
        hit = False
        for offset, arr in seq.mm_segments:
            lo = max(start, offset)
            hi = min(end, offset + arr.shape[0])
            if lo >= hi:
                continue
            hit = True
            extra[0, lo - start : hi - start] = arr[lo - offset : hi - offset]
            mask[0, lo - start : hi - start] = True
        if not hit:
            return None
        return {"extra_embeds": extra, "embeds_mask": mask}

    def build_decode_arrays(self, seqs: list[Sequence]) -> dict[str, np.ndarray]:
        bs = self.block_size
        n = len(seqs)
        B = self._decode_batch(n)
        max_blocks = max(len(s.block_table) for s in seqs)
        width = self._table_width(max_blocks)
        tokens = np.zeros((B, 1), np.int32)
        positions = np.zeros((B, 1), np.int32)
        slot_mapping = np.zeros((B,), np.int32)
        tables = np.zeros((B, width), np.int32)
        ctx = np.zeros((B,), np.int32)
        # steps of the fused decode window each sequence will actually
        # keep — mirrors _plan_decode's lookahead clamp, so the device
        # step never writes KV past the blocks allocated for the seq
        valid_steps = np.zeros((B,), np.int32)
        for i, s in enumerate(seqs):
            all_toks = s.tokens.all_tokens()
            tokens[i, 0] = all_toks[-1]
            pos = s.total_len - 1
            positions[i, 0] = pos
            slot_mapping[i] = s.block_table[pos // bs] * bs + pos % bs
            self._fill_table(tables, i, s)
            ctx[i] = s.total_len
            valid_steps[i] = self._seq_lookahead(s)
        return {
            "valid_steps": valid_steps,
            "tokens": tokens,
            "positions": positions,
            "slot_mapping": slot_mapping,
            "block_tables": tables,
            "context_lens": ctx,
            "last_token_idx": np.zeros((B,), np.int32),
        }
