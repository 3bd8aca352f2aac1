"""The set of prefill rectangles (scheduler.prefill_rects): ONE sorted
list of (rows, tokens) that the planner grows batches into, the array
builder pads to, and every start-up loop compiles — so no step can name
a shape that was not warmed. Under static shapes the set is the few
programs a serving engine compiles, with a single-row 512 rung; without,
what the product of the two class ladders leaves reachable: every shape a
step can name is the ``next_bucket`` pair it was before the set existed,
and start-up warms what it warmed then."""

import numpy as np
import pytest

from dynamo_tpu.engine.allocator import BlockAllocator
from dynamo_tpu.engine.config import EngineConfig
from dynamo_tpu.engine.scheduler import (
    Scheduler,
    Sequence,
    mixed_rect_of,
    prefill_rectangles,
)
from dynamo_tpu.protocols.common import PreprocessedRequest, StopConditions
from dynamo_tpu.tokens import TokenBlockSequence
from dynamo_tpu.utils.bucketing import next_bucket

BS = 128
PAD, CHUNK, BUDGET = 64, 1024, 4096
# what a serving engine builds from its defaults (engine.py, static_shapes)
SERVED = [(1, 128), (1, 256), (1, 512), (1, 1024), (8, 128), (8, 256), (32, 128)]


def static_prefill_rects(rows, budget, chunk):
    """As engine.py builds the set under static shapes."""
    return prefill_rectangles(rows, Scheduler.STATIC_CHUNK_TOKENS, budget, chunk,
                              Scheduler.STATIC_SINGLE_ROW_TOKENS)


def ladder_rects(rows, tokens):
    """The whole product of two ladders: no budget, no chunk size."""
    return prefill_rectangles(rows, tokens, rows[-1] * tokens[-1], tokens[-1])


def reachable_before(budget, chunk):
    """What the start-up loops walked before the set existed: the chunk
    ladder up to the rung that holds a chunk, and at each length the row
    counts whose area fits the budget (a single row whatever its area)."""
    pb, pc = Scheduler.BATCH_BUCKETS, Scheduler.CHUNK_BUCKETS
    return sorted(
        (b, c) for c in pc if c <= next_bucket(chunk, pc)
        for b in pb if not (b > pb[0] and b * c > budget)
    )


def _seq(n_tokens: int, request_id: str) -> Sequence:
    tokens = list(range(1, n_tokens + 1))
    return Sequence(
        request=PreprocessedRequest(
            request_id=request_id, token_ids=tokens,
            stop=StopConditions(max_tokens=4),
        ),
        tokens=TokenBlockSequence(tokens, block_size=BS),
    )


def _static_sched(rects=None) -> Scheduler:
    sched = Scheduler(
        BlockAllocator(1024, BS), BS, max_batch_size=PAD,
        prefill_chunk_size=CHUNK, max_prefill_tokens=BUDGET,
    )
    sched.prefill_rects = rects or static_prefill_rects([1, 8, 32, PAD], BUDGET, CHUNK)
    return sched


def _smallest_cover(rects, n, t):
    fits = [r for r in rects if r[0] >= n and r[1] >= t]
    return min(fits, key=lambda r: (r[0] * r[1], r)) if fits else None


def test_the_served_set_is_todays_six_and_the_single_row_512():
    rects = static_prefill_rects([1, 8, 32, PAD], BUDGET, CHUNK)
    assert rects == SERVED == sorted(rects)
    # the rung exists at ONE row count
    assert [r for r, t in rects if t == 512] == [1]


@pytest.mark.parametrize("chunk,longest", [(16, 128), (256, 256), (300, 1024),
                                           (512, 1024), (1024, 1024), (2048, 4096)])
def test_the_set_stops_at_the_length_that_holds_a_chunk(chunk, longest):
    rects = static_prefill_rects([1, 8, 32, PAD], BUDGET, chunk)
    assert max(t for _, t in rects) == longest
    # a lone chunk of any length up to the chunk size has a rectangle
    sched = _static_sched(rects)
    assert all(sched.prefill_rect(1, t) for t in (1, chunk // 2 or 1, chunk))


def test_every_n_and_t_gets_the_smallest_covering_rectangle_or_none():
    sched = _static_sched()
    for n in range(1, PAD + 1):
        for t in range(1, CHUNK + 1):
            got = sched.prefill_rect(n, t)
            assert got == _smallest_cover(SERVED, n, t), (n, t)
            assert got is None or got in SERVED
            if n == 1:
                assert got is not None


@pytest.mark.parametrize("t", [1, 100, 128, 129, 256, 257, 300, 512, 513, 1024])
@pytest.mark.parametrize("n", [1, 2, 3, 8, 9, 32, 33, 64])
def test_planner_and_builder_stay_inside_the_set(n, t):
    """``n`` prompts of ``t`` tokens waiting at once: every prefill step
    until all have run is a rectangle of the set, the smallest that
    covers the rows the planner gave it."""
    sched = _static_sched()
    for i in range(n):
        sched.add_request(_seq(t, f"r{i}"))
    left = n
    while left:
        plan = sched.plan()
        assert plan.kind == "prefill"
        works = plan.prefill_batch
        shape = sched.build_prefill_batch_arrays(works)["tokens"].shape
        assert shape in SERVED
        assert shape == _smallest_cover(SERVED, len(works), t)
        assert shape[0] * shape[1] <= BUDGET or len(works) == 1
        for w in works:
            sched.complete_prefill_chunk(w)
        left -= len(works)


def test_a_lone_300_token_chunk_runs_1x512():
    sched = _static_sched()
    sched.add_request(_seq(300, "a"))
    works = sched.plan().prefill_batch
    assert sched.build_prefill_batch_arrays(works)["tokens"].shape == (1, 512)


@pytest.mark.parametrize("lengths", [(300, 300), (200, 300), (300, 100),
                                     (512, 257), (100, 100, 300), (600, 300)])
def test_rows_past_256_tokens_never_share_a_step(lengths):
    """No rectangle of several rows is longer than 256 tokens: such rows
    run one by one, each in its own single-row rectangle."""
    sched = _static_sched()
    for i, t in enumerate(lengths):
        sched.add_request(_seq(t, f"r{i}"))
    shapes = []
    while sched.prefilling or sched.waiting:
        works = sched.plan().prefill_batch
        shapes.append(sched.build_prefill_batch_arrays(works)["tokens"].shape)
        for w in works:
            sched.complete_prefill_chunk(w)
    assert all(s in SERVED for s in shapes)
    assert all(rows == 1 for rows, tokens in shapes if tokens > 256)


def test_the_builder_refuses_what_the_planner_would_not_plan():
    sched = _static_sched()
    for i in range(2):
        sched.add_request(_seq(300, f"r{i}"))
    sched.plan()
    works = sched._plan_prefill_batch()
    assert len(works) == 1
    forced = works + [works[0]]
    with pytest.raises(ValueError, match="no prefill rectangle"):
        sched.build_prefill_batch_arrays(forced)


@pytest.mark.parametrize("t", [1, 16, 17, 100, 128, 300, 512, 1000, 1024])
@pytest.mark.parametrize("n", [1, 2, 3, 5, 8, 9, 33, 64])
def test_without_static_shapes_a_shape_is_the_two_ladders_product(n, t):
    sched = Scheduler(BlockAllocator(64, BS), BS, max_batch_size=PAD,
                      prefill_chunk_size=CHUNK, max_prefill_tokens=BUDGET)
    assert sched.prefill_rects == reachable_before(BUDGET, CHUNK)
    pair = (next_bucket(n, Scheduler.BATCH_BUCKETS),
            next_bucket(t, Scheduler.CHUNK_BUCKETS))
    # the pair where a step could have that shape (one row, or an area
    # inside the budget), and NO rectangle where the planner refused it
    reachable = pair[0] == 1 or pair[0] * pair[1] <= BUDGET
    assert sched.prefill_rect(n, t) == (pair if reachable else None)


@pytest.mark.parametrize("budget,chunk", [(4096, 1024), (32, 32), (16, 16),
                                          (8192, 512), (100, 300), (4096, 4096)])
def test_without_static_shapes_the_set_is_what_start_up_walked_before(budget, chunk):
    sched = Scheduler(BlockAllocator(64, BS), BS, max_batch_size=8,
                      prefill_chunk_size=chunk, max_prefill_tokens=budget)
    assert sched.prefill_rects == reachable_before(budget, chunk)
    # nothing past the budget but single rows, nothing past a chunk's rung
    assert all(r == 1 or r * t <= budget for r, t in sched.prefill_rects)
    assert max(t for _, t in sched.prefill_rects) == next_bucket(
        chunk, Scheduler.CHUNK_BUCKETS)


@pytest.mark.parametrize("lengths", [(16,) * 4, (10, 16, 3), (32, 32), (20, 5, 5),
                                     (16,) * 9, (1,) * 8, (32, 1, 1, 1)])
def test_without_static_shapes_the_planner_plans_as_before(lengths):
    """The planner over the reachable set against the rule it had over
    the two ladders: grow while the ``next_bucket`` area fits the budget
    or does not grow."""
    budget, chunk = 64, 32
    sched = Scheduler(BlockAllocator(256, 4), 4, max_batch_size=16,
                      prefill_chunk_size=chunk, max_prefill_tokens=budget)
    for i, t in enumerate(lengths):
        sched.add_request(_seq(t, f"r{i}"))
    works = sched.plan().prefill_batch
    pb, pc = Scheduler.BATCH_BUCKETS, Scheduler.CHUNK_BUCKETS
    n, longest, cur = 0, 0, 0
    for t in lengths:
        new = max(longest, min(t, chunk))
        area = next_bucket(n + 1, pb) * next_bucket(new, pc)
        if n and area > budget and area > cur:
            break
        n, longest, cur = n + 1, new, area
    assert len(works) == n
    arrays = sched.build_prefill_batch_arrays(works)
    assert arrays["tokens"].shape == (next_bucket(n, pb), next_bucket(longest, pc))


def test_the_ladders_reach_a_batch_or_chunk_past_their_end():
    sched = Scheduler(BlockAllocator(64, BS), BS, max_batch_size=300,
                      prefill_chunk_size=8192, max_prefill_tokens=512 * 8192)
    assert sched.prefill_rect(300, 8192) == (512, 8192)


@pytest.mark.parametrize("rows,length,cap,want", [
    (8, 256, 4096, (8, 256)),     # the default
    (8, 512, 4096, (8, 256)),     # 512 exists at one row only: not 8 x 512
    (8, 1024, 4096, (8, 256)),
    (1, 512, 4096, (1, 512)),
    (3, 200, 4096, (8, 256)),
    (40, 100, 4096, (32, 128)),   # 64 x 128 is no rectangle
    (8, 256, 1024, (1, 256)),     # the budget takes rows away
    (8, 256, 100, None),
])
def test_the_mixed_window_lands_on_a_rectangle_of_its_row_count(
    rows, length, cap, want
):
    assert mixed_rect_of(SERVED, rows, length, cap) == want


@pytest.mark.parametrize("rows,length,cap", [(8, 256, 4096), (3, 100, 4096),
                                             (8, 1024, 4096), (64, 4096, 4096),
                                             (8, 256, 256), (2, 16, 8)])
def test_on_the_ladders_the_mixed_window_is_normalised_as_before(rows, length, cap):
    """The rule the engine had for two ladders: round both up, shorten
    while the length alone passes the cap, then drop rows."""
    pb, pc = Scheduler.BATCH_BUCKETS, Scheduler.CHUNK_BUCKETS
    r, t = next_bucket(rows, pb), next_bucket(length, pc)
    while t > cap and t > pc[0]:
        t = pc[pc.index(t) - 1]
    while r * t > cap and r > pb[0]:
        r = pb[pb.index(r) - 1]
    want = (r, t) if r * t <= cap else None
    assert mixed_rect_of(ladder_rects(pb, pc), rows, length, cap) == want


# -- the engine: what it builds, warms and counts ---------------------------

def _tiny_model():
    from dynamo_tpu.models.config import ModelConfig

    return ModelConfig(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=256,
    )


async def _launch(**kw):
    from dynamo_tpu.engine.engine import JaxEngine

    cfg = dict(
        model_path="", model_name="rects", random_weights=True,
        num_blocks=64, block_size=4, max_batch_size=PAD,
        prefill_chunk_size=CHUNK, max_prefill_tokens=BUDGET,
        max_model_len=128, kv_cache_dtype="float32", prewarm=False,
    )
    cfg.update(kw)
    return await JaxEngine.launch(EngineConfig(**cfg), model_config=_tiny_model())


async def test_a_static_engine_builds_the_served_set_from_its_limits():
    engine = await _launch(static_shapes=True)
    try:
        assert engine.scheduler.prefill_rects == SERVED
        state = engine.debug_state()["scheduler"]
        assert state["prefill_rects"] == [f"{r}x{t}" for r, t in SERVED]
    finally:
        await engine.shutdown()


@pytest.mark.parametrize("rows,length,wide,want", [
    (8, 512, 0, (8, 256)),      # 512 exists at one row: never 8 x 512
    (8, 256, 0, (8, 256)),
    (8, 256, 1024, (8, 256)),
    (4, 512, 0, (4, 1024)),     # 4 rows x 1 024 fills the budget: a rectangle
])
async def test_a_mixed_window_of_512_lands_on_a_warmed_rectangle(
    rows, length, wide, want
):
    engine = await _launch(
        static_shapes=True, decode_steps=4, mixed_prefill_rows=rows,
        mixed_prefill_len=length, mixed_prefill_wide_len=wide,
    )
    try:
        sched = engine.scheduler
        got = (engine.config.mixed_prefill_rows, engine.config.mixed_prefill_len)
        assert got == want and got in sched.prefill_rects
        assert (sched.mixed_prefill_rows, sched.mixed_prefill_len) == got
        assert (rows, 512) not in sched.prefill_rects
        if wide:
            # the wide window is ONE more rectangle, not a row of a ladder
            assert engine._wide_rect == (2, 1024)
            assert sched.prefill_rects == sorted(SERVED + [(2, 1024)])
            assert sched.prefill_rect(2, 100, within=(2, 1024)) == (2, 1024)
        elif rows == 8:
            assert sched.prefill_rects == SERVED
    finally:
        await engine.shutdown()


class _RecordingStep:
    """Stands in for the jitted step: records what it is asked to run
    and to lower; returns arrays of the right shapes."""

    def __init__(self):
        self.calls, self.guided, self.lowered = [], [], []

    def __call__(self, params, k, v, tokens, positions, slots, tables, ctx,
                 last, sampling):
        import jax.numpy as jnp

        shape = tuple(tokens.shape)
        (self.guided if "allow_mask" in sampling else self.calls).append(shape)
        rows = shape[0]
        return (jnp.zeros((rows,), jnp.int32), jnp.zeros((rows,), jnp.float32),
                k, v)

    def lower(self, *args):
        import jax

        self.lowered.append(tuple(args[3].shape))
        return jax.jit(lambda: 0).lower()


async def test_prewarm_and_guided_prewarm_compile_exactly_the_set():
    engine = await _launch(static_shapes=True, prewarm_guided=True)
    try:
        step = _RecordingStep()

        def warm():
            engine._step_fn = step
            engine.device_report.pop("mosaic_calls_in_step", None)
            engine._prewarm()

        await engine.acall_on_thread(warm)
        decode = [(b, 1) for b in (4, 32, 64)]
        prefill = [s for s in step.calls if s[1] > 1]
        # the set once, in its own (sorted) order, then the first
        # rectangle again: it alone met init_cache's arrays and not a
        # step's own outputs
        assert prefill == SERVED + SERVED[:1]
        assert {s for s in step.calls if s[1] == 1} == set(decode)
        assert [s for s in step.guided if s[1] > 1] == SERVED
        assert {s for s in step.guided if s[1] == 1} == set(decode)
        # the first program is lowered once, for its count of kernels
        assert step.lowered == [SERVED[0]]
        assert engine.device_report["mosaic_calls_in_step"] == 0
    finally:
        await engine.shutdown()


async def test_without_static_shapes_prewarm_walks_what_it_walked_before():
    """Prewarm is on wherever the backend is a TPU, static shapes or not:
    it compiles the shapes a step can name (5 here), never the whole
    product of the ladders (81 rectangles, up to 256 x 4 096)."""
    budget, chunk = 64, 32
    engine = await _launch(static_shapes=False, max_batch_size=4, prewarm_guided=True,
                           prefill_chunk_size=chunk, max_prefill_tokens=budget)
    try:
        step = _RecordingStep()

        def warm():
            engine._step_fn = step
            engine._prewarm()

        await engine.acall_on_thread(warm)
        want = reachable_before(budget, chunk)
        assert len(want) == 5 and max(r * t for r, t in want) == budget
        assert engine.scheduler.prefill_rects == want
        assert [s for s in step.calls if s[1] > 1] == want + want[:1]
        assert [s for s in step.guided if s[1] > 1] == want
    finally:
        await engine.shutdown()


@pytest.mark.parametrize("tp", [1, 2])
async def test_prewarm_leaves_no_shape_to_compile_against_a_steps_own_caches(tp):
    """One pass over the set and the first rectangle again is enough, on
    one device and on a tp mesh: the first call meets init_cache's arrays,
    whose sharding XLA spells otherwise in a step's outputs — another jit
    signature, so the re-warm of the first rectangle compiles — and after
    it a whole second prewarm finds every step program compiled."""
    engine = await _launch(static_shapes=False, max_batch_size=4,
                           prefill_chunk_size=32, max_prefill_tokens=64,
                           tensor_parallel_size=tp)
    try:
        jitted, fresh = engine._step_fn, []

        def step(*args):
            before = jitted._cache_size()
            out = jitted(*args)
            if jitted._cache_size() > before and args[3].shape[1] > 1:
                fresh.append(tuple(args[3].shape))
            return out

        step.lower = jitted.lower
        sizes = []

        def warm():
            engine._step_fn = step
            for _ in range(2):
                engine._prewarm()
                sizes.append(jitted._cache_size())
            engine._step_fn = jitted

        await engine.acall_on_thread(warm)
        rects = engine.scheduler.prefill_rects
        assert fresh == rects + rects[:1]   # the re-warm was a signature of its own
        assert sizes[1] == sizes[0]         # and nothing is left for a request
    finally:
        await engine.shutdown()


def test_a_lowering_is_shared_with_the_call_that_follows():
    """What _prewarm's count relies on: ``jit(f).lower(args)`` then
    ``jit(f)(args)`` runs the Python of ``f`` once — the count of kernels
    in the first program's text costs no second trace."""
    import jax
    import jax.numpy as jnp

    runs = []

    def f(x):
        runs.append(1)
        return x + 1

    jf = jax.jit(f)
    x = jnp.ones((3,))
    jf.lower(x).as_text()
    jf(x)
    assert len(runs) == 1


async def test_the_engine_counts_real_and_padded_prefill_tokens():
    from tests.test_engine import _generate

    engine = await _launch(static_shapes=False, max_batch_size=4,
                           prefill_chunk_size=16, max_prefill_tokens=16)
    try:
        before = engine.program_counts()
        assert before["prefill_tokens_real"] == before["prefill_tokens_padded"] == 0
        await _generate(engine, list(range(1, 12)), max_tokens=3, request_id="c1")
        await _generate(engine, list(range(20, 60)), max_tokens=3, request_id="c2")
        counts = engine.program_counts()
        # 11 tokens in a 1 x 16 rectangle; 40 as 16 + 16 + 8 in three
        assert counts["prefill_tokens_real"] == 11 + 40
        assert counts["prefill_tokens_padded"] == 16 + 3 * 16
        state = engine.debug_state()["scheduler"]
        assert state["prefill_tokens_real"] == 51
        assert state["prefill_tokens_padded"] == 64
    finally:
        await engine.shutdown()


def test_count_prefill_reads_the_builders_arrays():
    from dynamo_tpu.engine.engine import JaxEngine

    sched = _static_sched()
    for i, t in enumerate((100, 37, 128)):
        sched.add_request(_seq(t, f"r{i}"))
    arrays = sched.build_prefill_batch_arrays(sched.plan().prefill_batch)
    assert arrays["tokens"].shape == (8, 128)

    class Counts:
        _prefill_tokens = [0, 0]

    JaxEngine._count_prefill(Counts, arrays)
    assert Counts._prefill_tokens == [100 + 37 + 128, 8 * 128]
    assert np.count_nonzero(arrays["context_lens"]) == 3
