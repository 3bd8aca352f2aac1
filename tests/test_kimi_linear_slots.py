"""Kimi-Linear through the ENGINE on the CPU, the life of a state slot:
a slot reused after a finish or an abort holds no stale state, prefix
reuse is a counted miss, and what the family does not build is refused
when the engine starts (tests/test_kimi_linear_engine.py holds the
parity of prefill, decode and preemption with the plain reference)."""

import asyncio

import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine.config import EngineConfig
from dynamo_tpu.models import kimi_linear as kl
from dynamo_tpu.models.reference import kimi_linear as ref
from dynamo_tpu.protocols.common import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.runtime.engine import Context
from tests.kimi_tiny import tiny_kimi
from tests.test_kimi_linear_engine import (
    PROMPTS,
    assert_matches,
    engine_config,
    generate,
    launch,
)


@pytest.mark.parametrize("how", ["finish", "abort"])
async def test_reused_state_slot_holds_no_stale_state(how):
    """With ONE usable slot every request takes the slot the last one
    left (finished, or aborted mid-answer): the next answer is exact."""
    cfg = tiny_kimi()
    engine, params = await launch(cfg, max_batch_size=1)
    try:
        slots = engine.scheduler.state_slots
        assert slots.num_slots == 2
        ctx = Context()

        async def stop_at_3(n):
            if how == "abort" and n == 3:
                ctx.stop_generating()

        first, _ = await generate(engine, PROMPTS["three_chunks"], 8, "a",
                                  ctx=ctx, on_token=stop_at_3)
        # an abort lands a step or two late: steps already in flight deliver
        assert len(first) == 8 if how == "finish" else 3 <= len(first) < 8
        for _ in range(50):
            if slots.num_used == 0:
                break
            await asyncio.sleep(0.02)
        assert slots.num_used == 0
        toks, lps = await generate(engine, PROMPTS["one_chunk"], 8, "b")
        assert_matches(cfg, params, PROMPTS["one_chunk"], toks, lps)
        state = engine.debug_state()["state_plane"]
        assert state["total_slots"] == 1 and state["used_slots"] == 0
    finally:
        await engine.shutdown()


async def test_prefix_reuse_is_a_counted_miss():
    """The same prompt twice: the second is recomputed, counted as a
    query and not as a hit, and answers the same."""
    cfg = tiny_kimi()
    engine, params = await launch(cfg)
    try:
        a, _ = await generate(engine, PROMPTS["three_chunks"], 6, "p1")
        b, lps = await generate(engine, PROMPTS["three_chunks"], 6, "p2")
        assert a == b
        assert_matches(cfg, params, PROMPTS["three_chunks"], b, lps)
        sched = engine.scheduler
        assert (sched.prefix_queries, sched.prefix_hits) == (2, 0)
        assert sched.prompt_tokens_cached == 0
    finally:
        await engine.shutdown()


REFUSED = {
    "tp": dict(tensor_parallel_size=2),
    "ep": dict(expert_parallel_size=2),
    "pp": dict(pipeline_parallel_size=2),
    "spec": dict(spec_decode="ngram"),
    "kvbm": dict(host_kv_blocks=8),
    "int8_cache": dict(kv_cache_dtype="int8"),
}


@pytest.mark.parametrize("what", sorted(REFUSED))
async def test_unsupported_combinations_raise_at_start_up(what):
    from dynamo_tpu.engine.engine import JaxEngine

    with pytest.raises(ValueError, match="kimi_linear"):
        await JaxEngine.launch(engine_config(**REFUSED[what]),
                               model_config=tiny_kimi())


async def test_kv_transfer_is_refused():
    engine, _ = await launch()
    try:
        with pytest.raises(NotImplementedError, match="recurrent"):
            await engine.export_kv_blocks([1, 2])
        with pytest.raises(NotImplementedError, match="recurrent"):
            await engine.import_kv_blocks([1], np.zeros((1,)))
    finally:
        await engine.shutdown()
