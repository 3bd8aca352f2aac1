"""Nemotron-H through the ENGINE on the CPU: what the served path returns
— chosen ids and their logprobs, prefill then decode through the K/V pages
and the state plane — against the plain reference's full forward pass, in
float32 so that they meet to rounding; the life of a state slot; what is
refused at start-up; the counts the family keeps; ``/debug/state``'s
``state_plane`` with this family's shapes and bytes."""

import asyncio

import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine.config import EngineConfig
from dynamo_tpu.models import nemotron_h as nh
from dynamo_tpu.models.reference import nemotron_h as ref
from dynamo_tpu.runtime.engine import Context
from tests.nemotron_h_tiny import tiny_nemotron_h
from tests.test_kimi_linear_engine import PROMPTS, generate

TOL = 5e-4   # float32 end to end: differences are summation order


def engine_config(**kw) -> EngineConfig:
    defaults = dict(
        model_name="tiny-nemotron-h", random_weights=True, seed=5, num_blocks=64,
        block_size=8, max_batch_size=4, prefill_chunk_size=16,
        max_model_len=128, kv_cache_dtype="float32", static_shapes=False,
    )
    defaults.update(kw)
    return EngineConfig(**defaults)


async def launch(cfg=None, **kw):
    """An engine whose parameters are the seeded draw in float32."""
    from dynamo_tpu.engine.engine import JaxEngine

    cfg = cfg or tiny_nemotron_h()
    engine = await JaxEngine.launch(engine_config(**kw), model_config=cfg)
    params = nh.init_params(cfg, seed=5, dtype=jnp.float32)
    await engine.acall_on_thread(lambda: setattr(engine, "params", params))
    return engine, params


def assert_matches(cfg, params, prompt, toks, lps):
    """The reference's logprob of each chosen id, and its own greedy id,
    from one full forward pass over prompt + chosen."""
    seq = np.asarray([list(prompt) + list(toks)], np.int32)
    logits = np.asarray(ref.forward(cfg, params, jnp.asarray(seq)))[0]
    at = np.arange(len(prompt) - 1, len(seq[0]) - 1)
    top = logits[at].max(-1, keepdims=True)
    lp = logits[at] - top - np.log(np.exp(logits[at] - top).sum(-1, keepdims=True))
    assert toks == logits[at].argmax(-1).tolist()
    np.testing.assert_allclose(lps, lp[np.arange(len(at)), np.asarray(toks)], atol=TOL)


@pytest.mark.parametrize("name", sorted(PROMPTS))
async def test_prefill_then_decode_matches_reference(name):
    """Chunk boundaries fall inside every Mamba-2 layer's recurrence and
    convolution: state and tail are carried through the state plane; the
    attention layer reads its earlier chunks' pages."""
    cfg = tiny_nemotron_h()
    engine, params = await launch(cfg)
    try:
        toks, lps = await generate(engine, PROMPTS[name], 12, name)
        assert len(toks) == 12
        assert_matches(cfg, params, PROMPTS[name], toks, lps)
    finally:
        await engine.shutdown()


@pytest.mark.parametrize("decode_steps", [1, 3])
async def test_mixed_prefill_and_decode_steps_of_unequal_rows(decode_steps):
    """Arrivals staggered so that prefill chunks and decode rows share
    steps; right-padded rows enter neither state nor tail."""
    cfg = tiny_nemotron_h()
    engine, params = await launch(cfg, decode_steps=decode_steps)
    try:
        names = sorted(PROMPTS)

        async def later(i, n):
            await asyncio.sleep(0.05 * i)
            return await generate(engine, PROMPTS[n], 9, n)

        got = await asyncio.gather(*[later(i, n) for i, n in enumerate(names)])
        for n, (toks, lps) in zip(names, got):
            assert_matches(cfg, params, PROMPTS[n], toks, lps)
        counts = engine.program_counts()
        assert set(nh.COUNT_NAMES) <= set(counts)
        assert counts["recurrent_prefill_tokens"] >= sum(
            len(PROMPTS[n]) for n in names if len(PROMPTS[n]) > 1)
        assert counts["recurrent_prefill_chunks"] >= 6
        assert counts["moe_layer_calls"] % 2 == 0           # two expert layers a step
        assert counts["moe_experts_touched"] <= 8 * counts["moe_layer_calls"]
        assert counts["state_slot_steps_total"] > counts["state_slot_steps_used"] > 0
    finally:
        await engine.shutdown()


async def test_preempted_row_is_recomputed_exactly():
    cfg = tiny_nemotron_h()
    engine, params = await launch(cfg)
    try:
        sched = engine.scheduler
        hit = []

        async def preempt_once(n_tokens):
            if n_tokens == 4 and not hit:
                def do():
                    victim = next(s for s in sched.running
                                  if s.request_id == "victim")
                    slot = victim.state_slot
                    sched._preempt(victim)
                    hit.append((slot, victim.state_slot))
                await engine.acall_on_thread(do)

        (toks, lps), (toks2, lps2) = await asyncio.gather(
            generate(engine, PROMPTS["three_chunks"], 10, "victim",
                     on_token=preempt_once),
            generate(engine, PROMPTS["one_chunk"], 10, "bystander"))
        assert hit and hit[0][0] > 0 and hit[0][1] == 0
        assert sched.preemptions == 1
        assert_matches(cfg, params, PROMPTS["three_chunks"], toks, lps)
        assert_matches(cfg, params, PROMPTS["one_chunk"], toks2, lps2)
    finally:
        await engine.shutdown()


@pytest.mark.parametrize("how", ["finish", "abort"])
async def test_reused_state_slot_holds_no_stale_state(how):
    """With ONE usable slot every request takes the slot the last one
    left (finished, or aborted mid-answer): the next answer is exact."""
    cfg = tiny_nemotron_h()
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
        assert len(first) == 8 if how == "finish" else 3 <= len(first) < 8
        for _ in range(50):
            if slots.num_used == 0:
                break
            await asyncio.sleep(0.02)
        assert slots.num_used == 0
        toks, lps = await generate(engine, PROMPTS["one_chunk"], 8, "b")
        assert_matches(cfg, params, PROMPTS["one_chunk"], toks, lps)
        plane = engine.debug_state()["state_plane"]
        assert plane["total_slots"] == 1 and plane["used_slots"] == 0
        # the K/V pool's and the plane's bytes, as the family sizes them: 2
        # Mamba-2 layers x 2 slots x (8 heads x [8, 16] float32 + 3 tail rows
        # of 8 * 8 + 2 * 2 * 16 channels), and the five counts
        assert plane["page_pool_bytes"] == 64 * nh.page_bytes_per_block(cfg, 8, 4)
        assert nh.state_bytes(cfg, 2, 4) == 2 * 2 * (8 * 8 * 16 + 3 * 128) * 4
        assert plane["bytes"] == nh.state_bytes(cfg, 2, 4) + 4 * len(nh.COUNT_NAMES)
    finally:
        await engine.shutdown()


async def test_prefix_reuse_is_a_counted_miss():
    cfg = tiny_nemotron_h()
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
    "dp": dict(data_parallel_size=2),
    "spec": dict(spec_decode="ngram"),
    "kvbm": dict(host_kv_blocks=8),
    "int8_cache": dict(kv_cache_dtype="int8"),
}


@pytest.mark.parametrize("what", sorted(REFUSED))
async def test_unsupported_combinations_raise_at_start_up(what):
    from dynamo_tpu.engine.engine import JaxEngine

    with pytest.raises(ValueError, match="nemotron_h"):
        await JaxEngine.launch(engine_config(**REFUSED[what]),
                               model_config=tiny_nemotron_h())


async def test_a_checkpoint_kv_transfer_and_injected_embeddings_are_refused(tmp_path):
    from dynamo_tpu.models import loader

    with pytest.raises(NotImplementedError, match="nemotron_h"):
        loader.resolve_model(str(tmp_path), model_config=tiny_nemotron_h(),
                             random_weights=False)
    engine, params = await launch()
    try:
        with pytest.raises(NotImplementedError, match="recurrent"):
            await engine.export_kv_blocks([1, 2])
        with pytest.raises(NotImplementedError, match="recurrent"):
            await engine.import_kv_blocks([1], np.zeros((1,)))
    finally:
        await engine.shutdown()
    cfg = tiny_nemotron_h()
    pages, state = nh.init_cache(cfg, 4, 8, dtype=jnp.float32)
    z = np.zeros((1, 1), np.int32)
    with pytest.raises(NotImplementedError, match="injected embeddings"):
        nh.forward(cfg, params, pages, state, z, z, z.reshape(-1),
                   np.zeros((1, 2), np.int32), np.ones((1,), np.int32),
                   np.zeros((1,), np.int32), 8,
                   extra_embeds=jnp.zeros((1, 1, 64)))
