"""``deepseek_v3`` (Kanana-2's family) through the ENGINE on the CPU:
what the served path returns — chosen ids and their logprobs, prefill
then decode through the latent pages — against the plain reference's
full forward pass, in float32 so that they meet to rounding; and what a
family that owns its pages WITHOUT recurrent state gets from the engine:
no state slots, no slot column, the prefix cache on."""

import asyncio

import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine.config import EngineConfig
from dynamo_tpu.models import deepseek_v3 as ds
from dynamo_tpu.models.reference import deepseek_v3 as ref
from tests.deepseek_v3_tiny import tiny_deepseek
from tests.test_kimi_linear_engine import generate

TOL = 2e-4   # float32 end to end: differences are summation order


def engine_config(**kw) -> EngineConfig:
    defaults = dict(
        model_name="tiny-deepseek", random_weights=True, seed=5, num_blocks=64,
        block_size=8, max_batch_size=4, prefill_chunk_size=16,
        max_model_len=128, kv_cache_dtype="float32", static_shapes=False,
    )
    defaults.update(kw)
    return EngineConfig(**defaults)


async def launch(cfg=None, **kw):
    """An engine whose parameters are the seeded draw in float32."""
    from dynamo_tpu.engine.engine import JaxEngine

    cfg = cfg or tiny_deepseek()
    engine = await JaxEngine.launch(engine_config(**kw), model_config=cfg)
    params = ds.init_params(cfg, seed=5, dtype=jnp.float32)
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


PROMPTS = {
    "one_chunk": list(range(3, 14)),                 # 11 tokens < chunk 16
    "chunk_edge": list(range(20, 36)),               # exactly one chunk
    "three_chunks": [(7 * i) % 251 for i in range(41)],   # 16 + 16 + 9
    "single_token": [9],
}


@pytest.mark.parametrize("name", sorted(PROMPTS))
async def test_prefill_then_decode_matches_reference(name):
    """A later chunk starts at a position > 0 and attends the earlier
    chunks' rows in the pages, rotary part and all."""
    cfg = tiny_deepseek()
    engine, params = await launch(cfg)
    try:
        toks, lps = await generate(engine, PROMPTS[name], 12, name)
        assert len(toks) == 12
        assert_matches(cfg, params, PROMPTS[name], toks, lps)
    finally:
        await engine.shutdown()


@pytest.mark.parametrize("decode_steps", [1, 3])
async def test_batched_rows_of_unequal_length(decode_steps):
    cfg = tiny_deepseek()
    engine, params = await launch(cfg, decode_steps=decode_steps)
    try:
        names = sorted(PROMPTS)
        got = await asyncio.gather(*[
            generate(engine, PROMPTS[n], 9, n) for n in names])
        for n, (toks, lps) in zip(names, got):
            assert_matches(cfg, params, PROMPTS[n], toks, lps)
    finally:
        await engine.shutdown()


async def test_the_engine_gives_pages_without_a_state_plane():
    """Page ownership and recurrent state are two questions: this family
    answers yes and no."""
    cfg = tiny_deepseek()
    assert cfg.owns_pages and not cfg.has_recurrent_state
    engine, _ = await launch(cfg)
    try:
        sched = engine.scheduler
        assert sched.state_slots is None and sched.table_extra == 0
        assert sched.allocator.enable_prefix_caching
        assert set(engine.v_cache) == {"counts"}
        assert engine.k_cache["latent"].shape == (3, 64 * 8, 128)
        state = engine.debug_state()
        assert "state_plane" not in state
        plane = state["page_plane"]
        assert plane["page_pool_bytes"] == 3 * 64 * 8 * 128 * 4
        assert plane["pages_total"] == 63 and plane["pages_in_use"] == 0
    finally:
        await engine.shutdown()


async def test_a_shared_multi_page_prefix_is_a_hit_and_the_rest_alone_is_prefilled():
    """The second request shares 32 tokens = 4 pages with the first: the
    allocator reports the hit, the prefill computes only the rest (a
    start position > 0 over cached pages), and the answer equals the
    same request served cold."""
    cfg = tiny_deepseek()
    shared = [(11 * i) % 249 + 3 for i in range(32)]
    first, second = shared + [5, 6, 7], shared + [90, 91, 92, 93, 94]
    cold_engine, params = await launch(cfg)
    try:
        cold, cold_lps = await generate(cold_engine, second, 8, "cold")
        assert cold_engine.scheduler.prefix_hits == 0
    finally:
        await cold_engine.shutdown()
    engine, params = await launch(cfg)
    try:
        await generate(engine, first, 4, "a")
        sched = engine.scheduler
        before = engine.program_counts()
        warm, warm_lps = await generate(engine, second, 8, "b")
        after = engine.program_counts()
        assert (sched.prefix_queries, sched.prefix_hits) == (2, 1)
        assert sched.prompt_tokens_cached == 32
        # only the 5 new tokens were prefilled, in every one of 3 layers
        assert after["mla_prefill_query_tokens"] \
            - before["mla_prefill_query_tokens"] == 3 * 5
        assert after["prefill_tokens_real"] - before["prefill_tokens_real"] == 5
        assert warm == cold
        np.testing.assert_allclose(warm_lps, cold_lps, atol=TOL)
        assert_matches(cfg, params, second, warm, warm_lps)
        plane = engine.debug_state()["page_plane"]
        assert plane["pages_cached_reusable"] >= 4
    finally:
        await engine.shutdown()


async def test_prefill_pairs_are_counted_by_position(monkeypatch):
    """A token at position p sees p + 1 keys: 41 cold tokens are
    41 * 42 / 2 pairs a layer, whatever the chunking."""
    monkeypatch.setattr(ds, "PAIR_UNIT", 1)
    cfg = tiny_deepseek()
    engine, _ = await launch(cfg)
    try:
        await generate(engine, PROMPTS["three_chunks"], 2, "p")
        counts = engine.program_counts()
        assert counts["mla_prefill_query_tokens"] == 3 * 41
        assert counts["mla_prefill_pairs"] == 3 * (41 * 42 // 2)
        assert counts["moe_layer_calls"] > 0
    finally:
        await engine.shutdown()


async def test_preempted_row_resumes_and_still_meets_the_reference():
    cfg = tiny_deepseek()
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
                    hit.append(True)
                await engine.acall_on_thread(do)

        (toks, lps), (toks2, lps2) = await asyncio.gather(
            generate(engine, PROMPTS["three_chunks"], 10, "victim",
                     on_token=preempt_once),
            generate(engine, PROMPTS["one_chunk"], 10, "bystander"))
        assert hit and sched.preemptions == 1
        # it came back through the prefix cache: its own pages were a hit
        assert sched.prefix_hits >= 1
        assert_matches(cfg, params, PROMPTS["three_chunks"], toks, lps)
        assert_matches(cfg, params, PROMPTS["one_chunk"], toks2, lps2)
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

    with pytest.raises(ValueError, match="deepseek_v3"):
        await JaxEngine.launch(engine_config(**REFUSED[what]),
                               model_config=tiny_deepseek())


async def test_a_checkpoint_kv_transfer_and_injected_embeddings_are_refused(tmp_path):
    from dynamo_tpu.models import loader

    with pytest.raises(NotImplementedError, match="deepseek_v3"):
        loader.resolve_model(str(tmp_path), model_config=tiny_deepseek(),
                             random_weights=False)
    engine, params = await launch()
    try:
        with pytest.raises(NotImplementedError, match="lays its pages out"):
            await engine.export_kv_blocks([1, 2])
        with pytest.raises(NotImplementedError, match="lays its pages out"):
            await engine.import_kv_blocks([1], np.zeros((1,)))
    finally:
        await engine.shutdown()
    cfg = tiny_deepseek()
    pages, counts = ds.init_cache(cfg, 4, 8, dtype=jnp.float32)
    z = np.zeros((1, 1), np.int32)
    with pytest.raises(NotImplementedError, match="injected embeddings"):
        ds.forward(cfg, params, pages, counts, z, z, z.reshape(-1),
                   np.zeros((1, 2), np.int32), np.ones((1,), np.int32),
                   np.zeros((1,), np.int32), 8,
                   extra_embeds=jnp.zeros((1, 1, 64)))
