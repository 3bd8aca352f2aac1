"""``glm_moe_dsa`` (GLM-5's family) through the ENGINE on the CPU: what
the served path returns — chosen ids and their logprobs, chunked prefill
then decode through BOTH page planes, the indexer selecting 12 of up to
53 keys — against the plain reference's full forward pass in float32; and
what a family with two per-token planes under one page id gets from the
engine: one allocator, one table, the prefix cache on and serving the
indexer's keys with the latents, every page of both planes returned."""

import asyncio

import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine.config import EngineConfig
from dynamo_tpu.models import glm_moe_dsa as glm
from dynamo_tpu.models.reference import glm_moe_dsa as ref
from tests.glm_moe_dsa_tiny import tiny_glm
from tests.test_kimi_linear_engine import generate

TOL = 2e-4   # float32 end to end: differences are summation order


def engine_config(**kw) -> EngineConfig:
    defaults = dict(
        model_name="tiny-glm", random_weights=True, seed=5, num_blocks=64,
        block_size=8, max_batch_size=4, prefill_chunk_size=16,
        max_model_len=128, kv_cache_dtype="float32", static_shapes=False,
    )
    defaults.update(kw)
    return EngineConfig(**defaults)


async def launch(cfg=None, **kw):
    """An engine whose parameters are the seeded draw in float32."""
    from dynamo_tpu.engine.engine import JaxEngine

    cfg = cfg or tiny_glm()
    engine = await JaxEngine.launch(engine_config(**kw), model_config=cfg)
    params = glm.init_params(cfg, seed=5, dtype=jnp.float32)
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
    "within_top_k": list(range(3, 12)),              # 9 tokens: the dense regime
    "chunk_edge": list(range(20, 36)),               # exactly one chunk, 16 > 12 keys
    "three_chunks": [(7 * i) % 251 for i in range(41)],   # 16 + 16 + 9, 41 keys
    "single_token": [9],
}


@pytest.mark.parametrize("name", sorted(PROMPTS))
async def test_prefill_then_decode_matches_reference(name):
    """A later chunk starts at a position > 0 and scores, selects among
    and attends the earlier chunks' rows in both planes; 12 decode steps
    cross a page (8 tokens) at least once."""
    cfg = tiny_glm()
    engine, params = await launch(cfg)
    try:
        toks, lps = await generate(engine, PROMPTS[name], 12, name)
        assert len(toks) == 12
        assert_matches(cfg, params, PROMPTS[name], toks, lps)
    finally:
        await engine.shutdown()


@pytest.mark.parametrize("decode_steps", [1, 3])
async def test_batched_rows_of_unequal_length(decode_steps):
    cfg = tiny_glm()
    engine, params = await launch(cfg, decode_steps=decode_steps)
    try:
        names = sorted(PROMPTS)
        got = await asyncio.gather(*[
            generate(engine, PROMPTS[n], 9, n) for n in names])
        for n, (toks, lps) in zip(names, got):
            assert_matches(cfg, params, PROMPTS[n], toks, lps)
    finally:
        await engine.shutdown()


async def test_the_engine_gives_two_planes_one_table_and_no_state():
    cfg = tiny_glm()
    assert cfg.owns_pages and not cfg.has_recurrent_state and not cfg.released_window
    engine, _ = await launch(cfg)
    try:
        sched = engine.scheduler
        assert sched.state_slots is None and sched.table_extra == 0
        assert sched.window_plane is None
        assert sched.allocator.enable_prefix_caching
        assert set(engine.v_cache) == {"counts"}
        assert set(engine.k_cache) == {"latent", "index_k"}
        assert engine.k_cache["latent"].shape == (3, 64 * 8, 128)
        assert engine.k_cache["index_k"].shape == (3, 64 * 8, 16)
        state = engine.debug_state()
        assert "state_plane" not in state and "page_planes" not in state
        plane = state["page_plane"]
        assert plane["bytes_by_plane"] == {
            "latent": 3 * 64 * 8 * 128 * 4, "index_k": 3 * 64 * 8 * 16 * 4}
        assert plane["page_pool_bytes"] == sum(plane["bytes_by_plane"].values()) \
            == 64 * glm.page_bytes_per_block(cfg, 8, 4)
        assert plane["pages_total"] == 63 and plane["pages_in_use"] == 0
        for name in glm.DSA_COUNT_NAMES:
            assert name in engine.program_counts()
    finally:
        await engine.shutdown()


async def test_a_prefix_hit_serves_the_indexers_keys_with_the_latents(monkeypatch):
    """The second request shares 32 tokens = 4 pages with the first: the
    allocator reports the hit, the prefill computes only the rest — whose
    queries score the CACHED ``index_k`` pages and select among them — and
    the answer equals the same request served cold. Were the indexer's
    keys not on the shared pages, the 5 new tokens would select among
    zeros and the logits would differ."""
    monkeypatch.setattr(glm, "PAIR_UNIT", 1)
    cfg = tiny_glm()
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
        # only the 5 new tokens were scored, against 33..37 keys, in 3 layers
        assert after["prefill_tokens_real"] - before["prefill_tokens_real"] == 5
        assert after["dsa_index_pairs"] - before["dsa_index_pairs"] \
            == 3 * sum(range(33, 38))
        assert after["dsa_prefill_selected"] - before["dsa_prefill_selected"] \
            == 3 * 5 * 12
        assert warm == cold
        np.testing.assert_allclose(warm_lps, cold_lps, atol=TOL)
        assert_matches(cfg, params, second, warm, warm_lps)
        plane = engine.debug_state()["page_plane"]
        assert plane["pages_cached_reusable"] >= 4 and plane["pages_in_use"] == 0
        # the shared pages hold the indexer's keys: zero them and the
        # same request no longer meets the reference
        index_k = engine.k_cache["index_k"]
        await engine.acall_on_thread(lambda: engine.k_cache.__setitem__(
            "index_k", jnp.zeros_like(index_k)))
        third = shared + [90, 91, 92, 93, 95]
        broken, broken_lps = await generate(engine, third, 8, "c")
        want_lp, _ = reference_logprobs(cfg, params, third, broken)
        assert np.abs(np.asarray(broken_lps) - want_lp).max() > 10 * TOL
    finally:
        await engine.shutdown()


def pages_in_use(engine) -> int:
    return engine.debug_state()["page_plane"]["pages_in_use"]


async def settle(engine) -> None:
    for _ in range(200):
        if pages_in_use(engine) == 0 and not engine.scheduler.has_work:
            return
        await asyncio.sleep(0.01)


async def test_preempt_cancel_and_finish_return_both_planes_pages():
    """One id space: a page returned is returned in both planes. A
    preempted row comes back through the prefix cache — its own pages of
    BOTH planes a hit — and what it then selects is the reference's."""
    from dynamo_tpu.runtime.engine import Context

    cfg = tiny_glm()
    engine, params = await launch(cfg)
    try:
        sched = engine.scheduler
        hit = []
        ctx = Context()

        async def preempt_once(n_tokens):
            if n_tokens == 4 and not hit:
                def do():
                    victim = next(s for s in sched.running
                                  if s.request_id == "victim")
                    sched._preempt(victim)
                    hit.append(True)
                await engine.acall_on_thread(do)

        async def stop_soon(n_tokens):
            if n_tokens == 3:
                ctx.stop_generating()

        (toks, lps), (toks2, lps2), (gone, _) = await asyncio.gather(
            generate(engine, PROMPTS["three_chunks"], 10, "victim",
                     on_token=preempt_once),
            generate(engine, PROMPTS["within_top_k"], 10, "bystander"),
            generate(engine, PROMPTS["chunk_edge"], 60, "gone", ctx=ctx,
                     on_token=stop_soon))
        assert hit and sched.preemptions == 1 and 3 <= len(gone) < 60
        assert sched.prefix_hits >= 1
        assert_matches(cfg, params, PROMPTS["three_chunks"], toks, lps)
        assert_matches(cfg, params, PROMPTS["within_top_k"], toks2, lps2)
        await settle(engine)
        plane = engine.debug_state()["page_plane"]
        assert plane["pages_in_use"] == 0
        assert sched.allocator.num_free == plane["pages_total"]
    finally:
        await engine.shutdown()


async def test_the_prefill_span_carries_the_candidate_keys():
    from dynamo_tpu.telemetry import get_tracer, reset_tracer

    reset_tracer()
    buf = get_tracer().keep_in_memory()
    engine, _ = await launch()
    try:
        await generate(engine, PROMPTS["three_chunks"], 2, "p")
        spans, _ = buf.snapshot()
        (prefill,) = [s for s in spans if s["name"] == "engine.prefill"]
        assert prefill["attrs"]["chunks"] == 3
        # token p scores p + 1 keys a layer: 41 cold tokens
        assert prefill["attrs"]["candidate_keys"] == 41 * 42 // 2
    finally:
        await engine.shutdown()
        reset_tracer()


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

    with pytest.raises(ValueError, match="glm_moe_dsa"):
        await JaxEngine.launch(engine_config(**REFUSED[what]),
                               model_config=tiny_glm())


async def test_a_checkpoint_kv_transfer_and_injected_embeddings_are_refused(tmp_path):
    from dynamo_tpu.models import loader

    with pytest.raises(NotImplementedError, match="glm_moe_dsa"):
        loader.resolve_model(str(tmp_path), model_config=tiny_glm(),
                             random_weights=False)
    engine, params = await launch()
    try:
        with pytest.raises(NotImplementedError, match="lays its pages out"):
            await engine.export_kv_blocks([1, 2])
        with pytest.raises(NotImplementedError, match="lays its pages out"):
            await engine.import_kv_blocks([1], np.zeros((1,)))
    finally:
        await engine.shutdown()
    cfg = tiny_glm()
    pages, counts = glm.init_cache(cfg, 4, 8, dtype=jnp.float32)
    z = np.zeros((1, 1), np.int32)
    with pytest.raises(NotImplementedError, match="injected embeddings"):
        glm.forward(cfg, params, pages, counts, z, z, z.reshape(-1),
                    np.zeros((1, 2), np.int32), np.ones((1,), np.int32),
                    np.zeros((1,), np.int32), 8,
                    extra_embeds=jnp.zeros((1, 1, 64)))
