"""Kimi-Linear through the ENGINE on the CPU: what the served path
returns — chosen ids and their logprobs, prefill then decode through
the latent pages and the state plane — against the plain reference's
full forward pass, in float32 so that they meet to rounding."""

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

TOL = 2e-4   # float32 end to end: differences are summation order


def engine_config(**kw) -> EngineConfig:
    defaults = dict(
        model_name="tiny-kimi", random_weights=True, seed=5, num_blocks=64,
        block_size=8, max_batch_size=4, prefill_chunk_size=16,
        max_model_len=128, kv_cache_dtype="float32", static_shapes=False,
    )
    defaults.update(kw)
    return EngineConfig(**defaults)


async def launch(cfg=None, **kw):
    """An engine whose parameters are the seeded draw in float32."""
    from dynamo_tpu.engine.engine import JaxEngine

    cfg = cfg or tiny_kimi()
    engine = await JaxEngine.launch(engine_config(**kw), model_config=cfg)
    params = kl.init_params(cfg, seed=5, dtype=jnp.float32)
    await engine.acall_on_thread(lambda: setattr(engine, "params", params))
    return engine, params


async def generate(engine, prompt, max_tokens, rid, ctx=None, on_token=None):
    req = PreprocessedRequest(
        request_id=rid, token_ids=list(prompt),
        sampling=SamplingOptions(use_greedy=True),
        stop=StopConditions(max_tokens=max_tokens, ignore_eos=True),
    )
    toks, lps = [], []
    async for item in engine.as_async_engine().generate(req, ctx or Context()):
        toks += item.token_ids
        lps += item.log_probs or []
        if on_token is not None and item.token_ids:
            await on_token(len(toks))
    return toks, lps


def reference_logprobs(cfg, params, prompt, chosen):
    """The reference's logprob of each chosen id, and its own greedy id,
    from one full forward pass over prompt + chosen."""
    seq = np.asarray([list(prompt) + list(chosen)], np.int32)
    logits = np.asarray(ref.forward(cfg, params, jnp.asarray(seq)))[0]
    at = np.arange(len(prompt) - 1, len(seq[0]) - 1)
    lp = logits[at] - np.log(np.exp(
        logits[at] - logits[at].max(-1, keepdims=True)).sum(-1, keepdims=True)
    ) - logits[at].max(-1, keepdims=True)
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
    """Chunk boundaries fall inside every KDA layer's recurrence and
    convolution: state and tail are carried through the state plane."""
    cfg = tiny_kimi()
    engine, params = await launch(cfg)
    try:
        toks, lps = await generate(engine, PROMPTS[name], 12, name)
        assert len(toks) == 12
        assert_matches(cfg, params, PROMPTS[name], toks, lps)
    finally:
        await engine.shutdown()


@pytest.mark.parametrize("decode_steps", [1, 3])
async def test_batched_rows_of_unequal_length(decode_steps):
    """Right-padded prefill rows and padded decode rows: padding enters
    neither state nor convolution tail; fused decode windows too."""
    cfg = tiny_kimi()
    engine, params = await launch(cfg, decode_steps=decode_steps)
    try:
        names = sorted(PROMPTS)
        got = await asyncio.gather(*[
            generate(engine, PROMPTS[n], 9, n) for n in names])
        for n, (toks, lps) in zip(names, got):
            assert_matches(cfg, params, PROMPTS[n], toks, lps)
    finally:
        await engine.shutdown()


async def test_preempted_row_is_recomputed_exactly():
    """A preempted sequence gives its slot back and is recomputed from
    its tokens: its answer still meets the reference."""
    cfg = tiny_kimi()
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
                    hit.append((slot, victim.state_slot, sched.state_slots.num_used))
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
