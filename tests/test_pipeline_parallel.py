"""Pipeline-parallel forward vs the plain lax.scan forward.

Runs on the virtual 8-device CPU mesh (conftest). forward_pp must produce
identical logits and identical paged-KV cache contents (modulo the pad
slot 0, which bubble ticks scribble on by design).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.models.llama import forward, init_cache, init_params
from dynamo_tpu.parallel.mesh import MeshConfig, build_mesh
from dynamo_tpu.parallel.pipeline import (
    PP_CACHE_SPEC,
    forward_pp,
    pp_param_specs,
)

BLOCK = 8


def _cfg(L=4):
    return ModelConfig(
        vocab_size=256, hidden_size=32, intermediate_size=64,
        num_hidden_layers=L, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=128,
    )


def _step_args(cfg, B, T, n_blocks_per_seq, seed=0):
    from dynamo_tpu.utils.testing import make_paged_inputs

    return make_paged_inputs(cfg.vocab_size, B, T, BLOCK, n_blocks_per_seq, seed)


def _run_pp(pp, tp, B=4, T=16, L=4, microbatches=None):
    cfg = _cfg(L)
    mesh = build_mesh(
        MeshConfig(pp=pp, tp=tp), jax.devices()[: pp * tp]
    )
    params = init_params(cfg, seed=0)
    nbps = max(1, T // BLOCK)
    n_blocks = 1 + B * nbps  # block 0 is the pad/scratch block
    k_cache, v_cache = init_cache(cfg, num_blocks=n_blocks, block_size=BLOCK)
    args = _step_args(cfg, B, T, nbps)

    # single-device oracle
    ref_logits, ref_k, ref_v = forward(
        cfg, params, k_cache, v_cache, *args, BLOCK
    )

    # pp-sharded run
    specs = pp_param_specs(cfg)
    params_pp = {
        k: jax.device_put(v, NamedSharding(mesh, specs[k]))
        for k, v in params.items()
    }
    cache_sh = NamedSharding(mesh, PP_CACHE_SPEC)
    k_pp = jax.device_put(k_cache, cache_sh)
    v_pp = jax.device_put(v_cache, cache_sh)
    with mesh:
        logits, new_k, new_v = jax.jit(
            lambda p, kc, vc, *a: forward_pp(
                cfg, p, kc, vc, *a, BLOCK, mesh,
                num_microbatches=microbatches,
            )
        )(params_pp, k_pp, v_pp, *args)
        jax.block_until_ready(logits)
    np.testing.assert_allclose(
        np.asarray(logits), np.asarray(ref_logits), rtol=5e-2, atol=1e-1
    )
    # cache contents match everywhere except the pad block (slots 0..BLOCK)
    np.testing.assert_allclose(
        np.asarray(new_k)[:, BLOCK:], np.asarray(ref_k)[:, BLOCK:],
        rtol=5e-2, atol=5e-2,
    )
    np.testing.assert_allclose(
        np.asarray(new_v)[:, BLOCK:], np.asarray(ref_v)[:, BLOCK:],
        rtol=5e-2, atol=5e-2,
    )


def test_pp_only():
    _run_pp(pp=4, tp=1)


def test_pp_times_tp():
    # tp=2 divides both H=4 and Hkv=2 in the test config
    _run_pp(pp=2, tp=2)


def test_pp_more_microbatches_than_stages():
    _run_pp(pp=2, tp=1, B=8, microbatches=4)


def test_pp_decode_step():
    # T=1 decode: every microbatch is one token per sequence
    _run_pp(pp=2, tp=2, B=4, T=1, L=2)


async def test_engine_serves_with_pipeline_parallelism():
    """A pp=2 x tp=2 engine must produce the same greedy tokens as the
    single-device engine for the same weights/config (the pp path is a
    distributed reformulation of the same forward)."""
    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.engine import JaxEngine
    from dynamo_tpu.protocols.common import (
        PreprocessedRequest,
        SamplingOptions,
        StopConditions,
    )
    from dynamo_tpu.runtime.engine import Context

    mc = ModelConfig(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        num_hidden_layers=4, num_attention_heads=8, num_key_value_heads=4,
        max_position_embeddings=128,
    )

    async def run(pp: int, tp: int) -> list[int]:
        engine = await JaxEngine.launch(
            EngineConfig(
                model_path="", model_name="pp-test", random_weights=True,
                num_blocks=32, block_size=4, max_batch_size=4,
                pipeline_parallel_size=pp, tensor_parallel_size=tp,
                kv_cache_dtype="float32",
            ),
            model_config=mc,
        )
        req = PreprocessedRequest(
            request_id=f"pp{pp}", token_ids=list(range(1, 14)),
            sampling=SamplingOptions(use_greedy=True),
            stop=StopConditions(max_tokens=5, ignore_eos=True),
        )
        toks: list[int] = []
        async for item in engine.as_async_engine().generate(req, Context()):
            toks.extend(item.token_ids)
        await engine.shutdown()
        return toks

    base = await run(1, 1)
    pp_toks = await run(2, 2)
    assert base == pp_toks


async def test_engine_rejects_incompatible_pp():
    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.engine import JaxEngine

    mc = ModelConfig(
        vocab_size=64, hidden_size=32, intermediate_size=64,
        num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
    )
    with pytest.raises(ValueError, match="must divide"):
        await JaxEngine.launch(
            EngineConfig(model_path="", random_weights=True, num_blocks=8,
                         block_size=4, pipeline_parallel_size=3),
            model_config=mc,
        )
