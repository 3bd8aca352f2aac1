"""Sparse-routed MoE: top-k grouped matmuls must match the dense
all-experts oracle exactly (same routing, same experts, same math) —
single device, ep-sharded mesh, and int8 experts.

Reference analogue: the role of expert parallelism in SURVEY §2.6 and
BASELINE config 4 (Mixtral-style EP decode); the dense formulation pays
E/k× the FLOPs, which is what the sparse path removes.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest


from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.models.llama import (
    _moe_mlp_dense,
    _moe_mlp_sparse,
    init_params,
    layer_param_names,
    set_attention_mesh,
)
from dynamo_tpu.parallel.mesh import MeshConfig, build_mesh

CFG = ModelConfig(
    vocab_size=512, hidden_size=64, intermediate_size=128,
    num_hidden_layers=2, num_attention_heads=8, num_key_value_heads=4,
    max_position_embeddings=256, num_local_experts=4, num_experts_per_tok=2,
)


def _layer_params(cfg, quantize=False, mesh=None):
    if quantize:
        from dynamo_tpu.models.llama import param_specs
        from dynamo_tpu.models.quant import init_params_quantized

        params = init_params_quantized(
            cfg, seed=0, mesh=mesh, specs=param_specs(cfg) if mesh else None
        )
    else:
        params = init_params(cfg, seed=0, mesh=mesh)
    return {k: params[k][0] for k in layer_param_names(params)}


def _h(B=2, T=3, D=64, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal((B, T, D)), jnp.bfloat16)


def _assert_close(a, b, atol=2e-2):
    np.testing.assert_allclose(
        np.asarray(a, np.float32), np.asarray(b, np.float32), atol=atol
    )


def test_sparse_matches_dense_single_device():
    lp = _layer_params(CFG)
    h = _h()
    dense = _moe_mlp_dense(CFG, lp, h)
    sparse = jax.jit(lambda l, x: _moe_mlp_sparse(CFG, l, x))(lp, h)
    _assert_close(dense, sparse)


def test_sparse_matches_dense_int8():
    lp = _layer_params(CFG, quantize=True)
    h = _h()
    dense = _moe_mlp_dense(CFG, lp, h)
    sparse = jax.jit(lambda l, x: _moe_mlp_sparse(CFG, l, x))(lp, h)
    _assert_close(dense, sparse)


@pytest.mark.parametrize("quantize", [False, True])
def test_sparse_ep_sharded_matches_dense(quantize):
    """Fully-manual ep×tp shard_map: every shard computes only its
    local experts' rows; the psum combine must reproduce the dense
    oracle."""
    mesh = build_mesh(MeshConfig(dp=2, ep=2, tp=2), jax.devices())
    lp_ref = _layer_params(CFG, quantize=quantize)
    h = _h()
    dense = _moe_mlp_dense(CFG, lp_ref, h)
    lp_sh = _layer_params(CFG, quantize=quantize, mesh=mesh)
    set_attention_mesh(mesh)
    try:
        with mesh:
            sparse = jax.jit(lambda l, x: _moe_mlp_sparse(CFG, l, x))(lp_sh, h)
    finally:
        set_attention_mesh(None)
    _assert_close(dense, sparse)


def test_sparse_routing_skews_to_selected_experts():
    """Zeroing one expert's weights changes outputs ONLY for tokens
    routed to it — evidence the grouped matmul actually routes rather
    than evaluating everything."""
    lp = dict(_layer_params(CFG))
    h = _h(B=4, T=8)
    from dynamo_tpu.models.llama import _moe_routing

    x = h.reshape(-1, CFG.hidden_size)
    _, topi = _moe_routing(CFG, lp, x)
    victim = 2
    routed = np.any(np.asarray(topi) == victim, axis=-1)
    assert routed.any() and not routed.all()  # interesting split

    base = np.asarray(
        jax.jit(lambda l, a: _moe_mlp_sparse(CFG, l, a))(lp, h), np.float32
    ).reshape(-1, CFG.hidden_size)
    lp2 = dict(lp)
    lp2["w_down"] = lp["w_down"].at[victim].set(0.0)
    out2 = np.asarray(
        jax.jit(lambda l, a: _moe_mlp_sparse(CFG, l, a))(lp2, h), np.float32
    ).reshape(-1, CFG.hidden_size)
    changed = np.abs(base - out2).max(axis=-1) > 1e-6
    np.testing.assert_array_equal(changed, routed)
