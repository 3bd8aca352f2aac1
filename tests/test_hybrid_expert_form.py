"""``models/hybrid.py``'s expert forms (PR 37: ``moe_local_dense`` /
``moe_local_grouped`` take what an expert IS as an argument, so that a
two-matrix relu^2 expert runs through them) leave the two delta-rule
families' step programs as they were: with the parent's own functions in
their place, ``kimi_linear`` and ``qwen3_next`` lower to the SAME text."""

import jax
import jax.numpy as jnp
import pytest

from dynamo_tpu.models import hybrid, kimi_linear, qwen3_next
from tests.kimi_tiny import tiny_kimi
from tests.qwen3_next_tiny import tiny_qwen3_next


def _parents_moe_local_dense(p, x, combine, idx, form=None):
    """``hybrid.moe_local_dense`` as the parent of PR 37 had it: three
    names and ``silu(gate) * up`` written in."""
    def edot(eq, a, name):
        w, scale = hybrid._expert_weights(p, name, idx, a.dtype)
        y = hybrid.einsum_f32(eq, a, w)
        return y if scale is None else y * scale[:, None, :]

    with jax.named_scope("moe_experts"):
        gate = edot("nd,edf->enf", x, "we_gate")
        up = edot("nd,edf->enf", x, "we_up")
        mid = (jax.nn.silu(gate) * up).astype(x.dtype)
        return jnp.einsum("end,ne->nd", edot("enf,efd->end", mid, "we_down"),
                          combine)


def _parents_moe_local_grouped(p, x, w, local_e, idx, E, form=None):
    """``hybrid.moe_local_grouped`` as the parent of PR 37 had it."""
    N, k = local_e.shape
    flat = local_e.reshape(-1)
    order = jnp.argsort(flat)
    sorted_e = flat[order]
    xs = jnp.take(x, order // k, axis=0)
    sizes = jnp.bincount(sorted_e, length=E + 1)[:E].astype(jnp.int32)
    held = sorted_e < E
    cpu = jax.default_backend() == "cpu"

    def gdot(a, name):
        stack, a = jax.lax.optimization_barrier((p[name], a))
        w8 = stack[idx]
        scale = p[name + "_scale"][idx] if w8.dtype == jnp.int8 else None
        wt = w8.astype(jnp.float32 if cpu else a.dtype)
        y = jax.lax.ragged_dot(a.astype(wt.dtype), wt, sizes,
                               preferred_element_type=jnp.float32)
        if scale is not None:
            y = y * jnp.take(scale, jnp.minimum(sorted_e, E - 1), axis=0)
        return y

    with jax.named_scope("moe_experts"):
        mid = jax.nn.silu(gdot(xs, "we_gate")) * gdot(xs, "we_up")
        out = jnp.where(held[:, None], gdot(mid.astype(x.dtype), "we_down"), 0)
    inv = jnp.zeros_like(order).at[order].set(jnp.arange(N * k))
    out = jnp.take(out, inv, axis=0).reshape(N, k, -1)
    return jnp.sum(out * w[..., None], axis=1)


def _lowered_step(fam, cfg, rows, T, quantized):
    p = (fam.init_params_quantized(cfg, seed=1) if quantized
         else fam.init_params(cfg, seed=1, dtype=jnp.float32))
    pages, state = fam.init_cache(cfg, 8, 8, dtype=jnp.float32, state_slots=3)
    z = jnp.zeros((rows, T), jnp.int32)
    args = (p, pages, state, z, z, jnp.zeros((rows * T,), jnp.int32),
            jnp.zeros((rows, 5), jnp.int32), jnp.ones((rows,), jnp.int32),
            jnp.zeros((rows,), jnp.int32))
    return jax.jit(lambda *a: fam.forward(cfg, *a, 8)).lower(*args).as_text()


# expert width and hidden size whole lane tiles, as both benchmark
# configurations have them (1024 / 2304 and 512 / 2048)
ALIGNED = dict(hidden_size=128, moe_intermediate_size=128)
STEP_SHAPES = [(2, 1), (2, 16), (4, 256)]   # decode; experts all at once; sorted rows


@pytest.mark.parametrize("rows,T", STEP_SHAPES)
@pytest.mark.parametrize("quantized", [False, True], ids=["float32", "int8"])
@pytest.mark.parametrize("name", ["kimi_linear", "qwen3_next"])
def test_hybrids_edit_leaves_the_delta_rule_families_step_programs_as_they_were(
    name, quantized, rows, T, monkeypatch
):
    """``moe_local_dense`` / ``moe_local_grouped`` now take the expert's
    form as an argument. With the parent's own functions in their place
    the two families' step programs lower to the SAME text, byte for byte:
    nothing an existing cell compiles, prewarms or runs has changed."""
    fam, cfg = ((kimi_linear, tiny_kimi(**ALIGNED)) if name == "kimi_linear"
                else (qwen3_next, tiny_qwen3_next(**ALIGNED)))
    now = _lowered_step(fam, cfg, rows, T, quantized)
    monkeypatch.setattr(hybrid, "moe_local_dense", _parents_moe_local_dense)
    monkeypatch.setattr(hybrid, "moe_local_grouped", _parents_moe_local_grouped)
    before = _lowered_step(fam, cfg, rows, T, quantized)
    assert now == before
