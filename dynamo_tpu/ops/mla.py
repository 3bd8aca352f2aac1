"""Latent-attention decode over the paged latent cache, as one Pallas
flash-decode kernel over a (row, table column) grid, a page a step: what
ops/paged_attention.py's decode kernel was before PR 30 (PERF.md §7).

The cache holds ONE row a token a layer: ``[c | k_r]``, the normalised
latent (``rank`` values) and the shared, unrotated key part (``rope``
values). The queries arrive with the latent's key up-projection already
absorbed (models/kimi_linear.py), so every one of the H heads scores the
row itself, all ``rank + rope`` of it, and the values are the row's first
``rank`` columns: one page tile ``[block, rank + rope]`` serves as key
and value of all heads at once, and is read from HBM once. Pages are
addressed through the BlockSpec (layer, block table and contexts ride as
scalar prefetch), so only the pages a row's context covers move; rows
with context 0 (padding) touch nothing.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(layer_ref, tables_ref, ctx_ref, q_ref, page_ref, o_ref,
            acc_ref, m_ref, l_ref, *, block_size: int, rank: int):
    b, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, -1e30)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    ctx = ctx_ref[b]

    @pl.when(j * block_size < ctx)
    def _page():
        q = q_ref[0]                                   # [H, C], scaled
        rows = page_ref[...]                           # [block, C]
        if rows.dtype != q.dtype:
            rows = rows.astype(q.dtype)
        s = jax.lax.dot_general(q, rows, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        pos = j * block_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_size), 1)
        valid = pos < ctx
        s = jnp.where(valid, s, -1e30)
        m_prev = m_ref[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[:] = l_ref[:] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p.astype(rows.dtype), rows[:, :rank], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _finish():
        o_ref[0] = (acc_ref[:] / jnp.maximum(l_ref[:], 1e-9)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_size", "rank", "interpret"))
def mla_decode_attention(q, latent, layer, tables, context_lens, *,
                         block_size: int, rank: int, interpret: bool = False):
    """``q`` [B, H, C] (key up-projection absorbed, softmax scale folded
    in); ``latent`` [Lm, slots, C], the whole stacked plane; ``layer``
    scalar int32; ``tables`` [B, W] page ids; ``context_lens`` [B].
    Returns the attention output IN LATENT SPACE, [B, H, rank]."""
    B, H, C = q.shape
    Lm, slots, _ = latent.shape
    pages = latent.reshape(Lm, slots // block_size, block_size, C)
    W = tables.shape[1]

    def page_index(b, j, lyr, t, c):
        last = jnp.maximum((c[b] - 1) // block_size, 0)
        return (lyr[0], t[b, jnp.minimum(j, last)], 0, 0)

    return pl.pallas_call(
        functools.partial(_kernel, block_size=block_size, rank=rank),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,  # layer, tables, contexts
            grid=(B, W),
            in_specs=[
                pl.BlockSpec((1, H, C), lambda b, j, lyr, t, c: (b, 0, 0)),
                pl.BlockSpec((None, None, block_size, C), page_index),
            ],
            out_specs=pl.BlockSpec((1, H, rank), lambda b, j, lyr, t, c: (b, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((H, rank), jnp.float32),
                pltpu.VMEM((H, 1), jnp.float32),
                pltpu.VMEM((H, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, rank), q.dtype),
        name="mla_decode_attention",
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1), tables.astype(jnp.int32),
      context_lens.astype(jnp.int32), q, pages)
