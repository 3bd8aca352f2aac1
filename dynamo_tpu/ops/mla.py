"""Latent attention over the paged latent cache. Decode: one Pallas
flash-decode kernel over a (row, table column) grid, a page a step: what
ops/paged_attention.py's decode kernel was before PR 30 (PERF.md §7).
Prefill (``mla_prefill_attention``, at the file's end): the same page
walk under a tile of query tokens, causal.

The cache holds ONE row a token a layer: ``[c | k_r]``, the normalised
latent (``rank`` values) and the shared key part (``rope`` values:
unrotated for ``kimi_linear``, already rotated for ``deepseek_v3`` — the
kernels do not care). The queries arrive with the latent's key up-projection already
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


# ---------------------------------------------------------------------------
# Prefill: a tile of query tokens against the row's own pages
# ---------------------------------------------------------------------------

PREFILL_TILE_ROWS = 1024   # (query token, head) rows a grid step scores
_PREFILL_VMEM_LIMIT_BYTES = 48 * 1024 * 1024


def _prefill_kernel(layer_ref, starts_ref, tables_ref, ctx_ref, q_ref,
                    page_ref, o_ref, acc_ref, m_ref, l_ref, *,
                    block_size: int, rank: int, tq: int, heads: int):
    """One tile of ``tq`` query tokens (all heads: ``tq * heads`` rows,
    token-major) against one page, causal, the online-softmax state in
    VMEM across the page axis. The chunk's own rows are read back from
    the pages (the caller writes them before attending), so a chunk at
    any start position attends its whole prefix — cached pages and the
    earlier chunks' alike — and no ``[T, S]`` score exists in HBM."""
    b, qi, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, -1e30)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    ctx = ctx_ref[b]
    q_lo = starts_ref[b] + qi * tq
    q_hi = jnp.minimum(q_lo + tq, ctx)      # one past the tile's last real token

    @pl.when((j * block_size < q_hi) & (q_lo < ctx))
    def _page():
        q = q_ref[0, 0]                                # [tq * H, C], scaled
        rows = page_ref[...]                           # [block, C]
        if rows.dtype != q.dtype:
            rows = rows.astype(q.dtype)
        s = jax.lax.dot_general(q, rows, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        key_pos = j * block_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_size), 1)
        q_pos = q_lo + jax.lax.broadcasted_iota(
            jnp.int32, (tq * heads, 1), 0) // heads
        valid = (key_pos <= q_pos) & (key_pos < ctx)
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

    @pl.when(j == pl.num_programs(2) - 1)
    def _finish():
        # tokens with no valid key (padding): clamp, not NaN
        o_ref[0, 0] = (acc_ref[:] / jnp.maximum(l_ref[:], 1e-9)).astype(o_ref.dtype)


def prefill_tile_tokens(T: int, heads: int) -> int:
    """Query tokens a grid step takes: ``PREFILL_TILE_ROWS`` rows of
    (token, head), halved while it does not divide ``T``."""
    tq = max(1, PREFILL_TILE_ROWS // heads)
    while tq > 1 and T % tq:
        tq //= 2
    return tq


@functools.partial(jax.jit, static_argnames=("block_size", "rank", "interpret"))
def mla_prefill_attention(q, latent, layer, tables, start_pos, context_lens, *,
                          block_size: int, rank: int, interpret: bool = False):
    """``q`` [B, T, H, C] (key up-projection absorbed, softmax scale folded
    in): row ``b``'s token ``t`` sits at position ``start_pos[b] + t``;
    ``latent`` [Lm, slots, C] with this chunk's rows already written;
    ``tables`` [B, W]; ``context_lens`` [B] counts the chunk's real tokens
    (tokens at or past it, and rows of context 0, attend nothing and read
    no page). Returns the output IN LATENT SPACE, [B, T, H, rank]. A tile
    walks only the pages up to its own last token: pages past it repeat
    the last live one, which skips their copy."""
    B, T, H, C = q.shape
    Lm, slots, _ = latent.shape
    pages = latent.reshape(Lm, slots // block_size, block_size, C)
    W = tables.shape[1]
    tq = prefill_tile_tokens(T, H)
    n_tiles = T // tq
    q4 = q.reshape(B, n_tiles, tq * H, C)

    def page_index(b, qi, j, lyr, st, t, c):
        tile_hi = jnp.minimum(st[b] + (qi + 1) * tq, c[b])
        last = jnp.maximum((tile_hi - 1) // block_size, 0)
        return (lyr[0], t[b, jnp.minimum(j, last)], 0, 0)

    def tile_index(b, qi, j, lyr, st, t, c):
        return (b, qi, 0, 0)

    out = pl.pallas_call(
        functools.partial(_prefill_kernel, block_size=block_size, rank=rank,
                          tq=tq, heads=H),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,  # layer, starts, tables, contexts
            grid=(B, n_tiles, W),
            in_specs=[
                pl.BlockSpec((1, 1, tq * H, C), tile_index),
                pl.BlockSpec((None, None, block_size, C), page_index),
            ],
            out_specs=pl.BlockSpec((1, 1, tq * H, rank), tile_index),
            scratch_shapes=[
                pltpu.VMEM((tq * H, rank), jnp.float32),
                pltpu.VMEM((tq * H, 1), jnp.float32),
                pltpu.VMEM((tq * H, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, n_tiles, tq * H, rank), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_PREFILL_VMEM_LIMIT_BYTES),
        name="mla_prefill_attention",
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1),
      jnp.asarray(start_pos, jnp.int32), tables.astype(jnp.int32),
      context_lens.astype(jnp.int32), q4, pages)
    return out.reshape(B, T, H, rank)
