"""Latent attention over the paged latent cache. Decode
(``mla_decode_attention``): one Pallas flash-decode kernel with
``grid=(rows,)`` whose body walks a row's LIVE pages, several to a
compute block, each page its own DMA into a double buffer — the plan of
ops/paged_attention.py's decode kernel (PR 30) with a body of its own,
because here key and value are ONE plane. Prefill
(``mla_prefill_attention``, at the file's end): the same walk under a
TILE of query tokens, ``grid=(rows, tiles)``, causal — a tile's live
pages, several to a block, one online-softmax update a block.

The cache holds ONE row a token a layer: ``[c | k_r]``, the normalised
latent (``rank`` values) and the shared key part (``rope`` values:
unrotated for ``kimi_linear``, already rotated for ``deepseek_v3`` — the
kernels do not care), stored in whole 128-lane tiles (the models'
``Cpad``: a plane whose rows are 576 wide is laid out in 640 lanes all
the same, and the chip's compiler refuses a page copy from it). The
queries arrive with the latent's key up-projection already absorbed
(models/hybrid.py ``mla_mixer``), so every one of the H heads scores the
row itself, all ``rank + rope`` of it, and the values are the row's first
``rank`` columns: one block of pages ``[P * block, C]`` in VMEM serves
as key and value of all heads at once, and crosses HBM once. Layer,
block table and contexts ride as scalar prefetch; only the pages a
row's context covers move, table columns past them are never
dereferenced, and rows with context 0 (padding) touch nothing.

On a v5e (PERF.md, PR 47) a call takes 0.23 ms at 64 rows of 5-30 live
pages and 19 us at one row of 68, 68-72 % of the HBM floor of the 576
published values a row (the stored 640 lanes bound it at 90), where the
grid over the table took 0.72 ms and 93 us; flat from 6 to 16 pages a
block; the scores as ``q @ rows^T`` beat ``rows @ q^T`` by 15-20 %.
The prefill walk (PERF.md, PR 50) takes 1.65 us a further live page
under a tile of 1 024 (token, head) rows — the tile's two dots are 1.53
us of the MXU's peak — and ~20-30 us a tile beside them, where the grid
over the table, a page a step, took 4.2: a 1 024-token chunk over 8k /
14k / 20k keys 8.1 / 13.3 / 18.3 ms at 64 heads with marks (18.4 / 30.6
/ 42.9) and 4.4 / 6.9 at 32 heads over 8k / 14k (8.8 / 14.7); flat from
4 to 8 pages a block, 10-20 % slower at 16.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dynamo_tpu.ops.paged_attention import (
    _DECODE_BLOCK_COLUMNS,
    _DECODE_KV_BUFFER_BYTES,
    _DECODE_VMEM_LIMIT_BYTES,
)


def latent_pages_per_block(block_size: int, C: int, itemsize: int) -> int:
    """Pages of one compute block of the latent decode kernel, by
    ``ops/paged_attention.py`` ``decode_pages_per_block``'s rule and its
    two constants (one budget): as many as the double buffer holds of
    this plane's pages — ONE plane: a page is key and value at once —
    while the block stays within the dense kernel's ceiling on a
    block's MXU work. That ceiling is stated in score columns of
    128-lane rows; a latent column is ``ceil(C / 128)`` lane tiles of
    MXU weights where a dense one is one, so it counts as that many. A
    block's compute does not shrink with the pages it holds, and at 32
    query rows its cost is the weight tiles pushed, not the rows
    streamed: a short row pays for a whole block."""
    by_vmem = _DECODE_KV_BUFFER_BYTES // (2 * block_size * C * itemsize)
    by_columns = _DECODE_BLOCK_COLUMNS // (block_size * -(-C // 128))
    return max(1, min(by_vmem, by_columns))


def _decode_kernel(layer_ref, tables_ref, ctx_ref, q_ref, *rest,
                   block_size: int, rank: int, selected: bool):
    """``rest``: (``sel_ref`` where ``selected``,) ``pages_hbm``, ``o_ref``,
    ``buf``, ``sems``, ``state``.

    One grid step a ROW over the stacked plane left in HBM as pages
    ``[Lm, N, bs, C]``. A row walks its LIVE pages only,
    ``ceil(ctx / bs)`` of them, ``P`` to a compute block, each page one
    DMA into the ``[2, P, bs, C]`` double buffer; the next block (and,
    from a row's last block, the first block of the row after it, where
    that row is live) is in flight while the current one is computed.
    Table columns past the live range are never dereferenced; a row of
    context 0 runs no block and starts no copy. The layer is an index,
    never a slice (a slice of the carried plane before a custom call is
    a copy of the layer).

    One block is ONE dot of the ``H`` query rows against its ``P * bs``
    latent rows (all ``C`` lanes), one online-softmax update, one dot of
    the probabilities against the same rows' first ``rank`` lanes: the
    block in VMEM is key and value of every head at once.

    ``selected``: ``sel_ref`` [1, 1, >= pages x bs] float32 marks the keys
    the row attends (> 0.5); the others are masked like positions past
    the context. Every live page is still read: the masked WALK."""
    sel_ref = rest[0] if selected else None
    pages_hbm, o_ref, buf, sems, state = rest[1:] if selected else rest
    b = pl.program_id(0)
    B = pl.num_programs(0)
    H, C = q_ref.shape[1], q_ref.shape[2]
    P, bs = buf.shape[1], block_size
    cols = P * bs
    lyr = layer_ref[0]

    @pl.when(b == 0)
    def _first_row():
        # slot of this row's first block; whether the row before has
        # already started it
        state[0] = 0
        state[1] = 0
        # page slots a block leaves unfilled are masked by position, but
        # 0 x NaN is NaN in the PV dot: no slot may hold uninitialised
        # VMEM (a filled slot holds cache values, which are finite)
        buf[...] = jnp.zeros_like(buf)

    def live_pages(row):
        return (ctx_ref[row] + bs - 1) // bs

    def block_copies(row, n, i, slot, fn):
        """``fn`` (start or wait) on the copies of block ``i`` of ``row``
        into ``slot``: one a live page, none for the block's slots past
        the row's last live page."""
        def page_copy(p, carry):
            fn(pltpu.make_async_copy(
                pages_hbm.at[lyr, tables_ref[row, i * P + p]],
                buf.at[slot, p], sems.at[slot]))
            return carry

        jax.lax.fori_loop(0, jnp.minimum(P, n - i * P), page_copy, 0)

    start = lambda *a: block_copies(*a, lambda c: c.start())  # noqa: E731
    wait = lambda *a: block_copies(*a, lambda c: c.wait())  # noqa: E731

    ctx = ctx_ref[b]
    n_pages = live_pages(b)
    n_blocks = (n_pages + P - 1) // P
    slot0 = state[0]
    nxt = jnp.minimum(b + 1, B - 1)
    n_pages_nxt = live_pages(nxt)
    prefetch_nxt = (b + 1 < B) & (n_pages_nxt > 0) & (n_blocks > 0)

    @pl.when((n_blocks > 0) & (state[1] == 0))
    def _own_first_block():
        start(b, n_pages, 0, slot0)

    q = q_ref[0]                                       # [H, C], scaled

    def block(i, carry):
        m_prev, l_prev, acc = carry
        slot = (slot0 + i) % 2

        @pl.when(i + 1 < n_blocks)
        def _next_block():
            start(b, n_pages, i + 1, 1 - slot)

        @pl.when((i + 1 == n_blocks) & prefetch_nxt)
        def _next_row():
            start(nxt, n_pages_nxt, 0, 1 - slot)

        wait(b, n_pages, i, slot)
        rows = buf[slot].reshape(cols, C)
        if rows.dtype != q.dtype:
            rows = rows.astype(q.dtype)
        s = jax.lax.dot_general(q, rows, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        pos = i * cols + jax.lax.broadcasted_iota(jnp.int32, (1, cols), 1)
        live = pos < ctx
        if selected:
            live &= sel_ref[0, :, pl.ds(pl.multiple_of(i * cols, cols), cols)] > 0.5
        s = jnp.where(live, s, -1e30)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        # every block holds a key of the row's live range, so m_new is a
        # real score and a masked column's exp is exactly 0
        p = jnp.exp(s - m_new)
        if selected:    # a block may hold no selected key: m_new is then no score
            p = jnp.where(live, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        pv = jnp.dot(p.astype(rows.dtype), rows[:, :rank],
                     preferred_element_type=jnp.float32)
        return m_new, l_new, acc * alpha + pv

    _, l, acc = jax.lax.fori_loop(
        0, n_blocks, block,
        (jnp.full((H, 1), -1e30, jnp.float32),
         jnp.zeros((H, 1), jnp.float32),
         jnp.zeros((H, rank), jnp.float32)))
    state[0] = (slot0 + n_blocks) % 2
    state[1] = prefetch_nxt.astype(jnp.int32)
    o_ref[0] = (acc / jnp.maximum(l, 1e-9)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "block_size", "rank", "interpret", "pages_per_block"))
def mla_decode_attention(q, latent, layer, tables, context_lens, *,
                         block_size: int, rank: int, interpret: bool = False,
                         pages_per_block: Optional[int] = None, sel=None):
    """``q`` [B, H, C] (key up-projection absorbed, softmax scale folded
    in); ``latent`` [Lm, slots, C], the whole stacked plane; ``layer``
    scalar int32; ``tables`` [B, W] page ids; ``context_lens`` [B].
    Returns the attention output IN LATENT SPACE, [B, H, rank]; a row of
    context 0 gets zeros. ``pages_per_block``: pages of one compute
    block, for tests; by default sized from the call's own geometry
    (``latent_pages_per_block``). ``sel`` [B, W * block_size] float32,
    where given: the row attends only the keys it marks > 0.5 (in table
    order; ``ops/dsa.py`` ``select_topk``), and the kernel is named
    ``dsa_decode_attention``."""
    B, H, C = q.shape
    Lm, slots, _ = latent.shape
    pages = latent.reshape(Lm, slots // block_size, block_size, C)
    P = pages_per_block or latent_pages_per_block(
        block_size, C, latent.dtype.itemsize)
    row = lambda width: pl.BlockSpec(  # noqa: E731
        (1, H, width), lambda b, lyr, t, c: (b, 0, 0))
    marks, mark_specs = (), []
    if sel is not None:
        # whole compute blocks: the last one may reach past the table
        S = -(-sel.shape[1] // (P * block_size)) * P * block_size
        marks = (jnp.pad(sel.astype(jnp.float32),
                         ((0, 0), (0, S - sel.shape[1]))).reshape(B, 1, S),)
        mark_specs = [pl.BlockSpec((1, 1, S), lambda b, lyr, t, c: (b, 0, 0))]

    return pl.pallas_call(
        functools.partial(_decode_kernel, block_size=block_size, rank=rank,
                          selected=sel is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,  # layer, tables, contexts
            grid=(B,),
            in_specs=[row(C), *mark_specs, pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=row(rank),
            scratch_shapes=[
                pltpu.VMEM((2, P, block_size, C), latent.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((2,), jnp.int32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, rank), q.dtype),
        compiler_params=pltpu.CompilerParams(
            # rows run in order: a row starts the next row's first block
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_DECODE_VMEM_LIMIT_BYTES),
        name="mla_decode_attention" if sel is None else "dsa_decode_attention",
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1), tables.astype(jnp.int32),
      context_lens.astype(jnp.int32), q, *marks, pages)


# ---------------------------------------------------------------------------
# Prefill: a tile of query tokens against the row's own pages
# ---------------------------------------------------------------------------

PREFILL_TILE_ROWS = 1024   # (query token, head) rows a grid step scores
_PREFILL_VMEM_LIMIT_BYTES = 48 * 1024 * 1024
# ceiling on a compute block's float32 score, [tile rows, P * block_size]:
# the masked score, the probabilities and their bf16 copy are temporaries
# of that shape beside it
_PREFILL_SCORE_BYTES = 4 * 2**20
_MASKED = -1e30     # a masked key's score
_NO_SCORE = -1e29   # where a row's running max starts: above ``_MASKED``


def prefill_pages_per_block(block_size: int, C: int, itemsize: int,
                            tile_rows: int) -> int:
    """Pages of one compute block of the latent prefill kernel, from the
    call's own geometry: as many as ``latent_pages_per_block``'s double
    buffer holds of this plane's pages, while the block's
    ``[tile_rows, P * block_size]`` float32 score stays within
    ``_PREFILL_SCORE_BYTES``. At 1 024 tile rows (32 tokens x 32 heads,
    16 x 64) of 128-token pages: 8; the v5e sweep was flat from 4 to 8
    at both shapes and lost 10-20 % at 16 (PERF.md, PR 50)."""
    by_vmem = _DECODE_KV_BUFFER_BYTES // (2 * block_size * C * itemsize)
    by_score = _PREFILL_SCORE_BYTES // (tile_rows * block_size * 4)
    return max(1, min(by_vmem, by_score))


def _prefill_kernel(layer_ref, starts_ref, tables_ref, ctx_ref, q_ref, *rest,
                    block_size: int, rank: int, tq: int, heads: int,
                    selected: bool):
    """``rest``: (``sel_ref`` where ``selected``,) ``pages_hbm``, ``o_ref``,
    ``buf``, ``sems``, ``acc_ref``, ``m_ref``, ``l_ref``.

    One grid step a TILE of ``tq`` query tokens (all heads: ``tq * heads``
    rows, token-major) over the stacked plane left in HBM as pages
    ``[Lm, N, bs, C]``. The tile walks its LIVE pages only — up to the
    causal edge of its last real token — ``P`` to a compute block, each
    page one DMA into the ``[2, P, bs, C]`` double buffer, the next block
    in flight while this one is computed. Table columns past the live
    range are never dereferenced; a row of context 0 and a tile at or
    past its row's context start no copy and store zeros. The chunk's own
    rows are read back from the pages (the caller writes them before
    attending), so a chunk at any start position attends its whole prefix
    — cached pages and the earlier chunks' alike — and no ``[T, S]``
    score exists in HBM.

    One block is ONE dot of the tile's rows against its ``P * bs`` latent
    rows (all ``C`` lanes), one online-softmax update (state in VMEM
    across the blocks), one dot of the probabilities against the same
    rows' first ``rank`` lanes. Masks are made a TOKEN, ``[tq, P * bs]``,
    and spread over the token's heads by a sublane broadcast (rows are
    token-major: ``[tq * heads, keys]`` is ``[tq, heads, keys]`` where
    ``heads`` fills whole sublane tiles; a 0 / 1 spread matmul measured
    10 % slower at 64 heads); only the blocks that reach past the tile's
    first query position compare positions at all.

    ``selected``: ``sel_ref`` [1, tq, >= pages x bs] float32 marks, a
    query TOKEN, the keys it attends (> 0.5; one mark serves all its
    heads); every live page is read whatever it marks: the masked WALK."""
    sel_ref = rest[0] if selected else None
    pages_hbm, o_ref, buf, sems, acc_ref, m_ref, l_ref = (
        rest[1:] if selected else rest)
    b, qi = pl.program_id(0), pl.program_id(1)
    P, bs, C = buf.shape[1], block_size, buf.shape[3]
    W = tables_ref.shape[1]
    cols = P * bs
    lyr = layer_ref[0]
    ctx = ctx_ref[b]
    q_lo = starts_ref[b] + qi * tq
    q_hi = jnp.minimum(q_lo + tq, ctx)      # one past the tile's last real token
    n_pages = jnp.where(q_lo < ctx, (q_hi + bs - 1) // bs, 0)
    n_blocks = (n_pages + P - 1) // P
    # blocks whose every key lies at or before the tile's first query
    n_plain = jnp.minimum((q_lo + 1) // cols, n_blocks)

    def block_copies(i, slot, fn):
        """``fn`` (start or wait) on the copies of block ``i`` into
        ``slot``: one a live page, none for the slots past the last."""
        def page_copy(p, carry):
            fn(pltpu.make_async_copy(
                pages_hbm.at[lyr, tables_ref[b, i * P + p]],
                buf.at[slot, p], sems.at[slot]))
            return carry

        jax.lax.fori_loop(0, jnp.minimum(P, n_pages - i * P), page_copy, 0)

    start = lambda *a: block_copies(*a, lambda c: c.start())  # noqa: E731
    wait = lambda *a: block_copies(*a, lambda c: c.wait())  # noqa: E731

    m_ref[...] = jnp.full_like(m_ref, _NO_SCORE)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(n_blocks > 0)
    def _first_block():
        # page slots the last block leaves unfilled are masked by position,
        # but 0 x NaN is NaN in the PV dot: no slot may hold uninitialised
        # VMEM (a filled slot holds cache values, which are finite)
        buf[...] = jnp.zeros_like(buf)
        start(0, 0)

    # made once a tile: each query token's position
    tok_pos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (tq, 1), 0)

    def block(i, carry, *, edge: bool):
        slot = i % 2

        @pl.when(i + 1 < n_blocks)
        def _next_block():
            start(i + 1, 1 - slot)

        wait(i, slot)
        q = q_ref[0, 0]                                # [tq * H, C], scaled
        rows = buf[slot].reshape(cols, C)
        if rows.dtype != q.dtype:
            rows = rows.astype(q.dtype)
        s = jax.lax.dot_general(q, rows, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        keep = None                                    # [tq, cols], a token
        if selected:
            # a page's marks; the slots of a last block past the table's
            # width repeat its last column's (masked by position)
            keep = jnp.concatenate([
                sel_ref[0, :, pl.ds(pl.multiple_of(
                    jnp.minimum(i * P + p, W - 1) * bs, bs), bs)]
                for p in range(P)], axis=-1) > 0.5
        if edge:
            key_pos = i * cols + jax.lax.broadcasted_iota(
                jnp.int32, (1, cols), 1)
            causal = (key_pos <= tok_pos) & (key_pos < ctx)
            keep = causal if keep is None else keep & causal
        if keep is not None:
            # _MASKED + s is _MASKED: a score is some 2**70 times too small
            # to move it
            bias = jnp.where(keep, 0.0, _MASKED)
            s = (s.reshape(tq, heads, cols) + bias[:, None, :]).reshape(
                tq * heads, cols)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        # m_new >= _NO_SCORE > _MASKED: a masked key's exp is exactly 0, in
        # a block that holds no key of the row's too
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p.astype(rows.dtype), rows[:, :rank],
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new
        return carry

    jax.lax.fori_loop(0, n_plain, functools.partial(block, edge=False), 0)
    jax.lax.fori_loop(n_plain, n_blocks, functools.partial(block, edge=True), 0)
    # tokens with no valid key (padding): clamp, not NaN
    o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-9)).astype(o_ref.dtype)


def prefill_tile_tokens(T: int, heads: int) -> int:
    """Query tokens a grid step takes: ``PREFILL_TILE_ROWS`` rows of
    (token, head), halved while it does not divide ``T``."""
    tq = max(1, PREFILL_TILE_ROWS // heads)
    while tq > 1 and T % tq:
        tq //= 2
    return tq


@functools.partial(jax.jit, static_argnames=(
    "block_size", "rank", "interpret", "pages_per_block"))
def mla_prefill_attention(q, latent, layer, tables, start_pos, context_lens, *,
                          block_size: int, rank: int, interpret: bool = False,
                          pages_per_block: Optional[int] = None, sel=None):
    """``q`` [B, T, H, C] (key up-projection absorbed, softmax scale folded
    in): row ``b``'s token ``t`` sits at position ``start_pos[b] + t``;
    ``latent`` [Lm, slots, C] with this chunk's rows already written;
    ``tables`` [B, W]; ``context_lens`` [B] counts the chunk's real tokens
    (a tile at or past it, and rows of context 0, attend nothing and read
    no page). Returns the output IN LATENT SPACE, [B, T, H, rank]. A tile
    walks only the pages up to its own last token, ``pages_per_block`` to
    a compute block (for tests; by default sized from the call's own
    geometry, ``prefill_pages_per_block``). ``sel`` [B, T, W *
    block_size] float32, where given: a query token attends only the keys
    it marks > 0.5 (``ops/dsa.py`` ``select_topk``), every head alike, and
    the kernel is named ``dsa_prefill_attention``."""
    B, T, H, C = q.shape
    Lm, slots, _ = latent.shape
    pages = latent.reshape(Lm, slots // block_size, block_size, C)
    tq = prefill_tile_tokens(T, H)
    n_tiles = T // tq
    P = pages_per_block or prefill_pages_per_block(
        block_size, C, latent.dtype.itemsize, tq * H)
    tile = lambda width: pl.BlockSpec(  # noqa: E731
        (1, 1, tq * H, width), lambda b, qi, lyr, st, t, c: (b, qi, 0, 0))
    marks, mark_specs = (), []
    if sel is not None:
        marks = (sel.astype(jnp.float32),)
        mark_specs = [pl.BlockSpec(
            (1, tq, sel.shape[2]), lambda b, qi, lyr, st, t, c: (b, qi, 0))]

    out = pl.pallas_call(
        functools.partial(_prefill_kernel, block_size=block_size, rank=rank,
                          tq=tq, heads=H, selected=sel is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,  # layer, starts, tables, contexts
            grid=(B, n_tiles),
            in_specs=[tile(C), *mark_specs, pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=tile(rank),
            scratch_shapes=[
                pltpu.VMEM((2, P, block_size, C), latent.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((tq * H, rank), jnp.float32),
                pltpu.VMEM((tq * H, 1), jnp.float32),
                pltpu.VMEM((tq * H, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, n_tiles, tq * H, rank), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_PREFILL_VMEM_LIMIT_BYTES),
        name="mla_prefill_attention" if sel is None else "dsa_prefill_attention",
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1),
      jnp.asarray(start_pos, jnp.int32), tables.astype(jnp.int32),
      context_lens.astype(jnp.int32), q.reshape(B, n_tiles, tq * H, C),
      *marks, pages)
    return out.reshape(B, T, H, rank)
