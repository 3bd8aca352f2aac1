"""Pallas TPU paged-attention decode kernel.

The hot op of the decode loop (TPU replacement for the CUDA/Triton paged
attention the reference delegates to vLLM; ≈ the role of the patch's
Triton kernels, container/deps/vllm/...-patch kv_rearrange + vLLM's
paged_attention_v1). Semantics match
``models.llama.paged_attention_reference`` for T=1 queries, including
``sliding_window`` (Mistral-family).

Design of the decode kernel (see /opt/skills/guides/pallas_guide.md and
boom_attention_tricks.md §§9-11; body: ``_decode_kernel_stacked``):
- grid = (batch,): one step a row, no axis of the block table's width.
  K and V stay in HBM (``memory_space=pl.ANY``); ``block_tables`` and
  ``context_lens`` ride as scalar-prefetch args and the body itself
  walks the row's LIVE pages — from the page of the window's first key
  to the page of key ``ctx - 1`` — in a loop whose trip count is
  ``ceil(live pages / P)``. Table columns outside that range are never
  dereferenced and cost no time, where a grid over the table paid a
  step for each (40 columns at ``max_model_len`` 4096: seven steps in
  eight were dead at 64 rows); a row of context 0 runs no block.
- one compute block is ``P`` pages (``decode_pages_per_block``: from
  Hk, Dh, the cache dtype and a VMEM budget), each fetched by its own
  DMA into a ``[2, P, bs*Hk, Dh]`` double buffer; the next block, and
  from a row's last block the next row's first, is in flight while the
  current one is computed.
- a page is read as its ``bs*Hk`` (token, head) rows of ``Dh`` — the
  bytes as they lie in the cache ``[L, slots, Hk, Dh]``, whose layout
  does not change — and one fetch serves all ``H`` query heads: ONE dot
  of the ``H`` query rows against a block's rows, a mask sending the
  columns of other KV heads to probability 0, one softmax update, one
  PV dot (GQA needs no ``jnp.repeat``, no per-head slice, no
  concatenation).

HBM traffic per decode step is the live pages of K and V, each once —
the roofline minimum (``perf/roofline.py attn_decode_cost``) — and the
time follows it: 70-82 % of the HBM floor at 32-64 rows on a v5e, where
the grid over the table stood at 13-26 % (kernel alone; PERF.md, PR 30).

The prefill kernel below (``_prefill_kernel_stacked``) is the same walk
under a TILE of query tokens, ``grid=(B, n_tiles)``: a tile's live pages,
``P`` to a block, the next tile's first block started from this tile's
last; a KV head's rows are taken out of a block once, a dot a head.

TP: attention is local per KV-head shard, so multi-device meshes wrap
this kernel in ``shard_map`` over the "tp" axis (models/llama.py
attend_mlp) — one kernel instance per shard, no collectives.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _scale_rows(ks2: jax.Array, rows_per_hk: int) -> jax.Array:
    """Expand a per-page scale tile [Hk, bs] to score-row layout
    [Hk*rows_per_hk, bs] (rows are hk-major in both kernels). The tile
    is loaded in this orientation directly from the [L, N, Hk, bs]
    scale storage (ops/kv_quant.py explains why that layout is the one
    Mosaic accepts), so the expansion is a broadcast + leading-dim
    merge — the lane dim (bs) never moves."""
    Hk, bs = ks2.shape
    return jnp.broadcast_to(
        ks2[:, None, :], (Hk, rows_per_hk, bs)
    ).reshape(Hk * rows_per_hk, bs)


# VMEM the K and V double buffers of the decode kernel may take
# together: [2 slots, P pages] of K and of V.
_DECODE_KV_BUFFER_BYTES = 8 * 2**20
# ceiling on a compute block's score columns (P * block_size * Hk): the
# f32 scores, the probabilities and the head mask are [H, columns] each
_DECODE_BLOCK_COLUMNS = 4096
# scoped VMEM the decode kernel asks for: buffers + [H, columns] temporaries
_DECODE_VMEM_LIMIT_BYTES = 48 * 2**20


def decode_pages_per_block(
    block_size: int, Hk: int, Dh: int, itemsize: int,
    Dv: Optional[int] = None,
) -> int:
    """Pages of one compute block of the decode kernel, from what the
    call sees: as many as the double buffer's VMEM budget holds of this
    geometry's pages, while the block's score row stays within
    ``_DECODE_BLOCK_COLUMNS`` — a block's compute does not shrink with
    the pages it holds, so a short row pays for a whole one. At
    128-token pages of 128-wide heads: Llama / Mistral's 8 KV heads 4,
    Qwen's 4 heads 8, a tp=4 shard's 2 or 1 heads 16 or 32; the v5e
    sweep was flat from 4 to 16 pages at both geometries and lost 25 %
    at 16 384 columns (PERF.md, PR 30). Never above 32: the int8 path
    unrolls its scale spread over the pages. ``Dv``: the width of a V
    row where it is not K's ``Dh`` (a K page and a V page then differ
    in bytes; the budget holds two slots of each)."""
    kv_page_bytes = block_size * Hk * (Dh + (Dv or Dh)) * itemsize
    by_vmem = _DECODE_KV_BUFFER_BYTES // (2 * kv_page_bytes)
    by_columns = _DECODE_BLOCK_COLUMNS // (block_size * Hk)
    return max(1, min(by_vmem, by_columns, 32))


def _decode_kernel_stacked(
    layer_ref,  # scalar prefetch: [1] int32 — layer to read
    tables_ref,  # scalar prefetch: [B, W] int32
    ctx_ref,  # scalar prefetch: [B] int32
    *refs,  # q, [sinks,] k, v, [ks, vs,] o, then scratch — scales iff quantized
    block_size: int,
    scale: float,
    window: Optional[int],
    quantized: bool,
    sinks: bool = False,
):
    """THE flash-decode kernel body: one grid step a ROW, over a stacked
    cache left in HBM as pages ``[L, N, bs*Hk, Dh]`` (the per-layer API
    wraps it with L=1). A row walks its LIVE pages only — from the page
    holding the window's first key to the page holding key ``ctx - 1`` —
    ``P`` of them to a compute block, each page one DMA into a
    ``[2, P, bs*Hk, Dh]`` double buffer. The next block (and, from a
    row's last block, the next row's first block) is in flight while the
    current one is computed; table columns past the live range are never
    dereferenced, and a row of context 0 runs no block at all.

    Why the layer is an index and not a slice: slicing one layer out of
    the carried cache before a pallas_call materializes a full-layer
    copy at the custom-call boundary (XLA cannot fuse a producer slice
    into a custom call) — ~11 ms/step at a 4.7 GB cache.

    One compute block is ``C = P * bs * Hk`` score columns, a column
    being one (token, KV head) row of the pages as they lie in HBM
    (token-major, head-minor): the scores are ONE dot of all ``H`` query
    rows against all ``C`` rows, ``[H, C]`` f32, and a column whose KV
    head is not the query row's own gets -1e30 from a mask built once a
    call (``bias_ref``), so its probability is exactly 0 and the PV dot
    ``[H, C] x [C, Dh]`` needs no per-head split either. The MXU pushes
    the same K and V tiles as Hk per-head dots would (a page is
    ``bs*Hk*Dh / 128^2`` weight tiles either way); what goes is the
    sublane-strided ``k[:, hk, :]`` relayout, the concatenations of
    G-row pieces, and all but one softmax update and accumulator
    read-modify-write a block.

    ``quantized``: int8 cache values with per-(slot, head) f32 scales
    stored [L, N, Hk, bs]; their pages ride two more double buffers. K's
    scale applies to the f32 SCORES per column (exact: int8 -> bf16 is
    lossless, so the only rounding is the quantization itself); V's
    scale folds into the probabilities before the PV dot (p is f32 at
    that point). The scale tile lies [Hk, bs] and the columns run
    (token, head): ``spread_ref`` [bs, bs*Hk], 1 where column // Hk ==
    token, moves each scale over its token's Hk columns through an f32
    (``HIGHEST``: exact against 0 / 1) dot — the lane dim is never
    reshaped. An fp8 cache has no scales and upcasts in the kernel.

    K rows and V rows may differ in width (``Dk`` from the queries and
    K's buffer, ``Dv`` from V's: the accumulator and the output are
    ``Dv`` wide). ``sinks``: one learned logit a query head, ``[H, 1]``
    float32, that takes probability and adds no value — one more column
    of the softmax, which in the online recurrence is its START: ``m``
    the sink, ``l`` 1, ``acc`` 0."""
    sink_ref = None
    if sinks:
        q_ref, sink_ref, *refs = refs
        refs = (q_ref, *refs)
    if quantized:
        (q_ref, k_hbm, v_hbm, ks_hbm, vs_hbm, o_ref,
         k_buf, v_buf, sems, state, bias_ref,
         ks_buf, vs_buf, spread_ref) = refs
    else:
        (q_ref, k_hbm, v_hbm, o_ref,
         k_buf, v_buf, sems, state, bias_ref) = refs
        ks_hbm = vs_hbm = ks_buf = vs_buf = spread_ref = None
    b = pl.program_id(0)
    B = pl.num_programs(0)
    H, Dh = q_ref.shape[1], q_ref.shape[2]
    Dv = v_buf.shape[3]
    P, rows = k_buf.shape[1], k_buf.shape[2]  # rows = bs * Hk a page
    bs = block_size
    Hk = rows // bs
    G = H // Hk
    C = P * rows
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
        k_buf[...] = jnp.zeros_like(k_buf)
        v_buf[...] = jnp.zeros_like(v_buf)
        col_head = jax.lax.broadcasted_iota(jnp.int32, (H, C), 1) % Hk
        row_head = jax.lax.broadcasted_iota(jnp.int32, (H, C), 0) // G
        bias_ref[...] = jnp.where(col_head == row_head, 0.0, -1e30)
        if quantized:
            ks_buf[...] = jnp.zeros_like(ks_buf)
            vs_buf[...] = jnp.zeros_like(vs_buf)
            tok = jax.lax.broadcasted_iota(jnp.int32, (bs, rows), 0)
            col_tok = jax.lax.broadcasted_iota(jnp.int32, (bs, rows), 1) // Hk
            spread_ref[...] = (tok == col_tok).astype(jnp.float32)

    def live_pages(row):
        """(ctx, lo, first live page, number of live pages) of a row."""
        ctx = ctx_ref[row]
        lo = jnp.int32(0) if window is None else jnp.maximum(ctx - window, 0)
        first = lo // bs
        n = jnp.where(ctx > 0, (ctx - 1) // bs - first + 1, 0)
        return ctx, lo, first, n

    def block_copies(row, first, n, i, slot, fn):
        """``fn`` (start or wait) on the copies of block ``i`` of ``row``
        into ``slot``: one a live page and plane, none for the block's
        slots past the row's last live page."""
        planes = [(k_hbm, k_buf, 0), (v_hbm, v_buf, 1)]
        if quantized:
            planes += [(ks_hbm, ks_buf, 2), (vs_hbm, vs_buf, 3)]

        def page_copies(p, carry):
            page = tables_ref[row, first + i * P + p]
            for hbm, buf, s in planes:
                fn(pltpu.make_async_copy(
                    hbm.at[lyr, page], buf.at[slot, p], sems.at[s, slot]
                ))
            return carry

        jax.lax.fori_loop(0, jnp.minimum(P, n - i * P), page_copies, 0)

    start = lambda *a: block_copies(*a, lambda c: c.start())  # noqa: E731
    wait = lambda *a: block_copies(*a, lambda c: c.wait())  # noqa: E731

    ctx, lo, first, n_pages = live_pages(b)
    n_blocks = (n_pages + P - 1) // P
    slot0 = state[0]
    nxt = jnp.minimum(b + 1, B - 1)
    _, _, first_nxt, n_pages_nxt = live_pages(nxt)
    prefetch_nxt = (b + 1 < B) & (n_pages_nxt > 0) & (n_blocks > 0)

    @pl.when((n_blocks > 0) & (state[1] == 0))
    def _own_first_block():
        start(b, first, n_pages, 0, slot0)

    q = q_ref[0]

    def spread_scales(buf, slot):
        """[H, C] f32: a block's [P, Hk, bs] scale tiles laid over the
        score columns, each query row reading its own KV head's."""
        tiles = jnp.dot(
            buf[slot].reshape(P * Hk, bs), spread_ref[...],
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        ).reshape(P, Hk, rows)
        return jnp.concatenate(
            [_scale_rows(tiles[p], G) for p in range(P)], axis=1
        )

    def block(i, carry):
        m_prev, l_prev, acc = carry
        slot = (slot0 + i) % 2

        @pl.when(i + 1 < n_blocks)
        def _next_block():
            start(b, first, n_pages, i + 1, 1 - slot)

        @pl.when((i + 1 == n_blocks) & prefetch_nxt)
        def _next_row():
            start(nxt, first_nxt, n_pages_nxt, 0, 1 - slot)

        wait(b, first, n_pages, i, slot)
        # storage dtype straight into the MXU (bf16 operands, f32
        # accumulation). A quantized cache (int8, or fp8 with no
        # scales) upcasts to the query dtype here: every int8 / e4m3
        # value is exactly representable in bf16, so the HBM read is
        # byte-halved and the dot itself stays bf16 x bf16.
        k = k_buf[slot].reshape(C, Dh)
        v = v_buf[slot].reshape(C, Dv)
        if k.dtype != q.dtype:
            k = k.astype(q.dtype)
            v = v.astype(q.dtype)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        if quantized:
            # K dequant via per-column score scaling (f32, exact)
            s = s * spread_scales(ks_buf, slot)
        pos = (first + i * P) * bs + jax.lax.broadcasted_iota(
            jnp.int32, (1, C), 1
        ) // Hk
        valid = (pos < ctx) & (pos >= lo)
        s = jnp.where(valid, s + bias_ref[...], -1e30)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        # every block holds a key of the row's live range and every
        # query row a column of its own head there, so m_new is a real
        # score and a masked column's exp is exactly 0
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        if quantized:
            # V dequant folded into the probabilities while still f32
            p = p * spread_scales(vs_buf, slot)
        pv = jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32
        )
        return m_new, l_new, acc * alpha + pv

    _, l, acc = jax.lax.fori_loop(
        0, n_blocks, block,
        (
            jnp.full((H, 1), -1e30, jnp.float32) if sink_ref is None
            else sink_ref[...],
            jnp.full((H, 1), 0.0 if sink_ref is None else 1.0, jnp.float32),
            jnp.zeros((H, Dv), jnp.float32),
        ),
    )
    state[0] = (slot0 + n_blocks) % 2
    state[1] = prefetch_nxt.astype(jnp.int32)
    o_ref[0] = (acc / jnp.maximum(l, 1e-9)).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "block_size", "sliding_window", "interpret", "pages_per_block",
        "scale", "name",
    ),
)
def paged_attention_decode_stacked(
    q: jax.Array,  # [B, H, Dh]
    k_cache: jax.Array,  # [L, n_slots, Hkv, Dh] — the FULL stacked cache
    v_cache: jax.Array,  # [L, n_slots, Hkv, Dv] (Dv = Dh unless it says so)
    layer_idx: jax.Array,  # scalar int32 — layer to attend over
    block_tables: jax.Array,  # [B, W] int32
    context_lens: jax.Array,  # [B] int32
    block_size: int,
    sliding_window: Optional[int] = None,
    interpret: bool = False,
    k_scale: Optional[jax.Array] = None,  # [L, N, Hkv, bs] f32 (int8 cache)
    v_scale: Optional[jax.Array] = None,
    pages_per_block: Optional[int] = None,
    sinks: Optional[jax.Array] = None,  # [H] f32: a learned logit a head
    scale: Optional[float] = None,
    name: Optional[str] = None,
) -> jax.Array:
    """Decode attention over layer ``layer_idx`` of the stacked cache.

    Equivalent to ``paged_attention_decode(q, k_cache[layer_idx], ...)``
    but WITHOUT materializing the layer slice (see
    _decode_kernel_stacked). This is the hot decode path the engine's
    layer scan uses: the cache stays a scan carry in HBM and only the
    pages of each row's live range move, whatever the table's width.

    ``k_scale``/``v_scale``: per-(slot, head) dequant scales for an
    int8 cache, stored [L, N, Hk, bs] (layout rationale:
    ops/kv_quant.py). ``pages_per_block``: pages of one compute block;
    by default sized from the geometry (``decode_pages_per_block``).

    What a CALL says, not the model: the KV heads and the widths come
    from the arrays (V rows may be narrower than K rows; the output is
    as wide as V), ``sliding_window`` and ``sinks`` (``[H]`` float32,
    the kernel's docstring) from the arguments, so layers of different
    kinds in one model each make their own call. ``scale``: the score
    scale where it is not ``Dh ** -0.5`` (K rows stored wider than the
    head, the rest zeros)."""
    B, H, Dh = q.shape
    L, S, Hk, _ = k_cache.shape
    Dv = v_cache.shape[-1]
    N = S // block_size
    rows = block_size * Hk
    if scale is None:
        scale = 1.0 / math.sqrt(Dh)
    quantized = k_scale is not None
    P = pages_per_block or decode_pages_per_block(
        block_size, Hk, Dh, k_cache.dtype.itemsize, Dv
    )

    # a page as its (token, head) rows: the same bytes in the same order
    kp = k_cache.reshape(L, N, rows, Dh)
    vp = v_cache.reshape(L, N, rows, Dv)
    layer_arr = jnp.asarray(layer_idx, jnp.int32).reshape(1)

    row_spec = pl.BlockSpec((1, H, Dh), lambda b, lyr, t, c: (b, 0, 0))
    out_spec = row_spec if Dv == Dh else pl.BlockSpec(
        (1, H, Dv), lambda b, lyr, t, c: (b, 0, 0))
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    in_specs = [row_spec, in_hbm, in_hbm]
    inputs = [q, kp, vp]
    if sinks is not None:
        in_specs.insert(1, pl.BlockSpec((H, 1), lambda b, lyr, t, c: (0, 0)))
        inputs.insert(1, sinks.astype(jnp.float32).reshape(H, 1))
    scratch = [
        pltpu.VMEM((2, P, rows, Dh), k_cache.dtype),
        pltpu.VMEM((2, P, rows, Dv), v_cache.dtype),
        pltpu.SemaphoreType.DMA((4 if quantized else 2, 2)),
        pltpu.SMEM((2,), jnp.int32),
        pltpu.VMEM((H, P * rows), jnp.float32),  # head mask
    ]
    if quantized:
        in_specs += [in_hbm, in_hbm]
        inputs += [k_scale, v_scale]
        scratch += [
            pltpu.VMEM((2, P, Hk, block_size), jnp.float32),
            pltpu.VMEM((2, P, Hk, block_size), jnp.float32),
            pltpu.VMEM((block_size, rows), jnp.float32),  # scale spread
        ]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,  # layer, block_tables, context_lens
        grid=(B,),
        in_specs=in_specs,
        out_specs=out_spec,
        scratch_shapes=scratch,
    )
    kernel_kw = {"sinks": True} if sinks is not None else {}
    return pl.pallas_call(
        functools.partial(
            _decode_kernel_stacked, block_size=block_size, scale=scale,
            window=sliding_window, quantized=quantized, **kernel_kw,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, Dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            # rows run in order: a row starts the next row's first block
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_DECODE_VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
        name=name,
    )(layer_arr, block_tables, context_lens, *inputs)


# (token, head) rows of one query tile of the prefill kernel: its
# accumulator [rows, Dv] float32 is 1 MB at 2 048 x 128 and one KV head's
# score dot streams 256-1 024 rows past each K tile pushed
_PREFILL_TILE_ROWS = 2048
# ceiling on ONE KV head's float32 score of a compute block,
# [tile rows / Hk, P * block_size]: the probabilities and their copy in
# the value dtype are temporaries of that shape beside it, and the
# compiler keeps more than one head's in flight
_PREFILL_SCORE_BYTES = 4 * 2**20
# ceiling on a compute block's keys: a block's compute does not shrink
# with the pages it holds, so a tile of few live pages pays for a whole
# one (on a v5e 8 pages of 128 beat 16 at <= 1k keys by 25-40 % and lose
# 2-5 % at 12k; 4 lose 10-40 % from 2k keys on: PERF.md, PR 51)
_PREFILL_BLOCK_KEYS = 1024
# scoped VMEM the prefill kernel asks for: the double buffers (the decode
# kernel's budget, ``_DECODE_KV_BUFFER_BYTES``), the tile's state and the
# score temporaries
_PREFILL_VMEM_LIMIT_BYTES = 64 * 2**20
_MASKED = -1e30     # a masked key's score
_NO_SCORE = -1e29   # where a row's running max starts: above ``_MASKED``


def prefill_tile_tokens(T: int, H: int) -> int:
    """Query tokens of one grid step of the prefill kernel:
    the power of two that ``_PREFILL_TILE_ROWS`` (token, head) rows of
    the call's ``H`` heads hold, halved while it does not divide ``T``;
    a rectangle shorter than that is one tile."""
    tq = 1 << max(0, (_PREFILL_TILE_ROWS // H).bit_length() - 1)
    if T <= tq:
        return T
    while tq > 1 and T % tq:
        tq //= 2
    return tq


def prefill_pages_per_block(
    block_size: int, Hk: int, Dh: int, itemsize: int, Dv: int,
    head_rows: int, tq: int, sliding_window: Optional[int] = None,
) -> int:
    """Pages of one compute block of the prefill kernel, from what the
    call sees: as many as the decode kernel's double-buffer budget holds
    of this geometry's K and V pages, while ONE KV head's float32 score,
    ``[head_rows, P * block_size]``, stays within
    ``_PREFILL_SCORE_BYTES`` and the block within
    ``_PREFILL_BLOCK_KEYS`` — and never more than a tile of ``tq``
    tokens can have live under ``sliding_window`` (a window of 128 under
    a 32-token tile touches 2 pages of 128, 3 where the chunk starts off
    a page's edge) — rounded down to a power of two. At 128-token pages
    of bf16 every served geometry gets 8 (Llama / Mistral 32 / 8,
    Qwen2.5 28 / 4, heads of 256 at 16 / 2, 32 / 2, mimo's full layers
    64 / 4 with K 256 and V 128 wide), mimo's window layers 2."""
    by_vmem = _DECODE_KV_BUFFER_BYTES // (
        2 * block_size * Hk * (Dh + Dv) * itemsize)
    by_score = _PREFILL_SCORE_BYTES // (head_rows * block_size * 4)
    P = max(1, min(by_vmem, by_score, _PREFILL_BLOCK_KEYS // block_size))
    if sliding_window is not None:
        run = sliding_window - 1 + tq  # keys a tile's queries can see
        P = min(P, (run - 2) // block_size + 2 if run > 1 else 1)
    return 1 << (P.bit_length() - 1)


def _lane_chunks(width: int) -> tuple[int, int]:
    """(chunks, lanes a chunk): a page's rows are fetched in 128-lane
    chunks, a DMA each, because the strided loads that take a KV head's
    rows out of a block want a buffer exactly one lane tile wide."""
    return (width // 128, 128) if width % 128 == 0 else (1, width)


def _kv_head_rows(ref, Hk: int, first, out_dtype):
    """The ``[n, width]`` rows of KV head ``first`` — and, where rows are
    16 bits wide, of head ``first + 1`` (``first`` even, traced or not) —
    out of a block held in VMEM as ``ref`` ``[chunks, n * Hk, lanes]``,
    (token, head) rows token-major as they lie in the cache; a list of one
    or two arrays. One head: the block itself. 32-bit rows: a
    sublane-strided load a head. 16-bit rows, two to a sublane word: a
    word holds heads ``2j`` and ``2j + 1`` of one token (``Hk`` even), so
    the even tokens' and the odd tokens' words of a pair come by strided
    loads of the 32-bit view and three integer ops a head put two tokens
    of ONE head into each word — the layout the MXU takes its operand in,
    with no relayout of the block."""
    chunks, n = ref.shape[0], ref.shape[1] // Hk

    def lanes(parts):
        return parts[0] if chunks == 1 else jnp.concatenate(parts, axis=1)

    if Hk == 1:
        return [lanes([ref[c] for c in range(chunks)]).astype(out_dtype)]
    if ref.dtype.itemsize == 4:
        return [lanes([
            ref[c, pl.ds(first, n, stride=Hk), :] for c in range(chunks)
        ]).astype(out_dtype)]
    words = ref.bitcast(jnp.uint32)  # [chunks, n * Hk / 2, lanes]
    j = first // 2
    lo, hi = [], []
    for c in range(chunks):
        even = words[c, pl.ds(j, n // 2, stride=Hk), :]
        odd = words[c, pl.ds(j + Hk // 2, n // 2, stride=Hk), :]
        lo.append((even & 0xFFFF) | (odd << 16))
        hi.append((even >> 16) | (odd & jnp.uint32(0xFFFF0000)))
    return [pltpu.bitcast(lanes(x), ref.dtype).astype(out_dtype)
            for x in (lo, hi)]


def _prefill_kernel_stacked(
    layer_ref,   # scalar prefetch: [1] int32
    starts_ref,  # scalar prefetch: [B] int32 — first query position per row
    tables_ref,  # scalar prefetch: [B, W] int32
    ctx_ref,     # scalar prefetch: [B] int32 (context incl. this chunk)
    *refs,  # q, [sinks,] k, v, [ks, vs,] o, then scratch — scales iff quantized
    block_size: int,
    tq: int,
    scale: float,
    window: Optional[int],
    quantized: bool,
    staged: bool,
    sinks: bool,
):
    """Flash prefill over the paged cache: one grid step a TILE of ``tq``
    query tokens (all heads, as ``[Hk, tq * G, Dh]``: a KV head's rows
    run (token, group)), over the stacked cache left in HBM as pages
    ``[L, N, bs*Hk, D]`` — the decode kernel's view, the stored bytes.
    The tile walks its LIVE pages only — from the page holding its
    window's first key (column 0 without a window) to the page holding
    key ``min(start + (qi + 1) * tq, ctx) - 1`` — ``P`` of them to a
    compute block, each page's K and V a DMA a 128-lane chunk into
    ``[2, chunks, P * bs*Hk, 128]`` double buffers. The next block, and
    from a tile's last block the first block of the next live tile
    (the row's next, or the next row's first), is in flight while this
    one is computed. Table columns outside the live range are never
    dereferenced; a row of context 0 and a tile at or past its row's
    context start no copy and store zeros. The chunk's own K/V are read
    back from the cache (the caller scatters them in before attending),
    so chunked long prompts attend their full prefix and no ``[T, S]``
    score exists in HBM.

    One block is, a KV head: its ``P * bs`` rows taken out of the
    (token, head) rows (``_kv_head_rows``), ONE score dot
    ``[tq * G, Dh] x [P * bs, Dh]^T``, one online-softmax update (state
    in VMEM across the blocks), one value dot. Made once a tile: the
    rows' query positions and the state's start; once a block and only
    in the blocks that need it — those reaching past the tile's first
    query position or before its last query's window edge — the causal /
    window / context compare, one ``[tq * G, P * bs]`` bias for all KV
    heads. The (kv head, token, group) order of the query and output
    rows is the wrapper's transpose, in XLA.

    ``quantized`` (int8 values, f32 scales ``[L, N, Hk, bs]`` on two
    more double buffers): K's scale on the f32 scores a column, V's on
    the probabilities, as the decode kernel. ``staged``: the cache's
    dtype is not the queries' (int8, fp8, or 16-bit rows whose heads do
    not pair), so a block is upcast whole — exactly — into ``stage``
    buffers before its heads are taken. V rows may be narrower than K
    rows; ``sinks``: ``[Hk, tq * G, 1]`` float32, the state a tile
    starts from (``m`` the sink, ``l`` 1, ``acc`` 0)."""
    sink_ref = None
    if sinks:
        q_ref, sink_ref, *refs = refs
        refs = (q_ref, *refs)
    q_ref, k_hbm, v_hbm, *refs = refs
    ks_hbm = vs_hbm = ks_buf = vs_buf = k_stage = v_stage = None
    if quantized:
        ks_hbm, vs_hbm, *refs = refs
    o_ref, k_buf, v_buf, sems, state, acc_ref, m_ref, l_ref, *refs = refs
    if quantized:
        ks_buf, vs_buf, *refs = refs
    if staged:
        k_stage, v_stage = refs
    b, qi = pl.program_id(0), pl.program_id(1)
    B, n_tiles = pl.num_programs(0), pl.num_programs(1)
    Hk, rows_h = q_ref.shape[2], q_ref.shape[3]
    G = rows_h // tq
    bs = block_size
    rows = bs * Hk                       # (token, head) rows a page
    P = k_buf.shape[2] // rows
    n = P * bs                           # keys a block
    W = tables_ref.shape[1]
    lyr = layer_ref[0]

    @pl.when((b == 0) & (qi == 0))
    def _first_tile():
        # slot of this tile's first block; whether the tile before has
        # already started it
        state[0] = 0
        state[1] = 0
        # page slots a block leaves unfilled are masked by position, but
        # 0 x NaN is NaN in the PV dot: no slot may hold uninitialised
        # VMEM (a filled slot holds cache values, which are finite)
        k_buf[...] = jnp.zeros_like(k_buf)
        v_buf[...] = jnp.zeros_like(v_buf)
        if quantized:
            ks_buf[...] = jnp.zeros_like(ks_buf)
            vs_buf[...] = jnp.zeros_like(vs_buf)

    def live_pages(row, tile):
        """(first query position, first live page, live pages) of a tile."""
        ctx = ctx_ref[row]
        q_lo = starts_ref[row] + tile * tq
        q_hi = jnp.minimum(q_lo + tq, ctx)  # one past its last real token
        first = (
            jnp.int32(0) if window is None
            else jnp.maximum(q_lo - (window - 1), 0) // bs
        )
        last = jnp.minimum((q_hi - 1) // bs, W - 1)
        return q_lo, first, jnp.where(q_lo < ctx, last - first + 1, 0)

    def block_copies(row, first, n_pages, i, slot, fn):
        """``fn`` (start or wait) on the copies of block ``i`` of a tile
        of ``row`` into ``slot``: one a live page, plane and lane chunk,
        none for the block's slots past the tile's last live page."""
        planes = [(k_hbm, k_buf, 0), (v_hbm, v_buf, 1)]

        def page_copies(p, carry):
            page = tables_ref[row, first + i * P + p]
            at = pl.ds(pl.multiple_of(p * rows, rows), rows)
            for hbm, buf, s in planes:
                chunks, lanes = buf.shape[1], buf.shape[3]
                for c in range(chunks):
                    fn(pltpu.make_async_copy(
                        hbm.at[lyr, page, :, pl.ds(c * lanes, lanes)],
                        buf.at[slot, c, at], sems.at[s, slot]))
            if quantized:
                for hbm, buf, s in ((ks_hbm, ks_buf, 2), (vs_hbm, vs_buf, 3)):
                    fn(pltpu.make_async_copy(
                        hbm.at[lyr, page], buf.at[slot, p], sems.at[s, slot]))
            return carry

        jax.lax.fori_loop(0, jnp.minimum(P, n_pages - i * P), page_copies, 0)

    start = lambda *a: block_copies(*a, lambda c: c.start())  # noqa: E731
    wait = lambda *a: block_copies(*a, lambda c: c.wait())  # noqa: E731

    ctx = ctx_ref[b]
    q_lo, first, n_pages = live_pages(b, qi)
    n_blocks = (n_pages + P - 1) // P
    slot0 = state[0]
    row_ends = qi + 1 == n_tiles
    nxt_row = jnp.where(row_ends, jnp.minimum(b + 1, B - 1), b)
    nxt_tile = jnp.where(row_ends, 0, qi + 1)
    _, first_nxt, n_pages_nxt = live_pages(nxt_row, nxt_tile)
    prefetch_nxt = (
        ~(row_ends & (b + 1 == B)) & (n_pages_nxt > 0) & (n_blocks > 0)
    )

    @pl.when((n_blocks > 0) & (state[1] == 0))
    def _own_first_block():
        start(b, first, n_pages, 0, slot0)

    # made once a tile: the softmax state's start and each row's position
    if sink_ref is None:
        m_ref[...] = jnp.full_like(m_ref, _NO_SCORE)
        l_ref[...] = jnp.zeros_like(l_ref)
    else:
        m_ref[...] = sink_ref[...]
        l_ref[...] = jnp.ones_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    row_pos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (rows_h, 1), 0) // G

    def scale_row(buf, slot, hk):
        """[1, n] f32: KV head ``hk``'s scales over a block's keys."""
        return jnp.concatenate(
            [buf[slot, p, pl.ds(hk, 1), :] for p in range(P)], axis=1)

    def compute(slot, base, masked: bool):
        bias = None
        if masked:
            key_pos = base + jax.lax.broadcasted_iota(jnp.int32, (1, n), 1)
            keep = (key_pos <= row_pos) & (key_pos < ctx)
            if window is not None:
                keep &= key_pos > row_pos - window
            # _MASKED + s is _MASKED: a score is some 2**70 times too
            # small to move it
            bias = jnp.where(keep, 0.0, _MASKED)
        if staged:
            # quantized or unpaired rows: an exact upcast of the whole
            # block (int8 / e4m3 ⊂ bf16 ⊂ f32); HBM traffic stays the
            # cache's bytes
            k_stage[...] = k_buf[slot].astype(k_stage.dtype)
            v_stage[...] = v_buf[slot].astype(v_stage.dtype)
            k_rows, v_rows = k_stage, v_stage
        else:
            k_rows, v_rows = k_buf.at[slot], v_buf.at[slot]
        # KV heads in steps of what one extraction yields (a pair where
        # rows are 16 bits wide): a LOOP, not an unrolled body — the
        # compiler lays every vector op of a [rows, keys] score out
        # vreg by vreg, so a body of all heads is Hk x the code to load
        # at start-up (PERF.md, PR 51)
        dtype = q_ref.dtype
        step = len(_kv_head_rows(k_rows, Hk, 0, dtype))

        def head(hk, k, v):
            # operands in the queries' dtype (bf16 in serving) straight
            # into the MXU, f32 accumulation
            s = jax.lax.dot_general(
                q_ref[0, 0, hk], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale  # [tq * G, n] f32
            if quantized:
                # K dequant via per-column score scaling (f32, exact)
                s = s * scale_row(ks_buf, slot, hk)
            if masked:
                s = s + bias
            m_prev = m_ref[hk]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            # m_new >= _NO_SCORE > _MASKED: a masked key's exp is exactly
            # 0, for a row with no key of its own in this block too
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[hk] = l_ref[hk] * alpha + jnp.sum(p, axis=-1, keepdims=True)
            if quantized:
                # V dequant folded into the probabilities while still f32
                p = p * scale_row(vs_buf, slot, hk)
            acc_ref[hk] = acc_ref[hk] * alpha + jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32)
            m_ref[hk] = m_new

        def heads(i, carry):
            first = i * step
            ks = _kv_head_rows(k_rows, Hk, first, dtype)
            vs = _kv_head_rows(v_rows, Hk, first, dtype)
            for d, (k, v) in enumerate(zip(ks, vs)):
                head(first + d, k, v)
            return carry

        if Hk == step:
            heads(0, 0)
        else:
            jax.lax.fori_loop(0, Hk // step, heads, 0)

    # under a window a tile's first block holds its window's edge and its
    # last the diagonal: where no tile has more than two blocks the plain
    # body would hardly ever run, and is not built (half the kernel's
    # code); the masked one is right for any block
    all_masked = False
    if window is not None:
        run = window - 1 + tq
        all_masked = ((run - 2) // bs + 2 if run > 1 else 1) <= 2 * P

    def block(i, carry):
        slot = (slot0 + i) % 2

        @pl.when(i + 1 < n_blocks)
        def _next_block():
            start(b, first, n_pages, i + 1, 1 - slot)

        @pl.when((i + 1 == n_blocks) & prefetch_nxt)
        def _next_tile():
            start(nxt_row, first_nxt, n_pages_nxt, 0, 1 - slot)

        wait(b, first, n_pages, i, slot)
        base = (first + i * P) * bs  # position of the block's first key
        if all_masked:
            # (under a condition that always holds: Pallas' interpreter
            # cannot run a loop nested directly in a loop's body)
            pl.when(i < n_blocks)(lambda: compute(slot, base, True))
            return carry
        # the compare only where a key of the block lies past the tile's
        # first query, or at or before its last query's window edge
        edge = base + n - 1 > q_lo
        if window is not None:
            edge |= base <= q_lo + tq - 1 - window
        pl.when(edge)(lambda: compute(slot, base, True))
        pl.when(~edge)(lambda: compute(slot, base, False))
        return carry

    jax.lax.fori_loop(0, n_blocks, block, 0)
    state[0] = (slot0 + n_blocks) % 2
    state[1] = prefetch_nxt.astype(jnp.int32)
    # tokens at or past the context (padding) and rows with no valid key:
    # zeros, not NaN
    out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-9)
    o_ref[0, 0] = jnp.where(row_pos < ctx, out, 0.0).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "block_size", "sliding_window", "interpret", "pages_per_block",
        "scale", "name",
    ),
)
def paged_attention_prefill_stacked(
    q: jax.Array,  # [B, T, H, Dh] — a (possibly chunked) prefill rectangle
    k_cache: jax.Array,  # [L, n_slots, Hkv, Dh] stacked cache
    v_cache: jax.Array,
    layer_idx: jax.Array,  # scalar int32
    block_tables: jax.Array,  # [B, W] int32
    start_pos: jax.Array,  # [B] int32 — absolute position of q[:, 0]
    context_lens: jax.Array,  # [B] int32 — total context incl. this chunk
    block_size: int,
    sliding_window: Optional[int] = None,
    interpret: bool = False,
    k_scale: Optional[jax.Array] = None,  # [L, N, Hkv, bs] f32 (int8 cache)
    v_scale: Optional[jax.Array] = None,
    sinks: Optional[jax.Array] = None,  # [H] f32: a learned logit a head
    scale: Optional[float] = None,
    name: Optional[str] = None,
    pages_per_block: Optional[int] = None,
) -> jax.Array:
    """Flash prefill attention over the paged cache; returns
    [B, T, H, Dv] (``Dv`` = V's width, ``Dh`` unless the V cache is
    narrower; ``sinks`` and ``scale`` as the decode wrapper says). Requires the chunk's K/V to already be scattered
    into the cache (models/llama.py writes before attending). Rows are
    contiguous token runs: q[b, t] sits at absolute position
    start_pos[b] + t (padded rows: start 0 / ctx 0 -> all-masked).
    ``k_scale``/``v_scale``: int8-cache dequant scales (layout and
    constraints documented on paged_attention_decode_stacked).

    The cache is read in place, as the decode wrapper reads it (the
    stacked cache whole, the layer an index, a page its ``bs*Hk`` stored
    rows): a tile of ``prefill_tile_tokens`` tokens walks its live pages
    alone, whatever the table's width. ``pages_per_block``: pages of one
    compute block, for tests; by default sized from the call's geometry
    (``prefill_pages_per_block``)."""
    B, T, H, Dh = q.shape
    L, S, Hk, _ = k_cache.shape
    Dv = v_cache.shape[-1]
    N = S // block_size
    G = H // Hk
    rows = block_size * Hk
    if scale is None:
        scale = 1.0 / math.sqrt(Dh)
    quantized = k_scale is not None
    tq = prefill_tile_tokens(T, H)
    n_tiles = T // tq
    rows_h = tq * G  # query rows a KV head a tile
    P = pages_per_block or min(block_tables.shape[1], prefill_pages_per_block(
        block_size, Hk, Dh, k_cache.dtype.itemsize, Dv, rows_h, tq,
        sliding_window,
    ))
    # the dtype a block's rows are taken a head in (``_kv_head_rows``): the
    # queries', unless that is 16-bit and the heads or a block's tokens do
    # not pair; a cache of another dtype is staged into it, block by block
    in_q_dtype = q.dtype.itemsize == 4 or Hk == 1 or (
        q.dtype.itemsize == 2 and Hk % 2 == 0 and (P * block_size) % 2 == 0)
    rows_dtype = q.dtype if in_q_dtype else jnp.float32
    staged = k_cache.dtype != rows_dtype

    # a page as its (token, head) rows: the same bytes in the same order
    kp = k_cache.reshape(L, N, rows, Dh)
    vp = v_cache.reshape(L, N, rows, Dv)
    # a tile's query rows a KV head, (token, group): head = hk * G + g
    qt = q.reshape(B, n_tiles, tq, Hk, G, Dh).swapaxes(2, 3).reshape(
        B, n_tiles, Hk, rows_h, Dh)
    layer_arr = jnp.asarray(layer_idx, jnp.int32).reshape(1)
    starts = jnp.asarray(start_pos, jnp.int32)

    def tile(width):
        return pl.BlockSpec(
            (1, 1, Hk, rows_h, width),
            lambda b, qi, lyr, st, t, c: (b, qi, 0, 0, 0))

    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    in_specs = [tile(Dh), in_hbm, in_hbm]
    inputs = [qt, kp, vp]
    if sinks is not None:
        in_specs.insert(1, pl.BlockSpec(
            (Hk, rows_h, 1), lambda b, qi, lyr, st, t, c: (0, 0, 0)))
        inputs.insert(1, jnp.broadcast_to(
            sinks.astype(jnp.float32).reshape(Hk, 1, G), (Hk, tq, G),
        ).reshape(Hk, rows_h, 1))
    k_chunks, k_lanes = _lane_chunks(Dh)
    v_chunks, v_lanes = _lane_chunks(Dv)
    scratch = [
        pltpu.VMEM((2, k_chunks, P * rows, k_lanes), k_cache.dtype),
        pltpu.VMEM((2, v_chunks, P * rows, v_lanes), v_cache.dtype),
        pltpu.SemaphoreType.DMA((4 if quantized else 2, 2)),
        pltpu.SMEM((2,), jnp.int32),
        pltpu.VMEM((Hk, rows_h, Dv), jnp.float32),  # accumulator
        pltpu.VMEM((Hk, rows_h, 1), jnp.float32),  # running max
        pltpu.VMEM((Hk, rows_h, 1), jnp.float32),  # running sum
    ]
    if quantized:
        in_specs += [in_hbm, in_hbm]
        inputs += [k_scale, v_scale]
        scratch += [
            pltpu.VMEM((2, P, Hk, block_size), jnp.float32),
            pltpu.VMEM((2, P, Hk, block_size), jnp.float32),
        ]
    if staged:
        scratch += [
            pltpu.VMEM((k_chunks, P * rows, k_lanes), rows_dtype),
            pltpu.VMEM((v_chunks, P * rows, v_lanes), rows_dtype),
        ]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,  # layer, starts, block_tables, context_lens
        grid=(B, n_tiles),
        in_specs=in_specs,
        out_specs=tile(Dv),
        scratch_shapes=scratch,
    )
    out = pl.pallas_call(
        functools.partial(
            _prefill_kernel_stacked, block_size=block_size, tq=tq,
            scale=scale, window=sliding_window, quantized=quantized,
            staged=staged, sinks=sinks is not None,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, n_tiles, Hk, rows_h, Dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            # tiles run in order: a tile starts the next tile's first block
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_PREFILL_VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
        name=name,
    )(layer_arr, starts, block_tables, context_lens, *inputs)
    return out.reshape(B, n_tiles, Hk, tq, G, Dv).swapaxes(2, 3).reshape(
        B, T, H, Dv)


@functools.partial(
    jax.jit, static_argnames=(
        "block_size", "sliding_window", "interpret", "pages_per_block",
        "scale",
    ),
)
def paged_attention_decode(
    q: jax.Array,  # [B, H, Dh] (decode: one query token per sequence)
    k_cache_l: jax.Array,  # [n_slots, Hkv, Dh] (one layer)
    v_cache_l: jax.Array,
    block_tables: jax.Array,  # [B, W] int32
    context_lens: jax.Array,  # [B] int32
    block_size: int,
    sliding_window: Optional[int] = None,
    interpret: bool = False,
    k_scale: Optional[jax.Array] = None,  # [N, Hkv, bs] f32 (int8 cache)
    v_scale: Optional[jax.Array] = None,
    pages_per_block: Optional[int] = None,
    sinks: Optional[jax.Array] = None,
    scale: Optional[float] = None,
) -> jax.Array:
    """Returns [B, H, Dh] attention outputs.

    Thin wrapper over the stacked kernel with a single-layer stack
    (k_cache_l[None] is a free expand-dims) — ONE flash-decode kernel
    body serves both the per-layer API (tests, external callers) and
    the engine's stacked hot path."""
    return paged_attention_decode_stacked(
        q, k_cache_l[None], v_cache_l[None], jnp.int32(0), block_tables,
        context_lens, block_size=block_size, sliding_window=sliding_window,
        interpret=interpret,
        k_scale=None if k_scale is None else k_scale[None],
        v_scale=None if v_scale is None else v_scale[None],
        pages_per_block=pages_per_block, sinks=sinks, scale=scale,
    )
