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

The prefill kernel below still walks ``grid=(B, n_tiles, W)`` one page
a step, dead steps clamped onto the nearest live page (Pallas skips
the copy when the block index repeats; the compute is skipped via
``pl.when``): the same cure applies there (PERF.md §7).

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


def _prefill_kernel_stacked(
    layer_ref,   # scalar prefetch: [1] int32
    starts_ref,  # scalar prefetch: [B] int32 — first query position per row
    tables_ref,  # scalar prefetch: [B, W] int32
    ctx_ref,     # scalar prefetch: [B] int32 (context incl. this chunk)
    *refs,  # q, [sinks,] k, v, [ks, vs,] o, acc, m, l — scales iff quantized
    block_size: int,
    tq: int,
    scale: float,
    window: Optional[int],
    quantized: bool,
    sinks: bool = False,
):
    """Flash prefill over the paged cache: one query TILE of ``tq``
    tokens vs one KV page per grid step, causal (+ sliding window)
    masked, online-softmax state in VMEM across the page axis. The
    chunk's own K/V are read back from the cache (the caller scatters
    them in before attending), so chunked long prompts attend their
    full prefix without any [T, S] score materialization — the XLA
    reference path's [B, Hk, G, T, S] scores tensor is ~400 MB at
    T=1024/S=3072 and its HBM traffic dominates long-prompt TTFT.

    V pages may be narrower than K pages (the accumulator and the
    output are as wide as V). ``sinks``: a learned logit a query head,
    laid over the kernel's (kv head, token, group) rows as ``[rows, 1]``
    float32 — the state a tile starts from is ``m`` the sink, ``l`` 1,
    ``acc`` 0 (the decode kernel's docstring)."""
    sink_ref = None
    if sinks:
        q_ref, sink_ref, *refs = refs
        refs = (q_ref, *refs)
    if quantized:
        q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref, acc_ref, m_ref, l_ref = refs
    else:
        q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref = refs
        ks_ref = vs_ref = None
    b = pl.program_id(0)
    qi = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        if sink_ref is None:
            m_ref[:] = jnp.full_like(m_ref, -1e30)
            l_ref[:] = jnp.zeros_like(l_ref)
        else:
            m_ref[:] = sink_ref[...]
            l_ref[:] = jnp.ones_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    ctx = ctx_ref[b]
    start = starts_ref[b]
    # query positions covered by this tile
    q_lo = start + qi * tq
    q_hi_excl = jnp.minimum(start + (qi + 1) * tq, ctx)
    # keys this tile may attend: [lo_bound, q_hi_excl)
    lo_bound = (
        jnp.int32(0) if window is None
        else jnp.maximum(q_lo - (window - 1), 0)
    )
    page_live = (
        (j * block_size < q_hi_excl)
        & ((j + 1) * block_size > lo_bound)
        & (q_lo < ctx)
    )

    @pl.when(page_live)
    def _page():
        Tq, H, Dh = q_ref.shape[2], q_ref.shape[3], q_ref.shape[4]
        bs, Hk = k_ref.shape[2], k_ref.shape[3]
        G = H // Hk
        # keep q/k/v in their storage dtype (bf16 in serving): the MXU
        # takes bf16 operands natively with f32 accumulation, and f32
        # upcasts would double the kernel's VMEM footprint (scoped-vmem
        # OOM at block_size=128 geometries)
        q = q_ref[0, 0]  # [Tq, H, Dh]
        k = k_ref[0, 0]  # [bs, Hk, Dh]
        v = v_ref[0, 0]
        if k.dtype != q.dtype:
            # quantized fp8 cache: upcast to the query/compute dtype
            # (exact — e4m3 ⊂ bf16); HBM traffic stays 1 byte/elem
            k = k.astype(q.dtype)
            v = v.astype(q.dtype)
        # hk-major rows: [Hk, Tq*G, Dh] -> flat [Hk*Tq*G, Dh]
        qg = q.reshape(Tq, Hk, G, Dh).swapaxes(0, 1).reshape(Hk, Tq * G, Dh)
        s = jnp.concatenate(
            [
                jax.lax.dot_general(
                    qg[hk], k[:, hk, :], (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                for hk in range(Hk)
            ],
            axis=0,
        ) * scale  # [Hk*Tq*G, bs] f32
        if quantized:
            # int8 cache: K's per-(slot, head) scale applied to the f32
            # scores per column (see _decode_kernel_stacked)
            s = s * _scale_rows(ks_ref[0, 0], Tq * G)
        key_pos = j * block_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, bs), 1
        )  # [1, bs]
        # per-row query position: row r = (hk, t, g) -> q token t
        t_idx = (
            jax.lax.broadcasted_iota(jnp.int32, (Hk * Tq * G, 1), 0)
            // G % Tq
        )
        q_pos = q_lo + t_idx  # [rows, 1]
        valid = (key_pos <= q_pos) & (key_pos < ctx) & (q_pos < ctx)
        if window is not None:
            valid = valid & (key_pos > q_pos - window)
        s = jnp.where(valid, s, -1e30)
        m_prev = m_ref[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(valid, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[:] = l_ref[:] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        if quantized:
            # V dequant folded into the probabilities while still f32
            p = p * _scale_rows(vs_ref[0, 0], Tq * G)
        # p in the value dtype for the MXU (standard flash practice; the
        # softmax stats above stay f32)
        pg = p.astype(v.dtype).reshape(Hk, Tq * G, bs)
        pv = jnp.concatenate(
            [
                jax.lax.dot_general(
                    pg[hk], v[:, hk, :], (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                for hk in range(Hk)
            ],
            axis=0,
        )  # [Hk*Tq*G, Dh]
        acc_ref[:] = acc_ref[:] * alpha + pv
        m_ref[:] = m_new

    @pl.when(j == pl.num_programs(2) - 1)
    def _finish():
        Tq, H, Dh = q_ref.shape[2], q_ref.shape[3], v_ref.shape[4]
        Hk = k_ref.shape[3]
        G = H // Hk
        # rows with no valid key (padded rows/tokens): clamp, not NaN
        out = acc_ref[:] / jnp.maximum(l_ref[:], 1e-9)
        out = out.reshape(Hk, Tq, G, Dh).swapaxes(0, 1).reshape(Tq, H, Dh)
        o_ref[0, 0] = out.astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "block_size", "sliding_window", "interpret", "scale", "name"),
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
) -> jax.Array:
    """Flash prefill attention over the paged cache; returns
    [B, T, H, Dv] (``Dv`` = V's width, ``Dh`` unless the V cache is
    narrower; ``sinks`` and ``scale`` as the decode wrapper says). Requires the chunk's K/V to already be scattered
    into the cache (models/llama.py writes before attending). Rows are
    contiguous token runs: q[b, t] sits at absolute position
    start_pos[b] + t (padded rows: start 0 / ctx 0 -> all-masked).
    ``k_scale``/``v_scale``: int8-cache dequant scales (layout and
    constraints documented on paged_attention_decode_stacked)."""
    B, T, H, Dh = q.shape
    L, S, Hk, _ = k_cache.shape
    Dv = v_cache.shape[-1]
    N = S // block_size
    W = block_tables.shape[1]
    if scale is None:
        scale = 1.0 / math.sqrt(Dh)
    quantized = k_scale is not None
    # query tile: 128 keeps the kernel's VMEM state ~2 MB for the 8B
    # geometry at block_size=16; halve while the f32 working-set
    # ESTIMATE (acc + scores) exceeds 5 MB — measured actual usage runs
    # ~2.8x the estimate (17.5 MB at a 6.3 MB estimate: probs, masks,
    # relayout copies), and the scoped-VMEM budget is 16 MB, so 5 MB
    # estimated ≈ 14 MB actual with margin. Hit by big block_size
    # (128-token pages) and wide-H geometries (70B H=64).
    tq = 128 if T % 128 == 0 else T
    # only halve while divisibility survives (odd-factor T stops where
    # it is — the kernel then runs one bigger tile; correctness first)
    while tq > 16 and T % (tq // 2) == 0 and (
        tq * H * (Dh + 2 * block_size) * 4 > 5 * 2**20
    ):
        tq //= 2
    n_tiles = T // tq

    kp = k_cache.reshape(L, N, block_size, Hk, Dh)
    vp = v_cache.reshape(L, N, block_size, Hk, Dv)
    q5 = q.reshape(B, n_tiles, tq, H, Dh)
    layer_arr = jnp.asarray(layer_idx, jnp.int32).reshape(1)
    starts = jnp.asarray(start_pos, jnp.int32)

    def kv_index(b, qi, j, lyr, st, t, c):
        # clamp dead steps onto the nearest live page: repeats skip the
        # HBM copy. Live range for tile qi: pages touching
        # [max(0, tile_start - window), min(tile_end, ctx))
        last_any = jnp.maximum((c[b] - 1) // block_size, 0)
        tile_hi = jnp.minimum(st[b] + (qi + 1) * tq, c[b])
        last = jnp.clip((tile_hi - 1) // block_size, 0, last_any)
        jj = jnp.minimum(j, last)
        if sliding_window is not None:
            first = jnp.clip(
                (st[b] + qi * tq - (sliding_window - 1)) // block_size,
                0, last,
            )
            jj = jnp.maximum(jj, first)
        return (lyr[0], t[b, jj], 0, 0, 0)

    in_specs = [
        pl.BlockSpec(
            (1, 1, tq, H, Dh),
            lambda b, qi, j, lyr, st, t, c: (b, qi, 0, 0, 0),
        ),
        pl.BlockSpec((1, 1, block_size, Hk, Dh), kv_index),
        pl.BlockSpec((1, 1, block_size, Hk, Dv), kv_index),
    ]
    inputs = [q5, kp, vp]
    n_rows = Hk * tq * (H // Hk)
    if sinks is not None:
        # a tile's rows run (kv head, token, group): head = hk * G + g
        per_row = jnp.broadcast_to(
            sinks.astype(jnp.float32).reshape(Hk, 1, H // Hk),
            (Hk, tq, H // Hk)).reshape(n_rows, 1)
        in_specs.insert(1, pl.BlockSpec(
            (n_rows, 1), lambda b, qi, j, lyr, st, t, c: (0, 0)))
        inputs.insert(1, per_row)
    if quantized:
        def scale_index(b, qi, j, lyr, st, t, c):
            return kv_index(b, qi, j, lyr, st, t, c)[:2] + (0, 0)

        in_specs += [
            pl.BlockSpec((1, 1, Hk, block_size), scale_index),
            pl.BlockSpec((1, 1, Hk, block_size), scale_index),
        ]
        inputs += [k_scale, v_scale]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,  # layer, starts, block_tables, context_lens
        grid=(B, n_tiles, W),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, 1, tq, H, Dv),
            lambda b, qi, j, lyr, st, t, c: (b, qi, 0, 0, 0),
        ),
        scratch_shapes=[
            pltpu.VMEM((n_rows, Dv), jnp.float32),
            pltpu.VMEM((n_rows, 1), jnp.float32),
            pltpu.VMEM((n_rows, 1), jnp.float32),
        ],
    )
    kernel_kw = {"sinks": True} if sinks is not None else {}
    out = pl.pallas_call(
        functools.partial(
            _prefill_kernel_stacked, block_size=block_size, tq=tq,
            scale=scale, window=sliding_window, quantized=quantized,
            **kernel_kw,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, n_tiles, tq, H, Dv), q.dtype),
        interpret=interpret,
        name=name,
    )(layer_arr, starts, block_tables, context_lens, *inputs)
    return out.reshape(B, T, H, Dv)


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
