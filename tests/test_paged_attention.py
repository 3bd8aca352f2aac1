"""Pallas paged-attention decode kernel vs the XLA reference path.

Runs the kernel in interpreter mode on the CPU backend (the fake-TPU rung
of the test ladder); the same code compiles natively on TPU.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from dynamo_tpu.models.llama import paged_attention_reference
from dynamo_tpu.ops.paged_attention import paged_attention_decode


def _setup(B, H, Hk, Dh, num_blocks, bs, ctx_lens, seed=0):
    rng = np.random.default_rng(seed)
    q = rng.standard_normal((B, H, Dh)).astype(np.float32)
    k = rng.standard_normal((num_blocks * bs, Hk, Dh)).astype(np.float32)
    v = rng.standard_normal((num_blocks * bs, Hk, Dh)).astype(np.float32)
    W = max((c + bs - 1) // bs for c in ctx_lens if c) if any(ctx_lens) else 1
    tables = np.zeros((B, W), np.int32)
    # assign distinct (non-zero) pages per sequence, scattered order
    next_page = 1
    for b, c in enumerate(ctx_lens):
        n = (c + bs - 1) // bs
        ids = np.arange(next_page, next_page + n, dtype=np.int32)
        rng.shuffle(ids)
        tables[b, :n] = ids
        next_page += n
    ctx = np.asarray(ctx_lens, np.int32)
    return (
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
        jnp.asarray(tables), jnp.asarray(ctx),
    )


@pytest.mark.parametrize(
    "B,H,Hk,ctx_lens",
    [
        (2, 4, 2, [7, 29]),  # GQA, ragged contexts
        (3, 8, 1, [1, 33, 5]),  # MQA, ctx=1 edge
        (2, 4, 2, [40, 0]),  # padded row (ctx=0)
    ],
)
@pytest.mark.parametrize("window", [None, 24])
def test_decode_kernel_stacked_matches_per_layer(B, H, Hk, ctx_lens, window):
    """The stacked-cache kernel (layer via scalar prefetch — the engine's
    decode hot path, avoiding the per-layer slice copy) must match the
    per-layer kernel on every layer."""
    from dynamo_tpu.ops.paged_attention import paged_attention_decode_stacked

    Dh, bs, num_blocks, L = 128, 16, 16, 3
    rng = np.random.default_rng(7)
    q, k0, v0, tables, ctx = _setup(B, H, Hk, Dh, num_blocks, bs, ctx_lens)
    k_stack = jnp.asarray(
        rng.standard_normal((L, num_blocks * bs, Hk, Dh)).astype(np.float32)
    )
    v_stack = jnp.asarray(
        rng.standard_normal((L, num_blocks * bs, Hk, Dh)).astype(np.float32)
    )
    for layer in range(L):
        out = paged_attention_decode_stacked(
            q, k_stack, v_stack, jnp.int32(layer), tables, ctx, bs,
            sliding_window=window, interpret=True,
        )
        ref = paged_attention_decode(
            q, k_stack[layer], v_stack[layer], tables, ctx, bs,
            sliding_window=window, interpret=True,
        )
        valid = np.asarray(ctx) > 0
        np.testing.assert_allclose(
            np.asarray(out)[valid], np.asarray(ref)[valid],
            rtol=2e-2, atol=2e-2,
        )


def _pallas_grids(fn, *shapes):
    """The grid of every pallas_call ``fn`` traces to at ``shapes``."""
    found = []

    def walk(jp):
        for eqn in jp.eqns:
            if eqn.primitive.name == "pallas_call":
                found.append(tuple(eqn.params["grid_mapping"].grid))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*shapes).jaxpr)
    return found


def _prefill(q, k, v, tables, starts, ctx, bs, tile_rows=None, **kw):
    """The prefill kernel, interpreted. ``tile_rows``: the (token, head)
    rows of a query tile for this call (the module's rule reads a
    constant, so the unjitted wrapper is traced under another value) —
    tiles of a few tokens keep several of them cheap to interpret."""
    from dynamo_tpu.ops import paged_attention as pa

    fn = pa.paged_attention_prefill_stacked
    args = (q, k[None], v[None], jnp.int32(0), tables,
            jnp.asarray(starts, jnp.int32), ctx, bs)
    if tile_rows is None:
        return fn(*args, interpret=True, **kw)
    saved = pa._PREFILL_TILE_ROWS
    pa._PREFILL_TILE_ROWS = tile_rows
    try:
        return fn.__wrapped__(*args, interpret=True, **kw)
    finally:
        pa._PREFILL_TILE_ROWS = saved


def _assert_prefill_matches(out, q, k, v, tables, starts, ctx, bs, window,
                            tol=2e-2):
    """Real tokens (start + t < ctx) against the XLA reference; padded
    tokens — a tile's tail past the context, whole tiles past it, rows
    of context 0 — are zeros (the reference NaN-masks them instead)."""
    B, T = q.shape[:2]
    starts = np.asarray(starts, np.int32)
    positions = jnp.asarray(starts[:, None] + np.arange(T, dtype=np.int32))
    ref = paged_attention_reference(q, k, v, tables, positions, ctx, bs, window)
    out = np.asarray(out, np.float32)
    assert np.isfinite(out).all()
    for b in range(B):
        n = min(max(0, int(ctx[b]) - int(starts[b])), T)
        np.testing.assert_allclose(
            out[b, :n], np.asarray(ref, np.float32)[b, :n], rtol=tol, atol=tol)
        np.testing.assert_array_equal(out[b, n:], 0.0)


@pytest.mark.parametrize(
    "B,H,Hk,T,starts,ctx_lens,window,pages,cache",
    [
        # full prefill from position 0, ragged lens, GQA
        (2, 4, 2, 32, [0, 0], [30, 17], None, None, "f32"),
        # chunked: rows resume mid-prompt, mid-page (prefix already in
        # cache)
        (2, 4, 2, 16, [20, 5], [36, 21], None, None, "f32"),
        # MQA + block-aligned + a padded row (start 0 / ctx 0)
        (3, 8, 1, 16, [0, 16, 0], [16, 32, 0], None, None, "f32"),
        # sliding window across pages
        (2, 4, 2, 32, [0, 24], [32, 56], 20, None, "f32"),
        # a compute block of 1 page and of 3 (live ranges of 2 and 4
        # pages: whole blocks, and a last block that is partial)
        (2, 4, 2, 32, [0, 0], [30, 17], None, 1, "f32"),
        (2, 4, 2, 16, [20, 40], [36, 56], None, 3, "f32"),
        (2, 4, 2, 32, [0, 24], [32, 56], 20, 3, "f32"),
        # padded rows leading, between and trailing: no copy starts for
        # them, and the live row behind one starts its own first block
        (5, 4, 2, 16, [0, 20, 0, 5, 0], [0, 36, 0, 21, 0], None, None, "f32"),
        (5, 4, 2, 16, [0, 20, 0, 5, 0], [0, 36, 0, 21, 0], None, 1, "f32"),
        (4, 4, 2, 16, [0, 0, 30, 0], [0, 0, 41, 0], 24, 3, "f32"),
        # a window whose first live page is column 5, not 0
        (1, 4, 2, 16, [100], [116], 20, None, "f32"),
        (1, 4, 2, 16, [100], [116], 20, 1, "f32"),
        # groups of 7 (Qwen2.5) and of 16 (nemotron, mimo's full layers)
        (2, 28, 4, 16, [3, 0], [19, 9], None, None, "f32"),
        (2, 32, 2, 16, [3, 0], [19, 9], None, 3, "f32"),
        # 16-bit rows: two heads to a sublane word, taken apart through
        # the 32-bit view; one head; heads that do not pair (staged f32)
        (2, 8, 4, 16, [20, 5], [36, 21], None, None, "bf16"),
        (2, 8, 4, 32, [0, 24], [32, 56], 20, 3, "bf16"),
        (2, 28, 4, 16, [3, 0], [19, 9], None, 1, "bf16"),
        (3, 8, 1, 16, [0, 16, 0], [16, 32, 0], None, None, "bf16"),
        (2, 6, 3, 16, [20, 5], [36, 21], None, None, "bf16"),
    ],
)
def test_prefill_kernel_matches_reference(
    B, H, Hk, T, starts, ctx_lens, window, pages, cache
):
    """Flash prefill over the paged cache (VERDICT r3 item 2: the T>1
    path must stop falling back to the XLA group-expand reference)."""
    Dh, bs, num_blocks = 128, 16, 16
    rng = np.random.default_rng(11)
    _, k, v, tables, ctx = _setup(B, H, Hk, Dh, num_blocks, bs, ctx_lens)
    q = jnp.asarray(rng.standard_normal((B, T, H, Dh)).astype(np.float32))
    tol = 2e-2
    if cache == "bf16":
        q, k, v = (x.astype(jnp.bfloat16) for x in (q, k, v))
        tol = 1e-1
    out = _prefill(q, k, v, tables, starts, ctx, bs,
                   sliding_window=window, pages_per_block=pages)
    assert out.dtype == q.dtype
    _assert_prefill_matches(
        out, q, k, v, tables, starts, ctx, bs, window, tol)


@pytest.mark.parametrize(
    "B,H,Hk,T,tile_rows,starts,ctx_lens,window,pages",
    [
        # two 128-token tiles
        (1, 2, 1, 256, 256, [0], [256], None, None),
        # 16-token tiles. Row 0: its context ends inside tile 1, tiles 2
        # and 3 lie wholly past it (zeros, no copy); row 1: a chunk from
        # mid-page whose context ends inside tile 1
        (2, 4, 2, 64, 64, [0, 40], [20, 70], None, None),
        (2, 4, 2, 64, 64, [0, 40], [20, 70], None, 1),
        (2, 4, 2, 64, 64, [0, 40], [20, 70], None, 3),
        # padded rows around and between rows of several tiles: a tile
        # starts the next live tile's first block, across dead tiles not
        (5, 4, 2, 32, 32, [0, 37, 0, 0, 0], [0, 69, 0, 25, 0], None, 2),
        (5, 4, 2, 32, 32, [0, 37, 0, 0, 0], [0, 69, 0, 25, 0], 24, None),
        # a window under several tiles: each tile's first live page its
        # own (columns 4, 5, 6, 7 of 8), blocks of 1 and of 3
        (1, 4, 2, 64, 64, [90], [154], 20, 1),
        (1, 4, 2, 64, 64, [90], [154], 20, 3),
        # groups of 7 and of 16 under 8-token tiles
        (1, 28, 4, 32, 224, [13], [45], None, 2),
        (1, 32, 2, 32, 256, [13], [45], 24, None),
    ],
)
def test_prefill_kernel_multi_tile(
    B, H, Hk, T, tile_rows, starts, ctx_lens, window, pages
):
    """T > tile size exercises the query-tile grid axis."""
    Dh, bs, num_blocks = 128, 16, 20
    rng = np.random.default_rng(3)
    _, k, v, tables, ctx = _setup(B, H, Hk, Dh, num_blocks, bs, ctx_lens)
    q = jnp.asarray(rng.standard_normal((B, T, H, Dh)).astype(np.float32))
    out = _prefill(q, k, v, tables, starts, ctx, bs, tile_rows=tile_rows,
                   sliding_window=window, pages_per_block=pages)
    _assert_prefill_matches(out, q, k, v, tables, starts, ctx, bs, window)


@pytest.mark.parametrize("fill", ["nan", "out_of_range"])
@pytest.mark.parametrize("window", [None, 24])
def test_prefill_kernel_never_reads_dead_table_columns(fill, window):
    """Table columns outside a tile's live range — past the page of its
    last real token, before the page of its first query's window edge —
    are never dereferenced: NaN-filled pages there, or page ids far
    outside the pool, leave the result as it was."""
    Dh, bs, H, Hk, T = 128, 16, 4, 2, 32
    starts, ctx_lens = [40, 0, 0, 64], [70, 0, 16, 96]
    num_blocks, W = 24, 12
    _, k, v, tables, ctx = _setup(4, H, Hk, Dh, num_blocks, bs, ctx_lens)
    q = jnp.asarray(np.random.default_rng(5).standard_normal(
        (4, T, H, Dh)).astype(np.float32))
    clean = np.zeros((4, W), np.int32)
    clean[:, : tables.shape[1]] = np.asarray(tables)
    kw = dict(tile_rows=32, sliding_window=window, pages_per_block=2)
    want = _prefill(q, k, v, jnp.asarray(clean), starts, ctx, bs, **kw)
    _assert_prefill_matches(
        want, q, k, v, jnp.asarray(clean), starts, ctx, bs, window)
    dirty = clean.copy()
    nan_page = num_blocks - 1  # no row's live page
    k = k.at[nan_page * bs:].set(jnp.nan)
    v = v.at[nan_page * bs:].set(jnp.nan)
    for b, (s, c) in enumerate(zip(starts, ctx_lens)):
        lo = 0 if window is None else max(s - (window - 1), 0)
        live = range(lo // bs, -(-c // bs)) if c else range(0)
        for j in range(W):
            if j not in live:
                dirty[b, j] = nan_page if fill == "nan" else 2**30 + j
    got = _prefill(q, k, v, jnp.asarray(dirty), starts, ctx, bs, **kw)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_prefill_kernel_grid_has_no_table_axis():
    """The lowered pallas_call's grid is (rows, tiles), whatever the
    block table's width: no step is spent on a dead column; and the tile
    and the pages of a block follow from the call's geometry."""
    from dynamo_tpu.ops import paged_attention as pa

    def grids(W, T=256):
        B, H, Hk, Dh, bs = 2, 64, 4, 128, 16
        cache = jax.ShapeDtypeStruct((2, 64 * bs, Hk, Dh), jnp.bfloat16)
        return _pallas_grids(
            lambda q, kc, vc, lyr, t, s, c: pa.paged_attention_prefill_stacked(
                q, kc, vc, lyr, t, s, c, block_size=bs, interpret=True
            ),
            jax.ShapeDtypeStruct((B, T, H, Dh), jnp.bfloat16), cache, cache,
            jax.ShapeDtypeStruct((), jnp.int32),
            jax.ShapeDtypeStruct((B, W), jnp.int32),
            jax.ShapeDtypeStruct((B,), jnp.int32),
            jax.ShapeDtypeStruct((B,), jnp.int32),
        )

    assert grids(8) == grids(136) == [(2, 8)]
    # the six served geometries: (H, T) -> tokens a tile
    assert [pa.prefill_tile_tokens(T, H) for T, H in (
        (1024, 64), (128, 64), (1024, 32), (512, 28), (512, 16), (16, 4),
        (96, 64))] == [32, 32, 64, 64, 128, 16, 32]
    # pages a block at 128-token pages of bf16: mimo's full layers (512
    # rows a KV head), its window layers (a window of 128 under 32-token
    # tiles sees 3 pages at most), Mistral / Llama 32 / 8, Qwen2.5 28 / 4
    ppb = pa.prefill_pages_per_block
    assert ppb(128, 4, 256, 2, 128, 512, 32) == 8
    assert ppb(128, 8, 256, 2, 128, 256, 32, 128) == 2
    assert ppb(128, 8, 128, 2, 128, 256, 64, 4096) == 8
    assert ppb(128, 4, 128, 2, 128, 448, 64) == 8


def _quantized(k, bs):
    """Float [S, Hk, Dh] -> (int8 values, scales [N, Hk, bs]): the
    int8 cache as ops/kv_quant.py stores it."""
    from tests.test_kv_quant import _quantize_layer, _scales_to_layout

    q8, sc = _quantize_layer(k)
    return q8, jnp.asarray(_scales_to_layout(sc, bs))


@pytest.mark.parametrize(
    "B,H,Hk,ctx_lens,bs,pages,cache",
    [
        # pages None: a compute block sized from the geometry
        (2, 4, 2, [7, 29], 16, None, "f32"),  # GQA, ragged contexts
        (1, 4, 4, [16], 16, None, "f32"),  # MHA, exactly block-aligned
        (3, 8, 1, [1, 33, 5], 16, None, "f32"),  # MQA, ctx=1 edge
        (2, 4, 2, [40, 0], 16, None, "f32"),  # padded row (ctx=0)
        # a block of `pages` pages: contexts of 1, exactly one block,
        # one key short of it and one key past it (a second block that
        # holds a single key), two blocks and a key
        (5, 4, 2, [1, 32, 31, 33, 65], 16, 2, "f32"),
        # rows of context 0 between live rows: no block runs for them,
        # and the live row behind one starts its own first block
        (6, 4, 2, [40, 0, 7, 0, 0, 65], 16, 2, "f32"),
        (3, 4, 2, [0, 0, 50], 16, 2, "f32"),  # dead rows first
        (3, 4, 2, [100, 3, 64], 16, 1, "f32"),  # one page a block
        (2, 4, 2, [100, 64], 16, 3, "f32"),  # blocks that split unevenly
        # the served head geometries at the served page size
        (3, 32, 8, [1, 256, 257], 128, 2, "f32"),  # Llama / Mistral
        (3, 28, 4, [255, 0, 300], 128, 2, "f32"),  # Qwen2.5: G = 7
        # a tp=4 shard of each: 2 and 1 KV heads
        (2, 8, 2, [33, 200], 128, 2, "f32"),
        (2, 7, 1, [129, 64], 128, 2, "f32"),
        # int8 cache: scales spread over the (token, head) columns
        (3, 8, 4, [23, 37, 0], 16, 2, "int8"),
        (2, 32, 8, [130, 256], 128, 2, "int8"),
        (2, 28, 4, [257, 90], 128, 2, "int8"),
        (2, 8, 2, [40, 129], 128, None, "int8"),
        (2, 7, 1, [300, 5], 128, 2, "int8"),
    ],
)
def test_decode_kernel_matches_reference(B, H, Hk, ctx_lens, bs, pages, cache):
    Dh = 128
    num_blocks = sum(-(-c // bs) for c in ctx_lens) + 2
    q, k, v, tables, ctx = _setup(B, H, Hk, Dh, num_blocks, bs, ctx_lens)
    scales = {}
    if cache == "int8":
        (k, ks), (v, vs) = _quantized(k, bs), _quantized(v, bs)
        scales = dict(k_scale=ks, v_scale=vs)
    out = paged_attention_decode(
        q, k, v, tables, ctx, bs, interpret=True, pages_per_block=pages,
        **scales,
    )
    # reference wants [B, T, H, Dh] and per-token positions
    positions = jnp.maximum(ctx - 1, 0)[:, None]  # decode: last position
    ref = paged_attention_reference(
        q[:, None],
        (k, scales["k_scale"]) if scales else k,
        (v, scales["v_scale"]) if scales else v,
        tables, positions, ctx, bs,
    )[:, 0]
    valid = np.asarray(ctx) > 0
    tol = 5e-2 if scales else 2e-2
    np.testing.assert_allclose(
        np.asarray(out)[valid], np.asarray(ref)[valid], rtol=tol, atol=tol
    )
    # a row of context 0 attends nothing: zeros, never NaN
    assert not np.asarray(out)[~valid].any()


@pytest.mark.parametrize("fill", ["nan", "out_of_range"])
def test_decode_kernel_never_reads_dead_table_columns(fill):
    """Table columns past a row's last live page (and before its
    window's first) are never dereferenced: NaN-filled pages there, or
    page ids far outside the pool, leave the result as it was."""
    Dh, bs, H, Hk, P = 128, 16, 4, 2, 2
    ctx_lens = [37, 0, 16, 70]
    window = 24
    num_blocks = 24
    q, k, v, tables, ctx = _setup(4, H, Hk, Dh, num_blocks, bs, ctx_lens)
    W = 12
    clean = np.zeros((4, W), np.int32)
    clean[:, : tables.shape[1]] = np.asarray(tables)
    want = paged_attention_decode(
        q, k, v, jnp.asarray(clean), ctx, bs, sliding_window=window,
        interpret=True, pages_per_block=P,
    )
    dirty = clean.copy()
    nan_page = num_blocks - 1  # no row's live page
    k = k.at[nan_page * bs:].set(jnp.nan)
    v = v.at[nan_page * bs:].set(jnp.nan)
    for b, c in enumerate(ctx_lens):
        lo = max(c - window, 0)
        live = range(lo // bs, -(-c // bs)) if c else range(0)
        for j in range(W):
            if j not in live:
                dirty[b, j] = nan_page if fill == "nan" else 2**30 + j
    got = paged_attention_decode(
        q, k, v, jnp.asarray(dirty), ctx, bs, sliding_window=window,
        interpret=True, pages_per_block=P,
    )
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_decode_kernel_grid_has_no_table_axis():
    """The lowered pallas_call's grid is the rows alone, whatever the
    block table's width: no step is spent on a dead column."""
    from dynamo_tpu.ops.paged_attention import paged_attention_decode_stacked

    def grids(W):
        B, H, Hk, Dh, bs = 4, 8, 2, 128, 16
        cache = jax.ShapeDtypeStruct((2, 64 * bs, Hk, Dh), jnp.bfloat16)
        return _pallas_grids(
            lambda q, kc, vc, lyr, t, c: paged_attention_decode_stacked(
                q, kc, vc, lyr, t, c, block_size=bs, interpret=True
            ),
            jax.ShapeDtypeStruct((B, H, Dh), jnp.bfloat16), cache, cache,
            jax.ShapeDtypeStruct((), jnp.int32),
            jax.ShapeDtypeStruct((B, W), jnp.int32),
            jax.ShapeDtypeStruct((B,), jnp.int32),
        )

    assert grids(8) == grids(40) == [(4,)]


def test_decode_kernel_bf16():
    Dh, bs, num_blocks = 128, 16, 8
    q, k, v, tables, ctx = _setup(2, 4, 2, Dh, num_blocks, bs, [12, 20])
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
    out = paged_attention_decode(qb, kb, vb, tables, ctx, bs, interpret=True)
    assert out.dtype == jnp.bfloat16
    ref = paged_attention_reference(
        qb[:, None], kb, vb, tables, (ctx - 1)[:, None], ctx, bs
    )[:, 0]
    np.testing.assert_allclose(
        np.asarray(out, dtype=np.float32),
        np.asarray(ref, dtype=np.float32),
        rtol=1e-1, atol=1e-1,
    )


@pytest.mark.parametrize("window,ctx_lens,pages", [
    (8, [7, 29], None),     # window < block_size
    (16, [40, 33], None),   # window == block_size
    (24, [50, 3], None),    # window spans pages; one ctx inside window
    # the window opens inside the context, on a page that starts no
    # block of the table: the walk begins at the window's first page
    (40, [100, 37], 2),
    (33, [97, 64], 1),
    (64, [129, 70], 3),
])
def test_decode_kernel_sliding_window(window, ctx_lens, pages):
    Dh, bs, num_blocks = 128, 16, 16
    B, H, Hk = 2, 4, 2
    q, k, v, tables, ctx = _setup(B, H, Hk, Dh, num_blocks, bs, ctx_lens)
    out = paged_attention_decode(
        q, k, v, tables, ctx, bs, sliding_window=window, interpret=True,
        pages_per_block=pages,
    )
    positions = jnp.maximum(ctx - 1, 0)[:, None]
    ref = paged_attention_reference(
        q[:, None], k, v, tables, positions, ctx, bs, sliding_window=window
    )[:, 0]
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-2, atol=2e-2
    )


def test_decode_kernel_shard_map_tp():
    """The kernel under shard_map over a tp axis (attention is local per
    KV-head shard) matches the single-kernel result — the multi-device
    integration models/llama.py attend_mlp uses."""
    import functools

    from jax.sharding import PartitionSpec as P

    from dynamo_tpu.parallel.mesh import MeshConfig, build_mesh

    Dh, bs, num_blocks = 128, 16, 16
    B, H, Hk = 2, 8, 4
    q, k, v, tables, ctx = _setup(B, H, Hk, Dh, num_blocks, bs, [23, 37])
    mesh = build_mesh(MeshConfig(dp=2, tp=4), jax.devices())
    kern = functools.partial(
        paged_attention_decode, block_size=bs, interpret=True
    )
    # manual over EVERY mesh axis, as models/llama.py wraps it: the
    # chip's compiler refuses a Mosaic kernel in a partly automatic region
    wrapped = jax.shard_map(
        kern,
        mesh=mesh,
        in_specs=(
            P(None, "tp", None), P(None, "tp", None), P(None, "tp", None),
            P(None, None), P(None),
        ),
        out_specs=P(None, "tp", None),
        check_vma=False,
    )
    out = jax.jit(wrapped)(q, k, v, tables, ctx)
    single = kern(q, k, v, tables, ctx)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(single), rtol=2e-2, atol=2e-2
    )


async def test_engine_tp_with_pallas_attention(monkeypatch):
    """Full engine on a tp=2 CPU mesh with the Pallas kernel forced
    (interpret) must match the reference-path engine's greedy tokens —
    the integration that unlocks fast attention on multi-chip ladders."""
    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.engine import JaxEngine
    from dynamo_tpu.models.llama import set_attention_mesh
    from tests.test_engine import MODEL_DIR, _generate

    cfg = dict(
        model_path=MODEL_DIR, model_name="tiny", random_weights=True,
        num_blocks=32, block_size=8, max_batch_size=4,
        prefill_chunk_size=32, max_model_len=128,
        tensor_parallel_size=2,
    )
    prompt = list(range(1, 20))
    try:
        monkeypatch.setenv("DYN_ATTN_IMPL", "reference")
        eng = await JaxEngine.launch(EngineConfig(**cfg))
        try:
            ref_toks, _ = await _generate(eng, prompt, max_tokens=4)
        finally:
            await eng.shutdown()

        monkeypatch.setenv("DYN_ATTN_IMPL", "pallas")
        eng = await JaxEngine.launch(EngineConfig(**cfg))
        try:
            pal_toks, _ = await _generate(eng, prompt, max_tokens=4)
        finally:
            await eng.shutdown()
    finally:
        set_attention_mesh(None)
    assert pal_toks == ref_toks


async def test_engine_with_pallas_attention(monkeypatch):
    """Full engine decode through the kernel (interpret mode) must produce
    the same greedy tokens as the reference path."""
    import os

    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.engine import JaxEngine
    from tests.test_engine import MODEL_DIR, _generate

    cfg = dict(
        model_path=MODEL_DIR, model_name="tiny", random_weights=True,
        num_blocks=32, block_size=8, max_batch_size=4,
        prefill_chunk_size=32, max_model_len=128,
    )
    prompt = list(range(1, 20))

    monkeypatch.setenv("DYN_ATTN_IMPL", "reference")
    eng = await JaxEngine.launch(EngineConfig(**cfg))
    try:
        ref_toks, _ = await _generate(eng, prompt, max_tokens=4)
    finally:
        await eng.shutdown()

    monkeypatch.setenv("DYN_ATTN_IMPL", "pallas")
    eng = await JaxEngine.launch(EngineConfig(**cfg))
    try:
        pal_toks, _ = await _generate(eng, prompt, max_tokens=4)
    finally:
        await eng.shutdown()
    assert pal_toks == ref_toks
