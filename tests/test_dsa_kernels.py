"""``ops/dsa.py`` and the ``sel`` argument of ``ops/mla.py``'s kernels
(interpreted here; ``tests/test_chip_compile.py`` hands them to the chip's
compiler at GLM-5's widths): the index score against the formula, the
EXACT top k against a full stable sort — planted ties go to the lower
index — and the masked page walk in prefill and decode against a softmax
over the selected keys alone, with padded rows leading, trailing and
between."""

import functools

import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.ops import dsa
from dynamo_tpu.ops.mla import mla_decode_attention, mla_prefill_attention


def full_sort_mask(scores: np.ndarray, k: int) -> np.ndarray:
    """The first ``k`` keys of a stable descending sort, ``-inf`` never."""
    out = np.zeros_like(scores)
    for idx in np.ndindex(scores.shape[:-1]):
        order = np.argsort(-scores[idx], kind="stable")[:k]
        out[idx][[j for j in order if scores[idx][j] > -np.inf]] = 1.0
    return out


def tied_scores(T: int, S: int, seed: int = 0) -> np.ndarray:
    """[2, T, S] scores, causal (query t sees 20 + t keys): planted ties
    that straddle the k-th place, a run of equal maxima, exact zeros (an
    index score whose every head's ReLU is 0), a query with one key and a
    query with no candidate at all."""
    rng = np.random.default_rng(seed)
    sc = rng.normal(size=(2, T, S)).astype(np.float32)
    sc[0, 3, :40] = 0.5                       # 40 equal keys around the cut
    sc[1, 2, 10:30] = sc[1, 2].max() + 1      # 20 equal maxima > k
    sc[0, 5, ::3] = 0.0                       # exact zeros among signed scores
    sc[1, 7, :] = -3.25                       # every key tied
    for t in range(T):
        sc[:, t, t + 20:] = -np.inf
    sc[0, 1, 1:] = -np.inf                    # one candidate
    sc[1, 5, :] = -np.inf                     # none
    return sc


@pytest.mark.parametrize("k", [1, 12, 24, 64])
@pytest.mark.parametrize("form", ["xla", "kernel"])
def test_the_top_k_is_exact_and_ties_go_to_the_lower_index(form, k):
    sc = tied_scores(16, 64)
    want = full_sort_mask(sc, k)
    got = np.asarray(dsa.select_topk_xla(jnp.asarray(sc), k) if form == "xla"
                     else dsa.select_topk(jnp.asarray(sc), jnp.ones((2,), jnp.int32), k=k,
                                          interpret=True))
    np.testing.assert_array_equal(got, want)
    n = np.isfinite(sc).sum(-1)
    np.testing.assert_array_equal(got.sum(-1), np.minimum(n, k))
    # the planted ties: the first of the equal keys are the ones taken
    if k == 12:
        assert got[0, 3, :12].all() and not got[0, 3, 12:].any()
        assert got[1, 2, 10:22].all() and got[1, 2].sum() == 12


@pytest.mark.parametrize("T, S", [(1, 384), (1, 2048), (8, 256), (5, 40)])
def test_the_top_k_kernel_at_other_block_shapes(T, S):
    """One query a row (decode) whose ``S`` is whole vector registers is
    selected as ``[8, S / 8]`` — the tie order is still the key's index —
    and a padded row (context 0: the last) costs a store of zeros."""
    sc = np.random.default_rng(T).normal(size=(3, T, S)).astype(np.float32)
    sc[:, :, S - 7:] = -np.inf
    sc[1] = np.round(sc[1] * 2) / 2            # many exact ties
    sc[2] = -np.inf
    want = full_sort_mask(sc, 24)
    np.testing.assert_array_equal(
        np.asarray(dsa.select_topk(jnp.asarray(sc), jnp.asarray([S - 7, S - 7, 0]),
                                   k=24, interpret=True)), want)
    np.testing.assert_array_equal(
        np.asarray(dsa.select_topk_xla(jnp.asarray(sc), 24)), want)


def test_the_sort_key_orders_floats_as_floats():
    x = np.array([-np.inf, -3e38, -1.5, -1e-30, -0.0, 0.0, 1e-30, 2.0, 3e38],
                 np.float32)
    key = np.asarray(dsa.sort_key(jnp.asarray(x)))
    assert (np.diff(key.astype(np.int64)) >= 0).all()
    assert key[0] < key[1] and key[-2] < key[-1]


def index_formula(q, w, k, start, ctx):
    B, T, G, _ = q.shape
    S = k.shape[1]
    out = np.full((B, T, S), -np.inf, np.float32)
    for b in range(B):
        for t in range(T):
            for j in range(min(int(start[b]) + t + 1, int(ctx[b]))):
                out[b, t, j] = sum(
                    w[b, t, g] * max(0.0, float(q[b, t, g] @ k[b, j]))
                    for g in range(G))
    return out


@pytest.mark.parametrize("T", [1, 5, 16])
def test_the_index_score_is_the_weighted_relu_sum_over_heads(T):
    """Rows with a start position > 0 (a later chunk, or decode), a row at
    0, and a padded row (context 0) between them; negative head weights."""
    rng = np.random.default_rng(3)
    B, G, d, S = 4, 4, 16, 64
    q = rng.normal(size=(B, T, G, d)).astype(np.float32)
    w = rng.normal(size=(B, T, G)).astype(np.float32)
    k = rng.normal(size=(B, S, d)).astype(np.float32)
    start = np.array([30, 0, 0, 9], np.int32)
    ctx = np.array([30 + T, 0, min(T, 11), 9 + T], np.int32)
    want = index_formula(q, w, k, start, ctx)
    args = tuple(map(jnp.asarray, (q, w, k, start, ctx)))
    for got in (dsa.index_scores_xla(*args),
                dsa.index_scores(*args, interpret=True)):
        got = np.asarray(got)
        np.testing.assert_array_equal(np.isinf(got), np.isinf(want))
        np.testing.assert_allclose(np.where(np.isinf(got), 0, got),
                                   np.where(np.isinf(want), 0, want), atol=2e-5)
    assert np.isinf(want[1]).all()             # the padded row scores nothing


# -- the masked walk --------------------------------------------------------------------

BS, H, C, RANK = 8, 4, 128, 96
# padded rows (context 0) lead, sit between and trail the live ones
TABLES = np.array([[0, 0, 0, 0, 0], [3, 4, 5, 6, 0], [0, 0, 0, 0, 0],
                   [7, 8, 0, 0, 0], [0, 0, 0, 0, 0]], np.int32)


def attend_selected(q, rows, keep):
    s = np.einsum("hc,sc->hs", q, rows[keep])
    pr = np.exp(s - s.max(-1, keepdims=True))
    return (pr / pr.sum(-1, keepdims=True)) @ rows[keep, :RANK]


@pytest.mark.parametrize("pages_per_block", [1, 2, 3, None])
def test_prefill_walks_every_page_and_attends_only_the_marked_keys(pages_per_block):
    rng = np.random.default_rng(1)
    T, S = 16, 5 * BS
    latent = jnp.asarray(rng.normal(size=(2, 10 * BS, C)), jnp.float32)
    q = rng.normal(size=(5, T, H, C)).astype(np.float32) * 0.2
    start = np.array([0, 16, 0, 0, 0], np.int32)
    ctx = np.array([0, 29, 0, 11, 0], np.int32)
    sel = (rng.random((5, T, S)) < 0.4).astype(np.float32)
    for b in range(5):
        for t in range(T):
            sel[b, t, int(start[b]) + t] = 1
    sel[1, 2, :] = 0
    sel[1, 2, 3] = 1          # one early key: every later page holds none
    walk = functools.partial(
        mla_prefill_attention, jnp.asarray(q), latent, jnp.int32(1),
        jnp.asarray(TABLES), jnp.asarray(start), jnp.asarray(ctx),
        block_size=BS, rank=RANK, interpret=True,
        pages_per_block=pages_per_block)
    got = np.asarray(walk(sel=jnp.asarray(sel)))
    rows = np.asarray(latent)[1][(TABLES[:, :, None] * BS + np.arange(BS)).reshape(5, -1)]
    for b in (1, 3):
        for t in range(int(ctx[b] - start[b])):
            p_abs = int(start[b]) + t
            keep = np.nonzero(sel[b, t, :p_abs + 1] > 0.5)[0]
            np.testing.assert_allclose(
                got[b, t], attend_selected(q[b, t], rows[b], keep), atol=2e-5)
    assert np.isfinite(got).all()
    # all keys marked: the dense kernel's answer
    every = np.asarray(walk(sel=jnp.ones((5, T, S), jnp.float32)))
    np.testing.assert_allclose(every[[1, 3]], np.asarray(walk())[[1, 3]], atol=1e-6)


@pytest.mark.parametrize("pages_per_block", [1, 2, 3, None])
@pytest.mark.parametrize("marked", [False, True], ids=["dense", "marked"])
def test_prefill_walks_a_tiles_live_pages_in_blocks(marked, pages_per_block):
    """Three tiles of 32 tokens a row at 32 heads. Row 1's chunk starts
    mid-page over cached pages and ends inside its second tile (the third
    is past its context: zeros, no page read); row 3 fills all three, its
    last block a partial one at 3 pages a block; rows of context 0 lead,
    trail and sit between. Table columns past a row's live pages name a
    page past the pool, which a copy would clamp to the pool's last page
    — all NaN here: one dereferenced column and the output is NaN."""
    heads, T, W, pool = 32, 96, 16, 40
    rng = np.random.default_rng(3)
    plane = rng.normal(size=(2, pool * BS, C)).astype(np.float32)
    plane[:, -BS:] = np.nan
    q = rng.normal(size=(5, T, heads, C)).astype(np.float32) * 0.2
    start = np.array([0, 20, 0, 0, 0], np.int32)
    ctx = np.array([0, 60, 0, 96, 0], np.int32)
    tables = np.full((5, W), 10**6, np.int32)
    tables[1, :8] = 3 + np.arange(8)            # ceil(60 / 8) live pages
    tables[3, :12] = 20 + np.arange(12)
    sel = None
    if marked:
        sel = (rng.random((5, T, W * BS)) < 0.3).astype(np.float32)
        for b in (1, 3):
            for t in range(T):
                sel[b, t, int(start[b]) + t] = 1
        sel[1, 5, 1:] = 0
        sel[1, 5, 0] = 1      # the first page only: every later block holds none
        sel[3, 70, :64] = 0   # no marked key before its fifth page
    got = np.asarray(mla_prefill_attention(
        jnp.asarray(q), jnp.asarray(plane), jnp.int32(1), jnp.asarray(tables),
        jnp.asarray(start), jnp.asarray(ctx), block_size=BS, rank=RANK,
        interpret=True, pages_per_block=pages_per_block,
        sel=None if sel is None else jnp.asarray(sel)))
    assert np.isfinite(got).all()
    assert not got[[0, 2, 4]].any()             # padded rows: zeros
    assert not got[1, 64:].any()                # a tile past its row's context
    for b in (1, 3):
        live = tables[b, :-(-int(ctx[b]) // BS)]
        rows = plane[1][(live[:, None] * BS + np.arange(BS)).reshape(-1)]
        for t in range(0, int(ctx[b] - start[b]), 3):
            p_abs = int(start[b]) + t
            keep = np.arange(p_abs + 1)
            if marked:
                keep = keep[sel[b, t, :p_abs + 1] > 0.5]
            np.testing.assert_allclose(
                got[b, t], attend_selected(q[b, t], rows, keep), atol=2e-5)


@pytest.mark.parametrize("pages_per_block", [1, 2, 3, None])
def test_decode_walks_live_pages_and_attends_only_the_marked_keys(pages_per_block):
    """Blocks whose pages hold no marked key at all come first (row 1's
    first page), in the middle and last; a block past the table's width
    is padded marks; padded rows lead, trail and sit between."""
    rng = np.random.default_rng(2)
    S = 5 * BS
    latent = jnp.asarray(rng.normal(size=(2, 10 * BS, C)), jnp.float32)
    q = rng.normal(size=(5, H, C)).astype(np.float32) * 0.2
    ctx = np.array([0, 29, 0, 11, 0], np.int32)
    sel = (rng.random((5, S)) < 0.3).astype(np.float32)
    sel[1, :8] = 0
    sel[1, 16:24] = 0
    sel[1, 20] = 0
    sel[1, 12] = 1
    sel[3, 3] = 1
    sel[3, 8:] = 0            # the row's last page holds none
    got = np.asarray(mla_decode_attention(
        jnp.asarray(q), latent, jnp.int32(1), jnp.asarray(TABLES),
        jnp.asarray(ctx), block_size=BS, rank=RANK, interpret=True,
        pages_per_block=pages_per_block, sel=jnp.asarray(sel)))
    rows = np.asarray(latent)[1][(TABLES[:, :, None] * BS + np.arange(BS)).reshape(5, -1)]
    for b in (1, 3):
        keep = np.nonzero(sel[b, :int(ctx[b])] > 0.5)[0]
        np.testing.assert_allclose(
            got[b], attend_selected(q[b], rows[b], keep), atol=2e-5)
    assert not got[[0, 2, 4]].any()            # padded rows: zeros, no NaN
    dense = np.asarray(mla_decode_attention(
        jnp.asarray(q), latent, jnp.int32(1), jnp.asarray(TABLES),
        jnp.asarray(ctx), block_size=BS, rank=RANK, interpret=True,
        pages_per_block=pages_per_block))
    every = np.asarray(mla_decode_attention(
        jnp.asarray(q), latent, jnp.int32(1), jnp.asarray(TABLES),
        jnp.asarray(ctx), block_size=BS, rank=RANK, interpret=True,
        pages_per_block=pages_per_block, sel=jnp.ones((5, S), jnp.float32)))
    np.testing.assert_allclose(every, dense, atol=1e-6)
