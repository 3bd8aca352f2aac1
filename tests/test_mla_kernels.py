"""The latent-attention decode kernel (``ops/mla.py``
``mla_decode_attention``) in interpret mode against a plain
gather-and-softmax over the row's table: the walk over a row's live
pages, several to a block. What the chip's compiler says of it is
``tests/test_chip_compile.py``'s; the models' own tests drive it through
``hybrid.mla_mixer``."""

from __future__ import annotations

import ast
import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.ops import mla, paged_attention
from dynamo_tpu.ops.mla import latent_pages_per_block, mla_decode_attention

BS, W, H, RANK, LAYERS = 128, 5, 4, 512, 2

# context lengths a row: around a page's edge, nothing at all, a ragged
# batch with padded rows between live ones, one row as long as the table
CONTEXTS = {
    "0": [0],
    "1": [1],
    "127": [127],
    "128": [128],
    "129": [129],
    "ragged": [0, 5, 300, 128, 0, 77, 513],
    "whole-table": [W * BS, 3],
}


def _setup(ctx_lens, C, dtype=jnp.float32, seed=0):
    """(q, plane, tables, contexts): every row its own pages, in a
    shuffled order, page 0 left to nobody."""
    B = len(ctx_lens)
    rng = np.random.default_rng(seed)
    n_pages = B * W + 1
    q = rng.standard_normal((B, H, C)).astype(np.float32) / np.sqrt(C)
    plane = rng.standard_normal((LAYERS, n_pages * BS, C)).astype(np.float32)
    tables = (1 + rng.permutation(B * W)).reshape(B, W).astype(np.int32)
    return (jnp.asarray(q, dtype), jnp.asarray(plane, dtype),
            jnp.asarray(tables), jnp.asarray(ctx_lens, jnp.int32))


def _reference(q, plane, layer, tables, ctx, rank=RANK):
    """Every column of the table gathered, masked by position, one
    softmax; a row of context 0 gives zeros."""
    B, S = tables.shape[0], tables.shape[1] * BS
    slots = (tables[:, :, None] * BS + jnp.arange(BS)).reshape(B, S)
    rows = plane[layer][slots].astype(jnp.float32)            # [B, S, C]
    s = jnp.einsum("bhc,bsc->bhs", q.astype(jnp.float32), rows,
                   precision="highest")
    live = (jnp.arange(S)[None, :] < ctx[:, None])[:, None, :]
    p = jax.nn.softmax(jnp.where(live, s, -1e30), axis=-1)
    out = jnp.einsum("bhs,bsc->bhc", jnp.where(live, p, 0.0),
                     rows[..., :rank], precision="highest")
    return jnp.where((ctx > 0)[:, None, None], out, 0.0)


@pytest.mark.parametrize("layer", [0, 1])
@pytest.mark.parametrize("C", [576, 640])
@pytest.mark.parametrize("P", [1, 4, 8])
@pytest.mark.parametrize("case", sorted(CONTEXTS))
def test_mla_decode_matches_gather_and_softmax(case, P, C, layer):
    q, plane, tables, ctx = _setup(CONTEXTS[case], C)
    got = mla_decode_attention(
        q, plane, jnp.int32(layer), tables, ctx, block_size=BS, rank=RANK,
        interpret=True, pages_per_block=P)
    assert got.shape == (len(CONTEXTS[case]), H, RANK)
    want = _reference(q, plane, layer, tables, ctx)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("P", [None, 2])
def test_mla_decode_over_bf16_pages(P):
    """The served dtypes: bf16 queries and pages, float32 softmax; ``P``
    from the geometry by default."""
    q, plane, tables, ctx = _setup(CONTEXTS["ragged"], 640, jnp.bfloat16)
    got = mla_decode_attention(
        q, plane, jnp.int32(1), tables, ctx, block_size=BS, rank=RANK,
        interpret=True, pages_per_block=P)
    assert got.dtype == jnp.bfloat16
    want = _reference(q, plane, 1, tables, ctx)
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want),
                               rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("fill", ["nan-page", "out-of-range"])
@pytest.mark.parametrize("P", [1, 4])
def test_mla_decode_never_reads_dead_table_columns(P, fill):
    """Columns past a row's last live page name a page of NaN, or no
    page at all: the result is the clean table's, bit for bit."""
    ctx_lens = CONTEXTS["ragged"]
    q, plane, tables, ctx = _setup(ctx_lens, 640)
    want = mla_decode_attention(
        q, plane, jnp.int32(0), tables, ctx, block_size=BS, rank=RANK,
        interpret=True, pages_per_block=P)
    plane = plane.at[:, :BS].set(jnp.nan)        # page 0 is nobody's
    dirty = np.asarray(tables).copy()
    for b, c in enumerate(ctx_lens):
        for j in range(-(-c // BS), W):
            dirty[b, j] = 0 if fill == "nan-page" else 2**30 + j
    got = mla_decode_attention(
        q, plane, jnp.int32(0), jnp.asarray(dirty), ctx, block_size=BS,
        rank=RANK, interpret=True, pages_per_block=P)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("P", [1, 4])
def test_mla_decode_row_of_context_0_is_zeros_and_touches_nothing(P):
    """Padded rows — first, last, all of them — name pages that do not
    exist over a plane of NaN: zeros come back."""
    q, plane, _, _ = _setup([0, 0, 0], 640)
    tables = jnp.full((3, W), 2**30, jnp.int32)
    got = mla_decode_attention(
        q, jnp.full_like(plane, jnp.nan), jnp.int32(1), tables,
        jnp.zeros((3,), jnp.int32), block_size=BS, rank=RANK, interpret=True,
        pages_per_block=P)
    np.testing.assert_array_equal(np.asarray(got), 0.0)


def test_mla_decode_grid_has_no_table_axis():
    """The lowered pallas_call's grid is the rows alone, whatever the
    table's width: no step is spent on a dead column."""
    def grids(width):
        jaxpr = jax.make_jaxpr(
            lambda q, plane, lyr, t, c: mla_decode_attention(
                q, plane, lyr, t, c, block_size=BS, rank=RANK, interpret=True)
        )(
            jax.ShapeDtypeStruct((4, 32, 640), jnp.bfloat16),
            jax.ShapeDtypeStruct((2, 256 * BS, 640), jnp.bfloat16),
            jax.ShapeDtypeStruct((), jnp.int32),
            jax.ShapeDtypeStruct((4, width), jnp.int32),
            jax.ShapeDtypeStruct((4,), jnp.int32),
        )
        found = []

        def walk(jp):
            for eqn in jp.eqns:
                if eqn.primitive.name == "pallas_call":
                    found.append(tuple(eqn.params["grid_mapping"].grid))
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    walk(sub)

        walk(jaxpr.jaxpr)
        return found

    assert grids(40) == grids(136) == [(4,)]


@pytest.mark.parametrize("bs,C,itemsize,want", [
    (128, 640, 2, 6),     # kimi-linear-48b's and kanana-2-30b's planes
    (128, 576, 2, 6),     # a row stored unpadded: five lane tiles all the same
    (128, 640, 4, 6),
    (16, 640, 2, 51),     # the small pages of the CPU tests
    (128, 8192, 2, 1),    # never less than a page
])
def test_latent_pages_per_block_follows_the_dense_kernels_budget(
    bs, C, itemsize, want
):
    P = latent_pages_per_block(bs, C, itemsize)
    assert P == want
    assert P == 1 or 2 * P * bs * C * itemsize <= (
        paged_attention._DECODE_KV_BUFFER_BYTES)
    assert P == 1 or P * bs * -(-C // 128) <= (
        paged_attention._DECODE_BLOCK_COLUMNS)


def test_the_latent_kernel_takes_only_the_sizing_budget_from_the_dense_file():
    """``ops/mla.py`` has a body of its own: of ``ops/paged_attention.py``
    it imports the budget's constants and nothing that runs, and that
    file still exports what the dense families call."""
    tree = ast.parse(inspect.getsource(mla))
    taken = {a.name for node in ast.walk(tree)
             if isinstance(node, ast.ImportFrom)
             and node.module == "dynamo_tpu.ops.paged_attention"
             for a in node.names}
    assert taken == {"_DECODE_BLOCK_COLUMNS", "_DECODE_KV_BUFFER_BYTES",
                     "_DECODE_VMEM_LIMIT_BYTES"}
    assert not any(isinstance(node, ast.Attribute)
                   and isinstance(node.value, ast.Name)
                   and node.value.id == "paged_attention"
                   for node in ast.walk(tree))
    for name in ("decode_pages_per_block", "paged_attention_decode",
                 "paged_attention_decode_stacked",
                 "paged_attention_prefill_stacked"):
        assert callable(getattr(paged_attention, name))
