"""Learned sparse selection over a paged cache (DeepSeek Sparse
Attention, ``models/glm_moe_dsa.py``): the index score of a tile of
queries against a row's cached indexer keys, and the EXACT top ``k`` of a
query's candidates as a mask. The attention that then reads only the
selected keys is ``ops/mla.py``'s two kernels with their ``sel``
argument.

**Index score** (``index_scores``). ``I[p, j] = sum_g w[p, g] *
ReLU(q[p, g] . k[j])`` over ``G`` indexer heads of ``d`` values, for the
keys ``j <= p`` of the row's context, ``-inf`` elsewhere. One Pallas
kernel, grid (row, query tile, key tile): a tile's ``G * tq`` query rows
(head-major inside the tile) against ``ts`` keys is one MXU dot; the
ReLU, the float32 head weights and the sum over heads happen on the
``[G * tq, ts]`` block in VMEM, so what reaches HBM is ``[T, S]``
float32 a layer and never ``[T, G, S]``. Key tiles wholly behind the
causal edge cost a store of ``-inf``. No output block is revisited.

**Selection** (``select_topk``). The ``k``-th largest score of a query
by bisection on the float's bit pattern — a float32 ordered as the int32
``bits ^ ((bits >> 31) & 0x7fffffff)`` — 32 counting passes that build
the threshold from its top bit down, then 15 more over the key's INDEX
among the scores equal to the threshold, so that ties go to the lower
index: exactly ``min(k, valid)`` keys, the set a stable full sort would
give (``tests/test_dsa_kernels.py`` holds it to one, planted ties
included). No approximate choice anywhere: ``approx_max_k`` would be a
different model. The kernel keeps a block of ``8`` queries' ``S`` scores
(decode: ONE query's, as ``[8, S / 8]``) in VMEM through all 47 passes and
writes the mask, ``1.0`` selected / ``0.0`` not, once; a padded row
(context 0) skips them.

Both have a plain ``jax.numpy`` form (``kernels=False``: the CPU tests'
path and the kernels' oracle) of the same arithmetic.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float("-inf")
_VMEM_LIMIT_BYTES = 48 * 1024 * 1024
INT_MIN = -(1 << 31)


def _largest_dividing(n: int, choices: tuple[int, ...]) -> int:
    return next((c for c in choices if n % c == 0), n)


# ---------------------------------------------------------------------------
# The index score
# ---------------------------------------------------------------------------


def index_scores_xla(q, w, k, start_pos, context_lens):
    """The oracle: ``q`` [B, T, G, d], ``w`` [B, T, G] float32, ``k``
    [B, S, d] -> [B, T, S] float32, ``-inf`` where key ``j`` is past the
    query's position ``start_pos + t`` or the row's context."""
    from dynamo_tpu.models.hybrid import einsum_f32

    B, T, G, _ = q.shape
    S = k.shape[1]
    s = einsum_f32("btgd,bsd->btgs", q, k.astype(q.dtype))
    score = jnp.sum(w.astype(jnp.float32)[..., None] * jax.nn.relu(s), axis=2)
    key_pos = jnp.arange(S, dtype=jnp.int32)[None, None, :]
    q_pos = (start_pos.astype(jnp.int32)[:, None]
             + jnp.arange(T, dtype=jnp.int32)[None, :])[..., None]
    valid = (key_pos <= q_pos) & (key_pos < context_lens[:, None, None])
    return jnp.where(valid, score, NEG_INF)


def _index_kernel(starts_ref, ctx_ref, q_ref, w_ref, k_ref, o_ref, *,
                  tq: int, ts: int, heads: int):
    b, qi, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    ctx = ctx_ref[b]
    q_lo = starts_ref[b] + qi * tq

    @pl.when(j * ts >= jnp.minimum(q_lo + tq, ctx))
    def _behind_the_edge():
        o_ref[0] = jnp.full((tq, ts), NEG_INF, jnp.float32)

    @pl.when(j * ts < jnp.minimum(q_lo + tq, ctx))
    def _score():
        q = q_ref[0, 0]                                   # [G * tq, d]
        keys = k_ref[0]                                   # [ts, d]
        if keys.dtype != q.dtype:
            keys = keys.astype(q.dtype)
        s = jax.lax.dot_general(q, keys, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = w_ref[0, 0] * jnp.maximum(s, 0.0)             # [G * tq, ts]
        if tq == 1:
            score = jnp.sum(s, axis=0, keepdims=True)
        elif tq % 8 == 0:
            # head-major rows: head g of the tile's tokens is one aligned
            # slice, and the sum over heads is G - 1 adds of such slices
            score = s[:tq]
            for g in range(1, heads):
                score = score + s[g * tq:(g + 1) * tq]
        else:
            score = jnp.sum(s.reshape(heads, tq, ts), axis=0)
        key_pos = j * ts + jax.lax.broadcasted_iota(jnp.int32, (tq, ts), 1)
        q_pos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (tq, ts), 0)
        o_ref[0] = jnp.where((key_pos <= q_pos) & (key_pos < ctx), score, NEG_INF)


@functools.partial(jax.jit, static_argnames=("interpret",))
def index_scores(q, w, k, start_pos, context_lens, *, interpret: bool = False):
    """``q`` [B, T, G, d] (rotated, the activation dtype); ``w`` [B, T, G]
    float32; ``k`` [B, S, d], the row's cached indexer keys in table
    order (this step's already among them); ``start_pos`` [B]: row
    ``b``'s token ``t`` sits at ``start_pos[b] + t``; ``context_lens``
    [B]. Returns the index score [B, T, S] float32, ``-inf`` at keys a
    query may not see."""
    B, T, G, d = q.shape
    S = k.shape[1]
    tq = 1 if T == 1 else _largest_dividing(T, (32, 16, 8))
    ts = _largest_dividing(S, (2048, 1024, 512, 256, 128) if T == 1
                           else (512, 256, 128))
    n_tiles = T // tq
    # head-major inside a tile: row g * tq + t
    q4 = q.reshape(B, n_tiles, tq, G, d).swapaxes(2, 3).reshape(
        B, n_tiles, G * tq, d)
    w4 = w.astype(jnp.float32).reshape(B, n_tiles, tq, G).swapaxes(2, 3).reshape(
        B, n_tiles, G * tq, 1)

    def tile(b, qi, j, st, c):
        return (b, qi, 0, 0)

    def keys(b, qi, j, st, c):
        # key tiles behind the tile's causal edge repeat the last live
        # one, which skips their copy
        hi = jnp.minimum(st[b] + (qi + 1) * tq, c[b])
        return (b, jnp.minimum(j, jnp.maximum(hi - 1, 0) // ts), 0)

    return pl.pallas_call(
        functools.partial(_index_kernel, tq=tq, ts=ts, heads=G),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,  # starts, contexts
            grid=(B, n_tiles, S // ts),
            in_specs=[
                pl.BlockSpec((1, 1, G * tq, d), tile),
                pl.BlockSpec((1, 1, G * tq, 1), tile),
                pl.BlockSpec((1, ts, d), keys),
            ],
            out_specs=pl.BlockSpec((1, tq, ts), lambda b, qi, j, st, c: (b, qi, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((B, T, S), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        name="dsa_index_decode" if T == 1 else "dsa_index_prefill",
        interpret=interpret,
    )(jnp.asarray(start_pos, jnp.int32), context_lens.astype(jnp.int32),
      q4, w4, k)


# ---------------------------------------------------------------------------
# The exact top k, as a mask
# ---------------------------------------------------------------------------


def sort_key(scores):
    """float32 -> int32 of the same order (``-inf`` lowest; no NaN)."""
    bits = jax.lax.bitcast_convert_type(scores, jnp.int32)
    return bits ^ ((bits >> 31) & jnp.int32(0x7FFFFFFF))


def _topk_mask(scores, k: int, one_query: bool = False):
    """``scores`` [rows, S] float32 -> [rows, S] float32 mask of each
    row's ``min(k, finite)`` largest scores above ``-inf``, ties to the
    lower index. ``one_query``: the block is ONE query's candidates laid
    row after row (a decode row's ``S`` scores as ``[8, S / 8]``, so that
    they fill whole vector registers). Written once for XLA and for the
    kernel's body: compares and float32 counts (exact below 2**24) alone."""
    rows, S = scores.shape
    key = sort_key(scores)
    want = jnp.float32(k)
    axes = (0, 1) if one_query else (1,)

    def count(hit):
        return jnp.sum(jnp.where(hit, 1.0, 0.0), axis=axes, keepdims=True)

    # the k-th largest key, from its top bit down: INT_MIN + 2**31 is 0
    thr = jnp.where(count(key >= 0) >= want, jnp.int32(0), jnp.int32(INT_MIN))

    def value_bit(i, thr):
        cand = thr + jnp.left_shift(jnp.int32(1), 30 - i)
        return jnp.where(count(key >= cand) >= want, cand, thr)

    thr = jax.lax.fori_loop(0, 31, value_bit, thr)
    above = key > thr
    tied = key == thr
    need = want - count(above)              # >= 1 of the tied, lowest index first
    idx = jax.lax.broadcasted_iota(jnp.int32, (rows, S), 1)
    if one_query:
        idx = idx + S * jax.lax.broadcasted_iota(jnp.int32, (rows, S), 0)
    bits = max(1, ((rows * S if one_query else S) - 1).bit_length())

    # the largest cut with fewer than ``need`` tied keys below it: the
    # need-th tied key sits AT it
    def index_bit(i, cut):
        cand = cut + jnp.left_shift(jnp.int32(1), bits - 1 - i)
        return jnp.where(count(tied & (idx < cand)) < need, cand, cut)

    cut = jax.lax.fori_loop(0, bits, index_bit, jnp.zeros_like(thr))
    chosen = (above | (tied & (idx <= cut))) & (scores > NEG_INF)
    return jnp.where(chosen, 1.0, 0.0)


def select_topk_xla(scores, k: int):
    """The oracle: ``scores`` [B, T, S] -> the mask [B, T, S] float32."""
    B, T, S = scores.shape
    return _topk_mask(scores.reshape(B * T, S), k).reshape(B, T, S)


def _select_kernel(live_ref, s_ref, o_ref, *, k: int, one_query: bool):
    b = pl.program_id(0)

    @pl.when(live_ref[b] <= 0)
    def _padded_row():
        o_ref[0] = jnp.zeros(o_ref.shape[1:], jnp.float32)

    @pl.when(live_ref[b] > 0)
    def _select():
        o_ref[0] = _topk_mask(s_ref[0], k, one_query)


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def select_topk(scores, context_lens, *, k: int, interpret: bool = False):
    """``scores`` [B, T, S] float32 (``-inf``: not a candidate) -> the
    mask [B, T, S] float32 of each query's ``min(k, candidates)`` largest,
    ties to the lower index. A row of context 0 (padding) costs a store
    of zeros. One query a row (decode) is selected as ``[8, S / 8]``:
    eight times fewer vector registers a pass than ``[1, S]``."""
    B, T, S = scores.shape
    one_query = T == 1 and S % 1024 == 0
    if one_query:
        scores = scores.reshape(B, 8, S // 8)
    rows, cols = scores.shape[1:]
    tq = 8 if rows % 8 == 0 else rows
    block = pl.BlockSpec((1, tq, cols), lambda b, qi, live: (b, qi, 0))
    out = pl.pallas_call(
        functools.partial(_select_kernel, k=k, one_query=one_query),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,  # contexts
            grid=(B, rows // tq),
            in_specs=[block],
            out_specs=block,
        ),
        out_shape=jax.ShapeDtypeStruct(scores.shape, jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        name="dsa_select_decode" if T == 1 else "dsa_select_prefill",
        interpret=interpret,
    )(context_lens.astype(jnp.int32), scores)
    return out.reshape(B, T, S)
