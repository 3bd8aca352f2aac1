"""The KDA decode update as one Pallas kernel, in place on the state plane.

One recurrent step a row (models/kimi_linear.py ``kda_decode`` is the
same mathematics in plain XLA and the oracle of this kernel's test):

    S' = Diag(a) S;  u = beta (v - S'^T k);  S = S' + k u^T;  o = S^T q

The state plane ``[Lk, slots, H, d, d]`` float32 is read and written
THROUGH the kernel's BlockSpecs — the layer and each row's slot ride as
scalar-prefetch arguments, the output aliases the input — so a step
moves each LIVE state matrix across HBM once in and once out and nothing
else: no gather of the rows' states before the update, no scatter after
it (XLA's form of this step reads the gathered copy twice and writes it
twice). A row that starts at position 0 (``fresh``) reads zeros instead
of what its slot held.

Padded rows carry slot 0, the garbage slot (``StateSlots`` never hands
it out), wherever they stand in the batch, and move nothing: a padded
row's grid steps skip the arithmetic, write zeros to its row of ``o``
(it flows on through the output norm, the gate and the out projection)
and name the state block of a NEIGHBOURING live step — the first block
of the next live row, or the last block of the last live row for the
padded rows behind it (``nearest_live_row``) — and the pipeline neither
fetches nor writes back a block whose index did not change from one
step to the next. The next live row and not the one before: a step's
blocks are fetched while the step before it computes, so the next live
state arrives under the last live row's arithmetic, where a padded step
has none to hide it under. A batch of padded rows only (a prewarm's)
names slot 0 throughout and hands it back what it held.

Grid = (rows, head groups). A group is ``HEAD_BLOCK`` heads — 2 MB of
state at d = 128, the whole row at H = 32 — and the whole row where
``H`` is no multiple of it. Measured on the chip at both call shapes
(PERF.md section 6, PR 46), 32 | 16 | the earlier 8 heads that also ran
every padded row: 2.57 | 2.69 | 3.55 ms for 7 layers at 48 live rows of
64, 0.46 | 0.50 | 1.55 ms for 6 layers at 8 of 32; the same grid with a
body that only copies takes 2.25 and 0.38, so the copy and not the
arithmetic sets the time (76% of the HBM rate). The row's operands
are ``(1, H, d)`` blocks whose index does not move with the group: one
fetch a live row. Inside a step the arithmetic takes ``SUB_HEADS`` heads
a pass, so its ``[8, d, d]`` temporaries stay 0.5 MB whatever the block;
``vmem_limit`` asks for the state's four buffers (in and out, double
buffered), the operands' and the passes' temporaries. All arithmetic is
elementwise or a sublane reduction in float32 on the VPU: the two
matrix-vector products are [1, d] x [d, d], which the MXU would run at
1/128 of its width.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

HEAD_BLOCK = 32   # heads of one state block
SUB_HEADS = 8     # heads of one pass of the arithmetic


def head_block(H: int) -> int:
    return HEAD_BLOCK if H % HEAD_BLOCK == 0 else H


def sub_heads(hb: int) -> int:
    return SUB_HEADS if hb % SUB_HEADS == 0 else hb


def vmem_limit(H: int, d: int) -> int:
    """Scoped VMEM of a call: the state block in and out, double
    buffered; q, k, v, decay, o and beta (a lane tile wide) likewise;
    and room for the temporaries of a pass."""
    hb = head_block(H)
    state = 4 * hb * d * d * 4
    operands = 2 * (5 * H * d + H * 128) * 4
    return state + operands + 8 * sub_heads(hb) * d * d * 4


def nearest_live_row(slots: jax.Array) -> jax.Array:
    """For every row of ``slots [B]`` the index of the nearest LIVE row
    (slot != 0) at or after it; for the padded rows behind the last live
    row, that row's; zeros where no row is live."""
    B = slots.shape[0]
    live = slots != 0
    idx = jnp.arange(B, dtype=jnp.int32)
    ahead = jax.lax.cummin(jnp.where(live, idx, B), reverse=True)
    return jnp.where(ahead < B, ahead, jnp.max(jnp.where(live, idx, 0)))


def _kernel(layer_ref, slot_ref, fresh_ref, near_ref, q_ref, k_ref, v_ref,
            g_ref, b_ref, s_in_ref, o_ref, s_out_ref, *, sub):
    b, j = pl.program_id(0), pl.program_id(1)
    hb = s_in_ref.shape[0]
    live = slot_ref[b] != 0

    @pl.when(live)
    def _():
        fresh = fresh_ref[b] != 0

        def one_pass(i, carry):
            at = pl.multiple_of(i * sub, sub)
            blk = pl.ds(at, sub)                               # heads of the block
            row = pl.ds(pl.multiple_of(j * hb + at, sub), sub)  # heads of the row
            S = s_in_ref[blk].astype(jnp.float32)              # [sub, d(key), d(value)]
            S = jnp.where(fresh, jnp.zeros_like(S), S)
            k = k_ref[0, row]                                  # [sub, d]
            S = S * jnp.exp(g_ref[0, row])[:, :, None]
            u = b_ref[0, row] * (v_ref[0, row] - jnp.sum(S * k[:, :, None], axis=1))
            S = S + k[:, :, None] * u[:, None, :]
            o_ref[0, row] = jnp.sum(S * q_ref[0, row][:, :, None], axis=1)
            s_out_ref[blk] = S
            return carry

        jax.lax.fori_loop(0, hb // sub, one_pass, 0)

    @pl.when(jnp.logical_not(live))
    def _():
        o_ref[0, pl.ds(pl.multiple_of(j * hb, hb), hb)] = jnp.zeros(
            (hb, o_ref.shape[2]), o_ref.dtype)

        # no live row in the batch: the one state block the pipeline
        # writes back is slot 0's own, which no step computed
        @pl.when(slot_ref[near_ref[b]] == 0)
        def _():
            s_out_ref[...] = s_in_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",), donate_argnums=(0,))
def kda_decode_update(plane, layer, slots, fresh, q, k, v, glog, beta,
                      interpret: bool = False):
    """``plane`` [Lk, slots, H, d, d] float32 (donated, updated in place);
    ``layer`` scalar int32; ``slots``, ``fresh`` [B] int32; q, k, v, glog
    [B, H, d] float32; beta [B, H] float32. Returns (o [B, H, d], plane).
    A row whose slot is 0 is padding: its ``o`` is zeros and no slot's
    state moves for it."""
    B, H, d = q.shape
    hb = head_block(H)
    groups = H // hb
    slots = slots.astype(jnp.int32)

    def out_row(b, j, lyr, sl, fr, near):
        return (b, 0, 0)

    def row(b, j, lyr, sl, fr, near):
        return (near[b], 0, 0)

    def state(b, j, lyr, sl, fr, near):
        # a padded row stays on the block its live neighbour's step holds
        r = near[b]
        return (lyr[0], sl[r],
                jnp.where(r == b, j, jnp.where(r < b, groups - 1, 0)), 0, 0)

    vec = pl.BlockSpec((1, H, d), row)
    st = pl.BlockSpec((None, None, hb, d, d), state)
    o, plane = pl.pallas_call(
        functools.partial(_kernel, sub=sub_heads(hb)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,  # layer, slots, fresh, nearest live row
            grid=(B, groups),
            in_specs=[vec, vec, vec, vec, pl.BlockSpec((1, H, 1), row), st],
            out_specs=[pl.BlockSpec((1, H, d), out_row), st],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, H, d), jnp.float32),
                   jax.ShapeDtypeStruct(plane.shape, plane.dtype)],
        # operand index counts the scalar-prefetch arguments: the plane is 9
        input_output_aliases={9: 1},
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem_limit(H, d)),
        name="kda_decode_update",
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1), slots, fresh.astype(jnp.int32),
      nearest_live_row(slots), q, k, v, glog, beta[..., None], plane)
    return o, plane
