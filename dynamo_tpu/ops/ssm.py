"""The Mamba-2 decode update as one Pallas kernel, in place on the state plane.

One recurrent step a row (models/nemotron_h.py ``ssm_decode`` is the same
mathematics in plain XLA and the oracle of this kernel's test):

    h' = a h + (dt x) (x) B;   y = h' C

with ``h`` a head's ``[P, N]`` float32 state (``P`` the head size, ``N``
the state size: NOT square), ``a`` and ``dt`` one scalar a head, and ``B``,
``C`` ``[N]`` vectors shared by the ``H / G`` heads of a group. There is
no read-back correction: the delta rule's kernel (``ops/kda.py``) reads
``S^T k`` out of the decayed state before it writes, this one only adds.

The state plane ``[Lm, slots, H, P, N]`` float32 is read and written
THROUGH the kernel's BlockSpecs as in ``ops/kda.py``: the layer and each
row's slot ride as scalar-prefetch arguments and the output aliases the
input, so a step moves each live state across HBM once in and once out
and nothing else. A row that starts at position 0 (``fresh``) reads zeros
instead of what its slot held. Padded rows all carry slot 0, the garbage
slot.

Grid = (rows, head blocks); a block of ``HEAD_BLOCK`` heads is whole
groups (``[16, 64, 128]`` float32 = 512 KB at the published sizes). ``N``
stays the minor dimension, so a block is whole 128-lane tiles; the update
is elementwise on the VPU and ``y`` a lane reduction.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

HEAD_BLOCK = 16


def _kernel(rep, layer_ref, slot_ref, fresh_ref, x_ref, dt_ref, a_ref, b_ref,
            c_ref, s_in_ref, y_ref, s_out_ref):
    r = pl.program_id(0)
    fresh = fresh_ref[r] != 0
    groups = b_ref.shape[1]
    for gi in range(groups):          # static: a group's heads share B and C
        hs = slice(gi * rep, (gi + 1) * rep)
        S = s_in_ref[hs].astype(jnp.float32)               # [rep, P, N]
        S = jnp.where(fresh, jnp.zeros_like(S), S)
        B, C = b_ref[0, gi, 0], c_ref[0, gi, 0]            # [N]
        dtx = dt_ref[0, hs] * x_ref[0, hs]                 # [rep, P]
        S = S * a_ref[0, hs][:, :, None] + dtx[:, :, None] * B[None, None, :]
        y_ref[0, hs] = jnp.sum(S * C[None, None, :], axis=-1)
        s_out_ref[hs] = S


@functools.partial(jax.jit, static_argnames=("interpret",), donate_argnums=(0,))
def ssm_decode_update(plane, layer, slots, fresh, x, dt, a, B, C,
                      interpret: bool = False):
    """``plane`` [Lm, slots, H, P, N] float32 (donated, updated in place);
    ``layer`` scalar int32; ``slots``, ``fresh`` [R] int32; ``x`` [R, H, P],
    ``dt``, ``a`` [R, H] (the step and the decay ``exp(-exp(A_log) dt)``),
    ``B``, ``C`` [R, G, N], all float32. Returns (y [R, H, P], plane)."""
    R, H, P = x.shape
    G, N = B.shape[1:]
    rep = H // G
    hb = HEAD_BLOCK if H % HEAD_BLOCK == 0 and HEAD_BLOCK % rep == 0 else H
    gb = hb // rep

    def row(r, j, lyr, sl, fr):
        return (r, j, 0)

    def state(r, j, lyr, sl, fr):
        return (lyr[0], sl[r], j, 0, 0)

    heads = pl.BlockSpec((1, hb, P), row)
    scalar = pl.BlockSpec((1, hb, 1), row)
    # [R, G, 1, N]: a block's last two dimensions are then whole
    group = pl.BlockSpec((1, gb, 1, N), lambda r, j, lyr, sl, fr: (r, j, 0, 0))
    st = pl.BlockSpec((None, None, hb, P, N), state)
    y, plane = pl.pallas_call(
        functools.partial(_kernel, rep),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,  # layer, slots, fresh
            grid=(R, H // hb),
            in_specs=[heads, scalar, scalar, group, group, st],
            out_specs=[heads, st],
        ),
        out_shape=[jax.ShapeDtypeStruct((R, H, P), jnp.float32),
                   jax.ShapeDtypeStruct(plane.shape, plane.dtype)],
        # operand index counts the scalar-prefetch arguments: the plane is 8
        input_output_aliases={8: 1},
        name="ssm_decode_update",
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1), slots.astype(jnp.int32),
      fresh.astype(jnp.int32), x, dt[..., None], a[..., None], B[:, :, None],
      C[:, :, None], plane)
    return y, plane
