"""The KDA decode update as one Pallas kernel, in place on the state plane.

One recurrent step a row (models/kimi_linear.py ``kda_decode`` is the
same mathematics in plain XLA and the oracle of this kernel's test):

    S' = Diag(a) S;  u = beta (v - S'^T k);  S = S' + k u^T;  o = S^T q

The state plane ``[Lk, slots, H, d, d]`` float32 is read and written
THROUGH the kernel's BlockSpecs — the layer and each row's slot ride as
scalar-prefetch arguments, the output aliases the input — so a step
moves each live state matrix across HBM once in and once out and nothing
else: no gather of the rows' states before the update, no scatter after
it (XLA's form of this step reads the gathered copy twice and writes it
twice). A row that starts at position 0 (``fresh``) reads zeros instead
of what its slot held. Padded rows all carry slot 0, the garbage slot.

Grid = (rows, head groups); a head group's ``[hb, d, d]`` block is
0.5 MB at hb = 8, d = 128. All arithmetic is elementwise or a sublane
reduction in float32 on the VPU: the two matrix-vector products are
[1, d] x [d, d], which the MXU would run at 1/128 of its width.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

HEAD_BLOCK = 8


def _kernel(layer_ref, slot_ref, fresh_ref, q_ref, k_ref, v_ref, g_ref, b_ref,
            s_in_ref, o_ref, s_out_ref):
    b = pl.program_id(0)
    S = s_in_ref[...].astype(jnp.float32)                  # [hb, d(key), d(value)]
    S = jnp.where(fresh_ref[b] != 0, jnp.zeros_like(S), S)
    k = k_ref[0]                                           # [hb, d]
    S = S * jnp.exp(g_ref[0])[:, :, None]
    u = b_ref[0] * (v_ref[0] - jnp.sum(S * k[:, :, None], axis=1))
    S = S + k[:, :, None] * u[:, None, :]
    o_ref[0] = jnp.sum(S * q_ref[0][:, :, None], axis=1)
    s_out_ref[...] = S


@functools.partial(jax.jit, static_argnames=("interpret",), donate_argnums=(0,))
def kda_decode_update(plane, layer, slots, fresh, q, k, v, glog, beta,
                      interpret: bool = False):
    """``plane`` [Lk, slots, H, d, d] float32 (donated, updated in place);
    ``layer`` scalar int32; ``slots``, ``fresh`` [B] int32; q, k, v, glog
    [B, H, d] float32; beta [B, H] float32. Returns (o [B, H, d], plane)."""
    B, H, d = q.shape
    hb = HEAD_BLOCK if H % HEAD_BLOCK == 0 else H

    def row(b, j, lyr, sl, fr):
        return (b, j, 0)

    def state(b, j, lyr, sl, fr):
        return (lyr[0], sl[b], j, 0, 0)

    vec = pl.BlockSpec((1, hb, d), row)
    st = pl.BlockSpec((None, None, hb, d, d), state)
    o, plane = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,  # layer, slots, fresh
            grid=(B, H // hb),
            in_specs=[vec, vec, vec, vec, pl.BlockSpec((1, hb, 1), row), st],
            out_specs=[vec, st],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, H, d), jnp.float32),
                   jax.ShapeDtypeStruct(plane.shape, plane.dtype)],
        # operand index counts the scalar-prefetch arguments: the plane is 8
        input_output_aliases={8: 1},
        name="kda_decode_update",
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1), slots.astype(jnp.int32),
      fresh.astype(jnp.int32), q, k, v, glog, beta[..., None], plane)
    return o, plane
