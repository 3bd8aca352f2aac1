"""The depthwise convolution's decode step as one Pallas kernel, in place
on the state plane's convolution tails.

A recurrent layer keeps, a sequence, the last ``K - 1`` inputs of its
causal depthwise convolution (``models/hybrid.py`` ``conv_step`` holds
the same mathematics in plain XLA, which is this kernel's oracle). One
decode step a row:

    y = sum_i full[i] * w[i] (+ bias),  full = [tail; x];  tail' = full[1:]

The tails ``[L, slots, (K-1) * C / lane, lane]`` float32 (a slot's
``K - 1`` rows of ``C`` channels one after another, whole lane tiles:
``hybrid.conv_tail_shape``) stay in HBM and the kernel copies a LIVE
row's tail in and out itself, addressed by the scalar-prefetched layer
and slot, the output aliased to the input: once in and once out a live
row, where XLA's form gathers every row of the batch and scatters every
row back, a row at a time. A ``fresh`` row reads zeros. A padded row
(slot 0, wherever it stands) starts no copy and writes zeros to its row
of ``y``. Two things hold the plane where it is. The output is declared
in HBM (``pltpu.HBM`` as its ``out_shape``, which colours the aliased
operand too): a plane of tens of MB is one XLA otherwise stages in VMEM
WHOLE, in and out, around every call (compiled for the described chip:
67 MB each way a layer in kimi — the parent's gather and scatter paid
the same copies, 0.56 ms a step). And the copies are the kernel's own,
not BlockSpecs as in ``ops/kda.py``: the BlockSpec form of this kernel
halted the core on the chip whenever a batch held a padded row (PERF.md
section 6, PR 46).

Grid = (rows,), in order: a live row starts the next live row's copy in
(``nxt``) before it waits for its own, and its copy out is waited for
two live rows later, when its buffer comes round again (``ord`` is a
live row's ordinal; two buffers each way). The weights (and the bias,
where the family has one) are one block that never moves.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(layer_ref, slot_ref, fresh_ref, nxt_ref, ord_ref, x_ref, w_ref,
            *rest, bias: bool):
    bias_ref = rest[0] if bias else None
    plane_in, y_ref, plane_out, t_in, t_out, sem_in, sem_out = rest[int(bias):]
    b, B = pl.program_id(0), pl.num_programs(0)
    cr = x_ref.shape[1]                 # sublane rows of one tail row
    taps = w_ref.shape[0]
    lyr = layer_ref[0]

    def fetch(row, buf):
        return pltpu.make_async_copy(
            plane_in.at[lyr, slot_ref[row]], t_in.at[buf], sem_in.at[buf])

    def store(row, buf):
        return pltpu.make_async_copy(
            t_out.at[buf], plane_out.at[lyr, slot_ref[row]], sem_out.at[buf])

    live = slot_ref[b] != 0

    @pl.when(live)
    def _():
        k, nxt = ord_ref[b], nxt_ref[b]
        buf = k % 2

        @pl.when(k == 0)
        def _():
            fetch(b, buf).start()

        @pl.when(nxt < B)
        def _():
            fetch(nxt, 1 - buf).start()

        fetch(b, buf).wait()

        @pl.when(k >= 2)
        def _():
            store(b, buf).wait()        # the copy out of two live rows ago

        tail = t_in[buf]
        tail = jnp.where(fresh_ref[b] != 0, jnp.zeros_like(tail), tail)
        x = x_ref[0]
        y = tail[:cr] * w_ref[0]
        for i in range(1, taps - 1):
            y = y + tail[i * cr:(i + 1) * cr] * w_ref[i]
        y = y + x * w_ref[taps - 1]
        if bias:
            y = y + bias_ref[...]
        y_ref[0] = y
        t_out[buf, :(taps - 2) * cr] = tail[cr:]
        t_out[buf, (taps - 2) * cr:] = x
        store(b, buf).start()

        @pl.when(nxt >= B)              # the last live row waits for what is out
        def _():
            store(b, buf).wait()

            @pl.when(k >= 1)
            def _():
                store(b, 1 - buf).wait()

    @pl.when(jnp.logical_not(live))
    def _():
        y_ref[...] = jnp.zeros(y_ref.shape, y_ref.dtype)


def live_order(slots: jax.Array) -> tuple[jax.Array, jax.Array]:
    """For every row of ``slots [B]``: the index of the next LIVE row
    (slot != 0) after it, ``B`` where there is none; and how many live
    rows stand before it."""
    B = slots.shape[0]
    live = slots != 0
    idx = jnp.arange(B, dtype=jnp.int32)
    at_or_after = jax.lax.cummin(jnp.where(live, idx, B), reverse=True)
    nxt = jnp.concatenate([at_or_after[1:], jnp.full((1,), B, jnp.int32)])
    return nxt, jnp.cumsum(live, dtype=jnp.int32) - live.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("interpret",), donate_argnums=(0,))
def conv_tail_update(plane, layer, slots, fresh, x, w, bias=None,
                     interpret: bool = False):
    """``plane`` [L, slots, (K-1) * C / lane, lane] float32 (donated,
    updated in place); ``layer`` scalar int32; ``slots``, ``fresh`` [B]
    int32; ``x`` [B, C] float32, the step's input; ``w`` [K, C] float32,
    K >= 3; ``bias`` [C] or None. Returns (y [B, C] float32, plane). A
    row whose slot is 0 is padding: its ``y`` is zeros and no slot's
    tail moves."""
    B, C = x.shape
    K = w.shape[0]
    rows, lane = plane.shape[2:]
    cr = C // lane
    assert K >= 3 and C % lane == 0 and rows == (K - 1) * cr, (plane.shape, x.shape, K)
    slots = slots.astype(jnp.int32)

    def row(b, *_):
        return (b, 0, 0)

    in_hbm = pl.BlockSpec(memory_space=pltpu.HBM)
    operands = [x.reshape(B, cr, lane), w.reshape(K, cr, lane)]
    in_specs = [pl.BlockSpec((1, cr, lane), row),
                pl.BlockSpec((K, cr, lane), lambda b, *_: (0, 0, 0))]
    if bias is not None:
        operands.append(bias.reshape(cr, lane))
        in_specs.append(pl.BlockSpec((cr, lane), lambda b, *_: (0, 0)))
    y, plane = pl.pallas_call(
        functools.partial(_kernel, bias=bias is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,  # layer, slots, fresh, next live row, ordinal
            grid=(B,),
            in_specs=in_specs + [in_hbm],
            out_specs=[pl.BlockSpec((1, cr, lane), row), in_hbm],
            scratch_shapes=[
                pltpu.VMEM((2, rows, lane), plane.dtype),
                pltpu.VMEM((2, rows, lane), plane.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, cr, lane), jnp.float32),
                   # held to HBM, and the operand it aliases with it
                   pltpu.HBM(plane.shape, plane.dtype)],
        # operand index counts the scalar-prefetch arguments
        input_output_aliases={5 + len(operands): 1},
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        name="conv_tail_update",
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1), slots, fresh.astype(jnp.int32),
      *live_order(slots), *operands, plane)
    return y.reshape(B, C), plane
