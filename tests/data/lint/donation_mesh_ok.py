"""Clean fixture for DL303 donation-across-mesh: donation happens at
the unmapped boundary, and donated arguments are constrained to the
same layout the jit declares."""

import functools

import jax
from jax.sharding import PartitionSpec as P

from jax import shard_map


@functools.partial(jax.jit, donate_argnums=(0,))
def update(buf, delta):
    return buf + delta


def mapped_then_update(mesh, buf, delta):
    def body(b_l, d_l):
        return b_l + d_l

    mapped = shard_map(
        body,
        mesh=mesh,
        in_specs=(P("dp"), P("dp")),
        out_specs=P("dp"),
        axis_names={"dp"},
    )
    summed = mapped(buf, delta)
    # donation at the unmapped boundary: the buffer's layout is settled
    return update(summed, delta)


def dispatch(params, state):
    fn = jax.jit(
        apply_fn, in_shardings=(P("dp"), P(None)), donate_argnums=(0,)
    )
    # constrained layout matches the declared in_sharding: donation is
    # a true in-place reuse, no resharding copy
    state = jax.lax.with_sharding_constraint(state, P("dp"))
    return fn(state, params)


def apply_fn(state, params):
    return state * params
