"""Violating fixture for DL303 donation-across-mesh: buffer donation
under a mismatched sharding story — donating jits invoked from inside
shard_map bodies (directly and via a helper), and a donated argument
whose constrained layout disagrees with the jit's declared
in_shardings."""

import functools

import jax
from jax.sharding import PartitionSpec as P

from jax import shard_map


@functools.partial(jax.jit, donate_argnums=(0,))
def update(buf, delta):
    return buf + delta


def mapped_update(mesh, buf, delta):
    def body(b_l, d_l):
        return update(b_l, d_l)  # VIOLATION: donation inside the body

    return shard_map(
        body,
        mesh=mesh,
        in_specs=(P("dp"), P("dp")),
        out_specs=P("dp"),
        axis_names={"dp"},
    )


def nested_update(mesh, buf, delta):
    def body(b_l, d_l):
        return via_helper(b_l, d_l)

    return shard_map(
        body,
        mesh=mesh,
        in_specs=(P("dp"), P("dp")),
        out_specs=P("dp"),
        axis_names={"dp"},
    )


def via_helper(b, d):
    # one call level below the mapped body
    return update(b, d)  # VIOLATION: donation inside the body


def dispatch(params, state):
    fn = jax.jit(
        apply_fn, in_shardings=(P("dp"), P(None)), donate_argnums=(0,)
    )
    state = jax.lax.with_sharding_constraint(state, P("mp"))
    return fn(state, params)  # VIOLATION: constrained P("mp"), declared P("dp")


def apply_fn(state, params):
    return state * params
