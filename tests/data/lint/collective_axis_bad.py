"""Violating fixture for DL302 collective-axis-mismatch: collectives
named over axes the enclosing shard_map never declared — in the body
itself and one call level down."""

import jax
from jax.sharding import PartitionSpec as P

from jax import shard_map


def forward(mesh, x):
    def stage(x_l):
        total = jax.lax.psum(x_l, "pp")  # declared axis: fine
        drift = jax.lax.psum(x_l, "dp")  # VIOLATION: dp not declared
        rank = jax.lax.axis_index("mp")  # VIOLATION: mp not declared
        return reduce_helper(total + drift + rank)

    return shard_map(
        stage,
        mesh=mesh,
        in_specs=(P("pp"),),
        out_specs=P("pp"),
        axis_names={"pp"},
    )


def reduce_helper(y):
    # one call level below the mapped body
    return jax.lax.all_gather(y, "dp")  # VIOLATION: dp not declared
