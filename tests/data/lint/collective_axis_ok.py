"""Clean fixture for DL302 collective-axis-mismatch: collectives only
name axes the enclosing shard_map declares, and variable axis names
degrade to counted misses rather than guesses."""

import jax
from jax.sharding import PartitionSpec as P

from jax import shard_map


def forward(mesh, x):
    def stage(x_l):
        total = jax.lax.psum(x_l, "pp")
        return jax.lax.all_gather(total, ("pp",))

    return shard_map(
        stage,
        mesh=mesh,
        in_specs=(P("pp"),),
        out_specs=P("pp"),
        axis_names={"pp"},
    )


def ring(mesh, q, axis_name):
    # axis name arrives as a parameter: the rule refuses to guess and
    # records a dynamic miss instead of flagging
    def local(q_l):
        return jax.lax.ppermute(q_l, axis_name, [(0, 1)])

    return shard_map(
        local,
        mesh=mesh,
        in_specs=(P(None),),
        out_specs=P(None),
    )
