"""Violating fixture for DL304 spec-arity-drift: literal
in_specs/out_specs tuples whose arity disagrees with the wrapped
callable's signature or return shape, and specs naming axes the site
never declared."""

from jax.sharding import PartitionSpec as P

from jax import shard_map


def too_few(mesh, q, k, v):
    def body(q_l, k_l, v_l):
        return q_l + k_l + v_l

    return shard_map(  # VIOLATION: 2 in_specs for a 3-parameter body
        body,
        mesh=mesh,
        in_specs=(P("dp"), P("dp")),
        out_specs=P("dp"),
        axis_names={"dp"},
    )


def wrong_out(mesh, q, k):
    def body(q_l, k_l):
        return q_l, k_l, q_l + k_l

    return shard_map(  # VIOLATION: body returns a 3-tuple, 2 out_specs
        body,
        mesh=mesh,
        in_specs=(P("dp"), P("dp")),
        out_specs=(P("dp"), P("dp")),
        axis_names={"dp"},
    )


def stray_axis(mesh, x):
    def body(x_l):
        return x_l

    return shard_map(  # VIOLATION: specs name mp, site declares only dp
        body,
        mesh=mesh,
        in_specs=(P("mp"),),
        out_specs=P("mp"),
        axis_names={"dp"},
    )
