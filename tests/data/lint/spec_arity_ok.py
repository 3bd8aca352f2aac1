"""Clean fixture for DL304 spec-arity-drift: specs match the wrapped
signature and declared axes; dynamic specs and variadic bodies degrade
to counted misses rather than guessed indices."""

from jax.sharding import PartitionSpec as P

from jax import shard_map


def matched(mesh, q, k, v):
    def body(q_l, k_l, v_l):
        return q_l, k_l + v_l

    return shard_map(
        body,
        mesh=mesh,
        in_specs=(P("dp"), P("dp"), P("dp")),
        out_specs=(P("dp"), P("dp")),
        axis_names={"dp"},
    )


def dynamic_specs(mesh, x, specs):
    # in_specs arrives as a value: counted miss, never a guessed index
    def body(x_l):
        return x_l

    return shard_map(
        body,
        mesh=mesh,
        in_specs=specs,
        out_specs=P(None),
        axis_names={"dp"},
    )


def variadic(mesh, args):
    # *args body: no positional arity to compare against
    def body(*xs):
        return xs[0]

    return shard_map(
        body,
        mesh=mesh,
        in_specs=(P("dp"), P("dp")),
        out_specs=P("dp"),
        axis_names={"dp"},
    )
