"""Clean fixture for DL301 host-sync-in-shard-body: the mapped body
stays device-only; host materialization happens at the unmapped
boundary after the shard_map call returns."""

import numpy as np
from jax.sharding import PartitionSpec as P

from jax import shard_map


def ring_forward(mesh, q, k, v):
    def local(q_l, k_l, v_l):
        return attend(q_l, k_l, v_l)

    def attend(q_l, k_l, v_l):
        return q_l + k_l + v_l

    mapped = shard_map(
        local,
        mesh=mesh,
        in_specs=(P("dp"), P("dp"), P("dp")),
        out_specs=P("dp"),
        axis_names={"dp"},
    )
    out = mapped(q, k, v)
    # host read OUTSIDE the mapped region: one sync for the whole mesh
    return np.asarray(out)


def summarize(x):
    # host sync in a plain helper nobody maps: fine
    return float(x.sum())
