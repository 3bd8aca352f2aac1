"""Violating fixture for DL301 host-sync-in-shard-body: device->host
syncs reachable from inside shard_map-wrapped bodies — direct frames,
nested closures, and helpers the body calls."""

import numpy as np
from jax.sharding import PartitionSpec as P

from jax import shard_map


def ring_forward(mesh):
    def local(q_l, k_l, v_l):
        # direct frame of the mapped body
        depth = int(q_l.sum().item())  # VIOLATION: per-shard host sync
        gather_stats(k_l)
        return attend(q_l, k_l, v_l) + depth

    def attend(q_l, k_l, v_l):
        # nested closure: still the body's frame family
        return deep_norm(q_l + k_l + v_l)

    return shard_map(
        local,
        mesh=mesh,
        in_specs=(P("dp"), P("dp"), P("dp")),
        out_specs=P("dp"),
        axis_names={"dp"},
    )


def gather_stats(k):
    # one call level below the mapped body
    return np.asarray(k)  # VIOLATION: per-shard host sync

def deep_norm(x):
    # two call levels below the body (local -> attend -> deep_norm)
    return x / sum(x.tolist())  # VIOLATION: per-shard host sync
