"""The fully-manual shard_map mode the sharded model code relies on
(attention kernels per tp shard, MoE over ep x tp) executes on the CPU
test backend against the installed ``jax.shard_map``."""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P


def test_fully_manual_two_axis_mesh_executes_on_cpu():
    """Both declared axes are live inside the body as collective
    targets."""
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("dp", "tp"))

    def body(x):
        # psum over size-1 axes is identity; naming both axes proves
        # they are manual (an auto axis would reject the collective)
        return x * jax.lax.psum(1, "dp") * jax.lax.psum(1, "tp")

    mapped = jax.shard_map(
        body, mesh=mesh, in_specs=(P(),), out_specs=P(),
        axis_names={"dp", "tp"},
    )
    out = mapped(jnp.arange(4.0))
    assert np.allclose(np.asarray(jax.device_get(out)), np.arange(4.0))
