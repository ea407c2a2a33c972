# analysis-fixture: contract=redistribute-bounded expect=fire
"""A full-gather 'redistribution': every rank all_gathers the complete
stacked state and slices its target block out — numerically identical to
the bounded schedule, and exactly the peak-memory failure the contract
exists to catch (the gathered intermediate is n_ranks x the shard)."""

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import numpy as np

from stencil_tpu import analysis
from jax import shard_map

N_DEV = 4
BLOCK = (8, 8, 8)


def build():
    devices = np.array(jax.devices()[:N_DEV])
    mesh = Mesh(devices, ("r",))

    def per_shard(block):
        everything = lax.all_gather(block[0], "r")  # the whole domain, per chip
        rank = lax.axis_index("r")
        zero = jnp.int32(0)
        return lax.dynamic_slice(
            everything, (rank, zero, zero, zero), (1,) + BLOCK
        )

    fn = jax.jit(
        shard_map(per_shard, mesh=mesh, in_specs=P("r"), out_specs=P("r"))
    )
    block_bytes = int(np.prod(BLOCK)) * 4
    example = jax.ShapeDtypeStruct(
        (N_DEV,) + BLOCK, jnp.float32, sharding=NamedSharding(mesh, P("r"))
    )
    closed = jax.make_jaxpr(fn)(example)
    return analysis.ProgramArtifact(
        label="fixture:redistribute-bounded-fire",
        kind="redistribute",
        closed=closed,
        n_devices=N_DEV,
        meta={"bound_bytes": 3 * block_bytes, "union_ranks": N_DEV},
    )
