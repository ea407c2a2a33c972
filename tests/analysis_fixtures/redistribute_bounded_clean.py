# analysis-fixture: contract=redistribute-bounded expect=clean
"""The sanctioned shape: shard-sized staging chunks through one ppermute
round, blended into a zero-initialized target block — every intermediate
stays under the staging bound and nothing gathers."""

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
    pairs = [(i, (i + 1) % N_DEV) for i in range(N_DEV)]

    def per_shard(block):
        chunk = lax.dynamic_slice(block[0], (0, 0, 0), (4, 8, 8))
        moved = lax.ppermute(chunk, "r", pairs)
        out = jnp.zeros(BLOCK, jnp.float32)
        out = lax.dynamic_update_slice(out, moved, (4, 0, 0))
        return out[None]

    fn = jax.jit(
        shard_map(per_shard, mesh=mesh, in_specs=P("r"), out_specs=P("r"))
    )
    block_bytes = int(np.prod(BLOCK)) * 4
    example = jax.ShapeDtypeStruct(
        (N_DEV,) + BLOCK, jnp.float32, sharding=NamedSharding(mesh, P("r"))
    )
    closed = jax.make_jaxpr(fn)(example)
    return analysis.ProgramArtifact(
        label="fixture:redistribute-bounded-clean",
        kind="redistribute",
        closed=closed,
        n_devices=N_DEV,
        meta={"bound_bytes": 3 * block_bytes, "union_ranks": N_DEV},
    )
