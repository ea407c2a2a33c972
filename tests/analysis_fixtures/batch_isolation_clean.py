# analysis-fixture: contract=batch-isolation expect=clean
"""The sanctioned packed-serving shape: two tenants on DISJOINT 4-chip
sub-meshes traced through one program, each tenant's outputs a function of
its own inputs only, every shard_map confined to its tenant's device set,
no gathering collective anywhere."""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from stencil_tpu import analysis
from jax import shard_map


def build():
    devs = jax.devices()
    mesh_a = Mesh(np.array(devs[:4]), ("x",))
    mesh_b = Mesh(np.array(devs[4:8]), ("x",))
    f_a = shard_map(
        lambda q: q * 2.0, mesh=mesh_a, in_specs=(P("x"),), out_specs=P("x")
    )
    f_b = shard_map(
        lambda q: q + 1.0, mesh=mesh_b, in_specs=(P("x"),), out_specs=P("x")
    )

    def both(c_a, c_b):
        return f_a(c_a), f_b(c_b)

    c_a = jnp.zeros((8, 16), jnp.float32)
    c_b = jnp.ones((8, 16), jnp.float32)
    return analysis.trace_artifact(
        both,
        c_a,
        c_b,
        label="fixture:batch-isolation-clean",
        kind="serve",
        n_devices=8,
        meta={
            "mode": "subslice",
            "input_groups": [1, 1],
            "output_groups": [1, 1],
            "device_sets": [[d.id for d in devs[:4]], [d.id for d in devs[4:8]]],
        },
    )
