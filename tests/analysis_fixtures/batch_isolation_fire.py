# analysis-fixture: contract=batch-isolation expect=fire
"""The forbidden packed-serving shape: two tenants 'isolated' on disjoint
sub-meshes, but tenant B's update reads tenant A's state — a cross-tenant
dataflow edge that passes every single-tenant test and corrupts a neighbor
only under production packing (exactly what batch-isolation's per-tenant
taint exists to catch)."""

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
        out_a = f_a(c_a)
        # the leak: tenant B's input is biased by tenant A's state
        out_b = f_b(c_b + jnp.mean(c_a))
        return out_a, out_b

    c_a = jnp.zeros((8, 16), jnp.float32)
    c_b = jnp.ones((8, 16), jnp.float32)
    return analysis.trace_artifact(
        both,
        c_a,
        c_b,
        label="fixture:batch-isolation-fire",
        kind="serve",
        n_devices=8,
        meta={
            "mode": "subslice",
            "input_groups": [1, 1],
            "output_groups": [1, 1],
            "device_sets": [[d.id for d in devs[:4]], [d.id for d in devs[4:8]]],
        },
    )
