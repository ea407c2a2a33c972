# analysis-fixture: contract=numerics-bounded expect=clean
"""The sanctioned numerics shape: per-shard stats reduced IN-PROGRAM with
psum/pmin/pmax, scalar-only outputs within the per-quantity budget — the
host transfer is a handful of scalars regardless of field size."""

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from stencil_tpu import analysis
from jax import shard_map


def build():
    devs = jax.devices()[:8]
    mesh = Mesh(np.array(devs), ("x",))

    def body(q):
        mn = lax.pmin(jnp.min(q), "x")
        mx = lax.pmax(jnp.max(q), "x")
        s = lax.psum(jnp.sum(q), "x")
        s2 = lax.psum(jnp.sum(q * q), "x")
        nbad = lax.psum(jnp.sum(~jnp.isfinite(q)), "x")
        return mn, mx, s, s2, nbad

    fn = shard_map(
        body, mesh=mesh, in_specs=(P("x"),), out_specs=tuple(P() for _ in range(5))
    )
    q = jnp.zeros((8, 16), jnp.float32)
    return analysis.trace_artifact(
        fn,
        q,
        label="fixture:numerics-bounded-clean",
        kind="numerics",
        n_devices=8,
        meta={"n_quantities": 1},
    )
