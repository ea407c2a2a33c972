# analysis-fixture: contract=numerics-bounded expect=fire
"""The forbidden numerics shape: the 'stats program' all_gathers the whole
field and returns it for the host to reduce — numerically identical to the
sanctioned form, but the host transfer scales with the DOMAIN, not the
quantity count (exactly the PR-1 sentinel cost the observatory retired)."""

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
        whole = lax.all_gather(q, "x")  # materializes the full field
        return whole  # ...and ships it to the host to reduce there

    # check_vma off: the replication checker cannot infer through the
    # all_gather this fixture deliberately seeds
    fn = shard_map(
        body, mesh=mesh, in_specs=(P("x"),), out_specs=P(), check_vma=False
    )
    q = jnp.zeros((8, 16), jnp.float32)
    return analysis.trace_artifact(
        fn,
        q,
        label="fixture:numerics-bounded-fire",
        kind="numerics",
        n_devices=8,
        meta={"n_quantities": 1},
    )
