# analysis-fixture: contract=exchange-structure expect=clean
"""The sanctioned fused exchange: both quantities stack into ONE buffer per
direction, ≤6 permutes total regardless of field count."""

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from stencil_tpu import analysis
from stencil_tpu.telemetry import names as tm
from jax import shard_map


def build():
    devs = jax.devices()[:8]
    mesh = Mesh(np.array(devs), ("x",))
    fwd = [(i, (i + 1) % 8) for i in range(8)]
    rev = [(i, (i - 1) % 8) for i in range(8)]

    def body(q0, q1):
        fused = jnp.concatenate([q0, q1], axis=0)
        for name, perm in (
            (tm.SPAN_EXCHANGE_X_LOW, fwd),
            (tm.SPAN_EXCHANGE_X_HIGH, rev),
            (tm.SPAN_EXCHANGE_Y_LOW, fwd),
            (tm.SPAN_EXCHANGE_Y_HIGH, rev),
            (tm.SPAN_EXCHANGE_Z_LOW, fwd),
            (tm.SPAN_EXCHANGE_Z_HIGH, rev),
        ):
            with jax.named_scope(name):
                fused = lax.ppermute(fused, "x", perm)
        k = q0.shape[0]
        return fused[:k], fused[k:]

    fn = shard_map(
        body, mesh=mesh, in_specs=(P("x"), P("x")), out_specs=(P("x"), P("x"))
    )
    q = jnp.zeros((8, 16), jnp.float32)
    return analysis.trace_artifact(
        fn,
        q,
        q,
        label="fixture:exchange-structure-clean",
        kind="exchange",
        axes={"exchange_route": "direct"},
        n_devices=8,
    )
