# analysis-fixture: contract=exchange-scope expect=clean
"""The whole sweep — slab cut, wire, halo write — under ``exchange.x``,
the direction scope nested inside."""

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from stencil_tpu import analysis
from stencil_tpu.telemetry import names as tm
from jax import shard_map


def build():
    mesh = Mesh(np.array(jax.devices()[:8]), ("x",))
    fwd = [(i, (i + 1) % 8) for i in range(8)]

    def body(q):
        with jax.named_scope(tm.exchange_axis_span("x")):
            slab = q[-2:-1]
            with jax.named_scope(tm.SPAN_EXCHANGE_X_LOW):
                recv = lax.ppermute(slab, "x", fwd)
            return q.at[0:1].set(recv)

    fn = shard_map(body, mesh=mesh, in_specs=(P("x"),), out_specs=P("x"))
    q = jnp.zeros((32, 16), jnp.float32)
    return analysis.trace_artifact(
        fn, q, label="fixture:exchange-scope-clean", kind="exchange", n_devices=8
    )
