# analysis-fixture: contract=exchange-structure expect=fire
"""A broken exchange: per-quantity ppermutes (two messages per direction
scope — the fusion packer.cuh:52-69 collapses is gone; the second is as large
as the first, so it is no corner relay of a joint sweep) and more than six
face permutes in one traced exchange."""

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
        out0, out1 = q0, q1
        for name, perm in (
            (tm.SPAN_EXCHANGE_X_LOW, fwd),
            (tm.SPAN_EXCHANGE_X_HIGH, rev),
            (tm.SPAN_EXCHANGE_Y_LOW, fwd),
            (tm.SPAN_EXCHANGE_Y_HIGH, rev),
        ):
            with jax.named_scope(name):
                # BROKEN: one permute PER QUANTITY per direction — message
                # count scales with the field count
                out0 = lax.ppermute(out0, "x", perm)
                out1 = lax.ppermute(out1, "x", perm)
        return out0, out1

    fn = shard_map(
        body, mesh=mesh, in_specs=(P("x"), P("x")), out_specs=(P("x"), P("x"))
    )
    q = jnp.zeros((8, 16), jnp.float32)
    return analysis.trace_artifact(
        fn,
        q,
        q,
        label="fixture:exchange-structure-fire",
        kind="exchange",
        axes={"exchange_route": "direct"},
        n_devices=8,
    )
