# analysis-fixture: contract=exchange-structure expect=clean
"""The sanctioned fused exchange: both quantities stack into ONE buffer per
direction, ≤6 face permutes total regardless of field count -- and, the x and
y sweeps flying jointly, one smaller corner relay behind each y face, of cells
the x permutes received."""

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
        # the pair's four faces, every one cut from the blocks as they entered
        got = {}
        for name, perm in (
            (tm.SPAN_EXCHANGE_X_LOW, fwd),
            (tm.SPAN_EXCHANGE_X_HIGH, rev),
            (tm.SPAN_EXCHANGE_Y_LOW, fwd),
            (tm.SPAN_EXCHANGE_Y_HIGH, rev),
        ):
            with jax.named_scope(name):
                got[name] = lax.ppermute(fused, "x", perm)
        # the corner relays: a strip of what x RECEIVED, behind each y face
        corners = jnp.concatenate(
            [got[tm.SPAN_EXCHANGE_X_LOW][:, :2], got[tm.SPAN_EXCHANGE_X_HIGH][:, :2]], axis=1
        )
        for name, perm in ((tm.SPAN_EXCHANGE_Y_LOW, fwd), (tm.SPAN_EXCHANGE_Y_HIGH, rev)):
            with jax.named_scope(name):
                got[name] = got[name].at[:, :4].set(lax.ppermute(corners, "x", perm))
        fused = sum(got.values())
        for name, perm in ((tm.SPAN_EXCHANGE_Z_LOW, fwd), (tm.SPAN_EXCHANGE_Z_HIGH, rev)):
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
