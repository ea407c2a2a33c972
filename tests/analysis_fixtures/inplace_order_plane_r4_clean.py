# analysis-fixture: contract=inplace-order expect=clean
"""The real ``stream_plane_pass`` at ``x_radius = 4``, two quantities, output
``q`` aliased onto raw ``q``: in plane ``min(i, X-1)``, out plane
``clip(i - 4, 0, X-1)`` on the sequential grid ``(X + 4,)`` — every flush
trails every later fetch by at least 4 planes, so in place is safe."""

import jax
import jax.numpy as jnp

from stencil_tpu import analysis
from stencil_tpu.core.dim3 import Dim3
from stencil_tpu.ops.stream_pass import stream_plane_pass

R = 4


def _kernel(views, info):
    a = views["a"]
    return {"a": 0.5 * (a.sh(R, 0, 0) + a.sh(-R, 0, 0)) + views["b"].center()}


def build():
    n = 8 + 2 * R

    def step(origin, a, b):
        return stream_plane_pass(
            _kernel, ["a", "b"], [a, b], Dim3(R, R, R), Dim3(R, R, R), R,
            origin, Dim3(8, 8, 8), alias=True, interpret=True,
        )

    blk = jax.ShapeDtypeStruct((n, n, n), jnp.float32)
    origin = jax.ShapeDtypeStruct((3,), jnp.int32)
    return analysis.trace_artifact(
        step, origin, blk, blk,
        label="fixture:inplace-order-plane-r4", kind="fn",
    )
