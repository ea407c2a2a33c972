# analysis-fixture: contract=inplace-order expect=clean
"""The real ``stream_plane_pass`` at ``x_radius = 2`` with FEWER outputs than
inputs: three quantities in, ``writers=("b",)`` alone out, aliased onto raw
``b`` -- the alias pair is operand ``1 + 1`` (operand 0 is ``origin``) to
output 0, not ``q -> q``.  ``a`` and ``c`` are inputs only: nothing is
flushed over them, so they have no order to keep; ``b`` keeps the pass's own
(in plane ``min(i, X-1)``, out plane ``clip(i - 2, 0, X-1)``)."""

import jax
import jax.numpy as jnp

from stencil_tpu import analysis
from stencil_tpu.core.dim3 import Dim3
from stencil_tpu.ops.stream_pass import stream_plane_pass

R = 2


def _kernel(views, info):
    b = views["b"]
    return {
        "b": 0.5 * (b.sh(R, 0, 0) + b.sh(-R, 0, 0)) * views["a"].center()
        + views["c"].sh(0, R, 0)
    }


def build():
    n = 8 + 2 * R

    def step(origin, a, b, c):
        return stream_plane_pass(
            _kernel, ["a", "b", "c"], [a, b, c], Dim3(R, R, R), Dim3(R, R, R), R,
            origin, Dim3(8, 8, 8), alias=True, interpret=True, writers=("b",),
        )

    blk = jax.ShapeDtypeStruct((n, n, n), jnp.float32)
    origin = jax.ShapeDtypeStruct((3,), jnp.int32)
    return analysis.trace_artifact(
        step, origin, blk, blk, blk,
        label="fixture:inplace-order-plane-writers", kind="fn",
    )
