# analysis-fixture: contract=inplace-order expect=clean
"""The real ``stream_plane_pass`` at ``x_radius = 4`` with quantities that hold
NO ring (ISSUE 33): ``a`` is read off-centre along x and keeps the ``2r``-deep
ring (in plane ``min(i, X-1)``); ``b`` and ``c`` are read at ``dx == 0`` only
and are FETCHED LAGGED, at the output plane ``clip(i - 4, 0, X-1)``.  ``b``
and ``c`` are both written in place: a lagged input's plane ``j`` is fetched
before grid step ``j + 4`` and its aliased output's plane ``j`` is flushed
after it, and no later fetch goes back -- the order elastic's passes rest on
(``vx`` updated from the x-difference of ``txx``)."""

import jax
import jax.numpy as jnp

from stencil_tpu import analysis
from stencil_tpu.core.dim3 import Dim3
from stencil_tpu.ops.stream_pass import stream_plane_pass

R = 4


def _kernel(views, info):
    a, b, c = views["a"], views["b"], views["c"]
    dx = a.sh(R, 0, 0) - a.sh(1 - R, 0, 0)
    return {"b": b.center() + 0.5 * dx * c.sh(0, R, 0), "c": 0.5 * c.center() + b.sh(0, 0, -R)}


def build():
    n = 8 + 2 * R

    def step(origin, a, b, c):
        return stream_plane_pass(
            _kernel, ["a", "b", "c"], [a, b, c], Dim3(R, R, R), Dim3(R, R, R), R,
            origin, Dim3(8, 8, 8), alias=True, interpret=True,
            writers=("b", "c"), rings=("a",),
        )

    blk = jax.ShapeDtypeStruct((n, n, n), jnp.float32)
    origin = jax.ShapeDtypeStruct((3,), jnp.int32)
    return analysis.trace_artifact(
        step, origin, blk, blk, blk,
        label="fixture:inplace-order-plane-lagged", kind="fn",
    )
