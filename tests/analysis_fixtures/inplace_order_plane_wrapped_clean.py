# analysis-fixture: contract=inplace-order expect=clean
"""The real ``stream_plane_pass`` at ``x_radius = 4`` making the y and z halo
fills itself (ISSUE 34, ``wrap_fills``): ``a`` is read off-centre on every
axis and keeps its ring, ``b`` along y and z alone and is fetched lagged; both
are halo readers, both are written in place.  The fills touch the pipeline's
own input buffer in VMEM and nothing in HBM, so the block maps -- and the
order ``inplace-order`` proves from them -- are the ones the pass had: the
patched centre plane is flushed ``r`` planes behind the fetch, as before."""

import jax
import jax.numpy as jnp

from stencil_tpu import analysis
from stencil_tpu.core.dim3 import Dim3
from stencil_tpu.ops.stream_pass import stream_plane_pass

R = 4
N = 8


def _kernel(views, info):
    a, b = views["a"], views["b"]
    return {
        "a": 0.5 * a.center() + (a.sh(R, 0, 0) - a.sh(-R, 0, 0)) + b.sh(0, R, 0),
        "b": 0.5 * b.center() + (b.sh(0, 0, -R) - a.sh(0, 1 - R, 0)),
    }


def build():
    n = N + 2 * R
    fills = tuple((axis, d, s, R) for axis in (1, 2) for d, s in ((0, N), (R + N, R)))

    def step(origin, a, b):
        return stream_plane_pass(
            _kernel, ["a", "b"], [a, b], Dim3(R, R, R), Dim3(R, R, R), R,
            origin, Dim3(N, N, N), alias=True, interpret=True,
            halo_readers=("a", "b"), writers=("a", "b"), rings=("a",), wrap_fills=fills,
        )

    blk = jax.ShapeDtypeStruct((n, n, n), jnp.float32)
    origin = jax.ShapeDtypeStruct((3,), jnp.int32)
    return analysis.trace_artifact(
        step, origin, blk, blk, label="fixture:inplace-order-plane-wrapped", kind="fn"
    )
