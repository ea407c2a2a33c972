# analysis-fixture: contract=kernel-coverage expect=clean
"""The real ``stream_wrap_pass`` in its ``raw_out`` edge form (ISSUE 52): the
level-``k`` planes land in the domain's raw ``(Xr, Yr, Zr)`` block through one
whole-tile boundary block a plane at ``lo.x + (i - k) % X``.  The x halo
planes ``[0, lo.x)`` and ``[lo.x + X, Xr)`` are visited by no grid step: they
are carried in through ``input_output_aliases`` from the raw operand, which
the kernel holds in ``pl.ANY`` and never reads -- the coverage story of the
aliased wavefront ring, and ``tiling-legal`` / ``inplace-order`` stay quiet on
it too (``tests/test_analysis.py`` holds all three)."""

import jax
import jax.numpy as jnp

from stencil_tpu import analysis
from stencil_tpu.core.dim3 import Dim3
from stencil_tpu.ops.stream_pass import stream_wrap_pass

K, LO, N = 2, Dim3(1, 1, 1), Dim3(8, 8, 128)


def _kernel(views, info):
    q = views["q"]
    return {"q": 0.5 * (q.sh(1, 0, -1) + q.sh(-1, 1, 0))}


def build():
    def step(origin, bare, raw):
        return stream_wrap_pass(
            _kernel, ["q"], [bare], K, origin, N, interpret=True,
            interior=(LO, N), raw_out=[raw],
        )

    bare = jax.ShapeDtypeStruct(tuple(N), jnp.float32)
    raw = jax.ShapeDtypeStruct(tuple(N + LO + LO), jnp.float32)
    origin = jax.ShapeDtypeStruct((3,), jnp.int32)
    return analysis.trace_artifact(
        step, origin, bare, raw, label="fixture:kernel-coverage-wrap-edges", kind="fn",
    )
