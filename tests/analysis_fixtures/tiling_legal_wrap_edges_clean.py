# analysis-fixture: contract=tiling-legal expect=clean
"""The real ``stream_wrap_pass`` in its ``raw_in`` edge form (ISSUE 52): the
domain's raw ``(10, 10, 130)`` block streams through ``(1, 16, 256)`` boundary
blocks -- wider than the array in BOTH minor dims, whole (8, 128) tiles --, the
``[1 : 9, 1 : 129]`` window of each plane brought to the aligned corner by two
rotates on the whole-tile plane and cut there; the levels work on the bare
``(8, 128)`` plane."""

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
    def step(origin, raw):
        return stream_wrap_pass(
            _kernel, ["q"], [raw], K, origin, N, interpret=True,
            interior=(LO, N), raw_in=True,
        )

    raw = jax.ShapeDtypeStruct(tuple(N + LO + LO), jnp.float32)
    origin = jax.ShapeDtypeStruct((3,), jnp.int32)
    return analysis.trace_artifact(
        step, origin, raw, label="fixture:tiling-legal-wrap-edges", kind="fn",
    )
