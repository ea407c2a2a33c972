# analysis-fixture: contract=inplace-order expect=clean
"""The real ``stream_wavefront_pass`` at depth ``m = 3``, aliased: in plane
``i``, out plane ``max(i - 3, 0)`` on the sequential grid ``(Xr,)`` — the
write trails the read by ``m`` planes (what ``ops/stream.py`` used to say in
a comment), so the 8-field astaroth passes may run in place."""

import jax
import jax.numpy as jnp

from stencil_tpu import analysis
from stencil_tpu.core.dim3 import Dim3
from stencil_tpu.ops.stream_pass import stream_wavefront_pass

M = 3


def _kernel(views, info):
    q = views["q"]
    return {"q": 0.5 * (q.sh(1, 0, 0) + q.sh(-1, 0, 0))}


def build():
    n = 8 + 2 * M

    def step(origin, q):
        outs, _ = stream_wavefront_pass(
            _kernel, ["q"], [q], M, M, origin, Dim3(8, 8, 8),
            alias=True, interpret=True,
        )
        return outs

    blk = jax.ShapeDtypeStruct((n, n, n), jnp.float32)
    origin = jax.ShapeDtypeStruct((3,), jnp.int32)
    return analysis.trace_artifact(
        step, origin, blk,
        label="fixture:inplace-order-wavefront-m3", kind="fn",
    )
