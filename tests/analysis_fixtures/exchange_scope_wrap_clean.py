# analysis-fixture: contract=exchange-scope expect=clean
"""An axis the mesh does not split: no ppermute anywhere, the halo is filled
by one self-wrap kernel under ``exchange.z`` / ``exchange.z.wrap`` — the shape
of every one-chip exchange."""

import jax
import jax.numpy as jnp

from stencil_tpu import analysis
from stencil_tpu.ops.halo_blend import wrap_halo
from stencil_tpu.telemetry import names as tm


def build():
    def fn(q):
        with jax.named_scope(tm.exchange_axis_span("z")):
            with jax.named_scope(tm.exchange_wrap_span("z")):
                return wrap_halo(q, 2, 1, 1, 14, interpret=True)

    q = jnp.zeros((4, 8, 16), jnp.float32)
    return analysis.trace_artifact(
        fn, q, label="fixture:exchange-scope-wrap-clean", kind="exchange", n_devices=1
    )
