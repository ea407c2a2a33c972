# analysis-fixture: contract=exchange-scope expect=fire
"""Step work billed to the exchange: equations under ``exchange.z`` in a
program that fills no halo — no ppermute and no self-wrap kernel."""

import jax
import jax.numpy as jnp

from stencil_tpu import analysis
from stencil_tpu.telemetry import names as tm


def build():
    def fn(q):
        with jax.named_scope(tm.exchange_axis_span("z")):  # BROKEN: not an exchange
            return q * 2.0 + 1.0

    q = jnp.zeros((4, 8, 16), jnp.float32)
    return analysis.trace_artifact(
        fn, q, label="fixture:exchange-scope-wrap-fire", kind="step", n_devices=1
    )
