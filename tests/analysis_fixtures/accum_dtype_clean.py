# analysis-fixture: contract=accum-dtype expect=clean
"""The sanctioned contraction: bf16 storage, explicit f32 accumulation
(the bf16-storage / f32-accumulate contract)."""

import jax
import jax.experimental.pallas as pl
import jax.numpy as jnp

from stencil_tpu import analysis


def _band_kernel(a_ref, b_ref, o_ref):
    o_ref[...] = jnp.dot(
        a_ref[...], b_ref[...], preferred_element_type=jnp.float32
    )


def build():
    def step(a, b):
        return pl.pallas_call(
            _band_kernel,
            out_shape=jax.ShapeDtypeStruct((64, 64), jnp.float32),
            interpret=True,
        )(a, b)

    a = jax.ShapeDtypeStruct((64, 64), jnp.bfloat16)
    b = jax.ShapeDtypeStruct((64, 64), jnp.bfloat16)
    return analysis.trace_artifact(
        step, a, b, label="fixture:accum-dtype-clean", kind="fn"
    )
