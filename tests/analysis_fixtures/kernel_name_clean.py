# analysis-fixture: contract=kernel-name expect=clean
"""The same call under a registered kernel name."""

import jax
import jax.experimental.pallas as pl
import jax.numpy as jnp

from stencil_tpu import analysis
from stencil_tpu.telemetry import names as tm


def _copy_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...]


def build():
    def step(b):
        return pl.pallas_call(
            _copy_kernel,
            name=tm.KERNEL_PACK_SLAB,
            grid=(4,),
            in_specs=[pl.BlockSpec((1, 8, 128), lambda i: (i, 0, 0))],
            out_specs=pl.BlockSpec((1, 8, 128), lambda i: (i, 0, 0)),
            out_shape=jax.ShapeDtypeStruct((4, 8, 128), jnp.float32),
            interpret=True,
        )(b)

    b = jax.ShapeDtypeStruct((4, 8, 128), jnp.float32)
    return analysis.trace_artifact(
        step, b, label="fixture:kernel-name-clean", kind="fn"
    )
