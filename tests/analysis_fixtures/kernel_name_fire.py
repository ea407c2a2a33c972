# analysis-fixture: contract=kernel-name expect=fire
"""A pallas call with no ``name=``: jax names the kernel after the Python
function (``_copy_kernel``), which no registry entry knows — in a device
trace it is an anonymous custom-call."""

import jax
import jax.experimental.pallas as pl
import jax.numpy as jnp

from stencil_tpu import analysis


def _copy_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...]


def build():
    def step(b):
        return pl.pallas_call(
            _copy_kernel,
            grid=(4,),
            in_specs=[pl.BlockSpec((1, 8, 128), lambda i: (i, 0, 0))],
            out_specs=pl.BlockSpec((1, 8, 128), lambda i: (i, 0, 0)),
            out_shape=jax.ShapeDtypeStruct((4, 8, 128), jnp.float32),
            interpret=True,
        )(b)

    b = jax.ShapeDtypeStruct((4, 8, 128), jnp.float32)
    return analysis.trace_artifact(
        step, b, label="fixture:kernel-name-fire", kind="fn"
    )
