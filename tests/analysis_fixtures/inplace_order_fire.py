# analysis-fixture: contract=inplace-order expect=fire
"""A synthetic streaming pass whose OUT index leads its IN index: it reads
plane ``max(i - 1, 0)`` and writes plane ``i`` of the buffer it aliases.
Plane 1 is flushed after step 1 and fetched as input at step 2: in place the
kernel reads its own result (a one-plane smear down x), while CPU interpret
mode, which runs the aliased call functionally, returns the intended shift."""

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
            grid=(8,),
            in_specs=[
                pl.BlockSpec((1, 8, 128), lambda i: (jnp.maximum(i - 1, 0), 0, 0))
            ],
            out_specs=pl.BlockSpec((1, 8, 128), lambda i: (i, 0, 0)),
            out_shape=jax.ShapeDtypeStruct((8, 8, 128), jnp.float32),
            input_output_aliases={0: 0},
            interpret=True,
        )(b)

    b = jax.ShapeDtypeStruct((8, 8, 128), jnp.float32)
    return analysis.trace_artifact(
        step, b, label="fixture:inplace-order-fire", kind="fn"
    )
