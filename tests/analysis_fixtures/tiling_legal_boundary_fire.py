# analysis-fixture: contract=tiling-legal expect=fire
"""A boundary block that is NOT whole lane tiles: the ``(4, 16, 200)`` array
of the clean twin through ``(1, 16, 250)`` windows.  250 is neither the
array's 200 nor a multiple of 128, and on hardware the lowering refuses it::

    The Pallas TPU lowering currently requires that the last two dimensions
    of your block shape are divisible by 8 and 128 respectively, or be equal
    to the respective dimensions of the overall array.

(the wording ``tests/test_compiled_tpu.py`` pins for the pack kernels).
Interpret mode pads the array to the block and runs it; only the verifier
can refuse it before a compile."""

import jax
import jax.experimental.pallas as pl
import jax.numpy as jnp

from stencil_tpu import analysis

ARRAY, BLOCK = (4, 16, 200), (1, 16, 250)


def _copy_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...]


def build():
    def step(b):
        return pl.pallas_call(
            _copy_kernel,
            grid=(ARRAY[0],),
            in_specs=[pl.BlockSpec(BLOCK, lambda i: (i, 0, 0))],
            out_specs=pl.BlockSpec(BLOCK, lambda i: (i, 0, 0)),
            out_shape=jax.ShapeDtypeStruct(ARRAY, jnp.float32),
            interpret=True,
        )(b)

    b = jax.ShapeDtypeStruct(ARRAY, jnp.float32)
    return analysis.trace_artifact(
        step, b, label="fixture:tiling-legal-boundary-fire", kind="fn"
    )
