# analysis-fixture: contract=inplace-order expect=fire
"""A synthetic renaming pass gone wrong: two quantities in, ONE output that
aliases the OTHER quantity's operand (``{1: 0}``: input ``b``), as a rename
does -- but ``b`` is fetched LAGGED, at plane ``max(i - 2, 0)``, while the
output is written at plane ``i``, with no lag.  Plane 1 of the shared buffer
is flushed after step 1 and fetched as ``b`` at step 3: in place the kernel
reads ``a``'s new value where it meant the old ``b``.  The contract judges
the pair the call carries, whichever operand the output lands on."""

import jax
import jax.experimental.pallas as pl
import jax.numpy as jnp

from stencil_tpu import analysis


def _leapfrog_kernel(a_ref, b_ref, o_ref):
    o_ref[...] = 2.0 * a_ref[...] - b_ref[...]


def build():
    def step(a, b):
        return pl.pallas_call(
            _leapfrog_kernel,
            grid=(8,),
            in_specs=[
                pl.BlockSpec((1, 8, 128), lambda i: (i, 0, 0)),
                pl.BlockSpec((1, 8, 128), lambda i: (jnp.maximum(i - 2, 0), 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, 8, 128), lambda i: (i, 0, 0)),
            out_shape=jax.ShapeDtypeStruct((8, 8, 128), jnp.float32),
            input_output_aliases={1: 0},
            interpret=True,
        )(a, b)

    blk = jax.ShapeDtypeStruct((8, 8, 128), jnp.float32)
    return analysis.trace_artifact(
        step, blk, blk, label="fixture:inplace-order-renamed-fire", kind="fn"
    )
