# analysis-fixture: contract=tiling-legal expect=clean
"""The sanctioned BOUNDARY block (ISSUE 41): a block wider than the array
in the minor dimension, whole (8, 128) tiles — the form in which the z-slab
wavefront pass streams the domain's raw block: a ``(4, 16, 200)`` array
through ``(1, 16, 256)`` windows, in place, the dead lanes [200, 256) zeroed
in VMEM before the one lane rotate.  The DMA moves 200 lanes in and out; the
rest of the window lives in VMEM only.  ``kernel-coverage`` and
``inplace-order`` stay quiet on it too (``tests/test_analysis.py`` holds all
three): one such block IS the whole minor dim, counted on the array's
extent."""

import jax
import jax.experimental.pallas as pl
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

from stencil_tpu import analysis

ARRAY, BLOCK = (4, 16, 200), (1, 16, 256)


def _masked_rot_kernel(x_ref, o_ref):
    lane = jax.lax.broadcasted_iota(jnp.int32, BLOCK[1:], 1)
    v = jnp.where(lane < ARRAY[2], x_ref[0], 0.0)
    o_ref[0] = pltpu.roll(v, 3, 1)


def build(block=BLOCK):
    def step(b):
        return pl.pallas_call(
            _masked_rot_kernel,
            grid=(ARRAY[0],),
            in_specs=[pl.BlockSpec(block, lambda i: (i, 0, 0))],
            out_specs=pl.BlockSpec(block, lambda i: (jnp.maximum(i - 1, 0), 0, 0)),
            out_shape=jax.ShapeDtypeStruct(ARRAY, jnp.float32),
            input_output_aliases={0: 0},
            interpret=True,
        )(b)

    b = jax.ShapeDtypeStruct(ARRAY, jnp.float32)
    return analysis.trace_artifact(
        step, b, label="fixture:tiling-legal-boundary-clean", kind="fn"
    )
