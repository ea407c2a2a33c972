# analysis-fixture: contract=kernel-race expect=fire
"""A genuine grid write race: the grid's only dim is declared ``parallel``
(``dimension_semantics``), yet the output index map ``i // 2`` lands two
parallel grid points on the same output block while each reads a DIFFERENT
input plane — the writes are not provably identical, and with parallel
semantics the execution order (hence the surviving write) is unspecified.
The same map on a sequential grid is the sanctioned last-write-wins replay
(see kernel_race_clean.py)."""

import jax
import jax.experimental.pallas as pl
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

from stencil_tpu import analysis


def _copy_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...]


def build():
    def step(b):
        return pl.pallas_call(
            _copy_kernel,
            grid=(4,),
            in_specs=[pl.BlockSpec((1, 8, 128), lambda i: (i, 0, 0))],
            out_specs=pl.BlockSpec((1, 8, 128), lambda i: (i // 2, 0, 0)),
            out_shape=jax.ShapeDtypeStruct((2, 8, 128), jnp.float32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel",)
            ),
            interpret=True,
        )(b)

    b = jax.ShapeDtypeStruct((4, 8, 128), jnp.float32)
    return analysis.trace_artifact(
        step, b, label="fixture:kernel-race-fire", kind="fn"
    )
