"""Generic plane-streaming 6-neighbor-mean kernel (arbitrary shell widths).

Same ring-buffer structure as ops/jacobi_pallas.py (one HBM read + one write
per x-plane) generalized to a shell of any per-axis width: compute planes
``[lo.x, X - hi.x)`` with the in-plane window ``[lo.y, Y - hi.y) x
[lo.z, Z - hi.z)``; every other cell (the shell) passes through unchanged.
Used by the Astaroth proxy (radius-3 shell, distance-1 reads —
astaroth_sim.cu:65-83 via a 3-wide halo it exchanges but does not read, like
the real Astaroth's communication volume model).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from stencil_tpu.core.dim3 import Dim3
from stencil_tpu.telemetry import names as tm


def mean6_shell_wavefront_step(
    raw: jax.Array,  # (X+2s, Y+2s, Z+2s), uniform s-wide FILLED shell
    m: int,  # levels to advance, <= the shell width s
    shell_width: int,
    interpret: bool = False,
    f32_accumulate: bool = False,  # bf16-storage variant: upcast at load,
    # f32 level ring + arithmetic, one downcast at the final store
) -> jax.Array:
    """``m`` mean-of-6 levels in ONE pass over an s-shell-carrying shard —
    the Astaroth proxy's temporal wavefront (opt-in ``schedule="wavefront"``).

    The proxy exchanges a radius-3 shell but reads distance 1
    (astaroth_sim.cu:65-83), so the shell ALREADY holds enough boundary data
    for 3 levels of the stencil: validity shrinks one cell per level exactly
    as in ``jacobi_shell_wavefront_step`` (see its docstring for the
    contamination argument), and each HBM plane is read and written once per
    ``m`` iterations instead of once per iteration.  Shell cells land
    garbage/stale; the caller re-exchanges before the next pass and marks
    the shell stale for readback.  Summation order matches
    ``mean6_plane_step`` (x-1, x+1, y-1, y+1, z-1, z+1)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from stencil_tpu.ops.jacobi_pallas import (
        _level_sum,
        _make_roll,
        _tpu_compiler_params,
    )

    Xr, Yr, Zr = raw.shape
    assert 1 <= m <= shell_width and 2 * shell_width < min(Xr, Yr, Zr), (
        m, shell_width, raw.shape,
    )
    roll = _make_roll(interpret)
    acc_dtype = jnp.float32 if f32_accumulate else raw.dtype

    def kernel(in_ref, out_ref, ring):
        # ring[s] holds the two most recent level-s planes (level 0 = input)
        i = pl.program_id(0)
        vals = in_ref[0].astype(acc_dtype)  # level-0 raw plane i
        for s in range(1, m + 1):
            prev = ring[s - 1, i % 2]  # level-(s-1) plane i-s-1
            cent = ring[s - 1, (i + 1) % 2]  # level-(s-1) plane i-s
            ring[s - 1, i % 2] = vals  # push plane i-s+1 (after prev read)
            val = _level_sum(roll, prev, vals, cent) / 6.0
            vals = val.astype(acc_dtype)
        # level-m plane i-m; valid for the interior (the one f32_accumulate
        # downcast)
        out_ref[0] = vals.astype(raw.dtype)

    return pl.pallas_call(
        kernel,
        name=tm.KERNEL_MEAN6_SHELL_WAVEFRONT,
        grid=(Xr,),
        in_specs=[pl.BlockSpec((1, Yr, Zr), lambda i: (i, 0, 0))],
        out_specs=pl.BlockSpec((1, Yr, Zr), lambda i: (jnp.maximum(i - m, 0), 0, 0)),
        out_shape=jax.ShapeDtypeStruct((Xr, Yr, Zr), raw.dtype),
        # write of plane i-m trails the fetch of plane i+1: in-place safe
        input_output_aliases={0: 0},
        scratch_shapes=[pltpu.VMEM((m, 2, Yr, Zr), acc_dtype)],
        interpret=interpret,
        **_tpu_compiler_params(interpret),
    )(raw)


def mean6_plane_step(
    block: jax.Array, lo: Dim3, hi: Dim3, interpret: bool = False,
    f32_accumulate: bool = False,
) -> jax.Array:
    """One mean-of-6-face-neighbors iteration over a shell-carrying block.

    ``f32_accumulate`` is the bf16-storage variant: the mean is computed at
    f32 and rounded once at the interior store (pass-through shell planes
    keep their storage bytes untouched)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from stencil_tpu.ops.jacobi_pallas import _tpu_compiler_params

    X, Y, Z = block.shape
    # every side needs >= 1 shell cell: the distance-1 reads and the
    # plane-replay at the grid edges assume neighbors exist in-allocation
    assert lo.all_ge(1) and hi.all_ge(1), (lo, hi)
    y0, y1 = lo.y, Y - hi.y
    z0, z1 = lo.z, Z - hi.z
    up = (lambda v: v.astype(jnp.float32)) if f32_accumulate else (lambda v: v)

    def kernel(in_ref, out_ref, ring):
        i = pl.program_id(0)
        cur = in_ref[0]

        @pl.when(i == 0)
        def _():
            out_ref[0] = cur  # first plane passes through

        @pl.when(jnp.logical_and(i >= 1, i <= X))
        def _():
            cent = ring[(i + 1) % 2]  # plane i-1

            in_window = jnp.logical_and(i - 1 >= lo.x, i - 1 <= X - hi.x - 1)

            @pl.when(in_window)
            def _():
                prev = ring[i % 2]  # plane i-2
                mean = (
                    up(prev[y0:y1, z0:z1])
                    + up(cur[y0:y1, z0:z1])
                    + up(cent[y0 - 1 : y1 - 1, z0:z1])
                    + up(cent[y0 + 1 : y1 + 1, z0:z1])
                    + up(cent[y0:y1, z0 - 1 : z1 - 1])
                    + up(cent[y0:y1, z0 + 1 : z1 + 1])
                ) / 6.0
                out_ref[0] = cent  # keep the y/z shell
                out_ref[0, y0:y1, z0:z1] = mean.astype(cur.dtype)

            @pl.when(jnp.logical_not(in_window))
            def _():
                out_ref[0] = cent  # shell plane passes through

        @pl.when(i <= X - 1)
        def _():
            ring[i % 2] = cur

    return pl.pallas_call(
        kernel,
        name=tm.KERNEL_MEAN6_PLANE,
        grid=(X + 1,),
        in_specs=[pl.BlockSpec((1, Y, Z), lambda i: (jnp.minimum(i, X - 1), 0, 0))],
        out_specs=pl.BlockSpec((1, Y, Z), lambda i: (jnp.clip(i - 1, 0, X - 1), 0, 0)),
        out_shape=jax.ShapeDtypeStruct((X, Y, Z), block.dtype),
        scratch_shapes=[pltpu.VMEM((2, Y, Z), block.dtype)],
        interpret=interpret,
        **_tpu_compiler_params(interpret),
    )(block)
