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
    compute_unit: str = "vpu",  # "mxu" = one banded in-plane contraction
    # per axis on the matrix unit (ops/jacobi_pallas.band_matrix); ≤1
    # ulp/level vs the "vpu" roll+add chain; "mxu_band" = its blocked
    # (2r+1)-band form (ops/jacobi_pallas.band_wide_tile)
    f32_accumulate: bool = False,  # bf16-storage variant: upcast at load,
    # f32 level ring + arithmetic, one downcast at the final store
    mxu_input: str = "f32",  # MXU operand precision (jacobi_wrap_step)
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
        _check_compute_unit,
        _make_level_sum,
        _make_roll,
        _tpu_compiler_params,
        band_operands,
        make_plane_nbr_sum,
        plane_band_unit,
        unit_uses_mxu,
    )

    Xr, Yr, Zr = raw.shape
    assert 1 <= m <= shell_width and 2 * shell_width < min(Xr, Yr, Zr), (
        m, shell_width, raw.shape,
    )
    roll = _make_roll(interpret)
    acc_dtype = jnp.float32 if f32_accumulate else raw.dtype
    _check_compute_unit(compute_unit, acc_dtype)
    mxu = unit_uses_mxu(compute_unit)
    if mxu:
        compute_unit = plane_band_unit(compute_unit, Yr, Zr, where="mean6-wavefront")
    nbr_sum = (
        make_plane_nbr_sum(Yr, Zr, compute_unit, mxu_input) if mxu else None
    )
    level_sum = _make_level_sum(roll, compute_unit, nbr_sum)

    def kernel(in_ref, *rest):
        if mxu:
            by_ref, bz_ref, out_ref, ring = rest
            by, bz = by_ref[...], bz_ref[...]
        else:
            out_ref, ring = rest
            by = bz = None
        # ring[s] holds the two most recent level-s planes (level 0 = input)
        i = pl.program_id(0)
        vals = in_ref[0].astype(acc_dtype)  # level-0 raw plane i
        for s in range(1, m + 1):
            prev = ring[s - 1, i % 2]  # level-(s-1) plane i-s-1
            cent = ring[s - 1, (i + 1) % 2]  # level-(s-1) plane i-s
            ring[s - 1, i % 2] = vals  # push plane i-s+1 (after prev read)
            val = level_sum(prev, vals, cent, by, bz) / 6.0
            vals = val.astype(acc_dtype)
        # level-m plane i-m; valid for the interior (the one f32_accumulate
        # downcast)
        out_ref[0] = vals.astype(raw.dtype)

    in_specs = [pl.BlockSpec((1, Yr, Zr), lambda i: (i, 0, 0))]
    args = [raw]
    if mxu:
        b_args, b_specs = band_operands(Yr, Zr, compute_unit, mxu_input)
        in_specs += b_specs
        args += b_args
    return pl.pallas_call(
        kernel,
        name=tm.KERNEL_MEAN6_SHELL_WAVEFRONT,
        grid=(Xr,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, Yr, Zr), lambda i: (jnp.maximum(i - m, 0), 0, 0)),
        out_shape=jax.ShapeDtypeStruct((Xr, Yr, Zr), raw.dtype),
        # write of plane i-m trails the fetch of plane i+1: in-place safe
        input_output_aliases={0: 0},
        scratch_shapes=[pltpu.VMEM((m, 2, Yr, Zr), acc_dtype)],
        interpret=interpret,
        **_tpu_compiler_params(interpret),
    )(*args)


def mean6_plane_step(
    block: jax.Array, lo: Dim3, hi: Dim3, interpret: bool = False,
    compute_unit: str = "vpu", f32_accumulate: bool = False,
    mxu_input: str = "f32",
) -> jax.Array:
    """One mean-of-6-face-neighbors iteration over a shell-carrying block.

    ``compute_unit="mxu"`` computes the in-plane neighbor pair sums as one
    banded contraction per axis (``band_matrix``; ``"mxu_band"`` runs the
    blocked form); the interior window ``[y0, y1) x [z0, z1)`` sits at
    least one cell inside the plane, so the circulant wrap rows/columns
    never enter the sliced result and the contraction is exactly the
    shifted-slice sum up to summation order (≤1 ulp).  ``f32_accumulate``
    is the bf16-storage variant: the mean is computed at f32 and rounded
    once at the interior store (pass-through shell planes keep their
    storage bytes untouched)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from stencil_tpu.ops.jacobi_pallas import (
        _check_compute_unit,
        _tpu_compiler_params,
        band_operands,
        make_plane_nbr_sum,
        plane_band_unit,
        unit_uses_mxu,
    )

    X, Y, Z = block.shape
    # every side needs >= 1 shell cell: the distance-1 reads and the
    # plane-replay at the grid edges assume neighbors exist in-allocation
    assert lo.all_ge(1) and hi.all_ge(1), (lo, hi)
    y0, y1 = lo.y, Y - hi.y
    z0, z1 = lo.z, Z - hi.z
    acc_dtype = jnp.float32 if f32_accumulate else block.dtype
    _check_compute_unit(compute_unit, acc_dtype)
    mxu = unit_uses_mxu(compute_unit)
    if mxu:
        compute_unit = plane_band_unit(compute_unit, Y, Z, where="mean6-plane")
    nbr_sum = (
        make_plane_nbr_sum(Y, Z, compute_unit, mxu_input) if mxu else None
    )
    up = (lambda v: v.astype(jnp.float32)) if f32_accumulate else (lambda v: v)

    def kernel(in_ref, *rest):
        if mxu:
            by_ref, bz_ref, out_ref, ring = rest
        else:
            out_ref, ring = rest
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
                if mxu:
                    c = up(cent)
                    nbr = nbr_sum(c, by_ref[...], bz_ref[...])
                    mean = (
                        up(prev[y0:y1, z0:z1])
                        + up(cur[y0:y1, z0:z1])
                        + nbr[y0:y1, z0:z1]
                    ) / 6.0
                else:
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

    in_specs = [pl.BlockSpec((1, Y, Z), lambda i: (jnp.minimum(i, X - 1), 0, 0))]
    args = [block]
    if mxu:
        b_args, b_specs = band_operands(Y, Z, compute_unit, mxu_input)
        in_specs += b_specs
        args += b_args
    return pl.pallas_call(
        kernel,
        name=tm.KERNEL_MEAN6_PLANE,
        grid=(X + 1,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, Y, Z), lambda i: (jnp.clip(i - 1, 0, X - 1), 0, 0)),
        out_shape=jax.ShapeDtypeStruct((X, Y, Z), block.dtype),
        scratch_shapes=[pltpu.VMEM((2, Y, Z), block.dtype)],
        interpret=interpret,
        **_tpu_compiler_params(interpret),
    )(*args)
