"""Tile-local halo writes — pallas blend kernels for the y/z axes.

Writing a thin received halo slab into the carried shell with
``dynamic_update_slice`` looks cheap, but XLA's layout assignment sees the
y-axis update (a 3-cell sublane sliver) and the z-axis update (a 3-cell lane
sliver) and transposes the WHOLE array to a layout that favors one of them,
paying two full-domain relayout copies per exchange: a radius-3 halo fill of
a 518^3 block measured 9.2 ms where the per-axis work is ~0.45 ms
(scripts/probe6.py; the compiled HLO shows ``{2,0,1}`` internal layouts and a
``copy`` back to ``{2,1,0}``).

These kernels make the write tile-local instead: with
``input_output_aliases`` the block is updated in place, the grid visits ONLY
the (8,128) tiles that contain halo cells, and each visited tile is
read-blended-written in VMEM.  Layout stays the default tiled layout on both
sides (pallas pins it), so the exchange's sweeps stay additive.

Reference analog: the unpack kernels (copy.cuh:26-75) — the reference scatters
received bytes into the shell with a grid-stride loop; GPUs have no tiled
layouts so a plain scatter suffices there.  On TPU the scatter must be
expressed per-tile to avoid the relayout trap; this file is that expression.

The x axis never needs this: x-slabs are whole contiguous planes, which DUS
handles at slab cost in the native layout.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from stencil_tpu.telemetry import names as tm
from stencil_tpu.utils.config import pallas_interpret


def enabled() -> bool:
    """Use the blend kernels for y/z halo writes?  Auto: on for TPU only —
    the relayout trap these kernels dodge is a property of TPU tiled layouts,
    and the tile geometry below is TPU's; any other backend (cpu, gpu) takes
    the plain-DUS path.  Env
    override ``STENCIL_HALO_BLEND=0|1`` forces either path (tests force 1
    with interpret mode to pin blend semantics against DUS)."""
    from stencil_tpu.utils.config import env_choice

    env = env_choice("STENCIL_HALO_BLEND", "auto", ("auto", "0", "1"))
    if env == "0":
        return False
    if env == "1":
        return True
    return not pallas_interpret()


def interpret_mode() -> bool:
    return pallas_interpret()


#: second-to-minor (sublane) tile extent per itemsize, minor is always 128
_SUBLANE = {8: 4, 4: 8, 2: 16, 1: 32}


def supports(dtype) -> bool:
    """Blend kernels know the tile geometry only for these itemsizes; exotic
    dtypes (e.g. complex128, itemsize 16) fall back to the DUS path."""
    return jnp.dtype(dtype).itemsize in _SUBLANE


def vma_check(dtypes, valid_last=None, ndim_extra: int = 0) -> bool:
    """The ``check_vma`` value for a shard_map wrapping the exchange: vma
    validation stays ON (True) whenever the blend kernels — whose pallas
    outputs carry no vma annotation — cannot engage for this configuration
    (mirrors the blend condition in ``halo_exchange_multi``)."""
    if not enabled() or ndim_extra != 0:
        return True
    if not all(supports(dt) for dt in dtypes):
        return True
    # padded y/z axes blend too (blend_slab_dynamic), so valid_last does not
    # re-enable validation
    del valid_last
    return False


def _sublane(dtype) -> int:
    return _SUBLANE[jnp.dtype(dtype).itemsize]


def blend_slab(
    block: jax.Array,
    slab: jax.Array,
    axis: int,
    pos: int,
    interpret: bool = False,
) -> jax.Array:
    """Return ``block`` with ``slab`` written at offset ``pos`` along ``axis``
    (0 = x / whole planes, 1 = y / sublane, 2 = z / lane), touching only the
    tiles (axis 0: planes) that contain the region.  ``block`` is consumed
    (aliased to the output).

    The axis-0 case exists for composition, not layout: an x-plane DUS is
    already contiguous, but expressing the write as an aliased pallas call
    keeps the whole halo-write chain in-place inside loop bodies, where the
    jnp ``.at[].set`` form made XLA materialize full-domain copy+DUS fusions
    (~1.4 ms each at 516^3 — scripts/probe12)."""
    from jax.experimental import pallas as pl

    assert axis in (0, 1, 2), axis
    X, Y, Z = block.shape
    r = slab.shape[axis]
    if axis == 0:
        # the aliased input stays in ANY memory space: the kernel never reads
        # it, so the planes being overwritten are not fetched into VMEM
        def kernel0(in_ref, slab_ref, out_ref):
            del in_ref
            out_ref[...] = slab_ref[...]

        return pl.pallas_call(
            kernel0,
            name=tm.KERNEL_BLEND_PLANES,
            grid=(r,),
            in_specs=[
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec((1, Y, Z), lambda g: (g, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, Y, Z), lambda g: (pos + g, 0, 0)),
            out_shape=jax.ShapeDtypeStruct(block.shape, block.dtype),
            input_output_aliases={0: 0},
            interpret=interpret,
        )(block, slab)
    tile = _sublane(block.dtype) if axis == 1 else 128
    t0 = (pos // tile) * tile  # first touched tile start
    nb = (pos + r - 1) // tile - pos // tile + 1  # tiles spanned
    off = pos - t0  # halo offset inside the first touched tile
    bx = min(8, X)
    gx = -(-X // bx)

    def kernel(in_ref, slab_ref, out_ref):
        g = pl.program_id(1)
        out_ref[...] = in_ref[...]
        for gi in range(nb):
            # static slice bounds per visited tile
            lo = max(off - gi * tile, 0)
            hi = min(off + r - gi * tile, tile)
            s_lo = gi * tile - off + lo  # slab cells already written
            if hi <= lo:
                continue

            def write(gi=gi, lo=lo, hi=hi, s_lo=s_lo):
                if axis == 1:
                    out_ref[:, lo:hi, :] = slab_ref[:, s_lo : s_lo + (hi - lo), :]
                else:
                    out_ref[:, :, lo:hi] = slab_ref[:, :, s_lo : s_lo + (hi - lo)]

            if nb == 1:
                write()
            else:
                pl.when(g == gi)(write)

    if axis == 1:
        blk = (bx, tile, Z)
        sblk = (bx, r, Z)
        index = lambda i, g: (i, t0 // tile + g, 0)
        sindex = lambda i, g: (i, 0, 0)
    else:
        blk = (bx, Y, tile)
        sblk = (bx, Y, r)
        index = lambda i, g: (i, 0, t0 // tile + g)
        sindex = lambda i, g: (i, 0, 0)

    return pl.pallas_call(
        kernel,
        name=tm.KERNEL_BLEND_SLAB,
        grid=(gx, nb),
        in_specs=[
            pl.BlockSpec(blk, index),
            pl.BlockSpec(sblk, sindex),
        ],
        out_specs=pl.BlockSpec(blk, index),
        out_shape=jax.ShapeDtypeStruct(block.shape, block.dtype),
        input_output_aliases={0: 0},
        interpret=interpret,
    )(block, slab)


def blend_slab_dynamic(
    block: jax.Array,
    slab: jax.Array,
    axis: int,
    pos: jax.Array,
    interpret: bool = False,
) -> jax.Array:
    """``blend_slab`` with a TRACED per-shard offset ``pos`` — the padded
    (uneven) axes case, where the +axis halo lands right after the shard's
    own valid cells (``r_lo + n_valid``, differing on the last shard).  The
    offset rides scalar prefetch (``pltpu.PrefetchScalarGridSpec``) so the
    grid's index map picks the touched tiles per shard at run time; inside
    the kernel the slab rows land via iota==row masks (slab widths are a few
    cells, so ``r`` masked selects beat any gather).  Without this, padded
    domains fall back to ``dynamic_update_slice`` slivers — the full-domain
    relayout trap this module exists to dodge (see module docstring).

    The grid visits ``nb`` tiles starting at the one containing ``pos``,
    indexed MODULO ntiles: a width-r region spans at most nb tiles at any
    alignment, and when it spans fewer the surplus visits wrap to distinct
    low tiles where the kernel's row mask matches nothing and the body is an
    identity copy.  (Clamping instead would revisit the last tile, and with
    resident-block semantics the unconditional ``out = in`` copy of the
    revisit would clobber the rows blended by the first visit.)
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    assert axis in (1, 2), axis
    X, Y, Z = block.shape
    r = slab.shape[axis]
    tile = _sublane(block.dtype) if axis == 1 else 128
    ext = (Y, Z)[axis - 1]
    ntiles = -(-ext // tile)
    # worst-case tiles a width-r region can span at any alignment
    nb = min((r - 1) // tile + 2, ntiles)
    bx = min(8, X)
    gx = -(-X // bx)
    pos = jnp.asarray(pos, jnp.int32).reshape((1,))

    def kernel(pos_ref, in_ref, slab_ref, out_ref):
        g = pl.program_id(1)
        p = pos_ref[0]
        t0 = p // tile
        out_ref[...] = in_ref[...]
        # slab row s lands at row p + s - (t0+g)*tile of the UNWRAPPED tile
        # t0+g; out-of-[0,tile) targets (rows owned by other visits, or any
        # row of a wrapped surplus visit) match no iota and write nothing
        base = p - (t0 + g) * tile
        for s in range(r):
            t = base + s
            if axis == 1:
                rows = jax.lax.broadcasted_iota(jnp.int32, (bx, tile, Z), 1)
                sl = slab_ref[:, s, :][:, None, :]
            else:
                rows = jax.lax.broadcasted_iota(jnp.int32, (bx, Y, tile), 2)
                sl = slab_ref[:, :, s][:, :, None]
            out_ref[...] = jnp.where(rows == t, sl, out_ref[...])

    if axis == 1:
        blk = (bx, tile, Z)
        sblk = (bx, r, Z)
    else:
        blk = (bx, Y, tile)
        sblk = (bx, Y, r)

    # index maps take scalar-prefetch refs AFTER the grid indices (the kernel
    # takes them first)
    def index(i, g, pos_ref):
        tidx = jax.lax.rem(
            pos_ref[0] // tile + jnp.asarray(g, jnp.int32), jnp.int32(ntiles)
        )
        return (i, tidx, 0) if axis == 1 else (i, 0, tidx)

    def sindex(i, g, pos_ref):
        return (i, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(gx, nb),
        in_specs=[
            pl.BlockSpec(blk, index),
            pl.BlockSpec(sblk, sindex),
        ],
        out_specs=pl.BlockSpec(blk, index),
    )
    return pl.pallas_call(
        kernel,
        name=tm.KERNEL_BLEND_SLAB_DYNAMIC,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(block.shape, block.dtype),
        input_output_aliases={1: 0},
        interpret=interpret,
    )(pos, block, slab)
