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

``wrap_halo`` is the same tile-local write for an axis the mesh does not
split, where the "received slab" is the block's own interior: one aliased
call fills both halos from cells it reads through the aliased operand itself
— no slab is cut, sent to oneself or blended (each of those re-walked a whole
128-lane tile column for 3 lanes of data: 86% of an exchange on mesh [2,2,1]
— PERF.md §6, PR 26).  Reference analog: the same-GPU ``PeerAccessSender``
copy kernel (tx_cuda.cuh:39-104).
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


#: VMEM the self-wrap kernel sizes its x-block for (pipeline buffers plus the
#: tile scratch) and the scoped limit it requests to hold them.  Deeper
#: x-blocks amortize the per-grid-step cost over longer DMAs: the z wrap of a
#: 518^3 f32 block took 1.112 / 1.036 / 1.009 / 0.996 / 0.993 ms at 6 / 12 /
#: 24 / 48 / 96 MB on a v5e (PERF.md §6, PR 26), HBM-bound from 24 MB on
_WRAP_VMEM_BLOCKS = 24 * 1024 * 1024
_WRAP_VMEM_LIMIT = 48 * 1024 * 1024


def wrap_halo(
    block: jax.Array,
    axis: int,
    r_lo: int,
    r_hi: int,
    n: int,
    interpret: bool = False,
) -> jax.Array:
    """Return ``block`` with both ``axis`` halos filled from its OWN interior
    — the periodic self-wrap of an axis the mesh does not split: low halo
    ``[0, r_lo)`` <- ``[n, n + r_lo)``, high halo ``[r_lo + n, r_lo + n +
    r_hi)`` <- ``[r_lo, r_lo + r_hi)``, over the full extent of the other two
    axes.  ``n`` is the valid interior width (static: one shard owns the whole
    axis).  ``block`` is consumed (aliased to the output) and is the ONLY
    operand: a second, plain view of it would make XLA copy the whole array.

    Axis 0 streams the source planes through VMEM onto the halo planes.  On
    axes 1/2 the grid visits only the (sublane/lane) tiles holding one of the
    four ranges — ``k`` of them, statically known: per x-block, visits
    ``0..k-1`` gather the tiles into one VMEM scratch (consecutive tiles stay
    adjacent, so a range straddling a tile boundary stays contiguous), visit
    ``k-1`` shuffles the halo cells inside the scratch, and visits
    ``k-1..2k-2`` emit the tiles.  The index maps hold the input on the last
    tile while the rest are emitted and the output on the first while they
    are gathered, and the pipeline moves a block only when its index changes:
    every touched tile column is read once and written once, and nothing else
    of the block is touched.  Sources are never written (halo and interior are
    disjoint), so reading them through the aliased operand is race-free."""
    from jax.experimental import pallas as pl

    assert axis in (0, 1, 2), axis
    assert n >= max(r_lo, r_hi) and r_lo + r_hi > 0, (n, r_lo, r_hi)
    X, Y, Z = block.shape
    # (destination, source, width) of the two halo fills
    fills = [
        (d, s, w) for d, s, w in ((0, n, r_lo), (r_lo + n, r_lo, r_hi)) if w
    ]
    out_shape = jax.ShapeDtypeStruct(block.shape, block.dtype)
    if axis == 0:

        def plane_of(which):
            # grid step g -> the g-th plane of the fills' destinations
            # (which=0) or sources (which=1): one run per fill
            (a, wa), *rest = [(f[which], f[2]) for f in fills]
            if not rest:
                return lambda g: (a + g, 0, 0)
            b = rest[0][0]
            return lambda g: (jnp.where(g < wa, a + g, b + g - wa), 0, 0)

        def kernel0(in_ref, out_ref):
            out_ref[...] = in_ref[...]

        return pl.pallas_call(
            kernel0,
            name=tm.KERNEL_BLEND_PLANES,
            grid=(r_lo + r_hi,),
            in_specs=[pl.BlockSpec((1, Y, Z), plane_of(1))],
            out_specs=pl.BlockSpec((1, Y, Z), plane_of(0)),
            out_shape=out_shape,
            input_output_aliases={0: 0},
            interpret=interpret,
        )(block)

    tile = _sublane(block.dtype) if axis == 1 else 128
    tiles = sorted(
        {t for d, s, w in fills for p in (d, s) for t in range(p // tile, (p + w - 1) // tile + 1)}
    )
    k = len(tiles)

    def spot(p: int) -> int:
        """Scratch coordinate of axis position ``p`` (tile j of the touched
        list sits at ``[j * tile, (j + 1) * tile)``)."""
        return tiles.index(p // tile) * tile + p % tile

    rows, lanes = (tile, Z) if axis == 1 else (Y, tile)  # one tile of one x-row
    sub = _sublane(block.dtype)
    row_bytes = (-(-rows // sub) * sub) * (-(-lanes // 128) * 128) * block.dtype.itemsize
    # 2 input + 2 output pipeline buffers and the k-tile scratch
    bx = max(1, min(X, _WRAP_VMEM_BLOCKS // ((4 + k) * row_bytes)))
    gx = -(-X // bx)

    def cut(lo, hi):
        span = slice(lo, hi)
        return (slice(None), span, slice(None)) if axis == 1 else (slice(None), slice(None), span)

    def kernel(in_ref, out_ref, scratch):
        v = pl.program_id(1)

        def at_visit(j, body):
            body() if k == 1 else pl.when(v == j)(body)

        for j in range(k):

            def gather(j=j):
                scratch[cut(j * tile, (j + 1) * tile)] = in_ref[...]

            at_visit(j, gather)

        def shuffle():
            # both sources are read before either halo is written, as the
            # slab-cutting sweep does
            cells = [scratch[cut(spot(s), spot(s) + w)] for _, s, w in fills]
            for (d, _, w), c in zip(fills, cells):
                scratch[cut(spot(d), spot(d) + w)] = c

        at_visit(k - 1, shuffle)
        for j in range(k):

            def emit(j=j):
                out_ref[...] = scratch[cut(j * tile, (j + 1) * tile)]

            at_visit(k - 1 + j, emit)

    def tile_index(table):
        # a static tile list as a select chain over the visit index
        def index(i, v):
            t = jnp.int32(table[-1])
            for j in range(len(table) - 2, -1, -1):
                t = jnp.where(v <= j, table[j], t)
            return (i, t, 0) if axis == 1 else (i, 0, t)

        return index

    from jax.experimental.pallas import tpu as pltpu

    blk = (bx, rows, lanes)
    sshape = (bx, k * tile, Z) if axis == 1 else (bx, Y, k * tile)
    params = {}
    if not interpret:
        params["compiler_params"] = pltpu.CompilerParams(
            vmem_limit_bytes=_WRAP_VMEM_LIMIT
        )
    return pl.pallas_call(
        kernel,
        name=tm.KERNEL_BLEND_SLAB,
        grid=(gx, 2 * k - 1),
        # visits 0..k-1 read tile v, then the input rests on the last tile
        in_specs=[pl.BlockSpec(blk, tile_index(tiles + [tiles[-1]] * (k - 1)))],
        # the output rests on the first tile until visit k-1 writes it, then
        # visit k-1+j writes tile j
        out_specs=pl.BlockSpec(blk, tile_index([tiles[0]] * (k - 1) + tiles)),
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM(sshape, block.dtype)],
        input_output_aliases={0: 0},
        interpret=interpret,
        **params,
    )(block)


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

    assert axis in (0, 1, 2), axis
    X, Y, Z = block.shape
    r = slab.shape[axis]
    pos = jnp.asarray(pos, jnp.int32).reshape((1,))
    if axis == 0:
        # whole planes at a traced plane offset: ``blend_slab``'s axis-0 form
        # with the offset in the output's index map.  A traced
        # ``dynamic_update_slice`` here compiled to a whole-array loop fusion
        # with a fresh result (3.8 GB of temporaries for four 602 x 602 x 1197
        # quantities, cross-compiled for a v5e: PERF.md §6, PR 31)
        def kernel0(pos_ref, in_ref, slab_ref, out_ref):
            del pos_ref, in_ref
            out_ref[...] = slab_ref[...]

        return pl.pallas_call(
            kernel0,
            name=tm.KERNEL_BLEND_SLAB_DYNAMIC,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(r,),
                in_specs=[
                    pl.BlockSpec(memory_space=pl.ANY),
                    pl.BlockSpec((1, Y, Z), lambda g, pos_ref: (g, 0, 0)),
                ],
                out_specs=pl.BlockSpec(
                    (1, Y, Z), lambda g, pos_ref: (pos_ref[0] + g, 0, 0)
                ),
            ),
            out_shape=jax.ShapeDtypeStruct(block.shape, block.dtype),
            input_output_aliases={1: 0},
            interpret=interpret,
        )(pos, block, slab)
    tile = _sublane(block.dtype) if axis == 1 else 128
    ext = (Y, Z)[axis - 1]
    ntiles = -(-ext // tile)
    # worst-case tiles a width-r region can span at any alignment
    nb = min((r - 1) // tile + 2, ntiles)
    bx = min(8, X)
    gx = -(-X // bx)

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
