"""What the stream engine runs on the chip: the three streaming passes.

In the reference, the stencil kernel is USER code: apps write plain CUDA
through ``Accessor`` (accessor.hpp:13-40, jacobi3d.cu:65-108,
astaroth_sim.cu:65-83) and the GPU cache hierarchy gives every such kernel
operand reuse for free.  The TPU analog of that cache reuse is an explicit
VMEM plane ring.  The passes here run the SAME ``StepKernel`` signature that
``make_step``'s XLA route runs — ``views[name].sh(dx,dy,dz)`` reads plus
``info.coords()`` — but stream x-planes through VMEM so each HBM plane is
read once per pass instead of once per shifted operand (the XLA slice
formulation re-reads the block ~6x, measured 5-7.5 Gcells/s at 512^3 vs
~40+ for the streamed form).

* ``stream_plane_pass`` — ONE level per pass over shell-carrying blocks, any
  per-axis shell widths and any ``r >= 1`` (the kernel's x read distance);
  ``stream_plane_pass_tiled`` is its interior-window strip form for planes
  too large to stream whole: the pipeline moves y tiles of them.
* ``stream_wavefront_pass`` — ``m`` levels per pass over an ``s``-wide-shell
  shard (``m <= s // r``, ``r == 1`` only): each HBM plane is read and
  written once per ``m`` iterations (~``8/m`` B/cell), the temporal blocking
  that makes the flagship paths beat the bandwidth roofline; plain or in the
  z-slab form (z halos never touch the tiled array).
* ``stream_wrap_pass`` — ``k`` levels over the whole single-device domain
  with the periodic wrap folded into the index maps: no shell, no exchange.

The passes are bit-compatible with the XLA route: both call the user kernel
with the same per-cell arithmetic, so outputs agree exactly (modulo compiler
excess precision, which the interpret-mode tests pin).

**Fused unpack→blend** (``fused_shell=``; the plan's ``halo="fused"``,
``ops/stream_plan.py`` has its gates): under the packed ``yzpack_*``
exchange routes the macro's unpack step is redundant — the received shell
messages are blended into the big array only so the pass can read them
back out one plane later.  ``fused_shell_exchange`` (ops/exchange.py)
returns the received per-axis shell BUFFERS (corner-patched on the small
buffers in the exchange's sweep order), and the plane and wavefront passes
consume them as side inputs — each level-0 plane is patched in VMEM
(x-shell planes replaced from the x slabs, then y rows from the
sublane-major y buffer, then z columns from the lane-major z buffer,
replaying the x→y→z sweep order) before any kernel level runs.  The big
array is NEVER written with halo data: no blend kernels, no halo DUS, no
unpack kernels — the generalization of the z-slab wavefront's bespoke
zero-big-array-halo scheme to every axis.  Because the patched level-0
planes are bitwise equal to the unfused post-exchange planes, every pass
output — interior AND shell — is bitwise-identical to ``halo="array"``.

This module imports nothing of ``ops/stream_plan.py`` or ``ops/stream.py``:
what is decided and what is built sit above it.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from stencil_tpu.core.dim3 import Dim3
from stencil_tpu.telemetry import names as tm
from stencil_tpu.ops.jacobi_pallas import (
    _make_roll,
    _tpu_compiler_params,
    patch_z_halo,
)


class PlaneView:
    """Resident-plane window for one quantity inside a streaming kernel.

    ``sh(dx, dy, dz)`` mirrors ``ShardView.sh`` (the reference's
    ``src[o + Dim3(dx,dy,dz)]`` Accessor read, accessor.hpp:27-40): the
    x offset selects one of the ``2r+1`` VMEM-resident planes, the y/z
    offsets are in-plane rotates.  What the rotate's wraparound brings in at
    the plane's edges depends on the plane.  On a RAW, shell-carrying plane
    (the wavefront pass, the plane pass's ``"raw"`` window) it only
    contaminates shell cells the validity contract already sacrifices.  On a
    BARE interior that is the whole periodic extent of both in-plane axes
    (the wrap pass; the plane pass's ``"interior"`` window, which holds it
    rotated -- all the same to a rotate) the wraparound IS the halo: the cell
    a shift reads past an edge is the periodic neighbour, on the y and z
    faces and in the y-z corner alike, and no cell is sacrificed.

    ``off_centre(dx, dy, dz)`` is called, at trace time, on every read with
    a non-zero offset (``center()`` and ``sh(0, 0, 0)`` never call it): the
    footprint trace records the quantity and the offset there, and the plane
    pass raises there for a quantity whose halo was not filled
    (``trace_plane_kernel``).
    A window plane may be ``None``: the pass holds no ring for a quantity
    its kernel reads at ``dx == 0`` only, and ``no_ring`` is called on a read
    of such a plane (it raises, naming the quantity).
    """

    def __init__(self, window: Tuple[jax.Array, ...], roll, off_centre=None,
                 no_ring=None):
        self._window = window
        self._r = (len(window) - 1) // 2
        self._roll = roll
        self._off_centre = off_centre
        self._no_ring = no_ring

    def sh(self, dx: int = 0, dy: int = 0, dz: int = 0) -> jax.Array:
        # ALL axes are bounded by the declared read radius: an in-plane
        # shift beyond it would wrap opposite-edge values into cells the
        # validity contract counts as correct — silently wrong results, so
        # fail at trace time instead
        assert all(-self._r <= d <= self._r for d in (dx, dy, dz)), (
            (dx, dy, dz), self._r,
        )
        if self._off_centre is not None and (dx or dy or dz):
            self._off_centre(dx, dy, dz)
        v = self._window[self._r + dx]
        if v is None:
            self._no_ring()
        if dy:
            v = self._roll(v, -dy, 0)
        if dz:
            v = self._roll(v, -dz, 1)
        return v

    def center(self) -> jax.Array:
        return self._window[self._r]


class StripView(PlaneView):
    """A ``PlaneView`` over ONE STRIP of the working plane (the plane pass's
    strip form, ``plane_strip_rows``): ``read(dx, dy, dz)`` hands the ``(S, Z)``
    strip of window plane ``dx`` shifted by ``(dy, dz)`` -- in the pass a read
    of the plane's tiles ``dy`` further on (an address, no rotate) and a lane
    rotate for ``dz``, made in the loop or read from a plane rotated once for
    all its readers; in the footprint trace an input of its own -- or ``None``
    for a plane the pass holds no ring for."""

    def __init__(self, read, x_radius: int, off_centre=None, no_ring=None):
        self._read = read
        self._r = x_radius
        self._off_centre = off_centre
        self._no_ring = no_ring

    def sh(self, dx: int = 0, dy: int = 0, dz: int = 0) -> jax.Array:
        assert all(-self._r <= d <= self._r for d in (dx, dy, dz)), (
            (dx, dy, dz), self._r,
        )
        if self._off_centre is not None and (dx or dy or dz):
            self._off_centre(dx, dy, dz)
        v = self._read(dx, dy, dz)
        if v is None:
            self._no_ring()
        return v

    def center(self) -> jax.Array:
        return self._read(0, 0, 0)


@dataclasses.dataclass
class PlaneInfo:
    """Traced per-plane context handed to streaming kernels.  ``coords``
    returns broadcast-compatible pieces — x a scalar (the whole plane shares
    one global x), y a column, z a row — so kernels written against
    ``BlockInfo.coords()`` broadcasting run unchanged."""

    x_global: jax.Array  # int32 scalar: wrapped global x of the output plane
    y_global: jax.Array  # (Y, 1) int32 wrapped global y
    z_global: jax.Array  # (1, Z) int32 wrapped global z
    global_size: Dim3
    level: int  # wavefront level (1-based); 1 on the plane route

    def coords(self):
        return self.x_global, self.y_global, self.z_global


#: a streaming kernel is just a StepKernel evaluated on planes
PlaneKernel = Callable[[Dict[str, PlaneView], PlaneInfo], Dict[str, jax.Array]]


def lane_pad_width(z: int) -> int:
    """Plane width rounded up to a 128 multiple — ragged lane extents stream
    ~30% slower (probe22), so z-slab wavefronts pad with dead columns."""
    return -(-z // 128) * 128


class _ReadAtUse:
    """``planes[q]`` reads the ``(rows, lanes)`` corner of block ``q`` where it
    is asked for."""

    def __init__(self, refs, rows: int, lanes: int):
        self._refs, self._rows, self._lanes = refs, rows, lanes

    def __getitem__(self, q):
        return self._refs[q][0, : self._rows, : self._lanes]


def sublane_tile(dtypes) -> int:
    """Rows of a vector tile of the narrowest of ``dtypes``: 8 of f32, 16 of a
    2-byte dtype."""
    return max(max(8, 32 // jnp.dtype(d).itemsize) for d in dtypes)


#: vregs of f32 a value of the strip form's kernel is cut to (``plane_strip_
#: rows``)
_STRIP_VREGS = 4


def plane_strip_rows(window: str, plane: Tuple[int, int], dtypes, x_radius: int) -> int:
    """The rows ``S`` of a strip of ``stream_plane_pass``'s strip form over the
    ``plane = (Yw, Zw)`` working plane, 0 where the pass evaluates its kernel
    over the plane whole: read off the window, the plane and the read distance
    alone.  The strip form exists on the two ALIGNED windows only,
    ``"interior"`` and ``"interior-z"`` (``plane_window_form``: there the
    working plane is whole tiles, and the rotate's wraparound the halo on the
    axes the pass fills itself; a raw plane -- a split z, ragged lanes or rows
    -- is neither), and where the plane holds ``x_radius`` or more tiles of rows
    (a y shift then wraps around the tiles once at most).  A strip is ``G`` of the
    plane's ``K = Yw / T`` tiles (``T`` the sublane tile of the stored
    dtypes), ``S = G T`` rows: ``G`` divides ``K`` and makes a value of the
    kernel ``_STRIP_VREGS`` vregs (``G x Zw / 128``) or, on a plane too wide
    for that, one tile.  ``domain.step`` says it as ``plane_strip``."""
    if window == "raw":
        return 0
    yw, zw = plane
    tile = sublane_tile(dtypes)
    tiles = yw // tile
    if tiles < x_radius:
        return 0
    group = max(1, min(tiles, _STRIP_VREGS * 128 // zw))
    while tiles % group:
        group -= 1
    return group * tile


def plane_window_form(wrap_fills, lo: Dim3, hi: Dim3, plane: Tuple[int, int], dtypes) -> str:
    """The working plane of ``stream_plane_pass`` over ``plane = (Y, Z)`` raw
    planes stored as ``dtypes``, read off what the pass is told and the static
    shapes alone.  ``"interior"`` where the pass makes BOTH in-plane halo fills
    itself, each the self-wrap of the block's whole interior (``wrap_fills``
    as ``pass_wrap_fills`` gives them where the mesh splits neither y nor z),
    AND that interior is whole vector tiles of every stored dtype (8 sublanes
    of f32, 16 of a 2-byte dtype, by 128 lanes).  ``"interior-z"`` where the
    fills are the z self-wrap ALONE (the mesh splits y: the y halo is a
    neighbour's rows, real data that arrived over a wire), the interior is
    whole tiles as above, and the y shell is one the pass's tile layout
    carries: ``lo.y + hi.y`` rows that fit one sublane tile (they ride as the
    last sublane of that many margin tiles) and are no more than the plane has
    tiles.  ``"raw"`` everywhere else: a z the mesh splits (the z halo is then
    no rotate's wraparound), an interior of ragged lanes or rows (600: nothing
    of it is whole tiles, and cutting a plane at an unaligned row or lane costs
    more than the dead lanes do), a y shell wider than a tile, a plane of fewer
    tiles than its y shell has rows.  ``domain.step`` says it as
    ``plane_window``."""
    yi, zi = plane[0] - lo.y - hi.y, plane[1] - lo.z - hi.z
    wrap_y = ((1, 0, yi, lo.y), (1, lo.y + yi, lo.y, hi.y))
    wrap_z = ((2, 0, zi, lo.z), (2, lo.z + zi, lo.z, hi.z))
    tile = sublane_tile(dtypes)
    whole = yi % tile == 0 and zi % 128 == 0
    # ... and no narrower than the shell it stands in for (the rows and lanes
    # past the window repeat its first ``lo + hi``)
    whole = whole and zi >= lo.z + hi.z > 0
    if whole and tuple(wrap_fills) == wrap_y + wrap_z and yi >= lo.y + hi.y > 0:
        return "interior"
    if whole and tuple(wrap_fills) == wrap_z and 0 < lo.y + hi.y <= min(tile, yi // tile):
        return "interior-z"
    return "raw"


def _wrap_fill(ref, fills):
    """The y, then z, halo fills ``(axis, destination, source, width)`` of the
    ``(1, Y, Z)`` block ``ref``, made in place, each over the full extent of
    the other axis (so the later z fill completes the y-z corner)."""
    for axis, dst, src, w in fills:
        if axis == 1:
            ref[0, dst : dst + w, :] = ref[0, src : src + w, :]
        else:
            ref[0, :, dst : dst + w] = ref[0, :, src : src + w]


def _yz_coord_planes(origin_ref, Yr, Zr, off_y, off_z, gsize, rows=None):
    """Wrapped global y/z coordinates of the raw plane, as a (Yr, 1) column
    and a (1, Zr) row (2D iotas — Mosaic has no 1D iota); with ``rows`` (a
    (Yr, 1) int32 column) of those rows of it, for a strip."""
    y = lax.broadcasted_iota(jnp.int32, (Yr, 1), 0) if rows is None else rows
    z = lax.broadcasted_iota(jnp.int32, (1, Zr), 1)
    gy, gz = jnp.int32(gsize.y), jnp.int32(gsize.z)
    # + gsize keeps lax.rem's operand non-negative (origin - shell >= -shell)
    y_g = lax.rem(origin_ref[1] + gy + y - jnp.int32(off_y), gy)
    z_g = lax.rem(origin_ref[2] + gz + z - jnp.int32(off_z), gz)
    return y_g, z_g


def _zero_lane_pad(plane, valid: int):
    """``plane`` (Yr, Zp) with lanes [valid, Zp) set to zero -- the dead lanes
    of a boundary block, which hold whatever the VMEM buffer held.  ``Zp`` is
    ``lane_pad_width(valid)``, so they all sit in the LAST lane tile: one
    select there (the tile sliced out at a multiple of 128, as
    ``patch_z_halo`` takes its own), the other tiles untouched.  The zeros
    are what the ``jnp.pad`` this replaces stored, so nothing non-finite
    reaches a level, the emit or the stored shell."""
    Yr, Zp = plane.shape
    if Zp == valid:
        return plane
    at = Zp - 128
    assert at <= valid < Zp, (valid, Zp)
    lane = lax.broadcasted_iota(jnp.int32, (Yr, 128), 1)
    last = jnp.where(lane < valid - at, plane[:, at:], jnp.zeros((), plane.dtype))
    return jnp.concatenate([plane[:, :at], last], axis=1) if at else last


def _fused_plane_patch(v, xplane, yst, zst, t, lo_y, hi_y, lo_z, hi_z):
    """Patch one level-0 VMEM plane from the fused shell buffers, replaying
    the exchange's sweep order x -> y -> z: replace the whole plane when
    this is an x-shell position (``t`` is the threshold-iota row bound —
    the plane height at shell positions, 0 otherwise: the broadcast-compare
    pattern the dynamic blend kernels use), then land the y rows from the
    sublane-major buffer and the z columns from the lane-major one.
    Shared by the plane and wavefront passes (``fused_shell`` mode)."""
    Y, Z = v.shape
    rowv = lax.broadcasted_iota(jnp.int32, (Y, Z), 0)
    colv = lax.broadcasted_iota(jnp.int32, (Y, Z), 1)
    v = jnp.where(rowv < t, xplane, v)
    for j in range(lo_y):
        v = jnp.where(rowv == j, yst[j][None, :], v)
    for j in range(hi_y):
        v = jnp.where(rowv == Y - hi_y + j, yst[lo_y + j][None, :], v)
    for j in range(lo_z):
        v = jnp.where(colv == j, zst[j][:, None], v)
    for j in range(hi_z):
        v = jnp.where(colv == Z - hi_z + j, zst[lo_z + j][:, None], v)
    return v


def _output_homes(names, raws, writers, renames) -> Dict[int, int]:
    """Per writer (an index into ``names``) the quantity whose BLOCK its output
    lives in -- aliases, under ``alias`` --: its own, or for a pair ``(p, q)`` of
    ``renames`` raw ``p`` for writer ``q`` (``stream_plane_pass`` has the rule)."""
    home = {q: q for q in writers}
    for p_name, q_name in renames:
        p, q = names.index(p_name), names.index(q_name)
        assert q in home and p not in home, (renames, [names[w] for w in writers])
        assert raws[p].dtype == raws[q].dtype, (p_name, q_name)
        home[q] = p
    return home


def _told_guards(names, halo_readers, rings, writers, outputs):
    """``(no_ring, stale_read, unknown_output)`` of a plane pass: what raises, at
    trace time and by name, where the kernel reads or returns what the pass was
    not told of (``trace_plane_kernel`` has the rule).  ``outputs`` are the
    indices of the quantities the pass has an output block for."""

    def no_ring(name):
        def fail():
            raise ValueError(
                f"the kernel reads {name!r} off-centre along x, but its "
                f"footprint trace did not (it saw {tuple(rings)}), so the pass "
                f"holds no ring for {name!r}: a kernel must read the same "
                "offsets every time it is traced"
            )

        return fail

    def stale_read(name):
        if halo_readers is None or name in halo_readers:
            return None

        def fail(*offset):
            raise ValueError(
                f"the kernel reads {name!r} off-centre, but its footprint "
                f"trace did not (it saw {tuple(halo_readers)}), so the halo "
                f"of {name!r} was not exchanged: a kernel must read the same "
                "offsets every time it is traced"
            )

        return fail

    def unknown_output(vals):
        for q, name in enumerate(names):
            if name in vals and q not in outputs:
                raise ValueError(
                    f"the kernel returns {name!r}, but its footprint "
                    f"trace did not (it saw {tuple(writers)}), so "
                    f"{name!r} is not an output of the pass: a kernel "
                    "must return the same names every time it is traced"
                )

    return no_ring, stale_read, unknown_output


def stream_plane_pass(
    kernel: PlaneKernel,
    names: Sequence[str],
    raws: Sequence[jax.Array],  # per-quantity (X, Y, Z) shell-carrying blocks
    lo: Dim3,
    hi: Dim3,  # shell widths (allocation minus interior)
    x_radius: int,  # kernel x read distance r; ring depth is 2r
    origin: jax.Array,  # (3,) int32 global coords of the interior start
    global_size: Dim3,
    alias: bool = False,  # out q aliases raw q (in place; see below)
    interpret: bool = False,
    f32_accumulate: bool = False,  # bf16-storage variant: planes upcast to
    # f32 for the kernel, one downcast at the interior store (pass-through
    # shell planes keep their storage bytes bit-exact)
    fused_shell=None,  # (xbufs, ybufs, zbufs) per quantity — the packed
    # halo messages land in the level-0 planes in VMEM instead of having
    # been unpacked into the blocks (halo="fused"; see module docstring)
    halo_readers: Optional[Sequence[str]] = None,  # the quantities whose
    # shell was filled (trace_plane_kernel); None = every one
    writers: Optional[Sequence[str]] = None,  # the quantities the kernel
    # returns (trace_plane_kernel): the pass's only outputs; None = every one
    rings: Optional[Sequence[str]] = None,  # the quantities the kernel reads
    # at dx != 0 (PlaneTrace.pruned): the only ones with a ring; None = all
    wrap_fills: Sequence[Tuple[int, int, int, int]] = (),  # (axis, destination,
    # source, width) of the y / z halo fills the pass makes itself, in VMEM
    # (pass_wrap_fills): the self-wrap of an axis the mesh does not split
    renames: Sequence[Tuple[str, str]] = (),  # ``(p, q)``: writer ``q``'s new
    # value lands in ``p``'s buffer and ``p`` comes back as raw ``q``
    # (trace_plane_kernel): a time level renamed instead of copied
    window: str = "raw",  # the working plane (plane_window_form): the "raw"
    # plane, or the block's "interior" (rotated onto the block's aligned
    # corner) where the fills are its own self-wrap, or "interior-z" where
    # they are its z self-wrap alone and the y halo rows are a neighbour's
    strip: int = 0,  # rows of a strip the kernel is evaluated over at a time
    # (plane_strip_rows; the two aligned windows only); 0 = over the plane whole
    prerotated: Sequence[Tuple[str, int, int]] = (),  # the strip form: the
    # ``(quantity, dx, dz)`` whose plane is rotated by ``dz`` lanes ONCE a grid
    # step, for every strip and every ``dy`` that reads it (shared_rotations)
) -> List[jax.Array]:
    """ONE kernel level over shell-carrying blocks, streaming x-planes with a
    ``2r``-deep ring per quantity read off-centre along x; shell planes and
    the in-plane shell ring pass through unchanged (the exchange owns halo
    cells).  Generalizes ``mean6_plane_step``/``jacobi_plane_step`` to user
    kernels, any field count, and any ``r >= 1``.

    A quantity outside ``rings`` is read at ``dx == 0`` only -- a coefficient,
    an older time level, a quantity differenced along y or z alone -- and
    needs no window along x: its plane is FETCHED LAGGED, at the output
    plane ``clip(i - r, 0, X - 1)`` instead of ``min(i, X - 1)``, so the
    fetched block IS the centre plane, and it has no ring scratch and no
    push.  (VMEM per such quantity: two pipeline planes instead of ``2r +
    2`` -- what lets a pass carry nine quantities at 608 x 608, ``plan_plane
    _passes``.)  In place stays safe: a lagged input's plane ``j`` is fetched
    before grid step ``j + r`` and the aliased output's plane ``j`` is
    flushed after it, and no later fetch goes back (``check_inplace_order``
    proves it from the block maps, as for the ringed form below).  Not under
    ``fused_shell`` (the patch replays the sweep on the plane fetched at
    ``i``): every quantity keeps its ring there.

    With ``fused_shell`` the blocks' shell cells are STALE and the fresh
    halos ride as side inputs (``fused_shell_exchange``'s buffers): every
    loaded plane is patched in VMEM — x-shell planes replaced from the x
    slabs, then y rows, then z columns, replaying the exchange's sweep
    order — before it feeds the ring, the kernel, or the pass-through, so
    the pass is bitwise-identical to running over exchanged blocks.

    With ``wrap_fills`` the y / z shell of the blocks is STALE on the axes
    the fills name and there is no message at all: on an axis the mesh does
    not split the halo of a plane is a copy of cells of that same plane, so
    every loaded plane of every halo reader -- ringed or fetched lagged,
    x-shell planes included -- has its halo rows (y) and then its halo
    columns (z) copied from its own interior, each over the full extent of
    the other axis, before it feeds the ring, the kernel or the
    pass-through.  The step's exchange then sweeps the remaining axes only
    (x always: in place, the pass has overwritten the source planes of the
    high x shell long before it reaches it), and after that sweep the fills
    replay the exchange's order x -> y -> z cell for cell: every window is
    bitwise the one the kernel saw over exchanged blocks, and a writer that
    is also a reader leaves the same raw array in HBM, halo included (the
    pass-through writes the patched centre plane).  A reader no pass writes
    keeps a stale y / z shell in HBM, which the contract allows (the
    exchange owns halo cells and refills them before every read).  The
    copies are made in the pipeline's own input buffer, on the few sublane
    rows and the two lane tiles that hold the four ranges (as
    ``halo_blend.wrap_halo``'s shuffle does in its scratch): no VMEM of
    their own, and idempotent, so a plane the pipeline does not refetch is
    patched again to the same cells.  Not with ``fused_shell``.

    With ``window="interior"`` (``plane_window_form``: the fills are the
    self-wrap of the block's WHOLE interior on both in-plane axes, and that
    interior is whole vector tiles) the pass needs no halo around its working
    plane at all: a halo cell of a loaded plane is a copy of an interior cell
    of that plane, and a rotate of the bare ``(Yi, Zi)`` interior wraps around
    to exactly that cell.  A rotate's wraparound cares nothing for where the
    plane starts, so the working plane is the interior ROTATED by ``(lo.y,
    lo.z)``, which needs no unaligned access: once the two LOW halos of a
    fetched block hold their wrap (the first fill of each axis, made in the
    pipeline's input buffer as above, for EVERY quantity -- a centre read
    reads those rows and lanes too), the block's aligned corner ``[0:Yi,
    0:Zi]`` is that plane -- raw row ``k`` is interior row ``k - lo.y`` for
    ``k >= lo.y`` and the low halo, interior row ``Yi - lo.y + k``, below.
    The rings hold ``(2r, Yi, Zi)`` such planes; the kernel's windows are such
    planes and ``info.coords()`` their cells' own coordinates; every in-plane
    shift is one native rotate (``_make_roll``'s aligned branch) whose
    wraparound supplies the y, z and y-z corner reads, on interior and
    x-shell planes alike (the x-y / x-z edge halos of a diagonal read).  No
    read changes value and the kernel evaluates the same operations in the
    same order on the cells the raw window keeps: interiors are bitwise the
    raw window's.  What an OUTPUT block holds outside them: each stored plane
    -- the kernel's values, or an x-shell plane passed through from the ring
    or the lagged block -- goes onto the block's aligned corner whole
    (interior cells and low halos at once), and the rows and lanes past it,
    which repeat the plane's first ``lo + hi``, are copied behind it: the y /
    z shell of the stored plane is REBUILT from the stored interior, in the
    pipeline's output buffer.  An x-shell plane of a halo reader is then
    bitwise what the raw window writes; an interior plane's shell is the wrap
    of the NEW interior where the raw window keeps the wrap of the plane as
    loaded -- so after the step's x sweep the block is, halo included, what a
    full x -> y -> z exchange of the new state gives.  Either is the
    exchange's to own (it refills halo cells before every read), and nothing
    of the step reads a y / z halo cell of HBM.  The block maps, the aliases,
    the lagged fetches and every guard below are the raw window's
    (``check_inplace_order`` judges the same maps).  Everywhere else the
    window is ``"raw"`` and the pass is, operation for operation, the one
    above.  (Cutting the interior itself out of the block, at ``[lo.y:,
    lo.z:]``, costs a sublane and a lane shift of every vreg of every plane
    in and out: 2.4 ms of a 9.6 ms MHD pass, PERF.md PR 45.)

    With ``strip = S > 0`` (``plane_strip_rows``: the two aligned windows only)
    the kernel is not evaluated over the working plane whole but a STRIP of it
    at a time: inside a grid step a loop runs over the plane's strips,
    ``kernel(views, info)`` is traced ONCE, over ``StripView``s, and a value of
    the kernel is ``S / 8 x Zw / 128`` vregs and not the whole register file
    (64 at 256 x 256), so a chain of operations stays in registers.  The
    strips are cut so that a y shift is an ADDRESS.  The pass holds every plane
    as TILES, ``(K, T, Zw)`` with ``T`` the rows of a sublane tile and ``K = Yw
    / T``: tile ``k`` holds row ``s K + k`` of the working plane at sublane
    ``s`` (``to_tiles``: one transposition of the tiles' two leading
    dimensions a plane, as it is pushed), so the plane's next row is the next
    TILE's same sublane.  A strip is ``G = S / T`` consecutive tiles; ``sh(dx,
    dy, dz)`` reads tiles ``k + dy .. + G`` of window plane ``dx`` -- whole
    aligned tiles at a traced index of a leading dimension: no sublane rotate,
    no select -- and makes the one lane rotate for ``dz``, whose wraparound
    over the ``Zw`` whole lanes is the z halo exactly as on the whole plane.
    The y wraparound comes from ``r`` MARGIN tiles a side: tile ``K + m`` is
    tile ``m`` one sublane up (row ``s K + K + m`` is row ``(s + 1) K + m``,
    and the last sublane wraps onto row ``m``: the periodic neighbour), tile
    ``-m`` tile ``K - m`` one sublane down -- ``2r`` one-tile sublane rotates a
    plane.  Every ringed quantity holds ``2r + 1`` such planes and is pushed
    BEFORE the strips run (they read the newest plane there too); every other
    quantity holds its centre plane, put in before the strips.  The kernel's
    values are gathered as tiles in a staging plane a writer and go to the
    output block through ``put`` once a plane, transposed back.
    ``info.coords()`` hands the strip's own ``y_global`` rows.  ``prerotated``
    names the ``(quantity, dx, dz)`` whose plane is rotated by ``dz`` lanes
    ONCE a grid step, tiles and margins, into a plane of its own
    (``stream_plan.shared_rotations``: those that two or more ``dy`` read): a
    read of it at any ``dy`` is then tiles of that plane, where the loop would
    rotate the strip of each ``dy`` anew -- the lane rotates bound the loop
    (PERF.md PR 46).  The grid, the block maps, the aliases, the renames, the
    lagged fetches, the rings' x logic and the x-shell pass-through are the
    whole-plane form's, and so is every value: the same operations in the same
    order on every cell, bitwise.  With ``strip = 0`` the pass is, operation
    for operation, the one above.

    With ``window="interior-z"`` (``plane_window_form``: the fills are the z
    self-wrap of the block's whole z interior ALONE, because the mesh splits
    y; the interior is whole vector tiles; the strip form only) the y halo of a
    loaded plane is no copy of cells of that plane but a neighbour's rows,
    which the step's exchange has put into the block, and the working plane is
    aligned all the same.  Along z nothing changes: the LOW z fill is made in
    the pipeline's input buffer for every quantity over EVERY row, the y halo
    rows included (the y-z corner of the x -> y -> z sweep order: the y halo
    has arrived first), lanes ``[0, Zi)`` of the block are then the z interior
    rotated by ``lo.z``, and a ``dz`` shift is one native lane rotate whose
    wraparound is the z halo.  Along y NOTHING IS CUT at an unaligned row: the
    ``K = Yi / T`` tiles are taken over RAW rows ``[0, Yi)`` -- rows ``[0,
    lo.y)`` of them the low halo, real cells of the extended plane, the rest
    interior rows ``[0, Yi - lo.y)`` -- by the same transposition, and sit at
    tiles ``[0, K)`` of a scratch plane.  Behind them stand ``M = lo.y + hi.y``
    margin tiles: tile ``K + m`` is tile ``m`` one sublane up, as the interior
    window's high margin is, EXCEPT its last sublane, which is raw row ``Yi +
    m`` -- one of the block's tail rows ``[Yi, Y)``, its last ``lo.y`` interior
    rows and its high halo, copied there row by row from their static,
    tile-aligned place -- where the interior window has the periodic wrap onto
    row ``m``.  Tile ``k`` then holds raw rows ``s K + k`` for every ``k`` in
    ``[0, K + M)``, so tiles ``[lo.y, lo.y + K)`` hold every interior row
    exactly once; the strips run over THOSE ``K`` tiles (the interior window's
    trip count and loop body, ``first[dy]`` counted from ``lo.y`` where it
    counts from ``r`` there) and read tiles ``[lo.y - r, lo.y + K + r)``, all
    there.  No low margin is made, and with ``lo.y = hi.y = r`` the scratch
    shapes, and so the VMEM model, are the interior window's.  A plane of
    ``prerotated`` is rotated tiles and margins at once (the margins hold rows
    of their own).  Outputs mirror the inputs (``put_carried``): the staged
    tiles below ``K`` go out as they stand; the ``lo.y`` past it go one sublane
    down onto tiles ``[0, lo.y)``, whose sublane 0 -- a low y halo row -- passes
    through from the centre plane; what that shift drops off their last
    sublane are the tail rows ``Yi + m``, and the high halo rows behind those
    pass through from the centre plane too; the z shell of the stored plane is
    rebuilt from its first ``lo.z + hi.z`` lanes over every row, as on the
    interior window.  So a stored plane holds the kernel's values on its
    interior, the centre plane's y halo rows (bitwise the raw window's: the
    exchange owns them) and a z shell that is the wrap of what was stored.
    Pass-through planes (x-shell planes, which hold the x neighbour's cells,
    and plane 0) carry their tail rows along (``tail_rows``).  No read changes
    value and the kernel evaluates the same operations in the same order:
    interiors are bitwise the raw window's.  What the window cannot carry
    stays ``"raw"`` (``plane_window_form``): a y shell of more rows than a
    sublane tile or than the plane has tiles, a z the mesh splits (its halo is
    no wraparound of the block's own lanes), ragged lanes or rows (the three
    600-extent cells).

    Returns one array per quantity, but only the ``writers`` are OUTPUTS of
    the Pallas call: every quantity is an input with its ring and its view,
    and a quantity the kernel never returns is nothing else — its every raw
    cell, shell included, would be written back as it was read, so the pass
    returns ``raws[q]`` itself and moves a plane in where it moved one in and
    one out (acoustic: ``m`` and ``damp``, 8 arrays through HBM a step -> 6).
    A kernel that returns a name outside ``writers`` in THIS trace raises and
    names it (its values would otherwise be dropped silently); with no
    writer at all there is no call to make.  Not under ``fused_shell``:
    there the written planes are where the fresh shell lands, so every
    quantity stays an output whatever ``writers`` says (the same exception
    ``plan_plane_stages`` makes for the readers).

    With ``renames`` an output the kernel would return as another writer's
    centre plane, unchanged, is not written at all (``trace_plane_kernel``
    has the rule and where it does not apply; never with ``fused_shell``).
    For a pair ``(p, q)`` -- ``u_prev <- u`` -- ``p`` is no writer, and the
    output of ``q`` is what it always was, cell for cell (the kernel's value
    inside, ``q``'s own shell planes and in-plane shell ring passed through),
    but it has its HOME in ``p``'s block: under ``alias`` it aliases raw
    ``p`` (operand ``1 + p``), not raw ``q``.  The returned list holds that
    array under ``q`` and ``raws[q]`` ITSELF under ``p``: the two handles
    swap, and the pass moves one array less than writing ``p`` does
    (acoustic: reads 4, writes 1).  ``q`` comes back bitwise the array the
    un-renamed pass returns, shell included; ``p`` on its interior (its
    shell is now ``q``'s, as exchanged, where it was ``p``'s own stale one:
    the exchange owns both).  In place stays safe for the same reason as
    before, now for the pair (raw ``p``, output of ``q``): ``p`` is an
    operand of the pass whether the kernel reads it or not, fetched lagged
    (or ringed), so its plane ``j`` is read before grid step ``j + r``,
    after which the output's plane ``j`` is flushed onto it; raw ``q`` is an
    input only, nothing is flushed over it (``check_inplace_order`` judges
    whatever pair the call carries).  A value the kernel returns for ``p``
    in THIS trace is not looked at: the footprint trace proved it is ``q``'s
    centre plane.  The caller must hand the handles on permuted -- a loop
    that carries them pays whole-array copies unless a trip returns them to
    their places (``ops/stream.py _build_plane_step``) -- and a STAGE of several
    passes hands every pass the stage's entry blocks and swaps when its last
    pass has run: a later pass reads the old ``q`` under ``q``'s name.

    With ``alias`` a writer's output IS its raw block
    (``input_output_aliases`` maps operand ``1 + q`` — operand 0 is
    ``origin`` — to the writer's position among the outputs): a
    step loop that carries its blocks in place then needs no whole-array
    copy per quantity per step to put a fresh result where the carry lives.
    In place is safe because writes trail reads by ``r >= 1`` planes on the
    sequential grid ``(X + r,)``: step ``i`` fetches in plane ``min(i, X-1)``
    and holds out plane ``clip(i - r, 0, X-1)``.  The out plane flushed
    after step ``i`` is ``i - r <= i - 1``; every in plane fetched after
    step ``i`` is ``>= i + 1`` (or the clamped ``X-1``, which is written
    last, after the final step).  Out plane 0 is held for steps ``0..r``
    and flushed once, after plane 0 was read at step 0.  All the kernel
    needs of planes ``i-2r..i`` sits in the VMEM rings by the time plane
    ``i - r`` is written.  The ``inplace-order`` contract
    (``analysis/kernels.py check_inplace_order``) proves this from the
    traced block maps; CPU interpret mode runs an aliased call
    functionally and cannot.

    With ``halo_readers`` the shells of the OTHER quantities are stale (the
    step exchanged only what the kernel's footprint trace saw read
    off-centre): an off-centre ``sh`` on one of them in THIS trace raises
    and names it, so a kernel that traces differently the second time can
    never read a stale cell silently."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    nq = len(names)
    X, Y, Z = raws[0].shape
    r = x_radius
    assert r >= 1 and lo.x >= r and hi.x >= r, (r, lo, hi)
    assert lo.y >= r and hi.y >= r and lo.z >= r and hi.z >= r, (r, lo, hi)
    y0, y1 = lo.y, Y - hi.y
    z0, z1 = lo.z, Z - hi.z
    roll = _make_roll(interpret)
    gsize = global_size
    up = (lambda v: v.astype(jnp.float32)) if f32_accumulate else (lambda v: v)
    if writers is None or fused_shell is not None:
        wq = list(range(nq))
    else:
        wq = [q for q in range(nq) if names[q] in writers]
    if not wq:
        return list(raws)
    assert fused_shell is None or not renames, renames
    home = _output_homes(names, raws, wq, renames)
    if rings is None or fused_shell is not None:
        ringed = list(range(nq))
    else:
        ringed = [q for q in range(nq) if names[q] in rings]
    assert not wrap_fills or fused_shell is None
    assert all(a in (1, 2) for a, _, _, _ in wrap_fills), wrap_fills
    wrapped = [
        q for q in range(nq)
        if wrap_fills and (halo_readers is None or names[q] in halo_readers)
    ]
    assert window in ("raw", "interior", "interior-z"), window
    interior = window == "interior"
    carried = window == "interior-z"  # the y halo rows ride in the tiles
    aligned = interior or carried
    assert not aligned or fused_shell is None
    assert not aligned or plane_window_form(
        wrap_fills, lo, hi, (Y, Z), [b.dtype for b in raws]
    ) == window, (wrap_fills, lo, hi, (Y, Z))
    assert strip or not carried, window  # (the strip form only)
    # the working plane: what the rings hold and the kernel's windows are
    Yw, Zw = (y1 - y0, z1 - z0) if aligned else (Y, Z)
    low_fills = [f for f in wrap_fills if f[1] == 0]  # the fills of the LOW halos
    # the strip form: every quantity's planes sit in the scratch as ``K + 2r``
    # TILES of ``T`` rows -- tile ``k`` holds rows ``k, K + k, 2K + k, ...`` of
    # the working plane, one a sublane, between ``r`` margin tiles a side --,
    # a ringed quantity ``2r + 1`` planes deep (the newest is pushed BEFORE
    # the strips read it), every other one plane; and a writer's strips are
    # gathered in a staging plane of tiles before they go out
    T = sublane_tile([b.dtype for b in raws])
    K, G = Yw // T, strip // T
    assert not strip or (aligned and strip == G * T and K % G == 0 and r <= K), (
        strip, window, Yw, T)
    # where the plane's ``K`` tiles sit among a scratch plane's ``KT``, and the
    # tile the strips' first output tile is: between ``r`` margin tiles a side,
    # every tile an output -- or, where the y halo rows ride in the tiles, from
    # tile 0 on, ``M = lo.y + hi.y`` margin tiles behind them, the outputs the
    # ``K`` tiles from ``lo.y`` on (tile ``k`` holds RAW rows ``s K + k``)
    M = lo.y + hi.y
    t0, base, KT = (0, lo.y, K + M) if carried else (r, r, K + 2 * r)
    depth = 2 * r + 1 if strip else 2 * r
    held = list(range(nq)) if strip else ringed
    pre = [(names.index(nm), dx, dz) for nm, dx, dz in prerotated] if strip else []
    assert all(dz and (q in ringed or not dx) for q, dx, dz in pre), (prerotated, rings)

    no_ring, stale_read, unknown_output = _told_guards(
        names, halo_readers, rings, writers, set(wq) | set(home.values()))

    def body(origin_ref, *refs):
        in_refs = refs[:nq]
        if fused_shell is not None:
            xs_refs = refs[nq : 2 * nq]
            ys_refs = refs[2 * nq : 3 * nq]
            zs_refs = refs[3 * nq : 4 * nq]
            refs = refs[:nq] + refs[4 * nq :]
        out_refs = dict(zip(wq, refs[nq : nq + len(wq)]))  # writers only
        ring_refs = dict(zip(held, refs[nq + len(wq) :]))  # x readers only (the
        # strip form: everyone, as tiles)
        stage_refs = dict(zip(wq, refs[nq + len(wq) + len(held) :]))  # the strip form
        pre_refs = dict(zip(pre, refs[nq + 2 * len(wq) + len(held) :]))
        i = pl.program_id(0)
        if aligned:
            # every quantity, read off-centre or not: with its LOW halos
            # filled, the block's aligned (Yw, Zw) corner IS the interior,
            # rotated by (lo.y, lo.z) -- whole tiles, nothing shifted (beside a
            # split y: rotated by lo.z alone, rows [0, Yw) of the plane the
            # neighbours' halo rows extend)
            for ref in in_refs:
                _wrap_fill(ref, low_fills)  # y before z
            # (the strip form reads a plane where it is used: loaded here it
            # would be held, spilled, across every region of the body below)
            curs = _ReadAtUse(in_refs, Yw, Zw)
            if not strip:
                curs = [curs[q] for q in range(nq)]
        else:
            for q in wrapped:
                _wrap_fill(in_refs[q], wrap_fills)  # y before z
            curs = [ref[0] for ref in in_refs]
        if fused_shell is not None:
            # level-0 VMEM patch (module docstring; _fused_plane_patch)
            ip = jnp.minimum(i, X - 1)  # the replayed last-plane refetches
            t = jnp.where(
                jnp.logical_or(ip < lo.x, ip >= X - hi.x),
                jnp.int32(Y),
                jnp.int32(0),
            )
            for q in range(nq):
                curs[q] = _fused_plane_patch(
                    curs[q], xs_refs[q][0], ys_refs[q][0], zs_refs[q][0],
                    t, lo.y, hi.y, lo.z, hi.z,
                )

        # (row / lane ``k`` of either window is raw row / lane ``k``)
        y_g, z_g = _yz_coord_planes(origin_ref, Yw, Zw, lo.y, lo.z, gsize)

        def put(out, v, tail=None):
            """The working plane ``v`` into the output block: the raw plane
            whole; the rotated interior onto the block's aligned corner --
            its interior cells and its low halos at once --, then the raw
            rows and lanes past it, which repeat the plane's first ``lo +
            hi``: the y / z shell of the STORED plane, whole.  Beside a split
            y the rows past it are no repeat but the plane's own ``tail``
            rows (``tail_rows``), one at a time."""
            if not aligned:
                out[0] = v
                return
            out[0, :Yw, :Zw] = v
            if carried:
                for m, row in enumerate(tail):
                    out[0, Yw + m : Yw + m + 1, :Zw] = row
            else:
                out[0, Yw:, :Zw] = v[: Y - Yw, :]
            out[0, :, Zw:] = out[0, :, : Z - Zw]  # every row: the y-z corner too

        def tail_rows(q, t):
            """Beside a split y: raw rows ``[Yw, Y)`` of quantity ``q``'s raw
            plane ``i - t`` on the working plane's lanes -- its last ``lo.y``
            interior rows and its high y halo -- as ``(1, Zw)`` rows, read from
            the fetched block or from the last sublane of the ring plane's
            margin tiles (``tail_margins``); None on the other windows."""
            if not carried:
                return None
            if t == 0 or q not in ringed:  # (fetched lagged: the centre plane)
                return [in_refs[q][0, Yw + m : Yw + m + 1, :Zw] for m in range(M)]
            return [ring_refs[q][(i - t) % depth, K + m, T - 1 : T] for m in range(M)]

        # output plane j = i - r; window is raw planes j-r .. j+r
        j = i - r
        in_window = jnp.logical_and(j >= lo.x, j <= X - hi.x - 1)

        def to_tiles(v):
            """The working plane ``(Yw, Zw)`` as its ``K`` tiles ``(K, T, Zw)``:
            tile ``k`` holds row ``s K + k`` at sublane ``s``, so the plane's
            next row is the NEXT TILE's same sublane -- a y shift of a tile is
            another tile, an address and no rotate."""
            return jnp.swapaxes(v.reshape(T, K, Zw), 0, 1)

        def from_tiles(d):
            return jnp.swapaxes(d, 0, 1).reshape(Yw, Zw)

        def plane(q, t):  # raw plane i - t for quantity q (t in [0, 2r])
            if q not in ringed:  # fetched lagged: the centre plane alone
                return curs[q] if t == r else None
            if strip:  # the tiles between the margins
                return curs[q] if t == 0 else from_tiles(ring_refs[q][(i - t) % depth, t0 : t0 + K])
            return curs[q] if t == 0 else ring_refs[q][(i - t) % (2 * r)]

        def push_tiles(q, slot, margins):
            """The working plane of ``q`` into ``ring[slot]`` as tiles; with
            ``margins`` between the ``r`` tiles a side that continue it past its
            ends: tile ``K + m`` is tile ``m`` a sublane UP (row ``s K + K + m``
            is row ``(s + 1) K + m``; the last sublane wraps to row ``m`` of the
            plane: the y wraparound) and tile ``-m`` tile ``K - m`` a sublane
            down.  Beside a split y the margins are the plane's own next rows
            (``tail_margins``), whoever reads it: its last ``lo.y`` interior rows
            are among them."""
            ring_refs[q][slot, t0 : t0 + K] = to_tiles(curs[q])
            if carried:
                tail_margins(ring_refs[q], slot, in_refs[q])
            elif margins:
                wrap_margins(ring_refs[q], (slot,))

        def wrap_margins(ref, at):
            """The ``r`` margin tiles a side of the plane of tiles ``ref[at]``,
            from its own tiles (read back: the plane is not held live)."""
            low, high = ref[(*at, slice(K, K + r))], ref[(*at, slice(r, 2 * r))]
            ref[(*at, slice(0, r))] = roll(low, 1, 1).astype(ref.dtype)
            ref[(*at, slice(r + K, K + 2 * r))] = roll(high, -1, 1).astype(ref.dtype)

        def tail_margins(ref, slot, block):
            """Beside a split y: the ``M`` margin tiles BEHIND the plane of tiles
            ``ref[slot]``, which holds raw rows ``[0, Yw)`` of ``block``: tile ``K
            + m`` is tile ``m`` a sublane up, as above, but its last sublane is
            raw row ``Yw + m`` of the block -- the plane goes on where the
            periodic one wraps around -- so tiles ``[lo.y, lo.y + K)`` hold the
            interior rows, each once, and tiles ``[0, K + M)`` every raw row."""
            ref[slot, K : K + M] = roll(ref[slot, :M], -1, 1).astype(ref.dtype)
            for m in range(M):
                ref[slot, K + m, T - 1 : T] = block[0, Yw + m : Yw + m + 1, :Zw]

        if strip and ringed:  # FIRST: the strips read the newest plane there too

            @pl.when(i <= X - 1)  # (skip replayed last-plane refetches)
            def _():
                newest = i % depth
                for q in ringed:
                    push_tiles(q, newest, True)

        def strip_reader(q, slots, first):
            """``read(dx, dy, dz)`` of quantity ``q``'s ``StripView`` for one
            strip: ``slots[dx]`` the ring slot of window plane ``dx``,
            ``first[dy]`` the first of the strip's ``G`` tiles ``dy`` on (the y
            shift is an address) -- traced scalars, made once for all reads."""

            def tiles(ref, at, dy):
                if G == 1:
                    return up(ref[(*at, first[dy])])
                return up(ref[(*at, pl.ds(first[dy], G))].reshape(strip, Zw))

            unrotated = {}  # (dx, dy) -> the strip as read, for every dz of it

            def read(dx, dy, dz):
                if q not in ringed and dx:
                    return None
                if (q, dx, dz) in pre_refs:  # rotated once, for every strip and dy
                    return tiles(pre_refs[q, dx, dz], (), dy)
                if (dx, dy) not in unrotated:
                    at = (slots[dx] if q in ringed else 0,)
                    unrotated[dx, dy] = tiles(ring_refs[q], at, dy)
                v = unrotated[dx, dy]
                return roll(v, -dz, 1) if dz else v

            return read

        def window(q):
            return tuple(
                None if (v := plane(q, 2 * r - d)) is None else up(v)
                for d in range(2 * r + 1)
            )

        def x_global():
            return lax.rem(
                origin_ref[0] + jnp.int32(gsize.x) + j - jnp.int32(lo.x),
                jnp.int32(gsize.x),
            )

        def strips():
            """The window's output plane a strip at a time: the kernel is traced
            ONCE, over ``StripView``s, inside a loop over the ``K / G`` strips,
            its values are gathered as tiles and go to the output block whole."""

            for q in held:
                if q not in ringed:  # fetched lagged: the centre plane
                    push_tiles(q, 0, halo_readers is None or names[q] in halo_readers)

            # the planes of ``prerotated``, for all their readers: every tile of
            # a plane in one rotate, its margins as push_tiles makes them
            for (q, dx, dz), rotated in pre_refs.items():
                slot = (j + dx) % depth if q in ringed else 0
                if carried:  # the margins hold rows of their own: rotated with the rest
                    rotated[...] = roll(up(ring_refs[q][slot]), -dz, 2).astype(rotated.dtype)
                    continue
                tiles = roll(up(ring_refs[q][slot, r : r + K]), -dz, 2)
                rotated[r : r + K] = tiles.astype(rotated.dtype)
                wrap_margins(rotated, ())
            x_g = x_global()
            # row ``s K + g`` of the plane at row ``g T + s`` of a strip
            f = lax.broadcasted_iota(jnp.int32, (strip, 1), 0)
            rows0 = (f % T) * K + f // T
            if base != t0:  # (output tile ``k`` holds raw rows ``s K + lo.y + k``)
                rows0 = rows0 + (base - t0)

            def one(k, carry):
                k0 = k * G
                first = {dy: k0 + (base + dy) for dy in range(-r, r + 1)}
                # (made INSIDE the loop: carried in from outside, the seven
                # scalars cost the MHD loop 10% -- PERF.md PR 46, call 7)
                slots = {dx: (j + dx) % depth for dx in range(-r, r + 1)}
                views = {
                    names[q]: StripView(
                        strip_reader(q, slots, first), r, stale_read(names[q]),
                        no_ring(names[q]),
                    )
                    for q in range(nq)
                }
                y_s, _ = _yz_coord_planes(origin_ref, strip, Zw, lo.y, lo.z, gsize, rows0 + k0)
                vals = kernel(views, PlaneInfo(x_g, y_s, z_g, gsize, 1))
                unknown_output(vals)
                for q, stage in stage_refs.items():
                    v = vals[names[q]] if names[q] in vals else views[names[q]].center()
                    v = v.astype(stage.dtype)
                    if G == 1:
                        stage[k0] = v
                    else:
                        stage[pl.ds(k0, G)] = v.reshape(G, T, Zw)
                return carry

            lax.fori_loop(0, K // G, one, 0)
            for q, out in out_refs.items():
                if carried:
                    put_carried(out, q)
                else:
                    put(out, from_tiles(stage_refs[q][...]))

        def put_carried(out, q):
            """Beside a split y: the staged tiles of writer ``q`` -- tiles
            ``[lo.y, lo.y + K)`` of the plane of tiles -- into the output block.
            Those below ``K`` go out as they stand; the ``lo.y`` past it go one
            sublane DOWN onto tiles ``[0, lo.y)`` (row ``s K + K + m`` is row ``(s
            + 1) K + m``), where sublane 0, a low y halo row, passes through
            from the centre plane; what falls off their last sublane are the
            block's tail rows ``Yw + m``, and the high y halo rows behind those
            pass through from the centre plane too."""
            stage, centre = stage_refs[q], ring_refs[q]
            slot = j % depth if q in ringed else 0  # the centre plane's
            down = roll(stage[K - lo.y :], 1, 1).astype(stage.dtype)  # (lo.y, T, Zw)
            sublane = lax.broadcasted_iota(jnp.int32, down.shape, 1)
            head = jnp.where(sublane == 0, centre[slot, : lo.y], down)
            tail = [down[m, 0:1] for m in range(lo.y)] + [
                centre[slot, K + m, T - 1 : T] for m in range(lo.y, M)
            ]
            put(out, from_tiles(jnp.concatenate([head, stage[: K - lo.y]], axis=0)), tail)

        @pl.when(jnp.logical_and(i >= 1, i <= X + r - 1))
        def _():
            @pl.when(in_window)
            def _():
                if strip:
                    strips()
                    return
                views = {
                    names[q]: PlaneView(
                        window(q), roll, stale_read(names[q]), no_ring(names[q])
                    )
                    for q in range(nq)
                }
                info = PlaneInfo(x_global(), y_g, z_g, gsize, 1)
                vals = kernel(views, info)
                unknown_output(vals)
                for q, out in out_refs.items():
                    cent = plane(q, r)
                    if interior:  # the kernel's values are the plane, whole
                        put(out, vals[names[q]].astype(cent.dtype) if names[q] in vals else cent)
                        continue
                    out[0] = cent  # keep the y/z shell ring
                    if names[q] in vals:
                        out[0, y0:y1, z0:z1] = vals[names[q]][
                            y0:y1, z0:z1
                        ].astype(cent.dtype)

            @pl.when(jnp.logical_not(in_window))
            def _():
                for q, out in out_refs.items():
                    # shell plane j = i - r passes through from the ring
                    # (slot is garbage for i < r, where plane j < 0 doesn't
                    # exist — those writes land on out plane 0, which step
                    # i == r rewrites with the real pass-through)
                    put(out, plane(q, r), tail_rows(q, r))

        @pl.when(i == 0)
        def _():
            for q, out in out_refs.items():
                put(out, curs[q], tail_rows(q, 0))  # first plane passes through

        # push the fetched plane (skip replayed last-plane refetches)
        if ring_refs and not strip:

            @pl.when(i <= X - 1)
            def _():
                for q, ring in ring_refs.items():
                    ring[i % (2 * r)] = curs[q]

    in_specs = [pl.BlockSpec(memory_space=pltpu.SMEM)] + [
        pl.BlockSpec((1, Y, Z), lambda i: (jnp.minimum(i, X - 1), 0, 0))
        if q in ringed
        else pl.BlockSpec((1, Y, Z), lambda i: (jnp.clip(i - r, 0, X - 1), 0, 0))
        for q in range(nq)
    ]
    args = [origin.astype(jnp.int32), *raws]
    if fused_shell is not None:
        xs_list, ys_list, zs_list = fused_shell
        assert all(b.shape == (lo.x + hi.x, Y, Z) for b in xs_list)
        assert all(b.shape == (X, lo.y + hi.y, Z) for b in ys_list)
        assert all(b.shape == (X, lo.z + hi.z, Y) for b in zs_list)

        def xidx(i):
            # the x slab plane for shell positions; the long interior
            # stretch clamps to slot 0 (a constant index — no refetch)
            ip = jnp.minimum(i, X - 1)
            return (
                jnp.where(
                    ip < lo.x,
                    ip,
                    jnp.where(ip >= X - hi.x, lo.x + ip - (X - hi.x), 0),
                ),
                0,
                0,
            )

        in_specs += [pl.BlockSpec((1, Y, Z), xidx) for _ in range(nq)]
        in_specs += [
            pl.BlockSpec(
                (1, lo.y + hi.y, Z), lambda i: (jnp.minimum(i, X - 1), 0, 0)
            )
            for _ in range(nq)
        ]
        in_specs += [
            pl.BlockSpec(
                (1, lo.z + hi.z, Y), lambda i: (jnp.minimum(i, X - 1), 0, 0)
            )
            for _ in range(nq)
        ]
        args += list(xs_list) + list(ys_list) + list(zs_list)
    out_specs = tuple(
        pl.BlockSpec((1, Y, Z), lambda i: (jnp.clip(i - r, 0, X - 1), 0, 0))
        for _ in wq
    )
    out_shape = tuple(
        jax.ShapeDtypeStruct((X, Y, Z), raws[q].dtype) for q in wq
    )
    outs = pl.pallas_call(
        body,
        name=tm.KERNEL_STREAM_PLANE_PASS,
        grid=(X + r,),
        in_specs=in_specs,
        out_specs=out_specs if len(wq) > 1 else out_specs[0],
        out_shape=out_shape if len(wq) > 1 else out_shape[0],
        # operand 0 is origin; fused-shell side inputs sit after the raws,
        # so the map is the raw block a writer's output lives in -> its
        # place among the writers' outputs, whatever rides in
        input_output_aliases=(
            {1 + home[q]: k for k, q in enumerate(wq)} if alias else {}
        ),
        scratch_shapes=[
            pltpu.VMEM((2 * r, Yw, Zw), raws[q].dtype) for q in ringed
        ] if not strip else [
            pltpu.VMEM((depth if q in ringed else 1, KT, T, Zw), raws[q].dtype)
            for q in held
        ] + [pltpu.VMEM((K, T, Zw), raws[q].dtype) for q in wq] + [
            pltpu.VMEM((KT, T, Zw), raws[q].dtype) for q, _, _ in pre
        ],
        interpret=interpret,
        **_tpu_compiler_params(interpret),
    )(*args)
    result = list(raws)  # a non-writer comes back as the array that went in
    for q, o in zip(wq, outs if len(wq) > 1 else [outs]):
        result[home[q]] = raws[q]  # renamed: the handles swap (a no-op at home)
        result[q] = o
    return result


def stream_plane_pass_tiled(
    kernel: PlaneKernel,
    names: Sequence[str],
    raws: Sequence[jax.Array],  # per-quantity (X, Y, Z) shell-carrying blocks
    lo: Dim3,
    hi: Dim3,
    x_radius: int,
    origin: jax.Array,
    global_size: Dim3,
    tile_rows: int,  # rows ``Yt`` of a y tile of the working plane: what the
    # pipeline moves (``stream_plan.plan_plane_passes`` chooses it)
    strip: int,  # rows of a strip of the kernel (``plane_strip_rows``)
    alias: bool = False,
    interpret: bool = False,
    f32_accumulate: bool = False,
    halo_readers: Optional[Sequence[str]] = None,
    writers: Optional[Sequence[str]] = None,
    rings: Optional[Sequence[str]] = None,
    wrap_fills: Sequence[Tuple[int, int, int, int]] = (),
    shell_in: bool = True,
    shell_out: bool = True,
    renames: Sequence[Tuple[str, str]] = (),  # ``(p, q)``: writer ``q``'s new
    # value lands in ``p``'s block (``stream_plane_pass`` has the rule)
) -> List[jax.Array]:
    """``stream_plane_pass`` on one of its two ALIGNED windows in the strip
    form, for planes whose pipeline blocks do not fit VMEM whole: the pipeline
    moves ``(1, Yt, Z)`` Y TILES of a raw plane, ``NT = Yw / Yt`` of them and the
    block's tail rows a plane, on the grid ``(X + r + 1, NT + 1)`` -- x planes
    outer, y tiles inner -- and the planes the kernel reads are whole in VMEM
    scratch only.  Same kernel name, same values: every cell is bitwise the
    whole-plane form's (``tests/test_plane_tiles.py``).  The window is read off
    what the pass is told (``plane_window_form``), and decides ONE thing: how
    the two ENDS of a plane's y tiles are closed.

    What it holds.  Every quantity's working planes as TILES, one y tile after
    the other: y tile ``t`` is a small plane of its own, ``Kt = Yt / T`` tiles
    between its margin tiles (``stream_plane_pass``'s layout over ``Yt`` rows:
    tile ``k`` holds raw rows ``t Yt + s Kt + k``, one a sublane, so a y shift of
    a strip is another tile's address).  Its margins continue it into its
    NEIGHBOURS: the high margin tile ``m`` is tile ``m`` a sublane up with the
    next y tile's row ``m`` at its last sublane, the low one mirrors it
    (``link``), made as each tile lands.  A quantity read at ``dx != 0`` holds
    ``2r + 2`` such planes, every other TWO: a plane lands tile by tile WHILE
    the strips read the planes before it, so the window lags one plane more
    than the whole-plane form's (output plane ``j = i - r - 1`` at x step
    ``i``) and the newest slot is never read.

    The ends, on ``"interior"`` (the pass fills y and z itself): ``r`` margin
    tiles a side, and the last y tile's neighbour is the first -- the periodic
    wrap, one more ``link``.  The block's tail rows ``[Yw, Y)`` begin with the
    low y halo's wrap, so y tile 0 takes its rows ``[0, lo.y)`` from them, and
    the stored plane's tail rows are the first rows of its y tile 0.

    The ends, on ``"interior-z"`` (the mesh splits y: the rows outside the
    interior are a NEIGHBOUR's, put into the block by the step's exchange):
    nothing links the last y tile to the first.  The layout is ``stream_plane_
    pass``'s carried one a y tile: tiles over raw rows from tile 0 on -- the
    low y halo rows are y tile 0's own first rows, real cells --, no low
    margin, ``M = lo.y + hi.y`` high margin tiles, the LAST y tile's filled
    from the block's tail rows (its last ``lo.y`` interior rows and the high y
    halo; their z fill made first: the y-z corner of the x -> y -> z order).  The
    strips of y tile ``t`` compute the ``Kt`` tiles from ``lo.y`` on, raw rows
    ``[t Yt + lo.y, (t + 1) Yt + lo.y)``: every interior row once.  Out, the
    staged tiles go one sublane down as ``put_carried`` has it: the last
    ``lo.y`` rows a y tile computes belong to the NEXT block and wait in the
    stash (for the tail block after the last), the first block's rows ``[0,
    lo.y)`` and the tail's high halo rows pass through from the centre plane.

    A grid step ``(i, t)``.  In: the block's TAIL rows first (``t = 0``: raw rows
    ``[Yw, Y)``, kept in a one-tile stash), then y tiles ``0 .. NT - 1``; each
    has its low z halo filled in the pipeline's buffer as ever and goes into
    the scratch as tiles.  Out: y tile ``t`` of plane ``j`` -- the strips of
    that tile (the whole-plane form's loop over ``Kt / G`` strips, reading the
    ``2r + 1`` complete planes around ``j``), gathered in a one-tile staging
    block, or an x-shell plane's tile passed through -- onto the block's
    aligned corner, the z shell rebuilt behind it; the tail rows last (``t =
    NT``).  So a stored plane is, raw cell for raw cell, what the whole-plane
    form on the same window stores.

    In place (``alias``) is safe by planes: x step ``i`` fetches planes ``i``
    (ringed) and ``i - r`` (fetched lagged) and flushes plane ``i - r - 1``.
    The maps stand still where a plane index is clamped -- the out map until
    plane 0's real tiles come (x step ``r + 1``), the in maps once past plane
    ``X - 1`` -- so nothing is flushed before it is computed and nothing
    refetched after it was overwritten (``check_inplace_order`` judges the
    maps, which are the same on both windows).  With ``renames`` the output of
    a writer ``q`` has its home in ``p``'s block, as in ``stream_plane_pass``:
    cell for cell what it always was (the kernel's values, ``q``'s own x-shell
    planes and tail rows passed through), aliased onto raw ``p`` -- an operand
    of the pass whether the kernel reads it or not, fetched lagged, so its
    plane ``i - r`` is read before plane ``i - r - 1`` is flushed over it -- and
    the returned list holds ``raws[q]`` ITSELF under ``p``.  Not built:
    ``fused_shell``, ``prerotated``, the raw window -- ragged lanes or rows, a z
    the mesh splits -- (``plan_plane_passes`` does not tile those).

    The lanes behind the window.  Lanes ``[Zw, Z)`` of a raw row hold copies
    of the window's first lanes: this pass leaves them behind every row it
    stores, and only its own low z fill reads them back -- which after a call
    of this same pass changes nothing, lane 0 was computed as a cell of the
    window.  So a call BETWEEN two calls of one dispatch neither needs nor owes
    them, and of a 514-lane f32 row they are a fifth (8,128) tile with two live
    lanes.  ``shell_in=False``: the in blocks are ``(1, Yt, Zw)``, the aligned
    lane tiles alone, and nothing fills the low z halo (the caller says every
    block's lane 0 is already the window's cell: each was last written by this
    pass); ``shell_out=False`` (in place only): the out blocks are ``(1, Yt,
    Zw)`` and no z shell is rebuilt -- lanes ``[Zw, Z)`` of the aliased block
    keep what they held, stale.  Both true is the program as it was.  The maps,
    the grid and everything of y are the same in every form.  ``ops/stream.py
    _build_plane_step`` runs a dispatch's first call ``(True, False)``, the
    ``steps - 2`` calls between ``(False, False)`` in its loop and the last
    ``(False, True)``, so the dispatch leaves every raw cell as whole calls do
    (``stream_plan.plane_lane_forms`` lists them, ``plane_lanes_form`` says
    where; a dispatch of one step is one whole call, of two the two edge forms).
    At D3Q19 512^3 on one v5e a call reads 39.2 ms whole, 35.2 / 35.8 with one
    side narrow, 31.3 narrow both ways in the loop (PERF.md §6, PRs 54 and
    58), and every form traced is one more trace, lowering and Mosaic compile
    of the pass in ``setup_s``: +2.8 s for the third with the compile cache
    warm, +10% of that cell's set-up (PERF.md §6, PR 58)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    nq = len(names)
    X, Y, Z = raws[0].shape
    r = x_radius
    dtypes = [b.dtype for b in raws]
    window = plane_window_form(wrap_fills, lo, hi, (Y, Z), dtypes)
    assert window in ("interior", "interior-z"), (window, wrap_fills, lo, hi, (Y, Z))
    assert shell_out or alias, "the lanes a narrow call does not write are the aliased block's"
    carried = window == "interior-z"  # the y halo rows are a neighbour's
    Yw, Zw = Y - lo.y - hi.y, Z - lo.z - hi.z
    T = sublane_tile(dtypes)
    Yt, NT, M = tile_rows, Yw // tile_rows, Y - Yw
    Kt, G = Yt // T, strip // T
    # a y tile's margin tiles below and above its own ``Kt``, and the tile its
    # strips' first output tile is
    below, above, base = (0, M, lo.y) if carried else (r, r, r)
    KT = below + Kt + above
    assert NT * Yt == Yw and Kt * T == Yt and G and G * T == strip and Kt % G == 0, (
        tile_rows, strip, Yw, T)
    assert Kt >= max(below, above) and 0 < M <= T and lo.x >= r and hi.x >= r, (
        tile_rows, r, lo, hi)
    roll = _make_roll(interpret)
    gsize = global_size
    up = (lambda v: v.astype(jnp.float32)) if f32_accumulate else (lambda v: v)
    wq = list(range(nq)) if writers is None else [q for q in range(nq) if names[q] in writers]
    if not wq:
        return list(raws)
    home = _output_homes(names, raws, wq, renames)
    ringed = list(range(nq)) if rings is None else [q for q in range(nq) if names[q] in rings]
    depth = {q: 2 * r + 2 if q in ringed else 2 for q in range(nq)}
    low_z = [f for f in wrap_fills if f[0] == 2 and f[1] == 0]
    no_ring, stale_read, unknown_output = _told_guards(
        names, halo_readers, rings, writers, set(wq) | set(home.values()))

    def body(origin_ref, *refs):
        in_refs = refs[:nq]
        out_refs = dict(zip(wq, refs[nq : nq + len(wq)]))
        scratch = refs[nq + len(wq) :]
        held, stash_in = scratch[:nq], scratch[nq : 2 * nq]
        stage = dict(zip(wq, scratch[2 * nq : 2 * nq + len(wq)]))
        stash_out = dict(zip(wq, scratch[2 * nq + len(wq) :]))
        i, t = pl.program_id(0), pl.program_id(1)
        j = i - r - 1  # the output plane; its window is raw planes j - r .. j + r

        def link(ref, slot, before, after):
            """Y tile ``after`` continues y tile ``before`` of the plane
            ``ref[slot]``: the last sublane of ``before``'s high margin tiles is
            ``after``'s first rows, sublane 0 of ``after``'s low margin tiles
            ``before``'s last."""
            for m in range(above):
                ref[slot, before * KT + below + Kt + m, T - 1 : T] = (
                    ref[slot, after * KT + below + m, 0:1])
            for m in range(below):
                ref[slot, after * KT + m, 0:1] = ref[slot, before * KT + Kt + m, T - 1 : T]

        def land(qs, y):
            """Y tile ``y`` of the fetched planes of the quantities ``qs`` into
            their slots, as tiles between their margins (one region a
            condition, every quantity inside it: a region a quantity is
            nineteen times the trace); ``qs`` share a ring depth."""
            slot = i % depth[qs[0]]
            if not carried:

                @pl.when(y == 0)  # the low y halo: the block's own tail rows
                def _():
                    for q in qs:
                        in_refs[q][0, : lo.y, :Zw] = stash_in[q][: lo.y]

            for q in qs:
                ref = held[q]
                own = jnp.swapaxes(in_refs[q][0, :, :Zw].reshape(T, Kt, Zw), 0, 1)  # (Kt, T, Zw)
                ref[slot, pl.ds(y * KT + below, Kt)] = own
                if below:
                    ref[slot, pl.ds(y * KT, below)] = (
                        roll(own[Kt - below :], 1, 1).astype(ref.dtype))
                ref[slot, pl.ds(y * KT + below + Kt, above)] = (
                    roll(own[:above], -1, 1).astype(ref.dtype))
            if NT > 1:

                @pl.when(y >= 1)
                def _():
                    for q in qs:
                        link(held[q], slot, y - 1, y)

            @pl.when(y == NT - 1)
            def _():
                for q in qs:
                    if carried:  # the plane goes on into the block's tail rows
                        for m in range(M):
                            held[q][slot, (NT - 1) * KT + Kt + m, T - 1 : T] = (
                                stash_in[q][m : m + 1])
                    else:  # the periodic wrap: the first tile follows the last
                        link(held[q], slot, NT - 1, 0)

        if shell_in:
            for q in range(nq):
                _wrap_fill(in_refs[q], low_z)  # every row of the tile, the tail's too
        for lag, qs in ((0, ringed), (r, [q for q in range(nq) if q not in ringed])):
            if not qs:
                continue

            @pl.when(i <= X - 1 + lag)  # (not a clamped refetch)
            def _(qs=qs):
                @pl.when(t == 0)
                def _():
                    for q in qs:
                        stash_in[q][...] = in_refs[q][0, :T, :Zw]

                @pl.when(t >= 1)
                def _():
                    land(qs, t - 1)

        def from_tiles(d):  # a y tile's ``Kt`` tiles as its ``Yt`` rows
            return jnp.swapaxes(d, 0, 1).reshape(Yt, Zw)

        def put(out, v, rows, patch=None):
            """``rows`` working rows ``v`` onto the output block's aligned
            corner (``patch()`` then mends rows of it), the z shell of the
            stored rows rebuilt behind them."""
            out[0, :rows, :Zw] = v
            if patch is not None:
                patch()
            if shell_out:
                out[0, :rows, Zw:] = out[0, :rows, : Z - Zw]

        def slot_of(q, dx=0):
            """Where plane ``j + dx`` of ``q`` sits: a ringed plane ``p`` landed in
            slot ``p % depth``, one fetched lagged during the x step before."""
            return (j + dx) % depth[q] if q in ringed else (i - 1) % 2

        def tail_of_centre(q, m):
            """Beside a split y: raw row ``Yw + m`` of the centre plane, where
            ``land`` put it."""
            return held[q][slot_of(q), (NT - 1) * KT + Kt + m, T - 1 : T]

        def put_carried():
            """Beside a split y: the staged tiles of every writer -- tiles ``[lo.y,
            lo.y + Kt)`` of y tile ``t`` -- into output block ``t``, one sublane
            DOWN as ``stream_plane_pass``'s ``put_carried`` has it.  The block's
            rows ``[0, lo.y)`` are what the y tile BEFORE staged last (stashed) --
            before the first, the low y halo rows of the centre plane, passed
            through --, and what falls off this tile's last sublane waits in the
            stash for the next block."""

            @pl.when(t == 0)
            def _():
                for q in out_refs:
                    for m in range(lo.y):
                        stash_out[q][m : m + 1] = held[q][slot_of(q), m, 0:1]

            for q, out in out_refs.items():
                staged, stash = stage[q], stash_out[q]
                down = roll(staged[Kt - lo.y :], 1, 1).astype(staged.dtype)  # (lo.y, T, Zw)

                def patch():  # (``put`` calls it at once)
                    for m in range(lo.y):
                        out[0, m : m + 1, :Zw] = stash[m : m + 1]
                        stash[m : m + 1] = staged[Kt - lo.y + m, T - 1 : T]

                put(out, from_tiles(jnp.concatenate([down, staged[: Kt - lo.y]], axis=0)), Yt, patch)

        def strips(y):
            """Y tile ``y`` of the output plane, a strip at a time into the
            staging tile (``stream_plane_pass``'s loop over one y tile)."""
            x_g = lax.rem(
                origin_ref[0] + jnp.int32(gsize.x) + j - jnp.int32(lo.x), jnp.int32(gsize.x)
            )
            _, z_g = _yz_coord_planes(origin_ref, T, Zw, lo.y, lo.z, gsize)
            f = lax.broadcasted_iota(jnp.int32, (strip, 1), 0)
            # raw row of the plane at a strip's row (output tile ``k`` holds raw
            # rows ``y Yt + s Kt + k + base - below``)
            rows0 = y * Yt + (f % T) * Kt + f // T + (base - below)

            def one(k, carry):
                k0 = k * G
                first = {dy: y * KT + (k0 + base + dy) for dy in range(-r, r + 1)}

                def reader(q):
                    unrotated = {}

                    def read(dx, dy, dz):
                        if q not in ringed and dx:
                            return None
                        if (dx, dy) not in unrotated:
                            if G == 1:
                                v = held[q][slot_of(q, dx), first[dy]]
                            else:
                                v = held[q][slot_of(q, dx), pl.ds(first[dy], G)].reshape(strip, Zw)
                            unrotated[dx, dy] = up(v)
                        v = unrotated[dx, dy]
                        return roll(v, -dz, 1) if dz else v

                    return read

                views = {
                    names[q]: StripView(reader(q), r, stale_read(names[q]), no_ring(names[q]))
                    for q in range(nq)
                }
                y_s, _ = _yz_coord_planes(origin_ref, strip, Zw, lo.y, lo.z, gsize, rows0 + k0)
                vals = kernel(views, PlaneInfo(x_g, y_s, z_g, gsize, 1))
                unknown_output(vals)
                for q, staged in stage.items():
                    v = vals[names[q]] if names[q] in vals else views[names[q]].center()
                    v = v.astype(staged.dtype)
                    if G == 1:
                        staged[k0] = v
                    else:
                        staged[pl.ds(k0, G)] = v.reshape(G, T, Zw)
                return carry

            lax.fori_loop(0, Kt // G, one, 0)

        def interior_plane():  # is the output plane one the kernel computes?
            return jnp.logical_and(j >= lo.x, j <= X - hi.x - 1)

        @pl.when(jnp.logical_and(i >= r + 1, t <= NT - 1))
        def _():
            in_window = interior_plane()

            # (one region a condition, every writer inside it: see ``land``)
            @pl.when(in_window)
            def _():
                strips(t)
                if carried:
                    put_carried()
                else:
                    for q, out in out_refs.items():
                        put(out, from_tiles(stage[q][...]), Yt)

            @pl.when(jnp.logical_not(in_window))  # an x-shell plane passes through
            def _():
                for q, out in out_refs.items():
                    put(out, from_tiles(held[q][slot_of(q), pl.ds(t * KT + below, Kt)]), Yt)

            if not carried:

                @pl.when(t == 0)  # the stored plane's first rows: its tail rows too
                def _():
                    for q, out in out_refs.items():
                        stash_out[q][...] = out[0, :T, :Zw]

        @pl.when(jnp.logical_and(i >= r + 1, t == NT))
        def _():
            if carried:
                # the stash holds the last y tile's last ``lo.y`` rows; the high
                # y halo rows, and every tail row of an x-shell plane, pass
                # through from the centre plane
                for q in out_refs:
                    for m in range(lo.y, M):
                        stash_out[q][m : m + 1] = tail_of_centre(q, m)

                @pl.when(jnp.logical_not(interior_plane()))
                def _():
                    for q in out_refs:
                        for m in range(lo.y):
                            stash_out[q][m : m + 1] = tail_of_centre(q, m)

            for q, out in out_refs.items():
                put(out, stash_out[q][...], T)

    def in_map(lag):
        def index(i, t):
            live = i - lag <= X - 1  # past the last plane the map stands still
            return (
                jnp.clip(i - lag, 0, X - 1),
                jnp.where(live, (t + NT) % (NT + 1), NT - 1),  # the tail, then the tiles
                0,
            )

        return index

    def out_map(i, t):
        # (until plane 0's own tiles come the map stands still: nothing is flushed)
        return (jnp.clip(i - r - 1, 0, X - 1), jnp.where(i >= r + 1, t, 0), 0)

    outs = pl.pallas_call(
        body,
        name=tm.KERNEL_STREAM_PLANE_PASS,
        grid=(X + r + 1, NT + 1),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)] + [
            pl.BlockSpec((1, Yt, Z if shell_in else Zw), in_map(0 if q in ringed else r))
            for q in range(nq)
        ],
        out_specs=tuple(pl.BlockSpec((1, Yt, Z if shell_out else Zw), out_map) for _ in wq),
        out_shape=tuple(jax.ShapeDtypeStruct((X, Y, Z), raws[q].dtype) for q in wq),
        input_output_aliases={1 + home[q]: k for k, q in enumerate(wq)} if alias else {},
        scratch_shapes=[pltpu.VMEM((depth[q], NT * KT, T, Zw), raws[q].dtype) for q in range(nq)]
        + [pltpu.VMEM((T, Zw), raws[q].dtype) for q in range(nq)]
        + [pltpu.VMEM((Kt, T, Zw), raws[q].dtype) for q in wq]
        + [pltpu.VMEM((T, Zw), raws[q].dtype) for q in wq],
        interpret=interpret,
        **_tpu_compiler_params(interpret),
    )(origin.astype(jnp.int32), *raws)
    result = list(raws)  # a non-writer comes back as the array that went in
    for q, o in zip(wq, outs):
        result[home[q]] = raws[q]  # renamed: the handles swap (a no-op at home)
        result[q] = o
    return result


def stream_wavefront_pass(
    kernel: PlaneKernel,
    names: Sequence[str],
    raws: Sequence[jax.Array],  # per-quantity (Xr, Yr, Zr) FILLED-shell blocks
    m: int,  # levels to advance (<= shell width)
    s_off: int,  # shell width (raw index of the interior start)
    origin: jax.Array,
    global_size: Dim3,
    z_slabs: Sequence[jax.Array] = None,  # per-q (Xr, 2s, Yr) z-major slabs
    alias: bool = False,
    interpret: bool = False,
    f32_accumulate: bool = False,  # bf16-storage variant: upcast at load,
    # f32 level rings + arithmetic, one downcast at the final store/emit
    fused_shell=None,  # (xbufs, ybufs, zbufs) per quantity — the packed
    # halo messages land in the level-0 planes in VMEM (halo="fused");
    # mutually exclusive with z_slabs (the bespoke z-only scheme)
):
    """``m`` kernel levels in ONE pass over ``s_off``-shell-carrying shards —
    the user-kernel generalization of ``jacobi_shell_wavefront_step`` (see
    its docstring for the shrinking-validity contamination argument, the
    z-slab layout, and the lane-padding rationale; all carry over verbatim).
    Returns the advanced blocks, plus per-quantity outgoing z slabs when
    ``z_slabs`` is given.  In that form the blocks stay the domain's raw
    ``(Xr, Yr, Zr)`` ones and the LANE PADDING LIVES IN VMEM ONLY: every
    quantity streams through ``(1, Yr, Zp)`` blocks, ``Zp =
    lane_pad_width(Zr)`` -- a boundary block in the minor dimension, so the
    DMA brings ``Zr`` lanes into a ``Zp``-lane plane and writes ``Zr`` back
    -- and lanes [Zr, Zp) of each level-0 plane, whatever the VMEM block
    held, are set to zero (``domain.step`` says ``lane_pad: "vmem"``;
    ``"none"`` where ``Zr`` is whole lane tiles already).  Each level-0 plane
    gets its z halo from the slab block through
    ``jacobi_pallas.patch_z_halo``: on the lane-padded plane inside the lane
    tiles that hold the halo lanes -- tile 0 for [0, s), the one or two tiles
    over [Zr - s, Zr) -- and nowhere else (``z_halo_patch: "tile"``).

    With ``fused_shell`` the blocks' shell cells are STALE and every axis's
    fresh halos ride as side inputs (``fused_shell_exchange``): each
    level-0 plane is patched in VMEM — x-shell planes replaced, then y
    rows, then z columns (the exchange's sweep order) — so the level chain
    sees exactly the planes an in-array exchange would have produced and
    the pass output is bitwise-identical to the unfused form."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    nq = len(names)
    Xr, Yr, Zr = raws[0].shape
    # the working plane's width: whole lane tiles in the z-slab form
    Zp = lane_pad_width(Zr) if z_slabs is not None else Zr
    assert 1 <= m <= s_off and 2 * s_off < min(Xr, Yr, Zr), (m, s_off, Zr)
    assert z_slabs is None or fused_shell is None
    gsize = global_size
    assert 2 * s_off < gsize.x, (s_off, gsize)  # non-negative lax.rem operand
    roll = _make_roll(interpret)
    acc_dtypes = [
        jnp.float32 if f32_accumulate else b.dtype for b in raws
    ]
    up = (lambda v: v.astype(jnp.float32)) if f32_accumulate else (lambda v: v)

    def body(origin_ref, *refs):
        in_refs = refs[:nq]
        refs = refs[nq:]
        if fused_shell is not None:
            xs_refs = refs[:nq]
            ys_refs = refs[nq : 2 * nq]
            zsf_refs = refs[2 * nq : 3 * nq]
            refs = refs[3 * nq :]
        if z_slabs is not None:
            zs_refs = refs[:nq]
            out_refs = refs[nq : 2 * nq]
            zout_refs = refs[2 * nq : 3 * nq]
            rings = refs[3 * nq :]
        else:
            out_refs = refs[:nq]
            zout_refs = None
            rings = refs[nq :]
        i = pl.program_id(0)
        # level-0 raw plane i per quantity (upcast once under f32_accumulate)
        vals = [up(ref[0]) for ref in in_refs]
        y_g, z_g = _yz_coord_planes(origin_ref, Yr, Zp, s_off, s_off, gsize)
        if fused_shell is not None:
            # level-0 VMEM patch (module docstring; _fused_plane_patch —
            # upcast once under f32_accumulate, like the raw planes)
            s = s_off
            t = jnp.where(
                jnp.logical_or(i < s, i >= Xr - s), jnp.int32(Yr), jnp.int32(0)
            )
            for q in range(nq):
                vals[q] = _fused_plane_patch(
                    vals[q], up(xs_refs[q][0]), up(ys_refs[q][0]),
                    up(zsf_refs[q][0]), t, s, s, s, s,
                )
        if z_slabs is not None:
            # patch the z-shell columns in VMEM — never stored in the big
            # array (see jacobi_shell_wavefront_step) — in their lane tiles
            for q in range(nq):
                zst = up(jnp.swapaxes(zs_refs[q][0], 0, 1))  # (Yr, 2s)
                vals[q] = patch_z_halo(
                    _zero_lane_pad(vals[q], Zr), zst, s_off, 0, Zr - s_off, roll
                )
        for s in range(1, m + 1):
            prevs = [rings[q][s - 1, i % 2] for q in range(nq)]
            cents = [rings[q][s - 1, (i + 1) % 2] for q in range(nq)]
            for q in range(nq):
                rings[q][s - 1, i % 2] = vals[q]  # push plane i-s+1
            views = {
                names[q]: PlaneView((prevs[q], cents[q], vals[q]), roll)
                for q in range(nq)
            }
            x_g = lax.rem(
                origin_ref[0] + jnp.int32(gsize.x) + i - jnp.int32(s + s_off),
                jnp.int32(gsize.x),
            )
            info = PlaneInfo(x_g, y_g, z_g, gsize, s)
            new = kernel(views, info)
            vals = [
                new[names[q]].astype(cents[q].dtype)
                if names[q] in new
                else cents[q]
                for q in range(nq)
            ]
        for q in range(nq):
            # level-m plane i-m (the one f32_accumulate downcast)
            out_refs[q][0] = vals[q].astype(raws[q].dtype)
            if zout_refs is not None:
                emit = jnp.concatenate(
                    [
                        vals[q][:, Zr - 2 * s_off : Zr - s_off],
                        vals[q][:, s_off : 2 * s_off],
                    ],
                    axis=1,
                ).astype(raws[q].dtype)  # (Yr, 2s)
                zout_refs[q][0] = jnp.swapaxes(emit, 0, 1)

    out_idx = lambda i: (jnp.maximum(i - m, 0), 0, 0)
    in_specs = [pl.BlockSpec(memory_space=pltpu.SMEM)] + [
        pl.BlockSpec((1, Yr, Zp), lambda i: (i, 0, 0)) for _ in range(nq)
    ]
    out_specs: list = [pl.BlockSpec((1, Yr, Zp), out_idx) for _ in range(nq)]
    out_shape: list = [
        jax.ShapeDtypeStruct((Xr, Yr, Zr), b.dtype) for b in raws
    ]
    args = [origin.astype(jnp.int32), *raws]
    if fused_shell is not None:
        xs_list, ys_list, zs_list = fused_shell
        s = s_off
        assert all(b.shape == (2 * s, Yr, Zr) for b in xs_list)
        assert all(b.shape == (Xr, 2 * s, Zr) for b in ys_list)
        assert all(b.shape == (Xr, 2 * s, Yr) for b in zs_list)

        def xidx(i):
            # x slab slot for shell planes; interior clamps to a constant
            # slot 0 (no refetch over the long middle stretch)
            return (
                jnp.where(
                    i < s, i, jnp.where(i >= Xr - s, s + i - (Xr - s), 0)
                ),
                0,
                0,
            )

        in_specs += [pl.BlockSpec((1, Yr, Zr), xidx) for _ in range(nq)]
        in_specs += [
            pl.BlockSpec((1, 2 * s, Zr), lambda i: (i, 0, 0))
            for _ in range(nq)
        ]
        in_specs += [
            pl.BlockSpec((1, 2 * s, Yr), lambda i: (i, 0, 0))
            for _ in range(nq)
        ]
        args += list(xs_list) + list(ys_list) + list(zs_list)
    if z_slabs is not None:
        for q in range(nq):
            assert z_slabs[q].shape == (Xr, 2 * s_off, Yr), z_slabs[q].shape
        in_specs += [
            pl.BlockSpec((1, 2 * s_off, Yr), lambda i: (i, 0, 0))
            for _ in range(nq)
        ]
        out_specs += [pl.BlockSpec((1, 2 * s_off, Yr), out_idx) for _ in range(nq)]
        out_shape += [
            jax.ShapeDtypeStruct((Xr, 2 * s_off, Yr), b.dtype) for b in raws
        ]
        args += list(z_slabs)
    # in-place safe: out plane max(i - m, 0) trails in plane i by m >= 1
    # (the inplace-order contract, analysis/kernels.py, proves it from the
    # block maps).  Band-matrix inputs sit between the raws and the slabs,
    # so the alias map stays raw-q -> out-q regardless.
    aliases = {1 + q: q for q in range(nq)} if alias else {}
    outs = pl.pallas_call(
        body,
        name=tm.KERNEL_STREAM_WAVEFRONT_PASS,
        grid=(Xr,),
        in_specs=in_specs,
        out_specs=tuple(out_specs),
        out_shape=tuple(out_shape),
        input_output_aliases=aliases,
        scratch_shapes=[
            pltpu.VMEM((m, 2, Yr, Zp), acc) for acc in acc_dtypes
        ],
        interpret=interpret,
        **_tpu_compiler_params(interpret),
    )(*args)
    outs = list(outs)
    if z_slabs is not None:
        return outs[:nq], outs[nq:]
    return outs, None


def _periodic_margin(v, axis: int, ext: int, lo: int, roll):
    """``v`` grown by ``ext`` cells along ``axis`` so that cell ``lo + j`` of the
    result is cell ``j`` of ``v`` and the cells around it -- ``lo`` before, ``ext
    - lo`` behind -- are ``v``'s PERIODIC IMAGE: what a self-wrap exchange writes
    into a shell that wide.  The margin is built behind ``v`` (its first cells,
    and in the last ``lo`` places its last ones: one select over ``ext`` cells)
    and one rotate by ``lo`` carries those round to the front.  ``ext <= n``."""
    n = v.shape[axis]
    assert 0 <= lo <= ext <= n, (lo, ext, n)
    head = lax.slice_in_dim(v, 0, ext, axis=axis)
    last = lax.slice_in_dim(v, n - ext, n, axis=axis)
    at = lax.broadcasted_iota(jnp.int32, head.shape, axis)
    v = jnp.concatenate([v, jnp.where(at < ext - lo, head, last)], axis=axis)
    return roll(v, lo, axis) if lo else v


def wrap_edge_plane(raw_plane: Tuple[int, int], dtypes) -> Tuple[int, int]:
    """The ``(Yb, Zb)`` block ``stream_wrap_pass``'s edge forms move of a
    ``(Yr, Zr)`` raw plane: whole vector tiles of every stored dtype, a
    boundary block in both dims (the DMA moves ``Yr`` x ``Zr``)."""
    tile = sublane_tile(dtypes)
    return -(-raw_plane[0] // tile) * tile, lane_pad_width(raw_plane[1])


def stream_wrap_pass(
    kernel: PlaneKernel,
    names: Sequence[str],
    blocks: Sequence[jax.Array],  # per-quantity BARE (X, Y, Z) interiors
    k: int,  # temporal depth (1 <= k <= X//2)
    origin: jax.Array,  # (3,) int32 — global coords of the block start
    global_size: Dim3,
    interpret: bool = False,
    f32_accumulate: bool = False,  # bf16-storage variant (see
    # stream_wavefront_pass)
    interior: Tuple[Dim3, Dim3] = None,  # the edge forms: (lo, n), where the
    # bare interior sits in a RAW block and how large it is
    raw_in: bool = False,  # ``blocks`` are the domain's raw blocks
    raw_out: Sequence[jax.Array] = None,  # the raw blocks the results land in
    # (consumed: each is its output's buffer)
) -> List[jax.Array]:
    """``k`` kernel levels over the WHOLE (single-device) domain with the
    periodic wrap folded in — the user-kernel generalization of
    ``jacobi_wrap_step`` (see its docstring: the x-wrap rides the modular
    block index map with a ``2k``-step replay closing every level's ring;
    the y/z wrap is the natural roll wraparound on exact-sized planes).
    No shell, no exchange, ~8/k HBM bytes per cell per iteration.

    The two EDGE FORMS let a dispatch carry the domain's raw ``(Xr, Yr, Zr)``
    blocks at its two ends and nothing cut or landed by XLA between
    (``ops/stream.py _build_wrap_step``; ``domain.step`` says ``edges:
    "raw"``).  Both move a raw plane as ONE boundary block of whole tiles
    (``wrap_edge_plane``) at the x index ``lo.x +`` the bare one; the levels
    between work on the bare ``(Y, Z)`` plane exactly as above, so every
    interior value is the bare form's to the bit.

    * ``raw_in``: the level-0 plane is the ``[lo.y : lo.y + Y, lo.z : lo.z + Z]``
      window of the block -- two rotates on the whole-tile plane and an
      aligned cut.
    * ``raw_out``: the level-``k`` plane is placed at ``(lo.y, lo.z)`` of the
      whole-tile plane with its periodic image around it
      (``_periodic_margin``: on this route the domain is one periodic device,
      so that image is what ``exchange()`` writes there) and written into the
      ``raw_out`` block, which the output ALIASES and the kernel never reads
      (``pl.ANY``): the x halo planes, visited by no grid step, keep what the
      operand held.  Never both on the SAME buffers: a replay step would read
      a plane an earlier step has written.

    They exist where the interior is whole vector tiles no narrower than the
    margin (``ops/stream_plan.py wrap_edge_form``)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    nq = len(names)
    if interior is None:
        assert not raw_in and raw_out is None
        lo, (X, Y, Z) = Dim3(0, 0, 0), blocks[0].shape
    else:
        lo, (X, Y, Z) = interior
    raw_shape = blocks[0].shape if raw_in else (raw_out[0].shape if raw_out else None)
    assert 1 <= k <= X // 2, (k, X)
    roll = _make_roll(interpret)
    gsize = global_size
    acc_dtypes = [
        jnp.float32 if f32_accumulate else b.dtype for b in blocks
    ]
    up = (lambda v: v.astype(jnp.float32)) if f32_accumulate else (lambda v: v)
    if raw_shape is not None:
        Yb, Zb = wrap_edge_plane(raw_shape[1:], [b.dtype for b in blocks])

    def level0(ref, acc):  # level-0 plane i (mod X), bare
        v = up(ref[0])
        if raw_in:
            for axis, at in ((0, lo.y), (1, lo.z)):
                v = roll(v, -at, axis) if at else v
            v = v[:Y, :Z].astype(acc)  # (a narrow float comes back from a rotate as f32)
        return v

    def body(origin_ref, *refs):
        in_refs = refs[:nq]
        refs = refs[nq * (2 if raw_out else 1):]  # past the aliased operands
        out_refs = refs[:nq]
        rings = refs[nq:]
        i = pl.program_id(0)
        vals = [level0(ref, acc) for ref, acc in zip(in_refs, acc_dtypes)]
        y_g, z_g = _yz_coord_planes(origin_ref, Y, Z, 0, 0, gsize)
        for s in range(1, k + 1):
            prevs = [rings[q][s - 1, i % 2] for q in range(nq)]
            cents = [rings[q][s - 1, (i + 1) % 2] for q in range(nq)]
            for q in range(nq):
                rings[q][s - 1, i % 2] = vals[q]
            views = {
                names[q]: PlaneView((prevs[q], cents[q], vals[q]), roll)
                for q in range(nq)
            }
            x_g = lax.rem(
                origin_ref[0] + jnp.int32(gsize.x) + i - jnp.int32(s),
                jnp.int32(gsize.x),
            )
            info = PlaneInfo(x_g, y_g, z_g, gsize, s)
            new = kernel(views, info)
            vals = [
                new[names[q]].astype(cents[q].dtype)
                if names[q] in new
                else cents[q]
                for q in range(nq)
            ]
        for q in range(nq):
            v = vals[q]
            if raw_out:
                v = _periodic_margin(v, 0, Yb - Y, lo.y, roll)
                v = _periodic_margin(v, 1, Zb - Z, lo.z, roll)
            # level-k plane (i - k) % X (the one f32_accumulate downcast)
            out_refs[q][0] = v.astype(blocks[q].dtype)

    bare, edge = (1, Y, Z), (1, Yb, Zb) if raw_shape is not None else None
    in_specs = [pl.BlockSpec(memory_space=pltpu.SMEM)] + [
        pl.BlockSpec(edge, lambda i: (lo.x + i % X, 0, 0)) if raw_in
        else pl.BlockSpec(bare, lambda i: (i % X, 0, 0))
        for _ in range(nq)
    ]
    args = [origin.astype(jnp.int32), *blocks]
    aliases = {}
    if raw_out:
        in_specs += [pl.BlockSpec(memory_space=pl.ANY) for _ in range(nq)]
        args += list(raw_out)
        aliases = {1 + nq + q: q for q in range(nq)}
    outs = pl.pallas_call(
        body,
        name=tm.KERNEL_STREAM_WRAP_PASS,
        grid=(X + 2 * k,),
        in_specs=in_specs,
        out_specs=tuple(
            pl.BlockSpec(edge, lambda i: (lo.x + (i - k) % X, 0, 0)) if raw_out
            else pl.BlockSpec(bare, lambda i: ((i - k) % X, 0, 0))
            for _ in range(nq)
        ),
        out_shape=tuple(
            jax.ShapeDtypeStruct(raw_shape if raw_out else (X, Y, Z), b.dtype)
            for b in blocks
        ),
        input_output_aliases=aliases,
        scratch_shapes=[pltpu.VMEM((k, 2, Y, Z), acc) for acc in acc_dtypes],
        interpret=interpret,
        **_tpu_compiler_params(interpret),
    )(*args)
    # out_shape is always a tuple, so pallas returns a tuple even for nq=1
    return list(outs)
