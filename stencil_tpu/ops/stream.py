"""Plane-streaming engine for USER step kernels — fast by default.

In the reference, the stencil kernel is USER code: apps write plain CUDA
through ``Accessor`` (accessor.hpp:13-40, jacobi3d.cu:65-108,
astaroth_sim.cu:65-83) and the GPU cache hierarchy gives every such kernel
operand reuse for free.  The TPU analog of that cache reuse is an explicit
VMEM plane ring — which rounds 1-4 hard-coded into the jacobi/astaroth fast
paths.  This module is the generalization: it runs the SAME ``StepKernel``
signature that ``make_step``'s XLA route runs — ``views[name].sh(dx,dy,dz)``
reads plus ``info.coords()`` — but streams x-planes through VMEM so each HBM
plane is read once per pass instead of once per shifted operand (the XLA
slice formulation re-reads the block ~6x, measured 5-7.5 Gcells/s at 512^3
vs ~40+ for the streamed form).

Two routes, chosen by ``make_stream_step``:

* **plane** — one level per pass: exchange the shell of every quantity the
  kernel reads off-centre (the others' shells are read by nothing), then
  stream planes, a ``2r``-deep ring (``r`` = the kernel's declared x read
  distance) for every quantity read off-centre ALONG X and a lagged fetch
  for the others, writing back only the quantities the kernel returns
  with a value of their own (one returned as another's centre plane -- a
  leapfrog's ``u_prev <- u`` -- swaps handles with it instead).
  On a y or z axis the mesh does not split there is nothing to exchange:
  the pass fills that halo of every plane it loads from the plane itself,
  in VMEM (``pass_wrap_fills``), and the exchange sweeps the other axes.
  Works for any per-axis shell widths and any ``r >= 1``.  A step may be
  several STAGES (a sequence of kernels, each behind its own exchange) and
  a stage several PASSES, each over the quantities its outputs touch: all
  planned from one abstract trace of each kernel (``plan_plane_stages``).
* **wavefront** — ``m`` levels per pass over an ``s``-wide-shell shard
  (``m <= s // r``, ``r == 1`` only): each HBM plane is read and written
  once per ``m`` iterations (~``8/m`` B/cell), the temporal blocking that
  makes the flagship paths beat the bandwidth roofline.  Supports the z-slab
  form (z halos never touch the tiled array — see
  ``jacobi_shell_wavefront_step``'s layout notes) including the lane-padding
  of ragged plane widths, generalized to any field count.

The engine is bit-compatible with the XLA route: both call the user kernel
with the same per-cell arithmetic, so outputs agree exactly (modulo compiler
excess precision, which the interpret-mode tests pin).

**Split-step overlap schedule** (``overlap ∈ {off, split}``, a tuner axis —
docs/tuning.md "Stream overlap"): the exchange-then-compute macro serializes
the packed shell ppermutes against the whole pass.  Under ``split`` the
macro is restructured so XLA's latency-hiding scheduler can fly the
collectives behind the bulk of the VPU work (the reference's L6
interior/exterior orchestration, src/stencil.cu:567-666; T3/arxiv
2401.16677 is the modern treatment):

* the **interior pass** is the unchanged full-block pass run on the
  PRE-exchange blocks — it carries no data dependency on any ppermute, so
  the scheduler issues ``collective-permute-start`` before it and ``-done``
  after it.  Cells within the dependency cone of the (stale) shell compute
  garbage there, by design;
* the **exterior passes** recompute exactly that boundary band — six narrow
  sub-block passes (width ``3w`` rounded up to the axis tile granule,
  ``w = m·r``) over the freshly exchanged blocks, running the SAME pallas
  kernels so every recomputed cell is
  bitwise identical to the off-schedule value — and blend the width-``w``
  bands back tile-locally (``ops/halo_blend``; x bands are contiguous
  plane DUS).

Correctness rests on two invariants the tier-1 suites pin: (a) a cell at
distance ≥ ``w`` from the shell has a per-level dependency cone that never
reads shell values, so interior-pass values equal off-schedule values
bitwise; (b) the 3-sweep exchange's output halos depend only on interior
values — each sweep's surviving writes come from interior slabs or halos
freshly written by an earlier sweep of the same exchange — so the stale
shell the split schedule carries between macros can never leak into any
valid cell.  Shell cells of a split-step output differ from the off
schedule (stale pass-through vs fresh), which is already sacrificial state:
stream steps mark the shell stale and every consumer re-exchanges.

Structurally ``split`` engages on the ``plane`` and plain ``wavefront``
routes; ``wrap`` has no exchange to hide and the z-slab wavefront
interleaves its slab permutes with the pass, so both degrade to ``off``
with a warning.  Padded (uneven) shards ARE supported: the high-side band
offsets ride the same traced ``n_valid`` arithmetic as the exchange's
dynamic halo blends.

**Fused unpack→blend** (``halo ∈ {array, fused}``, a tuner axis —
docs/tuning.md "Fused halo consumption"): under the packed ``yzpack_*``
exchange routes the macro's unpack step is redundant — the received shell
messages are blended into the big array only so the pass can read them
back out one plane later.  ``halo="fused"`` removes the round trip: the
macro calls ``fused_shell_exchange`` (ops/exchange.py), which returns the
received per-axis shell BUFFERS (corner-patched on the small buffers in
the exchange's sweep order), and the pass consumes them as side inputs —
each level-0 plane is patched in VMEM (x-shell planes replaced from the x
slabs, then y rows from the sublane-major y buffer, then z columns from
the lane-major z buffer, replaying the x→y→z sweep order) before any
kernel level runs.  The big array is NEVER written with halo data: no
blend kernels, no halo DUS, no unpack kernels — the generalization of the
z-slab wavefront's bespoke zero-big-array-halo scheme to every axis of
the plane and plain-wavefront routes.  Because the patched level-0 planes
are bitwise equal to the unfused post-exchange planes, every pass output
— interior AND shell — is bitwise-identical to ``halo="array"``.
Structural gates: the ``yzpack_*`` exchange route, even shards (the pack
cuts at static offsets), blend-supported dtypes, ``overlap=off`` (the
split schedule's exterior bands read exchanged BLOCKS), and the plane /
plain-wavefront routes (a z-slab plan re-plans to the plain form first,
like split).  Ineligible requests degrade to ``array`` with a warning;
the ladder steps ``fused``→``array`` at the same depth before any depth
descent.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from stencil_tpu.core.dim3 import Dim3
from jax import shard_map
from stencil_tpu import telemetry
from stencil_tpu.telemetry import names as tm
from stencil_tpu.ops.jacobi_pallas import (
    _make_roll,
    _padded_plane_bytes,
    _tpu_compiler_params,
    _vmem_budget,
    _VMEM_STACK_MARGIN,
    _WRAP_MAX_K,
    patch_z_halo,
    z_halo_patch_form,
)


#: overlap schedules for the exchanging stream routes — a first-class tuner
#: axis (tune/space.py ``stream_space``; docs/tuning.md "Stream overlap"):
#: ``off`` = exchange-then-compute (the static fallback), ``split`` = the
#: interior/exterior split-step schedule (see module docstring).
STREAM_OVERLAP = ("off", "split")

#: halo consumption for the exchanging stream routes — a first-class tuner
#: axis (tune/space.py ``stream_space``; docs/tuning.md "Fused halo
#: consumption"): ``array`` = the exchange unpacks received shells into the
#: big arrays and the pass reads them back (the static fallback), ``fused``
#: = the packed messages land directly in the pass's level-0 VMEM working
#: planes and the big array never sees a halo write (see module docstring).
STREAM_HALO = ("array", "fused")


class PlaneView:
    """Resident-plane window for one quantity inside a streaming kernel.

    ``sh(dx, dy, dz)`` mirrors ``ShardView.sh`` (the reference's
    ``src[o + Dim3(dx,dy,dz)]`` Accessor read, accessor.hpp:27-40): the
    x offset selects one of the ``2r+1`` VMEM-resident planes, the y/z
    offsets are in-plane rotates.  Rotate wraparound at the plane edges only
    contaminates shell cells the validity contract already sacrifices.

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


def _yz_coord_planes(origin_ref, Yr, Zr, off_y, off_z, gsize):
    """Wrapped global y/z coordinates of the raw plane, as a (Yr, 1) column
    and a (1, Zr) row (2D iotas — Mosaic has no 1D iota)."""
    y = lax.broadcasted_iota(jnp.int32, (Yr, 1), 0)
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
    their places (``_build_stream_step``).

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
    # the quantity whose block a writer's output lives in (aliases, under
    # ``alias``): its own, or the one whose name its old block takes
    home = {q: q for q in wq}
    for p_name, q_name in renames:
        p, q = names.index(p_name), names.index(q_name)
        assert fused_shell is None and q in home and p not in home, (renames, writers)
        assert raws[p].dtype == raws[q].dtype, (p_name, q_name)
        home[q] = p
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

    def body(origin_ref, *refs):
        in_refs = refs[:nq]
        if fused_shell is not None:
            xs_refs = refs[nq : 2 * nq]
            ys_refs = refs[2 * nq : 3 * nq]
            zs_refs = refs[3 * nq : 4 * nq]
            refs = refs[:nq] + refs[4 * nq :]
        out_refs = dict(zip(wq, refs[nq : nq + len(wq)]))  # writers only
        ring_refs = dict(zip(ringed, refs[nq + len(wq) :]))  # x readers only
        i = pl.program_id(0)
        for q in wrapped:
            for axis, dst, src, w in wrap_fills:  # y before z
                if axis == 1:
                    in_refs[q][0, dst : dst + w, :] = in_refs[q][0, src : src + w, :]
                else:
                    in_refs[q][0, :, dst : dst + w] = in_refs[q][0, :, src : src + w]
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

        y_g, z_g = _yz_coord_planes(origin_ref, Y, Z, lo.y, lo.z, gsize)

        # output plane j = i - r; window is raw planes j-r .. j+r
        j = i - r
        in_window = jnp.logical_and(j >= lo.x, j <= X - hi.x - 1)

        def plane(q, t):  # raw plane i - t for quantity q (t in [0, 2r])
            if q not in ring_refs:  # fetched lagged: the centre plane alone
                return curs[q] if t == r else None
            return curs[q] if t == 0 else ring_refs[q][(i - t) % (2 * r)]

        def window(q):
            return tuple(
                None if (v := plane(q, 2 * r - d)) is None else up(v)
                for d in range(2 * r + 1)
            )

        @pl.when(jnp.logical_and(i >= 1, i <= X + r - 1))
        def _():
            @pl.when(in_window)
            def _():
                views = {
                    names[q]: PlaneView(
                        window(q), roll, stale_read(names[q]), no_ring(names[q])
                    )
                    for q in range(nq)
                }
                x_g = lax.rem(
                    origin_ref[0] + jnp.int32(gsize.x) + j - jnp.int32(lo.x),
                    jnp.int32(gsize.x),
                )
                info = PlaneInfo(x_g, y_g, z_g, gsize, 1)
                vals = kernel(views, info)
                for q, name in enumerate(names):
                    if name in vals and q not in out_refs and q not in home.values():
                        raise ValueError(
                            f"the kernel returns {name!r}, but its footprint "
                            f"trace did not (it saw {tuple(writers)}), so "
                            f"{name!r} is not an output of the pass: a kernel "
                            "must return the same names every time it is traced"
                        )
                for q, out in out_refs.items():
                    cent = plane(q, r)
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
                    out[0] = plane(q, r)

        @pl.when(i == 0)
        def _():
            for q, out in out_refs.items():
                out[0] = curs[q]  # first plane passes through

        # push the fetched plane (skip replayed last-plane refetches)
        if ring_refs:

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
            pltpu.VMEM((2 * r, Y, Z), raws[q].dtype) for q in ringed
        ],
        interpret=interpret,
        **_tpu_compiler_params(interpret),
    )(*args)
    result = list(raws)  # a non-writer comes back as the array that went in
    for q, o in zip(wq, outs if len(wq) > 1 else [outs]):
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
) -> List[jax.Array]:
    """``k`` kernel levels over the WHOLE (single-device) domain with the
    periodic wrap folded in — the user-kernel generalization of
    ``jacobi_wrap_step`` (see its docstring: the x-wrap rides the modular
    block index map with a ``2k``-step replay closing every level's ring;
    the y/z wrap is the natural roll wraparound on exact-sized planes).
    No shell, no exchange, ~8/k HBM bytes per cell per iteration."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    nq = len(names)
    X, Y, Z = blocks[0].shape
    assert 1 <= k <= X // 2, (k, X)
    roll = _make_roll(interpret)
    gsize = global_size
    acc_dtypes = [
        jnp.float32 if f32_accumulate else b.dtype for b in blocks
    ]
    up = (lambda v: v.astype(jnp.float32)) if f32_accumulate else (lambda v: v)

    def body(origin_ref, *refs):
        in_refs = refs[:nq]
        refs = refs[nq:]
        out_refs = refs[:nq]
        rings = refs[nq:]
        i = pl.program_id(0)
        vals = [up(ref[0]) for ref in in_refs]  # level-0 plane i (mod X)
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
            # level-k plane (i - k) % X (the one f32_accumulate downcast)
            out_refs[q][0] = vals[q].astype(blocks[q].dtype)

    in_specs = [pl.BlockSpec(memory_space=pltpu.SMEM)] + [
        pl.BlockSpec((1, Y, Z), lambda i: (i % X, 0, 0)) for _ in range(nq)
    ]
    args = [origin.astype(jnp.int32), *blocks]
    outs = pl.pallas_call(
        body,
        name=tm.KERNEL_STREAM_WRAP_PASS,
        grid=(X + 2 * k,),
        in_specs=in_specs,
        out_specs=tuple(
            pl.BlockSpec((1, Y, Z), lambda i: ((i - k) % X, 0, 0))
            for _ in range(nq)
        ),
        out_shape=tuple(
            jax.ShapeDtypeStruct((X, Y, Z), b.dtype) for b in blocks
        ),
        scratch_shapes=[pltpu.VMEM((k, 2, Y, Z), acc) for acc in acc_dtypes],
        interpret=interpret,
        **_tpu_compiler_params(interpret),
    )(*args)
    # out_shape is always a tuple, so pallas returns a tuple even for nq=1
    return list(outs)


def stream_vmem_fits(
    m: int, plane_y: int, plane_z: int, itemsizes: Sequence[int], z_slabs: bool,
    ring_itemsizes: Sequence[int] = None,
) -> bool:
    """VMEM model of the generic wavefront: per quantity, 2m ring planes +
    4 pipeline planes (+ 4 z-slab blocks), plus a PER-QUANTITY stack margin —
    the level loop holds each field's roll/select temporaries live at once
    (measured: 8-field m=2 at 518x640 planes reported 108.6 MB against an
    85 MB block model, ~2.6 MB of stack per field).  Same padded-bytes
    accounting as ``wavefront_vmem_bytes``.  ``ring_itemsizes`` overrides
    the ring planes' itemsizes: bf16 STORAGE streams 2-byte pipeline planes
    but carries its level rings at f32 (the ``f32_accumulate`` contract),
    so the rings must be modeled at the NATIVE itemsize or the gate lies."""
    ring = itemsizes if ring_itemsizes is None else ring_itemsizes
    est = 0
    for it, rit in zip(itemsizes, ring):
        est += 2 * m * _padded_plane_bytes(plane_y, plane_z, rit)
        est += 4 * _padded_plane_bytes(plane_y, plane_z, it)
        if z_slabs:
            est += 4 * _padded_plane_bytes(2 * m, plane_y, it)
    return est + _VMEM_STACK_MARGIN * len(itemsizes) <= _vmem_budget()


def _tuned_stream_plan(dd, x_radius: int, separable: bool) -> dict:
    """A structurally VALID persisted plan for this domain from the
    autotuner, or None.  Validity is re-checked here (not trusted from the
    file): the cache key pins chip/shape/dtype/mesh/radius/route, but a
    hand-edited or cross-version file must degrade to the static plan, not
    crash the build."""
    from stencil_tpu import tune

    cfg = tune.best_config(dd.tune_key("stream"))
    if cfg is None:
        return None
    route = cfg.get("route")
    m = cfg.get("m")
    plan = {
        "route": route,
        "m": m,
        "z_slabs": bool(cfg.get("z_slabs", False)),
        "grouping": cfg.get("grouping", "joint"),
    }
    if cfg.get("alias") is not None:
        plan["alias"] = bool(cfg["alias"])
    # the overlap axis joined the persisted vocabulary WITHOUT a schema bump:
    # pre-overlap (v2-era) entries simply lack the key, and the resolver
    # falls through to the static ``off`` — warm caches stay warm.  A
    # present-but-garbage value invalidates the plan below (miss to static,
    # never a crash), like any other hand-edited field.
    if cfg.get("overlap") is not None:
        plan["overlap"] = cfg["overlap"]
    # ...and so does the fused-halo axis: pre-halo entries lack the key and
    # resolve to the static "array"; garbage invalidates to static
    if cfg.get("halo") is not None:
        plan["halo"] = cfg["halo"]
    n = dd.local_spec().sz
    shell = dd._shell_radius
    lo, hi = shell.lo(), shell.hi()
    padded = any(v is not None for v in dd._valid_last)
    ok = isinstance(m, int) and m >= 1
    if ok and plan.get("overlap") is not None:
        ok = plan["overlap"] in STREAM_OVERLAP
    if ok and plan.get("halo") is not None:
        ok = plan["halo"] in STREAM_HALO
    if ok and plan["grouping"] == "per-field":
        ok = separable and len(dd._handles) > 1
    elif ok and plan["grouping"] != "joint":
        ok = False
    if ok and route == "wrap":
        ok = dd.num_subdomains() == 1 and x_radius == 1 and m <= n.x // 2
    elif ok and route == "wavefront":
        uniform = len({lo.x, lo.y, lo.z, hi.x, hi.y, hi.z}) == 1
        v_min = min(
            (dd._valid_last[ax] if dd._valid_last[ax] is not None else n[ax])
            for ax in range(3)
        )
        ok = (
            x_radius == 1
            and uniform
            and lo.x >= 2
            and 2 <= m <= min(lo.x, v_min)
            and not (plan["z_slabs"] and padded)
        )
    elif ok and route == "plane":
        ok = m == 1 and not plan["z_slabs"]
    elif ok:
        ok = False
    if not ok:
        from stencil_tpu.utils.logging import log_warn

        log_warn(
            f"tuned stream config {cfg} is structurally invalid for this "
            "domain (shell/shards changed since it was measured?); using "
            "the static plan"
        )
        return None
    return plan


def plan_stream(dd, x_radius: int, path: str = "auto", separable: bool = False,
                max_m: int = None) -> dict:
    """Route planning for ``make_stream_step`` on a REALIZED domain.

    Returns ``{"route": "wrap"|"wavefront"|"plane", "m": int,
    "z_slabs": bool, "grouping": str}``.  On a SINGLE subdomain the wrap
    route wins (periodic boundary folded into the kernel: no shell reads,
    no exchange, deepest temporal blocking).  Wavefront needs: x_radius 1,
    uniform face shell >= 2; depth m = the deepest level count that fits
    the VMEM model, capped by the shell width and the measured plateau
    (_WRAP_MAX_K).  The plane route covers everything else the engine
    supports.

    PADDED (uneven) shards run BOTH routes: the exchange blends each halo at
    the dynamic valid-width offset, i.e. contiguously after the valid cells,
    so (a) every valid cell's stencil reads the right neighbor, (b) the
    wrapped linear coordinate formula ``(origin - s + index) mod g`` is
    correct at the halo positions too (the global size equals the last
    shard's origin + valid width), and (c) pad cells beyond the halo
    contaminate only the sacrificial shrinking-validity levels — the same
    argument as the wavefront's dead lane padding.  Hence the PLAIN
    wavefront works on padded shards with no kernel changes; only the
    z-slab form (static emit slices at the interior z boundary) stays
    even-shard-only, and the depth is additionally capped by the smallest
    VALID extent (a shard narrower than the shell cannot fill its
    neighbor's halo).

    ``path`` forces a route: "plane" skips the wavefront upgrade (per-step
    exchange parity, e.g. comm-volume modeling); "wavefront" raises instead
    of falling back.  Raises ValueError for N-D component data (the engine
    streams scalar planes only).

    ``separable=True`` declares that the kernel handles arbitrary SUBSETS of
    the views dict (each field's update reads only that field — astaroth's
    per-field mean).  When all fields together blow the VMEM model, the plan
    then falls back to per-field kernel calls ("grouped": one streaming pass
    per field per macro, same total HBM traffic) instead of a shallower m.
    ``max_m`` caps the wavefront depth (the runtime compile-failure fallback
    steps it down).
    """
    if any(h.components for h in dd._handles):
        raise ValueError("the streaming engine does not support N-D component data")
    if path not in ("auto", "plane", "wavefront", "wrap"):
        raise ValueError(f"unknown stream path {path!r}")
    # the autotuner's persisted pick wins over the static model below, but
    # only on the unconstrained auto path: a forced route is an explicit
    # request, and a depth cap (user stream_depth / the ladder's compile-
    # failure step-down) must re-plan statically under the cap rather than
    # re-apply the tuned depth that just failed
    if path == "auto" and max_m is None:
        tuned = _tuned_stream_plan(dd, x_radius, separable)
        if tuned is not None:
            return tuned
    padded = any(v is not None for v in dd._valid_last)
    shell = dd._shell_radius
    lo, hi = shell.lo(), shell.hi()
    n = dd.local_spec().sz
    if not all(lo[ax] >= x_radius and hi[ax] >= x_radius for ax in range(3)):
        raise ValueError(
            f"shell {lo}/{hi} narrower than the kernel x_radius {x_radius}"
        )
    uniform = len({lo.x, lo.y, lo.z, hi.x, hi.y, hi.z}) == 1
    s = lo.x
    # pipeline planes stream at the STORAGE itemsize; the level rings carry
    # the f32_accumulate working precision, i.e. the native itemsize
    itemsizes = [dd.field_dtype(h).itemsize for h in dd._handles]
    ring_sizes = [h.dtype.itemsize for h in dd._handles]
    # single device: the WRAP route folds the periodic boundary into the
    # kernel's index maps/rotates — no shell reads, no exchange, the deepest
    # temporal blocking (the user-kernel analog of jacobi_wrap_step)
    if path in ("auto", "wrap") and dd.num_subdomains() == 1 and x_radius == 1:
        cap = min(_WRAP_MAX_K, n.x // 2)
        if max_m is not None:
            cap = min(cap, max_m)
        best = None
        for grouping, sizes, rsizes in (
            [("joint", itemsizes, ring_sizes)]
            + (
                [("per-field", [max(itemsizes)], [max(ring_sizes)])]
                if separable and len(itemsizes) > 1
                else []
            )
        ):
            k = 0
            for cand in range(1, cap + 1):
                if stream_vmem_fits(cand, n.y, n.z, sizes, False, rsizes):
                    k = cand
            # deepest k across groupings — depth is the traffic lever
            # (~8/k B/cell/iter); joint wins ties
            if k >= 1 and (best is None or k > best["m"]):
                best = {"route": "wrap", "m": k, "z_slabs": False, "grouping": grouping}
        if best is not None:
            return best
    if path == "wrap":
        raise ValueError(
            "path='wrap' needs a single subdomain with >= 2 x-planes, "
            "x_radius 1, and VMEM for at least one resident plane ring"
        )
    if path != "plane" and x_radius == 1 and uniform and s >= 2:
        # (No shell-traffic heuristic here: the shell width s is GIVEN — the
        # domain already allocated and exchanges it — so advancing more
        # levels per exchange is strictly less traffic.)  realize() already
        # rejects any shard whose valid extent is below the shell width
        # (domain.py "subdomain ... smaller than radius shell"), so every
        # shard this plan can see fills an s-wide halo from valid cells.
        v_min = min(
            (dd._valid_last[ax] if dd._valid_last[ax] is not None else n[ax])
            for ax in range(3)
        )
        assert v_min >= s, (v_min, s)  # the realize() invariant
        cap = min(s, _WRAP_MAX_K)
        if max_m is not None:
            cap = min(cap, max_m)
        raw = dd.local_spec().raw_size()
        zp = -(-raw.z // 128) * 128
        # evaluate joint (all fields per pass) AND per-field grouping for
        # separable kernels, then take the DEEPEST m — depth is the traffic
        # lever (~8/m B/cell/iter); grouping only changes VMEM pressure and
        # per-pass ramp overhead, so joint wins ties
        group_options = [("joint", itemsizes, ring_sizes)]
        if separable and len(itemsizes) > 1:
            group_options.append(
                ("per-field", [max(itemsizes)], [max(ring_sizes)])
            )
        best = None
        # z-slab form's static emit slices assume even shards
        z_modes = ((False, raw.z),) if padded else ((True, zp), (False, raw.z))
        for grouping, sizes, rsizes in group_options:
            for z_mode, plane_z in z_modes:
                m = 0 if z_mode else 1
                for cand in range(2, cap + 1):
                    if stream_vmem_fits(cand, raw.y, plane_z, sizes, z_mode, rsizes):
                        m = cand
                if m >= 2 and (best is None or m > best["m"]):
                    best = {
                        "route": "wavefront",
                        "m": m,
                        "z_slabs": z_mode,
                        "grouping": grouping,
                    }
                if m >= 2:
                    # take the z-slab form for this grouping even if the
                    # plain form could fit a level deeper (its slab blocks
                    # are tiny): the plain form pays the ~64x-amplified
                    # thin-z in-array exchange every macro (probe12d)
                    break
        if best is not None:
            return best
    if path == "wavefront":
        raise ValueError(
            "path='wavefront' needs x_radius 1, a uniform face shell >= 2, "
            "valid shard extents >= the depth, and VMEM for m >= 2; got "
            f"shell {lo}/{hi}"
        )
    raw = dd.local_spec().raw_size()
    grouping = "joint"
    # the PLANE pass's ring scratch holds RAW (storage-dtype) planes —
    # stream_plane_pass upcasts transiently at view construction, never in
    # the ring — so its gate models rings at the STORAGE itemsize, unlike
    # the wavefront/wrap passes whose rings carry the f32 accumulator
    if not stream_vmem_fits(x_radius, raw.y, raw.z, itemsizes, False, itemsizes):
        # (2r+4) resident planes per field blow the budget jointly
        if separable and len(itemsizes) > 1:
            grouping = "per-field"
    return {"route": "plane", "m": 1, "z_slabs": False, "grouping": grouping}


def lane_pad_width(z: int) -> int:
    """Plane width rounded up to a 128 multiple — ragged lane extents stream
    ~30% slower (probe22), so z-slab wavefronts pad with dead columns."""
    return -(-z // 128) * 128


def prime_z_slabs(block: jax.Array, Zr: int, s: int) -> jax.Array:
    """The initial outgoing z-slab buffer for a macro chain: the block's
    interior z-boundary columns, packed [(-z)-bound | (+z)-bound] and
    transposed z-major (Xr, 2s, Yr) — the one strided read per dispatch;
    every later slab is kernel-emitted.  Exchange work (the z slab cut), so
    it sits under the ``exchange.z`` scope like the sweep it primes."""
    with telemetry.annotate(tm.SPAN_EXCHANGE_Z):
        return jnp.concatenate(
            [
                jnp.swapaxes(block[:, :, Zr - 2 * s : Zr - s], 1, 2),
                jnp.swapaxes(block[:, :, s : 2 * s], 1, 2),
            ],
            axis=1,
        )


def make_slab_extenders(Xr: int, Yr: int, s: int, mesh_shape, axis_names=None):
    """(yext, xext) for z-major slab buffers: after the z ppermute, each slab
    is extended with rows from the y neighbors and then planes from the x
    neighbors — two hops that carry the xyz-corner cells from the diagonal
    blocks, mirroring the in-array exchange's sweep order.  Shared by the
    generic engine and the bespoke jacobi wavefront."""
    from stencil_tpu.ops.exchange import _shift_from_high, _shift_from_low
    from stencil_tpu.parallel.mesh import MESH_AXES

    names = MESH_AXES if axis_names is None else axis_names

    def yext(S):
        lo_ = _shift_from_low(S[:, :, Yr - 2 * s : Yr - s], names[1], mesh_shape[1])
        hi_ = _shift_from_high(S[:, :, s : 2 * s], names[1], mesh_shape[1])
        # stencil-lint: disable=halo-set-in-loop writes land on the thin z-slab buffers (2s planes), not the full domain — slab extension IS the design that keeps z halos out of the big array (PERF_NOTES z-slabs)
        return S.at[:, :, 0:s].set(lo_).at[:, :, Yr - s : Yr].set(hi_)

    def xext(S):
        lo_ = _shift_from_low(S[Xr - 2 * s : Xr - s], names[0], mesh_shape[0])
        hi_ = _shift_from_high(S[s : 2 * s], names[0], mesh_shape[0])
        # stencil-lint: disable=halo-set-in-loop same: x-extension of the thin z-slab buffers, sublane-cheap and off the big array
        return S.at[0:s].set(lo_).at[Xr - s : Xr].set(hi_)

    return yext, xext


def permute_and_extend_z_slabs(zout, s: int, mesh_shape, yext, xext):
    """One macro's incoming z-slab buffer from the previous macro's outgoing
    one: ppermute the two direction halves along z, then extend with y- and
    x-neighbor content (corner propagation).  This IS the z sweep of the
    z-slab routes: all of it sits under the ``exchange.z`` scope (the y/x
    extension hops nest their own direction scopes inside)."""
    from stencil_tpu.ops.exchange import _shift_from_high, _shift_from_low
    from stencil_tpu.parallel.mesh import MESH_AXES

    with telemetry.annotate(tm.SPAN_EXCHANGE_Z):
        zlo = _shift_from_low(zout[:, 0:s, :], MESH_AXES[2], mesh_shape[2])
        zhi = _shift_from_high(zout[:, s : 2 * s, :], MESH_AXES[2], mesh_shape[2])
        return jnp.concatenate([xext(yext(zlo)), xext(yext(zhi))], axis=1)


@dataclasses.dataclass(frozen=True)
class PlaneTrace:
    """What ONE abstract trace of a plane-route kernel over one group of
    quantities learnt (``trace_plane_kernel``): who is read off-centre, who
    is returned, and the kernel itself as a jaxpr over ``x_g, y_g, z_g`` and
    every quantity's ``2r + 1`` window planes -- from which ``pruned`` cuts
    the kernel of any subset of the outputs."""

    names: Tuple[str, ...]  # the group's quantities, in the domain's order
    readers: Tuple[str, ...]  # read off-centre on any axis: the stage's exchange
    writers: Tuple[str, ...]  # returned: the outputs, in ``names``' order
    x_radius: int
    closed: Optional[object]  # the ClosedJaxpr; None = the trace raised
    kernel: PlaneKernel  # the user's callable (run as is when ``closed`` is None)
    renames: Tuple[Tuple[str, str], ...] = ()  # ``(p, q)``: output ``p`` IS the
    # centre plane of ``q``, a writer with a value of its own (``_plane_renames``)
    offsets: Tuple[Tuple[str, tuple], ...] = ()  # per reader, the ``(dx, dy, dz)``
    # it is read at off-centre (``footprint_counts``)

    def pruned(self, outputs: Sequence[str]):
        """``(kernel, reads, rings)`` of the pass that writes ``outputs``:
        the kernel with everything those outputs do not need cut away
        (``dce_jaxpr``), the quantities it still reads (the outputs
        themselves included: the pass carries their shell through), and the
        ones among them it reads at ``dx != 0``.  No second trace of the
        user's callable is made: what the footprint saw IS what runs."""
        if self.closed is None:  # fail closed: the whole kernel, every ring
            return self.kernel, self.names, self.names
        from jax.extend import core as jex
        from jax.interpreters import partial_eval as pe

        r, w = self.x_radius, 2 * self.x_radius + 1
        kept = [nm for nm in self.writers if nm in outputs]
        jaxpr, used = pe.dce_jaxpr(
            self.closed.jaxpr, [nm in outputs for nm in self.writers], instantiate=False
        )
        run = jex.jaxpr_as_fun(jex.ClosedJaxpr(jaxpr, self.closed.consts))
        planes = [
            (nm, d)
            for q, nm in enumerate(self.names)
            for d in range(w)
            if used[3 + q * w + d]
        ]

        def kernel(views, info):
            args = [c for c, u in zip(info.coords(), used[:3]) if u]
            args += [views[nm].sh(d - r, 0, 0) for nm, d in planes]
            return dict(zip(kept, run(*args)))

        touched = {nm for nm, _ in planes} | set(kept)
        ringed = {nm for nm, d in planes if d != r}
        return (
            kernel,
            tuple(nm for nm in self.names if nm in touched),
            tuple(nm for nm in self.names if nm in ringed),
        )


def trace_plane_kernel(
    kernel: PlaneKernel,
    names: Sequence[str],
    planes: Sequence[jax.ShapeDtypeStruct],  # per quantity, as the kernel sees it
    x_radius: int,
    global_size: Dim3,
    interpret: bool = True,
    storage: Optional[Sequence] = None,  # per quantity, the dtype its block is
    # STORED in (the rename rule compares them); None = the planes' own
) -> PlaneTrace:
    """The footprint of a PLANE-route kernel: trace it ONCE, abstractly
    (``jax.make_jaxpr``, nothing runs), over ``PlaneView``s that record
    every ``sh`` with a non-zero offset, and keep the keys of the dict it
    returns.  The quantities it reads off-centre are the ones the step
    exchanges, the ones it returns are the ones its passes write, and the
    jaxpr says which quantities each output touches and at which ``dx``
    (``PlaneTrace.pruned``).  A function of the kernel, as ``_sweep_kind``
    is a function of the mesh: no option, no plan value a user sets.
    ``interpret`` picks the rotate the passes will lower (``_make_roll``).

    Why the others keep a stale shell and the result is the same.  The plane
    pass is ONE level and writes interior cells only (shell planes and the
    in-plane shell ring pass through), so an interior cell's new value
    depends on a quantity's shell only through an off-centre read: a centre
    read of an interior cell is an interior cell.  A quantity outside the
    readers has its shell read by nothing, and every interior cell of every
    quantity is bitwise what exchanging all of them gives.  The step marks
    its shells stale (``step._marks_shell_stale``), so every reader of a
    shell re-exchanges every quantity, as before.

    Where the rule does NOT hold, and is not applied: the wavefront route —
    level >= 2 computes cells inside the shell, whose CENTRE reads need the
    shell of every quantity; ``halo="fused"`` — the side buffers are
    per-quantity operands of the pass; the wrap route has no exchange.  The
    plane route's split schedule takes it: its exterior bands are interior
    cells too.

    Why a quantity outside the writers need not be written.  The pass
    writes a quantity's centre plane back unchanged unless the kernel
    returned a value for it (``stream_plane_pass``): for a name the kernel
    never returns, every raw cell out is the raw cell in, so the step keeps
    the input array and moves nothing — a coefficient or an older time level
    is then read once a step, not read and written.

    Why an output that IS another quantity's centre plane need not be
    written either (``PlaneTrace.renames``).  A leapfrog scheme returns
    ``{"u": new, "u_prev": views["u"].center()}``: the second output is, value
    for value, an array the pass has already loaded under another name.
    Output ``p`` is a RENAME of ``q`` when its outvar in the jaxpr IS the
    invar of ``q``'s centre plane (``d == r``; no arithmetic, no ``where``,
    nothing in between: ``uc + 0.0`` or a masked copy is a value of its own
    and is written as before), ``q`` is another quantity that the kernel also
    returns with a value of its own (so after the step nothing else names
    ``q``'s old array), and ``p`` and ``q`` are stored alike (dtype and plane
    shape).  The pass then writes ``q``'s new value into ``p``'s buffer and
    the step hands ``q``'s old array back under the name ``p``
    (``stream_plane_pass(renames=)``): two handles swap, nothing is copied
    (acoustic: 6 arrays through HBM a step -> 5).  A source is claimed once
    (a second ``p2 <- q`` is written as before), and a chain ``p2 <- p <- q``
    renames ``p <- q`` alone: ``p`` has no value of its own, so ``p2 <- p`` is
    the copy it was.  ``q`` comes back bitwise what writing ``p`` gives on
    every raw cell and ``p`` on every interior cell; ``p``'s shell is now
    ``q``'s as the exchange left it where it was ``p``'s own stale one,
    which the contract allows: the step marks its shells stale and the
    exchange owns them.  Applied
    where the stage runs ONE in-place pass of the plane route's default
    schedule (``plan_plane_passes(rename=)``); not under ``overlap="split"``
    (fresh outputs), not under ``halo="fused"`` (every quantity is written),
    not in a stage cut into several passes, not when the trace failed.

    Fail closed: a trace that raises exchanges AND writes every quantity and
    runs the kernel as the user wrote it, every quantity ringed
    (``PlaneTrace.closed is None``).  The build runs the jaxpr THIS trace
    made, so its passes cannot see the kernel read or return anything the
    footprint did not; a pass handed a callable directly still raises, at
    trace time, on an off-centre read or a returned name it was not told of
    (``stream_plane_pass(halo_readers=, writers=, rings=)``)."""
    names = tuple(names)
    seen, returned = {}, []  # seen: reader -> the offsets it is read at
    roll = _make_roll(interpret)
    r, w = x_radius, 2 * x_radius + 1
    Y, Z = planes[0].shape

    def note(nm, dx, dy, dz):
        seen.setdefault(nm, set()).add((dx, dy, dz))

    def footprint(x_g, y_g, z_g, *vs):
        info = PlaneInfo(x_g, y_g, z_g, global_size, 1)
        vals = kernel(
            {
                nm: PlaneView(tuple(vs[q * w : (q + 1) * w]), roll, partial(note, nm))
                for q, nm in enumerate(names)
            },
            info,
        )
        returned[:] = [nm for nm in names if nm in vals]
        return [vals[nm] for nm in returned]

    i32 = partial(jax.ShapeDtypeStruct, dtype=jnp.int32)
    try:
        closed = jax.make_jaxpr(footprint)(
            i32(()), i32((Y, 1)), i32((1, Z)), *[p for p in planes for _ in range(w)]
        )
    except Exception as exc:  # noqa: BLE001 — whatever the user's kernel raises
        from stencil_tpu.utils.logging import log_warn

        log_warn(
            f"the stream kernel's footprint trace raised ({exc!r}); "
            "exchanging and writing every quantity"
        )
        return PlaneTrace(names, names, names, r, None, kernel)
    if storage is None:
        storage = [p.dtype for p in planes]
    stored = {nm: (jnp.dtype(d), p.shape) for nm, d, p in zip(names, storage, planes)}
    return PlaneTrace(
        names,
        tuple(nm for nm in names if nm in seen),
        tuple(returned),
        r,
        closed,
        kernel,
        _plane_renames(closed.jaxpr, names, tuple(returned), r, stored),
        tuple((nm, tuple(sorted(seen[nm]))) for nm in names if nm in seen),
    )


def footprint_counts(traces: Sequence[PlaneTrace]) -> Optional[dict]:
    """What a step's kernels read off-centre, counted from their footprint
    traces (every stage, every group): ``offcentre`` -- the quantities read at
    a non-zero offset; ``diagonal`` -- those of them read at an offset with
    two or more non-zero components (an EDGE or corner halo: only the full
    x, then y, then z sweep order fills it); ``read_sides`` -- the distinct
    (quantity, axis, side) triples read, where an exchange that serves a
    reader at all serves six.  None where a trace raised (nothing is known).
    D3Q19 lattice Boltzmann: 18, 12, 30; a 7-point star: 1, 0, 6."""
    if any(t.closed is None for t in traces):
        return None
    offsets = {}
    for t in traces:
        for nm, offs in t.offsets:
            offsets.setdefault(nm, set()).update(offs)
    sides = {
        (nm, a, o[a] > 0) for nm, offs in offsets.items() for o in offs for a in range(3) if o[a]
    }
    return {
        "offcentre": len(offsets),
        "diagonal": sum(
            any(sum(1 for c in o if c) >= 2 for o in offs) for offs in offsets.values()
        ),
        "read_sides": len(sides),
    }


def _plane_renames(jaxpr, names, writers, x_radius: int, stored: dict):
    """The ``(p, q)`` of ``trace_plane_kernel``'s rename rule, read off the
    kernel's jaxpr (invars: three coordinates, then ``2r + 1`` window planes a
    quantity; outvars: the writers in order): output ``p`` is ``q``'s centre
    invar itself, ``q`` is a writer whose own output is no quantity's centre
    plane, ``stored`` (dtype, plane shape) agree, and ``q`` is claimed once."""
    w = 2 * x_radius + 1
    centres = [(nm, jaxpr.invars[3 + q * w + x_radius]) for q, nm in enumerate(names)]
    pure = {}  # output -> the quantity whose centre plane it is, unchanged
    for p, var in zip(writers, jaxpr.outvars):
        q = next((nm for nm, centre in centres if centre is var), None)
        if q is not None:
            pure[p] = q
    pairs, claimed = [], set()
    for p, q in pure.items():
        if q != p and q in writers and q not in pure and q not in claimed:
            if stored[p] == stored[q]:
                claimed.add(q)
                pairs.append((p, q))
    return tuple(pairs)


def plane_pass_vmem_bytes(
    plane_bytes: Dict[str, int], x_radius: int, reads, rings, writes
) -> int:
    """VMEM model of one plane pass, ``stream_vmem_fits``' accounting cut to
    what the pass holds: two pipeline planes per quantity read, two more per
    quantity written, a ``2r``-deep ring per quantity read at ``dx != 0``,
    and the per-quantity stack margin (the kernel's roll / select
    temporaries).  ``plane_bytes`` is the tile-padded plane of each quantity
    at its STORAGE itemsize (the plane pass rings hold raw planes)."""
    est = sum(2 * plane_bytes[q] for q in reads)
    est += sum(2 * plane_bytes[q] for q in writes)
    est += sum(2 * x_radius * plane_bytes[q] for q in rings)
    return est + _VMEM_STACK_MARGIN * len(reads)


def plan_plane_passes(
    trace: PlaneTrace, plane_bytes: Dict[str, int], whole: bool = False,
    rename: bool = False,
) -> List[dict]:
    """The passes of one stage over one group: ``[{"writes", "reads",
    "rings", "renames", "vmem_bytes"}, ...]``, each a subset of the kernel's
    outputs with the quantities THOSE outputs touch (``PlaneTrace.pruned``).

    Outputs join the current pass, in the order the kernel returns them,
    while the pass still fits the VMEM budget (``plane_pass_vmem_bytes``
    against ``_vmem_budget``); the first that does not opens the next pass.
    Fewer passes move fewer arrays -- a quantity two outputs share is read
    once -- so a pass is as wide as the model allows (acoustic: one pass;
    elastic at 608 x 608: two a stage).  An output that fits no pass alone
    raises here, at plan time, naming its quantities and the bytes, instead
    of handing Mosaic a kernel it must refuse -- unless the pass carries
    that ONE quantity and nothing else: that is the engine's floor, nothing
    smaller exists and no restructuring of the kernel helps, so it is built
    whatever the model says (an over-tight ``STENCIL_VMEM_LIMIT_BYTES``
    degrades to it and never crashes; the model errs on the safe side).

    Passes run one after the other ON THE SAME ARRAYS (in place), while a
    kernel means all its outputs to come from the values it was called with:
    a pass that reads what an EARLIER pass of the stage has written would
    read the new value.  That raises too (make it a stage of its own).

    ``whole`` keeps the stage in one pass over every quantity, every one
    ringed and written (``halo="fused"``, whose side buffers are
    per-quantity operands of the pass).

    ``rename`` applies the rename rule (``trace_plane_kernel``) where the
    stage came out as ONE pass: the outputs that are another writer's centre
    plane leave ``writes`` and the pairs go under ``renames`` (``(p, q)``:
    ``q``'s new value lands in ``p``'s buffer, so ``p`` stays among the
    ``reads`` whether the kernel reads it or not).  The caller passes it for
    the in-place default schedule only (``_build_stream_step``)."""
    budget = _vmem_budget()

    def describe(outputs, whole=False, renames=()):
        if whole or trace.closed is None:
            reads = rings = writes = trace.names
        else:
            _, reads, rings = trace.pruned(outputs)
            writes = tuple(outputs)
            homes = set(reads) | {p for p, _ in renames}
            reads = tuple(nm for nm in trace.names if nm in homes)
        return {
            "writes": writes,
            "reads": reads,
            "rings": rings,
            "renames": tuple(renames),
            "vmem_bytes": plane_pass_vmem_bytes(
                plane_bytes, trace.x_radius, reads, rings, writes
            ),
        }

    def refuse(p):
        if len(p["reads"]) == 1:
            return  # the floor: one quantity, nothing to split
        raise ValueError(
            f"the plane pass that writes {p['writes']} reads {len(p['reads'])} "
            f"quantities {p['reads']}, {len(p['rings'])} of them off-centre "
            f"along x {p['rings']}: {p['vmem_bytes']} bytes of VMEM by the "
            f"model against a budget of {budget} -- it fits no pass; split "
            "the kernel into stages that touch fewer quantities each"
        )

    if not trace.writers:
        return []
    if whole or trace.closed is None:
        p = describe(trace.writers, whole=True)
        if p["vmem_bytes"] > budget:
            refuse(p)
        return [p]
    passes, current = [], []
    for out in trace.writers:
        p = describe(current + [out])
        if current and p["vmem_bytes"] > budget:  # close the pass, open the next
            passes.append(describe(current))
            current, p = [], describe([out])
        if p["vmem_bytes"] > budget:
            refuse(p)  # alone and too wide: raises, unless it is the floor
        current.append(out)
    passes.append(describe(current))
    written = set()
    for p in passes:
        clash = written & (set(p["reads"]) - set(p["writes"]))
        if clash:
            raise ValueError(
                f"the plane pass that writes {p['writes']} reads "
                f"{tuple(sorted(clash))}, which an earlier pass of the same "
                "stage has already written in place: the stage does not fit "
                "one pass and cannot be split; make the later update a stage "
                "of its own"
            )
        written |= set(p["writes"])
    if rename and len(passes) == 1 and trace.renames:
        renamed = {p for p, _ in trace.renames}
        kept = [out for out in trace.writers if out not in renamed]
        return [describe(kept, renames=trace.renames)]
    return passes


def static_stream_alias(route: str, n_fields: int) -> bool:
    """The no-tune alias rule, read from what the plan says of itself: the
    plane route always, any route from 4 fields up (``_build_stream_step``
    has the account: what is measured, what is round-5 hearsay)."""
    return route == "plane" or n_fields >= 4


def _resolve_stream_alias(plan: dict, n_fields: int) -> bool:
    """input_output_aliases decision for a stream plan.  Precedence mirrors
    the bespoke wavefront path (models/jacobi.py): an autotuner CANDIDATE
    build (``alias_forced`` — its A/B trials must actually differ, whatever
    the environment says) > ``STENCIL_STREAM_ALIAS=0/1`` (validated read) >
    the plan's persisted tuned ``alias`` > ``static_stream_alias``."""
    from stencil_tpu.utils.config import env_choice

    if plan.get("alias_forced") and plan.get("alias") is not None:
        return bool(plan["alias"])
    env = env_choice("STENCIL_STREAM_ALIAS", "auto", ("auto", "0", "1"))
    if env != "auto":
        return env == "1"
    if plan.get("alias") is not None:
        return bool(plan["alias"])
    return static_stream_alias(plan.get("route"), n_fields)


def _plan_passes_in_place(plan: dict) -> bool:
    """Do the main passes of a BUILT plan write onto their inputs?  The
    resolved ``plan["alias"]`` — except on the plane route under
    ``overlap="split"``, which keeps fresh outputs: the interior pass and
    the exchange both read the pre-exchange blocks, so XLA copies each block
    once a step either way (compiled for a described v5e 2x2, 260^3 shards:
    one whole-array copy per quantity per step aliased or not, and 73 MB
    more temporaries aliased)."""
    return bool(plan.get("alias")) and not (
        plan["route"] == "plane" and plan.get("overlap") == "split"
    )


def _overlap_request(plan: dict) -> Tuple[str, str]:
    """Pre-structural (value, source) of a stream plan's overlap schedule.
    Precedence mirrors the exchange route and stream alias rules: a FORCED
    plan value (``overlap_forced`` — explicit ``make_step(stream_overlap=
    ...)``/``make_stream_step(overlap=...)`` requests, autotuner candidate
    builds, and the ladder's split→off step-down, none of which ever consult
    further) > ``STENCIL_STREAM_OVERLAP`` (validated read) > the plan's
    tuned ``overlap`` > the static ``off``."""
    from stencil_tpu.utils.config import env_choice

    val: Optional[str] = None
    source = "static"
    if plan.get("overlap_forced") and plan.get("overlap") is not None:
        val, source = plan["overlap"], "explicit"
        if val not in STREAM_OVERLAP:
            raise ValueError(
                f"unknown stream overlap {val!r} (one of {STREAM_OVERLAP})"
            )
    else:
        env = env_choice(
            "STENCIL_STREAM_OVERLAP", "auto", ("auto",) + STREAM_OVERLAP
        )
        if env != "auto":
            val, source = env, "env"
        elif plan.get("overlap") is not None:
            tuned = plan["overlap"]
            if tuned in STREAM_OVERLAP:
                val, source = str(tuned), "tuned"
            else:
                from stencil_tpu.utils.logging import log_warn

                log_warn(
                    f"tuned stream overlap {tuned!r} is not one of "
                    f"{STREAM_OVERLAP}; using the static 'off' fallback"
                )
    if val is None:
        val = "off"
    return val, source


def _resolve_stream_overlap(plan: dict) -> Tuple[str, str]:
    """``_overlap_request`` plus the structural guard: a ``split`` the plan
    cannot serve — the wrap route has no exchange to hide, the z-slab
    wavefront interleaves its slab permutes with the pass — degrades to
    ``off`` with a warning (source tagged ``/degraded``), never an error: a
    stale persisted config or a cross-route env var must not kill a run
    ``off`` could have served.  (``make_stream_step`` re-plans a z-slab
    wavefront to the plain form BEFORE this guard when split was requested,
    so the degrade here is the last resort, not the common path.)"""
    val, source = _overlap_request(plan)
    if val == "split" and (
        plan.get("route") not in ("plane", "wavefront") or plan.get("z_slabs")
    ):
        from stencil_tpu.utils.logging import log_warn

        why = (
            "the z-slab wavefront interleaves its slab permutes with the pass"
            if plan.get("z_slabs")
            else f"the {plan.get('route')!r} route has no exchange to hide"
        )
        log_warn(
            f"overlap=split ({source}) cannot engage here ({why}); "
            "degrading to overlap=off"
        )
        val, source = "off", source + "/degraded"
    return val, source


def fused_halo_ineligible(dd, plan: dict, exch_route: str) -> Optional[str]:
    """Why ``halo="fused"`` cannot engage for this plan/domain/exchange
    route — or None when it can.  The structural gates (module docstring):
    the fused exchange packs at static offsets from even shards, patches
    need blend-supported tile geometry, the split schedule's exterior
    bands read exchanged BLOCKS, and only the plane / plain-wavefront
    routes stream level-0 planes the buffers can land in."""
    from stencil_tpu.ops import halo_blend
    from stencil_tpu.ops.exchange import Y_PACK_ROUTES

    if plan.get("route") not in ("plane", "wavefront"):
        return f"the {plan.get('route')!r} route has no exchange to fuse"
    if plan.get("z_slabs"):
        return "the z-slab wavefront already keeps z halos out of the big array"
    if plan.get("overlap") == "split":
        return "the split schedule's exterior band passes read exchanged blocks"
    if exch_route not in Y_PACK_ROUTES:
        return (
            f"the {exch_route!r} exchange route does not pack the y shell "
            f"(fused needs one of {Y_PACK_ROUTES})"
        )
    if any(v is not None for v in dd._valid_last):
        return "padded (uneven) shards — the fused pack cuts at static offsets"
    if not all(halo_blend.supports(dd.field_dtype(h)) for h in dd._handles):
        return "a field dtype without known tile geometry"
    return None


def _halo_request(plan: dict) -> Tuple[Optional[str], str]:
    """Pre-structural (value, source) of a stream plan's halo consumption
    mode.  Precedence mirrors the overlap axis: a FORCED plan value
    (``halo_forced`` — explicit requests, autotuner candidate builds, the
    ladder's fused→array step-down) > ``STENCIL_STREAM_HALO`` (validated
    read) > the plan's tuned ``halo`` > the static ``array``."""
    from stencil_tpu.utils.config import env_choice

    val: Optional[str] = None
    source = "static"
    if plan.get("halo_forced") and plan.get("halo") is not None:
        val, source = plan["halo"], "explicit"
        if val not in STREAM_HALO:
            raise ValueError(
                f"unknown stream halo mode {val!r} (one of {STREAM_HALO})"
            )
    else:
        env = env_choice("STENCIL_STREAM_HALO", "auto", ("auto",) + STREAM_HALO)
        if env != "auto":
            val, source = env, "env"
        elif plan.get("halo") is not None:
            tuned = plan["halo"]
            if tuned in STREAM_HALO:
                val, source = str(tuned), "tuned"
            else:
                from stencil_tpu.utils.logging import log_warn

                log_warn(
                    f"tuned stream halo {tuned!r} is not one of "
                    f"{STREAM_HALO}; using the static 'array' fallback"
                )
    if val is None:
        val = "array"
    return val, source


def _resolve_stream_halo(dd, plan: dict, exch_route: str) -> Tuple[str, str]:
    """``_halo_request`` plus the structural guard: a ``fused`` the plan
    cannot serve degrades to ``array`` with a warning (source tagged
    ``/degraded``), never an error — a stale persisted config or a
    cross-route env var must not kill a run ``array`` could have served.
    (``make_stream_step`` re-plans a z-slab wavefront to the plain form
    BEFORE this guard when fused was requested, like the split path.)"""
    val, source = _halo_request(plan)
    if val == "fused":
        why = fused_halo_ineligible(dd, plan, exch_route)
        if why is not None:
            from stencil_tpu.utils.logging import log_warn

            log_warn(
                f"halo=fused ({source}) cannot engage here ({why}); "
                "degrading to halo=array"
            )
            val, source = "array", source + "/degraded"
    return val, source


def plain_wavefront_plan(dd, plan: dict, max_depth: Optional[int] = None) -> Optional[dict]:
    """The PLAIN-form twin of a z-slab wavefront plan, at the deepest depth
    the VMEM model fits (the z-slab blocks leave the budget; the unpadded
    ``raw.z`` planes enter it) — or None when no plain depth >= 2 fits.
    The split-step schedule needs it: z halos must live in the big array for
    the exchange the interior pass overlaps, and the packed ``zpack_*``
    exchange routes already de-amplified the thin-z traffic the z-slab form
    exists to dodge.  Shared by ``make_stream_step`` (a split request
    re-plans through it) and ``tune/space.py`` (the split candidate)."""
    if plan.get("route") != "wavefront" or not plan.get("z_slabs"):
        return None
    shell = dd._shell_radius
    s = shell.lo().x
    raw = dd.local_spec().raw_size()
    itemsizes = [dd.field_dtype(h).itemsize for h in dd._handles]
    ring_sizes = [h.dtype.itemsize for h in dd._handles]
    per_field = plan.get("grouping") == "per-field" and len(itemsizes) > 1
    sizes = [max(itemsizes)] if per_field else itemsizes
    rsizes = [max(ring_sizes)] if per_field else ring_sizes
    cap = min(s, _WRAP_MAX_K)
    if max_depth is not None:
        cap = min(cap, max_depth)
    m = 0
    for cand in range(2, cap + 1):
        if stream_vmem_fits(cand, raw.y, raw.z, sizes, False, rsizes):
            m = cand
    if m < 2:
        return None
    out = dict(plan)
    out["z_slabs"] = False
    out["m"] = m
    return out


def _stream_groups(plan: dict, n_fields: int) -> List[List[int]]:
    """per-field grouping: one streaming pass per group per macro (valid only
    for kernels declared separable); the exchange stays JOINT (<= 6 permutes
    for any field count) either way"""
    if plan.get("grouping") == "per-field":
        return [[q] for q in range(n_fields)]
    return [list(range(n_fields))]


def _as_stages(kernel) -> Tuple[PlaneKernel, ...]:
    """A step's kernel is one callable or the sequence of its STAGES."""
    return tuple(kernel) if isinstance(kernel, (list, tuple)) else (kernel,)


def plan_plane_stages(dd, kernel, x_radius: int, plan: dict, interpret: bool,
                      fused: bool = False, rename: bool = False) -> List[List[tuple]]:
    """Plan a PLANE-route step from its kernels' own footprints and write the
    plan back: ``plan["stages"]`` -- per stage its ``readers`` (the
    quantities its exchange fills) and its ``passes`` (``plan_plane_passes``:
    writes, reads, rings, renames, modeled VMEM bytes) -- and the step-wide
    unions ``plan["halo_readers"]`` / ``plan["writers"]`` /
    ``plan["renamed"]`` (the quantities whose write became a rename).
    Raises ``ValueError`` for a step that fits in no pass.  Returns, per
    stage, what the build runs: ``[(pass kernel, reads, rings, writes,
    renames), ...]`` (names).

    Every stage is traced once per group (``trace_plane_kernel``); a function
    of the kernels, as ``_sweep_kind`` is a function of the mesh: no option.
    Under ``fused`` every quantity rides the exchange and every pass is
    whole (``plan_plane_passes``); ``rename`` is the build's to pass, where
    its passes run in place on the default schedule."""
    names = [h.name for h in dd._handles]
    raw = dd.local_spec().raw_size()
    f32_acc = any(dd.field_dtype(h) != h.dtype for h in dd._handles)
    planes = [
        jax.ShapeDtypeStruct(
            (raw.y, raw.z), jnp.float32 if f32_acc else dd.field_dtype(h)
        )
        for h in dd._handles
    ]
    plane_bytes = {
        h.name: _padded_plane_bytes(raw.y, raw.z, dd.field_dtype(h).itemsize)
        for h in dd._handles
    }
    described, built, traces = [], [], []
    for stage in _as_stages(kernel):
        readers, passes, runs = set(), [], []
        for g in _stream_groups(plan, len(names)):
            trace = trace_plane_kernel(
                stage, [names[q] for q in g], [planes[q] for q in g], x_radius,
                dd._size, interpret, [dd.field_dtype(dd._handles[q]) for q in g],
            )
            traces.append(trace)
            readers |= set(names) if fused else set(trace.readers)
            for p in plan_plane_passes(trace, plane_bytes, whole=fused, rename=rename):
                passes.append(p)
                runs.append((
                    trace.pruned(p["writes"])[0], p["reads"], p["rings"], p["writes"],
                    p["renames"],
                ))
        described.append({
            "readers": tuple(nm for nm in names if nm in readers),
            "passes": tuple(passes),
        })
        built.append(runs)
    plan["stages"] = tuple(described)
    plan["footprint"] = footprint_counts(traces)
    for key, of in (
        ("halo_readers", lambda st: st["readers"]),
        ("writers", lambda st: [w for p in st["passes"] for w in p["writes"]]),
        ("renamed", lambda st: [a for p in st["passes"] for a, _ in p["renames"]]),
    ):
        union = {nm for st in described for nm in of(st)}
        plan[key] = tuple(nm for nm in names if nm in union)
    return built


def macros_per_trip(in_place: bool) -> int:
    """After how many macros a macro loop's carry is back in its own buffer:
    1 where the kernel writes in place, 2 where it writes a fresh result
    (``macro_loop``)."""
    return 1 if in_place else 2


def macro_loop(macro, macros: int, carry, per_trip: int):
    """``macros`` applications of ``macro`` to ``carry`` as one ``fori_loop``
    of ``per_trip`` macros a trip (``macros_per_trip``: as many as it takes
    for the carry to be back in its own buffers), the handles passed on in
    Python between them, what is left over unrolled behind the loop.  The
    loop of ``models/jacobi.py``'s bespoke kernels and of the stream engine's
    wrap route.

    A ``while`` wants its carry back in the buffer it came in.  With ONE
    fresh-result kernel call a trip, result and operand are alive together
    and cannot share a buffer, so XLA copies a whole block every trip to put
    the result where the carry lives (10.4% of ``jacobi3d-512.bulk``'s busy
    time, 7.2% of its four-chip twin: PERF.md, PR 38; nineteen such copies a
    trip, as much traffic as the pass itself, in the wrap route's LBM step
    cross-compiled at 256^3: PERF.md, PR 39).  The SECOND result of
    a trip is born after the trip's operand has died and takes its buffer:
    the carry comes home and nothing is copied (``_carry_period`` is the
    same count for the plane route's renames).  With
    an odd ``macros`` the last result flows into the program's edge (the
    ``dynamic_update_slice`` / ``pad`` of the dispatch), which has no carry
    to honour: XLA may copy there, once a DISPATCH -- dispatch an even count
    of macros."""

    def trip(_, c):
        for _ in range(per_trip):
            c = macro(c)
        return c

    trips, behind = divmod(macros, per_trip)
    if trips:
        carry = lax.fori_loop(0, trips, trip, carry)
    for _ in range(behind):
        carry = macro(carry)
    return carry


def _carry_period(names: Sequence[str], stages) -> int:
    """After how many steps a step loop's carry is back in its own buffers:
    the order of the permutation one step's renames (``plan["stages"]``)
    make of the quantities' blocks -- 1 with none, 2 for one or more disjoint
    swaps such as ``u_prev <- u``."""
    index = {name: q for q, name in enumerate(names)}
    home = list(range(len(names)))
    once = list(home)  # once[q]: the buffer q's value is in after a step
    for st in stages:
        for p in st["passes"]:
            for a, b in p["renames"]:
                once[index[a]], once[index[b]] = once[index[b]], once[index[a]]
    period, now = 1, once
    while now != home:
        period, now = period + 1, [once[b] for b in now]
    return period


def pass_wrap_fills(dd, exch_route: str) -> Tuple[str, tuple]:
    """Which of the y and z sweeps of this domain's exchange the plane passes
    make themselves, and how: ``(axes, fills)`` -- ``axes`` a substring of
    ``"yz"`` (``plan["pass_wrap_axes"]``), ``fills`` the ``(axis,
    destination, source, width)`` of each halo fill, y before z
    (``stream_plane_pass(wrap_fills=)``).

    An axis rides in the pass exactly where its sweep IS the self-wrap
    (``ops/exchange.py wrap_axes``, i.e. ``_sweep_kind``: mesh extent 1 on the
    axis, 3-D blocks, a supported dtype, the blend kernels enabled, an
    interior no narrower than the halo, no packed route on the axis): the halo
    is then a copy of cells of the same plane, at the static offsets
    ``halo_blend.wrap_halo`` computes -- low halo ``[0, r_lo)`` <- ``[n, n +
    r_lo)``, high halo ``[r_lo + n, r_lo + n + r_hi)`` <- ``[r_lo, r_lo +
    r_hi)``.  A function of the mesh and the domain, as ``_sweep_kind`` is:
    no option.  Never x: in place, the pass has overwritten the source planes
    of the high x shell before it reaches it."""
    from stencil_tpu.ops.exchange import wrap_axes
    from stencil_tpu.parallel.mesh import MESH_AXES

    raw = dd.local_spec().raw_size()
    shell = dd._shell_radius
    swept = wrap_axes(
        tuple(dd.mesh.shape[a] for a in MESH_AXES),
        shell,
        (raw.x, raw.y, raw.z),
        [dd.field_dtype(h) for h in dd._handles],
        all_3d=not any(h.components for h in dd._handles),
        valid_last=dd._valid_last,
        route=exch_route,
    )
    axes, fills = "", []
    for a in (1, 2):
        if MESH_AXES[a] not in swept:
            continue
        r_lo, r_hi = shell.axis(a, -1), shell.axis(a, +1)
        n = dd._valid_last[a]  # one shard is the last shard
        if n is None:
            n = raw[a] - r_lo - r_hi
        axes += MESH_AXES[a]
        fills += [
            (a, d, s, w) for d, s, w in ((0, n, r_lo), (r_lo + n, r_lo, r_hi)) if w
        ]
    return axes, tuple(fills)


def _build_stream_step(dd, kernel, x_radius, plan, interpret, donate=True):
    from jax.sharding import PartitionSpec as P

    from stencil_tpu.ops.exchange import (
        fused_shell_exchange,
        halo_exchange_multi,
        wire_plan,
    )
    from stencil_tpu.parallel.mesh import MESH_AXES

    names = [h.name for h in dd._handles]
    valid_last = dd._valid_last
    n = dd.local_spec().sz
    shell = dd._shell_radius
    lo, hi = shell.lo(), shell.hi()
    mesh_shape = tuple(dd.mesh.shape[a] for a in MESH_AXES)
    gsize = dd._size
    raw = dd.local_spec().raw_size()
    spec = P(*MESH_AXES)
    groups = _stream_groups(plan, len(names))
    stages = _as_stages(kernel)
    if len(stages) > 1 and plan["route"] != "plane":
        raise ValueError(
            f"a step of {len(stages)} stages runs the plane route (an exchange "
            f"before every stage); the plan says {plan['route']!r}"
        )
    kernel = stages[0]  # the wrap and wavefront routes run one kernel
    # the z sweep of every in-step exchange runs the domain's realize-
    # resolved route (packed z-shell vs direct — ops/exchange.py), so stream
    # steps escape the 64×-amplified thin-z path exactly like exchange()
    exch_route = getattr(dd, "_exchange_route", "direct")
    # Pass outputs alias their inputs or not (_resolve_stream_alias), written
    # back into the plan like overlap / halo (the ladder,
    # step._stream_plan and domain.step's ``aliased`` read it).  The wrap
    # pass has no in-place form.
    # MEASURED on the v5e (PERF.md §6, PR 28): the plane route un-aliased
    # pays one whole-array copy per quantity per step — its pass runs inside
    # the step loop, whose carry lives in place.  Acoustic, four quantities
    # at 608^3: 29.35 -> 17.77 ms a step aliased, the pass itself unchanged
    # at 11.48 ms; one quantity at radius 2, 512^3: 5.44 -> 3.35 ms.  So the
    # plane route always aliases (static_stream_alias).
    # Round-5 HEARSAY, never re-measured on this chip: un-aliased WAVEFRONT
    # passes ~10-20% faster for few fields (probe21b: the in-place alias
    # serializes the deep-m pipeline), aliased ahead from 4 fields up (8 x
    # ~700 MB of fresh results exhausted HBM; per-field passes at 8x512^3
    # read 19.1 ms/iter un-aliased against 12.8).  The wavefront rule rests
    # on that and stays as it was.
    alias = _resolve_stream_alias(plan, len(names)) and plan["route"] != "wrap"
    plan["alias"] = alias
    # split-step overlap schedule (module docstring): resolve, write the
    # decision back into the plan (the ladder and step._stream_plan read it),
    # and record it — the stream-engine twin of the exchange.route event
    overlap, overlap_source = _resolve_stream_overlap(plan)
    plan["overlap"] = overlap
    telemetry.emit_event(
        tm.EVENT_STEP_OVERLAP,
        overlap=overlap,
        source=overlap_source,
        route=plan["route"],
        m=plan["m"],
    )
    split = overlap == "split"
    # fused unpack→blend axis (module docstring): resolved AFTER overlap —
    # the split schedule structurally excludes fused — written back into
    # the plan (the ladder and step._stream_plan read it) and recorded,
    # the stream-engine twin of the exchange.route / step.overlap events
    halo, halo_source = _resolve_stream_halo(dd, plan, exch_route)
    plan["halo"] = halo
    telemetry.emit_event(
        tm.EVENT_STEP_HALO,
        halo=halo,
        source=halo_source,
        route=plan["route"],
        m=plan["m"],
        exchange_route=exch_route,
    )
    fused = halo == "fused"
    # bf16 STORAGE: the passes upcast at load and accumulate at the native f32
    f32_acc = any(dd.field_dtype(h) != h.dtype for h in dd._handles)

    if split:
        from stencil_tpu.ops import halo_blend

        interp_blend = interpret or halo_blend.interpret_mode()
        lo_t = (lo.x, lo.y, lo.z)
        hi_t = (hi.x, hi.y, hi.z)

        def _n_valid(ax):
            """Valid interior width on ``ax`` for THIS shard: a plain int on
            even axes, traced on padded ones (the last shard owns the
            remainder — the same arithmetic as the exchange's dynamic halo
            offsets, so band positions land exactly where the halos did)."""
            if valid_last[ax] is None:
                return n[ax]
            idx = lax.axis_index(MESH_AXES[ax])
            return jnp.where(
                idx == mesh_shape[ax] - 1, valid_last[ax], n[ax]
            ).astype(jnp.int32)

        def _starts3(ax, start):
            # uniform index dtype: a traced (int32) padded-axis offset must
            # not mix with python-int (x64) zeros in dynamic_slice/DUS
            starts = [jnp.int32(0)] * 3
            starts[ax] = jnp.asarray(start, jnp.int32)
            return tuple(starts)

        def _sub_slice(b, ax, start, width):
            sizes = list(b.shape)
            sizes[ax] = width
            return lax.dynamic_slice(b, _starts3(ax, start), tuple(sizes))

        def _blend_band(block, band, ax, pos):
            """Write a recomputed width-``w`` band at ``pos`` along ``ax``.
            x bands are whole contiguous planes (DUS at slab cost); y/z bands
            go through the tile-local blend kernels exactly like the
            exchange's halo writes (static offset on even axes, traced on
            padded ones)."""
            if ax == 0:
                # stencil-lint: disable=sliver-dus x-plane band write-back: whole contiguous planes, the exchange's sanctioned axis-0 pattern (no relayout bait)
                return lax.dynamic_update_slice(block, band, _starts3(0, pos))
            if not halo_blend.supports(block.dtype):
                # exotic-dtype correctness fallback, off the measured path
                # stencil-lint: disable=sliver-dus exotic-dtype (no known tile geometry) fallback — the blend kernels cannot engage, and such dtypes are off the measured fast path
                return lax.dynamic_update_slice(block, band, _starts3(ax, pos))
            if isinstance(pos, int):
                return halo_blend.blend_slab(
                    block, band, ax, pos, interpret=interp_blend
                )
            return halo_blend.blend_slab_dynamic(
                block, band, ax, pos, interpret=interp_blend
            )

        # Mosaic rejects thin band sub-blocks outright (a 6-sublane ring
        # scratch is an "invalid offsets in tiling target"; thin-lane shapes
        # likewise): the band window is rounded up to the axis tile granule
        # — 32 sublanes / 128 lanes cover the native tiling of every dtype —
        # which costs nothing the VMEM tile padding wasn't already paying
        # (PERF_NOTES "Thin z-region access": a 6-lane sliver occupies full
        # 128-lane tiles regardless).  x slices whole planes (the grid
        # axis — no granule).  Interpret mode pads identically so tier-1
        # exercises the same window arithmetic the TPU compiles.
        _BAND_GRANULE = (1, 32, 128)

        def _band_window(ax, start, w, raw_ax):
            """(clamped start, width) of one band's support window: ``3w``
            rounded up to the axis granule, slid down (never past 0) to stay
            inside the raw extent.  The clamp only widens the interior side
            of the window, so the band keeps its full dependency cone."""
            g = _BAND_GRANULE[ax]
            width = min(-(-3 * w // g) * g, raw_ax)
            if isinstance(start, int):
                return max(min(start, raw_ax - width), 0), width
            return jnp.clip(start, 0, raw_ax - width), width

        def _exterior_fix(outs, ex, w, origin, narrow_pass):
            """Recompute the six width-``w`` boundary bands of ``outs`` from
            the freshly exchanged blocks ``ex`` and blend them in.  Each
            band's support window is ``>= 3w`` wide (band + ``w`` of fresh
            shell + interior, granule-padded), so the narrow pass reproduces
            the full pass's values bitwise on the band; band overlaps at
            edges and corners write identical values twice."""
            outs = list(outs)
            for ax in range(3):
                nv = _n_valid(ax)
                for start, pos in (
                    (lo_t[ax] - w, lo_t[ax]),  # low face: static offsets
                    # high face: right after this shard's valid cells —
                    # static on even axes, traced on padded ones
                    (lo_t[ax] + nv - 2 * w, lo_t[ax] + nv - w),
                ):
                    start, width = _band_window(ax, start, w, ex[0].shape[ax])
                    subs = [_sub_slice(e, ax, start, width) for e in ex]
                    sub_outs = narrow_pass(subs, ax, start, w, origin)
                    for q in range(len(outs)):
                        band = _sub_slice(sub_outs[q], ax, pos - start, w)
                        outs[q] = _blend_band(outs[q], band, ax, pos)
            return outs

    def origin_of():
        # NOTE: must be called INSIDE the fori_loop body that consumes it.
        # axis_index lowers to partition-id; a while-loop OPERAND whose def
        # chain includes partition-id trips XLA's SPMD partitioner
        # ("PartitionId instruction is not supported for SPMD partitioning")
        # on some toolchains, while the same op inside the body partitions
        # fine (and LICM hoists it after partitioning anyway).
        return jnp.stack(
            [lax.axis_index(MESH_AXES[ax]) * n[ax] for ax in range(3)]
        )

    # the quantities that ride the step's exchange and those its passes
    # write, written back like alias (domain.step's ``exchanged`` and
    # ``written`` count them): on the plane route what each stage's kernel
    # reads off-centre and returns (plan_plane_stages), every one wherever
    # the rules do not hold (trace_plane_kernel says where and why); the wrap
    # route exchanges none
    plan["writers"] = tuple(names)
    # the y / z sweeps the plane passes make themselves in VMEM
    # (pass_wrap_fills), written back like the two above (domain.step's
    # ``wrapped``): on the plane route's default schedule only -- the fused
    # mode's side buffers and the split schedule's exterior bands keep the
    # exchange they have
    plan["pass_wrap_axes"], wrap_fills = "", ()
    # the quantities whose write became a rename (trace_plane_kernel),
    # written back like the three above (domain.step's ``renamed``): on the
    # same schedule, where the passes run in place
    plan["renamed"] = ()
    # the axes the step's exchange sends over wires and the bytes a shard
    # receives over them a step, where the exchange is the plane route's
    # swept one (set below; no other schedule says)
    plan.pop("wired", None)
    plan.pop("wire_bytes", None)
    plan.pop("macros_per_trip", None)
    plan.pop("z_halo_patch", None)
    plan.pop("lane_pad", None)
    if plan["route"] == "plane":
        default = not fused and not split
        stage_runs = plan_plane_stages(
            dd, stages, x_radius, plan, interpret, fused,
            rename=default and _plan_passes_in_place(plan),
        )
        if default:
            plan["pass_wrap_axes"], wrap_fills = pass_wrap_fills(dd, exch_route)
    else:
        plan["halo_readers"] = () if plan["route"] == "wrap" else tuple(names)
        # what the kernel reads off-centre, as the plane route's planner
        # learns it (plan_plane_stages): one abstract trace a group, which
        # decides nothing here -- domain.step's ``offcentre`` / ``diagonal`` /
        # ``read_sides`` say it beside what the route serves
        plane = jax.ShapeDtypeStruct((raw.y, raw.z), jnp.float32)
        plan["footprint"] = footprint_counts([
            trace_plane_kernel(
                kernel, [names[q] for q in g], [plane] * len(g), x_radius, gsize, interpret
            )
            for g in groups
        ])

    if plan["route"] == "wrap":
        k = plan["m"]
        # the wrap pass writes fresh results: two macros a trip bring the
        # loop's carry home (macro_loop), written back like alias (domain.
        # step's ``macros_per_trip``); ``steps`` an even count of macros
        # keeps the dispatch's edge free of copies too
        per_trip = plan["macros_per_trip"] = macros_per_trip(False)

        def per_shard(steps, *blocks_raw):
            bs = tuple(
                lax.slice(b, (lo.x, lo.y, lo.z), (lo.x + n.x, lo.y + n.y, lo.z + n.z))
                for b in blocks_raw
            )

            def one(depth, bs):
                origin = origin_of()
                out = list(bs)
                for g in groups:
                    outs = stream_wrap_pass(
                        kernel, [names[q] for q in g], [bs[q] for q in g],
                        depth, origin, gsize, interpret=interpret,
                        f32_accumulate=f32_acc,
                    )
                    for q, o in zip(g, outs):
                        out[q] = o
                return tuple(out)

            blocked, rem = divmod(steps, k)
            bs = macro_loop(partial(one, k), blocked, bs, per_trip)
            if rem:
                bs = one(rem, bs)
            return tuple(
                # stencil-lint: disable=sliver-dus whole-interior write-back after the wrap loop — b spans the full interior, not a y/z sliver
                lax.dynamic_update_slice(rb, b, (lo.x, lo.y, lo.z))
                for rb, b in zip(blocks_raw, bs)
            )

    elif plan["route"] == "plane":
        import contextlib

        in_place = _plan_passes_in_place(plan)
        index = {name: q for q, name in enumerate(names)}
        stage_readers = [st["readers"] for st in plan["stages"]]

        def stage_scope(k):
            """``step.stage.<k>`` around a stage's exchange and passes, for a
            step of more than one (a one-stage step's scopes stay as they
            were)."""
            if len(stages) == 1:
                return contextlib.nullcontext()
            return telemetry.annotate(tm.step_stage_span(k))

        swept_axes = tuple(
            a for a in range(3) if MESH_AXES[a] not in plan["pass_wrap_axes"]
        )
        if not fused:
            # what the sweeps that are left send to ANOTHER shard, written
            # back like the wrapped axes (domain.step's ``wired`` and
            # ``wire_bytes``): per stage the message plan of the exchange
            # below (ops/exchange.py wire_plan, i.e. ``_sweep_kind``), the
            # axes joined and the bytes summed over the stages of a step
            per_stage = [
                wire_plan(
                    mesh_shape, shell, (raw.x, raw.y, raw.z),
                    [dd.field_dtype(dd._handles[index[name]]) for name in readers],
                    valid_last=valid_last, route=exch_route, axes=swept_axes,
                )
                for readers in stage_readers if readers
            ]
            plan["wired"] = "".join(
                ax for ax in MESH_AXES if any(ax in w for w, _ in per_stage)
            )
            plan["wire_bytes"] = sum(b for _, b in per_stage)

        def exchange_readers(bs, k):
            """``bs`` with the shells of stage ``k``'s halo readers filled --
            one joint exchange of those blocks alone; the others ride on
            untouched -- on the axes the passes do not wrap themselves."""
            riders = [index[name] for name in stage_readers[k]]
            out = list(bs)
            filled = halo_exchange_multi(
                [bs[q] for q in riders], shell, mesh_shape,
                valid_last=valid_last, axes=swept_axes, route=exch_route,
            )
            for q, b in zip(riders, filled):
                out[q] = b
            return out

        def plane_passes(k, bs, origin, fused_bufs=None, lo=lo, hi=hi,
                         alias=in_place,
                         scope=partial(telemetry.annotate, tm.SPAN_STEP_PASS)):
            """Stage ``k``'s passes in order, each over the quantities it
            touches; a later pass sees what an earlier one wrote."""
            out = list(bs)
            for pass_kernel, reads, rings, writes, renames in stage_runs[k]:
                g = [index[name] for name in reads]
                fs = None
                if fused_bufs is not None:
                    xb, yb, zb = fused_bufs
                    fs = (
                        [xb[q] for q in g],
                        [yb[q] for q in g],
                        [zb[q] for q in g],
                    )
                with scope():
                    outs = stream_plane_pass(
                        pass_kernel, reads, [out[q] for q in g],
                        lo, hi, x_radius, origin, gsize, alias=alias,
                        interpret=interpret, fused_shell=fs,
                        f32_accumulate=f32_acc, halo_readers=stage_readers[k],
                        writers=writes, rings=rings, wrap_fills=wrap_fills,
                        renames=renames,
                    )
                for q, o in zip(g, outs):
                    out[q] = o
            return out

        if fused:

            def stage(k, bs, origin):
                # the packed messages never unpack into the blocks: the
                # received shell buffers ride into the pass and land in
                # the level-0 VMEM planes — no big-array halo write
                bufs = fused_shell_exchange(bs, shell, mesh_shape, route=exch_route)
                return plane_passes(k, bs, origin, bufs)

        elif split:

            def narrow_plane(k, subs, ax, start, w, origin):
                """One kernel level over ``3w``-wide face sub-blocks (``w ==
                x_radius``): the sliced axis carries a ``w``-deep pseudo
                shell, the other axes keep the true shell widths, and the
                origin shifts so wrapped coordinates match the full pass at
                every sub-block position (traced on padded axes)."""
                lo2 = Dim3(*[w if b == ax else lo_t[b] for b in range(3)])
                hi2 = Dim3(*[w if b == ax else hi_t[b] for b in range(3)])
                delta = [
                    jnp.asarray(start - lo_t[b] + w if b == ax else 0, jnp.int32)
                    for b in range(3)
                ]
                return plane_passes(
                    k, subs, origin + jnp.stack(delta), lo=lo2, hi=hi2,
                    alias=False, scope=contextlib.nullcontext,
                )

            def stage(k, bs, origin):
                # the ppermutes read slabs of the PRE-exchange blocks;
                # the interior pass below also reads those blocks — no
                # data dependency between them, so XLA's latency-hiding
                # scheduler flies the collectives behind the pass
                ex = exchange_readers(bs, k)
                with telemetry.annotate(tm.SPAN_OVERLAP_INTERIOR):
                    out = plane_passes(k, bs, origin)
                with telemetry.annotate(tm.SPAN_OVERLAP_EXTERIOR):
                    return _exterior_fix(
                        out, ex, x_radius, origin, partial(narrow_plane, k)
                    )

        else:

            def stage(k, bs, origin):
                return plane_passes(k, exchange_readers(bs, k), origin)

        # A renaming pass hands its blocks on PERMUTED, and a loop body that
        # returns its carry permuted makes XLA copy whole arrays to put each
        # value back where the carry lives (three ``copy`` a trip for a
        # two-array leapfrog on the CPU compiler; PR 28 met the same copy,
        # 39% of busy).  So a trip runs as many steps as the permutation's
        # period -- two for ``u_prev <- u`` -- with the handles swapped in
        # Python between them: the carry comes back in its own places and
        # every pass writes in place.  The remainder runs unrolled behind the
        # loop; with ``steps`` no multiple of the period the program's
        # outputs are permuted against its donated inputs and XLA may copy at
        # the program's edge, once a DISPATCH: correct, and dearer by up to a
        # step's traffic -- keep ``steps`` a multiple of the period (1 with no
        # rename: the loop every other plan had).
        period = _carry_period(names, plan["stages"])

        def per_shard(steps, *blocks):
            def one(bs):
                origin = origin_of()
                bs = list(bs)
                for k in range(len(stages)):
                    with stage_scope(k):
                        bs = stage(k, bs, origin)
                return tuple(bs)

            def body(_, bs):
                for _ in range(period):
                    bs = one(bs)
                return bs

            trips, rem = divmod(steps, period)
            bs = tuple(blocks)
            if trips:
                bs = lax.fori_loop(0, trips, body, bs)
            for _ in range(rem):
                bs = one(bs)
            return bs

    else:
        m = plan["m"]
        s = lo.x
        z_slab_mode = plan["z_slabs"]
        Xr, Yr, Zr = raw.x, raw.y, raw.z
        if z_slab_mode:
            # where the pass patches its z halo, read off the working plane's
            # shape as the kernel's own helper reads it (patch_z_halo),
            # written back like macros_per_trip (domain.step's
            # ``z_halo_patch``): "tile" on the lane-padded plane -- which the
            # pass makes in VMEM from the raw block where ``Zr`` is not whole
            # lane tiles (``lane_pad``), so the step carries the domain's own
            # blocks and pads or cuts nothing
            plan["z_halo_patch"] = z_halo_patch_form(lane_pad_width(Zr), s)
            plan["lane_pad"] = "vmem" if Zr % 128 else "none"
        yext, xext = make_slab_extenders(Xr, Yr, s, mesh_shape)

        def wavefront_groups(bs, depth, origin, zs=None, fused_bufs=None):
            """Run the m-level pass group by group; returns (outs, zouts)."""
            outs = list(bs)
            zouts = [None] * len(bs) if zs is not None else None
            for g in groups:
                fs = None
                if fused_bufs is not None:
                    xb, yb, zb = fused_bufs
                    fs = (
                        [xb[q] for q in g],
                        [yb[q] for q in g],
                        [zb[q] for q in g],
                    )
                o, z = stream_wavefront_pass(
                    kernel, [names[q] for q in g], [bs[q] for q in g],
                    depth, s, origin, gsize,
                    z_slabs=[zs[q] for q in g] if zs is not None else None,
                    alias=alias,
                    interpret=interpret,
                    fused_shell=fs,
                    f32_accumulate=f32_acc,
                )
                for j, q in enumerate(g):
                    outs[q] = o[j]
                    if z is not None:
                        zouts[q] = z[j]
            return outs, zouts

        def narrow_wavefront(subs, ax, start, w, origin):
            """``w`` kernel levels over ``3w``-wide face sub-blocks (``w`` is
            this macro's depth; the remainder macro passes a shallower one).
            The sub-block's pseudo shell is ``w`` on every axis — minimal
            support for a width-``w`` band at level ``w`` — with the origin
            shifted so wrapped coordinates match the full pass."""
            delta = [
                jnp.asarray(start - lo_t[b] + w if b == ax else w - lo_t[b],
                            jnp.int32)
                for b in range(3)
            ]
            origin_sub = origin + jnp.stack(delta)
            out = list(subs)
            for g in groups:
                o, _ = stream_wavefront_pass(
                    kernel, [names[q] for q in g], [subs[q] for q in g],
                    w, w, origin_sub, gsize, alias=False, interpret=interpret,
                    f32_accumulate=f32_acc,
                )
                for q, oo in zip(g, o):
                    out[q] = oo
            return out

        def per_shard(steps, *blocks):
            if not z_slab_mode:

                if fused:

                    def macro(depth, bs):
                        origin = origin_of()
                        bs = list(bs)
                        # messages pack from the (stale-shell) blocks, the
                        # received buffers corner-patch each other in the
                        # sweep order, and the pass lands them in VMEM —
                        # the big array never sees a halo write
                        bufs = fused_shell_exchange(
                            bs, shell, mesh_shape, route=exch_route
                        )
                        outs, _ = wavefront_groups(
                            bs, depth, origin, fused_bufs=bufs
                        )
                        return tuple(outs)

                elif split:

                    def macro(depth, bs):
                        origin = origin_of()
                        bs = list(bs)
                        # ppermutes on slabs of the PRE-exchange blocks; the
                        # interior pass reads the same blocks — independent
                        # dataflow, so the collectives fly behind the m-level
                        # pass and only the narrow band passes wait for them
                        ex = list(
                            halo_exchange_multi(
                                bs, shell, mesh_shape, valid_last=valid_last,
                                route=exch_route,
                            )
                        )
                        with telemetry.annotate(tm.SPAN_OVERLAP_INTERIOR):
                            outs, _ = wavefront_groups(bs, depth, origin)
                        with telemetry.annotate(tm.SPAN_OVERLAP_EXTERIOR):
                            outs = _exterior_fix(
                                outs, ex, depth, origin, narrow_wavefront
                            )
                        return tuple(outs)

                else:

                    def macro(depth, bs):
                        origin = origin_of()
                        bs = list(
                            halo_exchange_multi(
                                bs, shell, mesh_shape, valid_last=valid_last,
                                route=exch_route,
                            )
                        )
                        outs, _ = wavefront_groups(bs, depth, origin)
                        return tuple(outs)

                macros, rem = divmod(steps, m)
                bs = lax.fori_loop(0, macros, lambda _, b: macro(m, b), tuple(blocks))
                if rem:
                    bs = macro(rem, bs)
                return bs

            def macro(depth, carry):
                origin = origin_of()
                bs, zouts = carry
                bs = list(
                    halo_exchange_multi(bs, shell, mesh_shape, axes=(0, 1))
                )
                zs = [
                    permute_and_extend_z_slabs(zout, s, mesh_shape, yext, xext)
                    for zout in zouts
                ]
                outs, zouts = wavefront_groups(bs, depth, origin, zs)
                return tuple(outs), tuple(zouts)

            # prime slabs from the blocks' interior z boundaries
            zouts = tuple(prime_z_slabs(b, Zr, s) for b in blocks)
            macros, rem = divmod(steps, m)
            carry = lax.fori_loop(
                0, macros, lambda _, c: macro(m, c), (tuple(blocks), zouts)
            )
            if rem:
                carry = macro(rem, carry)
            return carry[0]

    donate_kw = {"donate_argnums": 0} if donate else {}

    @partial(jax.jit, static_argnums=1, **donate_kw)
    def step(curr, steps: int = 1):
        # check_vma off: pallas_call outputs carry no vma annotation
        fn = shard_map(
            partial(per_shard, steps),
            mesh=dd.mesh,
            in_specs=tuple(spec for _ in names),
            out_specs=tuple(spec for _ in names),
            check_vma=False,
        )
        outs = fn(*[curr[k] for k in names])
        return dict(zip(names, outs))

    return step


def make_stream_step(
    dd,
    kernel: PlaneKernel,
    x_radius: int = 1,
    path: str = "auto",
    separable: bool = False,
    interpret: bool = False,
    donate: bool = True,
    max_depth: int = None,
    overlap: str = "auto",
    halo: str = "auto",
):
    """Build a ``step(curr, steps) -> curr`` running ``kernel`` under the
    plane-streaming engine — the fast-by-default path for user stencils
    (``DistributedDomain.make_step(..., engine="stream")``).

    The kernel is the SAME ``(views, info) -> {name: values}`` callable the
    XLA route accepts, restricted to: ALL shifts (x, y, and z) within
    ``x_radius`` (``PlaneView.sh`` asserts this at trace time), elementwise
    arithmetic (every view read and ``info.coords()`` piece broadcasts to
    the plane), no N-D component data.
    ``separable=True`` additionally declares the kernel correct on arbitrary
    view subsets, letting many-field domains stream per-field (see
    ``plan_stream``).

    ``kernel`` may be a SEQUENCE of such callables: the STAGES of a time
    step, run in order inside one device program, each behind its own
    exchange (a later stage reads what an earlier one wrote, halo included).
    A staged step runs the plane route.

    On the plane route the step exchanges only the quantities a stage's
    kernel reads OFF-CENTRE (``plan_plane_stages``: one abstract trace of
    each kernel at build time; the resolved set is ``plan["halo_readers"]``,
    its size ``domain.step``'s ``exchanged``).  A quantity read through
    ``center()`` alone — a coefficient, an older time level — keeps a stale
    shell that nothing reads; every interior cell is bitwise what exchanging
    all of them gives.  The same trace learns which quantities the kernel
    RETURNS (``plan["writers"]``, ``domain.step``'s ``written``): the others
    are inputs of the pass and nothing else, read once a step and never
    written back; which returned quantities are RENAMES (``plan["renamed"]``,
    ``domain.step``'s ``renamed``: an output that is another writer's centre
    plane takes that writer's old array instead of a copy of it, where the
    passes run in place on the default schedule; the step loop then runs the
    permutation's period a trip, and ``steps`` that is no multiple of it --
    an odd count for one swap -- costs up to three whole-array copies and one
    block of temporaries at the program's edge, once a dispatch); which
    quantities each output TOUCHES, so that a stage too
    wide for one pass's VMEM runs as several, each over its own quantities
    (``plan["stages"]``; a step that fits in no pass raises here); and which
    are read off-centre ALONG X — the only ones that keep a VMEM ring.  The
    passes run the jaxpr that trace made: the callable is traced once.

    What the exchange still sweeps and what the passes wrap.  On the plane
    route's default schedule (not ``halo="fused"``, not ``overlap="split"``)
    a y or z axis whose sweep would be the self-wrap -- the mesh does not
    split it, and the blend kernels can engage (``pass_wrap_fills``, the
    resolved ``plan["pass_wrap_axes"]``, ``domain.step``'s ``wrapped``) --
    is not swept at all: ``stream_plane_pass`` fills that halo of every
    reader plane it loads from the plane's own interior, in VMEM.  The
    exchange keeps x (always a sweep of its own) and every axis the mesh
    splits; the result is bitwise the full exchange's on every cell a kernel
    can read.  A function of the mesh: no option.

    ``max_depth`` caps the temporal depth (wrap k / wavefront m).  The auto
    planner maximizes depth because depth is the HBM-traffic lever
    (~bytes/k per cell) — correct for bandwidth-bound kernels, but a
    COMPUTE-heavy kernel (e.g. 27 taps/cell) multiplies its VPU work by the
    depth with nothing to amortize; cap it low (2-4) for such kernels.

    ``overlap`` selects the split-step schedule (module docstring):
    ``"auto"`` resolves ``STENCIL_STREAM_OVERLAP`` > the tuned config >
    the static ``off``; an explicit ``"off"``/``"split"`` is an explicit
    request and never consults further.  ``split`` is bitwise-identical to
    ``off`` on every valid cell; a route it cannot serve (wrap, z-slab
    wavefront) degrades to ``off`` with a warning, and a compile-rejected
    split build steps down to ``off`` at the same depth through the ladder
    before any depth descent.

    ``halo`` selects the fused unpack→blend mode (module docstring):
    ``"auto"`` resolves ``STENCIL_STREAM_HALO`` > the tuned config > the
    static ``"array"``; under ``"fused"`` the packed exchange messages
    land directly in the pass's level-0 VMEM planes and the big array
    never sees a halo write — bitwise-identical to ``"array"``.  A plan
    it cannot serve (wrap, split schedule, non-``yzpack_*`` exchange
    route, uneven shards) degrades to ``"array"`` with a warning; a
    z-slab wavefront plan re-plans to the plain form first (like split);
    a compile-rejected fused build steps down to ``"array"`` at the same
    depth through the ladder before any depth descent.

    The returned step rides the resilience DEGRADATION LADDER
    (``resilience/ladder.py``): if Mosaic rejects the planned wavefront depth
    (scoped-VMEM OOM, or any other classified compile reject), the ladder
    re-plans one level shallower and retries, logging a recalibration hint,
    until the plane route is reached — at which point the failure propagates.
    Re-invocation is donation-guarded (a deleted input buffer refuses the
    descent), and fault-injection hooks labeled ``stream:<rung>`` fire at
    build and execute time (``STENCIL_FAULT_PLAN``).  The current plan is
    exposed as ``step._stream_plan``; the descent history as
    ``step._resilience.descents``.
    """
    if max_depth is not None:
        import operator

        if isinstance(max_depth, bool):  # True would cap depth at 1 silently
            raise ValueError(f"stream_depth must be an integer, got {max_depth!r}")
        try:
            max_depth = operator.index(max_depth)  # int, np.int64, ...
        except TypeError:
            raise ValueError(
                f"stream_depth must be an integer >= 1, got {max_depth!r}"
            ) from None
        if max_depth < 1:
            raise ValueError(
                f"stream_depth must be >= 1, got {max_depth} (a 0/negative "
                "cap would silently disable temporal blocking)"
            )
    from stencil_tpu.resilience.ladder import DegradationLadder, Rung

    if overlap not in ("auto",) + STREAM_OVERLAP:
        raise ValueError(
            f"unknown stream overlap {overlap!r} (one of "
            f"{('auto',) + STREAM_OVERLAP})"
        )
    if halo not in ("auto",) + STREAM_HALO:
        raise ValueError(
            f"unknown stream halo mode {halo!r} (one of "
            f"{('auto',) + STREAM_HALO})"
        )
    stages = _as_stages(kernel)
    if len(stages) > 1:
        # an exchange before every stage: the plane route's schedule
        if path not in ("auto", "plane"):
            raise ValueError(
                f"a step of {len(stages)} stages runs the plane route; "
                f"stream_path={path!r} cannot"
            )
        path = "plane"
    plan = plan_stream(dd, x_radius, path, separable, max_m=max_depth)
    if overlap != "auto" or halo != "auto":
        plan = dict(plan)
    if overlap != "auto":
        plan["overlap"] = overlap
        plan["overlap_forced"] = True
    if halo != "auto":
        plan["halo"] = halo
        plan["halo_forced"] = True
    # a split request (explicit/env/tuned) against a z-slab wavefront plan
    # re-plans to the PLAIN form when it fits: split needs z halos in the
    # big array for the exchange it overlaps, and the packed zpack_* routes
    # already de-amplified the thin-z traffic the slab form dodges.  When no
    # plain depth fits, the build's structural guard degrades split -> off.
    # The FUSED halo request re-plans the same way: the fused buffers are
    # the level-0 patch of a plain pass, and the packed routes make the
    # plain form's exchange cheap — when no plain depth fits, the build's
    # structural guard degrades fused -> array.
    if _overlap_request(plan)[0] == "split" or _halo_request(plan)[0] == "fused":
        plain = plain_wavefront_plan(dd, plan, max_depth=max_depth)
        if plain is not None:
            plan = plain

    def rung_for(p):
        # build() resolves _build_stream_step through module globals at call
        # time, so tests may monkeypatch it
        if p["route"] == "plane" and "stages" not in p:
            # the passes, before anything is built: a step that fits in no
            # pass raises HERE, and the ladder's VMEM prefilter reads them
            plan_plane_stages(dd, kernel, x_radius, p, interpret)
        suffix = ",split" if p.get("overlap") == "split" else ""
        if p.get("halo") == "fused":
            suffix += ",fused"
        return Rung(
            name=f"{p['route']}[m={p['m']}{suffix}]",
            build=lambda: _build_stream_step(
                dd, kernel, x_radius, p, interpret, donate
            ),
            state={"plan": p},
        )

    def lower(rung, cls, exc):
        plan_now = rung.state["plan"]
        from stencil_tpu.utils.logging import log_warn

        if plan_now.get("halo") == "fused":
            # first rung down: drop the fused halo mode at the SAME depth —
            # the fused pass carries extra side-buffer blocks and per-plane
            # patch selects, so a VMEM_OOM or compile reject may be the
            # fused form's fault, not the depth's
            log_warn(
                f"halo=fused on {plan_now['route']}[m={plan_now['m']}] "
                f"exceeded the compiler's capability ({cls.value}); stepping "
                "down to halo=array at the same depth"
            )
            p2 = dict(plan_now)
            p2["halo"] = "array"
            p2["halo_forced"] = True
            return rung_for(p2)
        if plan_now.get("overlap") == "split":
            # next rung down: drop the split schedule at the SAME depth —
            # the exterior passes carry their own scratch, so a VMEM_OOM or
            # compile reject may be the overlap's fault, not the depth's
            log_warn(
                f"split-step overlap on {plan_now['route']}[m={plan_now['m']}] "
                f"exceeded the compiler's capability ({cls.value}); stepping "
                "down to overlap=off at the same depth"
            )
            p2 = dict(plan_now)
            p2["overlap"] = "off"
            p2["overlap_forced"] = True
            return rung_for(p2)
        if plan_now["route"] not in ("wavefront", "wrap") or plan_now["m"] <= 1:
            return None  # plane route is the bottom rung — propagate
        new_max = plan_now["m"] - 1
        log_warn(
            f"{plan_now['route']} depth m={plan_now['m']} exceeded the "
            f"compiler's capability ({cls.value}) at runtime; stepping down to "
            f"m<={new_max} (the VMEM model under-estimates on this "
            "toolchain — consider recalibrating _VMEM_STACK_MARGIN / "
            "STENCIL_VMEM_LIMIT_BYTES)"
        )
        p2 = dict(plan_stream(dd, x_radius, path, separable, max_m=new_max))
        # a descent never re-enables split or fused: carry the
        # (post-step-down) axis state into the shallower plan as forced
        p2["overlap"] = plan_now.get("overlap", "off")
        p2["overlap_forced"] = True
        p2["halo"] = plan_now.get("halo", "array")
        p2["halo_forced"] = True
        return rung_for(p2)

    # static prefilters on real backends: a rung the VMEM model
    # (analysis/vmem.py) already rejects descends WITHOUT compiling, and a
    # rung the Mosaic legality model (analysis/kernels.py) rejects
    # descends as a recorded COMPILE_REJECT the same way (the tuple
    # verdict names the class).  Interpret mode has no Mosaic: nothing to
    # budget, nothing to lower, the models must not veto there.
    prefilter = None
    if not interpret:
        def prefilter(rung):
            from stencil_tpu.analysis import check_kernel_legal, check_vmem
            from stencil_tpu.resilience.taxonomy import FailureClass

            p = rung.state["plan"]
            reason = check_vmem(dd, p)
            if reason is not None:
                return reason
            reason = check_kernel_legal(dd, p)
            if reason is not None:
                return (reason, FailureClass.COMPILE_REJECT)
            return None

    ladder = DegradationLadder(
        rung_for(plan), lower=lower, label="stream", prefilter=prefilter
    )

    raw = dd.local_spec().raw_size()
    n_doms = dd.num_subdomains()
    band_area = 2 * (raw.y * raw.z + raw.x * raw.z + raw.x * raw.y) * len(
        dd._handles
    ) * n_doms
    def _exterior_cells(plan_now, steps: int) -> int:
        """Analytic cells recomputed by the exterior band passes for this
        dispatch (all shards, all fields) — 0 under ``overlap=off``."""
        if plan_now.get("overlap") != "split":
            return 0
        if plan_now["route"] == "wavefront":
            mm = plan_now["m"]
            blocked, rem = divmod(steps, mm)
            return band_area * (blocked * mm + rem)
        return band_area * x_radius * steps

    def step(curr, steps: int = 1):
        out = ladder.step(curr, steps)
        plan_now = ladder.rung.state["plan"]
        step._stream_plan = plan_now
        cells = _exterior_cells(plan_now, steps)
        if cells:
            telemetry.inc(tm.STEP_OVERLAP_EXTERIOR_CELLS, cells)
        return out

    step._marks_shell_stale = True
    # the eager build may already have descended (compile-phase rejection),
    # so expose the LADDER's plan, not the initial one
    step._stream_plan = ladder.rung.state["plan"]

    def span_args() -> dict:
        """What this step's ``domain.step`` span says of the plan it runs
        NOW (``telemetry/names.py SPAN_STEP``; the ladder may have moved it)."""
        plan_now = step._stream_plan
        nq = len(dd._handles)
        in_place = _plan_passes_in_place(plan_now)
        args = {
            "route": plan_now["route"],
            "x_radius": x_radius,
            "grouping": plan_now.get("grouping", "joint"),
            "streamed": nq,
            # quantities the passes carry in place (all or none): a written
            # one's output aliases its input, an unwritten one IS its input
            "aliased": nq if in_place else 0,
            # quantities riding the step's exchange: what the kernel reads
            # off-centre on the plane route (plan_plane_stages), every one
            # on the wavefront route, none on the wrap route (and, like
            # ``aliased``, none while the plan is not built yet)
            "exchanged": len(plan_now.get("halo_readers", ())),
            # quantities the passes write: what the kernel returns on the
            # plane route (plan_plane_stages), every one elsewhere
            "written": len(plan_now.get("writers", ())),
            # quantities whose write became a rename: an output that is
            # another writer's centre plane swaps handles with it instead of
            # being copied (trace_plane_kernel); they are not ``written``
            "renamed": len(plan_now.get("renamed", ())),
            # the axes whose halo the plane passes fill themselves in VMEM,
            # so that the exchange does not sweep them (pass_wrap_fills): one
            # value for every stage, a function of the mesh and the domain
            "wrapped": plan_now.get("pass_wrap_axes", ""),
            # how many quantities the step carries, and what its kernels READ
            # of them off-centre (footprint_counts: quantities read at a
            # non-zero offset, those of them read at a diagonal one, and the
            # distinct (quantity, axis, side) triples read; None each where a
            # footprint trace raised) beside what the route SERVES: every
            # exchanged quantity's halo is filled -- by the exchange's sweeps
            # or the pass's own fills -- on all six sides
            "quantities": nq,
            **(plan_now.get("footprint") or dict.fromkeys(("offcentre", "diagonal", "read_sides"))),
            "exchanged_sides": 6 * len(plan_now.get("halo_readers", ())),
        }
        if "macros_per_trip" in plan_now:
            # the wrap route: macros a trip of its device-side loop, as many
            # as bring the fresh-result pass's carry home (macro_loop)
            args["macros_per_trip"] = plan_now["macros_per_trip"]
        if "z_halo_patch" in plan_now:
            # the z-slab wavefront: whether the pass patches its z halo in
            # the lane tiles that hold it or over the whole plane
            args["z_halo_patch"] = plan_now["z_halo_patch"]
            # ... and where its lane padding lives: "vmem" (the pass widens
            # the raw block's plane itself) or "none" (nothing to pad)
            args["lane_pad"] = plan_now["lane_pad"]
        if "wired" in plan_now:
            # the axes whose sweep of the step's exchange crosses to another
            # shard, and the bytes one shard receives over them a step, all
            # stages (ops/exchange.py wire_plan): "" and 0 on one device
            args["wired"] = plan_now["wired"]
            args["wire_bytes"] = plan_now["wire_bytes"]
        per_stage = plan_now.get("stages", ())
        if len(per_stage) > 1:
            # a staged step says the three PER STAGE, in order ("6/3"): each
            # stage exchanges, writes and carries its own subset ("/" because
            # a profiler annotation splits its arguments at "," and "=")
            def each(count):
                return "/".join(str(count(st)) for st in per_stage)

            args["stages"] = len(per_stage)
            args["passes"] = sum(len(st["passes"]) for st in per_stage)
            args["exchanged"] = each(lambda st: len(st["readers"]))
            args["written"] = each(lambda st: sum(len(p["writes"]) for p in st["passes"]))
            args["renamed"] = each(lambda st: sum(len(p["renames"]) for p in st["passes"]))
            args["aliased"] = each(
                lambda st: len({q for p in st["passes"] for q in p["reads"]}) if in_place else 0
            )
        return args

    step._span_args = span_args
    step._resilience = ladder
    step._resilience_label = "stream"
    return step


# --- batched dispatch (serve/pack.py) ----------------------------------------
#
# The serving layer's batch planner stacks geometry-matched tenant states
# along a leading axis and runs them as ONE dispatch.  How the batch axis
# is carried depends on the engine under the step:
#
# * the XLA slice engine (``make_step``'s jnp route) is plain traceable
#   jax — ``vmap`` threads the batch axis straight through the shard_map
#   and its ppermutes, and XLA fuses the batched program;
# * the plane pipeline (``make_stream_step``) bottoms out in pallas_call
#   grids whose VMEM plane rings are sized for ONE shard — vmap over a
#   pallas grid is not a supported lowering, so the batch axis is carried
#   as an EXPLICIT leading dim instead: ``lax.scan`` over the stacked
#   states calls the unbatched pass once per element inside one jitted
#   program (one dispatch at the host boundary, which is what serving
#   throughput is bounded by — see docs/serving.md "Throughput").
#
# Either way the per-element program is the UNBATCHED step itself, so each
# tenant's slice is bitwise-identical to a serial dispatch (the soak's
# packed legs pin this digest-for-digest).


def batch_axis_mode(step) -> str:
    """How a batched dispatch must carry the leading batch axis over
    ``step``: ``"vmap"`` for traceable-jax steps, ``"leading_dim"`` (an
    explicit scan) for plane-pipeline steps (``_stream_plan`` present)
    whose pallas grids vmap cannot lower."""
    return (
        "leading_dim"
        if getattr(step, "_stream_plan", None) is not None
        else "vmap"
    )


def make_batched_dispatch(
    step_fn: Callable, steps: int, mode: str
) -> Callable:
    """One jitted callable running ``step_fn(curr, steps)`` over every
    element of a stacked state dict (leading batch axis), per ``mode``
    (see ``batch_axis_mode``).  ``step_fn`` must be the RESOLVED per-shard
    callable — a raw ``make_step`` jit or a ladder's ``built()`` — not the
    telemetry-wrapping closure.  The stacked input is donated: callers
    stack with ``jnp.stack`` (a copy), so the per-tenant source buffers
    stay live for the serial fallback path."""
    if mode not in ("vmap", "leading_dim"):
        raise ValueError(
            f"unknown batch axis mode {mode!r} (vmap | leading_dim)"
        )
    if mode == "vmap":

        def batched(stacked):
            return jax.vmap(lambda c: step_fn(c, steps))(stacked)

    else:

        def batched(stacked):
            def body(carry, c):
                return carry, step_fn(c, steps)

            return lax.scan(body, 0, stacked)[1]

    return jax.jit(batched, donate_argnums=0)
