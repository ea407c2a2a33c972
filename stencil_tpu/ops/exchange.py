"""The halo exchange — the reference's entire transport layer as collectives.

Replaces the five transports + poll loop (reference tx_cuda.cuh:39-974,
src/stencil.cu:670-864) with ``lax.ppermute`` inside ``shard_map`` over the 3D
device mesh.  ICI plays NVLink/IPC; DCN plays inter-node MPI; XLA's async
collective scheduling replaces the hand-rolled state machines (SURVEY.md §2.2
"TPU mapping").

Design: each shard is a *shell-carrying* block — interior of size ``n`` plus
``radius`` face-widths of halo on each side, exactly the reference's
``LocalDomain`` allocation (local_domain.cuh:309-313 ``raw_size``).  The
exchange runs **three axis sweeps** (x, then y, then z).  Each sweep sends
slabs spanning the *full* extent of the other axes — including their already-
filled halos — so edge and corner data propagate without dedicated diagonal
messages: 26 neighbor messages collapse into <=6 face ppermutes (SURVEY.md §7
"26-neighbor exchange").

Two WIRED sweeps that follow each other fly JOINTLY (``_sweep_groups``; x and
y on mesh [2,2,1]): a chip's x and y neighbours sit on different ICI ports, and
all the y slabs need of the x sweep is their corner columns.  So both axes'
faces are cut from the blocks as they enter and all four messages are sent
before any is used; the corner strips — the x slabs a shard RECEIVED, on the y
slabs' rows — follow the y faces to the same neighbours as one small fused
message a direction (``_relay_corners``: <=2 more ppermutes, under the y
direction scopes) and overwrite the stale columns of the received y slabs
before they are blended.  The halos are bitwise those of the sweeps run in
turn, and a sweep that flies alone is the same code with a group of one.

The ``-dir`` extent convention holds by construction: the slab sent in
direction ``+a`` has width ``radius(-a)`` (the receiver's ``-a`` halo width),
and the slab sent in ``-a`` has width ``radius(+a)`` (packer.cuh:91-93).

A mesh axis of size 1 has no neighbor but the shard itself: its sweep is
the SELF-WRAP (``halo_blend.wrap_halo`` under ``exchange.<axis>.wrap``) — one
in-place kernel per quantity copies the shard's own interior cells onto its
halos, no slab cut, no message and no unpack, like the reference's same-GPU
``PeerAccessSender`` copy kernels (tx_cuda.cuh:39-104).  Where the blend
kernels are off (CPU, ``STENCIL_HALO_BLEND=0``) or cannot engage (N-D blocks,
exotic dtypes) such an axis still ppermutes to itself, which is the same
periodic boundary by the general path.  The z-slab routes' slab BUFFERS
(``ops/stream.py permute_and_extend_z_slabs``: z-major ``(Xr, 2s, Yr)``, the z
halo kept out of the big array) go by the same rule since PR 56
(``slab_wrap_axes``): on an unsplit z the outgoing buffer is the incoming one,
on an unsplit y or x ``wrap_halo`` fills the buffer's own shell in place, and
only a split axis cuts halves, sends and lands them.

The y and z sweeps have selectable ROUTES (``EXCHANGE_ROUTES``, a tuner
axis — docs/tuning.md "Exchange routes"): ``direct`` sends the thin sliver
slabs as sliced (the historical path; the z sliver is ~64×-amplified on
the (8,128) tiling — PERF_NOTES "Thin z-region access" — and the y sliver
~8/(2r)-amplified on the sublane granule — "Thin y-region access"), the
``zpack_*`` routes send the z shell lane-major through the pack pipeline
(``_zpack_sweep`` / ops/pack.py), and the ``yzpack_*`` routes additionally
send the y shell sublane-major (``_ypack_sweep``) — the reference packer's
move (packer.cuh:71-366): reshape the message, not the domain.  All routes
produce bitwise-identical halos.

``fused_shell_exchange`` is the exchange's FUSED-CONSUMER form (the
packed-exchange story's second half): instead of unpacking received
messages back into the big arrays, it returns the received per-axis shell
buffers themselves — sweep-ordered corner patching happens on the small
buffers — so a consumer (the stream engine's ``halo="fused"`` mode,
ops/stream.py) can land them directly in its VMEM working planes and the
big array never sees a halo write at all.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Dict, List, Mapping, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from stencil_tpu.core.dim3 import Dim3
from jax import shard_map
from stencil_tpu.core.radius import Radius
from stencil_tpu.parallel.mesh import MESH_AXES
from stencil_tpu.telemetry import names as tm

#: exchange implementations for the y/z axis sweeps — a first-class tuner
#: axis (tune/space.py ``exchange_space``; docs/tuning.md "Exchange
#: routes"):
#:
#: * ``direct``       — send the (X, Y, r) z-sliver and (X, r, Z) y-sliver
#:   slabs as sliced (the historical path; the static no-tune fallback).
#:   On the (8,128)-tiled layout the z sliver is ~64×-amplified (PERF_NOTES
#:   "Thin z-region access"): a radius-2 z exchange costs ~one full-domain
#:   copy at 512³.  The y sliver is sublane-amplified ~8/(2r) (PERF_NOTES
#:   "Thin y-region access") — cheaper, but still the only unfused leg.
#: * ``zpack_xla``    — reshape the message, not the domain: the z shell
#:   travels lane-major as ``(2m, Y, Xpad)`` (ops/pack.py ``pack_zshell_*``)
#:   with XLA fusing the slice+transpose into the permute operand.
#: * ``zpack_pallas`` — same buffer, but packed/unpacked by the tile-local
#:   pallas pipeline (whole x-planes HBM->VMEM, the thin cut in VMEM) so the
#:   big array is never read or written through a thin-z window at all.
#: * ``yzpack_xla``   — ``zpack_xla`` plus the y twin: the y shell travels
#:   sublane-major as ``(2m, X, Z)`` (ops/pack.py ``pack_yshell_*``), so
#:   BOTH thin sweeps ride packed messages and only whole x-plane slabs
#:   remain direct.
#: * ``yzpack_pallas`` — both packed sweeps through the tile-local pallas
#:   pipelines: the big array is never read or written through a thin-y OR
#:   thin-z window.
EXCHANGE_ROUTES = (
    "direct", "zpack_xla", "zpack_pallas", "yzpack_xla", "yzpack_pallas"
)

#: routes whose z sweep rides the packed z-shell pipeline
Z_PACK_ROUTES = ("zpack_xla", "zpack_pallas", "yzpack_xla", "yzpack_pallas")
#: routes whose y sweep rides the packed y-shell pipeline
Y_PACK_ROUTES = ("yzpack_xla", "yzpack_pallas")


def zpack_supported(dtypes, valid_last=None) -> bool:
    """Can the packed z sweep engage for this configuration?  Requires an
    evenly divided z axis (the pack kernels cut the shell at static offsets;
    a padded z falls back to ``direct`` for that sweep) and dtypes whose
    (8,128) tile geometry the kernels know (``halo_blend.supports``)."""
    from stencil_tpu.ops import halo_blend

    if valid_last is not None and valid_last[2] is not None:
        return False
    return all(halo_blend.supports(dt) for dt in dtypes)


def ypack_supported(dtypes, valid_last=None) -> bool:
    """Can the packed y sweep engage?  The y twin of ``zpack_supported``:
    an evenly divided y axis (static row offsets) and known tile
    geometry."""
    from stencil_tpu.ops import halo_blend

    if valid_last is not None and valid_last[1] is not None:
        return False
    return all(halo_blend.supports(dt) for dt in dtypes)


def route_supported(route: str, dtypes, valid_last=None) -> bool:
    """Can ``route`` engage for ANY of its packed sweeps here?  ``direct``
    always; ``zpack_*`` need the z sweep; ``yzpack_*`` engage if EITHER
    packed sweep can (each sweep degrades independently inside the
    exchange, so a partially engageable route is still a different — and
    correct — program from ``direct``)."""
    if route == "direct":
        return True
    z_ok = zpack_supported(dtypes, valid_last)
    if route in Y_PACK_ROUTES:
        return z_ok or ypack_supported(dtypes, valid_last)
    return z_ok


def route_vma_check(dtypes, valid_last, ndim_extra: int, route: str) -> bool:
    """``check_vma`` for a shard_map wrapping the exchange, route-aware: the
    packed pallas routes' outputs carry no vma annotation (exactly like the
    blend kernels), so validation must stay off whenever one can engage."""
    from stencil_tpu.ops import halo_blend

    if route.endswith("pallas") and (
        zpack_supported(dtypes, valid_last)
        or (route in Y_PACK_ROUTES and ypack_supported(dtypes, valid_last))
    ):
        return False
    return halo_blend.vma_check(dtypes, valid_last, ndim_extra)


def zpack_message_stats(raw_spatial, r_lo: int, r_hi: int, itemsizes) -> Tuple[int, int]:
    """Analytic (bytes, kernels) per shard per exchange through a packed z
    sweep: one ``(depth, Y, Xpad)`` buffer per 3D quantity slice per
    direction, one pack + one unpack kernel each (the ``exchange.packed.*``
    telemetry counters — modeled, like ``exchange_bytes_total``)."""
    from stencil_tpu.ops.pack import lane_pad

    X, Y, _ = raw_spatial
    nbytes = 0
    kernels = 0
    for depth in (r_lo, r_hi):
        if depth == 0:
            continue
        for isz in itemsizes:
            nbytes += depth * Y * lane_pad(X) * isz
            kernels += 2  # pack + unpack
    return nbytes, kernels


def ypack_message_stats(raw_spatial, r_lo: int, r_hi: int, itemsizes) -> Tuple[int, int]:
    """The y twin of ``zpack_message_stats``: one sublane-major
    ``(depth, X, Z)`` buffer per quantity slice per direction (no explicit
    pad — Z stays the lane dim), one pack + one unpack kernel each."""
    X, _, Z = raw_spatial
    nbytes = 0
    kernels = 0
    for depth in (r_lo, r_hi):
        if depth == 0:
            continue
        for isz in itemsizes:
            nbytes += depth * X * Z * isz
            kernels += 2  # pack + unpack
    return nbytes, kernels


def _shift_from_low(x, axis_name: str, n: int):
    """Each shard receives the value held by its -1 neighbor (data moves +)."""
    # NVTX analog: a REGISTERED per-direction scope (names.ALL_SPANS), so
    # profiler traces attribute this ppermute's device time to its mesh hop
    with jax.named_scope(tm.exchange_direction_span(axis_name, "low")):
        return lax.ppermute(x, axis_name, [(k, (k + 1) % n) for k in range(n)])


def _shift_from_high(x, axis_name: str, n: int):
    """Each shard receives the value held by its +1 neighbor (data moves -)."""
    with jax.named_scope(tm.exchange_direction_span(axis_name, "high")):
        return lax.ppermute(x, axis_name, [(k, (k - 1) % n) for k in range(n)])


class _Stacked(NamedTuple):
    """Several quantities' slabs as ONE message (``_stack``): ``bufs`` -- per
    dtype, in first-seen order, the slabs of that dtype concatenated along a
    flattened leading (quantity / batch) axis --, ``idxs`` -- which slabs each
    buffer holds -- and the slabs' own ``shapes``.  A single slab is its own
    buffer, unreshaped."""

    bufs: List[jax.Array]
    idxs: List[List[int]]
    shapes: List[Tuple[int, ...]]


def _stack(slabs: List[jax.Array]) -> _Stacked:
    """The reference packs all quantities of one neighbor into a single aligned
    buffer so message count is independent of field count (packer.cuh:52-69,
    146-160).  Here: same-dtype slabs stack along a flattened leading axis."""
    shapes = [s.shape for s in slabs]
    if len(slabs) == 1:
        return _Stacked(list(slabs), [[0]], shapes)
    # flatten leading (quantity/batch) dims so same-dtype slabs concatenate
    flat = [s.reshape((-1,) + s.shape[-3:]) for s in slabs]
    groups: Dict[object, List[int]] = {}
    for i, s in enumerate(flat):
        groups.setdefault(s.dtype, []).append(i)
    return _Stacked(
        [jnp.concatenate([flat[i] for i in idxs], axis=0) for idxs in groups.values()],
        list(groups.values()), shapes,
    )


def _unstack(st: _Stacked) -> List[jax.Array]:
    """The slabs of a message, in their original order and shapes."""
    if len(st.shapes) == 1:
        return [st.bufs[0].reshape(st.shapes[0])]
    out: List[Optional[jax.Array]] = [None] * len(st.shapes)
    for buf, idxs in zip(st.bufs, st.idxs):
        off = 0
        for i in idxs:
            k = math.prod(st.shapes[i][:-3])
            out[i] = buf[off : off + k].reshape(st.shapes[i])
            off += k
    return out  # type: ignore[return-value]


def _shift_bufs(bufs: List[jax.Array], shift_fn, name: str, n_dev: int) -> List[jax.Array]:
    """ppermute a message's buffers (``_Stacked.bufs``) as ONE
    collective-permute: one dtype's buffer as it is; mixed dtypes additionally
    fuse byte-wise via ``bitcast_convert_type`` — one buffer per direction,
    exactly the reference's byte-packed layout."""
    if len(bufs) == 1:
        return [shift_fn(bufs[0], name, n_dev)]

    # mixed dtypes: one byte buffer per direction (packer.cuh:52-69)
    def to_bytes(v):
        if v.dtype == jnp.bool_:
            return v.reshape(-1).astype(jnp.uint8)  # lossless 0/1
        if v.dtype.itemsize > 1:
            return lax.bitcast_convert_type(v.reshape(-1), jnp.uint8).reshape(-1)
        return lax.bitcast_convert_type(v.reshape(-1), jnp.uint8)

    def from_bytes(p, dt):
        if dt == jnp.bool_:
            return p.astype(jnp.bool_)
        if jnp.dtype(dt).itemsize > 1:
            return lax.bitcast_convert_type(
                p.reshape(-1, jnp.dtype(dt).itemsize), dt
            )
        return lax.bitcast_convert_type(p, dt)

    recv_bytes = shift_fn(jnp.concatenate([to_bytes(buf) for buf in bufs]), name, n_dev)
    recv, off = [], 0
    for buf in bufs:
        nbytes = buf.size * buf.dtype.itemsize
        recv.append(from_bytes(recv_bytes[off : off + nbytes], buf.dtype).reshape(buf.shape))
        off += nbytes
    return recv


def _fused_shift(slabs: List[jax.Array], shift_fn, name: str, n_dev: int) -> List[jax.Array]:
    """ppermute several quantities' slabs as ONE fused message (``_stack``,
    ``_shift_bufs``); returns received slabs in the original order/shapes."""
    st = _stack(slabs)
    return _unstack(st._replace(bufs=_shift_bufs(st.bufs, shift_fn, name, n_dev)))


def _zpack_sweep(
    blocks: List[jax.Array],
    r_lo: int,
    r_hi: int,
    n_pad: int,
    name: str,
    n_dev: int,
    route: str,
) -> List[jax.Array]:
    """One z-axis sweep through the packed pipeline (the tentpole of the
    exchange-route PR): extract every quantity's 2m-deep shell into
    lane-major ``(2m, Y, Xpad)`` buffers (``ops/pack.py``), ppermute the
    buffers as ONE fused message per direction (the ≤6-permute structure is
    preserved — this replaces the direct sweep's permutes one-for-one), and
    blend them back through aliased tile-local kernels.  On the
    ``zpack_pallas`` route the big array is only ever touched as whole
    x-planes — the ~64×-amplified thin-z access and the ``sliver-dus``
    relayout trap are impossible by construction (PERF_NOTES "Thin z-region
    access").  ``zpack_xla`` sends the same buffer but lets XLA fuse the
    packing; the received shell re-materializes as a thin slab only outside
    the big array, then lands via the blend kernels.

    Leading component/batch dims are flattened into per-slice 3D packs;
    all slices of all quantities still fuse into one message per direction.
    """
    from stencil_tpu.ops import halo_blend
    from stencil_tpu.ops.pack import (
        pack_zshell_pallas,
        pack_zshell_xla,
        unpack_zshell_pallas,
        zshell_to_slab,
    )

    interp = halo_blend.interpret_mode()
    pallas = route.endswith("pallas")
    # each 3D slice of each quantity packs its own buffer; the per-direction
    # message stays ONE fused ppermute regardless (packer.cuh:52-69)
    flat = [b.reshape((-1,) + b.shape[-3:]) for b in blocks]

    def pack_all(z0: int, depth: int) -> List[jax.Array]:
        return [
            pack_zshell_pallas(f[j], z0, depth, interpret=interp)
            if pallas
            else pack_zshell_xla(f[j], z0, depth)
            for f in flat
            for j in range(f.shape[0])
        ]

    lo_bufs = hi_bufs = None
    if r_lo > 0:
        # my low halo [z=0, r_lo) <- -z neighbor's top interior slab
        lo_bufs = _fused_shift(pack_all(n_pad, r_lo), _shift_from_low, name, n_dev)
    if r_hi > 0:
        hi_bufs = _fused_shift(pack_all(r_lo, r_hi), _shift_from_high, name, n_dev)
    blend = halo_blend.enabled()
    out_blocks: List[jax.Array] = []
    idx = 0  # slice cursor — pack_all emits both directions in this order
    for b, f in zip(blocks, flat):
        outs = []
        for j in range(f.shape[0]):
            s = f[j]
            x = s.shape[0]
            if lo_bufs is not None:
                if pallas:
                    s = unpack_zshell_pallas(s, lo_bufs[idx], 0, r_lo, interpret=interp)
                elif blend:
                    s = halo_blend.blend_slab(
                        s, zshell_to_slab(lo_bufs[idx], x), 2, 0, interpret=interp
                    )
                else:
                    s = s.at[:, :, 0:r_lo].set(zshell_to_slab(lo_bufs[idx], x))
            if hi_bufs is not None:
                z0 = r_lo + n_pad
                if pallas:
                    s = unpack_zshell_pallas(s, hi_bufs[idx], z0, r_hi, interpret=interp)
                elif blend:
                    s = halo_blend.blend_slab(
                        s, zshell_to_slab(hi_bufs[idx], x), 2, z0, interpret=interp
                    )
                else:
                    s = s.at[:, :, z0 : z0 + r_hi].set(zshell_to_slab(hi_bufs[idx], x))
            outs.append(s)
            idx += 1
        out = outs[0] if len(outs) == 1 else jnp.concatenate([o[None] for o in outs])
        out_blocks.append(out.reshape(b.shape))
    return out_blocks


def _ypack_sweep(
    blocks: List[jax.Array],
    r_lo: int,
    r_hi: int,
    n_pad: int,
    name: str,
    n_dev: int,
    route: str,
) -> List[jax.Array]:
    """One y-axis sweep through the packed pipeline — the sublane twin of
    ``_zpack_sweep`` (this PR's tentpole): every quantity's 2m-deep y shell
    is extracted into sublane-major ``(2m, X, Z)`` buffers (``ops/pack.py``
    ``pack_yshell_*``), ppermuted as ONE fused message per direction, and
    blended back tile-locally.  On the ``yzpack_pallas`` route the big
    array is only ever touched as whole x-planes — the ~8/(2r) sublane
    amplification of thin y windows (PERF_NOTES "Thin y-region access")
    never hits the big array.  ``yzpack_xla`` sends the same buffer but
    lets XLA fuse the packing; the received shell re-materializes as a thin
    slab only outside the big array, then lands via the blend kernels.

    Leading component/batch dims are flattened into per-slice 3D packs;
    all slices of all quantities still fuse into one message per direction.
    """
    from stencil_tpu.ops import halo_blend
    from stencil_tpu.ops.pack import (
        pack_yshell_pallas,
        pack_yshell_xla,
        unpack_yshell_pallas,
        yshell_to_slab,
    )

    interp = halo_blend.interpret_mode()
    pallas = route.endswith("pallas")
    flat = [b.reshape((-1,) + b.shape[-3:]) for b in blocks]

    def pack_all(y0: int, depth: int) -> List[jax.Array]:
        return [
            pack_yshell_pallas(f[j], y0, depth, interpret=interp)
            if pallas
            else pack_yshell_xla(f[j], y0, depth)
            for f in flat
            for j in range(f.shape[0])
        ]

    lo_bufs = hi_bufs = None
    if r_lo > 0:
        # my low halo [y=0, r_lo) <- -y neighbor's top interior rows
        lo_bufs = _fused_shift(pack_all(n_pad, r_lo), _shift_from_low, name, n_dev)
    if r_hi > 0:
        hi_bufs = _fused_shift(pack_all(r_lo, r_hi), _shift_from_high, name, n_dev)
    blend = halo_blend.enabled()
    out_blocks: List[jax.Array] = []
    idx = 0  # slice cursor — pack_all emits both directions in this order
    for b, f in zip(blocks, flat):
        outs = []
        for j in range(f.shape[0]):
            s = f[j]
            if lo_bufs is not None:
                if pallas:
                    s = unpack_yshell_pallas(s, lo_bufs[idx], 0, r_lo, interpret=interp)
                elif blend:
                    s = halo_blend.blend_slab(
                        s, yshell_to_slab(lo_bufs[idx]), 1, 0, interpret=interp
                    )
                else:
                    s = s.at[:, 0:r_lo, :].set(yshell_to_slab(lo_bufs[idx]))
            if hi_bufs is not None:
                y0 = r_lo + n_pad
                if pallas:
                    s = unpack_yshell_pallas(s, hi_bufs[idx], y0, r_hi, interpret=interp)
                elif blend:
                    s = halo_blend.blend_slab(
                        s, yshell_to_slab(hi_bufs[idx]), 1, y0, interpret=interp
                    )
                else:
                    s = s.at[:, y0 : y0 + r_hi, :].set(yshell_to_slab(hi_bufs[idx]))
            outs.append(s)
            idx += 1
        out = outs[0] if len(outs) == 1 else jnp.concatenate([o[None] for o in outs])
        out_blocks.append(out.reshape(b.shape))
    return out_blocks


def _sweep_kind(
    axis: int,
    r_lo: int,
    r_hi: int,
    n_dev: int,
    size: int,
    v_last: Optional[int],
    route: str,
    dtypes,
    all_3d: bool,
) -> str:
    """Which implementation one axis sweep takes — ``ypack`` / ``zpack``
    (the route's packed pipelines), ``wrap`` (the self-wrap kernel) or
    ``direct`` (cut, ppermute, blend / DUS) — from what the sweep can observe:
    the route, the mesh extent, the axis padding, block rank and dtype.

    A packed route engages per SWEEP: the y sweep packs on the yzpack_*
    routes, the z sweep on every packed route; a sweep that structurally
    cannot engage (uneven axis, unsupported dtype) runs ``direct``, so a
    pinned route is always correct.  An axis the mesh does not split takes
    the self-wrap wherever the blend kernels can engage: its padding offset is
    static there (one shard is the last shard)."""
    from stencil_tpu.ops import halo_blend

    n_pad = size - r_lo - r_hi
    n_last = n_pad if v_last is None else v_last  # the last shard's valid width
    known = all(halo_blend.supports(dt) for dt in dtypes)
    if route in Y_PACK_ROUTES and axis == 1 and n_last == n_pad and known:
        return "ypack"
    if route != "direct" and axis == 2 and n_last == n_pad and known:
        return "zpack"
    if (
        n_dev == 1
        and known
        and all_3d
        and halo_blend.enabled()
        # narrower interiors than halos read halo cells as sources: the
        # general path's cut-before-write order is the semantics there
        and n_last >= max(r_lo, r_hi)
    ):
        return "wrap"
    return "direct"


def wrap_axes(
    mesh_shape: Tuple[int, int, int],
    radius: Radius,
    raw_spatial: Tuple[int, int, int],
    dtypes,
    all_3d: bool = True,
    valid_last: Optional[Tuple[Optional[int], Optional[int], Optional[int]]] = None,
    route: str = "direct",
) -> str:
    """The mesh axes (a substring of ``"xyz"``) whose sweep of this exchange
    is the self-wrap kernel — what ``exchange.route`` and ``domain.exchange``
    report as ``wrap_axes``."""
    return "".join(
        MESH_AXES[a]
        for a in range(3)
        if radius.axis(a, -1) + radius.axis(a, +1) > 0
        and _sweep_kind(
            a, radius.axis(a, -1), radius.axis(a, +1), mesh_shape[a],
            raw_spatial[a], valid_last[a] if valid_last is not None else None,
            route, dtypes, all_3d,
        ) == "wrap"
    )


class _Sweep(NamedTuple):
    """One axis sweep of an exchange: the mesh ``axis``, the ``kind``
    ``_sweep_kind`` picks for it, the halo widths of its two sides, the mesh
    extent ``n_dev`` and the raw extent ``size`` on that axis, and ``v_last``,
    the last shard's valid interior cells (None = even)."""

    axis: int
    kind: str
    r_lo: int
    r_hi: int
    n_dev: int
    size: int
    v_last: Optional[int]

    @property
    def wired(self) -> bool:
        """Cut, ``ppermute`` to ANOTHER shard, blend: the kind that can fly
        beside another of its kind."""
        return self.kind == "direct" and self.n_dev > 1


def _sweeps(mesh_shape, radius, raw_spatial, dtypes, valid_last, route, axes, all_3d=True):
    """The ``_Sweep`` of every axis of one ``halo_exchange_multi`` that has a
    halo, in sweep order, the kind as ``_sweep_kind`` picks it (block rank,
    ``all_3d``, matters to the self-wrap alone, which is neither a wire nor a
    packed sweep)."""
    for a in axes:
        r_lo, r_hi = radius.axis(a, -1), radius.axis(a, +1)
        if r_lo + r_hi:
            v_last = valid_last[a] if valid_last is not None else None
            yield _Sweep(
                a,
                _sweep_kind(
                    a, r_lo, r_hi, mesh_shape[a], raw_spatial[a], v_last, route,
                    dtypes, all_3d,
                ),
                r_lo, r_hi, mesh_shape[a], raw_spatial[a], v_last,
            )


def _sweep_groups(sweeps) -> List[List[_Sweep]]:
    """The sweeps of one exchange in the groups that fly together: the first
    two WIRED sweeps that follow each other with no other sweep between them
    are one group of two -- both axes' faces cut from the blocks as they enter
    and sent at once, the corner strips relayed behind them (``_sweep_group``)
    -- and every other sweep is a group of one, in sweep order.  x and y on
    mesh [2,2,1] and [2,2,2] (z after the pair, its slabs carrying the pair's
    finished halos), y and z on [1,2,2]; on [2,1,2] the unsplit y lies between
    the wires and every sweep flies alone, as do the packed kinds."""
    sweeps = list(sweeps)
    for i in range(len(sweeps) - 1):
        if sweeps[i].wired and sweeps[i + 1].wired:
            return (
                [[s] for s in sweeps[:i]] + [sweeps[i : i + 2]]
                + [[s] for s in sweeps[i + 2 :]]
            )
    return [[s] for s in sweeps]


def _relay_cells(first: _Sweep, second: _Sweep, raw_spatial, r_second: int) -> int:
    """The cells a quantity's corner relay carries to one side of ``second``
    (halo width ``r_second`` there): both received slabs of ``first`` on that
    side's slab rows, over the whole raw extent of the third axis."""
    (third,) = {0, 1, 2} - {first.axis, second.axis}
    return (first.r_lo + first.r_hi) * r_second * raw_spatial[third]


_PACKED_STATS = {"zpack": zpack_message_stats, "ypack": ypack_message_stats}


def exchange_account(
    mesh_shape: Tuple[int, int, int],
    radius: Radius,
    raw_spatial: Tuple[int, int, int],
    dtypes,
    valid_last: Optional[Tuple[Optional[int], Optional[int], Optional[int]]] = None,
    route: str = "direct",
    axes: Tuple[int, ...] = (0, 1, 2),
    cells=None,
) -> "WireAccount":
    """The account of ONE ``halo_exchange_multi`` of blocks of ``dtypes``
    (``cells``: the 3D slices each block holds, its leading dims multiplied; 1
    each when None), read off ``_sweep_kind`` sweep by sweep:

    * ``hops`` -- ``{(mesh axis, "low" | "high"): bytes ONE shard receives}``
      over wires: a hop is there where the mesh splits the axis among ``axes``
      -- so the sweep cannot be the self-wrap and the direction is a
      ``ppermute`` to ANOTHER shard -- and the side has a halo.  The bytes are
      those of the message the sweep kind forms: the sliced slab of ``direct``
      (the side's halo width x the raw cross-section), the packed buffer of
      ``ypack`` / ``zpack`` with its lane padding, every quantity; the second
      axis of a jointly swept pair (``_sweep_groups``) adds the corner relay
      that follows its face (``_relay_cells``).  ``{}`` on one device.
    * ``joint`` -- ``(the pair of axes whose sweeps fly jointly, 1)``,
      ``("", 0)`` where every sweep flies alone.
    * ``packed`` -- ``(bytes, kernels)`` one shard moves through the packed
      sweeps' pack and unpack kernels (``z/ypack_message_stats``), split axis
      or not: a packed sweep packs its own wrap too.

    The ONE account of the wires: the step builders' ``WireAccount``s and
    ``DistributedDomain``'s own (``exchange()``, ``exchange_hop_bytes``) are
    made of it, and ``tests/test_wire_account.py`` holds it to the ``ppermute``
    operands of the traced programs."""
    itemsizes = [
        jnp.dtype(dt).itemsize * k for dt, k in zip(dtypes, cells or [1] * len(dtypes))
    ]
    hops: Dict[Tuple[str, str], int] = {}
    packed_bytes = packed_kernels = 0
    joint = ""
    for group in _sweep_groups(
        _sweeps(mesh_shape, radius, raw_spatial, dtypes, valid_last, route, axes)
    ):
        for s in group:
            if s.kind in _PACKED_STATS:
                nbytes, kernels = _PACKED_STATS[s.kind](raw_spatial, s.r_lo, s.r_hi, itemsizes)
                packed_bytes += nbytes
                packed_kernels += kernels
            if s.n_dev == 1:
                continue
            assert s.kind != "wrap", (s.axis, mesh_shape)  # a split axis has a neighbour
            face = math.prod(raw_spatial) // raw_spatial[s.axis]
            for side, lo, hi in (("low", s.r_lo, 0), ("high", 0, s.r_hi)):
                if s.kind in _PACKED_STATS:
                    nbytes = _PACKED_STATS[s.kind](raw_spatial, lo, hi, itemsizes)[0]
                else:
                    nbytes = (lo + hi) * face * sum(itemsizes)
                    if s is not group[0]:  # the corner relay behind the face
                        nbytes += _relay_cells(group[0], s, raw_spatial, lo + hi) * sum(itemsizes)
                if nbytes:
                    hops[(MESH_AXES[s.axis], side)] = nbytes
        if len(group) == 2:
            joint = "".join(MESH_AXES[s.axis] for s in group)
    return WireAccount(1, hops, 1, (packed_bytes, packed_kernels), (joint, 1 if joint else 0))


class WireAccount(NamedTuple):
    """What a built step sends over wires, declared by its builder from the
    message plan it resolved: ``exchanges`` halo exchanges, ``hops`` --
    ``{(mesh axis, side): bytes ONE shard receives}`` -- and ``packed`` --
    ``(bytes, kernels)`` one shard's packed sweeps move --, both summed over
    those exchanges, per ``every`` raw steps (a macro's depth; a dispatch
    whose steps are no multiple runs one more, shallower macro behind a whole
    exchange); ``joint`` -- ``(axes, sweeps)``: the mesh axes whose sweeps
    fly jointly in those exchanges (``_sweep_groups``; "" where every sweep
    flies alone) and how many joint sweeps a unit runs.
    ``DistributedDomain.run_step`` counts ``domain.exchange.*``,
    ``exchange.hop.*.bytes``, ``exchange.packed.*`` and
    ``exchange.joint.sweeps`` from it and the ``domain.step`` span says
    ``wired`` / ``wire_bytes`` / ``joint`` of it (``span_args``), so span and
    counter cannot differ."""

    exchanges: int
    hops: Mapping[Tuple[str, str], int]
    every: int = 1
    packed: Tuple[int, int] = (0, 0)
    joint: Tuple[str, int] = ("", 0)

    def units(self, raw_steps: int) -> int:
        """The exchanging units (steps, macros) a dispatch of ``raw_steps``
        runs."""
        return -(-raw_steps // self.every)

    def said(self) -> Tuple[str, int]:
        """``(wired, wire_bytes)`` as a span says them: the axes with a hop,
        and the bytes one shard receives over all hops a RAW step (a macro's
        bytes over its depth, whole where the depth divides them)."""
        axes = {axis for axis, _ in self.hops}
        return (
            "".join(a for a in MESH_AXES if a in axes),
            sum(self.hops.values()) // self.every,
        )

    def span_args(self) -> Dict[str, object]:
        """What a ``domain.step`` / ``domain.exchange`` span says of this
        account: ``wired`` and ``wire_bytes`` (``said``), and ``joint``, the
        axes whose sweeps fly jointly."""
        wired, wire_bytes = self.said()
        return {"wired": wired, "wire_bytes": wire_bytes, "joint": self.joint[0]}


def sum_accounts(accounts, every: int = 1) -> WireAccount:
    """The exchanges of one unit (a step's stages, a macro's one) as one
    account: counts, hops and packed traffic added up."""
    accounts = list(accounts)
    return WireAccount(
        sum(a.exchanges for a in accounts),
        sum_hops(*(a.hops for a in accounts)),
        every,
        (sum(a.packed[0] for a in accounts), sum(a.packed[1] for a in accounts)),
        (
            "".join(x for x in MESH_AXES if any(x in a.joint[0] for a in accounts)),
            sum(a.joint[1] for a in accounts),
        ),
    )


def z_slab_hops(mesh_shape: Tuple[int, int, int], Xr: int, Yr: int, s: int,
                itemsizes) -> Dict[Tuple[str, str], int]:
    """The hops of one macro's z-slab permutes (``ops/stream.py
    permute_and_extend_z_slabs``), one z-major ``(Xr, 2s, Yr)`` slab buffer a
    quantity: its two ``(Xr, s, Yr)`` halves over z, each then extended by
    ``(Xr, s, s)`` rows from either y neighbour and ``(s, s, Yr)`` planes from
    either x neighbour.  As ``exchange_account``'s hops: split axes only (an
    unsplit axis sends to itself, or, where ``slab_wrap_axes`` names it,
    nothing at all)."""
    cells = {"x": 2 * s * s * Yr, "y": 2 * Xr * s * s, "z": Xr * s * Yr}
    return {
        (MESH_AXES[a], side): cells[MESH_AXES[a]] * sum(itemsizes)
        for a in range(3) if mesh_shape[a] > 1
        for side in ("low", "high")
    }


def slab_wrap_axes(mesh_shape: Tuple[int, int, int], Xr: int, Yr: int, s: int, dtypes) -> str:
    """The mesh axes (a substring of ``"xyz"``) on which one macro's z-slab
    extension (``ops/stream.py permute_and_extend_z_slabs``) is a SELF-WRAP:
    on z the outgoing ``(Xr, 2s, Yr)`` buffer is the incoming one, on y and x
    ``halo_blend.wrap_halo`` fills the buffer's own shell in place -- what
    ``domain.step`` says as ``slab_wrap``.  ``_sweep_kind``'s answer for a 3-D
    block of the buffer's extents on the ``direct`` route: the mesh does not
    split the axis, the dtype's tile geometry is known and the blend kernels
    are on; "" wherever they are not (the CPU default), and every slab hop is
    then a ``ppermute``, to oneself on an unsplit axis."""
    # (extent, halo width) of the buffer along x, y and z; along z it IS the
    # halo, the two directions' s planes side by side, and holds none to fill
    shells = ((Xr, s), (Yr, s), (0, 0))
    return "".join(
        MESH_AXES[a]
        for a, (size, r) in enumerate(shells)
        if _sweep_kind(a, r, r, mesh_shape[a], size, None, "direct", dtypes, True) == "wrap"
    )


def sum_hops(*plans: Mapping[Tuple[str, str], int]) -> Dict[Tuple[str, str], int]:
    """Several exchanges' hops (``exchange_account``) added hop by hop."""
    out: Dict[Tuple[str, str], int] = {}
    for hops in plans:
        for hop, nbytes in hops.items():
            out[hop] = out.get(hop, 0) + nbytes
    return out


def uneven_axes(
    mesh_shape: Tuple[int, int, int],
    radius: Radius,
    raw_spatial: Tuple[int, int, int],
    valid_last: Optional[Tuple[Optional[int], Optional[int], Optional[int]]],
) -> str:
    """The mesh axes (a substring of ``"xyz"``) whose sweep runs at per-shard
    TRACED offsets -- the mesh splits the axis and does not divide the global
    extent, so the last shard owns fewer cells than it is padded to (what
    ``domain.exchange`` reports as ``uneven_axes``; "" on every aligned
    extent).  An unsplit axis is never padded: one shard is the last shard."""
    if valid_last is None:
        return ""
    return "".join(
        MESH_AXES[a]
        for a in range(3)
        if mesh_shape[a] > 1
        and radius.axis(a, -1) + radius.axis(a, +1) > 0
        and valid_last[a] is not None
        and valid_last[a] != raw_spatial[a] - radius.axis(a, -1) - radius.axis(a, +1)
    )


def _cut(b: jax.Array, axis: int, start, width: int) -> jax.Array:
    """``width`` cells of ``b`` from ``start`` along spatial ``axis`` (of its
    last three dims): a static slice where ``start`` is a Python int, a
    ``lax.dynamic_slice`` where it is a traced per-shard offset (an uneven
    axis)."""
    d = b.ndim - 3 + axis
    if isinstance(start, int):
        idx = [slice(None)] * b.ndim
        idx[d] = slice(start, start + width)
        return b[tuple(idx)]
    starts = [jnp.int32(0)] * b.ndim
    starts[d] = start
    sizes = list(b.shape)
    sizes[d] = width
    return lax.dynamic_slice(b, tuple(starts), tuple(sizes))


def _put(b: jax.Array, part: jax.Array, axis: int, start) -> jax.Array:
    """``b`` with ``part`` written from ``start`` (as ``_cut`` takes it) along
    spatial ``axis``.  The no-kernel halo write (CPU, N-D quantities, exotic
    dtypes), and the corner patch of a received SLAB -- never the kernels'
    way into a big array."""
    d = b.ndim - 3 + axis
    if isinstance(start, int):
        idx = [slice(None)] * b.ndim
        idx[d] = slice(start, start + part.shape[d])
        return b.at[tuple(idx)].set(part)
    starts = [jnp.int32(0)] * b.ndim
    starts[d] = start
    # stencil-lint: disable=sliver-dus the no-kernel path at a traced offset, or a received slab's corner columns
    return lax.dynamic_update_slice(b, part, tuple(starts))


class _Flight(NamedTuple):
    """A wired sweep whose faces are sent: the axis ``name``, ``n_valid`` --
    this shard's valid interior cells, a Python int on an even axis and a
    traced one on an uneven axis --, and the received messages, every
    quantity's slab stacked (``_Stacked``): ``lo`` for the halo at
    ``[0, r_lo)`` and ``hi`` for the one right behind the valid cells (None
    where that side has no halo)."""

    sweep: _Sweep
    name: str
    n_valid: object
    lo: Optional[_Stacked]
    hi: Optional[_Stacked]


def _send_faces(blocks: List[jax.Array], s: _Sweep, name: str, flat: bool) -> _Flight:
    """Cut the two face slabs of every block along ``s.axis`` -- full raw
    extent on the other axes -- and send each direction's as ONE fused
    message, its first two spatial dims merged where ``flat``."""
    n_pad = s.size - s.r_lo - s.r_hi  # per-shard (padded) interior width
    n_valid = n_pad
    if s.v_last is not None and s.v_last != n_pad:
        n_valid = jnp.where(
            lax.axis_index(name) == s.n_dev - 1, s.v_last, n_pad
        ).astype(jnp.int32)

    def through_permute(slabs, shift_fn) -> _Stacked:
        # axis-0 slabs (r, Y, Z) travel as (1, r*Y, Z): the slice is
        # contiguous, and the 2D-spatial buffer keeps XLA's layout
        # assignment from giving the permute operand a transposed layout
        # whose feeder is a full-domain relayout copy (seen as a ~3 ms
        # {2,1,0}->{2,0,1} copy per macro step in the wavefront loop).  So
        # do the slabs of a sweep that flies behind another, (X, r, Z) as
        # (1, X*r, Z): cut from the blocks as a loop carries them, not from
        # a blend's result, they draw the same copy
        st = _stack(slabs if not flat else [
            b.reshape(b.shape[:-3] + (1, b.shape[-3] * b.shape[-2], b.shape[-1]))
            for b in slabs
        ])
        bufs = _shift_bufs(st.bufs, shift_fn, name, s.n_dev)
        if flat:
            spatial = slabs[0].shape[-3:]
            bufs = [buf.reshape(buf.shape[:-3] + spatial) for buf in bufs]
        return _Stacked(bufs, st.idxs, [b.shape for b in slabs])

    lo = hi = None
    if s.r_lo > 0:
        # my low halo [0, r_lo) <- -axis neighbor's top slab of VALID
        # interior, width r_lo (message traveling +axis has extent
        # radius(-axis)).  Uneven: top r_lo rows of my valid interior,
        # [n_valid, n_valid + r_lo) in allocation coords.
        lo = through_permute(
            [_cut(b, s.axis, n_valid, s.r_lo) for b in blocks], _shift_from_low
        )
    if s.r_hi > 0:
        # my high halo <- +axis neighbor's interior bottom slab, width
        # r_hi, written right after MY valid cells
        hi = through_permute(
            [_cut(b, s.axis, s.r_lo, s.r_hi) for b in blocks], _shift_from_high
        )
    return _Flight(s, name, n_valid, lo, hi)


def _relay_corners(first: _Flight, second: _Flight) -> _Flight:
    """``second`` with the corner columns of its received slabs made what a
    sweep AFTER ``first`` would have sent.  Its faces were cut from the blocks
    as they entered the exchange, so on ``first``'s halo columns they carry
    stale cells; what belongs there is ``first``'s RECEIVED slabs on the face's
    rows -- they need the slabs to have arrived, not the blend.  Those strips,
    ``(r_lo + r_hi of first) x (the side's width of second) x the third axis``
    a quantity, follow the face to the same neighbour as one small fused
    message a direction and overwrite the columns of the message it received
    -- cut, sent and written on the stacked buffers, every quantity at once."""
    a1, a2 = first.sweep.axis, second.sweep.axis
    # first's received messages, and where each lies along a1 in a raw block --
    # and so in a slab of second
    lands = [
        (recv, start)
        for recv, start in (
            (first.lo, 0), (first.hi, first.sweep.r_lo + first.n_valid)
        )
        if recv is not None
    ]

    def relay(face: _Stacked, row0, width: int, shift_fn) -> _Stacked:
        strips = [
            jnp.concatenate(
                [_cut(recv.bufs[g], a2, row0, width) for recv, _ in lands],
                axis=buf.ndim - 3 + a1,
            )
            for g, buf in enumerate(face.bufs)
        ]
        strips = _shift_bufs(strips, shift_fn, second.name, second.sweep.n_dev)
        bufs = []
        for buf, strip in zip(face.bufs, strips):
            at = 0
            for recv, start in lands:
                w = recv.bufs[0].shape[-3 + a1]
                buf = _put(buf, _cut(strip, a1, at, w), a1, start)
                at += w
            bufs.append(buf)
        return face._replace(bufs=bufs)

    s2 = second.sweep
    lo, hi = second.lo, second.hi
    if lo is not None:  # the rows ``_send_faces`` cut for each side
        lo = relay(lo, second.n_valid, s2.r_lo, _shift_from_low)
    if hi is not None:
        hi = relay(hi, s2.r_lo, s2.r_hi, _shift_from_high)
    return second._replace(lo=lo, hi=hi)


def _blend_faces(blocks: List[jax.Array], f: _Flight) -> List[jax.Array]:
    """The received slabs of one flight written into the halos of the
    blocks."""
    from stencil_tpu.ops import halo_blend

    axis = f.sweep.axis
    interp = halo_blend.interpret_mode()
    # y/z halo writes go through tile-local pallas blend kernels where
    # possible: plain DUS slivers on those axes bait XLA's layout
    # assignment into transposing the whole array (two full-domain
    # relayout copies per exchange — see ops/halo_blend.py).
    blend = halo_blend.enabled() and all(
        b.ndim == 3 and halo_blend.supports(b.dtype) for b in blocks
    )
    lo = _unstack(f.lo) if f.lo is not None else None
    hi = _unstack(f.hi) if f.hi is not None else None
    hi_at = f.sweep.r_lo + f.n_valid  # right behind MY valid cells
    out = []
    for j, b in enumerate(blocks):
        if lo is not None:
            # the low halo sits at 0 even on padded axes, so the static
            # kernel serves both cases
            if blend:
                b = halo_blend.blend_slab(b, lo[j], axis, 0, interpret=interp)
            else:
                b = _put(b, lo[j], axis, 0)
        if hi is not None:
            if not blend:
                b = _put(b, hi[j], axis, hi_at)
            elif isinstance(hi_at, int):
                b = halo_blend.blend_slab(b, hi[j], axis, hi_at, interpret=interp)
            else:
                # every axis, x included: a traced x-plane DUS is no relayout
                # bait but compiles to a whole-array fusion with a fresh
                # result (halo_blend.blend_slab_dynamic)
                b = halo_blend.blend_slab_dynamic(
                    b, hi[j], axis, hi_at, interpret=interp
                )
        out.append(b)
    return out


def _sweep_group(
    blocks: List[jax.Array],
    group: Sequence[_Sweep],
    axis_names: Sequence[str],
    route: str,
) -> List[jax.Array]:
    """One group of ``_sweep_groups`` run on ``blocks``: a packed sweep or a
    self-wrap, or the ``direct`` sweeps that fly together -- every axis's
    faces cut from the blocks AS THEY ENTER and sent, so no face message of
    one axis waits for another's; for a pair the corner strips relayed behind
    the second axis's faces (``_relay_corners``); then the blends in sweep
    order.  A group of one is the single-axis sweep: cut, send, blend.  Every
    instruction sits under the ``exchange.<axis>`` scope of the axis whose
    halo it completes -- slab cut / pack, the wires (the per-direction scopes
    nest inside), relay, unpack / blend carry it in their HLO op_name, so a
    trace tells the exchange from step glue."""
    from stencil_tpu.ops import halo_blend

    names = [axis_names[s.axis] for s in group]
    scopes = [partial(jax.named_scope, tm.exchange_axis_span(n)) for n in names]
    s = group[0]
    if s.kind != "direct":
        (name,), n_pad = names, s.size - s.r_lo - s.r_hi
        with scopes[0]():
            if s.kind == "ypack":
                return _ypack_sweep(blocks, s.r_lo, s.r_hi, n_pad, name, s.n_dev, route)
            if s.kind == "zpack":
                return _zpack_sweep(blocks, s.r_lo, s.r_hi, n_pad, name, s.n_dev, route)
            # the self-wrap: one shard is the last shard, its valid width is static
            n_last = n_pad if s.v_last is None else s.v_last
            interp = halo_blend.interpret_mode()
            with jax.named_scope(tm.exchange_wrap_span(name)):
                return [
                    halo_blend.wrap_halo(b, s.axis, s.r_lo, s.r_hi, n_last, interpret=interp)
                    for b in blocks
                ]
    flights = []
    for s, name, scope in zip(group, names, scopes):
        with scope():
            flights.append(_send_faces(blocks, s, name, flat=s.axis == 0 or bool(flights)))
    if len(flights) == 2:
        with scopes[1]():
            flights[1] = _relay_corners(*flights)
    for f, scope in zip(flights, scopes):
        with scope():
            blocks = _blend_faces(blocks, f)
    return blocks


def halo_exchange_multi(
    blocks: Sequence[jax.Array],
    radius: Radius,
    mesh_shape: Tuple[int, int, int],
    axis_names: Sequence[str] = MESH_AXES,
    valid_last: Optional[Tuple[Optional[int], Optional[int], Optional[int]]] = None,
    axes: Tuple[int, ...] = (0, 1, 2),
    route: str = "direct",
) -> List[jax.Array]:
    """Fill the halo shells of several shell-carrying shards JOINTLY — ONE
    face ppermute a direction of every axis sweep (≤ 6), plus one small corner
    relay a direction of the second axis of a jointly swept pair (≤ 2), no
    matter how many quantities: the reference's fused multi-quantity buffers
    (packer.cuh:52-69).  Must run inside ``shard_map`` over a mesh with
    ``axis_names``.

    Each block's spatial extent is its LAST three dims (leading batch/
    quantity dims ride along inside the fused message); every block must
    share the same spatial shape ``interior + r_lo + r_hi`` per axis, with
    the interior at ``[r_lo, r_lo + n)``.

    The sweeps run in the order of ``axes``, and the first two WIRED ones that
    follow each other fly jointly (``_sweep_groups``): a chip's x and y
    neighbours sit on different ICI ports, and all a y slab needs of the x
    sweep is its corner columns.  The halos are bitwise those of sweeps run
    strictly in turn; nothing selects the form but how many axes of this
    exchange are wired.

    ``valid_last`` supports uneven global sizes via pad-and-mask (the
    reference's +-1-cell remainders, partition.hpp:83-114): entry ``a`` is the
    number of VALID interior cells in the LAST shard of axis ``a`` (``None``
    = axis divides evenly).  On a padded axis every shard sends the top slab
    of its own valid cells and writes the received +axis halo right after its
    valid cells — slab positions become per-shard ``lax.dynamic_slice``
    offsets derived from ``axis_index``; the collective itself is unchanged.

    ``route`` picks the y/z-sweep implementations (``EXCHANGE_ROUTES``):
    ``direct`` is today's sliced-slab path; the ``zpack_*`` routes send the
    z shell through the lane-major pack pipeline (``_zpack_sweep``), the
    ``yzpack_*`` routes additionally send the y shell through the
    sublane-major pipeline (``_ypack_sweep``) — bitwise-identical halos,
    differently shaped messages.  A packed sweep that cannot engage
    (uneven axis, unsupported dtype) silently runs ``direct``, so a pinned
    route is always correct.
    """
    if route not in EXCHANGE_ROUTES:
        raise ValueError(f"unknown exchange route {route!r} (one of {EXCHANGE_ROUTES})")
    blocks = list(blocks)
    if not blocks:
        return blocks
    spatial = blocks[0].shape[-3:]
    if not all(b.shape[-3:] == spatial for b in blocks):
        raise ValueError(
            "all quantities must share one spatial (last-3-dims) shape; got "
            f"{[b.shape for b in blocks]}"
        )
    sweeps = _sweeps(
        mesh_shape, radius, spatial, [b.dtype for b in blocks], valid_last, route,
        axes, all(b.ndim == 3 for b in blocks),
    )
    for group in _sweep_groups(sweeps):
        blocks = _sweep_group(blocks, group, axis_names, route)
    return blocks


def halo_exchange_shard(
    block: jax.Array,
    radius: Radius,
    mesh_shape: Tuple[int, int, int],
    axis_names: Sequence[str] = MESH_AXES,
    valid_last: Optional[Tuple[Optional[int], Optional[int], Optional[int]]] = None,
    axes: Tuple[int, ...] = (0, 1, 2),
    route: str = "direct",
) -> jax.Array:
    """Single-quantity convenience wrapper over ``halo_exchange_multi``."""
    return halo_exchange_multi(
        [block], radius, mesh_shape, axis_names, valid_last, axes=axes, route=route
    )[0]


def fused_shell_exchange(
    blocks: Sequence[jax.Array],
    radius: Radius,
    mesh_shape: Tuple[int, int, int],
    axis_names: Sequence[str] = MESH_AXES,
    route: str = "yzpack_xla",
) -> Tuple[List[jax.Array], List[jax.Array], List[jax.Array]]:
    """The exchange WITHOUT the unpack: run the three fused-message sweeps
    and return the received shell buffers instead of writing them into the
    big arrays — the producer half of the stream engine's fused
    unpack→blend mode (``halo="fused"``, ops/stream.py), where the buffers
    land directly in the level-0 VMEM working planes and the big array
    never sees a halo-region write at all (the generalization of the
    z-slab wavefront's bespoke zero-big-array-halo scheme to EVERY axis of
    the generic routes).

    Per quantity (3D scalar blocks, even shards, all shell widths > 0 —
    the stream engine's structural gate), returns:

    * ``xbufs`` — ``(lo_x + hi_x, Y, Z)``: the whole-plane x slabs,
      ``[low-halo planes | high-halo planes]``;
    * ``ybufs`` — ``(X, lo_y + hi_y, Z)``: the packed y shell
      (``pack_yshell_*`` wire format, transposed to the pass's sublane
      orientation);
    * ``zbufs`` — ``(X, lo_z + hi_z, Y)``: the packed z shell (``pack_
      zshell_*`` wire format, transposed to the z-slab pass orientation,
      dead lane-pad columns dropped).

    Correctness mirrors the in-array 3-sweep order EXACTLY, with the
    corner propagation happening on the small buffers instead of through
    big-array halo writes: the y messages' x-shell planes are overwritten
    from the freshly received x slabs before the y permute (the in-array y
    sweep spans x halos the x sweep just filled), and the z messages' x
    columns and y rows are overwritten from the received x slabs and
    (already-patched) y buffers before the z permute.  Every returned
    buffer cell therefore equals the corresponding post-exchange big-array
    cell bitwise — the consumer's VMEM patch (x-replace, then y rows, then
    z columns) replays the sweep order, so fused and unfused programs
    compute identical level-0 planes.

    Structure: one ``_fused_shift`` per direction — the same ≤6-permute,
    one-message-per-direction shape (and the same ``exchange.<axis>.<side>``
    scopes) the ``exchange-structure`` contract pins on every route.
    """
    from stencil_tpu.ops.pack import (
        pack_yshell_pallas,
        pack_yshell_xla,
        pack_zshell_pallas,
        pack_zshell_xla,
    )
    from stencil_tpu.ops import halo_blend

    if route not in Y_PACK_ROUTES:
        raise ValueError(
            f"fused_shell_exchange needs a y+z packed route ({Y_PACK_ROUTES}); "
            f"got {route!r}"
        )
    blocks = list(blocks)
    interp = halo_blend.interpret_mode()
    pallas = route.endswith("pallas")
    X, Y, Z = blocks[0].shape
    lo = [radius.axis(a, -1) for a in range(3)]
    hi = [radius.axis(a, +1) for a in range(3)]
    n = [blocks[0].shape[a] - lo[a] - hi[a] for a in range(3)]
    assert all(b.ndim == 3 and b.shape == (X, Y, Z) for b in blocks)
    assert all(lo[a] > 0 and hi[a] > 0 for a in range(3)), (lo, hi)

    # --- x sweep: whole-plane slabs (the exchange's 2D-spatial layout pin) --
    def permute_x(slabs, shift_fn):
        shapes = [s.shape for s in slabs]
        flat = [s.reshape((1, s.shape[0] * s.shape[1], s.shape[2])) for s in slabs]
        out = _fused_shift(flat, shift_fn, axis_names[0], mesh_shape[0])
        return [o.reshape(sh) for o, sh in zip(out, shapes)]

    # each sweep (cut / pack, corner patch, wire) and the final orientation of
    # its buffers sit under that axis's ``exchange.<axis>`` scope, like the
    # in-array sweeps of ``halo_exchange_multi``
    scope_x, scope_y, scope_z = (
        partial(jax.named_scope, tm.exchange_axis_span(a)) for a in axis_names
    )
    with scope_x():
        xlo = permute_x([b[n[0] : n[0] + lo[0]] for b in blocks], _shift_from_low)
        xhi = permute_x([b[lo[0] : lo[0] + hi[0]] for b in blocks], _shift_from_high)

    # --- y sweep: packed (2m, X, Z) buffers, x-corner-patched pre-permute ---
    def pack_y(y0, depth):
        bufs = [
            pack_yshell_pallas(b, y0, depth, interpret=interp)
            if pallas
            else pack_yshell_xla(b, y0, depth)
            for b in blocks
        ]
        # the in-array y sweep spans x halos the x sweep just filled; here
        # the block's x-shell planes are stale, so the message's x planes
        # are overwritten from the received x slabs (small-buffer writes —
        # the big array is untouched)
        out = []
        for q, buf in enumerate(bufs):
            buf = buf.at[:, 0 : lo[0], :].set(
                jnp.transpose(xlo[q][:, y0 : y0 + depth, :], (1, 0, 2))
            )
            buf = buf.at[:, X - hi[0] : X, :].set(
                jnp.transpose(xhi[q][:, y0 : y0 + depth, :], (1, 0, 2))
            )
            out.append(buf)
        return out

    with scope_y():
        ylo = _fused_shift(pack_y(n[1], lo[1]), _shift_from_low, axis_names[1], mesh_shape[1])
        yhi = _fused_shift(pack_y(lo[1], hi[1]), _shift_from_high, axis_names[1], mesh_shape[1])

    # --- z sweep: packed (2m, Y, Xpad) buffers, x+y-corner-patched ----------
    def pack_z(z0, depth):
        bufs = [
            pack_zshell_pallas(b, z0, depth, interpret=interp)
            if pallas
            else pack_zshell_xla(b, z0, depth)
            for b in blocks
        ]
        out = []
        for q, buf in enumerate(bufs):
            # x-shell lane columns from the received x slabs...
            buf = buf.at[:, :, 0 : lo[0]].set(
                jnp.transpose(xlo[q][:, :, z0 : z0 + depth], (2, 1, 0))
            )
            buf = buf.at[:, :, X - hi[0] : X].set(
                jnp.transpose(xhi[q][:, :, z0 : z0 + depth], (2, 1, 0))
            )
            # ...then y-shell sublane rows from the received (already
            # x-patched) y buffers — the in-array sweep order x→y→z, so the
            # x∩y∩z corners carry the two-hop diagonal content.  Pad
            # columns past X stay dead (the consumer never reads them).
            buf = buf.at[:, 0 : lo[1], 0:X].set(
                jnp.transpose(ylo[q][:, :, z0 : z0 + depth], (2, 0, 1))
            )
            buf = buf.at[:, Y - hi[1] : Y, 0:X].set(
                jnp.transpose(yhi[q][:, :, z0 : z0 + depth], (2, 0, 1))
            )
            out.append(buf)
        return out

    with scope_z():
        zlo = _fused_shift(pack_z(n[2], lo[2]), _shift_from_low, axis_names[2], mesh_shape[2])
        zhi = _fused_shift(pack_z(lo[2], hi[2]), _shift_from_high, axis_names[2], mesh_shape[2])

    with scope_x():
        xbufs = [jnp.concatenate([xlo[q], xhi[q]], axis=0) for q in range(len(blocks))]
    with scope_y():
        ybufs = [
            jnp.transpose(jnp.concatenate([ylo[q], yhi[q]], axis=0), (1, 0, 2))
            for q in range(len(blocks))
        ]
    with scope_z():
        zbufs = [
            jnp.transpose(jnp.concatenate([zlo[q], zhi[q]], axis=0), (2, 0, 1))[:X]
            for q in range(len(blocks))
        ]
    return xbufs, ybufs, zbufs


def make_exchange_fn_allgather(mesh: Mesh, radius: Radius, spec, dim):
    """Debug exchange: reconstruct every shard's raw block (interior + filled
    shell) as wrapped windows of the LOGICAL global field, letting XLA insert
    whatever collectives the resharding needs (effectively all-gathers).
    Obviously slow — exists to validate the ppermute path, the role the
    reference's ``MethodFlags`` method selection plays for benchmarking
    alternatives (stencil.hpp:29-41; SURVEY.md §7 "MethodFlags").  Even
    (unpadded) sizes only.
    """
    raw = spec.raw_size()
    n = spec.sz
    lo = radius.lo()
    sharding = NamedSharding(mesh, P(*MESH_AXES))

    def axis_indices(ax: int):
        size = dim[ax] * n[ax]  # logical extent
        parts = [
            (i * n[ax] - lo[ax] + jnp.arange(raw[ax])) % size for i in range(dim[ax])
        ]
        return jnp.concatenate(parts)

    idx = [axis_indices(ax) for ax in range(3)]

    @jax.jit
    def exchange(arrays):
        def one(arr):
            # extract the logical field from the shell-carrying layout
            g = arr.reshape(dim[0], raw[0], dim[1], raw[1], dim[2], raw[2])
            g = g[:, lo[0] : lo[0] + n[0], :, lo[1] : lo[1] + n[1], :, lo[2] : lo[2] + n[2]]
            logical = g.reshape(dim[0] * n[0], dim[1] * n[1], dim[2] * n[2])
            # every raw cell is a wrapped-window read of the logical field
            out = logical[idx[0]][:, idx[1]][:, :, idx[2]]
            return jax.lax.with_sharding_constraint(out, sharding)

        return jax.tree.map(one, arrays)

    return exchange


def make_exchange_fn_rollcompare(mesh: Mesh, radius: Radius, spec, dim):
    """Oracle exchange: wrap-pad the LOGICAL field (``jnp.pad(mode='wrap')``,
    the jnp.roll formulation) and rebuild every shard's raw block by static
    slicing — a formulation structurally independent of both the ppermute
    sweeps and the AllGather window-gather, completing the ``MethodFlags``
    debug set (utils/config.py RollCompare).  Even (unpadded) sizes only."""
    raw = spec.raw_size()
    n = spec.sz
    lo = radius.lo()
    hi = radius.hi()
    sharding = NamedSharding(mesh, P(*MESH_AXES))

    @jax.jit
    def exchange(arrays):
        def one(arr):
            g = arr.reshape(dim[0], raw[0], dim[1], raw[1], dim[2], raw[2])
            g = g[:, lo[0] : lo[0] + n[0], :, lo[1] : lo[1] + n[1], :, lo[2] : lo[2] + n[2]]
            logical = g.reshape(dim[0] * n[0], dim[1] * n[1], dim[2] * n[2])
            padded = jnp.pad(
                logical,
                ((lo[0], hi[0]), (lo[1], hi[1]), (lo[2], hi[2])),
                mode="wrap",
            )
            rows = []
            for ix in range(dim[0]):
                planes = []
                for iy in range(dim[1]):
                    cols = [
                        padded[
                            ix * n[0] : ix * n[0] + raw[0],
                            iy * n[1] : iy * n[1] + raw[1],
                            iz * n[2] : iz * n[2] + raw[2],
                        ]
                        for iz in range(dim[2])
                    ]
                    planes.append(jnp.concatenate(cols, axis=2))
                rows.append(jnp.concatenate(planes, axis=1))
            out = jnp.concatenate(rows, axis=0)
            return jax.lax.with_sharding_constraint(out, sharding)

        return jax.tree.map(one, arrays)

    return exchange


def make_exchange_fn(
    mesh: Mesh,
    radius: Radius,
    ndim_extra: int = 0,
    valid_last: Optional[Tuple[Optional[int], Optional[int], Optional[int]]] = None,
    route: str = "direct",
    axes: Tuple[int, ...] = (0, 1, 2),
    donate: bool = True,
    out_shardings=None,
):
    """Build a jitted exchange over a pytree of shell-carrying global arrays.

    Returns ``exchange(arrays) -> arrays`` where each array is sharded
    ``P('x','y','z')`` on its last three dims; leading component/batch dims
    (N-D data, per leaf — ``leaf.ndim - 3``; ``ndim_extra`` sets a floor for
    validation bookkeeping) are unsharded and ride inside the fused
    per-direction messages.  Donates its input (``donate=False`` for
    measurement harnesses that must not consume the domain's live buffers —
    the autotuner's route trials, bench-exchange's A/B): the halo write is
    in-place in HBM, like the reference filling halos inside the existing
    allocation.  ``valid_last`` — see ``halo_exchange_shard``; ``route`` —
    see ``EXCHANGE_ROUTES``; ``axes`` restricts the sweeps (bench-exchange's
    per-axis breakdown).  ``out_shardings`` (a pytree like ``arrays``, of
    shardings or ``Format``s) pins where and in which layout the results
    live -- a domain whose arrays are not in the backend's default layout
    hands its own (``domain._row_major_format``); None leaves it to jit.
    """
    if route not in EXCHANGE_ROUTES:
        raise ValueError(f"unknown exchange route {route!r} (one of {EXCHANGE_ROUTES})")
    mesh_shape = tuple(mesh.shape[a] for a in MESH_AXES)

    def leaf_spec(leaf) -> P:
        assert leaf.ndim >= 3, leaf.shape
        return P(*([None] * (leaf.ndim - 3)), *MESH_AXES)

    jit_kw = {"donate_argnums": 0} if donate else {}
    if out_shardings is not None:
        jit_kw["out_shardings"] = out_shardings

    @partial(jax.jit, **jit_kw)
    def exchange(arrays):
        def per_shard(*blocks):
            # ALL quantities (and any leading batch dims) ride one fused
            # message per direction — ≤6 permutes total (packer.cuh:52-69)
            return tuple(
                halo_exchange_multi(
                    blocks,
                    radius,
                    mesh_shape,
                    valid_last=valid_last,
                    axes=axes,
                    route=route,
                )
            )

        leaves, treedef = jax.tree.flatten(arrays)
        # vma validation stays on whenever neither the blend kernels nor the
        # packed pallas route can engage
        max_extra = max(
            [ndim_extra] + [l.ndim - 3 for l in leaves], default=ndim_extra
        )
        shard_fn = shard_map(
            per_shard,
            mesh=mesh,
            in_specs=tuple(leaf_spec(l) for l in leaves),
            out_specs=tuple(leaf_spec(l) for l in leaves),
            check_vma=route_vma_check(
                [l.dtype for l in leaves], valid_last, max_extra, route
            ),
        )
        return jax.tree.unflatten(treedef, list(shard_fn(*leaves)))

    return exchange
