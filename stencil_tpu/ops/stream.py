"""Plane-streaming engine for USER step kernels — fast by default: what is
built and run.

``make_stream_step`` plans a step (``ops/stream_plan.py``: route, depth and
the resolved plan every reader goes by), builds it — one builder a route,
over the passes of ``ops/stream_pass.py`` — and runs it on the resilience
ladder.  The engine is bit-compatible with the XLA route: both call the user
kernel with the same per-cell arithmetic.

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
Padded (uneven) shards ARE supported: the high-side band offsets ride the
same traced ``n_valid`` arithmetic as the exchange's dynamic halo blends.
"""

from __future__ import annotations

import types
from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax

from stencil_tpu.core.dim3 import Dim3
from jax import shard_map
from stencil_tpu import telemetry
from stencil_tpu.telemetry import names as tm
from stencil_tpu.ops.stream_pass import (
    PlaneKernel,
    stream_plane_pass,
    stream_plane_pass_tiled,
    stream_wavefront_pass,
    stream_wrap_pass,
)
from stencil_tpu.ops.stream_plan import (
    _as_stages,
    _halo_request,
    _overlap_request,
    _plan_passes_in_place,
    _stream_groups,
    plain_wavefront_plan,
    plane_lane_forms,
    plan_stream,
    resolve_stream_plan,
    swept_axes,
)
from stencil_tpu.parallel.mesh import MESH_AXES


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
    """(yext, xext) for z-major slab buffers, the WIRED form: each slab half
    is extended with rows from the y neighbors and then planes from the x
    neighbors — two hops that carry the xyz-corner cells from the diagonal
    blocks, mirroring the in-array exchange's sweep order.  Shared by the
    generic engine and the bespoke jacobi wavefront.
    ``permute_and_extend_z_slabs`` calls them on the axes the mesh splits, and
    on every axis where the blend kernels cannot engage; an axis the mesh does
    not split takes ``halo_blend.wrap_halo`` there instead (``ops/exchange.py
    slab_wrap_axes``)."""
    from stencil_tpu.ops.exchange import _shift_from_high, _shift_from_low

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
    one: send the two direction halves along z, then extend with y- and
    x-neighbor content (corner propagation).  This IS the z sweep of the
    z-slab routes: all of it sits under the ``exchange.z`` scope (the y/x
    extension hops nest their own direction or self-wrap scopes inside).

    Each axis takes the form the in-array exchange gives it
    (``ops/exchange.py slab_wrap_axes``, ``_sweep_kind``'s rule; ``domain.step``
    says ``slab_wrap``).  Where the mesh SPLITS it, or the blend kernels cannot
    engage: the ``ppermute``s, on the two ``(Xr, s, Yr)`` halves (``yext`` /
    ``xext`` land what they receive with ``.at[].set``, which XLA runs as a
    copy of the whole half: 67 us a call at ``(518, 3, 518)``, PERF.md §6 PR
    56).  Where it does not: nothing is sent to oneself.  On z the outgoing
    buffer ``[(-z)-bound | (+z)-bound]`` IS the incoming one, uncut; on y and
    x the halo of the slab is the slab's own interior, whatever the middle
    (z-side) axis holds, so ``halo_blend.wrap_halo`` fills it in the WHOLE
    buffer, both halves at once, in place -- ``zout`` is consumed as its only
    operand.  The order stays y then x (the x halo planes carry the y-extended
    rows); halves are cut, and put together again, only between an axis of one
    form and an axis of the other."""
    from stencil_tpu.ops import halo_blend
    from stencil_tpu.ops.exchange import _shift_from_high, _shift_from_low, slab_wrap_axes

    Xr, _, Yr = zout.shape
    wraps = slab_wrap_axes(mesh_shape, Xr, Yr, s, [zout.dtype])

    def halves(v):
        return v if isinstance(v, tuple) else (v[:, 0:s, :], v[:, s : 2 * s, :])

    def wrapped(v, name, axis, raw):
        whole = jnp.concatenate(v, axis=1) if isinstance(v, tuple) else v
        with jax.named_scope(tm.exchange_wrap_span(name)):
            return halo_blend.wrap_halo(
                whole, axis, s, s, raw - 2 * s, interpret=halo_blend.interpret_mode()
            )

    with telemetry.annotate(tm.SPAN_EXCHANGE_Z):
        v = zout
        if "z" not in wraps:
            v = (
                _shift_from_low(zout[:, 0:s, :], MESH_AXES[2], mesh_shape[2]),
                _shift_from_high(zout[:, s : 2 * s, :], MESH_AXES[2], mesh_shape[2]),
            )
        if "y" not in wraps and "x" not in wraps:
            # half by half, as the program without self-wraps always was
            v = tuple(xext(yext(h)) for h in halves(v))
        else:
            for name, axis, raw, ext in (("y", 2, Yr, yext), ("x", 0, Xr, xext)):
                if name in wraps:
                    v = wrapped(v, name, axis, raw)
                else:
                    v = tuple(ext(h) for h in halves(v))
        return jnp.concatenate(v, axis=1) if isinstance(v, tuple) else v


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
    an odd ``macros`` the last result flows into what follows the loop --
    on the wrap route the dispatch's edge CALL, the ``stream_wrap_pass`` that
    writes the raw blocks, which takes a fresh operand as it comes; in
    ``models/jacobi.py`` and under ``edges: "xla"`` the ``dynamic_update_slice``
    / ``pad`` of the dispatch, which has no carry to honour: XLA may copy
    there, once a DISPATCH -- dispatch an even count of macros."""

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


def _shard(dd, interpret: bool):
    """What every builder reads of the domain, once: the shard's geometry."""
    from stencil_tpu.ops import halo_blend

    shell = dd._shell_radius
    return types.SimpleNamespace(
        names=[h.name for h in dd._handles],
        n=dd.local_spec().sz,
        raw=dd.local_spec().raw_size(),
        shell=shell,
        lo=shell.lo(),
        hi=shell.hi(),
        mesh_shape=tuple(dd.mesh.shape[a] for a in MESH_AXES),
        gsize=dd._size,
        valid_last=dd._valid_last,
        # bf16 STORAGE: the passes upcast at load and accumulate at the native f32
        f32_acc=any(dd.field_dtype(h) != h.dtype for h in dd._handles),
        interpret=interpret,
        blend_interpret=interpret or halo_blend.interpret_mode(),
    )


def _origin_of(g):
    # NOTE: must be called INSIDE the fori_loop body that consumes it.
    # axis_index lowers to partition-id; a while-loop OPERAND whose def
    # chain includes partition-id trips XLA's SPMD partitioner
    # ("PartitionId instruction is not supported for SPMD partitioning")
    # on some toolchains, while the same op inside the body partitions
    # fine (and LICM hoists it after partitioning anyway).
    return jnp.stack([lax.axis_index(MESH_AXES[ax]) * g.n[ax] for ax in range(3)])


def _group_bufs(fused_bufs, group):
    """The fused shell buffers ``(x, y, z)`` of the quantities ``group``
    (indices), as a pass takes them; None without."""
    if fused_bufs is None:
        return None
    return tuple([bufs[q] for q in group] for bufs in fused_bufs)


# --- the split schedule's exterior bands (module docstring) ------------------


def _n_valid(g, ax):
    """Valid interior width on ``ax`` for THIS shard: a plain int on
    even axes, traced on padded ones (the last shard owns the
    remainder — the same arithmetic as the exchange's dynamic halo
    offsets, so band positions land exactly where the halos did)."""
    if g.valid_last[ax] is None:
        return g.n[ax]
    idx = lax.axis_index(MESH_AXES[ax])
    return jnp.where(
        idx == g.mesh_shape[ax] - 1, g.valid_last[ax], g.n[ax]
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


def _blend_band(block, band, ax, pos, interpret):
    """Write a recomputed width-``w`` band at ``pos`` along ``ax``.
    x bands are whole contiguous planes (DUS at slab cost); y/z bands
    go through the tile-local blend kernels exactly like the
    exchange's halo writes (static offset on even axes, traced on
    padded ones)."""
    from stencil_tpu.ops import halo_blend

    if ax == 0:
        # stencil-lint: disable=sliver-dus x-plane band write-back: whole contiguous planes, the exchange's sanctioned axis-0 pattern (no relayout bait)
        return lax.dynamic_update_slice(block, band, _starts3(0, pos))
    if not halo_blend.supports(block.dtype):
        # exotic-dtype correctness fallback, off the measured path
        # stencil-lint: disable=sliver-dus exotic-dtype (no known tile geometry) fallback — the blend kernels cannot engage, and such dtypes are off the measured fast path
        return lax.dynamic_update_slice(block, band, _starts3(ax, pos))
    if isinstance(pos, int):
        return halo_blend.blend_slab(block, band, ax, pos, interpret=interpret)
    return halo_blend.blend_slab_dynamic(block, band, ax, pos, interpret=interpret)


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
    gran = _BAND_GRANULE[ax]
    width = min(-(-3 * w // gran) * gran, raw_ax)
    if isinstance(start, int):
        return max(min(start, raw_ax - width), 0), width
    return jnp.clip(start, 0, raw_ax - width), width


def _exterior_fix(g, outs, ex, w, origin, narrow_pass):
    """Recompute the six width-``w`` boundary bands of ``outs`` from
    the freshly exchanged blocks ``ex`` and blend them in.  Each
    band's support window is ``>= 3w`` wide (band + ``w`` of fresh
    shell + interior, granule-padded), so the narrow pass reproduces
    the full pass's values bitwise on the band; band overlaps at
    edges and corners write identical values twice."""
    lo_t = (g.lo.x, g.lo.y, g.lo.z)
    outs = list(outs)
    for ax in range(3):
        nv = _n_valid(g, ax)
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
                outs[q] = _blend_band(outs[q], band, ax, pos, g.blend_interpret)
    return outs


# --- one builder a route: ``per_shard(steps, *blocks) -> blocks`` -------------


def _build_wrap_step(g, stages, x_radius, plan):
    kernel, k, per_trip = stages[0], plan["m"], plan["macros_per_trip"]
    names, lo, n = g.names, g.lo, g.n
    groups = _stream_groups(plan, len(names))

    def one(depth, bs, raw_in=False, raw_out=None):
        origin = _origin_of(g)
        out = list(bs)
        for grp in groups:
            outs = stream_wrap_pass(
                kernel, [names[q] for q in grp], [bs[q] for q in grp],
                depth, origin, g.gsize, interpret=g.interpret,
                f32_accumulate=g.f32_acc, interior=(lo, n), raw_in=raw_in,
                raw_out=raw_out and [raw_out[q] for q in grp],
            )
            for q, o in zip(grp, outs):
                out[q] = o
        return tuple(out)

    def per_shard(steps, *blocks_raw):
        blocked, rem = divmod(steps, k)
        calls = blocked + bool(rem)
        if plan["edges"] == "raw" and calls >= 2:
            # the dispatch carries the raw blocks at its two edges: its first
            # call reads them, its last one writes them, in place (the blocks
            # are the step's donated operand), and the calls between run bare
            # in the loop -- ``steps`` an even count of macros keeps the loop
            # whole trips.  A dispatch of ONE call would read the blocks it
            # writes: it takes the cut and the write-back below
            bs = one(k, blocks_raw, raw_in=True)
            bs = macro_loop(partial(one, k), calls - 2, bs, per_trip)
            return one(rem or k, bs, raw_out=blocks_raw)
        bs = tuple(
            lax.slice(b, (lo.x, lo.y, lo.z), (lo.x + n.x, lo.y + n.y, lo.z + n.z))
            for b in blocks_raw
        )
        # ``steps`` an even count of macros keeps the dispatch's edge free
        # of copies too (macro_loop)
        bs = macro_loop(partial(one, k), blocked, bs, per_trip)
        if rem:
            bs = one(rem, bs)
        return tuple(
            # stencil-lint: disable=sliver-dus whole-interior write-back after the wrap loop — b spans the full interior, not a y/z sliver
            lax.dynamic_update_slice(rb, b, (lo.x, lo.y, lo.z))
            for rb, b in zip(blocks_raw, bs)
        )

    return per_shard


def _build_plane_step(g, stages, x_radius, plan):
    import contextlib

    from stencil_tpu.ops.exchange import fused_shell_exchange, halo_exchange_multi

    names, lo, hi = g.names, g.lo, g.hi
    lo_t, hi_t = (lo.x, lo.y, lo.z), (hi.x, hi.y, hi.z)
    in_place = _plan_passes_in_place(plan)
    index = {name: q for q, name in enumerate(names)}
    stage_readers = [st["readers"] for st in plan["stages"]]
    axes = swept_axes(plan)

    def stage_scope(k):
        """``step.stage.<k>`` around a stage's exchange and passes, for a
        step of more than one (a one-stage step's scopes stay as they
        were)."""
        if len(stages) == 1:
            return contextlib.nullcontext()
        return telemetry.annotate(tm.step_stage_span(k))

    def exchange_readers(bs, k):
        """``bs`` with the shells of stage ``k``'s halo readers filled --
        one joint exchange of those blocks alone; the others ride on
        untouched -- on the axes the passes do not wrap themselves."""
        riders = [index[name] for name in stage_readers[k]]
        out = list(bs)
        filled = halo_exchange_multi(
            [bs[q] for q in riders], g.shell, g.mesh_shape,
            valid_last=g.valid_last, axes=axes, route=plan.exchange_route,
        )
        for q, b in zip(riders, filled):
            out[q] = b
        return out

    def plane_passes(k, bs, origin, fused_bufs=None, lo=lo, hi=hi,
                     alias=in_place,
                     scope=partial(telemetry.annotate, tm.SPAN_STEP_PASS), **lanes):
        """Stage ``k``'s passes in order, each over the quantities it
        touches.  Every pass reads the blocks the STAGE was handed: the
        planner lets no pass read a block an earlier one of the stage has
        written (``plan_plane_passes``), and a renaming pass leaves its new
        ``q`` in ``p``'s block, so a later pass that reads ``q`` still finds the
        stage's entry value under its name.  What the passes wrote, and the
        handles their renames swap, take their places once the last has run.
        ``lanes`` is the tiled pass's ``shell_in`` / ``shell_out``
        (``per_shard``)."""
        out = list(bs)
        runs = plan.stage_runs[k]
        # a stage of several passes that hand blocks on renamed: each under a
        # scope of its own, so that a device trace splits the stage by pass
        numbered = len(runs) > 1 and any(p["renames"] for p in plan["stages"][k]["passes"])
        for i, (pass_kernel, reads, rings, writes, renames, prerotated, tile_rows) in enumerate(runs):
            grp = [index[name] for name in reads]
            with contextlib.ExitStack() as scopes:
                if numbered:
                    scopes.enter_context(telemetry.annotate(tm.stage_pass_span(i)))
                scopes.enter_context(scope())
                shared = dict(
                    alias=alias, interpret=g.interpret, f32_accumulate=g.f32_acc,
                    halo_readers=stage_readers[k], writers=writes, rings=rings,
                    wrap_fills=plan.wrap_fills, strip=plan["plane_strip"], renames=renames,
                )
                args = (
                    pass_kernel, reads, [bs[q] for q in grp], lo, hi, x_radius, origin, g.gsize,
                )
                if tile_rows:  # planes that fit VMEM in y tiles only
                    outs = stream_plane_pass_tiled(*args, tile_rows=tile_rows, **lanes, **shared)
                else:
                    outs = stream_plane_pass(
                        *args, fused_shell=_group_bufs(fused_bufs, grp),
                        window=plan["plane_window"], prerotated=prerotated, **shared,
                    )
            moved = set(writes) | {p for p, _ in renames}
            for q, name, o in zip(grp, reads, outs):
                if name in moved:
                    out[q] = o
        return out

    if plan["plane_lanes"] == "window":
        # the lane forms of a dispatch (``per_shard``) differ in the pass's
        # blocks and nowhere in the step's exchange: one trace and one lowering
        # of it serve them all (set-up time; XLA inlines the call).  Only here:
        # every other plan traces one form, and keeps its program byte for byte
        exchange_readers = jax.jit(exchange_readers, static_argnums=1)

    if plan["halo"] == "fused":

        def stage(k, bs, origin):
            # the packed messages never unpack into the blocks: the
            # received shell buffers ride into the pass and land in
            # the level-0 VMEM planes — no big-array halo write
            bufs = fused_shell_exchange(bs, g.shell, g.mesh_shape, route=plan.exchange_route)
            return plane_passes(k, bs, origin, bufs)

    elif plan["overlap"] == "split":

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
                    g, out, ex, x_radius, origin, partial(narrow_plane, k)
                )

    else:

        def stage(k, bs, origin, **lanes):
            return plane_passes(k, exchange_readers(bs, k), origin, **lanes)

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
    period = plan.period

    def per_shard(steps, *blocks):
        def one(bs, **lanes):
            origin = _origin_of(g)
            bs = list(bs)
            for k in range(len(stages)):
                with stage_scope(k):
                    bs = stage(k, bs, origin, **lanes)
            return tuple(bs)

        if plan["plane_lanes"] == "window" and steps >= 2:
            # the lane tile behind the aligned window moves only at the
            # dispatch's two edges (``stream_plan.plane_lanes_form``), in the
            # forms ``plane_lane_forms`` lists: the first call reads whole raw
            # planes and makes the fills -- the step still assumes nothing of
            # the halo at entry -- and writes the window's lane tiles alone;
            # the calls between, the loop, move the window's lane tiles both
            # ways, fill nothing and rebuild no z shell; the last reads those
            # alone and writes whole planes, the z shell rebuilt, so the
            # dispatch leaves every raw cell as whole calls do.  The x wrap
            # and a mesh's wires go on moving whole planes and rows: after the
            # first call the stale shell lanes ride along and nobody reads
            # them.  Each traced form of the pass is set-up time: a dispatch of
            # two traces no middle form, of ONE takes the whole call below
            # (the third form read ``setup_s`` +26.5% against the one-form
            # program of PR 54, over a 25% bound; against the two-form one it
            # costs +2.8 s of trace and lowering, +10% of the one-chip cell's
            # ``setup_s``, for +8% of its rate: PERF.md §6, PR 58).  Only the default
            # schedule's ``stage`` takes the forms (fused side buffers and
            # split exterior bands would read the stale lanes)
            assert period == 1 and plan["halo"] != "fused" and plan["overlap"] != "split", (
                plan["steps_per_trip"], plan["halo"], plan["overlap"])
            bs = tuple(blocks)
            for (shell_in, shell_out), calls in plane_lane_forms(plan, steps):
                call = partial(one, shell_in=shell_in, shell_out=shell_out)
                if shell_in or shell_out:  # an edge: once
                    bs = call(bs)
                else:
                    bs = lax.fori_loop(0, calls, lambda _, b: call(b), bs)
            return bs

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

    return per_shard


def _build_wavefront_step(g, stages, x_radius, plan):
    from stencil_tpu.ops.exchange import fused_shell_exchange, halo_exchange_multi

    kernel, m, alias = stages[0], plan["m"], plan["alias"]
    names, s = g.names, g.lo.x
    lo_t = (g.lo.x, g.lo.y, g.lo.z)
    Xr, Yr, Zr = g.raw.x, g.raw.y, g.raw.z
    groups = _stream_groups(plan, len(names))

    def wavefront_groups(bs, depth, origin, zs=None, fused_bufs=None):
        """Run the m-level pass group by group; returns (outs, zouts)."""
        outs = list(bs)
        zouts = [None] * len(bs) if zs is not None else None
        for grp in groups:
            o, z = stream_wavefront_pass(
                kernel, [names[q] for q in grp], [bs[q] for q in grp],
                depth, s, origin, g.gsize,
                z_slabs=[zs[q] for q in grp] if zs is not None else None,
                alias=alias,
                interpret=g.interpret,
                fused_shell=_group_bufs(fused_bufs, grp),
                f32_accumulate=g.f32_acc,
            )
            for j, q in enumerate(grp):
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
        for grp in groups:
            o, _ = stream_wavefront_pass(
                kernel, [names[q] for q in grp], [subs[q] for q in grp],
                w, w, origin_sub, g.gsize, alias=False, interpret=g.interpret,
                f32_accumulate=g.f32_acc,
            )
            for q, oo in zip(grp, o):
                out[q] = oo
        return out

    def exchanged(bs):
        return list(
            halo_exchange_multi(
                bs, g.shell, g.mesh_shape, valid_last=g.valid_last,
                route=plan.exchange_route,
            )
        )

    if plan["z_slabs"]:
        yext, xext = make_slab_extenders(Xr, Yr, s, g.mesh_shape)

        def macro(depth, carry):
            origin = _origin_of(g)
            bs, zouts = carry
            bs = list(halo_exchange_multi(bs, g.shell, g.mesh_shape, axes=(0, 1)))
            zs = [
                permute_and_extend_z_slabs(zout, s, g.mesh_shape, yext, xext)
                for zout in zouts
            ]
            outs, zouts = wavefront_groups(bs, depth, origin, zs)
            return tuple(outs), tuple(zouts)

    elif plan["halo"] == "fused":

        def macro(depth, bs):
            origin = _origin_of(g)
            bs = list(bs)
            # messages pack from the (stale-shell) blocks, the
            # received buffers corner-patch each other in the
            # sweep order, and the pass lands them in VMEM —
            # the big array never sees a halo write
            bufs = fused_shell_exchange(bs, g.shell, g.mesh_shape, route=plan.exchange_route)
            outs, _ = wavefront_groups(bs, depth, origin, fused_bufs=bufs)
            return tuple(outs)

    elif plan["overlap"] == "split":

        def macro(depth, bs):
            origin = _origin_of(g)
            bs = list(bs)
            # ppermutes on slabs of the PRE-exchange blocks; the
            # interior pass reads the same blocks — independent
            # dataflow, so the collectives fly behind the m-level
            # pass and only the narrow band passes wait for them
            ex = exchanged(bs)
            with telemetry.annotate(tm.SPAN_OVERLAP_INTERIOR):
                outs, _ = wavefront_groups(bs, depth, origin)
            with telemetry.annotate(tm.SPAN_OVERLAP_EXTERIOR):
                outs = _exterior_fix(g, outs, ex, depth, origin, narrow_wavefront)
            return tuple(outs)

    else:

        def macro(depth, bs):
            origin = _origin_of(g)
            outs, _ = wavefront_groups(exchanged(bs), depth, origin)
            return tuple(outs)

    def per_shard(steps, *blocks):
        carry = tuple(blocks)
        if plan["z_slabs"]:
            # prime slabs from the blocks' interior z boundaries
            carry = (carry, tuple(prime_z_slabs(b, Zr, s) for b in blocks))
        macros, rem = divmod(steps, m)
        carry = lax.fori_loop(0, macros, lambda _, c: macro(m, c), carry)
        if rem:
            carry = macro(rem, carry)
        return carry[0] if plan["z_slabs"] else carry

    return per_shard


_ROUTE_BUILDERS = {
    "wrap": _build_wrap_step,
    "plane": _build_plane_step,
    "wavefront": _build_wavefront_step,
}


def _build_stream_step(dd, kernel, x_radius, plan, interpret, donate=True):
    """The jitted ``step(curr, steps)`` of a RESOLVED plan
    (``resolve_stream_plan``'s result): the route's builder makes the
    per-shard program, this wraps it.  Reads the plan and writes nothing;
    records what was resolved, once a build -- the stream-engine twins of
    the ``exchange.route`` event."""
    from jax.sharding import PartitionSpec as P

    telemetry.emit_event(
        tm.EVENT_STEP_OVERLAP, overlap=plan["overlap"], source=plan.overlap_source,
        route=plan["route"], m=plan["m"],
    )
    telemetry.emit_event(
        tm.EVENT_STEP_HALO, halo=plan["halo"], source=plan.halo_source,
        route=plan["route"], m=plan["m"], exchange_route=plan.exchange_route,
    )
    g = _shard(dd, interpret)
    per_shard = _ROUTE_BUILDERS[plan["route"]](g, _as_stages(kernel), x_radius, plan)
    spec = P(*MESH_AXES)
    donate_kw = {"donate_argnums": 0} if donate else {}

    @partial(jax.jit, static_argnums=1, **donate_kw)
    def step(curr, steps: int = 1):
        # check_vma off: pallas_call outputs carry no vma annotation
        fn = shard_map(
            partial(per_shard, steps),
            mesh=dd.mesh,
            in_specs=tuple(spec for _ in g.names),
            out_specs=tuple(spec for _ in g.names),
            check_vma=False,
        )
        outs = fn(*[curr[k] for k in g.names])
        return dict(zip(g.names, outs))

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

    On the plane route's default schedule a y or z axis the mesh does not
    split is not swept by the exchange at all: the passes fill that halo
    themselves, in VMEM (``pass_wrap_fills`` says where and why; the resolved
    ``plan["pass_wrap_axes"]``, ``domain.step``'s ``wrapped``).  A function
    of the mesh: no option.

    ``max_depth`` caps the temporal depth (wrap k / wavefront m).  The auto
    planner maximizes depth because depth is the HBM-traffic lever
    (~bytes/k per cell) — correct for bandwidth-bound kernels, but a
    COMPUTE-heavy kernel (e.g. 27 taps/cell) multiplies its VPU work by the
    depth with nothing to amortize; cap it low (2-4) for such kernels.

    ``overlap`` selects the split-step schedule (module docstring) and
    ``halo`` the fused unpack→blend mode (``ops/stream_pass.py``): ``"auto"``
    resolves ``STENCIL_STREAM_OVERLAP`` / ``STENCIL_STREAM_HALO`` > the tuned
    config > the static ``off`` / ``array``; an explicit value is an explicit
    request and never consults further.  Both are bitwise-identical to the
    static schedule on every valid cell.  A plan either cannot serve degrades
    to the static value with a warning (``ops/stream_plan.py`` has the gates;
    a z-slab wavefront plan re-plans to the plain form first), and a
    compile-rejected build steps down to it at the same depth through the
    ladder before any depth descent.

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

    stages = _as_stages(kernel)
    if len(stages) > 1:
        # an exchange before every stage: the plane route's schedule
        if path not in ("auto", "plane"):
            raise ValueError(
                f"a step of {len(stages)} stages runs the plane route; "
                f"stream_path={path!r} cannot"
            )
        path = "plane"
    request = dict(plan_stream(dd, x_radius, path, separable, max_m=max_depth))
    if overlap != "auto":
        request.update(overlap=overlap, overlap_forced=True)
    if halo != "auto":
        request.update(halo=halo, halo_forced=True)
    # a split request (explicit/env/tuned) against a z-slab wavefront plan
    # re-plans to the PLAIN form when it fits: split needs z halos in the
    # big array for the exchange it overlaps, and the packed zpack_* routes
    # already de-amplified the thin-z traffic the slab form dodges.  When no
    # plain depth fits, the resolution's structural guard degrades split ->
    # off.  The FUSED halo request re-plans the same way: the fused buffers
    # are the level-0 patch of a plain pass, and the packed routes make the
    # plain form's exchange cheap — when no plain depth fits, the structural
    # guard degrades fused -> array.
    # (an explicit value neither axis knows raises here, naming it)
    split, fused = _overlap_request(request)[0] == "split", _halo_request(request)[0] == "fused"
    if split or fused:
        request = plain_wavefront_plan(dd, request, max_depth=max_depth) or request

    def rung_for(req):
        # the plan, resolved before anything is built: a plane step that fits
        # in no pass raises HERE, and the ladder's prefilter judges the plan
        # that will be built.  build() resolves _build_stream_step through
        # module globals at call time, so tests may monkeypatch it
        plan = resolve_stream_plan(dd, kernel, x_radius, req, interpret)
        suffix = ",split" if plan["overlap"] == "split" else ""
        if plan["halo"] == "fused":
            suffix += ",fused"
        if plan.get("edges") == "raw":
            suffix += ",raw"
        return Rung(
            name=f"{plan['route']}[m={plan['m']}{suffix}]",
            build=lambda: _build_stream_step(
                dd, kernel, x_radius, plan, interpret, donate
            ),
            state={"plan": plan.plan, "request": req},
        )

    def lower(rung, cls, exc):
        plan_now, request = rung.state["plan"], rung.state["request"]
        from stencil_tpu.utils.logging import log_warn

        if plan_now["halo"] == "fused":
            # first rung down: drop the fused halo mode at the SAME depth —
            # the fused pass carries extra side-buffer blocks and per-plane
            # patch selects, so a VMEM_OOM or compile reject may be the
            # fused form's fault, not the depth's
            log_warn(
                f"halo=fused on {plan_now['route']}[m={plan_now['m']}] "
                f"exceeded the compiler's capability ({cls.value}); stepping "
                "down to halo=array at the same depth"
            )
            return rung_for(dict(request, halo="array", halo_forced=True))
        if plan_now["overlap"] == "split":
            # next rung down: drop the split schedule at the SAME depth —
            # the exterior passes carry their own scratch, so a VMEM_OOM or
            # compile reject may be the overlap's fault, not the depth's
            log_warn(
                f"split-step overlap on {plan_now['route']}[m={plan_now['m']}] "
                f"exceeded the compiler's capability ({cls.value}); stepping "
                "down to overlap=off at the same depth"
            )
            return rung_for(dict(request, overlap="off", overlap_forced=True))
        if plan_now.get("edges") == "raw":
            # the wrap route's edge forms hold two larger pipeline planes a
            # quantity than the bare pass the depth was chosen for: drop them
            # at the SAME depth before any depth descent
            log_warn(
                f"raw-block edges on wrap[m={plan_now['m']}] exceeded the "
                f"compiler's capability ({cls.value}); stepping down to the XLA "
                "cut and write-back at the same depth"
            )
            return rung_for(dict(request, edges="xla", edges_forced=True))
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
        # a descent never re-enables split or fused: the (post-step-down)
        # axis state rides into the shallower request as forced
        return rung_for(dict(
            plan_stream(dd, x_radius, path, separable, max_m=new_max),
            overlap=plan_now["overlap"], overlap_forced=True,
            halo=plan_now["halo"], halo_forced=True,
            # (nor the wrap route's raw-block edges: "xla" by now)
            **({"edges": "xla", "edges_forced": True} if "edges" in plan_now else {}),
        ))

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
        rung_for(request), lower=lower, label="stream", prefilter=prefilter
    )

    raw = dd.local_spec().raw_size()
    n_doms = dd.num_subdomains()
    band_area = 2 * (raw.y * raw.z + raw.x * raw.z + raw.x * raw.y) * len(
        dd._handles
    ) * n_doms
    def _exterior_cells(plan_now, steps: int) -> int:
        """Analytic cells recomputed by the exterior band passes for this
        dispatch (all shards, all fields) — 0 under ``overlap=off``."""
        if plan_now["overlap"] != "split":
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
    # what this step's ``domain.step`` span says of the plan it runs NOW (the
    # ladder may have moved it)
    step._span_args = lambda: stream_span_args(step._stream_plan, x_radius, len(dd._handles))
    # ... and of the dispatch of ``steps`` it is about to run of it
    step._dispatch_args = lambda steps: stream_dispatch_args(step._stream_plan, steps)
    # ... and of the wires its exchanges cross (``run_step``'s counters)
    step._wire_account = lambda: step._stream_plan["wire_account"]
    step._resilience = ladder
    step._resilience_label = "stream"
    return step


def stream_span_args(plan, x_radius: int, nq: int) -> dict:
    """What the ``domain.step`` span says of a RESOLVED stream plan over
    ``nq`` quantities (``telemetry/names.py SPAN_STEP``)."""
    in_place = _plan_passes_in_place(plan)
    args = {
        "route": plan["route"],
        "x_radius": x_radius,
        "grouping": plan.get("grouping", "joint"),
        "streamed": nq,
        # quantities the passes carry in place (all or none): a written
        # one's output aliases its input, an unwritten one IS its input
        "aliased": nq if in_place else 0,
        # quantities riding the step's exchange: what the kernel reads
        # off-centre on the plane route (plan_plane_stages), every one
        # on the wavefront route, none on the wrap route
        "exchanged": len(plan["halo_readers"]),
        # quantities the passes write: what the kernel returns on the
        # plane route (plan_plane_stages), every one elsewhere
        "written": len(plan["writers"]),
        # quantities whose write became a rename: an output that is
        # another writer's centre plane swaps handles with it instead of
        # being copied (trace_plane_kernel); they are not ``written``
        "renamed": len(plan["renamed"]),
        # the axes whose halo the plane passes fill themselves in VMEM,
        # so that the exchange does not sweep them (pass_wrap_fills): one
        # value for every stage, a function of the mesh and the domain
        "wrapped": plan["pass_wrap_axes"],
        # how many quantities the step carries, and what its kernels READ
        # of them off-centre (footprint_counts: quantities read at a
        # non-zero offset, those of them read at a diagonal one, and the
        # distinct (quantity, axis, side) triples read; None each where a
        # footprint trace raised) beside what the route SERVES: every
        # exchanged quantity's halo is filled -- by the exchange's sweeps
        # or the pass's own fills -- on all six sides
        "quantities": nq,
        **(plan["footprint"] or dict.fromkeys(("offcentre", "diagonal", "read_sides"))),
        "exchanged_sides": 6 * len(plan["halo_readers"]),
    }
    if "macros_per_trip" in plan:
        # the wrap route: macros a trip of its device-side loop, as many
        # as bring the fresh-result pass's carry home (macro_loop)
        args["macros_per_trip"] = plan["macros_per_trip"]
        # ... and where a dispatch's two edges live: "raw" = its first pass
        # reads the domain's raw blocks and its last one writes them, "xla" =
        # a slice and a dynamic_update_slice a quantity (wrap_edge_form)
        args["edges"] = plan["edges"]
    if "steps_per_trip" in plan:
        # the plane route: steps a trip of its step loop, as many as bring
        # the renamed handles home (_carry_period)
        args["steps_per_trip"] = plan["steps_per_trip"]
        # ... and the plane its passes work on: the block's "interior" where
        # they fill both in-plane halos themselves and it is whole vector
        # tiles (every in-plane shift one rotate, its wraparound the halo),
        # "interior-z" where they fill z alone beside a split y (the
        # neighbours' y halo rows ride in the strip form's margin tiles), the
        # "raw" shell-carrying plane elsewhere (plane_window_form)
        args["plane_window"] = plan["plane_window"]
        # ... and the rows of it their kernel is evaluated over at a time: a
        # y shift is then the address of a tile and a value a few vregs; 0 =
        # over the plane whole (plane_strip_rows, plan_plane_stages)
        args["plane_strip"] = plan["plane_strip"]
        # ... and the rows of the Y TILES its pipeline moves of a plane that
        # fits VMEM whole in no pass, and how many a plane is: 0 and 1 where
        # the passes move whole planes (plan_plane_passes)
        args["tile_rows"] = plan["tile_rows"]
        args["y_tiles"] = plan["y_tiles"]
        # ... and the lane tiles of a plane they move towards another call of
        # the dispatch: "window" = the aligned window's alone, the z shell
        # read by the dispatch's first call and written by every later one
        # (the span's ``steps`` says how many calls: one step is one whole
        # call), "raw" = whole raw planes both ways (plane_lanes_form)
        args["plane_lanes"] = plan["plane_lanes"]
    if "z_halo_patch" in plan:
        # the z-slab wavefront: whether the pass patches its z halo in
        # the lane tiles that hold it or over the whole plane
        args["z_halo_patch"] = plan["z_halo_patch"]
        # ... and where its lane padding lives: "vmem" (the pass widens
        # the raw block's plane itself) or "none" (nothing to pad)
        args["lane_pad"] = plan["lane_pad"]
        # ... and the axes on which its slab extension sends nothing to
        # itself: the self-wrap kernel, in place (slab_wrap_axes)
        args["slab_wrap"] = plan["slab_wrap"]
    # the axes whose sweep of the step's exchanges crosses to another shard,
    # and the bytes one shard receives over them a raw step, all stages (the
    # plan's ``wire_account``, which ``run_step`` counts the wires from; ops/
    # exchange.py exchange_account): "" and 0 on one device; and the pair of
    # them whose sweeps fly jointly ("" where the sweeps run in turn)
    args["wired"] = plan["wired"]
    args["wire_bytes"] = plan["wire_bytes"]
    args["joint"] = plan["joint"]
    if "wired_edges" in plan:
        # ... and the pairs of those axes whose EDGE halo the kernels read
        # (a diagonal offset across both): it reaches a shard over two wires
        # in turn ("xy"; several "/"-joined; "" where no edge is read so)
        args["wired_edges"] = "/".join(plan["wired_edges"])
    per_stage = plan.get("stages", ())
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
        if "wire_bytes_by_stage" in plan:  # the bytes over the wires, stage by stage
            args["wire_bytes_by_stage"] = "/".join(str(b) for b in plan["wire_bytes_by_stage"])
    if any(len(st["passes"]) > 1 for st in per_stage):
        # a stage of SEVERAL passes says each of them, in the order it runs
        # them: the quantities the pass writes, reads and holds a ring of, the
        # rows of its y tiles and how many a plane is (0 and 1: whole planes),
        # the writes that land in another quantity's block -- "w2-r10-g8-t256-
        # y2-n2", the passes of a stage "+"-joined, the stages "/"-joined
        # (``tile_rows`` / ``y_tiles`` above say the step's smallest tile)
        rows = plan["tile_rows"] * plan["y_tiles"]  # the working plane's
        args["passes_by_stage"] = "/".join(
            "+".join(
                f"w{len(p['writes'])}-r{len(p['reads'])}-g{len(p['rings'])}-t{p['tile_rows']}"
                f"-y{rows // p['tile_rows'] if p['tile_rows'] else 1}-n{len(p['renames'])}"
                for p in st["passes"]
            )
            for st in per_stage
        )
    return args


def stream_dispatch_args(plan, steps: int) -> dict:
    """What ``domain.step`` says of ONE dispatch of ``steps`` beside the plan's
    ``stream_span_args``: where the passes take the lane forms (``plane_lanes``
    "window"), narrow_calls = the calls of THIS dispatch that moved the aligned
    window's lane tiles both ways -- counted off the same list ``per_shard``
    runs (``plane_lane_forms``): ``steps - 2``, 0 for a dispatch of one or two.
    Nothing elsewhere."""
    if plan.get("plane_lanes") != "window":
        return {}
    return {"narrow_calls": dict(plane_lane_forms(plan, steps)).get((False, False), 0)}


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
