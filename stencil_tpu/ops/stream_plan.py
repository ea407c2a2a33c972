"""What the stream engine decides before anything is built: a step's plan.

A plan has two halves.  The REQUEST is what a caller, the tuner, the
environment and a descent choose — ``plan_stream``'s ``route`` / ``m`` /
``z_slabs`` / ``grouping`` and, where present, ``alias``, ``overlap`` /
``overlap_forced``, ``halo`` / ``halo_forced``.  The RESOLUTION is everything
the build and every reader of ``step._stream_plan`` go by
(``resolve_stream_plan``): the three axes settled, what each stage's kernel
reads and returns, the passes, the wires, the wraps.  It is computed from the
request alone, in one place, and nothing downstream writes to it.

Routes (``plan_stream`` chooses; ``ops/stream_pass.py`` has the passes):

* **plane** — one level per pass: exchange the shell of every quantity the
  kernel reads off-centre (the others' shells are read by nothing), then
  stream planes, writing back only the quantities the kernel returns with a
  value of their own (one returned as another's centre plane -- a leapfrog's
  ``u_prev <- u`` -- swaps handles with it instead).  On a y or z axis the
  mesh does not split the pass fills that halo itself, in VMEM
  (``pass_wrap_fills``), and the exchange sweeps the other axes; where it
  fills BOTH and the interior is whole vector tiles the pass works on the
  interior plane alone and the rotates' wraparound is the halo
  (``plane_window``, ``stream_pass.plane_window_form``), a heavy kernel
  evaluated a strip at a time (``plane_strip``, ``stream_pass.plane_strip_
  rows``, ``_STRIP_MIN_OPS``); where it fills z alone, beside a y the mesh
  splits, a heavy kernel's pass takes the aligned window all the same
  (``"interior-z"``: the strip form's tiles carry the y halo rows).  A step may
  be several STAGES (a sequence of kernels, each behind its own exchange)
  and a stage several PASSES, each over the quantities its outputs touch:
  all planned from one abstract trace of each kernel (``plan_plane_stages``).
  A pass that cannot be cut further and fits VMEM with whole planes in no
  form moves Y TILES of them (``plan_plane_passes``, ``tile_rows``;
  ``stream_pass.stream_plane_pass_tiled``): the strip form on either aligned
  window -- a light kernel falls to it too --, and only where the planner
  would otherwise raise; the raw window (ragged lanes or rows, a split z) and
  ``halo="fused"`` keep that refusal.  Inside a DISPATCH such a pass moves
  the lane tile behind the aligned window one way a call, where the step is
  that one pass, in place, writing all it reads (``plane_lanes``,
  ``plane_lanes_form``).
* **wavefront** — ``m`` levels per pass over an ``s``-wide-shell shard
  (``m <= s // r``, ``r == 1`` only), plain or in the z-slab form.
* **wrap** — a single subdomain, the periodic boundary folded into the pass.

The axes a plan settles beside its route:

* ``overlap ∈ {off, split}`` (a tuner axis — docs/tuning.md "Stream
  overlap"; ``ops/stream.py`` has the schedule): ``split`` engages on the
  ``plane`` and plain ``wavefront`` routes; ``wrap`` has no exchange to hide
  and the z-slab wavefront interleaves its slab permutes with the pass, so
  both degrade to ``off`` with a warning.
* ``halo ∈ {array, fused}`` (a tuner axis — docs/tuning.md "Fused halo
  consumption"; ``ops/stream_pass.py`` has the mechanics).  Structural
  gates: the ``yzpack_*`` exchange route, even shards (the pack cuts at
  static offsets), blend-supported dtypes, ``overlap=off`` (the split
  schedule's exterior bands read exchanged BLOCKS), and the plane /
  plain-wavefront routes (a z-slab plan re-plans to the plain form first,
  like split).  Ineligible requests degrade to ``array`` with a warning;
  the ladder steps ``fused``→``array`` at the same depth before any depth
  descent.
* ``alias``: whether the passes write onto their inputs.

This module imports ``ops/stream_pass.py`` (and ``ops/exchange.py``) and
nothing of ``ops/stream.py``.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable, Dict, List, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from stencil_tpu.core.dim3 import Dim3
from stencil_tpu.ops.jacobi_pallas import (
    _make_roll,
    _padded_plane_bytes,
    _vmem_budget,
    _VMEM_STACK_MARGIN,
    _WRAP_MAX_K,
    z_halo_patch_form,
)
from stencil_tpu.ops.stream_pass import (
    PlaneInfo,
    PlaneKernel,
    PlaneView,
    StripView,
    lane_pad_width,
    plane_strip_rows,
    plane_window_form,
    sublane_tile,
    wrap_edge_plane,
)
from stencil_tpu.parallel.mesh import MESH_AXES


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


def stream_vmem_bytes(
    m: int,
    plane_y: int,
    plane_z: int,
    itemsizes: Sequence[int],
    z_slabs: bool = False,
    ring_itemsizes: Optional[Sequence[int]] = None,
    fused: bool = False,
) -> int:
    """VMEM block bytes of one streaming pass, stack margin excluded
    (``stack_margin``): per quantity, 2m ring planes + 4 pipeline planes
    (+ 4 z-slab blocks); under ``halo="fused"`` the double-buffered
    fused-shell side blocks too: one (1, y, z) x-slab plane plus the (1, 2m,
    z) y and (1, 2m, y) z message blocks per grid step.  Same padded-bytes
    accounting as ``wavefront_vmem_bytes``.  ``ring_itemsizes`` overrides the
    ring planes' itemsizes: bf16 STORAGE streams 2-byte pipeline planes but
    carries its level rings at f32 (the ``f32_accumulate`` contract), so the
    rings must be modeled at the NATIVE itemsize or the gate lies.  The ONE
    copy of the arithmetic: the planners and the prefilters price a plan
    through ``stream_plan_vmem_bytes``, the ``vmem-budget`` contract prices
    the planes of a TRACED program here (``analysis/vmem.py check_traced``)."""
    ring = itemsizes if ring_itemsizes is None else ring_itemsizes
    est = 0
    for it, rit in zip(itemsizes, ring):
        est += 2 * m * _padded_plane_bytes(plane_y, plane_z, rit)
        est += 4 * _padded_plane_bytes(plane_y, plane_z, it)
        if z_slabs:
            est += 4 * _padded_plane_bytes(2 * m, plane_y, it)
        if fused:
            est += 2 * _padded_plane_bytes(plane_y, plane_z, it)
            est += 2 * _padded_plane_bytes(2 * m, plane_z, it)
            est += 2 * _padded_plane_bytes(2 * m, plane_y, it)
    return est


def stack_margin(n_fields: int) -> int:
    """The PER-QUANTITY stack margin a pass needs beside its blocks: the level
    loop holds each field's roll/select temporaries live at once (measured:
    8-field m=2 at 518x640 planes reported 108.6 MB against an 85 MB block
    model, ~2.6 MB of stack per field)."""
    return _VMEM_STACK_MARGIN * max(1, n_fields)


def stream_plan_vmem_bytes(dd, plan: dict, x_radius: Optional[int] = None) -> Tuple[int, int]:
    """``(block bytes, stack margin)`` of a stream plan, requested or resolved,
    on a realized domain: which planes its route streams, at which itemsizes,
    priced by ``stream_vmem_bytes`` at the plan's ``m``, z slabs and fused
    side blocks.  What ``plan_stream``'s searches, ``plain_wavefront_plan``
    and ``check_vmem`` (the ladder's, the tuner's and the serving door's
    prefilter) all ask, so that a planner and a prefilter cannot disagree.

    The wrap route works on the BARE interiors (the periodic boundary is
    folded into its index maps), the others on the raw, shell-carrying
    planes -- in the z-slab form on ``lane_pad_width(raw.z)`` lanes, which is
    what a raw plane occupies anyway (``_padded_plane_bytes``).  Pipeline
    planes stream at the STORAGE itemsize; the level rings carry the
    ``f32_accumulate`` working precision, i.e. the native itemsize.  Per-field
    grouping runs one quantity a pass: the widest.

    The PLANE pass's ring holds ``2r`` RAW (storage-dtype) planes --
    ``stream_plane_pass`` upcasts transiently at view construction, never in
    the ring -- so a caller that knows ``x_radius`` (``plan_stream``'s grouping
    choice) gets the ring at depth ``r`` and the storage itemsize.  Without
    it a plane plan is priced as it stands, ``m = 1`` and native rings (a
    tuner candidate, a tenant's plan); a RESOLVED plane plan carries its
    passes' own bytes (``plane_pass_vmem_bytes``) and ``check_vmem`` reads
    those."""
    spec = dd.local_spec()
    planes = spec.sz if plan["route"] == "wrap" else spec.raw_size()
    itemsizes = [dd.field_dtype(h).itemsize for h in dd._handles]
    ring_sizes = [h.dtype.itemsize for h in dd._handles]
    m = int(plan.get("m", 1))
    if plan["route"] == "plane" and x_radius is not None:
        m, ring_sizes = x_radius, itemsizes
    if plan.get("grouping") == "per-field" and len(itemsizes) > 1:
        itemsizes, ring_sizes = [max(itemsizes)], [max(ring_sizes)]
    est = stream_vmem_bytes(
        m, planes.y, planes.z, itemsizes, z_slabs=bool(plan.get("z_slabs")),
        ring_itemsizes=ring_sizes, fused=plan.get("halo") == "fused",
    )
    return est, stack_margin(len(itemsizes))


def _plan_fits(dd, plan: dict, x_radius: Optional[int] = None) -> bool:
    """Does the candidate ``plan`` fit the scoped-VMEM budget by the model?"""
    est, margin = stream_plan_vmem_bytes(dd, plan, x_radius)
    return est + margin <= _vmem_budget()


def _deepest_fit(dd, first: int, cap: int, route: str, z_slabs: bool, grouping: str):
    """The plan of this route and form at the deepest depth of ``first..cap``
    the model fits, or None."""
    best = None
    for m in range(first, cap + 1):
        plan = {"route": route, "m": m, "z_slabs": z_slabs, "grouping": grouping}
        if _plan_fits(dd, plan):
            best = plan
    return best


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
    # joint (all fields per pass) AND, for separable kernels, per-field
    # grouping are both priced (stream_plan_vmem_bytes) and the DEEPEST depth
    # wins -- depth is the traffic lever (~8/m B/cell/iter); grouping only
    # changes VMEM pressure and per-pass ramp overhead, so joint wins ties
    groupings = ["joint"] + (["per-field"] if separable and len(dd._handles) > 1 else [])

    # single device: the WRAP route folds the periodic boundary into the
    # kernel's index maps/rotates — no shell reads, no exchange, the deepest
    # temporal blocking (the user-kernel analog of jacobi_wrap_step)
    if path in ("auto", "wrap") and dd.num_subdomains() == 1 and x_radius == 1:
        cap = min(_WRAP_MAX_K, n.x // 2)
        if max_m is not None:
            cap = min(cap, max_m)
        best = None
        for grouping in groupings:
            plan = _deepest_fit(dd, 1, cap, "wrap", False, grouping)
            if plan is not None and (best is None or plan["m"] > best["m"]):
                best = plan
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
        best = None
        # z-slab form's static emit slices assume even shards
        z_modes = (False,) if padded else (True, False)
        for grouping in groupings:
            for z_mode in z_modes:
                plan = _deepest_fit(dd, 2, cap, "wavefront", z_mode, grouping)
                if plan is not None and (best is None or plan["m"] > best["m"]):
                    best = plan
                if plan is not None:
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
    joint = {"route": "plane", "m": 1, "z_slabs": False, "grouping": "joint"}
    # (2r+4) resident planes per field may blow the budget jointly
    if len(groupings) > 1 and not _plan_fits(dd, joint, x_radius):
        return dict(joint, grouping="per-field")
    return joint


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
    strip: int = 0  # rows of the strip the kernel was traced over (``stream_
    # pass.plane_strip_rows``): the jaxpr then takes ``(2r + 1)^3`` strips a
    # quantity, one for every ``(dx, dy, dz)``, and holds no shift at all; 0 =
    # ``2r + 1`` whole planes, the in-plane shifts rotates inside it

    def plane_offsets(self) -> Tuple[Tuple[int, int, int], ...]:
        """The ``(dx, dy, dz)`` of each of a quantity's inputs of the jaxpr, in
        order."""
        span = range(-self.x_radius, self.x_radius + 1)
        plane = span if self.strip else (0,)
        return tuple((dx, dy, dz) for dx in span for dy in plane for dz in plane)

    def pruned(self, outputs: Sequence[str]):
        """``(kernel, reads, rings, offsets)`` of the pass that writes
        ``outputs``: the kernel with everything those outputs do not need cut
        away (``dce_jaxpr``), the quantities it still reads (the outputs
        themselves included: the pass carries their shell through), the ones
        among them it reads at ``dx != 0``, and the ``(quantity, (dx, dy,
        dz))`` inputs of the jaxpr it still takes.  No second trace of the
        user's callable is made: what the footprint saw IS what runs."""
        if self.closed is None:  # fail closed: the whole kernel, every ring
            return self.kernel, self.names, self.names, ()
        from jax.extend import core as jex
        from jax.interpreters import partial_eval as pe

        offsets = self.plane_offsets()
        kept = [nm for nm in self.writers if nm in outputs]
        jaxpr, used = pe.dce_jaxpr(
            self.closed.jaxpr, [nm in outputs for nm in self.writers], instantiate=False
        )
        run = jex.jaxpr_as_fun(jex.ClosedJaxpr(jaxpr, self.closed.consts))
        planes = [
            (nm, off)
            for q, nm in enumerate(self.names)
            for k, off in enumerate(offsets)
            if used[3 + q * len(offsets) + k]
        ]

        def kernel(views, info):
            args = [c for c, u in zip(info.coords(), used[:3]) if u]
            args += [views[nm].sh(*off) for nm, off in planes]
            return dict(zip(kept, run(*args)))

        touched = {nm for nm, _ in planes} | set(kept)
        ringed = {nm for nm, off in planes if off[0]}
        return (
            kernel,
            tuple(nm for nm in self.names if nm in touched),
            tuple(nm for nm in self.names if nm in ringed),
            tuple(planes),
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
    strip: int = 0,  # the pass's strip form (``plane_strip_rows``): ``planes``
    # are then the ``(S, Z)`` strips the kernel is evaluated over
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
    With ``strip`` the kernel is traced as the pass's strip form will run it,
    over ``StripView``s: every ``(dx, dy, dz)`` a quantity is read at is an
    input of the jaxpr of its own and the jaxpr holds no shift -- the pass
    makes each where and as often as it chooses (``shared_rotations``).

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
    to the in-place passes of the plane route's default schedule, pass by
    pass where a stage is cut into several (``plan_plane_passes(rename=)``:
    the handles swap when the stage's last pass has run); not under
    ``overlap="split"`` (fresh outputs), not under ``halo="fused"`` (every
    quantity is written), not when the trace failed.

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
    per = w**3 if strip else w  # inputs a quantity (``PlaneTrace.plane_offsets``)

    def note(nm, dx, dy, dz):
        seen.setdefault(nm, set()).add((dx, dy, dz))

    def view(nm, mine):
        if not strip:
            return PlaneView(tuple(mine), roll, partial(note, nm))
        return StripView(
            lambda dx, dy, dz: mine[((r + dx) * w + r + dy) * w + r + dz], r, partial(note, nm)
        )

    def footprint(x_g, y_g, z_g, *vs):
        info = PlaneInfo(x_g, y_g, z_g, global_size, 1)
        vals = kernel(
            {nm: view(nm, vs[q * per : (q + 1) * per]) for q, nm in enumerate(names)},
            info,
        )
        returned[:] = [nm for nm in names if nm in vals]
        return [vals[nm] for nm in returned]

    i32 = partial(jax.ShapeDtypeStruct, dtype=jnp.int32)
    try:
        closed = jax.make_jaxpr(footprint)(
            i32(()), i32((Y, 1)), i32((1, Z)), *[p for p in planes for _ in range(per)]
        )
    except Exception as exc:  # noqa: BLE001 — whatever the user's kernel raises
        from stencil_tpu.utils.logging import log_warn

        log_warn(
            f"the stream kernel's footprint trace raised ({exc!r}); "
            "exchanging and writing every quantity"
        )
        return PlaneTrace(names, names, names, r, None, kernel, strip=strip)
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
        _plane_renames(closed.jaxpr, names, tuple(returned), per, stored),
        tuple((nm, tuple(sorted(seen[nm]))) for nm in names if nm in seen),
        strip,
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


def edge_reads(traces: Sequence[PlaneTrace]) -> Tuple[str, ...]:
    """The axis pairs (``"xy"``, ``"xz"``, ``"yz"``, in that order) some
    quantity is read across at an offset non-zero on BOTH axes: the edge
    halos a step's kernels need (``PlaneTrace.offsets``; a trace that raised
    says nothing).  Astaroth's MHD step: all three, the mixed differences'
    diagonals; a star: none."""
    offs = [o for t in traces for _, of_reader in t.offsets for o in of_reader]
    return tuple(
        MESH_AXES[a] + MESH_AXES[b]
        for a, b in ((0, 1), (0, 2), (1, 2))
        if any(o[a] and o[b] for o in offs)
    )


def _plane_renames(jaxpr, names, writers, per: int, stored: dict):
    """The ``(p, q)`` of ``trace_plane_kernel``'s rename rule, read off the
    kernel's jaxpr (invars: three coordinates, then ``per`` inputs a quantity
    -- its ``2r + 1`` window planes, or in the strip form a strip for every
    ``(dx, dy, dz)`` --, the centre in the middle; outvars: the writers in order):
    output ``p`` is ``q``'s centre invar itself, ``q`` is a writer whose own
    output is no quantity's centre plane, ``stored`` (dtype, plane shape)
    agree, and ``q`` is claimed once."""
    centres = [(nm, jaxpr.invars[3 + q * per + per // 2]) for q, nm in enumerate(names)]
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


#: operations a cell (equations of the kernel's strip-form jaxpr) from which a
#: step's passes take the strip form: Devito's acoustic update (52) runs 3-10%
#: SLOWER in it on a periodic 256^3 box, Astaroth's MHD substep (823 / 847)
#: faster (PERF.md PR 46)
_STRIP_MIN_OPS = 512


def shared_rotations(offsets) -> Tuple[Tuple[str, int, int], ...]:
    """The ``(quantity, dx, dz)`` of a strip-form pass whose plane is worth
    rotating ONCE a grid step (``stream_plane_pass(prerotated=)``): those among
    the ``(quantity, (dx, dy, dz))`` reads ``offsets`` (``PlaneTrace.pruned``)
    that two or more ``dy`` share -- a strip's y shift is an address, so every
    one of them then reads the ONE rotated plane where the loop would rotate
    each strip of each ``dy`` anew.  (Astaroth's MHD step: the y-z mixed
    differences read ``(0, +-k, +-k)`` beside the ``(0, 0, +-k)`` of the
    differences along z: 24 planes of four fields, a third of its 144 lane
    rotates a cell.)"""
    rows = {}
    for nm, (dx, dy, dz) in offsets:
        if dz:
            rows.setdefault((nm, dx, dz), set()).add(dy)
    return tuple(key for key, dys in rows.items() if len(dys) > 1)


def plane_pass_vmem_bytes(
    plane_bytes: Dict[str, int], x_radius: int, reads, rings, writes,
    ring_bytes: Optional[Dict[str, int]] = None,
    stage_bytes: Optional[Dict[str, int]] = None, prerotated=(),
    y_tiles: int = 0, stash_bytes: Optional[Dict[str, int]] = None,
) -> int:
    """VMEM model of one plane pass, ``stream_vmem_fits``' accounting cut to
    what the pass holds: two pipeline planes per quantity read, two more per
    quantity written, a ``2r``-deep ring per quantity read at ``dx != 0``,
    and the per-quantity stack margin (the kernel's roll / select
    temporaries).  ``plane_bytes`` is the tile-padded RAW plane of each
    quantity at its STORAGE itemsize -- what the pipeline moves -- and
    ``ring_bytes`` the plane its ring holds, where that is another: the
    block's interior on the interior window (``plane_window_form``; the
    rings hold storage-dtype working planes).  None = the raw plane.
    ``stage_bytes`` says the pass runs its strip form (``stream_pass.plane_
    strip_rows``): ``ring_bytes`` is then the working plane as tiles between
    its margin tiles, a ring holds ``2r + 1`` of them (the newest plane is
    pushed before the strips read it), every other quantity read holds one,
    every quantity written a staging plane of ``stage_bytes``, and every
    ``(quantity, dx, dz)`` of ``prerotated`` (``shared_rotations``) one more
    plane of ``ring_bytes``.  None = the kernel runs over the plane whole.
    ``y_tiles = NT > 0`` says the pipeline moves Y TILES of a plane
    (``stream_pass.stream_plane_pass_tiled``): ``plane_bytes``, ``ring_bytes``
    and ``stage_bytes`` are then ONE y tile's -- the raw rows a block moves, the
    tile's tiles between its own margins, the staging tile -- a quantity read at
    ``dx != 0`` holds ``2r + 2`` planes of ``NT`` such tiles (a plane lands
    while the strips read the ``2r + 1`` before it), every other two, every
    quantity read and every one written one more tile of ``stash_bytes`` (the
    block's tail rows), and the margin is ONE ``_VMEM_STACK_MARGIN``: a value
    of this form is a strip or a y tile, never a plane a quantity."""
    ring_bytes = plane_bytes if ring_bytes is None else ring_bytes
    est = sum(2 * plane_bytes[q] for q in reads)
    est += sum(2 * plane_bytes[q] for q in writes)
    if y_tiles:
        est += sum((2 * x_radius + 2 if q in rings else 2) * y_tiles * ring_bytes[q] for q in reads)
        est += sum(stage_bytes[q] + stash_bytes[q] for q in writes)
        est += sum(stash_bytes[q] for q in reads)
        return est + _VMEM_STACK_MARGIN
    if stage_bytes is None:
        est += sum(2 * x_radius * ring_bytes[q] for q in rings)
    else:
        est += sum((2 * x_radius + 1 if q in rings else 1) * ring_bytes[q] for q in reads)
        est += sum(stage_bytes[q] for q in writes)
        est += sum(ring_bytes[q] for q, _, _ in prerotated)
    return est + _VMEM_STACK_MARGIN * len(reads)


class FitsNoPass(ValueError):
    """A plane pass that cannot be cut further -- one output alone, or a stage
    whose passes would read a block an earlier one wrote -- fits the VMEM
    budget in no form the planner has (``plan_plane_passes``)."""


@dataclasses.dataclass(frozen=True)
class PlaneTiling:
    """The y tiles ``plan_plane_passes`` may cut a pass's planes into
    (``plan_plane_stages`` makes it): ``rows`` the candidates, largest first --
    every divisor of the working plane's rows that is whole strips, the plane
    itself first, one strip last --, ``bytes_of(rows)`` the ``(plane_bytes,
    ring_bytes, stage_bytes, stash_bytes)`` of ``plane_pass_vmem_bytes(y_tiles=)``
    for one of them, and, where ``rows`` is empty, ``why`` the step has no
    tiled form."""

    rows: Tuple[int, ...] = ()
    bytes_of: Optional[Callable[[int], tuple]] = None
    why: str = ""


def plan_plane_passes(
    trace: PlaneTrace, plane_bytes: Dict[str, int], whole: bool = False,
    rename: bool = False, ring_bytes: Optional[Dict[str, int]] = None,
    stage_bytes: Optional[Dict[str, int]] = None, tiling: PlaneTiling = PlaneTiling(),
) -> List[dict]:
    """The passes of one stage over one group: ``[{"writes", "reads",
    "rings", "renames", "prerotated", "tile_rows", "vmem_bytes"}, ...]``, each a subset of
    the kernel's outputs with the quantities THOSE outputs touch
    (``PlaneTrace.pruned``); ``prerotated`` is the strip form's
    ``shared_rotations``, () where they do not fit beside the rest.

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

    Y TILES (``tiling``; ``"tile_rows"``, 0 = whole planes).  Where an output
    fits no pass of whole planes ALONE -- the one place this function used to
    raise: a kernel that couples many quantities in every output, D3Q19's
    nineteen at 512 x 512 -- the pass moves y tiles of its planes instead
    (``stream_pass.stream_plane_pass_tiled``): the largest ``tiling.rows`` the
    one model fits (``plane_pass_vmem_bytes(y_tiles=)``), and the outputs
    behind it join that pass while ANY tile fits (fewer passes move fewer
    arrays; a smaller tile moves the same bytes); and a stage whose
    whole-plane passes CLASH (the next paragraph's refusal) is planned as one
    tiled pass over all its outputs where that fits.  Nothing that fits whole
    planes is ever tiled, so no plan that resolved before tiles existed
    changes.  A tiled pass takes renames as a whole-plane pass does, and no
    ``prerotated`` plane.  It raises ``FitsNoPass`` where no tile fits either,
    or the step has no tiled form (``tiling.why``), and says what was tried.

    Passes run one after the other ON THE SAME ARRAYS (in place), while a
    kernel means all its outputs to come from the values it was called with:
    a pass that reads a BLOCK an earlier pass of the stage has written would
    read the new value.  That raises too (make it a stage of its own), and
    says which block.  The check reads blocks, not names: a pass writes an
    output into the output's own block, or, renamed, into another
    quantity's -- and then the stage's entry value still stands under the
    output's name for every later pass to read.

    ``whole`` keeps the stage in one pass over every quantity, every one
    ringed and written (``halo="fused"``, whose side buffers are
    per-quantity operands of the pass).

    ``rename`` applies the rename rule (``trace_plane_kernel``), pass by
    pass: the outputs that are another writer's centre plane are written by
    no pass, and the pair ``(p, q)`` goes under the ``renames`` of the pass
    that computes ``q``, tiled or whole -- ``q``'s new value lands in ``p``'s
    buffer, so ``p`` stays among that pass's ``reads`` whether the kernel reads
    it or not.  In a stage of several passes that is what keeps the passes
    apart: Astaroth's two-buffer Runge-Kutta at 512 x 512 writes every new
    field into its ``*_prev`` block, so no pass reads a block another has
    written, where written in place the second pass would clash with the
    first.  The build swaps the handles once, when the stage's last pass has
    run (``ops/stream.py _build_plane_step``); ``_carry_period`` walks every
    pass's pairs.  The caller passes it for the in-place default schedule only
    (``resolve_stream_plan``).
    ``ring_bytes`` and ``stage_bytes`` are ``plane_pass_vmem_bytes``' own."""
    budget = _vmem_budget()
    # the rename rule, pass by pass: ``home[q]`` is the quantity whose block
    # the new ``q`` lands in, and the outputs that are such a ``q``'s centre
    # plane are written by no pass
    home = {}
    if rename and not whole and trace.closed is not None:
        home = {q: p for p, q in trace.renames}
    outputs = [out for out in trace.writers if out not in home.values()]

    def describe(outputs, whole=False):
        prerotated, renames = (), ()
        if whole or trace.closed is None:
            reads = rings = writes = trace.names
        else:
            _, reads, rings, offsets = trace.pruned(outputs)
            writes = tuple(outputs)
            renames = tuple((p, q) for p, q in trace.renames if home and q in writes)
            homes = set(reads) | {p for p, _ in renames}
            reads = tuple(nm for nm in trace.names if nm in homes)
            prerotated = shared_rotations(offsets) if trace.strip else ()

        def priced(prerotated):
            return plane_pass_vmem_bytes(
                plane_bytes, trace.x_radius, reads, rings, writes, ring_bytes, stage_bytes,
                prerotated,
            )

        if priced(prerotated) > budget:  # no room: the loop rotates every strip itself
            prerotated = ()
        return {
            "writes": writes,
            "reads": reads,
            "rings": rings,
            "renames": renames,
            "prerotated": prerotated,
            "tile_rows": 0,
            "vmem_bytes": priced(prerotated),
        }

    def tiled(outputs):
        """The pass that writes ``outputs`` over the largest y tile that fits, or
        None."""
        p = describe(outputs)
        for rows in tiling.rows:
            of_tile, of_ring, of_stage, of_stash = tiling.bytes_of(rows)
            est = plane_pass_vmem_bytes(
                of_tile, trace.x_radius, p["reads"], p["rings"], p["writes"], of_ring, of_stage,
                y_tiles=tiling.rows[0] // rows, stash_bytes=of_stash,
            )
            if est <= budget:
                return dict(p, prerotated=(), tile_rows=rows, vmem_bytes=est)
        return None

    def fit(outputs, in_tiles):
        p = tiled(outputs) if in_tiles else describe(outputs)
        return p if p is not None and p["vmem_bytes"] <= budget else None

    def blocks(p):
        """The quantities whose BLOCKS the pass ``p`` writes: an output's own,
        or the one its rename lands it in."""
        landed = {q: at for at, q in p["renames"]}
        return tuple(landed.get(q, q) for q in p["writes"])

    def refuse(p):
        if len(p["reads"]) == 1:
            return  # the floor: one quantity, nothing to split
        if whole or trace.closed is None:
            tried = "a pass that carries every quantity whole has no tiled form"
        elif tiling.rows:
            tried = (
                f"y tiles of its planes from {tiling.rows[0]} rows down to one strip of "
                f"{tiling.rows[-1]} fit none either"
            )
        else:
            tried = tiling.why or "the step has no tiled form"
        into = f" (into the blocks of {blocks(p)}: renamed)" if p["renames"] else ""
        raise FitsNoPass(
            f"the plane pass that writes {p['writes']}{into} reads {len(p['reads'])} "
            f"quantities {p['reads']}, {len(p['rings'])} of them off-centre "
            f"along x {p['rings']}: {p['vmem_bytes']} bytes of VMEM by the "
            f"model against a budget of {budget} -- it fits no pass ({tried}); "
            "split the kernel into stages that touch fewer quantities each"
        )

    if not trace.writers:
        return []
    if whole or trace.closed is None:
        p = describe(trace.writers, whole=True)
        if p["vmem_bytes"] > budget:
            refuse(p)
        return [p]
    passes, current, in_tiles = [], [], False
    for out in outputs:
        p = fit(current + [out], in_tiles)
        if current and p is None:  # close the pass, open the next
            passes.append(fit(current, in_tiles))
            current, in_tiles, p = [], False, fit([out], False)
        if p is None:  # alone and too wide for whole planes: y tiles of them
            alone = describe([out])
            in_tiles = len(alone["reads"]) > 1 and tiled([out]) is not None
            if not in_tiles:
                refuse(alone)  # raises, unless it is the floor
        current.append(out)
    passes.append(fit(current, in_tiles) or describe(current))
    written = set()  # the blocks the stage's passes have written so far
    for p in passes:
        clash = written & set(p["reads"])
        if clash and not any(q["tile_rows"] for q in passes):
            # the other place this function used to raise: in y tiles the
            # stage may be ONE pass after all (D3Q19 at 512 x 512 stored as
            # bf16: eight outputs fit whole planes, the ninth reads them)
            joint = tiled(outputs)
            if joint is not None:
                return [joint]
        if clash:
            raise FitsNoPass(
                f"the plane pass that writes {p['writes']} (into the blocks of "
                f"{blocks(p)}) reads {tuple(sorted(clash))}, whose block an earlier "
                "pass of the same stage has already written in place: the stage "
                "does not fit one pass and cannot be split; make the later update "
                "a stage of its own"
            )
        written |= set(blocks(p))
    return passes


def static_stream_alias(route: str, n_fields: int) -> bool:
    """The no-tune alias rule, read from what the plan says of itself: the
    plane route always, any route from 4 fields up (``resolve_stream_plan``
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
    """Do the main passes of a RESOLVED plan write onto their inputs?  The
    resolved ``plan["alias"]`` — except on the plane route under
    ``overlap="split"``, which keeps fresh outputs: the interior pass and
    the exchange both read the pre-exchange blocks, so XLA copies each block
    once a step either way (compiled for a described v5e 2x2, 260^3 shards:
    one whole-array copy per quantity per step aliased or not, and 73 MB
    more temporaries aliased)."""
    return bool(plan.get("alias")) and not (
        plan["route"] == "plane" and plan.get("overlap") == "split"
    )


def _axis_request(plan: dict, axis: str, env_name: str, choices, what: str) -> Tuple[str, str]:
    """Pre-structural (value, source) of one of a stream plan's two schedule
    axes, ``overlap`` or ``halo``.  Precedence mirrors the exchange route and
    stream alias rules: a FORCED plan value (``<axis>_forced`` — explicit
    ``make_step(stream_<axis>=...)`` / ``make_stream_step(<axis>=...)``
    requests, autotuner candidate builds, and the ladder's step-down to the
    static value, none of which ever consult further) > the environment
    (validated read) > the plan's tuned value > the static ``choices[0]``."""
    from stencil_tpu.utils.config import env_choice

    if plan.get(axis + "_forced") and plan.get(axis) is not None:
        if plan[axis] not in choices:
            raise ValueError(f"unknown stream {what} {plan[axis]!r} (one of {choices})")
        return plan[axis], "explicit"
    env = env_choice(env_name, "auto", ("auto",) + choices)
    if env != "auto":
        return env, "env"
    tuned = plan.get(axis)
    if tuned in choices:
        return str(tuned), "tuned"
    if tuned is not None:
        from stencil_tpu.utils.logging import log_warn

        log_warn(
            f"tuned stream {axis} {tuned!r} is not one of {choices}; using the "
            f"static {choices[0]!r} fallback"
        )
    return choices[0], "static"


def _overlap_request(plan: dict) -> Tuple[str, str]:
    return _axis_request(plan, "overlap", "STENCIL_STREAM_OVERLAP", STREAM_OVERLAP, "overlap")


def _halo_request(plan: dict) -> Tuple[str, str]:
    return _axis_request(plan, "halo", "STENCIL_STREAM_HALO", STREAM_HALO, "halo mode")


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
    cap = min(dd._shell_radius.lo().x, _WRAP_MAX_K)
    if max_depth is not None:
        cap = min(cap, max_depth)
    plain = _deepest_fit(dd, 2, cap, "wavefront", False, plan.get("grouping"))
    return None if plain is None else dict(plan, z_slabs=False, m=plain["m"])


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
                      fused: bool = False, rename: bool = False,
                      window: str = "raw", strip: int = 0) -> Tuple[dict, tuple]:
    """Plan a PLANE-route step from its kernels' own footprints: ``(keys,
    runs)``.  ``keys`` is what the plan says of it: ``stages`` -- per stage
    its ``readers`` (the quantities its exchange fills) and its ``passes``
    (``plan_plane_passes``: writes, reads, rings, renames, modeled VMEM
    bytes) --, ``footprint`` (``footprint_counts``) and the step-wide unions
    ``halo_readers`` / ``writers`` / ``renamed`` (the quantities whose write
    became a rename).  ``runs`` is, per stage, what the build runs: ``[(pass
    kernel, reads, rings, writes, renames, prerotated, tile_rows), ...]`` (names).
    Raises ``FitsNoPass`` (a ``ValueError``) for a step that fits in no pass.  Of ``plan`` only the
    grouping is read.

    Every stage is traced once per group (``trace_plane_kernel``); a function
    of the kernels, as ``_sweep_kind`` is a function of the mesh: no option.
    Under ``fused`` every quantity rides the exchange and every pass is
    whole (``plan_plane_passes``); ``rename`` is for the in-place default
    schedule (``resolve_stream_plan``).  ``window`` is the passes' working
    plane (``plane_window_form``): the kernels are traced over planes of ITS
    shape -- the rotate a shift lowers to is chosen there, at trace time
    (``_make_roll``) -- and the rings are priced at it; ``keys["plane_window"]``
    says the one the step is planned on, which is ``"raw"`` where
    ``"interior-z"`` was asked for and no strip form comes of it (that window
    has no whole-plane form: a light kernel beside a split y runs over whole
    raw planes while they fit a pass).  ``strip`` is the rows
    of a strip of the passes' strip form as the plane allows it
    (``plane_strip_rows``; 0 = the plane whole): the kernels are traced over
    such strips and the rings priced as the tiles they then hold -- unless the
    heaviest of the step's kernels makes fewer than ``_STRIP_MIN_OPS``
    operations a cell (the equations of its jaxpr, which in the strip form
    holds no shift): the step is then planned over whole planes, and
    ``keys["plane_strip"]`` says 0 -- unless whole planes fit no pass
    (``FitsNoPass``): the strip form, on either aligned window, can move y tiles
    of them (``PlaneTiling``; D3Q19 at 512 x 512, on one chip and beside a split
    y), and the step is planned in it after all."""
    names = [h.name for h in dd._handles]
    raw = dd.local_spec().raw_size()
    if window == "interior-z" and not strip:
        window = "raw"  # beside a split y the aligned window has a strip form only
    work = raw if window == "raw" else dd.local_spec().sz
    f32_acc = any(dd.field_dtype(h) != h.dtype for h in dd._handles)
    planes = [
        jax.ShapeDtypeStruct(
            (strip or work.y, work.z), jnp.float32 if f32_acc else dd.field_dtype(h)
        )
        for h in dd._handles
    ]
    # what a ring holds of a plane: in the strip form its tiles between
    # ``x_radius`` margin tiles a side, and a writer's staging plane beside
    # (``stream_plane_pass``)
    shell = dd._shell_radius
    tile = sublane_tile([dd.field_dtype(h) for h in dd._handles])
    margin_tiles = (shell.lo().y + shell.hi().y) if window == "interior-z" else 2 * x_radius
    margins = margin_tiles * tile if strip else 0
    def padded(rows, of):  # per quantity, ``rows`` tile-padded rows of a plane of ``of``
        return {
            h.name: _padded_plane_bytes(rows, of.z, dd.field_dtype(h).itemsize)
            for h in dd._handles
        }

    plane_bytes, stage_bytes, ring_bytes = (
        padded(raw.y, raw), padded(work.y, work), padded(work.y + margins, work)
    )
    groups = _stream_groups(plan, len(names))
    traced = [  # per stage, per group: traced before anything is planned
        [
            trace_plane_kernel(
                stage, [names[q] for q in g], [planes[q] for q in g], x_radius,
                dd._size, interpret, [dd.field_dtype(dd._handles[q]) for q in g], strip,
            )
            for g in groups
        ]
        for stage in _as_stages(kernel)
    ]
    traces = [t for of_stage in traced for t in of_stage]
    if strip and max(
        (len(t.closed.jaxpr.eqns) for t in traces if t.closed is not None), default=0
    ) < _STRIP_MIN_OPS:
        # a LIGHT kernel is bound by the planes it streams, and the strip form's
        # tiles cost it more than its few values in registers save: whole planes
        # -- unless no pass holds them whole: the strip form can move y tiles
        # (a strip form exists on the two aligned windows alone)
        try:
            return plan_plane_stages(dd, kernel, x_radius, plan, interpret, fused, rename, window)
        except FitsNoPass:
            if fused:
                raise
    # the y tiles a pass that fits no whole planes may move instead
    # (plan_plane_passes): built for the strip form, on either aligned window
    if strip and not fused:
        # a y tile holds at least the tiles its margins are cut from: ``r`` a
        # side on the interior window, the y shell's ``lo.y + hi.y`` behind it
        # beside a split y (``stream_plane_pass_tiled``)
        least = (margin_tiles if window == "interior-z" else x_radius) * tile
        tiling = PlaneTiling(
            tuple(
                work.y // n for n in range(1, work.y // strip + 1)
                if work.y % n == 0 and work.y // n % strip == 0 and work.y // n >= least
            ),
            # of ONE y tile: the raw rows a block moves, its tiles between its
            # own margins, the staging tile, a one-tile stash of tail rows
            lambda rows: (
                padded(rows, raw), padded(rows + margins, work),
                padded(rows, work), padded(tile, work),
            ),
        )
    else:
        tiling = PlaneTiling(why=(
            "y tiles of a plane are built for the strip form on the two aligned windows "
            "alone ('interior': y and z unsplit; 'interior-z': z unsplit beside a split y; "
            "an interior of whole vector tiles either way); this step's "
            f"passes work on the {window!r} window" + (
                ", whole planes" if not strip else "") + (
                " under halo='fused'" if fused else "")
        ))
    described, built = [], []
    for of_stage in traced:
        readers, passes, runs = set(), [], []
        for trace in of_stage:
            readers |= set(names) if fused else set(trace.readers)
            for p in plan_plane_passes(
                trace, plane_bytes, whole=fused, rename=rename, ring_bytes=ring_bytes,
                stage_bytes=stage_bytes if strip else None, tiling=tiling,
            ):
                passes.append(p)
                runs.append((
                    trace.pruned(p["writes"])[0], p["reads"], p["rings"], p["writes"],
                    p["renames"], p["prerotated"], p["tile_rows"],
                ))
        described.append({
            "readers": tuple(nm for nm in names if nm in readers),
            "passes": tuple(passes),
        })
        built.append(runs)
    # the rows of the y tiles its passes move (the smallest, of a step whose
    # passes differ) and how many of them a plane is: 0 and 1 over whole planes
    tile_rows = min(
        (p["tile_rows"] for st in described for p in st["passes"] if p["tile_rows"]), default=0
    )
    keys = {
        "stages": tuple(described), "footprint": footprint_counts(traces), "plane_strip": strip,
        "edge_reads": edge_reads(traces), "plane_window": window,
        "tile_rows": tile_rows, "y_tiles": work.y // tile_rows if tile_rows else 1,
    }
    for key, of in (
        ("halo_readers", lambda st: st["readers"]),
        ("writers", lambda st: [w for p in st["passes"] for w in p["writes"]]),
        ("renamed", lambda st: [a for p in st["passes"] for a, _ in p["renames"]]),
    ):
        union = {nm for st in described for nm in of(st)}
        keys[key] = tuple(nm for nm in names if nm in union)
    return keys, tuple(built)


def macros_per_trip(in_place: bool) -> int:
    """After how many macros a macro loop's carry is back in its own buffer:
    1 where the kernel writes in place, 2 where it writes a fresh result
    (``macro_loop``)."""
    return 1 if in_place else 2


def wrap_edge_form(dd, plan: Mapping) -> str:
    """Where the edges of a wrap-route dispatch live: ``"raw"`` = its first
    pass reads the domain's raw blocks and its last one writes them
    (``stream_wrap_pass``'s edge forms), ``"xla"`` = ``lax.slice`` cuts the
    bare interiors out before the passes and ``dynamic_update_slice`` lands
    them behind (nineteen of each, a tenth of ``lbm-d3q19-256.bulk``'s busy
    time: PERF.md, PR 52).  Read off the block's static shape and the VMEM
    model AFTER the depth is chosen, for the bare form as ever: ``"raw"`` where
    the y-z interior is whole vector tiles of every stored dtype, no narrower
    than the margin that rounds a raw plane up to whole tiles
    (``wrap_edge_plane``), and the bare form's bytes plus the edge forms' two
    larger pipeline planes a quantity still fit the budget -- so a kernel
    that fits bare only keeps its depth and the XLA edges.  ``domain.step``
    says it as ``edges``; no option."""
    spec = dd.local_spec()
    n, raw = spec.sz, spec.raw_size()
    dtypes = [dd.field_dtype(h) for h in dd._handles]
    yb, zb = wrap_edge_plane((raw.y, raw.z), dtypes)
    whole = n.y % sublane_tile(dtypes) == 0 and n.z % 128 == 0
    if not (whole and yb <= 2 * n.y and zb <= 2 * n.z):
        return "xla"
    itemsizes = [jnp.dtype(d).itemsize for d in dtypes]
    if plan.get("grouping") == "per-field":
        itemsizes = [max(itemsizes)]
    wider = sum(
        2 * (_padded_plane_bytes(raw.y, raw.z, it) - _padded_plane_bytes(n.y, n.z, it))
        for it in itemsizes
    )
    est, margin = stream_plan_vmem_bytes(dd, plan)
    return "raw" if est + wider + margin <= _vmem_budget() else "xla"


def plane_lanes_form(plan: Mapping) -> str:
    """Which lane tiles of a raw plane the passes of a plane-route DISPATCH
    move on the side that faces another call of it: ``"window"`` = the aligned
    working window ``[0, Zw)`` alone -- the dispatch's first call reads whole
    raw planes and makes today's fills but writes the window's lane tiles
    alone, every call between the first and the last reads and writes those
    alone, fills nothing and rebuilds no z shell, and the last reads them and
    writes whole planes with the z shell rebuilt (``plane_lane_forms``;
    ``stream_plane_pass_tiled(shell_in=, shell_out=)``, ``ops/stream.py
    _build_plane_step``) --, ``"raw"`` = every call moves whole raw planes
    both ways.  On either aligned window the lanes ``[Zw, Z)`` hold copies of
    the window's first lanes that a call of the pass leaves behind and only
    its own low z fill reads back: between two calls of one dispatch they
    carry nothing, and of a 514-lane f32 plane they are a fifth (8,128) tile
    of every row the pipeline moves (D3Q19 at 512^3: 26.0 GB a call whole,
    23.4 with one side narrow, 20.8 with both: 39.2 / 35.2-35.8 / 32.0 ms a
    call on one v5e, PERF.md §6 PR 54; a 6-step dispatch runs 1 + 4 + 1 of
    them: 32.7 ms a step where two forms read 35.3, PERF.md §6 PR 58).  Each
    form is one more trace, lowering and Mosaic compile of the pass at set-up:
    the third cost the one-chip cell +2.8 s of its first dispatch with the
    compile cache warm, +10% of ``setup_s`` (as measured, PR 58).

    Read off the resolved plan alone: ONE pass a step (a later pass of the
    dispatch's first step would fill its low z halo from lanes an earlier
    one left stale), moved in y tiles (``tile_rows``: the tiled pass is the
    one that has the forms; on an aligned window, whose z fills are the
    pass's own), in place (the lanes a narrow call does not write keep what
    the aliased block held), writing every quantity it reads (a read-only
    operand's low z halo is filled nowhere but in the pipeline's buffer), on
    the default schedule (``halo="fused"`` side buffers and the split
    schedule's exterior bands are cut from whole raw blocks: they would read
    the stale lanes, and their stages take no forms).  ``domain.step`` says
    it as ``plane_lanes``; no option."""
    passes = [p for st in plan["stages"] for p in st["passes"]]
    if len(passes) != 1 or not passes[0]["tile_rows"]:
        return "raw"
    if plan["halo"] == "fused" or plan["overlap"] == "split":
        return "raw"
    (p,) = passes
    aligned = plan["plane_window"] != "raw" and "z" in plan["pass_wrap_axes"]
    whole = set(p["reads"]) <= set(p["writes"]) and not p["renames"]
    return "window" if aligned and whole and _plan_passes_in_place(plan) else "raw"


def plane_lane_forms(plan: Mapping, steps: int) -> Tuple[Tuple[Tuple[bool, bool], int], ...]:
    """The calls of a plane-route dispatch of ``steps`` steps, in order, as
    runs ``((shell_in, shell_out), calls)`` of the tiled pass's lane forms
    (``plane_lanes_form``): with ``plane_lanes`` "window" the first call
    ``(True, False)``, the ``steps - 2`` between ``(False, False)``, the last
    ``(False, True)``; a dispatch of ONE step, like every call of a "raw"
    plan, is whole both ways.  ``steps`` is static, so a dispatch of two holds
    no middle run and traces no third form.  ``_build_plane_step`` runs what
    this lists, and ``domain.step``'s ``narrow_calls`` counts its ``(False,
    False)`` calls."""
    if plan["plane_lanes"] != "window" or steps < 2:
        return (((True, True), steps),)
    runs = (((True, False), 1), ((False, False), steps - 2), ((False, True), 1))
    return tuple(run for run in runs if run[1])


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


#: the REQUEST half of a plan (module docstring): all ``resolve_stream_plan``
#: reads of what it is handed, so a resolved plan handed back in as a request
#: brings none of its old resolution along
REQUEST_KEYS = (
    "route", "m", "z_slabs", "grouping", "alias", "alias_forced",
    "overlap", "overlap_forced", "halo", "halo_forced", "edges", "edges_forced",
)


@dataclasses.dataclass(frozen=True, eq=False)
class ResolvedPlan(Mapping):
    """What ``resolve_stream_plan`` returns.  ``plan`` is the resolved plan,
    request and resolution in one new ``dict``: what a rung of the ladder
    carries, ``step._stream_plan`` shows and ``stream_span_args`` says, and
    what this object reads as (a ``Mapping`` over it).  Beside it, what the
    build needs and a dict cannot describe."""

    plan: Mapping
    stage_runs: tuple  # plane route: per stage ``[(pass kernel, reads, rings,
    # writes, renames, prerotated, tile_rows), ...]`` (``plan_plane_stages``); ()
    # elsewhere
    wrap_fills: tuple  # the y / z halo fills the plane passes make themselves
    # (``pass_wrap_fills``), the how of ``plan["pass_wrap_axes"]``
    exchange_route: str  # the domain's realize-resolved exchange route
    overlap_source: str  # who chose ``overlap`` / ``halo`` (``step.overlap`` /
    halo_source: str  # ``step.halo`` events)

    @property
    def period(self) -> int:
        """Steps after which the plane route's step loop has its carry back in
        its own buffers (``plan["steps_per_trip"]``; 1 off the plane route)."""
        return self.plan.get("steps_per_trip", 1)

    def __getitem__(self, key):
        return self.plan[key]

    def __iter__(self):
        return iter(self.plan)

    def __len__(self):
        return len(self.plan)


def resolve_stream_plan(dd, kernel, x_radius: int, request: Mapping, interpret: bool) -> ResolvedPlan:
    """Everything a stream step WILL do, decided from ``request`` alone before
    anything is built (module docstring): the builder, the ladder's
    prefilter, ``step._stream_plan`` and the ``domain.step`` span all read
    the result and none writes to it.  ``request`` is read, never written;
    nothing of an earlier resolution can ride along, because nothing but the
    request is looked at.  ``kernel`` is one callable or a step's stages;
    ``interpret`` picks the rotate the traces use (``trace_plane_kernel``).
    The one abstract trace a group of each stage's kernel is made here, once.
    Raises ``ValueError`` for a plane step that fits in no pass."""
    from stencil_tpu.ops.exchange import slab_wrap_axes, sum_accounts

    names = [h.name for h in dd._handles]
    raw = dd.local_spec().raw_size()
    stages = _as_stages(kernel)
    route = request["route"]
    if len(stages) > 1 and route != "plane":
        raise ValueError(
            f"a step of {len(stages)} stages runs the plane route (an exchange "
            f"before every stage); the plan says {route!r}"
        )
    # the z sweep of every in-step exchange runs the domain's realize-
    # resolved route (packed z-shell vs direct — ops/exchange.py), so stream
    # steps escape the 64×-amplified thin-z path exactly like exchange()
    exch_route = getattr(dd, "_exchange_route", "direct")
    plan = {key: request[key] for key in REQUEST_KEYS if key in request}
    # Pass outputs alias their inputs or not (_resolve_stream_alias;
    # domain.step's ``aliased``).  The wrap pass has no in-place form.
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
    plan["alias"] = _resolve_stream_alias(plan, len(names)) and route != "wrap"
    plan["overlap"], overlap_source = _resolve_stream_overlap(plan)
    # resolved AFTER overlap: the split schedule structurally excludes fused
    plan["halo"], halo_source = _resolve_stream_halo(dd, plan, exch_route)
    fused, split = plan["halo"] == "fused", plan["overlap"] == "split"
    # who rides the step's exchange and whom its passes write (domain.step's
    # ``exchanged`` / ``written``): on the plane route what each stage's
    # kernel reads off-centre and returns, every one wherever the rules do
    # not hold (trace_plane_kernel says where and why); the wrap route
    # exchanges none.  The sweeps the plane passes make themselves in VMEM
    # (``wrapped``) and the writes that became renames (``renamed``): on the
    # plane route's default schedule only, where the passes run in place --
    # fused side buffers and split exterior bands keep the exchange they have
    plan.update(writers=tuple(names), pass_wrap_axes="", renamed=())
    stage_runs, wrap_fills = (), ()
    if route == "plane":
        default = not fused and not split
        if default:
            plan["pass_wrap_axes"], wrap_fills = pass_wrap_fills(dd, exch_route)
        # the passes' working plane (domain.step's ``plane_window``): the
        # block's interior where the fills above are its whole self-wrap on
        # both axes and it is whole vector tiles, the same aligned corner
        # beside the neighbours' y halo rows where they are its z self-wrap
        # alone, the raw plane elsewhere -- read off the fills and the
        # block's static shape alone (plan_plane_stages has the last word: a
        # window that exists in strips only is "raw" for a light kernel)
        shell = dd._shell_radius
        plan["plane_window"] = plane_window_form(
            wrap_fills, shell.lo(), shell.hi(), (raw.y, raw.z),
            [dd.field_dtype(h) for h in dd._handles],
        )
        # ... and the rows of it their kernel is evaluated over at a time
        # (domain.step's ``plane_strip``; 0 = the plane whole): what the plane
        # allows, and the kernels' own weight decides (plan_plane_stages)
        strip = plane_strip_rows(
            plan["plane_window"], (raw.y - shell.lo().y - shell.hi().y,
                                   raw.z - shell.lo().z - shell.hi().z),
            [dd.field_dtype(h) for h in dd._handles], x_radius,
        )
        keys, stage_runs = plan_plane_stages(
            dd, stages, x_radius, plan, interpret, fused,
            rename=default and _plan_passes_in_place(plan), window=plan["plane_window"],
            strip=strip,
        )
        plan.update(keys)
        # ... and the lane tiles of a plane they move between a dispatch's two
        # edges (domain.step's ``plane_lanes``)
        plan["plane_lanes"] = plane_lanes_form(plan)
    else:
        plan["halo_readers"] = () if route == "wrap" else tuple(names)
        # what the kernel reads off-centre, as the plane route's planner
        # learns it: one abstract trace a group, which decides nothing here
        # -- domain.step's ``offcentre`` / ``diagonal`` / ``read_sides`` say it
        # beside what the route serves
        plane = jax.ShapeDtypeStruct((raw.y, raw.z), jnp.float32)
        plan["footprint"] = footprint_counts([
            trace_plane_kernel(
                stages[0], [names[q] for q in g], [plane] * len(g), x_radius, dd._size,
                interpret,
            )
            for g in _stream_groups(plan, len(names))
        ])
    if route == "wrap":
        # the wrap pass writes fresh results: two macros a trip bring the
        # loop's carry home (macro_loop; domain.step's ``macros_per_trip``)
        plan["macros_per_trip"] = macros_per_trip(False)
        # ... and where a dispatch's two edges live (domain.step's ``edges``):
        # in the first and the last pass where the raw blocks' planes are
        # theirs to move, else in XLA's cut and write-back -- as after a
        # compile reject of the edge forms (the ladder's step down at the
        # same depth)
        if not plan.get("edges_forced"):
            plan["edges"] = wrap_edge_form(dd, plan)
    # what the step's exchanges send to ANOTHER shard, hop by hop, and pack
    # (ops/exchange.py ``exchange_account``, i.e. ``_sweep_kind``: the message
    # plan that is run): ``domain.run_step`` counts the wires from it and
    # ``domain.step`` says ``wired`` / ``wire_bytes`` / ``joint`` of it, a raw step
    stage_wires = _stage_wires(dd, plan, (raw.x, raw.y, raw.z), exch_route)
    plan["wire_account"] = sum_accounts(
        (st for st in stage_wires if st is not None),
        every=plan["m"] if route == "wavefront" else 1,
    )
    plan.update(plan["wire_account"].span_args())  # wired, wire_bytes, joint
    if route == "plane" and not fused:
        # ... the bytes stage by stage (``wire_bytes_by_stage``), and the
        # pairs of wired axes a kernel reads DIAGONALLY across
        # (``wired_edges``): that edge halo is the diagonal neighbour's, and
        # reaches the shard over two wires in turn, the later sweep carrying
        # what the earlier one received.  No other schedule says.
        plan["wire_bytes_by_stage"] = tuple(
            sum(st.hops.values()) if st else 0 for st in stage_wires
        )
        plan["wired_edges"] = tuple(
            pair for pair in plan["edge_reads"] if all(ax in plan["wired"] for ax in pair)
        )
    if route == "wavefront" and plan["z_slabs"]:
        # where the pass patches its z halo, read off the working plane's
        # shape as the kernel's own helper reads it (patch_z_halo; domain.
        # step's ``z_halo_patch``): "tile" on the lane-padded plane -- which
        # the pass makes in VMEM from the raw block where ``Zr`` is not whole
        # lane tiles (``lane_pad``), so the step carries the domain's own
        # blocks and pads or cuts nothing
        plan["z_halo_patch"] = z_halo_patch_form(lane_pad_width(raw.z), dd._shell_radius.lo().x)
        plan["lane_pad"] = "vmem" if raw.z % 128 else "none"
        # ... and the axes on which a macro's slab extension is the self-wrap,
        # nothing sent to oneself (domain.step's ``slab_wrap``): a function of
        # the mesh, the dtypes and the backend, as ``wrapped`` is
        plan["slab_wrap"] = slab_wrap_axes(
            tuple(dd.mesh.shape[a] for a in MESH_AXES), raw.x, raw.y, dd._shell_radius.lo().x,
            [dd.field_dtype(h) for h in dd._handles],
        )
    if route == "plane":
        # the steps one trip of the step loop runs (domain.step's
        # ``steps_per_trip``, the build's ``ResolvedPlan.period``): the period
        # of the permutation a step's renames make of the blocks -- acoustic's
        # one swap 2, the MHD step's three swaps of each of eight pairs (an odd
        # count) 2, elastic's none 1
        plan["steps_per_trip"] = _carry_period(names, plan["stages"])
    return ResolvedPlan(plan, stage_runs, wrap_fills, exch_route, overlap_source, halo_source)


def _stage_wires(dd, plan: Mapping, raw_spatial, exch_route: str) -> list:
    """Per exchange of ONE unit of a resolved plan -- a stage of a plane step,
    a wavefront macro -- its account (``exchange_account``), None for a stage
    that exchanges nothing; ``[]`` on the wrap route.  Each entry mirrors the
    call the route's builder makes (``ops/stream.py``)."""
    from stencil_tpu.ops.exchange import WireAccount, exchange_account, sum_hops, z_slab_hops

    mesh_shape = tuple(dd.mesh.shape[a] for a in MESH_AXES)
    dtype_of = {h.name: dd.field_dtype(h) for h in dd._handles}
    everyone = list(dtype_of.values())
    shell = dd._shell_radius
    if plan["route"] == "wrap":
        return []
    if plan["halo"] == "fused":  # fused_shell_exchange: every block, all three axes
        n = len(plan["stages"]) if plan["route"] == "plane" else 1
        return [exchange_account(mesh_shape, shell, raw_spatial, everyone, route=exch_route)] * n
    if plan["route"] == "plane":
        return [
            exchange_account(
                mesh_shape, shell, raw_spatial, [dtype_of[name] for name in st["readers"]],
                valid_last=dd._valid_last, route=exch_route, axes=swept_axes(plan),
            ) if st["readers"] else None
            for st in plan["stages"]
        ]
    if plan["z_slabs"]:
        # x and y in the array on the direct route, z as slab buffers that the
        # y and x neighbours extend (permute_and_extend_z_slabs)
        swept = exchange_account(mesh_shape, shell, raw_spatial, everyone, axes=(0, 1))
        return [WireAccount(1, sum_hops(
            swept.hops,
            z_slab_hops(
                mesh_shape, raw_spatial[0], raw_spatial[1], shell.lo().x,
                [jnp.dtype(dt).itemsize for dt in everyone],
            ),
        ), joint=swept.joint)]
    return [exchange_account(
        mesh_shape, shell, raw_spatial, everyone, valid_last=dd._valid_last, route=exch_route,
    )]


def swept_axes(plan: Mapping) -> Tuple[int, ...]:
    """The axes a resolved plane plan's exchange still sweeps: all but those
    its passes wrap themselves."""
    return tuple(a for a in range(3) if MESH_AXES[a] not in plan["pass_wrap_axes"])
