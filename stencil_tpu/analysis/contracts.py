"""The program-contract catalog — every traced-program invariant this tree
machine-checks (docs/static-analysis.md "Program contracts").

Each contract generalizes a property previously guarded by a one-off test
walker or a source-level heuristic the tracer can defeat:

* ``overlap-independence`` — the split-step schedule's latency-hiding
  property IS a dataflow property (arxiv 2401.16677 makes the same point:
  overlap is what the compiler's dependency graph permits).  Replaces the
  hand-rolled taint pass ``tests/test_overlap_structural.py`` carried.
* ``exchange-structure``  — the fused exchange (packer.cuh:52-69's
  collapse): one FACE message a direction (≤6) plus at most one corner relay
  a direction of a jointly swept axis (≤2) must survive every route and any
  quantity count.
* ``sliver-dus``          — the thin-z relayout trap (PERF_NOTES "Thin
  z-region access") checked on the traced program, where the source rule
  (``lint/rules/layout_traps.py``) cannot see through helpers.
* ``fused-halo``          — the fused unpack→blend mode's headline claim
  (``halo="fused"``, ops/stream.py): the big array never sees a halo
  write — no partial-window update on a raw-shaped array, no blend/unpack
  kernel consuming a (big array, thin slab) pair; the shell data flows
  message → VMEM patch → pass output only.
* ``redistribute-bounded`` — the elastic-capacity collective's headline
  claim (``parallel/redistribute.py``, per arxiv 2112.01075): the traced
  redistribution program moves shard-sized staging buffers through
  permutation rounds — every intermediate inside the shard-mapped body
  stays under a constant multiple of the shard size, and no gathering
  collective (all_gather / all_to_all) appears anywhere.  A full-gather
  "redistribution" would pass every numeric test and OOM only at scale.
* ``numerics-bounded``    — the numerics observatory's headline claim
  (``telemetry/numerics.py``): the fused field-stats program reduces
  on-device (psum/pmin/pmax inside the shard_map) and ships
  O(#quantities) scalars — scalar-only outputs under the per-quantity
  budget, no gathering collective anywhere.  A per-quantity host gather
  would pass every numeric test and silently reintroduce the PR-1
  sentinel's device→host cost.
* ``donation-soundness``  — the jaxpr-level twin of the ``donated-reuse``
  lint rule: a donated/aliased buffer must be dead after the call.
* ``accum-dtype``         — every contraction in a kernel jaxpr pins an
  f32+ accumulator (the bf16-storage/f32-accumulate contract).
* ``vmem-budget``         — the analytic footprint recomputed from the
  traced shapes must fit the chip budget (``analysis/vmem.py``; the same
  verdict ``tune/space.py`` and the stream ladder consult statically).
* ``span-registry``       — every dotted named-scope label in the traced
  program is a registered span or kernel name (``telemetry/names.py
  ALL_SPANS`` / ``ALL_KERNELS``): drift
  the source-level ``span-name`` rule cannot see through f-strings or
  indirection falls out of device-time attribution silently.
* ``kernel-name``         — every pallas call carries a registered kernel
  name (``telemetry/names.py ALL_KERNELS``): its identity in a device trace.
* ``exchange-scope``      — every ppermute and every self-wrap kernel
  (``exchange.<axis>.wrap``) sits under an ``exchange.<axis>`` sweep scope,
  a bare exchange program is >= 90% scoped, a program with neither a
  ppermute nor a wrap kernel carries no such scope: a trace tells exchange
  from glue by name.
* ``kernel-race``         — the kernel verifier's deliberate descent
  (``analysis/kernels.py``): no two PARALLEL grid points of any pallas
  call write the same output block unless the writes are provably
  identical; sequential grids keep their last-write-wins replays.
* ``kernel-coverage``     — every output block of every pallas call is
  written by some grid point or carried in via a shape-and-dtype-
  consistent ``input_output_aliases`` entry (the donation-soundness
  analog one level down); boundary shells up to the plan's depth margin
  are the one sanctioned gap.
* ``inplace-order``       — for every output a pallas call aliases onto
  an input, on a sequential grid, no input block is fetched after the
  output block over the same cells was flushed: writes trail reads, or in
  place the kernel reads its own result (``analysis/kernels.py``).
* ``tiling-legal``        — the Mosaic tiling-legality model over the
  traced kernels: no rotate on unaligned or non-32-bit planes, no
  blocked windows at sub-granule offsets, no int64 index arithmetic —
  the static form of PR 6's COMPILE_REJECT runtime rejections
  (``analysis/kernels.py``; ``check_kernel_legal`` is the same verdict
  pre-build for the tuner and the stream ladder).
"""

from __future__ import annotations

import math
from typing import List

from stencil_tpu.analysis.framework import (
    Contract,
    Finding,
    ProgramArtifact,
    register,
)

#: a z-window update narrower than this is certainly a sliver — halo and
#: band writes are radius-sized (≤ ~6 cells); whole-interior write-backs
#: are hundreds of lanes wide.  Below the f32 sublane extent of the (8,128)
#: tile the DUS is guaranteed partial-tile relayout bait.
SLIVER_Z_LIMIT = 8

#: the fused-exchange bound: ≤ 2 FACE ppermutes per axis sweep, ≤ 6 total,
#: regardless of quantity count (SURVEY.md §7 "26-neighbor exchange") ...
MAX_PERMUTES = 6
#: ... and, where two wired axes sweep jointly (ops/exchange.py
#: ``_sweep_groups``), one corner RELAY behind each face of the second axis
MAX_RELAYS = 2


def _exchanging(art: ProgramArtifact) -> bool:
    return art.n_devices > 1


@register
class OverlapIndependence(Contract):
    name = "overlap-independence"
    why = (
        "under overlap=split the step.overlap.interior pallas call must be "
        "transitively ppermute-free (XLA cannot serialize what the dataflow "
        "does not order); under off no pallas call may claim an overlap scope"
    )

    def applies_to(self, art: ProgramArtifact) -> bool:
        return art.kind in ("step", "fn") and "overlap" in art.axes

    def check(self, art: ProgramArtifact) -> List[Finding]:
        from stencil_tpu.analysis import jaxpr as jx
        from stencil_tpu.telemetry import names as tm

        rows = jx.pallas_taint_rows(art.closed)
        out: List[Finding] = []
        split = art.axes.get("overlap") == "split"
        if not split:
            # the off schedule must not masquerade as split: no pallas call
            # inside an overlap scope, and (on the direct exchanging route,
            # where no pre-exchange pack kernels exist) every pass consumes
            # the exchanged blocks — the historic sanity inverse
            for ns, _ in rows:
                if tm.SPAN_OVERLAP_INTERIOR in ns or tm.SPAN_OVERLAP_EXTERIOR in ns:
                    out.append(
                        art.finding(
                            self.name,
                            f"overlap=off program carries a pallas call in an "
                            f"overlap scope: {ns!r}",
                        )
                    )
            if (
                _exchanging(art)
                and art.axes.get("exchange_route", "direct") == "direct"
                and not (art.plan or {}).get("z_slabs")
            ):
                if not rows:
                    out.append(
                        art.finding(
                            self.name,
                            "exchanging off program traced no jaxpr holding "
                            "both ppermutes and pallas calls",
                        )
                    )
                for ns, tainted in rows:
                    if not tainted:
                        out.append(
                            art.finding(
                                self.name,
                                "off-schedule pallas call does NOT consume "
                                f"the exchanged blocks (scope {ns!r}) — the "
                                "taint pass is measuring an artifact",
                            )
                        )
            return out
        if not _exchanging(art):
            return out  # nothing to overlap on one device
        if not rows:
            return [
                art.finding(
                    self.name,
                    "split program traced no jaxpr holding both ppermutes "
                    "and pallas calls — the schedule is not what it claims",
                )
            ]
        clean_interior = [
            ns for ns, t in rows if not t and tm.SPAN_OVERLAP_INTERIOR in ns
        ]
        if not clean_interior:
            out.append(
                art.finding(
                    self.name,
                    "no ppermute-free pallas call inside the "
                    f"{tm.SPAN_OVERLAP_INTERIOR!r} scope: the interior pass "
                    "depends on the exchange it is meant to hide; rows="
                    f"{[(ns, t) for ns, t in rows]}",
                )
            )
        exterior = [(ns, t) for ns, t in rows if tm.SPAN_OVERLAP_EXTERIOR in ns]
        if not exterior:
            out.append(
                art.finding(
                    self.name,
                    f"split program has no {tm.SPAN_OVERLAP_EXTERIOR!r} band "
                    "passes — nothing recomputes the boundary",
                )
            )
        for ns, t in exterior:
            if not t:
                out.append(
                    art.finding(
                        self.name,
                        f"exterior band pass at {ns!r} does not consume the "
                        "exchanged halos — the boundary fix-up reads stale "
                        "data",
                    )
                )
        if art.axes.get("exchange_route", "direct") == "direct":
            # the strong historic pin: with no pre-exchange pack kernels in
            # the program, EVERY pallas call outside the interior scope must
            # consume exchanged data
            for ns, t in rows:
                if not t and tm.SPAN_OVERLAP_INTERIOR not in ns:
                    out.append(
                        art.finding(
                            self.name,
                            f"pallas call outside the interior scope is "
                            f"ppermute-free ({ns!r}) — more of the program "
                            "than the declared interior dodges the exchange",
                        )
                    )
        return out


@register
class ExchangeStructure(Contract):
    name = "exchange-structure"
    why = (
        "every exchange route traces to one fused FACE message per direction "
        "(<=6 ppermutes) plus at most one smaller corner relay per direction "
        "of ONE jointly swept axis (<=2), independent of the quantity count "
        "(the reference's packed-buffer collapse, packer.cuh:52-69)"
    )

    def applies_to(self, art: ProgramArtifact) -> bool:
        if not _exchanging(art):
            return False
        if art.kind == "exchange":
            return True
        # the z-slab wavefront interleaves per-level slab permutes with the
        # pass BY DESIGN (ROADMAP "finish the packed-exchange story") — its
        # generic-exchange structure is pinned via the exchange artifacts
        return art.kind in ("step", "fn") and not (art.plan or {}).get("z_slabs")

    def check(self, art: ProgramArtifact) -> List[Finding]:
        from stencil_tpu.analysis import jaxpr as jx

        def is_permute(e) -> bool:
            return e.primitive.name == "ppermute"

        def nbytes(e) -> int:
            return sum(
                math.prod(v.aval.shape) * v.aval.dtype.itemsize for v in e.invars
            )

        out: List[Finding] = []
        saw_any = False
        for j in jx.walk(getattr(art.closed, "jaxpr", art.closed)):
            if not any(is_permute(e) for e in j.eqns):
                continue
            saw_any = True
            # a permute whose operand derives from an earlier permute's result
            # is ``tainted``: what a relay of RECEIVED corners must be
            by_scope: dict = {}
            for row in jx.taint_rows(j, source=is_permute, watch=is_permute):
                by_scope.setdefault(row.scopes, []).append(row)
            faces, relayed = 0, []
            for ns, rows in by_scope.items():
                rows = sorted(rows, key=lambda r: -nbytes(r.eqn))
                relay = rows[1] if len(rows) == 2 else None
                if len(rows) > 2 or (
                    relay is not None
                    and not (relay.tainted and nbytes(relay.eqn) < nbytes(rows[0].eqn))
                ):
                    out.append(
                        art.finding(
                            self.name,
                            f"{len(rows)} ppermutes under one direction scope "
                            f"({ns!r}) and the extra is no corner relay (a "
                            "smaller message of cells another permute "
                            "received): the per-quantity messages did not "
                            "fuse into one buffer per direction",
                        )
                    )
                    faces += len(rows)
                    continue
                faces += 1
                if relay is not None:
                    relayed.append(ns)
            if faces > MAX_PERMUTES:
                out.append(
                    art.finding(
                        self.name,
                        f"one traced exchange issues {faces} face ppermutes "
                        f"(> {MAX_PERMUTES}): the per-direction fusion is "
                        "broken",
                    )
                )
            # ``.../exchange.y.low`` -> ``.../exchange.y``: the relays of one
            # exchange all complete the halo of ONE axis, the pair's second
            if len(relayed) > MAX_RELAYS or len({ns.rpartition(".")[0] for ns in relayed}) > 1:
                out.append(
                    art.finding(
                        self.name,
                        f"corner relays under {sorted(relayed)}: more than one "
                        f"a direction of ONE jointly swept axis (<= {MAX_RELAYS})",
                    )
                )
        if art.kind == "exchange" and not saw_any:
            out.append(
                art.finding(
                    self.name,
                    "exchange program traced no ppermute at all on a "
                    "multi-device mesh",
                )
            )
        return out


@register
class SliverDus(Contract):
    name = "sliver-dus"
    why = (
        "no dynamic-update-slice on a big array with a z-extent below the "
        "(8,128) tile granule — the thin-z relayout trap, checked where the "
        "source rule cannot see through helpers (PERF_NOTES probe6)"
    )

    def applies_to(self, art: ProgramArtifact) -> bool:
        # the redistribution schedule writes staging windows whose extents
        # are whatever the mesh intersection yields — a one-shot capacity
        # transition, not a per-step hot path; its own contract
        # (redistribute-bounded) checks what actually matters there.  The
        # serve programs wrap whatever step each TENANT built (the
        # baseline XLA route included, whose shell scatter this trap is a
        # known property of) — the per-engine step programs already hold
        # this pin on the streamed hot paths, and batch-isolation checks
        # what packing itself must guarantee
        return art.kind not in ("redistribute", "serve")

    def check(self, art: ProgramArtifact) -> List[Finding]:
        from stencil_tpu.analysis import jaxpr as jx

        out: List[Finding] = []
        for e in jx.iter_eqns(art.closed):  # pallas bodies opaque: VMEM-
            # ref updates are tile-local, not big-array relayout bait
            if e.primitive.name == "dynamic_update_slice":
                operand, update = e.invars[0].aval, e.invars[1].aval
            elif e.primitive.name == "scatter":
                # ``.at[static slices].set`` lowers to scatter on some
                # toolchains — same window write, same relayout bait
                operand, update = e.invars[0].aval, e.invars[-1].aval
                if len(update.shape) != len(operand.shape):
                    continue  # gather-style updates, not a window write
            else:
                continue
            if len(operand.shape) < 3:
                continue
            if min(operand.shape[-3:]) < SLIVER_Z_LIMIT:
                # a narrow STAGING buffer (the z-slab route's (x, 2m, y)
                # slab extenders), not the big domain array — those sites
                # carry their own reasoned source-level suppressions
                continue
            oz, uz = operand.shape[-1], update.shape[-1]
            if uz < oz and uz < SLIVER_Z_LIMIT:
                out.append(
                    art.finding(
                        self.name,
                        f"{e.primitive.name} writes a {uz}-deep z window "
                        f"of a {tuple(operand.shape)} array (scope "
                        f"{jx.name_stack_str(e)!r}) — relayout bait on the "
                        "(8,128) tiling; route it through the blend kernels "
                        "(ops/halo_blend.py) or the packed exchange",
                    )
                )
        return out


@register
class FusedHalo(Contract):
    name = "fused-halo"
    why = (
        "under halo=fused the big array must never see a halo write: no "
        "partial-window DUS/scatter on a raw-shaped array and no blend/"
        "unpack kernel pairing a raw-shaped aliased block with a thin slab "
        "— the packed messages land in the pass's VMEM planes only"
    )

    def applies_to(self, art: ProgramArtifact) -> bool:
        return art.kind in ("step", "fn") and art.axes.get("halo") == "fused"

    def check(self, art: ProgramArtifact) -> List[Finding]:
        from stencil_tpu.analysis import jaxpr as jx

        raw = None
        if art.dd is not None:
            r = art.dd.local_spec().raw_size()
            raw = (r.x, r.y, r.z)

        def is_raw(aval) -> bool:
            shape = tuple(getattr(aval, "shape", ()))
            if len(shape) < 3:
                return False
            if raw is not None:
                return shape[-3:] == raw
            return True  # fixtures without a domain: any big 3-D array

        out: List[Finding] = []
        for e in jx.iter_eqns(art.closed):
            if e.primitive.name in ("dynamic_update_slice", "scatter"):
                operand = e.invars[0].aval
                update = (
                    e.invars[1].aval
                    if e.primitive.name == "dynamic_update_slice"
                    else e.invars[-1].aval
                )
                if len(getattr(update, "shape", ())) != len(
                    getattr(operand, "shape", ())
                ):
                    continue  # gather-style scatter, not a window write
                if is_raw(operand) and tuple(update.shape) != tuple(operand.shape):
                    out.append(
                        art.finding(
                            self.name,
                            f"{e.primitive.name} writes a partial window of "
                            f"a raw-shaped {tuple(operand.shape)} array "
                            f"(scope {jx.name_stack_str(e)!r}) — the fused "
                            "program must not write halo data into the big "
                            "array",
                        )
                    )
            elif e.primitive.name == "pallas_call":
                # a blend/unpack kernel: a SMALL call (block + slab [+ a
                # scalar-prefetch operand]) pairing one raw-shaped input
                # with a strictly-smaller 3-D slab.  The fused passes carry
                # the origin ref plus per-quantity raws AND three shell
                # side-buffers, so they never match this signature.
                avals = [getattr(v, "aval", None) for v in e.invars]
                three_d = [
                    a for a in avals if len(getattr(a, "shape", ())) == 3
                ]
                if len(avals) > 3 or not three_d:
                    continue
                raws_in = [a for a in three_d if is_raw(a)]
                slabs = [
                    a
                    for a in three_d
                    for b in raws_in
                    if a is not b
                    and all(x <= y for x, y in zip(a.shape, b.shape))
                    and any(x < y for x, y in zip(a.shape, b.shape))
                ]
                if raws_in and slabs:
                    out.append(
                        art.finding(
                            self.name,
                            "blend/unpack-shaped pallas call (a raw-shaped "
                            "block paired with a thin slab, scope "
                            f"{jx.name_stack_str(e)!r}) — the fused program "
                            "must land shells in the pass's VMEM planes, "
                            "never back in the big array",
                        )
                    )
        return out


#: collectives that materialize gathered state — the exact failure mode
#: the bounded redistribution schedule exists to avoid
_GATHERING_PRIMITIVES = frozenset(
    {"all_gather", "all_gather_invariant", "all_to_all"}
)


def _aval_nbytes(aval) -> int:
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0
    n = 1
    for s in shape:
        n *= int(s)
    return n * dtype.itemsize


@register
class RedistributeBounded(Contract):
    name = "redistribute-bounded"
    why = (
        "the traced redistribution program moves bounded staging buffers "
        "through ppermute rounds: every intermediate inside the "
        "shard-mapped body stays under meta['bound_bytes'] (a constant "
        "multiple of the shard size) and no gathering collective appears — "
        "a full-gather reshard passes every numeric test and OOMs at scale "
        "(parallel/redistribute.py, arxiv 2112.01075)"
    )

    def applies_to(self, art: ProgramArtifact) -> bool:
        return art.kind == "redistribute"

    def check(self, art: ProgramArtifact) -> List[Finding]:
        from stencil_tpu.analysis import jaxpr as jx
        from stencil_tpu.parallel.redistribute import STAGING_BOUND_FACTOR

        out: List[Finding] = []
        bound = art.meta.get("bound_bytes")
        if not isinstance(bound, int) or bound <= 0:
            return [
                art.finding(
                    self.name,
                    "redistribute artifact carries no meta['bound_bytes'] — "
                    "the staging bound cannot be verified",
                )
            ]
        for e in jx.iter_eqns(art.closed):
            if e.primitive.name in _GATHERING_PRIMITIVES:
                out.append(
                    art.finding(
                        self.name,
                        f"{e.primitive.name} (scope "
                        f"{jx.name_stack_str(e)!r}) — a gathering collective "
                        "in a redistribution program materializes more than "
                        "the bounded staging schedule allows",
                    )
                )
        bodies = [
            sub
            for e in jx.iter_eqns(art.closed)
            if e.primitive.name == "shard_map"
            for sub in jx.eqn_subjaxprs(e)
        ]
        if not bodies:
            return out + [
                art.finding(
                    self.name,
                    "redistribution program traced no shard_map body — the "
                    "per-chip memory bound has nothing to hold against",
                )
            ]
        saw_permute = False
        for body in bodies:
            for j in jx.walk(body):
                for e in j.eqns:
                    if e.primitive.name == "ppermute":
                        saw_permute = True
                    for v in e.outvars:
                        nb = _aval_nbytes(getattr(v, "aval", None))
                        if nb > bound:
                            out.append(
                                art.finding(
                                    self.name,
                                    f"{e.primitive.name} (scope "
                                    f"{jx.name_stack_str(e)!r}) materializes "
                                    f"a {nb}-byte intermediate inside the "
                                    f"shard-mapped body (> the "
                                    f"{bound}-byte staging bound, "
                                    f"{STAGING_BOUND_FACTOR}x the shard) — "
                                    "the schedule is not memory-bounded",
                                )
                            )
        if art.meta.get("union_ranks", 2) > 1 and not saw_permute:
            out.append(
                art.finding(
                    self.name,
                    "multi-rank redistribution program issues no ppermute — "
                    "nothing actually moves through the collective schedule",
                )
            )
        return out


#: in-program reducing collectives — what the numerics stats program must
#: use instead of gathering (psum spells itself psum2 on current jax)
_REDUCING_PRIMITIVES = frozenset({"psum", "psum2", "pmin", "pmax"})


@register
class NumericsBounded(Contract):
    name = "numerics-bounded"
    why = (
        "the fused numerics stats program reduces on-device and ships "
        "O(#quantities) SCALARS to the host: every traced output is a "
        "0-d scalar, the output count is bounded by the per-quantity "
        "scalar budget, no gathering collective appears anywhere, and a "
        "multi-device program really reduces with psum/pmin/pmax — a "
        "per-quantity host gather would pass every numeric test and "
        "silently reintroduce the PR-1 sentinel's cost "
        "(telemetry/numerics.py, arxiv 2401.16677)"
    )

    def applies_to(self, art: ProgramArtifact) -> bool:
        return art.kind == "numerics"

    def check(self, art: ProgramArtifact) -> List[Finding]:
        from stencil_tpu.analysis import jaxpr as jx
        from stencil_tpu.telemetry.numerics import SCALARS_PER_QUANTITY

        out: List[Finding] = []
        nq = art.meta.get("n_quantities")
        if not isinstance(nq, int) or nq <= 0:
            return [
                art.finding(
                    self.name,
                    "numerics artifact carries no meta['n_quantities'] — "
                    "the scalar-output bound cannot be verified",
                )
            ]
        jaxpr = getattr(art.closed, "jaxpr", art.closed)
        outvars = list(jaxpr.outvars)
        if len(outvars) > SCALARS_PER_QUANTITY * nq:
            out.append(
                art.finding(
                    self.name,
                    f"{len(outvars)} outputs for {nq} quantities (> the "
                    f"{SCALARS_PER_QUANTITY}/quantity scalar budget) — the "
                    "host transfer is no longer O(#quantities)",
                )
            )
        for v in outvars:
            shape = tuple(getattr(getattr(v, "aval", None), "shape", ()))
            if shape != ():
                out.append(
                    art.finding(
                        self.name,
                        f"output with shape {shape} — the numerics program "
                        "must ship scalars, never arrays (a shaped output "
                        "is a gather in disguise)",
                    )
                )
        saw_reduce = False
        for e in jx.iter_eqns(art.closed):
            if e.primitive.name in _GATHERING_PRIMITIVES:
                out.append(
                    art.finding(
                        self.name,
                        f"{e.primitive.name} (scope "
                        f"{jx.name_stack_str(e)!r}) — a gathering "
                        "collective in the stats program materializes "
                        "whole fields; reduce with psum/pmin/pmax instead",
                    )
                )
            if e.primitive.name in _REDUCING_PRIMITIVES:
                saw_reduce = True
        if art.n_devices > 1 and not saw_reduce:
            out.append(
                art.finding(
                    self.name,
                    "multi-device numerics program issues no reducing "
                    "collective (psum/pmin/pmax) — per-shard stats were "
                    "never combined, so the scalars describe one shard, "
                    "not the domain",
                )
            )
        return out


#: named-axis collectives whose axis names the batch-isolation contract
#: inspects — a collective naming the BATCH axis (vmap's axis, not a mesh
#: axis) mixes tenants that share a batched dispatch
_NAMED_COLLECTIVES = frozenset(
    {
        "ppermute",
        "psum",
        "psum2",
        "pmin",
        "pmax",
        "pbroadcast",
        "all_gather",
        "all_gather_invariant",
        "all_to_all",
    }
)


def _collective_axes(eqn) -> list:
    """Every axis a collective eqn communicates over (ppermute spells them
    ``axis_name``, psum and friends ``axes``).  Mesh-axis collectives carry
    the axis NAME (a string); a collective traced through ``vmap`` carries
    the POSITIONAL batch axis as an int — both are returned, because in a
    batched serving program an int axis IS the batch axis."""
    axes = []
    for key in ("axis_name", "axes"):
        val = eqn.params.get(key)
        if val is None:
            continue
        if not isinstance(val, (tuple, list)):
            val = (val,)
        axes.extend(val)
    return axes


@register
class BatchIsolation(Contract):
    name = "batch-isolation"
    why = (
        "a packed serving dispatch must not couple tenants: in a BATCHED "
        "program no collective communicates over the batch axis (only the "
        "mesh axes) and every output keeps its leading batch dim; in a "
        "SUB-SLICE program no tenant's outputs are dataflow-reachable "
        "from another tenant's inputs and every shard_map stays confined "
        "to exactly one tenant's device set; neither form may gather — "
        "cross-tenant coupling would pass every single-tenant test and "
        "corrupt a neighbor only under production packing (serve/pack.py)"
    )

    def applies_to(self, art: ProgramArtifact) -> bool:
        return art.kind == "serve"

    def check(self, art: ProgramArtifact) -> List[Finding]:
        from stencil_tpu.analysis import jaxpr as jx

        out: List[Finding] = []
        mode = art.meta.get("mode")
        if mode not in ("batched", "subslice"):
            return [
                art.finding(
                    self.name,
                    f"serve artifact carries meta['mode']={mode!r} — the "
                    "isolation claims cannot be verified",
                )
            ]
        for e in jx.iter_eqns(art.closed):
            if e.primitive.name in _GATHERING_PRIMITIVES:
                out.append(
                    art.finding(
                        self.name,
                        f"{e.primitive.name} (scope "
                        f"{jx.name_stack_str(e)!r}) — a gathering "
                        "collective in a packed serving program "
                        "materializes state across tenants",
                    )
                )
        if mode == "batched":
            out.extend(self._check_batched(art, jx))
        else:
            out.extend(self._check_subslice(art, jx))
        return out

    def _check_batched(self, art: ProgramArtifact, jx) -> List[Finding]:
        out: List[Finding] = []
        batch = art.meta.get("batch")
        mesh_axes = set(art.meta.get("mesh_axes") or ())
        if not isinstance(batch, int) or batch < 2 or not mesh_axes:
            return [
                art.finding(
                    self.name,
                    "batched artifact needs meta['batch'] >= 2 and "
                    "meta['mesh_axes'] — the batch-axis claims cannot be "
                    "verified",
                )
            ]
        for e in jx.iter_eqns(art.closed):
            if e.primitive.name not in _NAMED_COLLECTIVES:
                continue
            stray = [
                n for n in _collective_axes(e)
                if not (isinstance(n, str) and n in mesh_axes)
            ]
            if stray:
                out.append(
                    art.finding(
                        self.name,
                        f"{e.primitive.name} communicates over non-mesh "
                        f"axis(es) {stray} (scope "
                        f"{jx.name_stack_str(e)!r}) — a collective over "
                        "the batch axis mixes tenants that share one "
                        "batched dispatch",
                    )
                )
        jaxpr = getattr(art.closed, "jaxpr", art.closed)
        for v in jaxpr.outvars:
            shape = tuple(getattr(getattr(v, "aval", None), "shape", ()))
            if not shape or shape[0] != batch:
                out.append(
                    art.finding(
                        self.name,
                        f"output with shape {shape} does not keep the "
                        f"leading batch dim {batch} — per-tenant slices "
                        "cannot be separated back out of the dispatch",
                    )
                )
        return out

    def _check_subslice(self, art: ProgramArtifact, jx) -> List[Finding]:
        out: List[Finding] = []
        in_groups = art.meta.get("input_groups")
        out_groups = art.meta.get("output_groups")
        device_sets = [
            frozenset(s) for s in (art.meta.get("device_sets") or [])
        ]
        jaxpr = getattr(art.closed, "jaxpr", art.closed)
        if (
            not in_groups
            or not out_groups
            or len(device_sets) != len(in_groups)
            or sum(in_groups) != len(jaxpr.invars)
            or sum(out_groups) != len(jaxpr.outvars)
        ):
            return [
                art.finding(
                    self.name,
                    "subslice artifact needs matching meta['input_groups']/"
                    "['output_groups']/['device_sets'] — the per-tenant "
                    "isolation claims cannot be verified",
                )
            ]
        # slice the flat invar/outvar lists back into per-tenant groups
        # (the builder records the pytree flatten order)
        in_of, out_of, i, o = [], [], 0, 0
        for n_in, n_out in zip(in_groups, out_groups):
            in_of.append(list(jaxpr.invars[i : i + n_in]))
            out_of.append(list(jaxpr.outvars[o : o + n_out]))
            i += n_in
            o += n_out
        # per-tenant forward taint at the top level: seed every OTHER
        # tenant's inputs, flow conservatively through the top-level eqns
        # (pjit boundaries — a traced sub-call mixes whatever it consumes),
        # and require this tenant's outputs stay untainted
        for t in range(len(in_groups)):
            tainted = set()
            for s, group in enumerate(in_of):
                if s != t:
                    tainted.update(id(v) for v in group)
            for e in jaxpr.eqns:
                if any(
                    id(v) in tainted
                    for v in e.invars
                    if not isinstance(v, jx.Literal)
                ):
                    tainted.update(id(v) for v in e.outvars)
            dirty = [v for v in out_of[t] if id(v) in tainted]
            if dirty:
                out.append(
                    art.finding(
                        self.name,
                        f"tenant {t}'s output(s) are dataflow-reachable "
                        f"from another tenant's inputs ({len(dirty)} of "
                        f"{len(out_of[t])} outputs tainted) — sub-slice "
                        "execution is not isolated",
                    )
                )
        # every shard_map must stay confined to exactly one tenant's
        # declared device set — an eqn spanning two sets is a collective
        # bridge between "disjoint" sub-slices
        for e in jx.iter_eqns(art.closed):
            if e.primitive.name != "shard_map":
                continue
            mesh = e.params.get("mesh")
            devs = getattr(mesh, "devices", None)
            if devs is None:
                continue
            ids = {int(d.id) for d in devs.flat}
            if not any(ids <= s for s in device_sets):
                out.append(
                    art.finding(
                        self.name,
                        f"shard_map over devices {sorted(ids)} (scope "
                        f"{jx.name_stack_str(e)!r}) is not confined to "
                        "any single tenant's declared device set "
                        f"{[sorted(s) for s in device_sets]} — its "
                        "collectives bridge sub-slices",
                    )
                )
        return out


@register
class DonationSoundness(Contract):
    name = "donation-soundness"
    why = (
        "every donated/aliased input in the traced program is dead after "
        "the consuming call or rebound — the jaxpr-level twin of the "
        "donated-reuse lint rule (SSA + anti-dependency scheduling make "
        "the remaining hazards exact per jaxpr)"
    )

    def check(self, art: ProgramArtifact) -> List[Finding]:
        from stencil_tpu.analysis import jaxpr as jx

        out: List[Finding] = []
        for j in jx.walk(getattr(art.closed, "jaxpr", art.closed)):
            for eqn, other, why in jx.donation_hazards(j):
                where = (
                    "the jaxpr outputs"
                    if other == "outvars"
                    else f"a later {other.primitive.name} eqn"
                )
                out.append(
                    art.finding(
                        self.name,
                        f"{eqn.primitive.name} (scope "
                        f"{jx.name_stack_str(eqn)!r}) vs {where}: {why}",
                    )
                )
        return out


@register
class AccumDtype(Contract):
    name = "accum-dtype"
    why = (
        "every dot_general in a kernel jaxpr carries an f32+ "
        "preferred_element_type — bf16 operands must never accumulate at "
        "bf16 (the f32-accumulate contract, docs/tuning.md)"
    )

    def check(self, art: ProgramArtifact) -> List[Finding]:
        import jax.numpy as jnp

        from stencil_tpu.analysis import jaxpr as jx

        out: List[Finding] = []
        # descend into pallas kernels: the contractions live INSIDE them
        for e in jx.iter_eqns(art.closed, opaque=()):
            if e.primitive.name != "dot_general":
                continue
            pref = e.params.get("preferred_element_type")
            ok = (
                pref is not None
                and jnp.issubdtype(pref, jnp.floating)
                and jnp.dtype(pref).itemsize >= 4
            )
            if not ok:
                out.append(
                    art.finding(
                        self.name,
                        f"dot_general (scope {jx.name_stack_str(e)!r}) "
                        f"carries preferred_element_type={pref!r} — the "
                        "accumulator must be an explicit >=32-bit float",
                    )
                )
        return out


@register
class VmemBudget(Contract):
    name = "vmem-budget"
    why = (
        "the analytic per-kernel VMEM footprint, recomputed from the traced "
        "shapes, fits the chip budget — the static form of the "
        "compile-and-catch VMEM_OOM prune (analysis/vmem.py)"
    )

    def applies_to(self, art: ProgramArtifact) -> bool:
        return art.plan is not None

    def check(self, art: ProgramArtifact) -> List[Finding]:
        from stencil_tpu.analysis import vmem

        reason = vmem.check_traced(art)
        if reason is not None:
            return [art.finding(self.name, reason)]
        return []


@register
class KernelRace(Contract):
    name = "kernel-race"
    why = (
        "no two grid points that differ in a declared-parallel grid dim "
        "may write the same output block of a pallas call unless the "
        "writes are provably identical — parallel dims leave the order "
        "unspecified, so an overlap is a silent value race on chip "
        "(sequential grids keep their deliberate last-write-wins replays; "
        "analysis/kernels.py)"
    )

    def applies_to(self, art: ProgramArtifact) -> bool:
        return art.closed is not None

    def check(self, art: ProgramArtifact) -> List[Finding]:
        from stencil_tpu.analysis import kernels

        return [
            art.finding(self.name, msg) for msg in kernels.check_races(art)
        ]


@register
class KernelCoverage(Contract):
    name = "kernel-coverage"
    why = (
        "every output block of every pallas call is written by some grid "
        "point or carried in via input_output_aliases — whose in/out "
        "shape-and-dtype consistency is checked too (the donation-"
        "soundness analog one level down); an unwritten block past the "
        "plan's shell margin ships uninitialized VMEM to HBM "
        "(analysis/kernels.py)"
    )

    def applies_to(self, art: ProgramArtifact) -> bool:
        return art.closed is not None

    def check(self, art: ProgramArtifact) -> List[Finding]:
        from stencil_tpu.analysis import kernels

        return [
            art.finding(self.name, msg)
            for msg in kernels.check_coverage(art)
        ]


@register
class InplaceOrder(Contract):
    name = "inplace-order"
    why = (
        "an output aliased onto an input (input_output_aliases) shares its "
        "HBM buffer: on a sequential grid no input block may be fetched "
        "after the output block over the same cells was flushed, or the "
        "kernel reads its own result — CPU interpret mode, which runs an "
        "aliased call functionally, can never show it (analysis/kernels.py)"
    )

    def applies_to(self, art: ProgramArtifact) -> bool:
        return art.closed is not None

    def check(self, art: ProgramArtifact) -> List[Finding]:
        from stencil_tpu.analysis import kernels

        return [
            art.finding(self.name, msg)
            for msg in kernels.check_inplace_order(art)
        ]


@register
class TilingLegal(Contract):
    name = "tiling-legal"
    why = (
        "every traced pallas kernel survives the Mosaic tiling-legality "
        "model — no rotate on unaligned or non-32-bit planes, no blocked "
        "windows at sub-granule offsets, no int64 index arithmetic: the "
        "static form of the COMPILE_REJECT runtime failures PR 6 ate "
        "(analysis/kernels.py; the tuner and the stream ladder consult "
        "the same verdict pre-build via check_kernel_legal)"
    )

    def applies_to(self, art: ProgramArtifact) -> bool:
        return art.closed is not None

    def check(self, art: ProgramArtifact) -> List[Finding]:
        from stencil_tpu.analysis import kernels

        return [
            art.finding(self.name, msg) for msg in kernels.check_tiling(art)
        ]


@register
class SpanRegistry(Contract):
    name = "span-registry"
    why = (
        "every named-scope label in the traced program is a registered span "
        "(telemetry/names.py ALL_SPANS) — an unregistered scope silently "
        "falls out of device-time attribution.  The exchange sweeps' "
        "per-direction scopes (exchange.<axis>.<side>) are covered too: "
        "the undotted-local-marker escape hatch is gone now that every "
        "in-kernel scope comes from the registry"
    )

    def check(self, art: ProgramArtifact) -> List[Finding]:
        from stencil_tpu.analysis import jaxpr as jx
        from stencil_tpu.telemetry import names as tm

        out: List[Finding] = []
        # a named pallas call stamps its kernel name as the innermost scope
        registered = tm.ALL_SPANS | tm.ALL_KERNELS
        for label in sorted(jx.scope_labels(art.closed)):
            if label not in registered:
                out.append(
                    art.finding(
                        self.name,
                        f"named scope {label!r} is not a registered span — "
                        "add it to telemetry/names.py ALL_SPANS or rename "
                        "the scope",
                    )
                )
        return out


@register
class KernelName(Contract):
    name = "kernel-name"
    why = (
        "every pallas call carries a registered kernel name "
        "(pl.pallas_call(name=...), telemetry/names.py ALL_KERNELS): the "
        "name is the kernel's identity in a device trace — an unnamed call "
        "is a custom-call told apart only by its result shape, and falls "
        "out of every per-kernel metric"
    )

    def check(self, art: ProgramArtifact) -> List[Finding]:
        from stencil_tpu.analysis import jaxpr as jx
        from stencil_tpu.telemetry import names as tm

        unnamed = sorted(
            {
                str(e.params.get("name"))
                for e in jx.iter_eqns(art.closed)
                if e.primitive.name == "pallas_call"
                and e.params.get("name") not in tm.ALL_KERNELS
            }
        )
        return [
            art.finding(
                self.name,
                f"pallas call named {name!r} is not a registered kernel — "
                "pass name=tm.KERNEL_* (telemetry/names.py ALL_KERNELS)",
            )
            for name in unnamed
        ]


@register
class ExchangeScope(Contract):
    name = "exchange-scope"
    why = (
        "the exchange is told from step glue BY NAME: every ppermute and "
        "every self-wrap kernel (the sweep of an axis the mesh does not "
        "split: exchange.<axis>.wrap, no wire) sits under an "
        "exchange.<axis> sweep scope, a bare exchange program carries one "
        "on >= 90% of its equations (slab cuts, reshapes and blends "
        "included, not just the wire), and a program that fills no halo -- "
        "neither a ppermute nor a wrap kernel -- carries none"
    )

    #: share of a bare exchange program's leaf equations (containers -- jit,
    #: shard_map, loops -- count through their bodies) under a sweep scope
    MIN_COVERAGE = 0.9

    def applies_to(self, art: ProgramArtifact) -> bool:
        return art.kind in ("step", "exchange", "fn")

    def check(self, art: ProgramArtifact) -> List[Finding]:
        from stencil_tpu.analysis import jaxpr as jx
        from stencil_tpu.telemetry import names as tm

        sweeps = set(tm.EXCHANGE_AXIS_SPANS.values())
        wrap_scopes = set(tm.EXCHANGE_WRAP_SPANS.values())
        eqns, scoped = [], []

        def visit(jaxpr, under: bool) -> None:
            # a nested jit's equations (jnp.pad, ...) carry a stack relative
            # to the call eqn: they inherit its scope, as their HLO op_name does
            for e in jaxpr.eqns:
                here = under or bool(
                    sweeps & set(jx.name_stack_str(e).split("/"))
                )
                subs = (
                    []
                    if e.primitive.name in jx.OPAQUE_PRIMITIVES
                    else list(jx.eqn_subjaxprs(e))
                )
                for sub in subs:
                    visit(sub, here)
                if subs:
                    continue  # a container (jit, shard_map, loop): its body counts
                eqns.append(e)
                if here:
                    scoped.append(e)

        visit(getattr(art.closed, "jaxpr", art.closed), False)
        scoped_ids = {id(e) for e in scoped}
        permutes = [e for e in eqns if e.primitive.name == "ppermute"]
        out: List[Finding] = []
        bare = sum(1 for e in permutes if id(e) not in scoped_ids)
        if bare:
            out.append(
                art.finding(
                    self.name,
                    f"{bare} of {len(permutes)} ppermute(s) sit under no "
                    "exchange.<axis> sweep scope "
                    "(names.exchange_axis_span) — a trace would read that "
                    "wire time as step glue",
                )
            )
        wraps = [
            e
            for e in eqns
            if e.primitive.name == "pallas_call"
            and wrap_scopes & set(jx.name_stack_str(e).split("/"))
        ]
        bare_wraps = sum(1 for e in wraps if id(e) not in scoped_ids)
        if bare_wraps:
            out.append(
                art.finding(
                    self.name,
                    f"{bare_wraps} of {len(wraps)} self-wrap kernel(s) sit under "
                    "an exchange.<axis>.wrap scope but no exchange.<axis> "
                    "sweep scope — a trace would read that halo fill as "
                    "step glue",
                )
            )
        if not permutes and not wraps and scoped:
            out.append(
                art.finding(
                    self.name,
                    f"{len(scoped)} equation(s) carry an exchange.<axis> "
                    "scope in a program with no ppermute and no self-wrap "
                    "kernel — the scope would bill step work to the exchange",
                )
            )
        if art.kind == "exchange" and eqns:
            share = len(scoped) / len(eqns)
            if share < self.MIN_COVERAGE:
                out.append(
                    art.finding(
                        self.name,
                        f"only {len(scoped)} of {len(eqns)} equations of "
                        "the exchange program sit under an exchange.<axis> "
                        f"sweep scope ({share:.0%} < "
                        f"{self.MIN_COVERAGE:.0%}): slab cuts, reshapes and "
                        "blends must be scoped, not just the ppermute",
                    )
                )
        return out
