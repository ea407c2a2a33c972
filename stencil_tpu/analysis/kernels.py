"""Kernel-level static verifier — the analyzer's deliberate descent into
the pallas box.

Everything else in this package holds ``pallas_call`` conservatively OPAQUE
(``analysis/jaxpr.py``): for array-dataflow questions (taint, donation,
DUS scanning) a kernel's inner jaxpr describes VMEM-ref mutation and must
not be mistaken for array dataflow.  But the kernels are exactly where the
remaining historically-runtime failure classes live — write races between
grid points, block-map coverage gaps, and Mosaic lowering rejections — and
THOSE are decidable from the pallas call's own metadata, because BlockSpec
index maps are pure functions of the grid indices.  This module evaluates
them concretely over the (bounded) grid and turns three runtime failure
classes into static verdicts:

* **Write races** (:func:`check_races`, contract ``kernel-race``).  TPU
  grids are SEQUENTIAL by default (``dimension_semantics`` "arbitrary"):
  two grid points landing on the same output block is a deliberate
  last-write-wins replay, and every streaming kernel in ops/ relies on it
  (the wrap pass revisits ``(i - k) % X``, the wavefront clamps
  ``max(i - m, 0)``, the plane pass clamps ``clip(i - r, 0, X - 1)``).  A
  race exists only when two grid points that differ in a dim DECLARED
  ``"parallel"`` (compiler_params ``dimension_semantics``) write the same
  output block — then the execution order is unspecified.  Exemption: the
  writes are provably identical (every input footprint coincides for the
  two points and the body never reads ``program_id``), the replicated-
  write idiom.
* **Coverage** (:func:`check_coverage`, contract ``kernel-coverage``).
  Every output block must be written by some grid point, or carried in via
  ``input_output_aliases`` — whose in/out shape-and-dtype consistency is
  checked here too, the ``donation-soundness`` analog one level down.
  Unaliased wavefront outputs deliberately leave an uninitialized trailing
  shell (``max(i - m, 0)`` never reaches the last ``m`` blocks; downstream
  slicing drops them), so boundary-confined gaps up to the artifact's
  shell margin (``plan["m"]``, or ``meta["kernel_shell_margin"]``) are
  tolerated.  A second deliberate-gap idiom: lane-padded message buffers
  (``ops/pack.py lane_pad``) round their minor extent up to 128 and never
  visit the dead pad columns, so a trailing minor-dim run of uncovered
  blocks shorter than one lane tile — on an output whose minor extent is
  a 128-multiple — is tolerated too.  Any other gap fires.  A BOUNDARY
  block — wider than the array in a dim, as the z-slab wavefront pass
  streams a raw ``(Xr, Yr, Zr)`` block through ``(1, Yr, Zp)`` windows,
  ``Zp`` the next 128-multiple (the DMA moves ``Zr`` lanes, the rest of the
  window is VMEM only) — covers the ARRAY's extent in that dim, not its
  own: blocks are counted ``ceil(array / block)`` and a block's cells are
  clamped to the array (:func:`_block_box`), so one such block is the whole
  dim and in-place order is judged on the cells that exist.
* **In-place order** (:func:`check_inplace_order`, contract
  ``inplace-order``).  An output that aliases an input
  (``input_output_aliases``) shares its HBM buffer, so on a sequential
  grid a block the kernel has already FLUSHED must never be FETCHED again
  as input — the kernel would read its own result (and, one step apart,
  race its own write-back DMA).  The pipeline fetches an input block when
  its index changes (and at step 0) and flushes an output block after the
  last step that holds it (the step before its index changes, or the last
  step): for every aliased pair, no input block fetched at a step ``> i``
  may overlap an output block flushed at a step ``<= i``.  The streaming
  passes satisfy it by construction — the plane pass writes ``clip(i - r,
  0, X - 1)`` while reading ``min(i, X - 1)``, the wavefront ``max(i - m,
  0)`` while reading ``i``: writes trail reads — and until this contract
  that was a comment.  CPU interpret mode runs an aliased call
  functionally and can never show the hazard; this check and a chip run
  are what can.  Grids with a ``parallel`` dim have no order to check
  (``kernel-race`` owns them).
* **Mosaic tiling legality** (:func:`check_tiling`, contract
  ``tiling-legal``; :func:`check_kernel_legal` is the pre-build plan
  surface).  The shape/op legality model for the lowering failures PR 6
  ate at runtime, with the pinned wordings the failure taxonomy classifies
  as COMPILE_REJECT (``resilience/taxonomy.py``):

  - Mosaic's rotate on a plane that is not natively tiled (minor %% 128,
    second-minor %% 8 for the 32-bit tiling) — "unsupported unaligned
    shape".  Static amounts have the two-slices+concatenate fallback
    (``ops/jacobi_pallas._make_roll`` picks it), TRACED amounts have no
    static form; either way a ``roll`` eqn on an unaligned plane cannot
    lower.
  - rotate on non-32-bit data — "rotate with non-32-bit data" (narrow
    floats upcast before the roll; 8-byte and narrow-int dtypes fail).
  - blocked windows at sub-granule offsets — a BlockSpec that blocks the
    second-minor dim with a MULTI-ROW block extent that is not a multiple
    of the (8, 128) f32 / (16, 128) bf16 sublane granule (or the minor
    dim off the 128 lane granule) places windows straddling tile rows at
    offsets Mosaic rejects as "invalid offsets in tiling target".
    Offsets, not extents: a narrow single-block operand (the split
    schedule's ``3w``-wide band sub-blocks) is legal, and so are
    DEGENERATE extent-1 windows — the pack kernels stream one lane
    column / sublane row per grid step (``ops/pack.py``), measured legal
    on v5e (partial-tile transfers cost bandwidth, not legality —
    PERF_NOTES "HBM ragged-edge tax").  Only a grid of multi-row windows
    whose extent is off the granule has no representable tiled layout.
    A BOUNDARY block (extent past the array's, one window) is legal where
    that extent is on the granule — Mosaic clamps the transfer to the
    array — and has no layout where it is not: the lowering wants the last
    two block dims "divisible by 8 and 128 respectively, or be equal to
    the respective dimensions of the overall array".
  - int64 grid index arithmetic (``jax_enable_x64``) — Mosaic index
    arithmetic is 32-bit ("failed to legalize").  Config legs are scoped
    to where the config is the KERNEL's fault: the traced contract fires
    on int64 index-map avals only when ambient x64 is OFF (someone forced
    the widening; under global x64 every map is int64 by default and the
    verdict belongs to the plan surface), and the plan surface applies
    its x64 leg only when the process would actually lower via Mosaic
    (:func:`_mosaic_target` — tier-1's CPU/interpret runs deliberately
    enable x64 and must not have their tuner spaces vetoed by it).

The footprint evaluation is bounded: grids with more than
``GRID_EVAL_BOUND`` points (or index maps taking scalar-prefetch operands,
whose block choice is a runtime value) are skipped with a note rather than
evaluated — the canonical kernels' grids are tens of points, and a bound
keeps the contract wall-time flat.  Skipping is conservative-quiet, never
conservative-loud: an unevaluable map yields no verdict, not a finding.

``check_kernel_legal(dd, plan)`` mirrors ``vmem.check_vmem`` exactly: a
stream PLAN against a realized domain, ``None`` = legal, else a reason
string.  ``tune/space.stream_space`` prefilters statically-illegal
candidates with zero compile attempts, and the stream ladder descends
rungs it rejects as recorded COMPILE_REJECT descents without compiling
(``resilience/ladder.py`` tuple-returning ``prefilter=``).
"""

from __future__ import annotations

import dataclasses
import itertools
from typing import Dict, List, Optional, Sequence, Tuple

from stencil_tpu.analysis import jaxpr as jx

#: hard cap on concretely-evaluated grid points per pallas call — canonical
#: streaming grids are O(X + shell) ~ tens of points; past this bound the
#: footprint analysis records a note and abstains (see module docstring)
GRID_EVAL_BOUND = 4096

#: the 32-bit native tile; narrower dtypes double the sublane granule
#: (``ops/jacobi_pallas._padded_plane_bytes`` is the same model)
LANE_GRANULE = 128


def sublane_granule(itemsize: int) -> int:
    """Sublane rows of one native tile: 8 for f32, 16 for bf16, 32 for i8."""
    return max(8, 32 // max(1, int(itemsize)))


@dataclasses.dataclass
class BlockUse:
    """One operand/output BlockMapping, flattened for the shape legs."""

    role: str  # "in" / "out"
    index: int  # operand (or output) position within its role
    block_shape: Tuple[int, ...]
    array_shape: Tuple[int, ...]
    dtype: object
    #: concrete block-index tuples per grid point, in grid iteration order;
    #: None when the map is unevaluable (scalar-prefetch args, grid bound)
    footprint: Optional[List[Tuple[int, ...]]]
    index_map_i64: bool = False

    @property
    def nblocks(self) -> Tuple[int, ...]:
        return tuple(
            -(-a // b) for a, b in zip(self.array_shape, self.block_shape)
        )


@dataclasses.dataclass
class KernelReport:
    """Everything the three contracts need from ONE pallas call."""

    label: str
    grid: Tuple[int, ...]
    parallel_dims: Tuple[int, ...]  # grid dims declared "parallel"
    inputs: List[BlockUse]
    outputs: List[BlockUse]
    #: {output index: aliased operand's BlockUse} per input_output_aliases
    aliases: Dict[int, BlockUse]
    alias_faults: List[str]  # in/out shape-or-dtype mismatches
    scratch: List[Tuple[Tuple[int, ...], object]]  # (shape, dtype)
    #: (plane shape, itemsize, traced amount?) per in-body rotate eqn
    rolls: List[Tuple[Tuple[int, ...], int, bool]]
    reads_program_id: bool
    notes: List[str]


def _dimension_semantics(params: dict) -> Tuple[str, ...]:
    # the eqn carries {backend name: CompilerParams} (pallas normalizes a
    # bare ``pltpu.CompilerParams`` into that mapping at call time)
    for cp in (params.get("compiler_params") or {}).values():
        ds = getattr(cp, "dimension_semantics", None)
        if ds:
            return tuple(str(getattr(d, "value", d)) for d in ds)
    return ()


def _aval_of(var):
    return getattr(var, "aval", None)


def _iter_body_eqns(body):
    stack = [body]
    while stack:
        j = stack.pop()
        for e in j.eqns:
            yield e
            stack.extend(jx.eqn_subjaxprs(e))


def _eval_index_map(bm, points) -> Optional[List[Tuple[int, ...]]]:
    """Concrete per-grid-point block indices, or None when the map takes
    non-grid operands (scalar prefetch — a runtime block choice)."""
    import jax.numpy as jnp
    from jax import core as jax_core

    imj = bm.index_map_jaxpr
    if len(imj.jaxpr.invars) != len(points[0]):
        return None
    # feed grid indices at each invar's own aval dtype (int32 normally,
    # int64 when the program was traced under x64 — tier-1's default)
    dtypes = [getattr(v.aval, "dtype", jnp.int32) for v in imj.jaxpr.invars]
    out: List[Tuple[int, ...]] = []
    for pt in points:
        vals = jax_core.eval_jaxpr(
            imj.jaxpr,
            imj.consts,
            *(jnp.asarray(g, dtype=dt) for g, dt in zip(pt, dtypes)),
        )
        out.append(tuple(int(v) for v in vals))
    return out


def _block_use(role, idx, bm, points, note_sink) -> BlockUse:
    sd = bm.array_aval
    # block_shape entries are ``pl.Blocked(block_size)`` (the default) or
    # ``pl.Squeezed()`` (the user-facing ``None``: a size-1 dim squeezed
    # out of the kernel ref, which carries no ``block_size``)
    block = tuple(int(getattr(b, "block_size", 1)) for b in bm.block_shape)
    footprint = None
    i64 = any(
        str(getattr(a, "dtype", "")) == "int64"
        for a in bm.index_map_jaxpr.out_avals
    )
    if points is not None:
        footprint = _eval_index_map(bm, points)
        if footprint is None:
            note_sink.append(
                f"{role}[{idx}] index map takes runtime operands "
                "(scalar prefetch) — footprint not evaluable"
            )
    return BlockUse(
        role, idx, block, tuple(sd.shape), sd.dtype, footprint, i64
    )


def kernel_reports(closed, grid_bound: int = GRID_EVAL_BOUND) -> List[KernelReport]:
    """One :class:`KernelReport` per pallas call anywhere in ``closed`` —
    the shared front half of all three kernel contracts."""
    cached = _REPORT_CACHE.get(id(closed))
    if cached is not None and cached[0] is closed:
        return cached[1]
    reports: List[KernelReport] = []
    for eqn in jx.iter_eqns(closed):
        if eqn.primitive.name != "pallas_call":
            continue
        params = eqn.params
        gm = params["grid_mapping"]
        grid = tuple(int(g) for g in gm.grid)
        notes: List[str] = []
        npoints = 1
        for g in grid:
            npoints *= g
        points = None
        if npoints <= grid_bound:
            points = list(itertools.product(*(range(g) for g in grid)))
        else:
            notes.append(
                f"grid {grid} exceeds the {grid_bound}-point evaluation "
                "bound — footprints not evaluated"
            )
        nidx = gm.num_index_operands
        bms = list(gm.block_mappings)
        n_in = gm.num_inputs
        inputs = [
            _block_use("in", k, bm, points, notes)
            for k, bm in enumerate(bms[:n_in])
        ]
        outputs = [
            _block_use("out", k, bm, points, notes)
            for k, bm in enumerate(bms[n_in : n_in + gm.num_outputs])
        ]
        aliases: Dict[int, BlockUse] = {}
        alias_faults: List[str] = []
        for pair in params.get("input_output_aliases") or ():
            in_op, out_i = int(pair[0]), int(pair[1])
            k = in_op - nidx  # operand index -> block-mapping index
            if not (0 <= k < len(inputs) and 0 <= out_i < len(outputs)):
                alias_faults.append(
                    f"alias {in_op}->{out_i} names a non-block operand"
                )
                continue
            src, dst = inputs[k], outputs[out_i]
            if src.array_shape != dst.array_shape or str(src.dtype) != str(
                dst.dtype
            ):
                alias_faults.append(
                    f"alias {in_op}->{out_i} carries "
                    f"{src.dtype}{list(src.array_shape)} into "
                    f"{dst.dtype}{list(dst.array_shape)} — aliased buffers "
                    "must agree in shape and dtype"
                )
            aliases[out_i] = src
        body = params["jaxpr"]
        rolls: List[Tuple[Tuple[int, ...], int, bool]] = []
        reads_pid = False
        for e in _iter_body_eqns(body):
            name = e.primitive.name
            if name == "program_id":
                reads_pid = True
            elif name in ("roll", "tpu_roll", "dynamic_rotate"):
                plane = _aval_of(e.invars[0])
                amt = e.invars[1] if len(e.invars) > 1 else None
                traced = amt is not None and not isinstance(amt, jx.Literal)
                rolls.append(
                    (
                        tuple(getattr(plane, "shape", ())),
                        int(getattr(getattr(plane, "dtype", None), "itemsize", 4)),
                        traced,
                    )
                )
        nscratch = gm.num_scratch_operands
        scratch: List[Tuple[Tuple[int, ...], object]] = []
        if nscratch:
            for v in body.invars[-nscratch:]:
                aval = _aval_of(v)
                shape = tuple(getattr(aval, "shape", ()) or ())
                scratch.append((shape, getattr(aval, "dtype", None)))
        # the kernel's registered name (``pl.pallas_call(name=...)``)
        label = params.get("name") or eqn.primitive.name
        reports.append(
            KernelReport(
                label=str(label),
                grid=grid,
                parallel_dims=tuple(
                    d
                    for d, sem in enumerate(_dimension_semantics(params))
                    if sem == "parallel"
                ),
                inputs=inputs,
                outputs=outputs,
                aliases=aliases,
                alias_faults=alias_faults,
                scratch=scratch,
                rolls=rolls,
                reads_program_id=reads_pid,
                notes=notes,
            )
        )
    _REPORT_CACHE[id(closed)] = (closed, reports)
    return reports


#: reports memoized per traced program — the three contracts (and the
#: fixture sweep) hit the same artifact objects back to back; keying on
#: ``id(closed)`` is safe because the entry holds the jaxpr alive
_REPORT_CACHE: Dict[int, Tuple[object, List[KernelReport]]] = {}


def reset_report_cache() -> None:
    _REPORT_CACHE.clear()


# ---------------------------------------------------------------------------
# contract cores
# ---------------------------------------------------------------------------


def check_races(art) -> List[str]:
    """``kernel-race``: no two PARALLEL grid points may write the same
    output block unless the writes are provably identical."""
    out: List[str] = []
    for rep in kernel_reports(art.closed):
        if not rep.parallel_dims:
            continue  # sequential grid: revisits are last-write-wins replay
        for o in rep.outputs:
            if o.footprint is None:
                continue
            by_block: Dict[Tuple[int, ...], List[int]] = {}
            points = list(
                itertools.product(*(range(g) for g in rep.grid))
            )
            for flat, blk in enumerate(o.footprint):
                by_block.setdefault(blk, []).append(flat)
            for blk, flats in by_block.items():
                if len(flats) < 2:
                    continue
                pair = _parallel_differing_pair(
                    [points[f] for f in flats], rep.parallel_dims
                )
                if pair is None:
                    continue
                if _provably_identical(rep, flats):
                    continue
                out.append(
                    f"{rep.label}: parallel grid points {pair[0]} and "
                    f"{pair[1]} both write block {blk} of output "
                    f"{o.index} — execution order is unspecified under "
                    f"dimension_semantics parallel dims {rep.parallel_dims}"
                )
    return out


def _parallel_differing_pair(points, parallel_dims):
    for a, b in itertools.combinations(points, 2):
        if any(a[d] != b[d] for d in parallel_dims):
            return (a, b)
    return None


def _provably_identical(rep: KernelReport, flats: Sequence[int]) -> bool:
    """The replicated-write exemption: identical input footprints at every
    colliding grid point and a body that never reads ``program_id``."""
    if rep.reads_program_id:
        return False
    for i in rep.inputs:
        if i.footprint is None:
            return False
        blocks = {i.footprint[f] for f in flats}
        if len(blocks) > 1:
            return False
    return True


def _shell_margin(art) -> int:
    meta = getattr(art, "meta", None) or {}
    if "kernel_shell_margin" in meta:
        return int(meta["kernel_shell_margin"])
    plan = getattr(art, "plan", None) or {}
    return int(plan.get("m", 0) or 0)


def check_coverage(art) -> List[str]:
    """``kernel-coverage``: every output block written by some grid point,
    or carried in via a shape-and-dtype-consistent alias; deliberate
    boundary shells up to the artifact's margin tolerated."""
    margin = _shell_margin(art)
    out: List[str] = []
    for rep in kernel_reports(art.closed):
        out.extend(f"{rep.label}: {m}" for m in rep.alias_faults)
        for o in rep.outputs:
            if o.index in rep.aliases:
                continue  # carried in: every unwritten block keeps its input
            if o.footprint is None:
                continue
            covered = set(o.footprint)
            nblocks = o.nblocks
            uncovered = [
                b
                for b in itertools.product(*(range(n) for n in nblocks))
                if b not in covered
            ]
            if uncovered:
                uncovered = _drop_lane_pad(uncovered, covered, o)
            bad = [
                u
                for u in uncovered
                if not _boundary_tolerable(u, nblocks, margin)
            ]
            if bad:
                out.append(
                    f"{rep.label}: output {o.index} "
                    f"({o.dtype}{list(o.array_shape)}, blocks {list(nblocks)}) "
                    f"leaves {len(bad)} block(s) unwritten beyond the "
                    f"{margin}-block shell margin (first: {bad[0]}) and is "
                    "not carried in via input_output_aliases"
                )
    return out


def _block_box(use: BlockUse, blk) -> Tuple[Tuple[int, int], ...]:
    """Element range ``[lo, hi)`` per dim of one block of ``use``."""
    return tuple(
        (i * b, min((i + 1) * b, n))
        for i, b, n in zip(blk, use.block_shape, use.array_shape)
    )


def _boxes_overlap(a, b) -> bool:
    return all(lo < bhi and blo < hi for (lo, hi), (blo, bhi) in zip(a, b))


def check_inplace_order(art) -> List[str]:
    """``inplace-order``: on a sequential grid, no aliased input block is
    fetched after the output block over the same cells was flushed (module
    docstring).  One finding per aliased pair, naming the first hazard."""
    out: List[str] = []
    for rep in kernel_reports(art.closed):
        if rep.parallel_dims:
            continue  # no order to check: kernel-race owns parallel grids
        for out_i, src in sorted(rep.aliases.items()):
            dst = rep.outputs[out_i]
            if not src.footprint or not dst.footprint:
                continue
            last = len(dst.footprint) - 1
            # last step at which each distinct input block is FETCHED (the
            # pipeline skips the DMA while the block index stands still)
            last_fetch: Dict[Tuple[int, ...], int] = {}
            for j, blk in enumerate(src.footprint):
                if j == 0 or blk != src.footprint[j - 1]:
                    last_fetch[blk] = j
            fetched = [(_block_box(src, b), b, j) for b, j in last_fetch.items()]
            for i, blk in enumerate(dst.footprint):
                if i != last and dst.footprint[i + 1] == blk:
                    continue  # still held: flushed after a later step
                box = _block_box(dst, blk)
                hit = next(
                    (
                        (b, j)
                        for fbox, b, j in fetched
                        if j > i and _boxes_overlap(fbox, box)
                    ),
                    None,
                )
                if hit is not None:
                    out.append(
                        f"{rep.label}: output {out_i} aliases in[{src.index}] "
                        f"and flushes block {blk} after grid step {i}, but "
                        f"input block {hit[0]} over the same cells is fetched "
                        f"at step {hit[1]} — in place the kernel reads its "
                        "own result (writes must trail reads on a "
                        "sequential grid)"
                    )
                    break
    return out


def _drop_lane_pad(uncovered, covered, o: BlockUse):
    """The dead lane-padding exemption (module docstring): on an output
    whose minor extent is a 128-multiple (the ``lane_pad`` round-up
    signature), a trailing minor-dim run of uncovered blocks spanning
    fewer than 128 elements is the pad the kernel deliberately never
    visits — drop it from the gap set."""
    d = len(o.array_shape) - 1
    if d < 0 or o.array_shape[d] % LANE_GRANULE != 0:
        return uncovered
    c = max((b[d] for b in covered), default=-1) + 1
    if c >= o.nblocks[d]:
        return uncovered  # minor dim fully reached: no trailing run
    pad_elems = o.array_shape[d] - c * o.block_shape[d]
    if not 0 < pad_elems < LANE_GRANULE:
        return uncovered
    return [u for u in uncovered if u[d] < c]


def _boundary_tolerable(u, nblocks, margin) -> bool:
    if margin <= 0:
        return False
    return any(
        u[d] < margin or u[d] >= n - margin
        for d, n in enumerate(nblocks)
        if n > 1
    )


def _roll_faults(rep: KernelReport) -> List[str]:
    out: List[str] = []
    for shape, itemsize, traced in rep.rolls:
        if itemsize != 4:
            out.append(
                f"{rep.label}: in-kernel rotate on a {itemsize}-byte plane "
                f"{list(shape)} — Mosaic rejects 'rotate with non-32-bit "
                "data' (narrow floats must upcast before the roll; see "
                "ops/jacobi_pallas._make_roll)"
            )
            continue
        minor = shape[-1] if shape else 0
        second = shape[-2] if len(shape) >= 2 else 0
        if minor % LANE_GRANULE != 0 or (len(shape) >= 2 and second % 8 != 0):
            kind = "traced-amount" if traced else "static-amount"
            fix = (
                "no static-slice fallback exists for a traced amount"
                if traced
                else "use the two-slices+concatenate form "
                "(ops/jacobi_pallas._make_roll picks it automatically)"
            )
            out.append(
                f"{rep.label}: {kind} rotate on a non-natively-tiled plane "
                f"{list(shape)} (minor % 128 / second-minor % 8) — Mosaic "
                f"rejects it as 'unsupported unaligned shape'; {fix}"
            )
    return out


def _window_faults(rep: KernelReport) -> List[str]:
    out: List[str] = []
    for use in rep.inputs + rep.outputs:
        shape = use.block_shape
        if len(shape) < 2:
            continue
        nblocks = use.nblocks
        itemsize = int(getattr(use.dtype, "itemsize", 4))
        sub = sublane_granule(itemsize)
        legs = (
            (len(shape) - 1, LANE_GRANULE, "lane"),
            (len(shape) - 2, sub, "sublane"),
        )
        for d, gran, name in legs:
            # extent-1 windows are the degenerate single-row/column
            # stream (the pack idiom), measured legal on v5e; only a
            # grid of MULTI-ROW sub-granule windows straddles tile rows
            if nblocks[d] > 1 and shape[d] > 1 and shape[d] % gran != 0:
                out.append(
                    f"{rep.label}: {use.role}[{use.index}] blocks the "
                    f"{name} dim into {nblocks[d]} windows of extent "
                    f"{shape[d]} — multi-row window offsets fall off the "
                    f"({sub}, {LANE_GRANULE}) {use.dtype} tile grid "
                    "('invalid offsets in tiling target')"
                )
            elif _off_granule_boundary(shape[d], use.array_shape[d], gran):
                out.append(
                    f"{rep.label}: {use.role}[{use.index}] streams the "
                    f"{name} dim's {use.array_shape[d]} cells through a "
                    f"boundary block of extent {shape[d]} — a block wider "
                    f"than the array must be whole ({sub}, {LANE_GRANULE}) "
                    f"{use.dtype} tiles ('divisible by 8 and 128 "
                    "respectively, or be equal to the respective dimensions "
                    "of the overall array')"
                )
    return out


def _off_granule_boundary(block: int, array: int, gran: int) -> bool:
    """A block wider than the array in this dim whose extent is not whole
    tiles: neither of the two forms the lowering takes (module docstring)."""
    return block > array and block % gran != 0


def _index_faults(rep: KernelReport) -> List[str]:
    import jax

    if jax.config.jax_enable_x64:
        # ambient x64 widens EVERY index map to int64 — that is the trace
        # config's doing, not any one kernel's, and the verdict for it
        # belongs to the plan surface (check_kernel_legal's x64 leg).
        # Firing here would flag the whole canonical matrix under tier-1's
        # deliberate x64 default.  Only an int64 map under 32-bit ambient
        # config is a kernel explicitly forcing the widening.
        return []
    bad = [
        f"{u.role}[{u.index}]"
        for u in rep.inputs + rep.outputs
        if u.index_map_i64
    ]
    if not bad:
        return []
    return [
        f"{rep.label}: index maps for {', '.join(bad)} produce int64 block "
        "offsets under jax_enable_x64 — Mosaic index arithmetic is 32-bit "
        "(the lowering 'failed to legalize' class)"
    ]


def check_tiling(art) -> List[str]:
    """``tiling-legal``: the traced surface of the Mosaic legality model
    (module docstring) over every pallas call in the artifact."""
    out: List[str] = []
    for rep in kernel_reports(art.closed):
        out.extend(_roll_faults(rep))
        out.extend(_window_faults(rep))
        out.extend(_index_faults(rep))
    return out


# ---------------------------------------------------------------------------
# pre-build plan surface (the check_vmem twin)
# ---------------------------------------------------------------------------


def _mosaic_target() -> bool:
    """Would a build issued NOW lower through Mosaic?  The x64 leg is a
    process-config fact and only matters where Mosaic actually runs — on
    the CPU/interpret tiers (which deliberately enable x64) it must not
    veto anything.  Tests monkeypatch this to simulate a TPU process."""
    from stencil_tpu.utils.config import pallas_interpret

    return not pallas_interpret()


def check_kernel_legal(dd, plan: dict) -> Optional[str]:
    """Would this stream plan's kernels survive Mosaic lowering on this
    realized domain?  ``None`` = legal; otherwise a reason string naming
    the leg, mirroring :func:`stencil_tpu.analysis.vmem.check_vmem` (a
    malformed plan raises — that is the caller's bug, not a verdict).

    The legs are the plan-derivable slice of the traced model: int64 index
    arithmetic under x64, rotate operand width (the streaming kernels
    rotate every resident plane; narrow floats upcast inside
    ``_make_roll``, 8-byte and narrow integer dtypes cannot), and the
    blocked-window offset granule over the pass's block layout (the stream
    passes stream single-window ``(1, Y, Z)``-family blocks — the z-slab
    wavefront a BOUNDARY one, ``lane_pad_width(Z)`` lanes over the raw
    block's ``Z``, whole lane tiles by construction — and a plane pass on
    planes too large for VMEM ``(1, tile_rows, Z)`` Y TILES of them, which
    the resolved plan names (``plan["stages"]``): several windows along the
    sublane dim, so a ``tile_rows`` that is not whole sublane tiles of the
    stored dtype is refused here; with ``plan["plane_lanes"]`` "window" the
    ``(1, tile_rows, Zw)`` blocks on the side of a call that faces another
    call of the dispatch too).
    """
    route = plan.get("route")
    if route not in ("wrap", "wavefront", "plane"):
        raise ValueError(f"not a stream plan: {plan!r}")
    import jax

    if _mosaic_target() and jax.config.jax_enable_x64:
        return (
            f"plan {route}[m={plan.get('m', 1)}] would trace its grid and "
            "coordinate index arithmetic at int64 under jax_enable_x64 — "
            "Mosaic index arithmetic is 32-bit (failed to legalize)"
        )
    import jax.numpy as jnp

    for h in dd._handles:
        dt = dd.field_dtype(h)
        if dt.itemsize == 8:
            return (
                f"plan {route}[m={plan.get('m', 1)}] rotates resident "
                f"{dt} planes in-kernel — Mosaic rejects 'rotate with "
                "non-32-bit data' and 8-byte dtypes have no upcast path"
            )
        if dt.itemsize < 4 and not jnp.issubdtype(dt, jnp.floating):
            return (
                f"plan {route}[m={plan.get('m', 1)}] rotates resident "
                f"{dt} planes in-kernel — narrow integer dtypes have no "
                "f32 upcast path ('rotate with non-32-bit data')"
            )
    raw = dd.local_spec().raw_size()
    m = int(plan.get("m", 1))
    # the pass block layouts: (block shape, array shape) per streamed
    # operand family — one x-plane window over the raw block, plus the
    # z-slab message blocks when the plan carries them
    layouts = [((1, raw.y, raw.z), (raw.x, raw.y, raw.z))]
    if plan.get("tile_rows"):
        # a resolved plane plan whose passes move Y TILES of a plane says so
        # (ops/stream_plan.py plan_plane_passes): the blocks are the plan's
        # own, read and not re-derived -- one layout a distinct tile
        layouts = [
            ((1, rows, raw.z), (raw.x, raw.y, raw.z))
            for rows in sorted({
                p["tile_rows"] or raw.y for st in plan["stages"] for p in st["passes"]
            })
        ]
        if plan.get("plane_lanes") == "window":
            # ... and towards another call of the dispatch the aligned window's
            # lane tiles alone, the lane dim in two windows (plane_lanes_form)
            zw = dd.local_spec().sz.z
            layouts += [((1, block[1], zw), array) for block, array in layouts]
    if plan.get("z_slabs"):
        from stencil_tpu.ops.stream_pass import lane_pad_width

        layouts = [
            ((1, raw.y, lane_pad_width(raw.z)), (raw.x, raw.y, raw.z)),
            ((1, 2 * m, raw.y), (raw.x, 2 * m, raw.y)),
        ]
    for h in dd._handles:
        itemsize = dd.field_dtype(h).itemsize
        sub = sublane_granule(itemsize)
        for block, array in layouts:
            for d, gran, name in (
                (len(block) - 1, LANE_GRANULE, "lane"),
                (len(block) - 2, sub, "sublane"),
            ):
                nb = -(-array[d] // block[d])
                if nb > 1 and block[d] > 1 and block[d] % gran != 0:
                    return (
                        f"plan {route}[m={m}] blocks the {name} dim into "
                        f"{nb} windows of extent {block[d]} — sub-granule "
                        "window offsets ('invalid offsets in tiling "
                        "target')"
                    )
                if _off_granule_boundary(block[d], array[d], gran):
                    return (
                        f"plan {route}[m={m}] streams the {name} dim's "
                        f"{array[d]} cells through a boundary block of "
                        f"extent {block[d]} — not whole tiles ('divisible "
                        "by 8 and 128 respectively, or be equal to the "
                        "respective dimensions of the overall array')"
                    )
    return None
