"""Discrete candidate spaces for the autotuner.

Axes (ISSUE: the constants PERF_NOTES.md says to re-qualify per chip):

* **temporal depth** ``k`` (wrap) / ``m`` (wavefront) — the HBM-traffic
  lever (~8/k B/cell/iter); the static default ``_WRAP_MAX_K = 16`` sits
  mid-plateau on the one v5e the probes ran on.
* **input_output_aliases on/off** — aliasing serializes the deep pipeline
  (probe21b) but halves the working set; the crossover is chip-dependent.
* **z-ring vs padded layout** — measured NEUTRAL on the probe chip (the
  pipeline is VPU-bound there); a faster-VPU generation flips it.
* **stream route** (wrap/plane/wavefront) and grouping — the generic
  engine's plan axes.
* **overlap** (off/split) — the stream engine's split-step schedule
  (ops/stream_plan.py ``STREAM_OVERLAP``): dispatch the interior pass with no
  ppermute dependency and recompute the boundary bands afterward, so the
  collectives hide behind the VPU work at the cost of ~``6·3w``-wide band
  recomputes; ``off`` is the static fallback, and the win flips with the
  exchange/compute cost ratio — measured, not assumed.
* **exchange route** (direct/zpack_xla/zpack_pallas/yzpack_xla/
  yzpack_pallas) — the halo exchange's y/z-sweep implementation: sliced
  thin slivers vs the packed lane-major z-shell message and, on the
  ``yzpack_*`` routes, the packed sublane-major y-shell message too
  (ops/exchange.py EXCHANGE_ROUTES); ``direct`` is the static fallback,
  the packed routes attack the measured amplification of shell-carrying
  halo storage (PERF_NOTES "Thin z-region access" / "Thin y-region
  access").
* **halo consumption** (array/fused) — the stream engine's fused
  unpack→blend mode (ops/stream_plan.py ``STREAM_HALO``): under ``fused`` the
  packed ``yzpack_*`` messages land directly in the pass's level-0 VMEM
  working planes and the big array never sees a halo write; ``array`` is
  the static fallback — the win trades the saved unpack/blend dispatches
  against per-plane patch selects, so it is measured, not assumed.
* **storage dtype** (native/bf16) — bf16 field buffers with f32
  accumulation in-kernel, halving bytes/cell on the DMA-bound shallow-k
  paths; ``native`` is the static fallback, bf16 prefiltered to f32
  fields (the only narrowing with an analytic error contract).
* **halo multiplier** — for the temporally-blocked paths the multiplier IS
  the wavefront depth (the m-wide shell is exchanged every m steps), so the
  ``m`` axis covers it; candidate dicts carry ``halo_multiplier == m`` to
  make that explicit in persisted configs.

Every space includes the CURRENT STATIC PICK as a candidate, so the search
winner is never worse than the no-tune fallback under the same protocol.
Candidates the VMEM model already excludes are returned separately
(``prefiltered``) — they count into the ``tune.pruned`` telemetry without
burning a trial.
"""

from __future__ import annotations

from typing import List, Optional, Tuple


def candidate_label(cand: dict) -> str:
    """Stable short label for logs / fault-plan targeting, e.g.
    ``alias=0/k=8``.  ``/``-separated, NOT commas: ``STENCIL_FAULT_PLAN``
    splits its entry list on commas, and these labels must be targetable."""
    parts = []
    for k in sorted(cand):
        v = cand[k]
        if isinstance(v, bool):
            v = int(v)
        parts.append(f"{k}={v}")
    return "/".join(parts)


#: candidate fields DERIVED from the depth (documentation riders in the
#: persisted config, not independent axes) — excluded when comparing
#: candidates for deeper-neighbor pruning, or the mirrored value would make
#: every deeper candidate look like a different config family
_DERIVED_FIELDS = ("halo_multiplier",)


def deeper_neighbors(cand: dict, candidates: List[dict], depth_key: Optional[str]) -> List[dict]:
    """Candidates identical to ``cand`` except for a LARGER ``depth_key``
    value — the ones a VMEM_OOM at ``cand`` proves can't compile either.
    Depth-derived riders (``halo_multiplier == m``) are ignored in the
    comparison."""
    if not depth_key or depth_key not in cand:
        return []

    def base_of(c):
        return {
            k: v
            for k, v in c.items()
            if k != depth_key and k not in _DERIVED_FIELDS
        }

    base = base_of(cand)
    return [
        c
        for c in candidates
        if c is not cand
        and c.get(depth_key) is not None
        and base_of(c) == base
        and c[depth_key] > cand[depth_key]
    ]


#: depth grid spanning the measured plateau and its edges (probe20b/c/d:
#: k=8 128-132, k=12 190, k=16 142-202, k=20-24 ~190, k=32 152 Gcells/s)
_DEPTH_GRID = (4, 8, 12, 16, 20, 24)


def jacobi_wrap_space(
    shape: Tuple[int, int, int],
    itemsize: int,
    static_k: int,
    ks=None,
    dtype=None,
) -> Tuple[List[dict], int]:
    """(candidates, prefiltered_count) over the wrap kernel's temporal depth
    ``k`` plus, at the static depth, the storage-dtype A/B (one twin, like
    the wavefront space's z-ring pair — the axis is independent of depth to
    first order, so one pair per search re-qualifies it cheaply).
    Structural prefilter: ``bf16`` only for f32 fields — a filtered twin
    counts into ``tune.pruned`` without burning a trial.  ``ks`` overrides
    the depth grid (tests / narrow re-qualification); ``dtype`` (default
    f32) drives the axis prefilter."""
    import jax.numpy as jnp

    from stencil_tpu.ops.jacobi_pallas import bf16_supported, wavefront_vmem_fits

    dtype = jnp.dtype(dtype or jnp.float32)
    X, Y, Z = shape
    grid = sorted({static_k, *(ks if ks is not None else _DEPTH_GRID)})
    grid = [k for k in grid if 1 <= k <= max(1, X // 2)]
    kept, prefiltered = [], 0
    for k in grid:
        # the static pick always runs (it IS the fallback being defended);
        # other depths must pass the VMEM model to be worth a compile
        if k == static_k or wavefront_vmem_fits(k, Y, Z, itemsize):
            kept.append({"k": k, "storage_dtype": "native"})
        else:
            prefiltered += 1
    # the storage A/B at the static depth (persisted winners carry the axis
    # explicitly; pre-axis cache entries without the field stay warm —
    # absent = the static native, no schema bump).  Unlike the static pick
    # itself the twin is NOT the defended fallback, so it must pass the VMEM
    # model — with bf16's narrow pipeline planes over an f32 level ring
    # folded in.
    if bf16_supported([dtype]) and wavefront_vmem_fits(
        static_k, Y, Z, jnp.dtype(jnp.bfloat16).itemsize,
        ring_itemsize=itemsize,
    ):
        kept.append({"k": static_k, "storage_dtype": "bf16"})
    else:
        prefiltered += 1
    return kept, prefiltered


def jacobi_wavefront_space(
    static_m: int,
    depth_cap: int,
    z_ring_eligible: bool,
    static_z_ring: bool,
    ms=None,
    bf16_ok: bool = False,
) -> Tuple[List[dict], int]:
    """(candidates, prefiltered) over the multi-device wavefront: depth ``m``
    (== the halo multiplier: the m-wide shell is exchanged every m steps),
    alias on/off, and — at the static depth — z-ring vs padded layout plus
    the storage-dtype A/B (``bf16_ok`` is the structural prefilter the
    caller evaluates: f32 fields).
    ``depth_cap`` is the structural bound (shard/valid extents)."""
    grid = sorted({static_m, *(ms if ms is not None else _DEPTH_GRID)})
    grid = [m for m in grid if 1 <= m <= depth_cap]
    cands: List[dict] = []

    def cand(m, alias, z_ring, storage="native"):
        return {
            "m": m,
            "halo_multiplier": m,
            "alias": alias,
            "z_ring": z_ring,
            "storage_dtype": storage,
        }

    for m in grid:
        for alias in (False, True):
            cands.append(cand(m, alias, static_z_ring and z_ring_eligible))
    if z_ring_eligible:
        # the layout A/B at the static depth only: probe25d measured it
        # NEUTRAL on v5e, so one pair per search re-qualifies it cheaply
        cands.append(cand(static_m, False, not static_z_ring))
    static_ring = static_z_ring and z_ring_eligible
    prefiltered = 0
    # the storage A/B at the static depth (one twin, like z-ring)
    if bf16_ok:
        cands.append(cand(static_m, False, static_ring, storage="bf16"))
    else:
        prefiltered += 1
    return cands, prefiltered


def exchange_space(dd) -> Tuple[List[dict], int]:
    """(candidates, prefiltered) over the halo exchange's y/z-sweep route
    (``ops/exchange.py`` EXCHANGE_ROUTES) for a REALIZED domain: ``direct``
    (the static fallback — the thin-z sliver path, ~64×-amplified on the
    (8,128) tiling, PERF_NOTES "Thin z-region access"; the y sliver is
    sublane-amplified ~8/(2r), "Thin y-region access") vs the packed
    z-shell routes (``zpack_xla`` / ``zpack_pallas``: lane-major ``(2m, Y,
    Xpad)`` messages) and the y+z packed routes (``yzpack_xla`` /
    ``yzpack_pallas``: additionally the sublane-major ``(2m, X, Z)`` y
    message).  Candidates that structurally cannot engage are prefiltered —
    they count into ``tune.pruned`` without burning a trial.  A ``zpack_*``
    candidate needs the z sweep; a ``yzpack_*`` candidate needs the Y sweep
    (with y ineligible it would compile and measure a byte-identical
    duplicate of its ``zpack_*`` sibling)."""
    from stencil_tpu.ops.exchange import (
        EXCHANGE_ROUTES,
        Y_PACK_ROUTES,
        ypack_supported,
        zpack_supported,
    )

    cands: List[dict] = [{"exchange_route": "direct"}]
    shell = dd._shell_radius
    dtypes = [dd.field_dtype(h) for h in dd._handles]
    z_ok = (
        shell is not None
        and (shell.axis(2, -1) > 0 or shell.axis(2, +1) > 0)
        and zpack_supported(dtypes, dd._valid_last)
    )
    y_ok = (
        shell is not None
        and (shell.axis(1, -1) > 0 or shell.axis(1, +1) > 0)
        and ypack_supported(dtypes, dd._valid_last)
    )
    prefiltered = 0
    for route in EXCHANGE_ROUTES[1:]:
        if y_ok if route in Y_PACK_ROUTES else z_ok:
            cands.append({"exchange_route": route})
        else:
            prefiltered += 1
    return cands, prefiltered


def stream_space(dd, x_radius: int, separable: bool,
                 static_plan: dict) -> Tuple[List[dict], int]:
    """(candidates, prefiltered) of full stream-engine plans around the
    static pick: the static plan, its shallower depths, the alias flip, the
    plane route as the m=1 structural baseline, and the split-step overlap
    A/B (``overlap ∈ {off, split}``, ops/stream.py — the interior pass
    dispatched with no ppermute dependency).
    Every candidate is a request ``resolve_stream_plan`` accepts verbatim
    (+ ``alias``/``overlap``).

    Every candidate carries explicit ``overlap`` and ``halo`` fields
    ("off"/"array" unless it IS that axis's twin) so persisted winners
    record the axes — while older entries WITHOUT the fields stay
    consultable (absent = the static off/array, ops/stream_plan.py
    ``_overlap_request`` / ``_halo_request``); no cache schema bump.  The
    split twin of a z-slab wavefront re-plans to the plain form
    (``plain_wavefront_plan``): split needs z halos in the big array for
    the exchange it overlaps.  The fused-halo twin (``halo="fused"`` —
    the packed messages land in the pass's level-0 VMEM planes,
    docs/tuning.md "Fused halo consumption") re-plans the same way and is
    structurally prefiltered unless the domain's resolved exchange route
    packs the y shell (``fused_halo_ineligible``)."""
    from stencil_tpu.ops.stream_plan import (
        fused_halo_ineligible,
        plain_wavefront_plan,
        plan_stream,
        static_stream_alias,
    )

    cands: List[dict] = []

    def add(plan: dict, alias: Optional[bool], overlap: str = "off",
            halo: str = "array") -> None:
        c = dict(plan)
        if alias is not None:
            c["alias"] = alias
        c["overlap"] = overlap
        c["halo"] = halo
        c.setdefault("halo_multiplier", c.get("m", 1))
        if c not in cands:
            cands.append(c)

    nq = len(dd._handles)
    static_alias = static_stream_alias(static_plan["route"], nq)
    add(static_plan, static_alias if static_plan["route"] != "wrap" else None)
    if static_plan["route"] in ("wavefront", "wrap"):
        m = static_plan["m"]
        depths = sorted({d for d in (*_DEPTH_GRID, m // 2) if 2 <= d < m})[-2:]
        for d in depths:
            shallower = plan_stream(
                dd, x_radius, static_plan["route"], separable, max_m=d
            )
            add(shallower, static_alias if shallower["route"] != "wrap" else None)
        if static_plan["route"] == "wavefront":
            add(static_plan, not static_alias)  # the alias A/B (probe21b)
    if static_plan["route"] != "plane":
        try:
            add(plan_stream(dd, x_radius, "plane", separable), None)
        except ValueError:
            pass
    # the overlap A/B: a split twin of the static plan (via the plain-form
    # re-plan when the static pick is a z-slab wavefront), plus a split twin
    # of the plane baseline when one made the space — both measured against
    # their off siblings under the same protocol
    split_bases: List[Tuple[dict, Optional[bool]]] = []
    if static_plan["route"] in ("plane", "wavefront"):
        base = static_plan
        if static_plan.get("z_slabs"):
            base = plain_wavefront_plan(dd, static_plan)
        if base is not None:
            split_bases.append((base, static_alias))
    for c in cands:
        if c["route"] == "plane" and c["overlap"] == "off":
            split_bases.append((c, c.get("alias")))
            break
    for base, alias_pick in split_bases:
        b = {k: v for k, v in base.items()
             if k not in ("overlap", "halo", "halo_multiplier")}
        add(b, alias_pick, overlap="split")
    prefiltered = 0
    # the fused-halo A/B: a fused twin of the static plan (plain-form
    # re-plan when the static pick is a z-slab wavefront, like split),
    # measured against its array sibling — prefiltered when the fused mode
    # structurally cannot engage (non-yzpack exchange route, uneven
    # shards, wrap route, unsupported dtype)
    fused_base = None
    if static_plan["route"] in ("plane", "wavefront"):
        fused_base = static_plan
        if static_plan.get("z_slabs"):
            fused_base = plain_wavefront_plan(dd, static_plan)
    if fused_base is not None and fused_halo_ineligible(
        dd,
        dict(fused_base, overlap="off", z_slabs=fused_base.get("z_slabs", False)),
        getattr(dd, "_exchange_route", "direct"),
    ) is None:
        b = {k: v for k, v in fused_base.items()
             if k not in ("overlap", "halo", "halo_multiplier")}
        add(b, static_alias, halo="fused")
    else:
        prefiltered += 1
    # static verdicts: candidates whose MODELED footprint busts the
    # scoped-VMEM budget (analysis/vmem.py), or whose kernels the Mosaic
    # legality model rejects (analysis/kernels.py — x64 index arithmetic,
    # rotate operand width, sub-granule block windows), are pruned here,
    # before the search pays a compile-and-catch VMEM_OOM/COMPILE_REJECT
    # for them.  plan_stream already depth-gates its plans through the
    # VMEM model, so that leg mostly catches the twins the planner never
    # modeled.  The static pick always survives (it IS the no-tune fallback being
    # defended), matching the wrap space's rule.
    from stencil_tpu.analysis import check_kernel_legal, check_vmem

    kept = []
    for c in cands:
        is_static = (
            all(c.get(k) == v for k, v in static_plan.items()
                if k not in ("halo_multiplier", "alias"))
            and c.get("overlap", "off") == "off"
            and c.get("halo", "array") == "array"
        )
        if not is_static and (
            check_vmem(dd, c) is not None
            or check_kernel_legal(dd, c) is not None
        ):
            prefiltered += 1
        else:
            kept.append(c)
    return kept, prefiltered
