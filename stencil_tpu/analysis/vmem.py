"""Static VMEM verdicts — the analytic footprint re-derived where the
compiler would otherwise discover it by failing.

Two entry points over the SAME models the planners use
(``ops/jacobi_pallas.wavefront_vmem_bytes`` / ``ops/stream.stream_vmem_fits``):

* :func:`check_vmem` — pre-build: a stream PLAN against a realized domain.
  ``tune/space.stream_space`` consults it to prefilter candidates before
  paying a compile-and-catch VMEM_OOM (the pruned twin still counts into
  ``tune.pruned``), and the stream ladder prefilters rungs through it on
  real backends (``resilience/ladder.py`` ``prefilter=``).
* :func:`check_traced` — post-trace: the ``vmem-budget`` contract recomputes
  the footprint from the TRACED pallas-call shapes (the planes the program
  actually streams), so a helper that resized buffers behind the planner's
  back still gets caught.

Both return ``None`` for "fits" or a human reason string — never raise on a
fit question (a malformed plan is the caller's bug and does raise).
"""

from __future__ import annotations

from typing import List, Optional, Sequence


def stream_plan_vmem_bytes(
    m: int,
    plane_y: int,
    plane_z: int,
    itemsizes: Sequence[int],
    z_slabs: bool = False,
    ring_itemsizes: Optional[Sequence[int]] = None,
    fused: bool = False,
) -> int:
    """Modeled VMEM block bytes of a stream plan (stack margin excluded —
    compare against :func:`budget_and_margin`).  The generic-engine model
    (``stream_vmem_fits``'s accounting) plus, under ``halo="fused"``, the
    double-buffered fused-shell side blocks: per field, one (1, y, z) x-slab
    plane plus the (1, 2m, z) y and (1, 2m, y) z message blocks per grid
    step."""
    from stencil_tpu.ops.jacobi_pallas import _padded_plane_bytes

    ring = list(itemsizes) if ring_itemsizes is None else list(ring_itemsizes)
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


def budget_and_margin(n_fields: int, budget: Optional[int] = None):
    """(requested scoped-VMEM budget bytes, per-plan stack margin) — the
    calibrated numbers the planners gate on (``STENCIL_VMEM_LIMIT_BYTES``
    validated read unless ``budget`` overrides)."""
    from stencil_tpu.ops.jacobi_pallas import _VMEM_STACK_MARGIN, _vmem_budget

    return (budget if budget is not None else _vmem_budget(),
            _VMEM_STACK_MARGIN * max(1, n_fields))


def check_vmem(dd, plan: dict, budget: Optional[int] = None) -> Optional[str]:
    """Does this stream plan's modeled footprint fit the scoped-VMEM budget
    on this realized domain?  ``None`` = fits; otherwise a reason string
    naming the estimate and the budget.  The per-field itemsizes honor the
    storage axis (bf16 buffers stream 2 B planes but carry f32 level
    rings — the ``f32_accumulate`` contract)."""
    route = plan.get("route")
    if route not in ("wrap", "wavefront", "plane"):
        raise ValueError(f"not a stream plan: {plan!r}")
    m = int(plan.get("m", 1))
    if route == "plane" and plan.get("stages"):
        # a planned plane step: its passes carry their own modeled bytes
        # (ops/stream.py plan_plane_passes, stack margin included)
        from stencil_tpu.ops.jacobi_pallas import _vmem_budget

        cap = budget if budget is not None else _vmem_budget()
        worst = max(
            (p for st in plan["stages"] for p in st["passes"]),
            key=lambda p: p["vmem_bytes"], default=None,
        )
        if worst is not None and worst["vmem_bytes"] > cap:
            return (
                f"plan plane[m=1]: the pass that writes {worst['writes']} models "
                f"{worst['vmem_bytes'] / 1e6:.1f} MB of VMEM (stack included) "
                f"against the {cap / 1e6:.1f} MB budget"
            )
        return None
    # the planes the pass streams, as plan_stream models them: the wrap
    # route works on the bare interiors (the periodic boundary is folded into
    # its index maps), the wavefront route on the raw, shell-carrying planes
    spec = dd.local_spec()
    planes = spec.sz if route == "wrap" else spec.raw_size()
    itemsizes: List[int] = [dd.field_dtype(h).itemsize for h in dd._handles]
    ring_sizes: List[int] = [h.dtype.itemsize for h in dd._handles]
    if plan.get("grouping") == "per-field" and len(itemsizes) > 1:
        itemsizes = [max(itemsizes)]
        ring_sizes = [max(ring_sizes)]
    est = stream_plan_vmem_bytes(
        m,
        planes.y,
        planes.z,
        itemsizes,
        z_slabs=bool(plan.get("z_slabs")),
        ring_itemsizes=ring_sizes,
        fused=plan.get("halo") == "fused",
    )
    cap, margin = budget_and_margin(len(itemsizes), budget)
    if est + margin > cap:
        tags = ",fused" if plan.get("halo") == "fused" else ""
        return (
            f"plan {plan.get('route')}[m={m}{tags}] models "
            f"{est / 1e6:.1f} MB of VMEM blocks (+{margin / 1e6:.1f} MB "
            f"stack) against the {cap / 1e6:.1f} MB budget"
        )
    return None


def check_traced(art, budget: Optional[int] = None) -> Optional[str]:
    """The ``vmem-budget`` contract's core: re-derive the footprint from the
    TRACED program — depth from the plan, plane dims and itemsizes from the
    3-D operands of the pallas calls actually in the jaxpr — and gate it
    against the budget.  ``None`` when it fits, or when the artifact has no
    stream plan / no pallas calls to model."""
    from stencil_tpu.analysis import jaxpr as jx

    plan = art.plan
    if not plan or plan.get("route") not in ("wrap", "wavefront", "plane"):
        return None
    # one pallas call = one streaming pass over its 3-D block operands (one
    # per field in a joint pass); model the heaviest call in the program
    best: Optional[tuple] = None  # ((y, z), [itemsizes]) with max raw bytes
    for e in jx.iter_eqns(art.closed):
        if e.primitive.name != "pallas_call":
            continue
        blocks = [
            v.aval
            for v in e.invars
            if len(getattr(getattr(v, "aval", None), "shape", ())) == 3
            and min(v.aval.shape) > 1
        ]
        if not blocks:
            continue
        import jax.numpy as jnp

        big = max(blocks, key=lambda a: a.shape[-2] * a.shape[-1])
        sizes = [a.dtype.itemsize for a in blocks]
        # bf16 STORAGE blocks still carry their level ring at the f32
        # accumulator (the f32_accumulate contract) — pricing the ring at
        # the traced 2-byte itemsize is exactly the storage-only model
        # that admitted ring-blown depths before the planners were fixed
        rings = [
            4 if a.dtype == jnp.bfloat16 else a.dtype.itemsize
            for a in blocks
        ]
        weight = sum(
            a.shape[-2] * a.shape[-1] * a.dtype.itemsize for a in blocks
        )
        if best is None or weight > best[0]:
            best = (weight, tuple(big.shape[-2:]), sizes, rings)
    if best is None:
        return None
    _, (py, pz), itemsizes, ring_itemsizes = best
    est = stream_plan_vmem_bytes(
        int(plan.get("m", 1)),
        py,
        pz,
        itemsizes,
        z_slabs=bool(plan.get("z_slabs")),
        ring_itemsizes=ring_itemsizes,
        fused=plan.get("halo") == "fused",
    )
    cap, margin = budget_and_margin(
        len(itemsizes), budget if budget is not None else art.vmem_budget
    )
    if est + margin > cap:
        return (
            f"traced pallas planes ({py}, {pz}) at depth m="
            f"{plan.get('m', 1)} model {est / 1e6:.1f} MB of VMEM blocks "
            f"(+{margin / 1e6:.1f} MB stack) against the {cap / 1e6:.1f} MB "
            "budget"
        )
    return None
