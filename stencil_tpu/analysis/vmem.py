"""Static VMEM verdicts — the analytic footprint re-derived where the
compiler would otherwise discover it by failing.

Two entry points over the ONE model the stream planners use
(``ops/stream_plan.py stream_plan_vmem_bytes`` / ``stream_vmem_bytes``):

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

from typing import Optional

from stencil_tpu.ops.jacobi_pallas import _vmem_budget
from stencil_tpu.ops.stream_plan import stack_margin, stream_plan_vmem_bytes, stream_vmem_bytes


def check_vmem(dd, plan: dict, budget: Optional[int] = None) -> Optional[str]:
    """Does this stream plan's modeled footprint fit the scoped-VMEM budget
    on this realized domain?  ``None`` = fits; otherwise a reason string
    naming the estimate and the budget (``budget`` overrides the validated
    ``STENCIL_VMEM_LIMIT_BYTES`` read).  The planes and itemsizes are the
    planner's own (``stream_plan_vmem_bytes``)."""
    route = plan.get("route")
    if route not in ("wrap", "wavefront", "plane"):
        raise ValueError(f"not a stream plan: {plan!r}")
    cap = budget if budget is not None else _vmem_budget()
    if route == "plane" and plan.get("stages"):
        # a resolved plane step: its passes carry their own modeled bytes
        # (ops/stream_plan.py plan_plane_passes, stack margin included)
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
    est, margin = stream_plan_vmem_bytes(dd, plan)
    if est + margin > cap:
        tags = ",fused" if plan.get("halo") == "fused" else ""
        return (
            f"plan {route}[m={plan.get('m', 1)}{tags}] models "
            f"{est / 1e6:.1f} MB of VMEM blocks (+{margin / 1e6:.1f} MB "
            f"stack) against the {cap / 1e6:.1f} MB budget"
        )
    return None


def check_traced(art, budget: Optional[int] = None) -> Optional[str]:
    """The ``vmem-budget`` contract's core: re-derive the footprint from the
    TRACED program — depth from the plan, plane dims and itemsizes from the
    3-D operands of the pallas calls actually in the jaxpr, never from the
    planner's choice of planes: only the arithmetic is shared
    (``stream_vmem_bytes``) — and gate it against the budget.  ``None`` when it fits, or when the artifact has no
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
    est = stream_vmem_bytes(
        int(plan.get("m", 1)), py, pz, itemsizes, z_slabs=bool(plan.get("z_slabs")),
        ring_itemsizes=ring_itemsizes, fused=plan.get("halo") == "fused",
    )
    margin = stack_margin(len(itemsizes))
    cap = budget if budget is not None else art.vmem_budget
    if cap is None:
        cap = _vmem_budget()
    if est + margin > cap:
        return (
            f"traced pallas planes ({py}, {pz}) at depth m="
            f"{plan.get('m', 1)} model {est / 1e6:.1f} MB of VMEM blocks "
            f"(+{margin / 1e6:.1f} MB stack) against the {cap / 1e6:.1f} MB "
            "budget"
        )
    return None
