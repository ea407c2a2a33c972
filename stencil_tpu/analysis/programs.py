"""The canonical program matrix — the REAL built artifacts the contracts
verify, swept over route × overlap × halo × storage-dtype in
interpret/CPU mode (the tier-1 gate
``tests/test_analysis.py::test_canonical_programs_verify`` and the CLI both
run exactly this list).

Each spec builds a small realized domain on the fake 8-chip mesh (the
conftest trick), builds the step / exchange the spec names, and traces it
to a :class:`~stencil_tpu.analysis.framework.ProgramArtifact`.  Domains are
16³ (or 17³ for the padded/uneven variants — a 17-cell axis over 2 shards
forces the pad-and-mask path and, with it, the PLAIN wavefront form).

Traces are taken under ``STENCIL_HALO_BLEND=1``: the blend kernels are the
TPU-shaped lowering of the y/z halo writes (their absence on CPU would
re-introduce the very sliver writes the ``sliver-dus`` contract hunts),
exactly as the bitwise blend tests force it.

The coverage ledger (``stencil_tpu/analysis/registry.py``) mirrors which
axis values this matrix exercises; ``tests/test_analysis.py::
test_registry_matches_matrix`` pins the two against each other, and the
``contract-coverage`` lint rule fails any ops/ module growing an axis
vocabulary past the ledger.
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
from typing import Iterable, List, Optional

from stencil_tpu.analysis.framework import ProgramArtifact, step_artifact, trace_artifact

#: devices the matrix needs (the conftest fake-8-chip fleet)
MATRIX_DEVICES = 8


def mean6_kernel(views, info):
    """The canonical 7-point mean — the same kernel every structural test
    streams (all shifts within radius 1, elementwise, separable)."""
    out = {}
    for name, src in views.items():
        out[name] = (
            src.sh(-1, 0, 0) + src.sh(1, 0, 0)
            + src.sh(0, -1, 0) + src.sh(0, 1, 0)
            + src.sh(0, 0, -1) + src.sh(0, 0, 1)
        ) / 6.0
    return out


@dataclasses.dataclass(frozen=True)
class ProgramSpec:
    """One canonical program: what to build and which axes it exercises."""

    label: str
    kind: str = "step"  # "step"|"exchange"|"redistribute"|"numerics"|"serve"
    size: tuple = (16, 16, 16)
    n_devices: int = MATRIX_DEVICES
    halo_mult: int = 1
    n_fields: int = 1
    exchange_route: str = "direct"
    stream_path: str = "auto"
    overlap: str = "off"
    halo: str = "array"
    storage_dtype: str = "native"
    reshard_to: tuple = ()  # redistribute only: the target mesh dim
    serve_mode: str = ""  # serve only: "batched" | "subslice" (pack.SERVE_MODES)

    @property
    def axes(self) -> dict:
        return {
            "route": self.stream_path,
            "overlap": self.overlap,
            "halo": self.halo,
            "exchange_route": self.exchange_route,
            "storage_dtype": self.storage_dtype,
        }


#: the matrix.  Route notes: at halo-mult 2 the auto plan is the z-slab
#: wavefront; a split request re-plans to the PLAIN form, and the padded
#: 17³ variants force the plain form under overlap=off too — so both
#: wavefront forms, the plane baseline, and the single-device wrap route
#: are all traced.  The z-slab entry keeps its per-level slab permutes
#: (exchange-structure pins the generic exchange via the exchange:* entries
#: instead — see that contract's ``applies_to``).
CANONICAL_PROGRAMS: List[ProgramSpec] = [
    ProgramSpec("step:wrap/off", n_devices=1),
    ProgramSpec("step:plane/off/direct", stream_path="plane"),
    # (No plane/split program: both wavefront/split programs exercise every
    # split-schedule contract clause — interior independence, exterior
    # taint, band-blend sliver hygiene — and the plane route stays covered
    # at overlap=off by two programs; no contract discriminates plane×split
    # from wavefront×split.)
    ProgramSpec(
        "step:plane/off/zpack_pallas",
        stream_path="plane",
        exchange_route="zpack_pallas",
        n_fields=2,
    ),
    ProgramSpec(
        "step:wavefront/off/direct/uneven", size=(17, 17, 17), halo_mult=2
    ),
    ProgramSpec("step:wavefront/off/direct/zslab", halo_mult=2, n_fields=2),
    ProgramSpec("step:wavefront/split/direct", halo_mult=2, overlap="split"),
    ProgramSpec(
        "step:wavefront/split/zpack_xla",
        halo_mult=2,
        overlap="split",
        exchange_route="zpack_xla",
        n_fields=2,
    ),
    # the one-field even wavefront at overlap=off (auto-planned to the
    # z-slab form like the two-field entry above; 16³ at mult 2 shards to
    # 12-wide raw planes)
    ProgramSpec("step:wavefront/off/direct", halo_mult=2),
    ProgramSpec(
        "step:wavefront/off/direct/bf16/uneven",
        size=(17, 17, 17),
        halo_mult=2,
        storage_dtype="bf16",
    ),
    ProgramSpec(
        "step:wavefront/off/yzpack_pallas/fused",
        halo_mult=2,
        exchange_route="yzpack_pallas",
        halo="fused",
    ),
    ProgramSpec(
        "step:plane/off/yzpack_xla/fused",
        stream_path="plane",
        exchange_route="yzpack_xla",
        halo="fused",
        n_fields=2,
    ),
    ProgramSpec("exchange:direct", kind="exchange", halo_mult=2, n_fields=2),
    # axes the mesh does not split sweep by the self-wrap kernel
    # (ops/halo_blend.py wrap_halo): z on the 4-chip [2,2,1] mesh beside real
    # x/y wires, and all three on one chip -- an exchange with no ppermute
    ProgramSpec(
        "exchange:direct/unsplit-z",
        kind="exchange",
        n_devices=4,
        halo_mult=2,
        n_fields=2,
    ),
    ProgramSpec("exchange:direct/one-chip", kind="exchange", n_devices=1),
    ProgramSpec(
        "exchange:zpack_xla",
        kind="exchange",
        halo_mult=2,
        exchange_route="zpack_xla",
    ),
    ProgramSpec(
        "exchange:zpack_pallas",
        kind="exchange",
        halo_mult=2,
        exchange_route="zpack_pallas",
        n_fields=2,
    ),
    ProgramSpec(
        "exchange:yzpack_xla",
        kind="exchange",
        halo_mult=2,
        exchange_route="yzpack_xla",
        n_fields=2,
    ),
    ProgramSpec(
        "exchange:yzpack_pallas",
        kind="exchange",
        halo_mult=2,
        exchange_route="yzpack_pallas",
    ),
    # the numerics observatory's fused stats program (telemetry/numerics.py)
    # on its hardest geometry: an UNEVEN halo-multiplier multi-quantity
    # domain — pad-and-mask validity masking, mult-2 shell offsets, and two
    # quantities through one dispatch.  The numerics-bounded contract holds
    # the scalar-outputs / no-gather / psum-reduced claims on exactly the
    # program the sentinel and the snapshot cadence dispatch.
    ProgramSpec(
        "numerics:stats/uneven",
        kind="numerics",
        size=(17, 17, 17),
        halo_mult=2,
        n_fields=2,
    ),
    # the elastic-capacity collective (parallel/redistribute.py): a shrink
    # of an UNEVEN halo-multiplier domain from the full 8-chip mesh onto 4
    # chips — the redistribute-bounded contract holds its staging bound
    # and no-gather claim on the really-planned schedule (uneven shards
    # and mult-2 shells give the chunk decomposition its hardest shapes)
    ProgramSpec(
        "redistribute:2x2x2->2x2x1/uneven",
        kind="redistribute",
        size=(17, 17, 17),
        halo_mult=2,
        reshard_to=(2, 2, 1),
    ),
    # the serving layer's packed dispatches (serve/pack.py — one program
    # per SERVE_MODES value, the batch-isolation contract's corpus):
    # "batched" traces the REAL batched callable (make_batched_dispatch
    # over a full-fleet XLA-engine step, leading batch axis 4) and pins
    # that no collective ever communicates over the batch axis and every
    # output keeps its batch dim; "subslice" traces two tenants' steps on
    # DISJOINT 4-chip sub-meshes through one program and pins that no
    # tenant's outputs are reachable from another tenant's inputs and
    # every shard_map stays confined to its tenant's device set.
    ProgramSpec("serve:batched", kind="serve", serve_mode="batched"),
    ProgramSpec(
        "serve:subslice", kind="serve", serve_mode="subslice", n_devices=4
    ),
]


def covered_axis_values() -> dict:
    """{axis tuple name: set of values the matrix exercises} — derived from
    the spec list, compared against the jax-free coverage ledger by
    ``test_registry_matches_matrix``."""
    out = {
        "EXCHANGE_ROUTES": set(),
        "STREAM_OVERLAP": set(),
        "STREAM_HALO": set(),
        "STORAGE_DTYPES": set(),
    }
    out["SERVE_MODES"] = set()
    for s in CANONICAL_PROGRAMS:
        if s.kind == "serve":
            # a serve program's step axes are incidental (the packers ride
            # whatever steps the tenants built); only its MODE is coverage
            out["SERVE_MODES"].add(s.serve_mode)
            continue
        out["EXCHANGE_ROUTES"].add(s.exchange_route)
        out["STREAM_OVERLAP"].add(s.overlap)
        out["STREAM_HALO"].add(s.halo)
        out["STORAGE_DTYPES"].add(s.storage_dtype)
    return out


@contextlib.contextmanager
def tpu_shaped_trace():
    """Force the TPU-shaped lowering knobs for a CPU trace: blend kernels
    on (their absence is a CPU-only divergence that would hide/seed sliver
    writes the contracts pin)."""
    # stencil-lint: disable=env-read save/restore WRITES of the knob around a trace, not a config consult — the consuming read stays validated in ops/halo_blend.py
    prev = os.environ.get("STENCIL_HALO_BLEND")
    # stencil-lint: disable=env-read see above: this is the write half of the save/restore
    os.environ["STENCIL_HALO_BLEND"] = "1"
    try:
        yield
    finally:
        if prev is None:
            os.environ.pop("STENCIL_HALO_BLEND", None)
        else:
            # stencil-lint: disable=env-read restore half of the save/restore write
            os.environ["STENCIL_HALO_BLEND"] = prev


def _build_domain(spec: ProgramSpec):
    import jax
    import jax.numpy as jnp

    from stencil_tpu.core.radius import Radius
    from stencil_tpu.domain import DistributedDomain

    devices = jax.devices()
    if len(devices) < spec.n_devices:
        raise RuntimeError(
            f"canonical matrix needs {spec.n_devices} devices, have "
            f"{len(devices)} — run under the fake-8-chip CPU config "
            "(conftest / the analysis CLI set it up)"
        )
    dd = DistributedDomain(*spec.size)
    dd.set_radius(Radius.constant(1))
    dd.set_devices(devices[: spec.n_devices])
    if spec.n_devices > 1:
        dd.set_exchange_route(spec.exchange_route)
    if spec.halo_mult > 1:
        dd.set_halo_multiplier(spec.halo_mult)
    if spec.storage_dtype != "native":
        dd.set_storage(spec.storage_dtype)
    handles = [dd.add_data(f"q{i}") for i in range(spec.n_fields)]
    dd.realize()
    for i, h in enumerate(handles):
        dd.init_by_coords(
            h, lambda x, y, z, i=i: jnp.sin(0.13 * (x + 2 * y + 3 * z) + i)
        )
    return dd


def _redistribute_artifact(spec: ProgramSpec, dd) -> ProgramArtifact:
    """Trace the really-planned redistribution schedule source mesh ->
    ``spec.reshard_to`` (the exact jitted program ``DistributedDomain.
    reshard`` dispatches), with the staging bound in ``meta``."""
    import jax

    from stencil_tpu.core.radius import Radius
    from stencil_tpu.domain import DistributedDomain
    from stencil_tpu.parallel.redistribute import (
        SideGeometry,
        plan_redistribution,
        redistribution_program,
    )

    n_target = 1
    for v in spec.reshard_to:
        n_target *= v
    tgt = DistributedDomain(*spec.size)
    tgt.set_radius(Radius.constant(1))
    tgt.set_devices(jax.devices()[:n_target])
    tgt.set_partition(*spec.reshard_to)
    if spec.halo_mult > 1:
        tgt.set_halo_multiplier(spec.halo_mult)
    tgt.realize(allocate=False)  # geometry only — the plan needs no arrays
    plan = plan_redistribution(
        tuple(spec.size),
        SideGeometry.of_domain(dd),
        SideGeometry.of_domain(tgt),
    )
    fn, example, meta = redistribution_program(plan)
    closed = jax.make_jaxpr(fn)(example)
    return ProgramArtifact(
        label=spec.label,
        kind="redistribute",
        closed=closed,
        n_devices=len(plan.union_devices),
        meta=meta,
    )


def _numerics_artifact(spec: ProgramSpec, dd) -> ProgramArtifact:
    """Trace the fused numerics stats program — exactly the jitted
    callable ``NumericsEngine.snapshot`` dispatches — with the quantity
    count in ``meta`` for the scalar-output bound."""
    import jax

    from stencil_tpu.telemetry.numerics import NumericsEngine

    fn, args, names = NumericsEngine(dd).program()
    closed = jax.make_jaxpr(fn)(*args)
    return ProgramArtifact(
        label=spec.label,
        kind="numerics",
        closed=closed,
        dd=dd,
        n_devices=spec.n_devices,
        meta={"n_quantities": len(names)},
    )


def _serve_artifact(spec: ProgramSpec, dd) -> ProgramArtifact:
    """Trace the serving layer's packed-dispatch programs (serve/pack.py)
    for the batch-isolation contract.

    ``batched`` — the REAL batched callable (``ops/stream.py
    make_batched_dispatch``) over a full-fleet XLA-engine step, batch 4;
    meta carries the batch extent and the mesh axis names so the contract
    can pin "no collective over the batch axis" and "outputs keep the
    batch dim".

    ``subslice`` — two tenants' steps on DISJOINT sub-meshes (devices
    [0:n) and [n:2n)) traced through ONE program ``(cA, cB) -> (outA,
    outB)``; meta carries the per-tenant input/output leaf counts (the
    pytree flatten order: tenant A's fields then tenant B's) and device
    sets so the contract can hold the cross-tenant taint and shard_map
    confinement claims."""
    import jax
    import jax.numpy as jnp

    from stencil_tpu.ops.stream import make_batched_dispatch
    from stencil_tpu.parallel.mesh import MESH_AXES

    if spec.serve_mode == "batched":
        step = dd.make_step(mean6_kernel, donate=False)
        batched = make_batched_dispatch(step, 1, "vmap")
        batch = 4
        stacked = {
            k: jnp.stack([v] * batch) for k, v in dd._curr.items()
        }
        closed = jax.make_jaxpr(batched)(stacked)
        return ProgramArtifact(
            label=spec.label,
            kind="serve",
            closed=closed,
            dd=dd,
            n_devices=spec.n_devices,
            meta={
                "mode": "batched",
                "batch": batch,
                "mesh_axes": tuple(MESH_AXES),
            },
        )
    from stencil_tpu.core.radius import Radius
    from stencil_tpu.domain import DistributedDomain

    devices = jax.devices()
    dd_b = DistributedDomain(*spec.size)
    dd_b.set_radius(Radius.constant(1))
    dd_b.set_devices(devices[spec.n_devices : 2 * spec.n_devices])
    handles = [dd_b.add_data(f"q{i}") for i in range(spec.n_fields)]
    dd_b.realize()
    for i, h in enumerate(handles):
        dd_b.init_by_coords(
            h, lambda x, y, z, i=i: jnp.cos(0.11 * (x + 2 * y + 3 * z) + i)
        )
    step_a = dd.make_step(mean6_kernel, donate=False)
    step_b = dd_b.make_step(mean6_kernel, donate=False)

    def both(c_a, c_b):
        return step_a(c_a, 1), step_b(c_b, 1)

    closed = jax.make_jaxpr(both)(dd._curr, dd_b._curr)
    sets = [
        sorted(d.id for d in dd.mesh.devices.flat),
        sorted(d.id for d in dd_b.mesh.devices.flat),
    ]
    return ProgramArtifact(
        label=spec.label,
        kind="serve",
        closed=closed,
        dd=dd,
        n_devices=2 * spec.n_devices,
        meta={
            "mode": "subslice",
            "input_groups": [len(dd._curr), len(dd_b._curr)],
            "output_groups": [len(dd._curr), len(dd_b._curr)],
            "device_sets": sets,
        },
    )


#: traced canonical programs memoized by label — tracing the 22-program
#: matrix costs ~tens of seconds and every per-contract consumer
#: (tests/test_analysis.py's contract tests, repeated in-process CLI
#: calls, the kernel verifier's report sweep) hits the same specs; an
#: artifact is immutable-in-practice (contracts only read it), so sharing
#: is safe.  ``reset_program_cache`` is the test-isolation hook.
_PROGRAM_MEMO: dict = {}


def reset_program_cache() -> None:
    _PROGRAM_MEMO.clear()


def build_program(spec: ProgramSpec) -> ProgramArtifact:
    """Build and trace one canonical program (interpret/CPU mode), memoized
    by label across contracts and callers (see ``_PROGRAM_MEMO``)."""
    cached = _PROGRAM_MEMO.get(spec.label)
    if cached is not None:
        return cached
    art = _build_program_uncached(spec)
    _PROGRAM_MEMO[spec.label] = art
    return art


def _build_program_uncached(spec: ProgramSpec) -> ProgramArtifact:
    with tpu_shaped_trace():
        dd = _build_domain(spec)
        if spec.kind == "serve":
            return _serve_artifact(spec, dd)
        if spec.kind == "numerics":
            return _numerics_artifact(spec, dd)
        if spec.kind == "redistribute":
            return _redistribute_artifact(spec, dd)
        if spec.kind == "exchange":
            fn = dd.make_exchange_route_fn(spec.exchange_route, donate=False)
            return trace_artifact(
                fn,
                dd._curr,
                label=spec.label,
                kind="exchange",
                axes=spec.axes,
                dd=dd,
                n_devices=spec.n_devices,
            )
        kw = dict(
            engine="stream",
            interpret=True,
            stream_path=spec.stream_path,
            stream_overlap=spec.overlap,
            stream_halo=spec.halo,
        )
        step = dd.make_step(mean6_kernel, **kw)
        return step_artifact(dd, step, label=spec.label, axes=spec.axes)


def build_matrix(
    labels: Optional[Iterable[str]] = None,
) -> List[ProgramArtifact]:
    """Build every canonical program (or the named subset)."""
    wanted = set(labels) if labels is not None else None
    if wanted is not None:
        known = {s.label for s in CANONICAL_PROGRAMS}
        unknown = wanted - known
        if unknown:
            raise ValueError(
                f"unknown program(s) {sorted(unknown)}; known: {sorted(known)}"
            )
    return [
        build_program(s)
        for s in CANONICAL_PROGRAMS
        if wanted is None or s.label in wanted
    ]
