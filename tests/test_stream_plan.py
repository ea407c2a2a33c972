"""The seam of the stream engine (ISSUE 42): a step's plan is RESOLVED once,
before anything is built (``ops/stream_plan.py resolve_stream_plan``), the
builder reads it and writes nothing (``ops/stream.py _build_stream_step``), and
one VMEM model prices a plan for the planner and for every prefilter."""

import ast
import copy
import dataclasses
import inspect
import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from stencil_tpu import DistributedDomain, Radius
from stencil_tpu.analysis import check_vmem
from stencil_tpu.analysis import programs as aprog
from stencil_tpu.analysis import vmem as avmem
from stencil_tpu.ops import stream as sm
from stencil_tpu.ops import stream_pass as spass
from stencil_tpu.ops import stream_plan as sp

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, "data", "program_fingerprints.json")) as f:
    STEP_LABELS = sorted(label for label in json.load(f) if label.startswith("step:"))

#: the canonical step programs, and the one route x schedule they leave out
#: (``analysis/programs.py`` says why it needs no contract of its own)
STEP_SPECS = {s.label: s for s in aprog.CANONICAL_PROGRAMS if s.label in STEP_LABELS}
STEP_SPECS["step:plane/split/direct"] = aprog.ProgramSpec(
    "step:plane/split/direct", stream_path="plane", overlap="split"
)


def test_the_step_programs_cover_every_route_and_schedule():
    assert set(STEP_LABELS) <= set(STEP_SPECS)
    covered = {
        (label.split("/")[0], "fused" if s.halo == "fused" else s.overlap)
        for label, s in STEP_SPECS.items()
    }
    assert covered >= {
        ("step:wrap", "off"), ("step:plane", "off"), ("step:plane", "split"),
        ("step:plane", "fused"), ("step:wavefront", "off"), ("step:wavefront", "split"),
        ("step:wavefront", "fused"),
    }, covered


@pytest.mark.parametrize("label", sorted(STEP_SPECS))
def test_the_builder_builds_from_a_read_only_plan(label, monkeypatch):
    """The builder is handed a ``MappingProxyType`` of the resolved plan and
    the program is built AND traced from it (the per-shard closures read the
    plan at trace time): one assignment into the plan raises ``TypeError``."""
    real_build, handed = sm._build_stream_step, []

    def read_only(dd, kernel, x_radius, plan, interpret, donate=True):
        handed.append(copy.deepcopy(dict(plan.plan)))
        frozen = dataclasses.replace(plan, plan=types.MappingProxyType(plan.plan))
        return real_build(dd, kernel, x_radius, frozen, interpret, donate)

    monkeypatch.setattr(sm, "_build_stream_step", read_only)
    art = aprog._build_program_uncached(STEP_SPECS[label])
    assert len(handed) == 1 and art.plan == handed[0]  # ... and nothing wrote behind it
    route = label.split(":")[1].split("/")[0]
    assert art.plan["route"] == route and {"alias", "overlap", "halo", "writers"} <= set(art.plan)


def _domain(names, size=16, radius=1, mult=1, n_dev=1):
    dd = DistributedDomain(size, size, size)
    dd.set_radius(Radius.constant(radius))
    dd.set_devices(jax.devices()[:n_dev])
    if mult > 1:
        dd.set_halo_multiplier(mult)
    for name in names:
        dd.add_data(name)
    dd.realize()
    return dd


def _mean2(views, info):
    return {nm: 0.5 * (v.sh(1, 0, 0) + v.sh(0, -1, 0)) for nm, v in views.items()}


_REQUESTS = [
    pytest.param(dict(path="wrap"), {}, id="wrap"),
    pytest.param(dict(path="plane"), {}, id="plane"),
    pytest.param(dict(path="plane"), {"overlap": "split", "overlap_forced": True}, id="plane-split"),
    pytest.param(dict(path="wavefront", mult=2), {}, id="wavefront-zslab"),
    pytest.param(dict(path="wavefront", mult=2), {"halo": "fused", "halo_forced": True},
                 id="wavefront-fused-degrades"),
]


@pytest.mark.parametrize("dom,extra", _REQUESTS)
def test_resolving_leaves_the_request_as_it_was(dom, extra):
    """``resolve_stream_plan`` returns a NEW dict and never writes to its
    argument; resolving the result again -- a resolved plan handed back in as
    a request -- gives the same plan, with nothing of the first resolution
    riding along."""
    dd = _domain(["u", "v"], mult=dom.get("mult", 1))
    request = dict(sp.plan_stream(dd, 1, dom["path"], False), **extra)
    before = copy.deepcopy(request)
    plan = sp.resolve_stream_plan(dd, _mean2, 1, request, True)
    assert request == before
    assert plan.plan is not request and set(request) < set(plan)
    assert all(plan[key] == request[key] for key in ("route", "m", "z_slabs", "grouping"))
    stale = dict(plan.plan, wired="xyz", wire_bytes=-1, macros_per_trip=7, lane_pad="hbm",
                 slab_wrap="q")
    again = sp.resolve_stream_plan(dd, _mean2, 1, stale, True)
    assert again.plan == plan.plan


def _stage_a(views, info):
    return {"u": views["u"].sh(1, 0, 0) + views["v"].center()}


def _stage_b(views, info):
    return {"v": views["v"].sh(0, 1, 0) - views["u"].center()}


@pytest.mark.parametrize("kernel,separable,calls", [
    pytest.param(_mean2, False, 1, id="one-stage"),
    pytest.param((_stage_a, _stage_b), False, 2, id="two-stages"),
    pytest.param(_mean2, True, 2, id="per-field-groups"),
])
def test_a_plane_rung_traces_each_group_of_each_stage_once(kernel, separable, calls, monkeypatch):
    """One ``trace_plane_kernel`` call a group a stage per rung: the plan that
    the prefilter judges IS the plan that is built, planned once."""
    real, seen = sp.trace_plane_kernel, []

    def spy(kernel, names, *a, **kw):
        seen.append(tuple(names))
        return real(kernel, names, *a, **kw)

    monkeypatch.setattr(sp, "trace_plane_kernel", spy)
    if separable:  # no joint pass fits, a pass over one quantity is the floor
        monkeypatch.setenv("STENCIL_VMEM_LIMIT_BYTES", str(4 * 1024 * 1024))
    dd = _domain(["u", "v"])
    step = sm.make_stream_step(dd, kernel, 1, path="plane", separable=separable, interpret=True)
    plan = step._stream_plan
    assert plan["grouping"] == ("per-field" if separable else "joint"), plan
    assert len(seen) == calls, seen
    assert len(plan["stages"]) == (2 if isinstance(kernel, tuple) else 1)
    dd.run_step(step, 2)
    assert len(seen) == calls, seen  # running plans nothing


def test_the_planner_and_the_prefilter_agree_on_the_wrap_route(monkeypatch):
    """PR 39's case -- 19 f32 quantities at 256^3 on one device, the budget
    pinned: ``plan_stream``'s search and a direct ``check_vmem`` call (the
    ladder's prefilter is off under interpret, so the CPU asks it itself) give
    the same verdict at m = 1, 2, 3, because both price the plan through one
    function."""
    assert avmem.stream_plan_vmem_bytes is sp.stream_plan_vmem_bytes
    monkeypatch.setenv("STENCIL_VMEM_LIMIT_BYTES", str(100 * 1024 * 1024))
    dd = DistributedDomain(256, 256, 256)
    dd.set_radius(Radius.constant(1))
    dd.set_devices(jax.devices()[:1])
    for q in range(19):
        dd.add_data(f"f{q}")
    dd.realize(allocate=False)
    planned = sp.plan_stream(dd, 1, "auto", False)
    assert (planned["route"], planned["m"], planned["grouping"]) == ("wrap", 2, "joint"), planned
    for m in (1, 2, 3):
        capped = sp.plan_stream(dd, 1, "wrap", False, max_m=m)
        verdict = check_vmem(dd, dict(planned, m=m))
        assert (verdict is None) == (capped["m"] == m) == (m <= planned["m"]), (m, verdict)


def _imports(module):
    tree = ast.parse(inspect.getsource(module))
    for node in ast.walk(tree):  # module level and function level alike
        if isinstance(node, ast.Import):
            yield from (a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            yield node.module
            yield from (f"{node.module}.{a.name}" for a in node.names)


def test_the_three_modules_import_one_way():
    """``stream`` imports ``stream_plan`` imports ``stream_pass``; nothing
    points the other way, at module level or inside a function."""
    above = {spass: ("stream_plan", "stream"), sp: ("stream",)}
    for module, banned in above.items():
        for name in _imports(module):
            assert not any(
                name.endswith(f"ops.{b}") or f"ops.{b}." in name for b in banned
            ), (module.__name__, name)
    assert any(n.endswith("ops.stream_pass") for n in _imports(sp))
    assert any(n.endswith("ops.stream_plan") for n in _imports(sm))


def test_the_builder_is_a_dispatch_and_writes_no_plan_key():
    lines = lambda fn: len(inspect.getsource(fn).splitlines())
    assert lines(sm._build_stream_step) < 40
    assert set(sm._ROUTE_BUILDERS) == {"wrap", "plane", "wavefront"}
    assert all(lines(build) <= 200 for build in sm._ROUTE_BUILDERS.values())
    tree = ast.parse(inspect.getsource(sm))
    writes = [
        node.lineno for node in ast.walk(tree)
        if isinstance(node, (ast.Assign, ast.AugAssign, ast.Delete))
        for target in (node.targets if hasattr(node, "targets") else [node.target])
        if isinstance(target, ast.Subscript) and isinstance(target.value, ast.Name)
        and target.value.id.startswith("plan")
    ]
    assert not writes, writes


# --- the plane pass's working plane (ISSUE 45) --------------------------------


def _benchmark_plane_model(cell):
    """The model of a benchmark plane cell at its REAL extent, nothing
    allocated, and the stages of its step."""
    if cell.startswith("astaroth-mhd-256"):
        from stencil_tpu.models.astaroth_mhd import RADIUS, AstarothMHD
        from stencil_tpu.models.astaroth_mhd_reference import MhdSetup

        # (the four-chip cell: 256^3 a device on the one-chip cell's uniform spacing)
        shape, n_dev = ((512, 512, 256), 4) if cell.endswith("x4") else ((256,) * 3, 1)
        box = tuple(MhdSetup((256,) * 3).box / 256 * n for n in shape)
        sim = AstarothMHD(*shape, setup=MhdSetup(shape, box=box), devices=jax.devices()[:n_dev],
                          seed_words=None)
        stages = lambda: tuple(sim._substep(s) for s in range(3))  # noqa: E731
    elif cell == "elastic-so8-600":
        from stencil_tpu.models.elastic import RADIUS, ElasticWave

        sim = ElasticWave(600, 600, 600, devices=jax.devices()[:1], seed_words=None)
        stages = lambda: (sim._stage_v, sim._stage_t)  # noqa: E731
    else:
        from stencil_tpu.models.acoustic import RADIUS, AcousticWave

        extent, n_dev = ((1200, 1200, 600), 4) if cell.endswith("x4") else ((600, 600, 600), 1)
        sim = AcousticWave(*extent, devices=jax.devices()[:n_dev], seed_words=None)
        stages = lambda: sim._kernel  # noqa: E731
    sim.dd.realize(allocate=False)
    return sim, stages(), RADIUS


#: rows of a strip of the MHD cell's passes (256 x 256 interior planes of f32:
#: ``stream_pass._STRIP_VREGS`` vregs a value)
MHD_STRIP = 16


@pytest.mark.parametrize("cell,wrapped,window", [
    ("astaroth-mhd-256", "yz", "interior"),  # 256 = 32 x 8 sublanes = 2 x 128 lanes
    ("acoustic-so8-600", "yz", "raw"),  # 600 = 4 x 128 + 88 lanes
    ("elastic-so8-600", "yz", "raw"),
    ("acoustic-so8-1200x4", "z", "raw"),  # mesh [2, 2, 1] splits y
    # ... and so it does here, beside whole tiles (ISSUE 48): the z halo the rotates'
    # wraparound, the neighbours' y halo rows in the tiles
    ("astaroth-mhd-256x4", "z", "interior-z"),
])
def test_the_benchmarks_plane_cells_resolve_their_window(cell, wrapped, window, monkeypatch):
    """``plan["plane_window"]`` of the five benchmark plane configurations at
    their real extents, the blend kernels on as on the chip: from the fills
    the plan resolved and the block's static shape alone -- no option, no
    model's name -- and ``domain.step`` says it beside ``wrapped``."""
    from stencil_tpu.ops import halo_blend

    monkeypatch.setattr(halo_blend, "pallas_interpret", lambda: False)
    sim, stages, r = _benchmark_plane_model(cell)
    assert tuple(sim.dd.mesh_dim()) == ((2, 2, 1) if cell.endswith("x4") else (1, 1, 1))
    plan = sp.resolve_stream_plan(sim.dd, stages, r, sp.plan_stream(sim.dd, r, "plane", False), False)
    assert (plan["pass_wrap_axes"], plan["plane_window"]) == (wrapped, window), plan.plan
    raw, n = sim.dd.local_spec().raw_size(), sim.dd.local_spec().sz
    shell = sim.dd._shell_radius
    assert plan["plane_window"] == spass.plane_window_form(
        plan.wrap_fills, shell.lo(), shell.hi(), (raw.y, raw.z),
        [sim.dd.field_dtype(h) for h in sim.dd._handles],
    )
    said = sm.stream_span_args(plan, r, len(sim.dd._handles))
    assert (said["wrapped"], said["plane_window"]) == (wrapped, window)
    # ... and the rows of it the kernel is evaluated over at a time (ISSUE 46):
    # strips of MHD_STRIP rows on the two aligned windows, the plane whole on the raw one
    strip = 0 if window == "raw" else MHD_STRIP
    assert plan["plane_strip"] == said["plane_strip"] == strip
    assert strip == spass.plane_strip_rows(window, (n.y, n.z), [jnp.float32], r)
    # the rings are priced at the plane they hold: the raw plane's on the raw
    # window; on the interior window, in the strip form, the interior as tiles
    # between r margin tiles a side (beside a split y: before the lo.y + hi.y
    # margin tiles that carry the y halo rows, as many here: the twin's shapes
    # and the twin's 96,955,392 B a pass) -- 2r + 1 deep for a ringed quantity (the
    # newest plane is pushed before the strips read it), one plane for every
    # other --, a staging plane of tiles a writer and the planes rotated once
    # for all their readers (the one VMEM model)
    pad = sp._padded_plane_bytes
    # every pass of the five moves WHOLE planes (ISSUE 51: y tiles are the
    # planner's answer only where it used to raise)
    assert (plan["tile_rows"], plan["y_tiles"], said["tile_rows"], said["y_tiles"]) == (0, 1, 0, 1)
    for st in plan["stages"]:
        for p in st["passes"]:
            assert p["tile_rows"] == 0
            blocks = 2 * (len(p["reads"]) + len(p["writes"])) * pad(raw.y, raw.z, 4)
            if strip:
                # ... and the 24 planes rotated once a grid step: the four fields
                # whose y-z mixed differences share the z shifts of their
                # differences along z (``shared_rotations``)
                assert len(p["prerotated"]) == 24 and {dx for _, dx, _ in p["prerotated"]} == {0}
                held = ((2 * r + 1) * len(p["rings"]) + len(p["reads"]) - len(p["rings"]) + 24) * pad(
                    n.y + 2 * r * 8, n.z, 4) + len(p["writes"]) * pad(n.y, n.z, 4)
            else:
                held = 2 * r * len(p["rings"]) * pad(raw.y, raw.z, 4)
            assert p["vmem_bytes"] == blocks + held + sp._VMEM_STACK_MARGIN * len(p["reads"]), p
            if strip:
                assert p["vmem_bytes"] == 96_955_392, p
    # a request that turns the schedule off the default one keeps the raw plane
    split = dict(sp.plan_stream(sim.dd, r, "plane", False), overlap="split", overlap_forced=True)
    if cell.startswith("astaroth-mhd-256"):
        assert sp.resolve_stream_plan(sim.dd, stages, r, split, False)["plane_window"] == "raw"


def _lag_kernel(views, info):
    u, c = views["u"], views["c"]
    new = 0.5 * u.center() + 0.25 * (u.sh(2, 0, 0) + u.sh(0, -2, 2)) + c.sh(0, 1, 0)
    return {"u": new, "p": u.center()}


@pytest.mark.parametrize("window", ["interior", "interior-in-strips", "raw", "interior-z-in-strips"])
@pytest.mark.parametrize("storage", ["native", "bf16"])
def test_the_vmem_model_is_what_the_pass_allocates(window, storage, monkeypatch):
    """``plane_pass_vmem_bytes`` against the traced Pallas call of the pass, in
    both forms and both storages: two tile-padded buffers a pipelined block
    (every operand, every result), the scratch as allocated, the stack margin
    a quantity read -- the pass allocates what the one model charges, ring
    planes of the interior included; beside a split y (ISSUE 48, mesh [1,2,1])
    the tiles of raw rows ``[0, Yi)`` before the ``lo.y + hi.y`` margin tiles
    that carry the y halo rows."""
    from stencil_tpu.analysis import jaxpr as jx

    monkeypatch.setenv("STENCIL_HALO_BLEND", "1")
    if window == "raw":
        monkeypatch.setattr(sp, "plane_window_form", lambda *a: "raw")
    window, strips = window.split("-in-")[0], window.endswith("strips")
    if strips:  # (a light kernel: the planner keeps it over whole planes)
        monkeypatch.setattr(sp, "_STRIP_MIN_OPS", 0)
    split_y = window == "interior-z"  # four tiles of rows a shard: as many as its y shell has rows
    rows = (2 if split_y else 1) * (16 if storage == "native" else 32)
    dd = DistributedDomain(8, 2 * rows if split_y else rows, 128)
    dd.set_radius(Radius.constant(2))
    dd.set_devices(jax.devices()[: 2 if split_y else 1])
    if split_y:
        dd.set_partition(1, 2, 1)
    if storage != "native":
        dd.set_storage(storage)
    for name in ("u", "c", "p"):
        dd.add_data(name)
    dd.realize()
    plan = sp.resolve_stream_plan(dd, _lag_kernel, 2, sp.plan_stream(dd, 2, "plane", False), True)
    assert (plan["pass_wrap_axes"], plan["plane_window"]) == ("z" if split_y else "yz", window)
    (p,) = plan["stages"][0]["passes"]
    assert (p["reads"], p["rings"], p["writes"], p["renames"]) == (
        ("u", "c", "p"), ("u",), ("u",), (("p", "u"),))
    step = sm._build_stream_step(dd, _lag_kernel, 2, plan, interpret=True)
    closed = jax.make_jaxpr(step, static_argnums=1)(dd._curr, 1)
    (call,) = [
        e for e in jx.iter_eqns(closed)
        if e.primitive.name == "pallas_call" and "stream_plane_pass" in str(e.params.get("name"))
    ]
    gm = call.params["grid_mapping"]
    pad = lambda shape, dtype: sp._padded_plane_bytes(  # noqa: E731
        shape[-2], shape[-1], dtype.itemsize) * int(np.prod(shape[:-2]))
    blocks = [bm for bm in gm.block_mappings if len(bm.block_shape) == 3]  # not ``origin``
    assert len(blocks) == len(p["reads"]) + len(p["writes"])
    allocated = sum(
        2 * pad(tuple(int(getattr(b, "block_size", 1)) for b in bm.block_shape), bm.array_aval.dtype)
        for bm in blocks
    )
    allocated += sum(pad(sc.shape, sc.dtype) for sc in gm.scratch_avals)
    assert p["vmem_bytes"] == allocated + sp._VMEM_STACK_MARGIN * len(p["reads"])
    tile = rows // (4 if split_y else 2)  # a sublane tile of the stored dtype
    if split_y:
        # the shard's four tiles of raw rows [0, Yi) and the four margin tiles
        # behind them, whose last sublane is a tail row of the block
        assert plan["plane_strip"] == rows and p["prerotated"] == ()
        ring, *lagged, stage = gm.scratch_avals
        assert [sc.shape for sc in (ring, *lagged, stage)] == [
            (5, 8, tile, 128), (1, 8, tile, 128), (1, 8, tile, 128), (4, tile, 128)]
    elif not strips:
        assert plan["plane_strip"] == 0
        (ring,) = gm.scratch_avals
        assert ring.shape == ((4, rows + 4, 132) if window == "raw" else (4, rows, 128))
    else:
        # the strip form (ISSUE 46): every quantity's interior as its two tiles
        # between two margin tiles a side -- ``u`` ringed 2r + 1 deep, ``c`` and
        # ``p`` one plane -- and the writer's staging plane of tiles
        assert plan["plane_strip"] == rows and p["prerotated"] == ()
        ring, *lagged, stage = gm.scratch_avals
        assert [sc.shape for sc in (ring, *lagged, stage)] == [
            (5, 6, rows // 2, 128), (1, 6, rows // 2, 128), (1, 6, rows // 2, 128),
            (2, rows // 2, 128)]
    assert ring.dtype == dd.field_dtype(dd._handles[0])  # the rings hold STORED planes


def test_the_step_span_carries_the_plane_window(monkeypatch):
    """``domain.step`` says ``plane_window`` on every dispatch of a plane
    step, beside ``wrapped``; a step off the plane route does not say it."""
    from stencil_tpu import telemetry
    from stencil_tpu.telemetry import names as tm

    monkeypatch.setenv("STENCIL_HALO_BLEND", "1")
    seen, real = [], telemetry.span

    def spy(name, *a, **kw):
        seen.append((name, kw))
        return real(name, *a, **kw)

    monkeypatch.setattr(telemetry, "span", spy)
    monkeypatch.setattr(sp, "_STRIP_MIN_OPS", 0)  # (``_mean2`` is a light kernel)
    for extent, path, want, strip in (
            ((8, 16, 128), "plane", "interior", 16), ((8, 16, 96), "plane", "raw", 0),
            ((8, 32, 128), "plane", "interior-z", 16),  # mesh [1,2,1]: y arrives over a wire (ISSUE 48)
            ((8, 16, 128), "wrap", None, None)):
        dd = DistributedDomain(*extent)
        dd.set_radius(Radius.constant(1))
        dd.set_devices(jax.devices()[: 2 if want == "interior-z" else 1])
        if want == "interior-z":
            dd.set_partition(1, 2, 1)
        dd.add_data("u")
        dd.add_data("v")
        dd.realize()
        step = dd.make_step(_mean2, engine="stream", stream_path=path, interpret=True)
        del seen[:]
        dd.run_step(step, 2)
        dd.run_step(step, 2)
        said = [kw for name, kw in seen if name == tm.SPAN_STEP]
        assert len(said) == 2 and all(kw.get("plane_window") == want for kw in said), said
        assert all(kw.get("plane_strip") == strip for kw in said), said  # (ISSUE 46)
        wrapped = {"interior-z": "z", None: ""}.get(want, "yz")
        assert all(kw["wrapped"] == wrapped for kw in said), said
