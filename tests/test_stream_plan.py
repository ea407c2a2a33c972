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
    stale = dict(plan.plan, wired="xyz", wire_bytes=-1, macros_per_trip=7, lane_pad="hbm")
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
