"""The wrap route's dispatch carries the domain's raw blocks at its two edges
(ISSUE 52).

Until ISSUE 52 a dispatch of the stream engine's wrap route cut every
quantity's bare interior out of its raw block with ``lax.slice`` and landed the
result with ``lax.dynamic_update_slice`` (a tenth of ``lbm-d3q19-256.bulk``).
Now the first ``stream_wrap_pass`` of a dispatch reads the raw blocks
(``raw_in``) and the last one writes them, in place (``raw_out``), wherever the
y-z interior is whole vector tiles and the two larger pipeline planes fit
(``ops/stream_plan.py wrap_edge_form``; ``domain.step`` says ``edges``).  These
tests hold that step, bitwise on every interior cell, to the parent's
formulation kept here as the plain reference; hold the shell it leaves to the
periodic image (y, z) and to the operand (x); hold the traced program to no cut
and no write-back; and hold the planner to the depth it chose before it asked.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from stencil_tpu import DistributedDomain, Radius, telemetry
from stencil_tpu.analysis import jaxpr as jx
from stencil_tpu.ops import stream as sm
from stencil_tpu.ops import stream_pass as spass
from stencil_tpu.ops import stream_plan as sp
from stencil_tpu.telemetry import names as tm

M = 2  # temporal depth of every build here: a macro is two raw steps
X = 8


def _ring(views, info):
    """Every quantity reads itself on all three axes, three times diagonally,
    the NEXT quantity diagonally too (itself where it is handed one view: the
    kernel is separable) and its own coordinates.  Sums and one division by a
    power of two only: nothing the CPU compiler could contract into a fused
    multiply-add, or turn into a multiplication by a rounded reciprocal, in
    one program and not in the other."""
    names = list(views)
    cx, cy, cz = info.coords()
    out = {}
    for i, nm in enumerate(names):
        v, o = views[nm], views[names[(i + 1) % len(names)]]
        where = ((cx + cy + cz) % 4).astype(jnp.float32)
        out[nm] = (v.center() + v.sh(1, 0, 0) + v.sh(-1, 0, 0) + v.sh(0, 1, -1) + v.sh(0, -1, 1)
                   + v.sh(1, 1, 0) + v.sh(-1, 0, -1) + o.sh(0, -1, -1) + where) / 8.0
    return out


def _domain(nq, r, storage):
    """``X`` x one sublane tile x one lane tile of interior under an
    ``r``-wide shell, EVERY raw cell seeded (the shell too: the step must leave
    its x halo planes alone)."""
    rows = 16 if storage == "bf16" else 8
    dd = DistributedDomain(X, rows, 128)
    dd.set_radius(Radius.constant(r))
    dd.set_devices(jax.devices()[:1])
    dd.set_storage(storage)
    hs = [dd.add_data(f"q{q}", dtype=jnp.float32) for q in range(nq)]
    dd.realize()
    raw = dd.local_spec().raw_size()
    rng = np.random.default_rng(52)
    for h in hs:
        seeded = jnp.asarray(rng.random((raw.x, raw.y, raw.z), dtype=np.float32), dd.field_dtype(h))
        dd._curr[h.name] = jax.device_put(seeded, dd._curr[h.name].sharding)
    return dd


def _plan(dd, grouping):
    request = dict(sp.plan_stream(dd, 1, "wrap", True, max_m=M), grouping=grouping)
    plan = sp.resolve_stream_plan(dd, _ring, 1, request, True)
    assert (plan["route"], plan["m"], plan["grouping"], plan["edges"]) == ("wrap", M, grouping, "raw"), plan
    return plan


def xla_edged_step(dd, plan, steps):
    """The dispatch as the parent commit formulated it, from the bare pass
    alone: ``lax.slice`` cuts the interiors, ``steps // m`` bare passes and a
    remainder pass run on them, ``lax.dynamic_update_slice`` lands them."""
    names = [h.name for h in dd._handles]
    lo, n = dd._shell_radius.lo(), dd.local_spec().sz
    f32_acc = any(dd.field_dtype(h) != h.dtype for h in dd._handles)
    groups = sp._stream_groups(plan, len(names))
    blocked, rem = divmod(steps, plan["m"])

    def macro(depth, bs):
        out = list(bs)
        for grp in groups:
            outs = spass.stream_wrap_pass(
                _ring, [names[q] for q in grp], [bs[q] for q in grp], depth,
                jnp.zeros(3, jnp.int32), dd._size, interpret=True, f32_accumulate=f32_acc,
            )
            for q, o in zip(grp, outs):
                out[q] = o
        return tuple(out)

    def run(curr):
        bs = tuple(lax.slice(curr[nm], tuple(lo), tuple(lo + n)) for nm in names)
        bs = lax.fori_loop(0, blocked, lambda _, c: macro(plan["m"], c), bs)  # one macro a trip
        if rem:
            bs = macro(rem, bs)
        return {nm: lax.dynamic_update_slice(curr[nm], b, tuple(lo)) for nm, b in zip(names, bs)}

    return jax.jit(run)


# dispatches of 2, 3, 4 and 5 macros and two with a remainder (one of them two
# calls in all: a macro and the remainder), shell radius 1 and 2, one and
# several quantities, joint and per-field grouping, f32 and bf16 storage -- a
# case is ~4 s of tracing and compiling interpreted kernels, so each of them
# varies several at once
CASES = [
    # (macros, rem, r, nq, grouping, storage)
    (2, 0, 1, 1, "joint", "native"), (3, 0, 2, 2, "per-field", "native"),
    (4, 0, 1, 2, "joint", "bf16"), (5, 0, 2, 2, "joint", "native"),
    (3, 1, 1, 2, "per-field", "bf16"), (1, 1, 2, 1, "joint", "bf16"),
]
IDS = [f"{m}macros+{rem}-r{r}-{nq}q-{grouping}-{storage}" for m, rem, r, nq, grouping, storage in CASES]


@functools.lru_cache(maxsize=None)
def _ran(case):
    """``(before, got, want)``: every raw block as seeded, after ONE dispatch of
    the built step, and after the plain reference's -- as f32 on the host."""
    macros, rem, r, nq, grouping, storage = case
    steps = macros * M + rem
    dd = _domain(nq, r, storage)
    plan = _plan(dd, grouping)
    host = lambda curr: [np.asarray(curr[h.name].astype(jnp.float32)) for h in dd._handles]
    before = host(dd._curr)
    want = host(xla_edged_step(dd, plan, steps)(dict(dd._curr)))
    step = sm._build_stream_step(dd, _ring, 1, plan, interpret=True)  # donates its blocks
    return r, before, host(step(dict(dd._curr), steps)), want


@pytest.mark.parametrize("case", CASES, ids=IDS)
def test_the_raw_edged_step_is_bitwise_the_cut_and_landed_one(case):
    """Every interior cell of every quantity after a dispatch whose first pass
    read the raw blocks and whose last one wrote them, against ``lax.slice`` ->
    bare passes -> ``dynamic_update_slice``."""
    r, before, got, want = _ran(case)
    for b, a, w in zip(before, got, want):
        inner = (slice(r, -r),) * 3
        np.testing.assert_array_equal(a[inner], w[inner])
        assert not np.array_equal(a[inner], b[inner])
    assert len(got) == 1 or not np.array_equal(got[0], got[1])


@pytest.mark.parametrize("case", CASES, ids=IDS)
def test_the_shell_is_the_periodic_image_and_the_x_halo_the_operands(case):
    """What the last pass writes around the interior: the y and z shell of
    every interior x plane is the periodic image of that plane -- on the wrap
    route the domain is one periodic device, so that is what ``exchange()``
    writes there -- and the x halo planes, which no grid step visits, are the
    operand's, as the parent's ``dynamic_update_slice`` left them."""
    r, before, got, _ = _ran(case)
    for b, a in zip(before, got):
        inner = a[r:-r, r:-r, r:-r]
        np.testing.assert_array_equal(a[r:-r], np.pad(inner, ((0, 0), (r, r), (r, r)), mode="wrap"))
        np.testing.assert_array_equal(a[:r], b[:r])
        np.testing.assert_array_equal(a[-r:], b[-r:])


def _wrap_calls(jaxpr):
    return [e for e in jx.iter_eqns(jaxpr) if e.primitive.name == "pallas_call"]


@pytest.mark.parametrize("macros,rem,grouping", [
    (2, 0, "joint"), (3, 0, "joint"), (4, 0, "joint"), (6, 0, "joint"), (7, 0, "joint"), (1, 1, "joint"),
    (3, 1, "joint"), (6, 1, "joint"), (2, 0, "per-field"), (4, 0, "per-field"), (5, 0, "per-field"),
    (2, 1, "per-field"),
])
def test_the_traced_step_cuts_and_lands_nothing(macros, rem, grouping):
    """The traced program where ``edges == "raw"``: no ``dynamic_update_slice``
    and no ``slice`` -- nothing but a kernel makes an array of a block's or an
    interior's shape --, every Pallas call named ``stream_wrap_pass`` -- the benchmark's
    kernel metrics match that name alone --, the bare macros between the two
    edge calls in a loop of TWO calls a trip (a group), the odd one behind it,
    and only the LAST call of each group aliased: onto the step's own blocks."""
    nq = 2
    dd = _domain(nq, 1, "native")
    plan = _plan(dd, grouping)
    groups = nq if grouping == "per-field" else 1
    step = sm._build_stream_step(dd, _ring, 1, plan, interpret=True)
    closed = jax.make_jaxpr(step, static_argnums=1)(dict(dd._curr), macros * M + rem)
    raw, bare = tuple(dd.local_spec().raw_size()), tuple(dd.local_spec().sz)
    seen = {e.primitive.name for e in jx.iter_eqns(closed)}
    assert not seen & {"dynamic_update_slice", "slice", "dynamic_slice", "pad", "copy"}, seen
    makers = {e.primitive.name for e in jx.iter_eqns(closed)
              if any(getattr(v.aval, "shape", None) in (raw, bare) for v in e.outvars)}
    assert makers <= {"pallas_call", "scan", "jit", "pjit", "shard_map"}, makers
    calls = _wrap_calls(closed)
    assert {e.params["name"] for e in calls} == {tm.KERNEL_STREAM_WRAP_PASS}
    between = macros + bool(rem) - 2
    trips = between // 2
    assert len(calls) == groups * (2 + (2 if trips else 0) + between % 2)
    loops = [e for e in jx.iter_eqns(closed) if e.primitive.name == "scan"]
    assert [(e.params["length"], len(_wrap_calls(e.params["jaxpr"]))) for e in loops] == (
        [(trips, 2 * groups)] if trips else [])
    aliased = [e for e in calls if e.params["input_output_aliases"]]
    assert len(aliased) == groups and not any(e.params["input_output_aliases"] for lp in loops
                                              for e in _wrap_calls(lp.params["jaxpr"]))
    for e in aliased:  # every result a raw block, on the buffer of the raw operand behind the bare ones
        width = len(e.outvars)
        assert all(v.aval.shape == raw for v in e.outvars)
        assert e.params["input_output_aliases"] == tuple((1 + width + q, q) for q in range(width))
    raw_in = [e for e in calls if e.invars[1].aval.shape == raw and not e.params["input_output_aliases"]]
    assert len(raw_in) == groups and all(v.aval.shape == bare for e in raw_in for v in e.outvars)


def test_a_dispatch_of_one_call_keeps_the_cut_and_the_write_back():
    """ONE call would read the blocks it writes (a replay step re-reads planes
    an earlier step has landed): such a dispatch -- a single macro, a single
    remainder -- runs the parent's edges whatever the plan says."""
    dd = _domain(2, 1, "native")
    step = sm._build_stream_step(dd, _ring, 1, _plan(dd, "joint"), interpret=True)
    for steps in (1, M):
        closed = jax.make_jaxpr(step, static_argnums=1)(dict(dd._curr), steps)
        names = [e.primitive.name for e in jx.iter_eqns(closed)]
        assert names.count("dynamic_update_slice") == 2 and names.count("slice") == 2
        (call,) = _wrap_calls(closed)
        assert not call.params["input_output_aliases"]


SHAPES = {
    # (extent, r, storage): whole tiles or not, and the margin against the interior
    "one-tile": ((X, 8, 128), 1, "native", "raw"),
    "r2": ((X, 8, 128), 2, "native", "raw"),
    "bf16-16-rows": ((X, 16, 128), 1, "bf16", "raw"),
    "bf16-8-rows": ((X, 8, 128), 1, "bf16", "xla"),  # half a bf16 tile of rows
    "ragged-lanes": ((X, 8, 96), 1, "native", "xla"),
    "ragged-rows": ((X, 12, 128), 1, "native", "xla"),
    "cube-8": ((8, 8, 8), 1, "native", "xla"),
    "margin-wider-than-the-rows": ((X, 8, 128), 5, "native", "xla"),  # 18 raw rows round up to 24: 16 > 8
    "lanes-256": ((X, 16, 256), 3, "native", "raw"),
}


@pytest.mark.parametrize("extent,r,storage,edges", list(SHAPES.values()), ids=list(SHAPES))
def test_the_edge_forms_exist_where_the_interior_is_whole_tiles(extent, r, storage, edges):
    """``wrap_edge_form`` reads the block's static shape: ``"raw"`` where the
    y-z interior is whole vector tiles of every stored dtype and no narrower
    than the margin that rounds a raw plane up to whole tiles."""
    dd = DistributedDomain(*extent)
    dd.set_radius(Radius.constant(r))
    dd.set_devices(jax.devices()[:1])
    dd.set_storage(storage)
    dd.add_data("q", dtype=jnp.float32)
    dd.realize(allocate=False)
    plan = sp.plan_stream(dd, 1, "wrap", False, max_m=M)
    assert sp.wrap_edge_form(dd, plan) == edges


# the bare form at 256^3 x 19, depth 2: 19 x (4 + 4) x 262,144 B + 19 x 3 MB of
# stack = 96,845,888; the edge forms' two (264, 384) planes a quantity for two
# (256, 256): + 5,447,680 = 102,293,568; depth 3: 106,807,360
@pytest.mark.parametrize("budget,edges", [
    (None, "raw"), (102_293_568, "raw"), (102_293_567, "xla"), (100_000_000, "xla"), (96_845_888, "xla"),
])
def test_the_depth_is_chosen_before_the_edges_are_asked(budget, edges, monkeypatch):
    """The lattice-Boltzmann cell's shapes: ``plan["m"]`` is the bare form's,
    2, with and without the edge forms, and a VMEM budget too tight for them
    gives ``edges: "xla"`` at the SAME depth -- the benchmark's ``ran_depth``
    check never meets a planner that traded depth for edges."""
    from stencil_tpu.models.lbm import RADIUS, LatticeBoltzmann

    if budget is not None:
        monkeypatch.setenv("STENCIL_VMEM_LIMIT_BYTES", str(budget))
    sim = LatticeBoltzmann(256, 256, 256, devices=jax.devices()[:1], seed_words=None)
    sim.dd.realize(allocate=False)
    request = sp.plan_stream(sim.dd, RADIUS, "auto", False)
    assert (request["route"], request["m"], request["grouping"]) == ("wrap", 2, "joint")
    assert sp.wrap_edge_form(sim.dd, request) == edges
    with monkeypatch.context() as mp:
        mp.setattr(sp, "wrap_edge_form", lambda dd, plan: "xla")  # the parent's planner
        assert sp.plan_stream(sim.dd, RADIUS, "auto", False) == request


def test_the_resolved_plan_of_the_cell_says_raw_edges():
    """... and ``resolve_stream_plan`` writes the answer beside
    ``macros_per_trip``; a request that forces the XLA edges (the ladder's step
    down after a compile reject) is honoured at the same depth."""
    from stencil_tpu.models.lbm import RADIUS, LatticeBoltzmann

    sim = LatticeBoltzmann(256, 256, 256, devices=jax.devices()[:1], seed_words=None)
    sim.dd.realize(allocate=False)
    request = sp.plan_stream(sim.dd, RADIUS, "auto", False)
    plan = sp.resolve_stream_plan(sim.dd, sim._kernel, RADIUS, request, True)
    assert (plan["m"], plan["macros_per_trip"], plan["edges"]) == (2, 2, "raw")
    forced = sp.resolve_stream_plan(
        sim.dd, sim._kernel, RADIUS, dict(request, edges="xla", edges_forced=True), True)
    assert (forced["m"], forced["edges"]) == (2, "xla")
    # a resolved plan handed back as a request brings no old resolution along
    again = sp.resolve_stream_plan(sim.dd, sim._kernel, RADIUS, dict(forced.plan, edges_forced=False), True)
    assert again["edges"] == "raw"


@pytest.mark.parametrize("extent,edges", [((X, 8, 128), "raw"), ((8, 8, 8), "xla")], ids=["raw", "xla"])
def test_the_step_span_says_where_the_edges_live(extent, edges):
    """``domain.step`` on the wrap route carries ``edges`` beside
    ``macros_per_trip`` (docs/observability.md)."""
    dd = DistributedDomain(*extent)
    dd.set_radius(Radius.constant(1))
    dd.set_devices(jax.devices()[:1])
    h = dd.add_data("q", dtype=jnp.float32)
    dd.realize()
    dd.init_by_coords(h, lambda x, y, z: jnp.sin(0.37 * (x + 2 * y + 3 * z)))
    step = dd.make_step(_ring, engine="stream", x_radius=1, interpret=True, stream_depth=M)
    assert step._stream_plan["edges"] == edges and step._span_args()["edges"] == edges
    seen = []
    real = telemetry.span

    def spy(name, *a, **kw):
        seen.append((name, kw))
        return real(name, *a, **kw)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(telemetry, "span", spy)
        dd.run_step(step, 2 * M)
    (kw,) = [kw for name, kw in seen if name == tm.SPAN_STEP]
    assert (kw["route"], kw["macros_per_trip"], kw["edges"]) == ("wrap", 2, edges)


def test_a_compile_reject_of_the_edge_forms_steps_down_at_the_same_depth():
    """The ladder: a rung whose edge forms the compiler rejects descends to the
    XLA edges at the SAME depth before any depth descent, and the result is the
    plain reference's."""
    from stencil_tpu.resilience import inject

    dd = _domain(1, 1, "native")
    want = xla_edged_step(dd, _plan(dd, "joint"), 2 * M)(dict(dd._curr))
    # (a plan's entries part at commas: name the rung ``wrap[m=2,raw]`` by its prefix, once)
    inject.set_plan(f"compile:vmem_oom:stream:wrap[m={M}*1")
    try:
        step = dd.make_step(_ring, engine="stream", x_radius=1, interpret=True, stream_depth=M)
        dd.run_step(step, 2 * M)
    finally:
        inject.set_plan(None)
    plan = step._stream_plan
    assert (plan["m"], plan["edges"]) == (M, "xla") and len(step._resilience.descents) == 1
    for nm in want:
        np.testing.assert_array_equal(np.asarray(dd._curr[nm])[1:-1, 1:-1, 1:-1], np.asarray(want[nm])[1:-1, 1:-1, 1:-1])
