"""The plane pass whose pipeline blocks are Y TILES of a plane (ISSUE 51:
``ops/stream_pass.py stream_plane_pass_tiled``, ``ops/stream_plan.py
plan_plane_passes`` / ``PlaneTiling``): the pass bitwise the whole-plane strip
form on every raw cell over several tile counts, one among them; the planner
gives tiles exactly where it used to raise, prices them with the one model and
allocates what it priced; D3Q19 at 512 x 512 x nineteen lands under the budget;
the lattice-Boltzmann model through tiles against its plain reference; the
in-place order of the tiled maps; the forms that keep the refusal say so.
Beside a SPLIT y (ISSUE 53: the ``"interior-z"`` window, the rows outside the
interior a neighbour's) the same pass closes a plane's ends on the block's own
rows: bitwise the whole-plane carried form, planned where the planner raised."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from stencil_tpu import analysis
from stencil_tpu.core.dim3 import Dim3
from stencil_tpu.models import lbm_reference as ref
from stencil_tpu.models.lbm import RADIUS, LatticeBoltzmann
from stencil_tpu.ops import stream as sm
from stencil_tpu.ops import stream_pass as spass
from stencil_tpu.ops import stream_plan as sp

#: the model's margin for a whole-plane pass of nineteen: nothing under it fits
#: whole planes, and the wrap route's m = 1 neither (``stack_margin``)
MARGIN_19 = 19 * sp._VMEM_STACK_MARGIN


def _fills(n, lo, hi):
    return tuple(
        (a, d, s, w) for a in (1, 2)
        for d, s, w in ((0, n[a], lo[a]), (lo[a] + n[a], lo[a], hi[a]))
    )


def _kernel(r):
    """``u`` ringed and read on every kind of diagonal, ``c`` read along y and z
    alone (fetched lagged, a halo reader), ``p`` at the centre; the cells' own
    coordinates; two outputs."""

    def kernel(views, info):
        u, c = views["u"], views["c"]
        x, y, z = info.coords()
        new = 0.5 * u.center() + 0.125 * (
            u.sh(r, r, 0) - u.sh(-r, 0, r) + u.sh(0, -r, -r) + u.sh(r, 0, 0) - u.sh(-1, 1, -1)
        ) + c.sh(0, r, 1) + 1e-3 * (x + 2 * y + 3 * z).astype(jnp.float32)
        return {"u": new, "p": 2.0 * u.center() + views["p"].center()}

    return kernel


_PASS_CASES = [
    pytest.param(1, {}, ((1, 1, 1), (1, 1, 1)), id="r1"),
    pytest.param(1, {"alias": True}, ((1, 1, 1), (1, 1, 1)), id="r1-in-place"),
    pytest.param(1, {"f32_accumulate": True}, ((1, 1, 1), (1, 1, 1)), id="r1-bf16-storage"),
    pytest.param(2, {"alias": True}, ((2, 3, 2), (3, 2, 4)), id="r2-uneven-shell"),
]


@pytest.mark.parametrize("y_tiles", [1, 2, 4])
@pytest.mark.parametrize("r,kw,shell", _PASS_CASES)
def test_the_tiled_pass_is_bitwise_the_whole_plane_pass(r, kw, shell, y_tiles):
    """The same blocks through ``stream_plane_pass``'s strip form on the interior
    window and through the tiled pass: EVERY raw cell of every quantity bitwise
    equal -- interiors, x-shell planes passed through, the rebuilt y / z shell,
    the tail rows -- with the plane moved whole (one y tile), in two and in four:
    every shifted read then crosses a margin tile that a NEIGHBOUR tile filled,
    and the first tile's low rows come from the block's tail.  A quantity the
    pass does not write comes back as the array that went in."""
    dtype = jnp.bfloat16 if kw.get("f32_accumulate") else jnp.float32
    tile = spass.sublane_tile([dtype])
    lo, hi = shell
    n, names = (5, 8 * tile, 128), ["u", "c", "p"]
    strip = 2 * tile  # four strips a plane, one or more a y tile
    shape = tuple(m + a + b for m, a, b in zip(n, lo, hi))
    rng = np.random.default_rng(51)
    raws = [jnp.asarray(rng.standard_normal(shape), dtype) for _ in names]
    common = dict(
        interpret=True, halo_readers=("u", "c"), rings=("u",), writers=("u", "p"),
        wrap_fills=_fills(n, lo, hi), **kw,
    )
    args = (_kernel(r), names, raws, Dim3(*lo), Dim3(*hi), r,
            jnp.asarray([5, 3, 7], jnp.int32), Dim3(64, n[1], n[2]))
    want = spass.stream_plane_pass(*args, window="interior", strip=strip, **common)
    got = spass.stream_plane_pass_tiled(
        *args, tile_rows=n[1] // y_tiles, strip=strip, **common)
    for name, a, b in zip(names, got, want):
        a, b = (np.asarray(v.astype(jnp.float32)) for v in (a, b))
        assert np.isfinite(b).all() and np.array_equal(a, b), name
    assert got[1] is raws[1]


@pytest.mark.parametrize("y_tiles", [2, 4])
@pytest.mark.parametrize("r,kw,shell", _PASS_CASES)
def test_the_tiled_pass_beside_a_split_y_is_bitwise_the_carried_form(r, kw, shell, y_tiles):
    """The ``"interior-z"`` window (the fills are the z self-wrap alone: the mesh
    splits y): the same blocks through ``stream_plane_pass``'s carried strip form
    and through the tiled pass, EVERY raw cell bitwise equal -- interiors, the y
    halo rows passed through, the rebuilt z shell, the tail rows, x-shell planes.
    The blocks' y shells are random, no wrap of their own interior: a ``link``
    of the last y tile to the first left in, or a tail row taken from the wrong
    place, shows in the rows beside either end."""
    dtype = jnp.bfloat16 if kw.get("f32_accumulate") else jnp.float32
    tile = spass.sublane_tile([dtype])
    lo, hi = shell
    # (a y tile holds at least the ``lo.y + hi.y`` tiles its margins are cut from)
    tiles = 8 if (lo[1] + hi[1]) * y_tiles <= 8 else 24
    n, names = (5, tiles * tile, 128), ["u", "c", "p"]
    strip = 2 * tile
    shape = tuple(m + a + b for m, a, b in zip(n, lo, hi))
    rng = np.random.default_rng(53)
    raws = [jnp.asarray(rng.standard_normal(shape), dtype) for _ in names]
    common = dict(
        interpret=True, halo_readers=("u", "c"), rings=("u",), writers=("u", "p"),
        wrap_fills=tuple(f for f in _fills(n, lo, hi) if f[0] == 2), **kw,
    )
    args = (_kernel(r), names, raws, Dim3(*lo), Dim3(*hi), r,
            jnp.asarray([5, 3, 7], jnp.int32), Dim3(64, 2 * n[1], n[2]))
    want = spass.stream_plane_pass(*args, window="interior-z", strip=strip, **common)
    got = spass.stream_plane_pass_tiled(
        *args, tile_rows=n[1] // y_tiles, strip=strip, **common)
    for name, a, b in zip(names, got, want):
        a, b = (np.asarray(v.astype(jnp.float32)) for v in (a, b))
        assert np.isfinite(b).all() and np.array_equal(a, b), name
    assert got[1] is raws[1]


def test_the_tiled_pass_fails_closed_by_name():
    """It checks what it is told as the whole-plane form does (``_told_guards``)."""
    r, n = 1, (4, 16, 128)
    blk = jax.ShapeDtypeStruct(tuple(m + 2 * r for m in n), jnp.float32)

    def one_pass(kernel, **kw):
        def fn(origin, a, c):
            return spass.stream_plane_pass_tiled(
                kernel, ["a", "c"], [a, c], Dim3(r, r, r), Dim3(r, r, r), r, origin, Dim3(*n),
                tile_rows=8, strip=8, interpret=True, wrap_fills=_fills(n, (r,) * 3, (r,) * 3),
                **kw,
            )

        return jax.make_jaxpr(fn)(jax.ShapeDtypeStruct((3,), jnp.int32), blk, blk)

    def reads(dx, dy):
        return lambda views, info: {"a": views["a"].sh(1, 0, 1) * views["c"].sh(dx, dy, 0)}

    one_pass(reads(0, 1), halo_readers=("a", "c"), rings=("a",), writers=("a",))  # told: fine
    with pytest.raises(ValueError, match=r"reads 'c' off-centre.*halo of 'c' was not exchanged"):
        one_pass(reads(0, 1), halo_readers=("a",), rings=("a",), writers=("a",))
    with pytest.raises(ValueError, match=r"reads 'c' off-centre along x.*no ring for 'c'"):
        one_pass(reads(1, 0), rings=("a",), writers=("a",))
    with pytest.raises(ValueError, match=r"returns 'c'.*'c' is not an output of the pass"):
        one_pass(lambda v, i: {"a": v["a"].center(), "c": v["c"].center()}, writers=("a",))


# --- the planner ----------------------------------------------------------------------


def _lbm(shape, monkeypatch, budget=None, mesh=(1, 1, 1), **kw):
    """A lattice-Boltzmann model realized WITHOUT arrays (the real size costs
    nothing), the pass's own fills engaged as on the chip."""
    monkeypatch.setenv("STENCIL_HALO_BLEND", "1")
    if budget is not None:
        monkeypatch.setenv("STENCIL_VMEM_LIMIT_BYTES", str(int(budget)))
    sim = LatticeBoltzmann(*shape, seed_words=None, interpret=True,
                           devices=jax.devices()[: int(np.prod(mesh))], **kw)
    sim.dd.set_partition(*mesh)
    sim.dd.realize(allocate=False)
    return sim


def _resolve(sim, **request):
    req = dict(sp.plan_stream(sim.dd, RADIUS, "auto", False), **request)
    return sp.resolve_stream_plan(sim.dd, sim._kernel, RADIUS, req, True)


def _tiled_bytes(n_y, n_z, rows, itemsize=4, tile=8, r=1, reads=19, rings=10, writes=19):
    """``plane_pass_vmem_bytes(y_tiles=)`` by hand; D3Q19's counts where none are
    given: 19 read and 19 written, 10 ringed."""
    pad = sp._padded_plane_bytes
    nt = n_y // rows
    pipeline = 2 * (reads + writes) * pad(rows, n_z + 2 * r, itemsize)
    held = (rings * (2 * r + 2) + (reads - rings) * 2) * nt * pad(rows + 2 * r * tile, n_z, itemsize)
    staged = writes * pad(rows, n_z, itemsize) + (reads + writes) * pad(tile, n_z, itemsize)
    return pipeline + held + staged + sp._VMEM_STACK_MARGIN


def test_the_card_filling_box_plans_in_y_tiles_under_the_budget(monkeypatch):
    """``LatticeBoltzmann(512, 512, 512)`` on one device, every axis ``auto``:
    the wrap route finds no depth, radius 1 rules the wavefront out, whole
    512 x 512 planes of nineteen coupled populations fit no pass -- and the
    planner answers with ONE in-place pass over y tiles of 128 rows, four a
    plane, 101.9 MB by the model against 104.9; the box the benchmark already
    had keeps its wrap route."""
    sim = _lbm((512,) * 3, monkeypatch)
    request = sp.plan_stream(sim.dd, RADIUS, "auto", False)
    assert (request["route"], request["m"]) == ("plane", 1)
    plan = sp.resolve_stream_plan(sim.dd, sim._kernel, RADIUS, request, True)
    (p,) = plan["stages"][0]["passes"]
    assert (len(p["writes"]), len(p["reads"]), len(p["rings"])) == (19, 19, 10)
    assert (plan["plane_window"], plan["plane_strip"], plan["pass_wrap_axes"]) == ("interior", 8, "yz")
    assert (plan["tile_rows"], plan["y_tiles"], p["tile_rows"]) == (128, 4, 128)
    assert p["vmem_bytes"] == _tiled_bytes(512, 512, 128) == 101_926_592 < sp._vmem_budget()
    assert _tiled_bytes(512, 512, 256) > sp._vmem_budget()  # the largest that fits
    assert plan["alias"] and not p["renames"] and not p["prerotated"]
    args = sm.stream_span_args(plan, RADIUS, 19)
    assert (args["tile_rows"], args["y_tiles"], args["aliased"]) == (128, 4, 19)
    assert analysis.check_vmem(sim.dd, plan.plan) is None
    small = _lbm((256,) * 3, monkeypatch)
    assert sp.plan_stream(small.dd, RADIUS, "auto", False) == {
        "route": "wrap", "m": 2, "z_slabs": False, "grouping": "joint"}


@pytest.mark.parametrize("slack,rows", [(5_000_000, 64), (0, 64), (-25_000_000, 0)])
def test_a_tighter_budget_takes_a_smaller_tile_or_says_what_it_tried(slack, rows, monkeypatch):
    """``STENCIL_VMEM_LIMIT_BYTES`` under the default: the largest tile the one
    model fits (at 512 lanes that is 64 rows and no smaller one: every y tile
    brings two margin tiles a plane), and where none holds even ONE output's
    nineteen reads down to one strip the refusal names what was tried before it
    asks for stages."""
    budget = _tiled_bytes(512, 512, 64) + slack
    assert budget < _tiled_bytes(512, 512, 128)
    sim = _lbm((512,) * 3, monkeypatch, budget=budget)
    if rows:
        plan = _resolve(sim)
        assert (plan["tile_rows"], plan["y_tiles"]) == (rows, 512 // rows)
        assert plan["stages"][0]["passes"][0]["vmem_bytes"] == _tiled_bytes(512, 512, rows)
        return
    with pytest.raises(sp.FitsNoPass, match=(
            r"fits no pass \(y tiles of its planes from 512 rows down to one strip of 8 "
            r"fit none either\); split the kernel into stages")):
        _resolve(sim)


def test_a_pass_that_fits_whole_planes_is_never_tiled(monkeypatch):
    """Tiles are the answer only where whole planes raise: at 256^3 the plane
    route's pass of nineteen fits whole and stays as it was."""
    sim = _lbm((256,) * 3, monkeypatch)
    plan = sp.resolve_stream_plan(
        sim.dd, sim._kernel, RADIUS, sp.plan_stream(sim.dd, RADIUS, "plane", False), True)
    (p,) = plan["stages"][0]["passes"]
    assert (plan["tile_rows"], plan["y_tiles"], p["tile_rows"], plan["plane_strip"]) == (0, 1, 0, 0)
    assert MARGIN_19 < p["vmem_bytes"] <= sp._vmem_budget()


@pytest.mark.parametrize("case,why", [
    ("ragged", r"passes work on the 'raw' window, whole planes\)"),
    ("fused", r"a pass that carries every quantity whole has no tiled form\)"),
])
def test_the_forms_that_keep_the_refusal_say_so(case, why, monkeypatch):
    """One more form, not three: ragged lanes (the raw window) and
    ``halo="fused"`` keep the refusal they had, and its message says that tiles
    were not on offer (a y the mesh splits has them since ISSUE 53: the next
    test)."""
    if case == "ragged":
        sim, request = _lbm((512, 512, 600), monkeypatch), {}
    else:
        sim = _lbm((512,) * 3, monkeypatch)
        request = {"halo": "fused", "halo_forced": True}
        monkeypatch.setattr(sp, "fused_halo_ineligible", lambda *a: None)
    with pytest.raises(sp.FitsNoPass, match=r"fits no pass \(.*" + why):
        _resolve(sim, route="plane", m=1, **request)


@pytest.mark.parametrize("shape,mesh,wired", [
    ((512, 1024, 512), (1, 2, 1), "y"),
    ((1024, 1024, 512), (2, 2, 1), "xy"),  # FluidX3D's 4-GPU line: lbm-d3q19-512x4
])
def test_the_box_beside_a_split_y_plans_in_y_tiles(shape, mesh, wired, monkeypatch):
    """512^3 a device beside a y the mesh splits, every axis ``auto``: the light
    kernel's whole raw planes fit no pass, and the planner -- which raised here
    until ISSUE 53 -- answers with y tiles on the ``"interior-z"`` window, at the
    one-chip cell's price (``lo.y + hi.y`` margin tiles are its ``2 r``); the
    legality prefilter and the VMEM verdict take the plan; a tighter budget takes
    the smaller tile."""
    sim = _lbm(shape, monkeypatch, mesh=mesh)
    request = sp.plan_stream(sim.dd, RADIUS, "auto", False)
    assert (request["route"], request["m"]) == ("plane", 1)
    plan = sp.resolve_stream_plan(sim.dd, sim._kernel, RADIUS, request, True)
    (p,) = plan["stages"][0]["passes"]
    assert (plan["plane_window"], plan["plane_strip"], plan["pass_wrap_axes"]) == ("interior-z", 8, "z")
    assert (plan["tile_rows"], plan["y_tiles"], p["tile_rows"]) == (128, 4, 128)
    assert 512 % p["tile_rows"] == 0 and p["vmem_bytes"] == _tiled_bytes(512, 512, 128) < sp._vmem_budget()
    assert plan["alias"] and plan["wired"] == wired and not p["renames"] and not p["prerotated"]
    args = sm.stream_span_args(plan, RADIUS, 19)
    assert (args["plane_window"], args["tile_rows"], args["y_tiles"], args["aliased"]) == (
        "interior-z", 128, 4, 19)
    assert (args["read_sides"], args["exchanged"], args["exchanged_sides"]) == (30, 18, 108)
    assert analysis.check_vmem(sim.dd, plan.plan) is None
    monkeypatch.setattr("stencil_tpu.analysis.kernels._mosaic_target", lambda: False)
    assert analysis.check_kernel_legal(sim.dd, dict(plan.plan)) is None
    monkeypatch.setenv("STENCIL_VMEM_LIMIT_BYTES", str(_tiled_bytes(512, 512, 64)))
    assert _resolve(sim)["tile_rows"] == 64


def test_the_legality_prefilter_reads_the_tile_from_the_plan(monkeypatch):
    """``check_kernel_legal`` judges the blocks the plan names: y tiles of whole
    sublane tiles pass, a tile that is not is refused by its rows."""
    sim = _lbm((512,) * 3, monkeypatch)
    plan = dict(_resolve(sim).plan)
    monkeypatch.setattr("stencil_tpu.analysis.kernels._mosaic_target", lambda: False)
    assert analysis.check_kernel_legal(sim.dd, plan) is None

    def with_rows(rows):
        stages = tuple(
            {**st, "passes": tuple({**p, "tile_rows": rows} for p in st["passes"])}
            for st in plan["stages"]
        )
        return {**plan, "tile_rows": rows, "stages": stages}

    assert analysis.check_kernel_legal(sim.dd, with_rows(64)) is None
    assert "windows of extent 100" in analysis.check_kernel_legal(sim.dd, with_rows(100))


# --- the model through tiles, and what the pass allocates -------------------------------


#: per tile: the realized model (one build, one traced dispatch a tile for every
#: test that drives it)
_SMALL = {}


def _small_lbm(monkeypatch, rows):
    """``LatticeBoltzmann(6, 64, 256)`` under a budget that the y tile of ``rows``
    rows just fits: no wrap depth, no whole-plane pass, no larger tile."""
    monkeypatch.setenv("STENCIL_HALO_BLEND", "1")
    budget = _tiled_bytes(64, 256, rows)
    assert budget < MARGIN_19
    monkeypatch.setenv("STENCIL_VMEM_LIMIT_BYTES", str(budget))
    if rows not in _SMALL:
        sim = LatticeBoltzmann(6, 64, 256, interpret=True, seed_words=None,
                               devices=jax.devices()[:1])
        sim.realize()
        _SMALL[rows] = sim
    return _SMALL[rows]


def _seed_populations(sim, seed):
    rng = np.random.default_rng(seed)
    state = [np.float32(w) * rng.uniform(0.6, 1.4, sim.setup.shape).astype(np.float32) for w in ref.W]
    for name, a in zip(ref.NAMES, state):
        sim.dd.set_quantity(sim.handles[name], a)
    return state


@pytest.mark.parametrize("rows", [64, 32, 16])
def test_the_model_through_y_tiles_matches_the_reference(rows, monkeypatch):
    """The lattice-Boltzmann model through its normal path, ``make_step(engine=
    "stream")`` with every axis ``auto``, the budget tightened until the planner
    answers with y tiles (one, two and four a plane): seeded random populations,
    every cell of all nineteen against the plain reference after three steps,
    to the tolerance the other routes are held to; and what the pass allocates
    is what the model priced."""
    from stencil_tpu.analysis import jaxpr as jx

    sim = _small_lbm(monkeypatch, rows)
    plan = sim._step._stream_plan
    assert (plan["route"], plan["plane_window"], plan["plane_strip"]) == ("plane", "interior", 16)
    assert (plan["tile_rows"], plan["y_tiles"]) == (rows, 64 // rows)
    state = _seed_populations(sim, rows)
    sim.step(3)
    want = ref.steps(sim.setup, state, 3)
    worst = max(float(np.abs(sim.field(q) - np.asarray(w)).max()) for q, w in zip(ref.NAMES, want))
    assert worst < 2e-6, worst
    assert not sim._step._resilience.descents
    # the traced call: two tile-padded buffers a pipelined block, the scratch as allocated
    resolved = _resolve(sim)
    step = sm._build_stream_step(sim.dd, sim._kernel, RADIUS, resolved, interpret=True)
    closed = jax.make_jaxpr(step, static_argnums=1)(sim.dd._curr, 1)
    (call,) = [
        e for e in jx.iter_eqns(closed)
        if e.primitive.name == "pallas_call" and "stream_plane_pass" in str(e.params.get("name"))
    ]
    gm = call.params["grid_mapping"]
    assert tuple(gm.grid) == (8 + RADIUS + 1, 64 // rows + 1)
    pad = lambda shape, dtype: sp._padded_plane_bytes(  # noqa: E731
        shape[-2], shape[-1], dtype.itemsize) * int(np.prod(shape[:-2]))
    blocks = [bm for bm in gm.block_mappings if len(bm.block_shape) == 3]
    allocated = sum(
        2 * pad(tuple(int(getattr(b, "block_size", 1)) for b in bm.block_shape), bm.array_aval.dtype)
        for bm in blocks
    ) + sum(pad(sc.shape, sc.dtype) for sc in gm.scratch_avals)
    (p,) = resolved["stages"][0]["passes"]
    assert len(blocks) == 38 and p["vmem_bytes"] == allocated + sp._VMEM_STACK_MARGIN


# --- in place -----------------------------------------------------------------------------


@pytest.mark.parametrize("split_y", [False, True], ids=["interior", "split-y"])
def test_the_inplace_order_contract_judges_the_tiled_maps(split_y, monkeypatch):
    """``check_inplace_order`` on the traced tiled pass, in place: its two aliased
    pairs are in order as built; with the maps NOT standing still where a plane
    index is clamped -- the out map cycling over plane 0's tiles before their own
    values exist, the in maps refetching the last plane's tiles while it is being
    overwritten -- the contract names the hazard."""
    import importlib.util
    import os

    from stencil_tpu.analysis import kernels

    path = os.path.join(os.path.dirname(__file__), "analysis_fixtures",
                        "inplace_order_plane_tiled_clean.py")
    spec = importlib.util.spec_from_file_location("tiled_fixture", path)
    fixture = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(fixture)
    art = fixture.build(split_y)
    (rep,) = kernels.kernel_reports(art.closed)
    assert not rep.parallel_dims and {o: a.index for o, a in rep.aliases.items()} == {0: 1, 1: 2}
    rows = 16 if split_y else 8
    assert len(rep.outputs[0].footprint) == (8 + 2) * (32 // rows + 1)  # every grid step judged
    assert not kernels.check_inplace_order(art)
    kernels.reset_report_cache()
    real = jnp.where
    monkeypatch.setattr(spass.jnp, "where", lambda cond, a, b: a if np.ndim(a) == 0 else real(cond, a, b))
    found = kernels.check_inplace_order(fixture.build(split_y))
    assert found and "in place the kernel reads its own result" in found[0]


def test_a_stage_whose_whole_plane_passes_clash_takes_one_tiled_pass(monkeypatch):
    """The other place the planner used to raise: stored as bf16 the 512^3 box
    fits eight outputs a whole-plane pass, and the ninth reads what the first
    pass wrote in place.  In y tiles the stage is ONE pass after all -- here the
    plane as one tile of 512 rows (the benchmark's bf16 control runs this)."""
    monkeypatch.setenv("STENCIL_HALO_BLEND", "1")
    sim = LatticeBoltzmann(512, 512, 512, seed_words=None, interpret=True, devices=jax.devices()[:1])
    sim.dd.set_storage("bf16")
    sim.dd.realize(allocate=False)
    plan = _resolve(sim)
    (p,) = plan["stages"][0]["passes"]
    assert (plan["plane_strip"], plan["tile_rows"], plan["y_tiles"]) == (16, 512, 1)
    assert len(p["writes"]) == 19 and p["vmem_bytes"] == _tiled_bytes(512, 512, 512, 2, 16) == 95_700_672


# --- the lanes behind the window: a dispatch's two edges (ISSUE 54) ------------------------
#
# The forms are the pass's, indifferent to the kernel it runs: these cases drive
# them with this file's three-quantity kernel (ISSUE 55: nineteen coupled
# populations cost 45 s of lowering a case and showed nothing more).  The model's
# own kernel goes through the same dispatch in ``test_the_model_through_y_tiles_
# matches_the_reference`` (three steps: a first and a later call) and, beside a
# split y, in ``tests/test_lbm.py``.

_LANE_NAMES = ("u", "c", "p")


def _coupled_kernel(views, info):
    """``_kernel(1)`` with ``c`` written too: a dispatch takes the window's lanes
    only where its pass writes every quantity it reads (``plane_lanes_form``).
    ``u`` is ringed and read on every kind of diagonal, both ways along z (the
    lanes behind the window are its low z fill's) and along y (across a y tile's
    margins), ``c`` along y and z, ``p`` at the centre."""
    out = _kernel(1)(views, info)
    return {**out, "c": 0.5 * views["c"].center() + 0.25 * views["u"].sh(0, -1, 1)}


#: per window: the realized domain, its handles, its resolved plan and the two programs
_LANES = {}


def _lanes_case(window):
    """A domain of three quantities the planner tiles on ``window`` -- one device
    (6 x 64 x 256, two y tiles of 32 rows) or mesh [2,2,1] on four CPU devices
    (shards of 4 x 32 x 256, two y tiles of 16 rows beside the split y): the
    smallest shards with two y tiles of whole strips and a z interior of whole
    lane tiles --, its resolved plan (``plane_lanes`` "window") and two programs
    of it, un-donated: the dispatch as built, and the parent's, every call
    moving whole raw planes."""
    if window not in _LANES:
        from stencil_tpu.core.radius import Radius
        from stencil_tpu.domain import DistributedDomain

        mesh, shape, rows = {
            "interior": ((1, 1, 1), (6, 64, 256), 32),
            "interior-z": ((2, 2, 1), (8, 64, 256), 16),
        }[window]
        with pytest.MonkeyPatch.context() as mp:
            mp.setenv("STENCIL_HALO_BLEND", "1")
            mp.setenv("STENCIL_VMEM_LIMIT_BYTES", str(_tiled_bytes(
                shape[1] // mesh[1], 256, rows, reads=3, rings=1, writes=3)))
            dd = DistributedDomain(*shape)
            dd.set_radius(Radius.constant(1))
            dd.set_devices(jax.devices()[: int(np.prod(mesh))])
            dd.set_partition(*mesh)
            hs = [dd.add_data(name, dtype=jnp.float32) for name in _LANE_NAMES]
            dd.realize()
            plan = sp.resolve_stream_plan(
                dd, _coupled_kernel, 1, sp.plan_stream(dd, 1, "plane", False), True)
            assert (plan["plane_window"], plan["tile_rows"], plan["y_tiles"]) == (window, rows, 2)
            assert plan["plane_lanes"] == "window" and len(plan["stages"][0]["passes"]) == 1
            raw = dataclasses.replace(plan, plan={**plan.plan, "plane_lanes": "raw"})
            build = lambda p: sm._build_stream_step(  # noqa: E731
                dd, _coupled_kernel, 1, p, interpret=True, donate=False)
            _LANES[window] = (dd, hs, plan, build(plan), build(raw))
    return _LANES[window]


def _seeded_blocks(dd, handles, seed, garbage=False):
    """The domain's raw blocks with seeded random values in every interior cell;
    the shell what ``set_quantity`` leaves (zeros), or with ``garbage`` large
    random numbers in every shell cell of every shard."""
    rng = np.random.default_rng(seed)
    size = dd._size
    for h in handles:
        dd.set_quantity(h, rng.uniform(0.6, 1.4, (size.x, size.y, size.z)).astype(np.float32))
    blocks = dict(dd._curr)
    if garbage:
        n, lo = dd.local_spec().sz, dd.local_spec().radius.lo()
        raw = dd.local_spec().raw_size()
        inside = [
            (np.arange(g) % r >= a) & (np.arange(g) % r < a + m)
            for g, r, a, m in zip(blocks[handles[0].name].shape, raw, lo, n)
        ]
        interior = inside[0][:, None, None] & inside[1][None, :, None] & inside[2][None, None, :]
        for name, b in blocks.items():
            junk = rng.uniform(-1e3, 1e3, b.shape).astype(np.float32)
            blocks[name] = jax.device_put(np.where(interior, np.asarray(b), junk), b.sharding)
    return blocks


def _same_raw_cells(got, want):
    for name in want:
        a, b = np.asarray(got[name]), np.asarray(want[name])
        assert np.isfinite(b).all() and np.array_equal(a, b), (
            name, np.argwhere(a != b)[:4].tolist())


@pytest.mark.parametrize("steps", [1, 2, 3, 6])
@pytest.mark.parametrize("window", ["interior", "interior-z"])
def test_a_dispatch_that_carries_the_z_shell_at_its_edges_is_bitwise_whole_calls(window, steps):
    """Dispatches of 1, 2, 3 and 6 steps through the dispatch structure's THREE
    forms -- first call raw in / window out, the calls between (one of three
    steps, four of six, none of two) window in / window out, the last window in
    / raw out: the lane tile behind the window moves at the dispatch's two edges
    alone -- against the parent's program, every call moving whole raw planes:
    EVERY raw cell of every quantity bitwise equal after the dispatch, the z
    shell, the tail rows and the x-halo planes included; on four devices the y
    halo rows a neighbour sent too."""
    dd, hs, _, lanes, whole = _lanes_case(window)
    blocks = _seeded_blocks(dd, hs, 54 + steps)
    want = whole(blocks, steps)
    assert not np.array_equal(np.asarray(want["u"]), np.asarray(blocks["u"]))  # (the state moved)
    _same_raw_cells(lanes(blocks, steps), want)


@pytest.mark.parametrize("steps", [3, 6])
@pytest.mark.parametrize("window", ["interior", "interior-z"])
def test_the_first_call_of_a_dispatch_assumes_nothing_of_the_shell(window, steps):
    """Blocks whose SHELL is garbage at entry, only the interior filled: the
    dispatch's first call still makes every fill the parent's makes (that is what
    the narrow calls behind it rest on), so three steps leave every raw cell as
    whole calls leave it -- and what they leave in the interior does not depend
    on the garbage.  Six steps likewise: the middle form, which fills nothing and
    leaves lanes ``[Zw, Z)`` stale, runs four times behind that shell."""
    dd, hs, _, lanes, whole = _lanes_case(window)
    blocks = _seeded_blocks(dd, hs, 7, garbage=True)
    got = lanes(blocks, steps)
    _same_raw_cells(got, whole(blocks, steps))
    clean = lanes(_seeded_blocks(dd, hs, 7), steps)
    for h in hs:
        dd._curr = dict(got)
        a = dd.quantity_to_host(h)
        dd._curr = dict(clean)
        assert np.array_equal(a, dd.quantity_to_host(h)), h.name


def _pass_blocks(closed):
    """Per ``stream_plane_pass`` call of a traced program (a loop body once),
    sorted: the last dimension of its in blocks and of its out blocks."""
    from stencil_tpu.analysis import jaxpr as jx

    said = []
    for e in jx.iter_eqns(closed):
        if e.primitive.name != "pallas_call" or "stream_plane_pass" not in str(e.params.get("name")):
            continue
        gm = e.params["grid_mapping"]
        lanes = [
            int(getattr(bm.block_shape[-1], "block_size", bm.block_shape[-1]))
            for bm in gm.block_mappings if len(bm.block_shape) == 3
        ]
        (lanes_in,), (lanes_out,) = set(lanes[: gm.num_inputs - 1]), set(lanes[gm.num_inputs - 1:])
        said.append((lanes_in, lanes_out))
    return sorted(said)


@pytest.mark.parametrize("window", ["interior", "interior-z"])
@pytest.mark.parametrize("steps,forms", [
    (1, ["ZZ"]), (2, ["ZW", "WZ"]), (3, ["ZW", "WW", "WZ"]), (6, ["ZW", "WW", "WZ"]),
])
def test_a_call_moves_the_lane_tile_behind_the_window_one_way(window, steps, forms):
    """Bytes counted, never time: the traced dispatch holds one call a form --
    the loop body once --: the first call's out blocks and every later call's in
    blocks end in ``Zw`` = 256 lanes where the parent's end in ``Z`` = 258 on
    both sides, and so do the out blocks of every call but the last; the first
    call's in blocks and the last call's out blocks are whole rows.  A dispatch
    of ONE step is one whole call, of two holds no middle form; the parent's
    program of six is one whole call in its loop."""
    dd, _, _, lanes, whole = _lanes_case(window)
    width = {"Z": 258, "W": 256}
    closed = jax.make_jaxpr(lanes, static_argnums=1)(dd._curr, steps)
    assert _pass_blocks(closed) == sorted((width[f[0]], width[f[1]]) for f in forms)
    closed = jax.make_jaxpr(whole, static_argnums=1)(dd._curr, steps)
    assert _pass_blocks(closed) == [(width["Z"], width["Z"])]


_T, _F = True, False


@pytest.mark.parametrize("window", ["interior", "interior-z"])
@pytest.mark.parametrize("steps,forms", [
    (1, [(_T, _T)]), (2, [(_T, _F), (_F, _T)]),
    (3, [(_T, _F), (_F, _F), (_F, _T)]), (6, [(_T, _F), (_F, _F), (_F, _T)]),
])
def test_a_dispatch_traces_one_call_a_lane_form(window, steps, forms, monkeypatch):
    """The ``(shell_in, shell_out)`` of the calls a dispatch TRACES, in order:
    one whole call for one step; first and last for two, no middle form; first,
    middle, last for three and for six alike -- the loop traces its body once,
    whatever its trips.  The pass is stubbed (it hands its blocks back), so this
    traces the dispatch's structure and the step's exchange, no Pallas body."""
    dd, _, _, lanes, _ = _lanes_case(window)
    seen = []

    def spy(kernel, names, raws, *a, shell_in=True, shell_out=True, **kw):
        seen.append((shell_in, shell_out))
        return list(raws)

    monkeypatch.setattr(sm, "stream_plane_pass_tiled", spy)
    # (a fresh function: jax would answer ``lanes``' own from its trace cache)
    jax.make_jaxpr(lambda curr: lanes.__wrapped__(curr, steps))(dd._curr)
    assert seen == forms


def _one_pass_plan(**over):
    p = {"tile_rows": 128, "reads": ("a", "b"), "writes": ("a", "b"), "renames": ()}
    plan = {"route": "plane", "alias": True, "overlap": "off", "halo": "array",
            "plane_window": "interior",
            "pass_wrap_axes": "yz", "stages": ({"passes": (p,)},)}
    for key, v in over.items():
        (p if key in p else plan)[key] = v
    return plan


@pytest.mark.parametrize("over,lanes", [
    ({}, "window"),
    ({"plane_window": "interior-z", "pass_wrap_axes": "z"}, "window"),
    ({"tile_rows": 0}, "raw"),  # whole planes: stream_plane_pass has no such forms
    ({"alias": False}, "raw"),  # the lanes a narrow call leaves are the aliased block's
    ({"overlap": "split"}, "raw"),  # (the split schedule keeps fresh outputs)
    # ... and its exterior bands, like the fused side buffers, are cut from whole
    # raw blocks: whatever the fills say, only the default schedule takes the forms
    ({"overlap": "split", "alias": False}, "raw"),
    ({"halo": "fused"}, "raw"),
    ({"writes": ("a",)}, "raw"),  # a read-only operand's low z halo is filled in VMEM only
    ({"plane_window": "raw", "pass_wrap_axes": ""}, "raw"),
    ({"renames": (("a", "b"),)}, "raw"),
])
def test_who_takes_the_window_lanes_is_read_off_the_plan(over, lanes):
    assert sp.plane_lanes_form(_one_pass_plan(**over)) == lanes


def test_a_step_of_several_passes_keeps_whole_calls():
    """A later pass of the dispatch's first step would fill its low z halo from
    lanes an earlier pass left stale: a step of two passes, or of two stages,
    moves whole raw planes every call."""
    plan = _one_pass_plan()
    (p,) = plan["stages"][0]["passes"]
    assert sp.plane_lanes_form({**plan, "stages": ({"passes": (p, p)},)}) == "raw"
    assert sp.plane_lanes_form({**plan, "stages": plan["stages"] * 2}) == "raw"


@pytest.mark.parametrize("shape,mesh,kw,said", [
    ((512,) * 3, (1, 1, 1), {}, ("interior", 128, "window")),
    ((1024, 1024, 512), (2, 2, 1), {}, ("interior-z", 128, "window")),
    ((256,) * 3, (1, 1, 1), {"route": "plane", "m": 1}, ("interior", 0, "raw")),
])
def test_domain_step_says_the_lanes_of_the_plan(shape, mesh, kw, said, monkeypatch):
    """``domain.step`` carries ``plane_lanes`` beside ``tile_rows`` / ``y_tiles``,
    as the resolved plan has it: both card-filling boxes take the window's lanes,
    a box whose planes fit a pass whole keeps its program."""
    sim = _lbm(shape, monkeypatch, mesh=mesh)
    plan = _resolve(sim, **kw)
    args = sm.stream_span_args(plan, RADIUS, 19)
    assert (plan["plane_window"], plan["tile_rows"], plan["plane_lanes"]) == said
    assert (args["plane_window"], args["tile_rows"], args["plane_lanes"]) == said


def _step_span_args(monkeypatch, dispatch):
    """The arguments of the one ``domain.step`` span that ``dispatch()`` opens."""
    from stencil_tpu import telemetry
    from stencil_tpu.telemetry import names as tm

    seen = []
    real = telemetry.span

    def spy(name, *a, **kw):
        seen.append((name, kw))
        return real(name, *a, **kw)

    with monkeypatch.context() as mp:
        mp.setattr(telemetry, "span", spy)
        dispatch()
    (kw,) = [kw for name, kw in seen if name == tm.SPAN_STEP]
    return kw


def test_a_dispatch_says_its_lanes_on_the_span(monkeypatch):
    """The span a dispatch of the model opens, held to the plan it ran (the
    model and the three-step dispatch ``test_the_model_through_y_tiles_matches_
    the_reference[32]`` built: nothing is traced again)."""
    sim = _small_lbm(monkeypatch, 32)
    _seed_populations(sim, 3)
    kw = _step_span_args(monkeypatch, lambda: sim.step(3))
    plan = sim._step._stream_plan
    assert (kw["steps"], kw["plane_lanes"], kw["narrow_calls"]) == (3, "window", 1)
    assert (kw["plane_lanes"], kw["tile_rows"], kw["y_tiles"]) == (
        plan["plane_lanes"], plan["tile_rows"], plan["y_tiles"])
    assert not sim._step._resilience.descents


@pytest.mark.parametrize("lanes,steps,narrow", [
    ("window", 1, 0), ("window", 2, 0), ("window", 3, 1), ("window", 6, 4), ("raw", 6, None),
])
def test_domain_step_counts_the_calls_that_moved_the_window_both_ways(lanes, steps, narrow, monkeypatch):
    """``narrow_calls`` on ``domain.step`` beside ``plane_lanes`` and ``steps``:
    the calls of THAT dispatch between its two edge calls, ``steps - 2`` and 0 for
    a dispatch of one or two -- off the list the dispatch runs (``plane_lane_
    forms``) --, and no such key where ``plane_lanes`` is "raw".  The dispatch
    itself is idle here (the model's plan and span hooks on a step that hands the
    blocks back): nothing is traced."""
    sim = _small_lbm(monkeypatch, 32)
    plan = {**sim._step._stream_plan, "plane_lanes": lanes}
    assert sim._step._stream_plan["plane_lanes"] == "window"
    forms = dict(sp.plane_lane_forms(plan, steps))
    assert sum(forms.values()) == steps and forms.get((False, False), 0) == (narrow or 0)

    def idle(curr, steps):
        return curr

    idle._span_args = lambda: sm.stream_span_args(plan, RADIUS, 19)
    idle._dispatch_args = lambda steps: sm.stream_dispatch_args(plan, steps)
    kw = _step_span_args(monkeypatch, lambda: sim.dd.run_step(idle, steps))
    assert (kw["steps"], kw["plane_lanes"], kw.get("narrow_calls")) == (steps, lanes, narrow)
