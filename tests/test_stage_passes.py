"""A STAGE of several plane passes that write by RENAME (ISSUE 57:
``ops/stream_plan.py plan_plane_passes``, ``ops/stream_pass.py
stream_plane_pass_tiled(renames=)``, ``ops/stream.py _build_plane_step``): the
rename rule applied pass by pass, tiled or whole, the clash check reading blocks
and not names, the handles swapped once when a stage's last pass has run.

The mechanism is driven by a light kernel of three two-buffer fields and a
read-only coefficient (``u v w``, their ``*_prev`` and ``c``: Astaroth's MHD
substep costs 25 s of lowering a strip-form call and shows nothing more), at
radius 1 and at radius 3: a stage of two and of three y-tiled passes is bitwise
the one-pass whole-plane form and matches a plain ``jax.numpy`` reference; the
handles after one, two and three stages; the tiled pass with ``renames=`` at
radius 3 over one, two and four y tiles bitwise the whole-plane strip form; the
512^3 plan of ``AstarothMHD`` by hand; the model once through tiles against its
plain reference; a plan that must still clash still raises."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from stencil_tpu.core.dim3 import Dim3
from stencil_tpu.core.radius import Radius
from stencil_tpu.domain import DistributedDomain
from stencil_tpu.ops import stream as sm
from stencil_tpu.ops import stream_pass as spass
from stencil_tpu.ops import stream_plan as sp
from stencil_tpu.telemetry import names as tm

FIELDS = ("u", "v", "w")
NAMES = FIELDS + tuple(f + "_prev" for f in FIELDS) + ("c",)
SHAPE = (6, 64, 128)


def _kernel(r):
    """Three fields advanced from each other's neighbours at distance ``r`` on
    every kind of diagonal and from their own second buffers, ``c`` a read-only
    centre-plane operand, the cells' own coordinates; each ``*_prev`` returned as
    its field's centre plane ITSELF: three renames."""

    def kernel(views, info):
        u, v, w, c = views["u"], views["v"], views["w"], views["c"].center()
        x, y, z = info.coords()
        pos = 1e-3 * (x + 2 * y + 3 * z).astype(jnp.float32)
        new = {
            "u": 0.5 * u.center()
            + 0.125 * (u.sh(r, r, 0) - u.sh(-r, 0, r) + v.sh(0, -r, -r) + w.sh(r, 0, 0))
            + c * (u.center() - views["u_prev"].center()) + pos,
            "v": 0.5 * v.center() + 0.125 * (v.sh(-r, 0, 0) + u.sh(1, -1, 0) - w.sh(0, r, 1))
            + c * views["v_prev"].center(),
            "w": 0.25 * (w.sh(0, 0, r) + w.sh(0, -r, 0)) + 0.125 * (u.sh(-1, 1, -1) + v.sh(r, 0, -r))
            - c * views["w_prev"].center() + pos,
        }
        new.update({f + "_prev": views[f].center() for f in FIELDS})
        return new

    return kernel


class _Rolled:
    """``sh`` on a whole periodic array: the plain reference's view."""

    def __init__(self, a):
        self._a = a

    def sh(self, dx=0, dy=0, dz=0):
        return jnp.roll(self._a, (-dx, -dy, -dz), (0, 1, 2))

    def center(self):
        return self._a


class _Coords:
    def coords(self):
        X, Y, Z = SHAPE
        i32 = lambda n: jnp.arange(n, dtype=jnp.int32)  # noqa: E731
        return i32(X)[:, None, None], i32(Y)[None, :, None], i32(Z)[None, None, :]


def _reference(r, state, stages):
    """``stages`` applications of the kernel in plain ``jax.numpy`` f32 on whole
    arrays (``jnp.roll``), the renames as the assignments they stand for."""
    kernel = _kernel(r)
    s = {k: jnp.asarray(v) for k, v in state.items()}
    for _ in range(stages):
        s = {**s, **kernel({k: _Rolled(v) for k, v in s.items()}, _Coords())}
    return {k: np.asarray(v) for k, v in s.items()}


def _domain(r):
    dd = DistributedDomain(*SHAPE)
    dd.set_radius(Radius.constant(r))
    dd.set_devices(jax.devices()[:1])
    hs = [dd.add_data(n, dtype=jnp.float32) for n in NAMES]
    dd.realize()
    return dd, hs


def _seeded(seed=57):
    rng = np.random.default_rng(seed)
    return {n: rng.uniform(0.5, 1.5, SHAPE).astype(np.float32) * (0.1 if n == "c" else 1.0)
            for n in NAMES}


#: per (radius, budget, stages): the host fields after one dispatch of ``steps``
def _run(monkeypatch, r, budget, stages, steps):
    monkeypatch.setenv("STENCIL_HALO_BLEND", "1")  # the pass's own y and z fills, as on the chip
    if budget:
        monkeypatch.setenv("STENCIL_VMEM_LIMIT_BYTES", str(budget))
    dd, hs = _domain(r)
    for h in hs:
        dd.set_quantity(h, _seeded()[h.name])
    step = dd.make_step((_kernel(r),) * stages, engine="stream", stream_path="plane",
                        x_radius=r, interpret=True)
    dd.run_step(step, steps)
    assert not step._resilience.descents
    return {h.name: dd.quantity_to_host(h) for h in hs}, step


#: the budgets under which the planner cuts a stage of the kernel into three and
#: into two y-tiled passes ((u) (v) (w); (u) (v w)): the model's own bytes of the
#: widest pass each (a y tile of 32 rows at radius 1, of the whole 64 at radius
#: 3, where every tile brings six margin tiles a plane)
_BUDGETS = {1: {3: 4_122_304, 2: 4_376_256}, 3: {3: 5_105_344, 2: 5_523_136}}


@pytest.mark.parametrize("r,passes,stages,steps", [
    (1, 3, 1, 1), (1, 3, 2, 1), (1, 3, 3, 1),  # the handles after one, two and three stages
    (1, 3, 3, 2), (1, 3, 1, 3),  # a trip of the step loop; a trip and a remainder
    (1, 2, 3, 1), (3, 3, 3, 1), (3, 2, 1, 1), (3, 2, 2, 1),
])
def test_a_stage_of_several_renaming_passes_is_bitwise_one_pass(r, passes, stages, steps, monkeypatch):
    """The same seeded fields through the one-pass whole-plane form (the default
    budget: one pass a stage, three renames) and through a stage the planner cuts
    into ``passes`` y-tiled passes, each writing its new fields into their
    ``*_prev`` blocks: EVERY interior cell of all seven quantities bitwise equal
    after one, two and three stages (an odd count of swaps leaves every field in
    what was its second buffer's block), after two steps of three stages (one
    trip of the step loop) and after three steps of one (a trip and a remainder).
    A pass that read the NEW value of a field an earlier pass of the stage has
    computed -- the handles swapped too early -- or a ``*_prev`` that was not the
    stage's entry value would show; so would a read-only operand written."""
    want, whole = _run(monkeypatch, r, None, stages, steps)
    (p,) = whole._stream_plan["stages"][0]["passes"]
    assert (len(p["writes"]), len(p["renames"]), p["tile_rows"]) == (3, 3, 0)
    got, tiled = _run(monkeypatch, r, _BUDGETS[r][passes], stages, steps)
    plan = tiled._stream_plan
    for st in plan["stages"]:
        assert len(st["passes"]) == passes and all(p["tile_rows"] for p in st["passes"]), st
        assert sum(len(p["renames"]) for p in st["passes"]) == 3
        assert [w for p in st["passes"] for w in p["writes"]] == list(FIELDS)
    assert plan["renamed"] == tuple(f + "_prev" for f in FIELDS) and plan["writers"] == FIELDS
    assert plan["steps_per_trip"] == (2 if stages % 2 else 1)
    assert plan["plane_lanes"] == "raw"  # (a step of several passes keeps whole calls)
    for name in NAMES:
        assert np.isfinite(want[name]).all() and np.array_equal(got[name], want[name]), name
    assert np.array_equal(got["c"], _seeded()["c"])
    if steps == 1:  # ... and both are the plain reference's numbers
        ref = _reference(r, _seeded(), stages)
        assert max(np.abs(ref[n] - _seeded()[n]).max() for n in FIELDS) > 0.1  # (it has moved)
        for name in NAMES:
            np.testing.assert_allclose(got[name], ref[name], rtol=0, atol=2e-6, err_msg=name)


def test_the_passes_of_a_renaming_stage_run_under_scopes_of_their_own(monkeypatch):
    """The traced program of a stage of three renaming passes: each pass's
    ``pallas_call`` under ``step.stage.<k>/pass.<i>/step.pass``, the output of
    pass ``i`` aliased onto the ``*_prev`` operand of its field; ``domain.step``
    says the passes stage by stage; a stage of ONE pass has no such scope and no
    such key."""
    from stencil_tpu.analysis import jaxpr as jx

    monkeypatch.setenv("STENCIL_HALO_BLEND", "1")
    monkeypatch.setenv("STENCIL_VMEM_LIMIT_BYTES", str(_BUDGETS[1][3]))
    dd, _ = _domain(1)
    kernel = _kernel(1)
    plan = sp.resolve_stream_plan(dd, (kernel, kernel), 1, sp.plan_stream(dd, 1, "plane", False), True)
    step = sm._build_stream_step(dd, (kernel, kernel), 1, plan, interpret=True)
    closed = jax.make_jaxpr(step, static_argnums=1)(dd._curr, 1)
    calls = [e for e in jx.iter_eqns(closed) if e.primitive.name == "pallas_call"
             and e.params.get("name") == tm.KERNEL_STREAM_PLANE_PASS]
    scopes = [jx.name_stack_str(e) for e in calls]
    assert len(calls) == 6
    for k in range(2):
        for i in range(3):
            want = f"{tm.step_stage_span(k)}/{tm.stage_pass_span(i)}/{tm.SPAN_STEP_PASS}"
            assert sum(want in s for s in scopes) == 1, (want, scopes)
    for e in calls:  # reads (field..., its *_prev, c): the one output lands in the *_prev block
        names = [n for n in NAMES if n in plan["stages"][0]["passes"][calls.index(e) % 3]["reads"]]
        (alias,) = e.params["input_output_aliases"]
        assert names[alias[0] - 1].endswith("_prev") and alias[1] == 0, (names, alias)
    args = sm.stream_span_args(plan, 1, len(NAMES))
    assert args["passes_by_stage"] == "/".join(["w1-r5-g2-t32-y2-n1+w1-r5-g2-t32-y2-n1+w1-r5-g2-t32-y2-n1"] * 2)
    assert (args["passes"], args["renamed"], args["tile_rows"], args["y_tiles"]) == (6, "3/3", 32, 2)
    monkeypatch.delenv("STENCIL_VMEM_LIMIT_BYTES")
    one = sp.resolve_stream_plan(dd, (kernel, kernel), 1, sp.plan_stream(dd, 1, "plane", False), True)
    assert "passes_by_stage" not in sm.stream_span_args(one, 1, len(NAMES))
    closed = jax.make_jaxpr(
        sm._build_stream_step(dd, (kernel, kernel), 1, one, interpret=True), static_argnums=1)(dd._curr, 1)
    assert not any(tm.SPAN_STAGE_PASS + "." in jx.name_stack_str(e) for e in jx.iter_eqns(closed))


@pytest.mark.parametrize("y_tiles", [1, 2, 4])
@pytest.mark.parametrize("alias", [False, True], ids=["fresh", "in-place"])
def test_the_tiled_pass_renames_at_radius_three(y_tiles, alias):
    """``stream_plane_pass_tiled(renames=)`` at RADIUS 3 (three margin tiles a
    side of every y tile, eight planes a ring) against ``stream_plane_pass``'s
    whole-plane strip form with the same renames, the plane whole, in two and in
    four y tiles: every raw cell of the two outputs bitwise equal -- they come
    back under ``u`` and ``w`` with their OWN x-shell planes and tail rows passed
    through --, the handles of ``u_prev`` / ``w_prev`` the arrays that went in as
    ``u`` / ``w``, everything else the array that went in."""
    r, tile = 3, 8
    n, lo, hi = (5, 16 * tile, 128), (3, 3, 3), (3, 3, 3)
    names = ["u", "v", "w", "u_prev", "w_prev", "c"]
    kernel = _kernel(r)

    def two_of_three(views, info):  # (``v`` is read and not advanced here)
        out = kernel({**views, "v_prev": views["v"]}, info)
        return {k: out[k] for k in ("u", "w")}

    shape = tuple(m + a + b for m, a, b in zip(n, lo, hi))
    rng = np.random.default_rng(3)
    raws = [jnp.asarray(rng.standard_normal(shape), jnp.float32) for _ in names]
    fills = tuple((a, d, s, w) for a in (1, 2)
                  for d, s, w in ((0, n[a], lo[a]), (lo[a] + n[a], lo[a], hi[a])))
    common = dict(
        interpret=True, halo_readers=("u", "v", "w"), rings=("u", "v", "w"), writers=("u", "w"),
        wrap_fills=fills, renames=(("u_prev", "u"), ("w_prev", "w")), alias=alias,
    )
    args = (two_of_three, names, raws, Dim3(*lo), Dim3(*hi), r,
            jnp.asarray([5, 3, 7], jnp.int32), Dim3(64, n[1], n[2]))
    strip = 4 * tile
    want = spass.stream_plane_pass(*args, window="interior", strip=strip, **common)
    got = spass.stream_plane_pass_tiled(*args, tile_rows=n[1] // y_tiles, strip=strip, **common)
    for name, a, b in zip(names, got, want):
        assert np.isfinite(np.asarray(b)).all() and np.array_equal(np.asarray(a), np.asarray(b)), name
    assert got[3] is raws[0] and got[4] is raws[2] and got[1] is raws[1] and got[5] is raws[5]
    assert not np.array_equal(np.asarray(got[0]), np.asarray(raws[0]))


def test_the_inplace_order_contract_judges_the_renamed_tiled_pass():
    """``check_inplace_order`` on the traced tiled pass at radius 3, in place,
    with a rename: the output of ``u`` is aliased onto raw ``u_prev`` (operand 3:
    the origin, ``u``, ``v``, ``u_prev``), which is fetched LAGGED -- plane ``j`` at
    x step ``j + 3`` -- and flushed over at x step ``j + 4``: in order, every grid
    step judged."""
    from stencil_tpu import analysis
    from stencil_tpu.analysis import kernels

    r, n = 3, (5, 64, 128)
    fills = tuple((a, d, s, r) for a in (1, 2) for d, s in ((0, n[a]), (r + n[a], r)))

    def kernel(views, info):
        u, v = views["u"], views["v"]
        return {"u": 0.5 * u.center() + 0.25 * (u.sh(r, r, 0) + v.sh(-r, 0, -r)) - views["u_prev"].center()}

    def step(origin, u, v, u_prev):
        return spass.stream_plane_pass_tiled(
            kernel, ["u", "v", "u_prev"], [u, v, u_prev], Dim3(r, r, r), Dim3(r, r, r), r, origin,
            Dim3(*n), tile_rows=32, strip=32, alias=True, interpret=True, halo_readers=("u", "v"),
            writers=("u",), rings=("u", "v"), wrap_fills=fills, renames=(("u_prev", "u"),),
        )

    blk = jax.ShapeDtypeStruct(tuple(m + 2 * r for m in n), jnp.float32)
    art = analysis.trace_artifact(
        step, jax.ShapeDtypeStruct((3,), jnp.int32), blk, blk, blk,
        label="fixture:inplace-order-plane-tiled-renamed", kind="fn")
    (rep,) = kernels.kernel_reports(art.closed)
    assert {o: a.index for o, a in rep.aliases.items()} == {0: 3}
    assert len(rep.outputs[0].footprint) == (n[0] + 2 * r + r + 1) * (64 // 32 + 1)
    assert not kernels.check_inplace_order(art)


# --- the planner -----------------------------------------------------------------------


def _mhd(shape, monkeypatch, budget=None):
    from stencil_tpu.models.astaroth_mhd import AstarothMHD

    monkeypatch.setenv("STENCIL_HALO_BLEND", "1")
    if budget is not None:
        monkeypatch.setenv("STENCIL_VMEM_LIMIT_BYTES", str(int(budget)))
    sim = AstarothMHD(*shape, seed_words=None, interpret=True, devices=jax.devices()[:1])
    sim.dd.realize(allocate=False)
    return sim


def _mhd_plan(sim):
    from stencil_tpu.models.astaroth_mhd import RADIUS, SUBSTEPS

    stages = tuple(sim._substep(s) for s in range(SUBSTEPS))
    return sp.resolve_stream_plan(sim.dd, stages, RADIUS, sp.plan_stream(sim.dd, RADIUS, "auto", False), True)


def _mhd_bytes(rows, reads, rings, writes, n=512, r=3, tile=8):
    """The one VMEM model by hand at Astaroth's 512 x 512 planes of f32: a y-tiled
    pass of ``rows`` rows (``tests/test_plane_tiles.py _tiled_bytes``: pipeline +
    held + staged + stashed + one margin), or with ``rows`` 0 a whole-plane
    strip-form pass (a margin a quantity read)."""
    from test_plane_tiles import _tiled_bytes

    if rows:
        return _tiled_bytes(n, n, rows, r=r, tile=tile, reads=reads, rings=rings, writes=writes)
    pad = sp._padded_plane_bytes
    pipeline = 2 * (reads + writes) * pad(n + 2 * r, n + 2 * r, 4)
    held = (rings * (2 * r + 1) + (reads - rings)) * pad(n + 2 * r * tile, n, 4)
    return pipeline + held + writes * pad(n, n, 4) + reads * sp._VMEM_STACK_MARGIN


def test_the_card_filling_mhd_box_plans_as_the_configuration_expects(monkeypatch):
    """``AstarothMHD(512, 512, 512)`` on one device, every axis ``auto`` (nothing
    allocated): the plane route on the interior window in strips of 8 rows, three
    stages of FOUR passes -- ``lnrho`` alone over whole planes (it reads two
    fields off-centre along x), then ``ux uy``, ``uz ax ay az`` and ``ss`` over y
    tiles of 256 rows --, eight renames a stage, every pass under the budget by
    the one model; the plan the benchmark's configuration expects and its
    ``passes`` state; the legality prefilter and the VMEM verdict take it."""
    import json
    import os

    from stencil_tpu import analysis
    from stencil_tpu.models.astaroth_mhd import RADIUS

    sim = _mhd((512,) * 3, monkeypatch)
    plan = _mhd_plan(sim)
    assert (plan["route"], plan["plane_window"], plan["plane_strip"], plan["pass_wrap_axes"]) == (
        "plane", "interior", 8, "yz")
    said = [
        [(p["writes"], len(p["reads"]), len(p["rings"]), p["tile_rows"], len(p["renames"]))
         for p in st["passes"]] for st in plan["stages"]
    ]
    assert said[0] == said[1] == said[2] == [
        (("lnrho",), 5, 2, 0, 1),
        (("ux", "uy"), 10, 8, 256, 2),
        (("uz", "ax", "ay", "az"), 12, 6, 256, 4),
        (("ss",), 9, 8, 256, 1),
    ]
    budget = sp._vmem_budget()
    priced = [p["vmem_bytes"] for p in plan["stages"][0]["passes"]]
    assert priced == [
        _mhd_bytes(0, 5, 2, 1), _mhd_bytes(256, 10, 8, 2), _mhd_bytes(256, 12, 6, 4),
        _mhd_bytes(256, 9, 8, 1),
    ] == [51_519_936, 104_646_336, 101_041_856, 98_977_472]
    assert max(priced) <= budget == 104_857_600
    # no wider pass and no other tile: the momentum pair at 512 and at 128 rows, a third output
    assert min(_mhd_bytes(512, 10, 8, 2), _mhd_bytes(128, 10, 8, 2), _mhd_bytes(256, 11, 8, 3)) > budget
    assert _mhd_bytes(0, 9, 8, 1) > budget  # one momentum output alone fits no whole planes
    for st in plan["stages"]:
        for p in st["passes"]:
            assert p["renames"] == tuple((q + "_prev", q) for q in p["writes"]) and not p["prerotated"]
    assert (plan["tile_rows"], plan["y_tiles"], plan["plane_lanes"], plan["steps_per_trip"]) == (
        256, 2, "raw", 2)
    assert plan["alias"] and len(plan["renamed"]) == 8 and len(plan["writers"]) == 8
    args = sm.stream_span_args(plan, RADIUS, 16)
    assert (args["stages"], args["passes"], args["renamed"], args["aliased"]) == (3, 12, "8/8/8", "16/16/16")
    assert args["passes_by_stage"] == "/".join(
        ["w1-r5-g2-t0-y1-n1+w2-r10-g8-t256-y2-n2+w4-r12-g6-t256-y2-n4+w1-r9-g8-t256-y2-n1"] * 3)
    with open(os.path.join(os.path.dirname(os.path.dirname(__file__)), "benchmark", "configs",
                           "astaroth-mhd-512.json")) as f:
        config = json.load(f)
    ran = {"route": plan["route"], "storage": "native", "quantities": args["quantities"],
           "stages": args["stages"], "renamed": len(plan["renamed"]), "passes": args["passes"]}
    assert config["expect"] == ran
    assert [(p["writes"], p["reads"]) for p in config["passes"]] == [(len(s[0]), s[1]) for s in said[0]]
    assert config["substeps"] * len(config["passes"]) == args["passes"]
    assert analysis.check_vmem(sim.dd, plan.plan) is None
    monkeypatch.setattr("stencil_tpu.analysis.kernels._mosaic_target", lambda: False)
    assert analysis.check_kernel_legal(sim.dd, dict(plan.plan)) is None
    # the box the benchmark already had keeps its one pass a stage
    small = _mhd_plan(_mhd((256,) * 3, monkeypatch))
    assert [len(st["passes"]) for st in small["stages"]] == [1, 1, 1] and small["tile_rows"] == 0


def test_a_pass_that_reads_what_an_earlier_one_wrote_into_its_own_block_still_raises(monkeypatch):
    """The clash check reads BLOCKS: a later output that reads a field an earlier
    pass has written into the field's OWN block (no rename: ``d <- 2 a`` is a value
    of its own) cannot be split off, tiles or none, and the refusal names the
    block; the same stage with ``d <- a`` (a rename) plans."""
    monkeypatch.setenv("STENCIL_HALO_BLEND", "1")
    dd = DistributedDomain(*SHAPE)
    dd.set_radius(Radius.constant(1))
    dd.set_devices(jax.devices()[:1])
    for n in ("a", "b", "d"):
        dd.add_data(n, dtype=jnp.float32)
    dd.realize(allocate=False)

    def advance(views):
        a, b = views["a"], views["b"]
        return (0.5 * (a.sh(1, 0, 0) + a.sh(-1, 0, 0)) + b.sh(0, 1, 0),
                0.5 * (b.sh(1, 0, 0) - a.sh(0, 0, 1)))

    def scaled(views, info):
        a, b = advance(views)
        return {"a": a, "b": b, "d": 2.0 * views["a"].center()}

    def leapfrog(views, info):
        a, b = advance(views)
        return {"a": a, "b": b, "d": views["a"].center()}

    def resolve(kernel):
        return sp.resolve_stream_plan(dd, kernel, 1, sp.plan_stream(dd, 1, "plane", False), True)

    # under the budget of the tiled pass that writes (a, b): no third output joins it
    monkeypatch.setenv("STENCIL_VMEM_LIMIT_BYTES", "3900000")
    with pytest.raises(sp.FitsNoPass, match=(
            r"writes \('d',\) \(into the blocks of \('d',\)\) reads \('a',\), whose block an earlier "
            r"pass of the same stage has already written in place")):
        resolve(scaled)
    passes = resolve(leapfrog)["stages"][0]["passes"]
    assert [p["writes"] for p in passes] == [("a", "b")] and passes[0]["renames"] == (("d", "a"),)
    # ... and tighter still the rename is what makes TWO passes legal: ``b`` reads the
    # stage's entry value of ``a``, whose block nothing has written (the new ``a`` is in
    # ``d``'s), where the copying stage clashes on ``b`` already
    monkeypatch.setenv("STENCIL_VMEM_LIMIT_BYTES", "3700000")
    with pytest.raises(sp.FitsNoPass, match=r"writes \('b',\) \(into the blocks of \('b',\)\) reads \('a',\)"):
        resolve(scaled)
    passes = resolve(leapfrog)["stages"][0]["passes"]
    assert [(p["writes"], p["renames"]) for p in passes] == [(("a",), (("d", "a"),)), (("b",), ())]


# --- the model once ----------------------------------------------------------------------


def test_the_mhd_model_through_tiled_renaming_passes_matches_the_reference(monkeypatch):
    """``AstarothMHD`` at 8 x 32 x 128 through its normal path, every axis
    ``auto``, the budget tightened until each substep is SEVERAL y-tiled passes
    that write by rename: every cell of all sixteen quantities against
    ``models/astaroth_mhd_reference.py`` after two time steps (six stages, one
    trip of the step loop), to the tolerance the one-pass plan is held to."""
    from stencil_tpu.models import astaroth_mhd_reference as ref
    from stencil_tpu.models.astaroth_mhd import AstarothMHD

    monkeypatch.setenv("STENCIL_HALO_BLEND", "1")
    monkeypatch.setenv("STENCIL_VMEM_LIMIT_BYTES", str(_MHD_SMALL_BUDGET))
    sim = AstarothMHD(8, 32, 128, interpret=True, seed_words=None, devices=jax.devices()[:1])
    sim.realize()
    plan = sim._step._stream_plan
    assert (plan["route"], plan["plane_window"]) == ("plane", "interior")
    for st in plan["stages"]:
        assert len(st["passes"]) >= 2 and all(p["tile_rows"] for p in st["passes"]), st
        assert sum(len(p["renames"]) for p in st["passes"]) == 8
    # (filled from the host: sixteen traced fills are a fifth of this case's time)
    state = ref.global_fields(sim.setup, np.asarray((3, 1, 4, 1), dtype=np.uint32))
    for q in ref.QUANTITIES:
        sim.dd.set_quantity(sim.handles[q], np.asarray(state[q]))
    sim.step(1)
    want = ref.steps(sim.setup, state, 1)
    assert not sim._step._resilience.descents
    for q in ref.QUANTITIES:
        assert float(jnp.abs(want[q] - state[q]).max()) > 1e-5, q  # (it has moved)
        np.testing.assert_allclose(sim.field(q), np.asarray(want[q]), rtol=0, atol=2e-6, err_msg=q)


#: a budget under which the 8 x 32 x 128 box's substeps take several tiled passes
_MHD_SMALL_BUDGET = 7_150_000  # (lnrho ux uy uz) (ax ay az ss): 7,128,768 B each by the model
