"""``AstarothMHD`` (Astaroth's compressible MHD step: eight fields and their
eight second buffers, sixth-order differences at full radius 3 with in-plane
diagonals, three Runge-Kutta substeps a step) against the plain reference
``models/astaroth_mhd_reference.py``: every cell of all sixteen quantities on
the plane route and on the XLA slice engine, after odd and even step counts
(three renames a step: the carry's period is two steps), on meshes where an
edge halo crosses two wires; an edge halo or a plane corner left unfilled
comes out wrong; the
plan at the benchmark's size; the ``domain.step`` span's account.  The
non-cubic box, the wires' account and the drivers are in
``tests/test_mhd_wide_box.py``, the reference's own physics in
``tests/test_mhd_reference.py``, the window beside a split y in
``tests/test_mhd_split_y.py``: a file is one worker's, and this model's
programs cost 25 s of lowering each (ROADMAP D13: a file under 400 s)."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from stencil_tpu import telemetry
from stencil_tpu.models import astaroth_mhd_reference as ref
from stencil_tpu.models.astaroth_mhd import RADIUS, AstarothMHD
from stencil_tpu.ops import stream_plan as sp
from stencil_tpu.telemetry import names as tm

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORDS = (0x1234, 0xBEEF, 0x5EED, 0xC0FFEE)
#: the program and the reference run the same ``substep``; the compilers round apart
TOL = 1e-6
#: rows of a strip of the cell's passes: 4 vregs a value on 256-lane planes (ISSUE 46)
MHD_STRIP = 16
N = 16


def _config():
    with open(os.path.join(ROOT, "benchmark", "configs", "astaroth-mhd-256.json")) as f:
        return json.load(f)


def _setup(n=N, **kw):
    """Two whole waves an axis at most: 16 cells hold them at sixth order."""
    return ref.MhdSetup((n, n, n), max_waves=2, **kw)


def _sim(mesh=(1, 1, 1), impl="pallas", **kw):
    sim = AstarothMHD(N, N, N, setup=_setup(), interpret=True, seed_words=None, kernel_impl=impl,
                      devices=jax.devices()[: int(np.prod(mesh))], **kw)
    sim.dd.set_partition(*mesh)
    sim.realize()
    return sim


_SIMS = {}


def _shared(mesh=(1, 1, 1), impl="pallas"):
    """One realized 16^3 model a (mesh, engine), shared by the cases that load
    their own state into it: building and compiling it is most of a case's
    time."""
    if (mesh, impl) not in _SIMS:
        _SIMS[mesh, impl] = _sim(mesh, impl)
    return _SIMS[mesh, impl]


_STATE = {}


def _state(words=WORDS):
    if words not in _STATE:
        _STATE[words] = ref.global_fields(_setup(), np.asarray(words, dtype=np.uint32))
    return _STATE[words]


def _load(sim, state):
    for name in ref.QUANTITIES:
        sim.dd.set_quantity(sim.handles[name], np.asarray(state[name]))


def _errors(sim, want) -> dict:
    return {q: float(np.abs(sim.field(q) - np.asarray(want[q])).max()) for q in ref.QUANTITIES}


# --- the program against the reference -------------------------------------------------


@pytest.mark.parametrize("steps", [1, 2, 3, 4])
def test_plane_route_matches_the_reference(steps):
    """One device, ``steps`` time steps: 1 is a step behind an empty loop (the
    handles come back permuted), 2 a trip of two, 3 a trip and a step behind
    the loop, each ONE dispatch; every cell of the eight fields and of the
    eight second buffers.  4 is two trips -- the program of 2 with ``length=2``
    on its loop and nothing else changed, which the traced programs are held to
    (tracing lowers nothing: ISSUE 55) -- and runs as two dispatches of that
    trip, the second from the handles the first brought home."""
    sim = _shared()
    _load(sim, _state())
    if steps == 4:
        from program_fingerprint import step_loop_and_text

        built = sim._step._resilience.built()
        (trips, of_two), (trips4, of_four) = (
            step_loop_and_text(jax.make_jaxpr(built, static_argnums=1)(sim.dd._curr, n)) for n in (2, 4))
        assert (trips, trips4) == (1, 2) and of_four == of_two
        sim.step(2)
        sim.step(2)
    else:
        sim.step(steps)
    plan = sim._step._stream_plan
    assert (plan["route"], plan["steps_per_trip"]) == ("plane", 2)
    assert max(_errors(sim, ref.steps(sim.setup, _state(), steps)).values()) < TOL


@pytest.mark.parametrize("steps", [1, 2, 3, 4])
def test_the_xla_engine_runs_the_same_kernels(steps):
    """The XLA slice engine, a step a dispatch (it has no carry to bring home)."""
    sim = _shared(impl="jnp")
    _load(sim, _state())
    for _ in range(steps):
        sim.step(1)
    assert max(_errors(sim, ref.steps(sim.setup, _state(), steps)).values()) < TOL


@pytest.mark.parametrize("steps", [1, 2, 3])
@pytest.mark.parametrize("mesh", [(2, 1, 1), (2, 2, 1), (2, 2, 2)])
def test_model_matches_the_reference_across_devices(mesh, steps):
    """CPU meshes: the mixed differences read the x-y, x-z and y-z EDGE halos
    at radius 3, which on [2,2,2] cross two wires each: the x, then y, then z
    sweeps must have carried them.  A step a dispatch: every second one starts
    from permuted handles."""
    sim = _shared(mesh=mesh)
    _load(sim, _state())
    for _ in range(steps):
        sim.step(1)
    assert tuple(sim.dd.mesh_dim()) == mesh
    assert max(_errors(sim, ref.steps(sim.setup, _state(), steps)).values()) < TOL


def test_the_seeded_state_matches_the_reference_and_takes_the_seed_as_an_argument():
    """The seeded plane waves through ``fill(args=)``: one compiled fill a
    quantity serves every seed (``init_by_coords(args=)``), every field
    nowhere constant, each ``*_prev`` its field."""
    sim = _shared()
    fields = ref.seeded_fields(sim.setup)
    c = (np.arange(4)[:, None, None], np.arange(4)[None, :, None], np.arange(4)[None, None, :])
    seeds = [np.asarray(WORDS, dtype=np.uint32), np.asarray(WORDS, dtype=np.uint32) + 5]
    assert len({jax.jit(fields["uy"]).lower(*c, w).as_text() for w in seeds}) == 1
    sim.fill(fields, (seeds[1],))
    want = ref.global_fields(sim.setup, seeds[1])
    assert max(_errors(sim, want).values()) < 5e-7
    other = _state()
    for q in ref.FIELDS:
        a = np.asarray(want[q])
        assert float(np.abs(a - np.asarray(other[q])).max()) > 1e-3  # another seed, another state
        rest = sim.setup.lnrho0 if q == "lnrho" else 0.0
        assert float(np.abs(a - rest).max()) <= sim.setup.amplitude * (1 + 1e-6)
        for axis in range(3):  # constant along no line
            assert float(np.abs(np.diff(a, axis=axis)).max(axis=axis).min()) > 0.0
        np.testing.assert_array_equal(a, np.asarray(want[q + "_prev"]))


def test_the_interior_window_matches_the_reference(monkeypatch):
    """A box whose y-z interior is whole vector tiles (32 x 128) on one device,
    the blend kernels on as on the chip, so the pass fills both in-plane halos
    itself (ISSUE 45): the passes work on the INTERIOR plane -- no halo in the
    kernel's windows at all, the rotates' wraparound supplies the y, z and y-z
    corner reads on interior and x-shell planes alike (the x-y and x-z edge
    halos of the mixed differences) -- and every cell of all sixteen
    quantities matches the reference after ONE time step of three times the
    Courant number (the time three steps covered until ISSUE 55; a dispatch of
    three is ``test_plane_route_matches_the_reference[3]``'s: each strip-form
    call costs 15 s of lowering, a step holds three, and the window is the
    same in every one).  The box is periodic and nowhere zero: a wrong wrap
    shows; 8 planes are the fewest a radius-3 ring takes."""
    monkeypatch.setenv("STENCIL_HALO_BLEND", "1")
    shape = (8, 32, 128)
    setup = ref.MhdSetup(shape, max_waves=2, courant=0.9)
    sim = AstarothMHD(*shape, setup=setup, interpret=True, seed_words=None,
                      devices=jax.devices()[:1])
    sim.realize()
    state = ref.global_fields(setup, np.asarray(WORDS, dtype=np.uint32))
    _load(sim, state)
    sim.step(1)
    said = sim._step._span_args()
    assert (said["route"], said["wrapped"], said["plane_window"]) == ("plane", "yz", "interior")
    # ... in its strip form (ISSUE 46): the kernel over ONE strip of the plane's
    # four tiles, every y shift a read of another tile, the margins' wrap included
    assert said["plane_strip"] == 32
    assert (said["renamed"], said["steps_per_trip"]) == ("8/8/8", 2)
    want = ref.steps(setup, state, 1)
    moved = min(float(jnp.abs(want[q] - state[q]).max()) for q in ref.FIELDS)
    assert moved > 100 * TOL, moved  # every field advanced: the comparison sees the step
    assert max(_errors(sim, want).values()) < TOL


def test_bf16_storage_fails_the_tolerance():
    sim = _sim(storage_dtype="bf16")
    _load(sim, _state())
    sim.step(1)
    assert sim.dd.storage_dtype() == "bf16"
    assert max(_errors(sim, ref.steps(sim.setup, _state(), 1)).values()) > 100 * TOL


@pytest.mark.parametrize("which", ["edge_xy", "corner_yz"])
def test_an_unfilled_edge_halo_or_plane_corner_comes_out_wrong(which, monkeypatch):
    """Mesh [2,2,2]: a sweep carries the halo of the axes swept before it
    along, which is what fills the edges.  ``edge_xy``: the x halo planes' y
    halo rows left as the y faces carried them, the corner relay of the joint
    x-y sweep taken out; ``corner_yz``: the
    y halo rows' z halo columns -- the corner of every x-plane the pass loads
    -- put back after the z sweep.  Every face halo is still filled, and the
    mixed differences of the second and third substeps come out wrong in the
    fields that read that diagonal (``grad div``: ``ux, uy`` in the x-y plane,
    ``uy, uz`` in the y-z plane, and the potentials likewise)."""
    from stencil_tpu.ops import exchange as ex

    if which == "edge_xy":
        # x and y fly jointly on this mesh: the y faces are cut before the x
        # halo is in, and the relay is what puts the x halo planes' y halo rows
        # right -- without it they hold what the neighbour's stale halo held
        monkeypatch.setattr(ex, "_relay_corners", lambda first, second: second)
    else:
        real = ex._sweep_group

        def faces_only(blocks, group, *rest):
            before = list(blocks)
            out = real(blocks, group, *rest)
            if [s.axis for s in group] != [2]:  # z sweeps alone, behind the pair
                return out
            (s,) = group
            stale = []
            for new, old in zip(out, before):
                for a in (slice(0, RADIUS), slice(-RADIUS, None)):  # the y halo rows' ...
                    for b in (slice(0, s.r_lo), slice(new.shape[2] - s.r_hi, None)):  # ... z halo columns
                        new = new.at[:, a, b].set(old[:, a, b])
                stale.append(new)
            return stale

        monkeypatch.setattr(ex, "_sweep_group", faces_only)
    sim = _sim(mesh=(2, 2, 2))
    _load(sim, _state())  # with its shell filled: the FIRST substep's edges are right as loaded
    sim.step(1)
    errs = _errors(sim, ref.steps(sim.setup, _state(), 1))
    a_read, a_spared = (("ax", "ay"), "az") if which == "edge_xy" else (("ay", "az"), "ax")
    assert all(errs[q] > 50 * TOL for q in ref.VELOCITY), errs  # coupled through div u at once
    # the potentials couple through B alone: the one whose grad div reads no
    # diagonal of that plane is still right after one step
    assert all(errs[q] > 5 * TOL for q in a_read) and errs[a_spared] < TOL, errs


# --- the plan ---------------------------------------------------------------------------


def test_the_plan_at_the_benchmarks_size_is_the_configurations(monkeypatch):
    """Plan only, nothing allocated: 256^3 x 16 on one described chip (the
    blend kernels on, as on the chip): the plane route, three stages of ONE
    pass each (16 read, 8 ringed, 8 written, 8 renamed), the eight ``*_prev``
    in no message, the y and z halos filled in the pass, two steps a trip --
    and the planner's VMEM model and ``check_vmem`` of one verdict."""
    from stencil_tpu import analysis
    from stencil_tpu.ops import halo_blend
    from stencil_tpu.ops.jacobi_pallas import _vmem_budget
    from stencil_tpu.ops.stream import stream_span_args

    monkeypatch.setattr(halo_blend, "pallas_interpret", lambda: False)
    config = _config()
    sim = AstarothMHD(*config["global_extent"], devices=jax.devices()[:1], seed_words=None)
    sim.dd.realize(allocate=False)
    stages = tuple(sim._substep(s) for s in range(ref.SUBSTEPS))
    request = sp.plan_stream(sim.dd, RADIUS, "auto", False)
    assert request["route"] == "plane"  # x_radius 3 admits no other
    plan = sp.resolve_stream_plan(sim.dd, stages, RADIUS, request, False)
    expect = config["expect"]
    assert "depth" not in expect  # a pinned depth would shut temporal blocking out
    assert (plan["route"], len(plan["stages"]), len(plan["renamed"])) == (
        expect["route"], expect["stages"], expect["renamed"])
    assert len(sim.dd._handles) == expect["quantities"] == config["quantities"] == 16
    for st in plan["stages"]:
        assert st["readers"] == ref.FIELDS  # the *_prev ride in no message
        (p,) = st["passes"]
        assert (len(p["reads"]), len(p["rings"]), len(p["writes"])) == (16, 8, 8)
        assert (config["pass"]["reads"], config["pass"]["writes"]) == (16, 8)
        assert p["renames"] == tuple((q + "_prev", q) for q in ref.FIELDS)
        # 16 x 2 + 8 x 2 = 48 pipeline planes of the raw 262 x 262 block (264 x
        # 384 f32 as tiled); its 256 x 256 interior (the interior window: ISSUE
        # 45) as 32 tiles between three margin tiles a side (the strip form:
        # ISSUE 46), 8 x 7 ring planes (the newest is pushed before it is read)
        # + 8 of the *_prev + the 24 planes rotated once a grid step (uy, uz,
        # ay, az by +-1..3 lanes: the z shifts their y-z mixed differences
        # share with their differences along z); eight staging planes of
        # tiles; sixteen stack margins: 97.0 MB, ONE pass a stage still
        assert p["vmem_bytes"] == (
            48 * 264 * 384 * 4 + (64 + 24) * 304 * 256 * 4 + 8 * 256 * 256 * 4
            + 16 * sp._VMEM_STACK_MARGIN) <= _vmem_budget()
        assert sorted(p["prerotated"]) == sorted(
            (q, 0, dz) for q in ("uy", "uz", "ay", "az") for dz in (-3, -2, -1, 1, 2, 3))
    assert plan["halo_readers"] == ref.FIELDS and plan["writers"] == ref.FIELDS
    assert (plan["pass_wrap_axes"], plan["steps_per_trip"], plan.period) == ("yz", 2, 2)
    assert plan["plane_window"] == "interior"  # 256 = 32 x 8 sublanes = 2 x 128 lanes
    assert plan["plane_strip"] == MHD_STRIP  # 16 strips of two tiles, 4 vregs a value (ISSUE 46)
    assert config["dispatch"]["bulk"] % plan["steps_per_trip"] == 0  # whole trips: no edge copy
    assert analysis.check_vmem(sim.dd, plan.plan) is None
    said = stream_span_args(plan.plan, RADIUS, 16)
    assert (said["exchanged_sides"], said["read_sides"], said["wrapped"]) == (48, 48, "yz")
    assert (said["plane_window"], said["plane_strip"]) == ("interior", MHD_STRIP)


def test_the_span_says_what_a_staged_renaming_step_does():
    """``domain.step``: three stages, eight renames and eight exchanged in
    each, sixteen quantities of which eight are read off-centre, six of them
    diagonally; 48 sides read of the 48 served (of 16 x 6: the mask's work)."""
    sim = _shared()
    _load(sim, _state())
    seen = []
    real = telemetry.span

    def spy(name, *a, **kw):
        seen.append((name, kw))
        return real(name, *a, **kw)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(telemetry, "span", spy)
        sim.step(2)
    (kw,) = [kw for name, kw in seen if name == tm.SPAN_STEP]
    assert (kw["label"], kw["steps"], kw["route"], kw["x_radius"]) == ("astaroth-mhd", 2, "plane", 3)
    assert (kw["stages"], kw["passes"], kw["steps_per_trip"]) == (3, 3, 2)
    assert (kw["renamed"], kw["exchanged"], kw["written"], kw["aliased"]) == (
        "8/8/8", "8/8/8", "8/8/8", "16/16/16")
    assert (kw["quantities"], kw["offcentre"], kw["diagonal"]) == (16, 8, 6)
    assert (kw["read_sides"], kw["exchanged_sides"]) == (48, 48)
    assert kw["plane_window"] == "raw"  # 16 lanes of interior: no whole tile (and a CPU run
    # without the blend kernels fills no halo in the pass: ``wrapped`` "")
    assert kw["plane_strip"] == 0  # ... and the raw window's kernel runs over the plane whole
    said = {k: v for k, v in kw.items() if k not in ("first", "total")}  # a first call's marks
    assert said == {"label": "astaroth-mhd", "steps": 2, **sim._step._span_args()}


def test_the_counter_is_registered_and_the_names_lint_passes():
    import inspect

    from stencil_tpu import lint

    registered = inspect.getsource(tm).split('SPAN_STEP = "domain.step"')[0]
    assert "steps_per_trip" in registered and "plane_window" in registered
    assert "plane_strip" in registered
    assert "wire_bytes_by_stage" in registered and "wired_edges" in registered
    assert lint.run_lint(select=["telemetry-name"]) == []
    with open(os.path.join(ROOT, "docs", "observability.md")) as f:
        said = f.read()
    assert "`steps_per_trip`" in said and "`plane_window`" in said and "`plane_strip`" in said
    assert "`wire_bytes_by_stage`" in said and "`wired_edges`" in said


def test_the_step_loop_brings_every_carry_home():
    """``_carry_period``: three stages that each swap all eight pairs is an odd
    count of swaps a step -- period 2; an even count would be 1."""
    names = list(ref.QUANTITIES)
    stage = {"passes": ({"renames": tuple((q + "_prev", q) for q in ref.FIELDS)},)}
    assert sp._carry_period(names, [stage] * 3) == 2
    assert sp._carry_period(names, [stage] * 2) == 1
    assert sp._carry_period(names, []) == 1


def test_rebuild_keeps_the_plan_and_bad_arguments_are_refused():
    sim = _shared()
    sim.rebuild_after_reshard()
    plan = sim._step._stream_plan
    assert (plan["route"], len(plan["stages"]), plan["steps_per_trip"]) == ("plane", 3, 2)
    _load(sim, _state())
    sim.step(1)  # (the rebuilt step's one program: three calls to lower, not six)
    assert max(_errors(sim, ref.steps(sim.setup, _state(), 1)).values()) < TOL
    with pytest.raises(ValueError, match="the set-up is for"):
        AstarothMHD(8, 8, 8, setup=_setup())
    with pytest.raises(ValueError, match="unknown kernel_impl"):
        AstarothMHD(8, 8, 8, kernel_impl="cuda")
