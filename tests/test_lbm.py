"""``LatticeBoltzmann`` (FluidX3D's benchmark: D3Q19, BGK, f32, a fully
periodic box) against the plain reference ``models/lbm_reference.py``: every
cell of all nineteen populations on every route the planner may take and on
meshes where every diagonal read crosses a shard edge somewhere; an edge halo
left unfilled comes out wrong; the reference itself conserves mass and
momentum and damps a shear wave at the viscosity it was given; the plan at the
benchmark's size; the ``domain.step`` span's account of what the kernel reads;
and (ISSUE 53) the same through Y TILES of planes beside a y the mesh splits,
the form ``lbm-d3q19-512x4`` runs on four chips."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from stencil_tpu import telemetry
from stencil_tpu.models import lbm_reference as ref
from stencil_tpu.models.lbm import RADIUS, LatticeBoltzmann, population_bounds
from stencil_tpu.ops import stream_plan as sp
from stencil_tpu.telemetry import names as tm

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORDS = (0x1234, 0xBEEF, 0x5EED, 0xC0FFEE)
#: the program and the reference sum in the same order; the compilers round apart
TOL = 2e-6


def _sim(n=16, mesh=(1, 1, 1), path=None, words=None, **kw):
    """``words=None`` skips the nineteen seeded fills (the caller loads a state)."""
    sim = LatticeBoltzmann(n, n, n, interpret=True, seed_words=words,
                           devices=jax.devices()[: int(np.prod(mesh))], **kw)
    sim.dd.set_partition(*mesh)
    sim.realize()
    if path is not None:  # a route forced through make_step's own option
        sim._step = sim.dd.make_step(sim._kernel, engine="stream", x_radius=RADIUS,
                                     interpret=True, stream_path=path)
    return sim


_SIMS = {}


def _shared(mesh=(1, 1, 1), path=None):
    """One realized 16^3 model a (mesh, route), shared by the cases that load
    their own state into it: building it is most of a case's time."""
    if (mesh, path) not in _SIMS:
        _SIMS[mesh, path] = _sim(mesh=mesh, path=path)
    return _SIMS[mesh, path]


#: a VMEM budget that the 16-row y tile of a 32 x 256 plane of nineteen just
#: fits (``plane_pass_vmem_bytes(y_tiles=2)``; tests/test_plane_tiles.py
#: ``_tiled_bytes(32, 256, 16)``): no whole-plane pass, no larger tile
_TILED_BUDGET = 9_291_456


def _tiled_sim(mesh, monkeypatch):
    """A model whose shards are 4 x 32 x 256 -- an interior of whole vector tiles
    beside a y the mesh splits -- under ``_TILED_BUDGET``: the planner answers
    with two y tiles a plane on the ``"interior-z"`` window."""
    monkeypatch.setenv("STENCIL_HALO_BLEND", "1")
    monkeypatch.setenv("STENCIL_VMEM_LIMIT_BYTES", str(_TILED_BUDGET))
    sim = LatticeBoltzmann(4 * mesh[0], 32 * mesh[1], 256, interpret=True, seed_words=None,
                           devices=jax.devices()[: int(np.prod(mesh))])
    sim.dd.set_partition(*mesh)
    sim.realize()
    plan = sim._step._stream_plan
    assert (plan["route"], plan["plane_window"], plan["tile_rows"], plan["y_tiles"]) == (
        "plane", "interior-z", 16, 2), plan
    return sim


def _random_state(shape, seed):
    """Seeded random positive populations: ``w_i (0.6 .. 1.4)``, so ``rho`` is
    near 1 and no two neighbouring cells agree in any population."""
    rng = np.random.default_rng(seed)
    return [np.float32(w) * rng.uniform(0.6, 1.4, shape).astype(np.float32) for w in ref.W]


def _load(sim, state):
    for name, a in zip(ref.NAMES, state):
        sim.dd.set_quantity(sim.handles[name], np.asarray(a))


def _worst(sim, want):
    return max(
        float(np.abs(sim.field(name) - np.asarray(w)).max()) for name, w in zip(ref.NAMES, want)
    )


# --- the program against the reference -------------------------------------------------


def _advance(sim, steps):
    """``steps`` time steps in the dispatches whose programs the other cases of
    the (mesh, route) trace anyway (a program is 5 s of lowering nineteen
    coupled populations: ISSUE 55): 1, 2 and 7 are ONE dispatch each -- a
    remainder alone, a macro alone, and a trip of the macro loop with a macro
    and the remainder behind it --, 3 is the dispatch of 2 and then the dispatch
    of 1 from the blocks it left, shell and all."""
    for n in {3: (2, 1)}.get(steps, (steps,)):
        sim.step(n)


@pytest.mark.parametrize("steps", [1, 2, 3, 7])
@pytest.mark.parametrize("path", [None, "wrap", "plane"])
def test_model_matches_the_reference_on_every_route(path, steps):
    """One device: the route ``auto`` picks (wrap) and each route forced, from
    seeded random populations, every cell of all nineteen (``_advance``)."""
    sim = _shared(path=path)
    state = _random_state(sim.setup.shape, 7)
    _load(sim, state)
    _advance(sim, steps)
    assert sim._step._stream_plan["route"] == (path or "wrap")
    assert _worst(sim, ref.steps(sim.setup, state, steps)) < TOL


@pytest.mark.parametrize("steps", [1, 2, 3, 7])
@pytest.mark.parametrize("mesh", [(2, 1, 1), (2, 2, 1), (2, 2, 2)])
def test_model_matches_the_reference_across_devices(mesh, steps):
    """CPU meshes: every diagonal read crosses a shard edge somewhere, so the
    x, then y, then z sweeps (or the pass's own fills on an unsplit axis)
    must have left the EDGE halos filled (``_advance``)."""
    sim = _shared(mesh=mesh)
    state = _random_state(sim.setup.shape, 11)
    _load(sim, state)
    _advance(sim, steps)
    assert tuple(sim.dd.mesh_dim()) == mesh
    assert _worst(sim, ref.steps(sim.setup, state, steps)) < TOL


@pytest.mark.parametrize("mesh", [(2, 2, 1), (1, 2, 1)])
def test_model_through_y_tiles_beside_a_split_y_matches_the_reference(mesh, monkeypatch):
    """ISSUE 53: planes too large for the (tightened) budget beside a y the mesh
    splits -- the pass moves y tiles whose two ENDS are the block's own rows, a
    neighbour's cells that came over the y wire (on [2,2,1] the x-y edge over
    both).  Seeded random populations, every cell of all nineteen after three
    steps; and ``domain.step`` says the window, the tile and the wires."""
    sim = _tiled_sim(mesh, monkeypatch)
    state = _random_state(sim.setup.shape, 13)
    _load(sim, state)
    seen = []
    real = telemetry.span

    def spy(name, *a, **kw):
        seen.append((name, kw))
        return real(name, *a, **kw)

    monkeypatch.setattr(telemetry, "span", spy)
    sim.step(3)
    want = ref.steps(sim.setup, state, 3)
    assert float(np.abs(np.asarray(want[1]) - state[1]).max()) > 1e-3  # (the state moved)
    assert _worst(sim, want) < TOL
    assert not sim._step._resilience.descents
    (kw,) = [kw for name, kw in seen if name == tm.SPAN_STEP]
    assert (kw["plane_window"], kw["tile_rows"], kw["y_tiles"], kw["aliased"]) == ("interior-z", 16, 2, 19)
    assert (kw["wired"], kw["wrapped"]) == ("xy" if mesh[0] > 1 else "y", "z")
    assert (kw["read_sides"], kw["exchanged"], kw["exchanged_sides"]) == (30, 18, 108)


@pytest.mark.parametrize("mesh", [(1, 1, 1), (2, 2, 1)])
def test_the_seeded_state_matches_the_reference(mesh):
    """The seeded Taylor-Green state through ``fill(args=)``: the fills and a
    dispatch of several macros and a remainder, on the shared 16^3 model of the
    mesh (24^3 and a model of its own until ISSUE 55: the fills and the program
    are the same at either size, and the seven-step dispatch is the one
    ``test_model_matches_the_reference_*[7]`` traces)."""
    sim = _shared(mesh=mesh)
    sim.fill(ref.seeded_fields(sim.setup), (np.asarray(WORDS, dtype=np.uint32),))
    want = ref.global_fields(sim.setup, WORDS)
    assert _worst(sim, want) < 5e-7
    sim.step(7)
    assert _worst(sim, ref.steps(sim.setup, want, 7)) < TOL


def test_the_xla_engine_runs_the_same_kernel():
    sim = _sim(kernel_impl="jnp")
    state = _random_state(sim.setup.shape, 3)
    _load(sim, state)
    sim.step(3)
    assert _worst(sim, ref.steps(sim.setup, state, 3)) < TOL


def test_bf16_storage_fails_the_tolerance():
    sim = _sim(storage_dtype="bf16", words=WORDS)
    sim.step(4)
    want = ref.steps(sim.setup, ref.global_fields(sim.setup, WORDS), 4)
    assert _worst(sim, want) > 100 * TOL


@pytest.mark.parametrize("tiled", [False, True], ids=["whole-planes", "y-tiles"])
def test_an_unfilled_edge_halo_comes_out_wrong(tiled, monkeypatch):
    """Mesh [2,2,1]: the y sweep carries the x halo planes along, which is what
    fills the x-y EDGE halo.  With that edge left as the y faces carried it
    (the joint sweep's corner relay taken out: the neighbour's stale halo) --
    every face halo still filled -- the second step's diagonal
    reads in the xy plane find the first step's cells and come out wrong;
    through y tiles (ISSUE 53) as over whole planes."""
    from stencil_tpu.ops import exchange as ex

    # x and y fly jointly on this mesh: the y faces are cut before the x halo
    # is in, and the corner relay is what carries the x halo planes along
    monkeypatch.setattr(ex, "_relay_corners", lambda first, second: second)
    sim = _tiled_sim((2, 2, 1), monkeypatch) if tiled else _sim(mesh=(2, 2, 1))
    state = _random_state(sim.setup.shape, 11)
    _load(sim, state)  # with its shell filled: the FIRST step's edges are right as loaded
    sim.step(2)
    want = ref.steps(sim.setup, state, 2)
    errs = {name: float(np.abs(sim.field(name) - np.asarray(w)).max())
            for name, w in zip(ref.NAMES, want)}
    assert max(errs.values()) > 1e-3
    # the populations that stream along x AND y read the edge; the collision
    # then spreads the fault to every population of those cells
    assert all(e > 1e-5 for e in errs.values())


# --- the reference against the physics --------------------------------------------------


def _totals(f):
    f = [np.asarray(a, dtype=np.float64) for a in f]
    mass = sum(a.sum() for a in f)
    mom = [sum(c[a] * x.sum() for c, x in zip(ref.C, f)) for a in range(3)]
    return mass, mom


@pytest.mark.parametrize("which", ["reference", "program"])
def test_mass_and_momentum_are_conserved(which):
    """Fifty steps on the periodic box, to f32 rounding."""
    setup = ref.LbmSetup((16, 16, 16))
    state = ref.global_fields(setup, WORDS)
    mass0, mom0 = _totals(state)
    if which == "reference":
        after = ref.steps(setup, state, 50)
    else:
        sim = _shared()
        _load(sim, state)
        for _ in range(7):  # (the seven-step dispatch the cases above trace, and one step)
            sim.step(7)
        sim.step(1)
        after = [sim.field(name) for name in ref.NAMES]
    mass, mom = _totals(after)
    assert abs(mass - mass0) / mass0 < 1e-6
    assert all(abs(a - b) / mass0 < 1e-6 for a, b in zip(mom, mom0))


def test_a_shear_wave_decays_at_the_viscosity():
    """``u_x = a sin(k y)`` in the REFERENCE decays as ``exp(-nu k^2 t)``:
    this ties the equations to the model, not to themselves."""
    n, settle, steps, amp = 32, 100, 200, 0.01
    setup = ref.LbmSetup((n, n, n))
    k = 2 * np.pi / n
    y = jnp.arange(n, dtype=jnp.float32)[None, :, None]
    ux = jnp.broadcast_to(amp * jnp.sin(k * y), setup.shape)
    zero = jnp.zeros(setup.shape, jnp.float32)
    f = ref.equilibrium(jnp.ones(setup.shape, jnp.float32), ux, zero, zero)
    mode = np.sin(k * np.arange(n))[None, :, None]

    def amplitude(f):  # of the one Fourier mode
        return 2 * float((np.asarray(ref.moments(f)[1], dtype=np.float64) * mode).mean())

    # the state starts AT equilibrium: its viscous stress builds up over the
    # first few relaxation times (the rate reads 2.7% high over steps 0-100)
    f = ref.steps(setup, f, settle)
    a0 = amplitude(f)
    a1 = amplitude(ref.steps(setup, f, steps))
    rate = -np.log(a1 / a0) / steps
    # 0.3% off at 32 cells a wave: the lattice's own k^2 / 12
    assert abs(rate / (setup.nu * k * k) - 1) < 0.01


def test_the_populations_bounds_hold_the_guardband():
    """``population_bounds`` brackets every equilibrium inside the guardband."""
    rng = np.random.default_rng(0)
    u = rng.uniform(-1, 1, (3, 4000))
    u = u / np.linalg.norm(u, axis=0) * rng.uniform(0, ref.U_MAX, 4000)
    for rho in ref.RHO_BAND:
        feq = ref.equilibrium(np.float64(rho), *u)
        for i in range(ref.Q):
            lo, hi = population_bounds(i)
            assert lo < np.min(feq[i]) and np.max(feq[i]) < hi


# --- the plan and the span ---------------------------------------------------------------


def _config():
    with open(os.path.join(ROOT, "benchmark", "configs", "lbm-d3q19-256.json")) as f:
        return json.load(f)


def test_the_plan_at_the_benchmarks_size_is_the_configurations():
    """Plan only, nothing allocated: 256^3 x 19, not separable."""
    config = _config()
    sim = LatticeBoltzmann(*config["global_extent"], devices=jax.devices()[:1], seed_words=None)
    sim.dd.realize(allocate=False)
    plan = sp.plan_stream(sim.dd, RADIUS, "auto", False)
    expect = config["expect"]
    assert (plan["route"], plan["m"], plan["grouping"]) == (expect["route"], expect["depth"], "joint")
    assert config["dispatch"]["bulk"] % (2 * plan["m"]) == 0  # whole trips of the macro loop
    assert len(sim.dd._handles) == expect["quantities"] == config["quantities"] == ref.Q
    # the ladder's static prefilter (compiled backends only, so no CPU run
    # meets it) models the planes the planner modelled -- the wrap route's
    # bare 256 x 256 interiors, not the raw 258 x 258 ones that pad to 264 x
    # 384: on the chip it rejected the planned rung and the first run
    # descended to m = 1 (PERF.md, PR 39)
    from stencil_tpu import analysis

    assert analysis.check_vmem(sim.dd, plan) is None
    deeper = analysis.check_vmem(sim.dd, {**plan, "m": plan["m"] + 1})
    assert deeper is not None and f"wrap[m={plan['m'] + 1}]" in deeper
    assert sp.plan_stream(sim.dd, RADIUS, "auto", False, max_m=plan["m"] + 1)["m"] == plan["m"]


@pytest.mark.parametrize("path, mesh, exchanged", [
    (None, (1, 1, 1), 0), ("plane", (1, 1, 1), 18), (None, (2, 2, 1), 18),
])
def test_the_span_says_what_the_kernel_reads(path, mesh, exchanged):
    """``domain.step``: 19 quantities, 18 read off-centre, 12 of them at a
    diagonal offset, 30 (quantity, axis, side) triples read -- beside the six
    sides of every exchanged quantity that the route serves."""
    sim = _shared(mesh=mesh, path=path)
    _load(sim, _random_state(sim.setup.shape, 5))
    sim.step(2)
    args = sim._step._span_args()
    assert (args["quantities"], args["offcentre"], args["diagonal"], args["read_sides"]) == (19, 18, 12, 30)
    assert args["exchanged"] == exchanged and args["exchanged_sides"] == 6 * exchanged
    if args["route"] == "wrap":
        assert (args["macros_per_trip"], args["aliased"]) == (2, 0)
    else:
        assert "macros_per_trip" not in args and args["aliased"] == 19
    seen = []
    real = telemetry.span

    def spy(name, *a, **kw):
        seen.append((name, kw))
        return real(name, *a, **kw)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(telemetry, "span", spy)
        sim.step(2)
    (kw,) = [kw for name, kw in seen if name == tm.SPAN_STEP]
    assert (kw["label"], kw["steps"], kw["quantities"], kw["diagonal"]) == ("lbm", 2, 19, 12)
    assert (kw["offcentre"], kw["read_sides"], kw["exchanged_sides"]) == (18, 30, 6 * exchanged)


# --- the fills and the driver -------------------------------------------------------------


def test_fill_takes_the_seed_as_an_argument():
    """One compiled fill a population serves every seed (``init_by_coords(args=)``)."""
    sim = _shared()
    f7 = ref.seeded_fields(sim.setup)["f7"]
    c = (np.arange(4)[:, None, None], np.arange(4)[None, :, None], np.arange(4)[None, None, :])
    seeds = [np.asarray(WORDS, dtype=np.uint32), np.asarray(WORDS, dtype=np.uint32) + 5]
    assert len({jax.jit(f7).lower(*c, w).as_text() for w in seeds}) == 1
    sim.fill({"f7": f7}, (seeds[1],))
    want = np.asarray(ref.global_fields(sim.setup, seeds[1])[7])
    np.testing.assert_allclose(sim.field("f7"), want, rtol=0, atol=5e-7)
    assert float(np.abs(want - np.asarray(ref.global_fields(sim.setup, seeds[0])[7])).max()) > 1e-4


def test_driver_runs_on_the_cpu(capsys):
    """``stencil-lbm`` takes the box, prints the source's figure of merit and
    says on stderr which route ran and what it reads."""
    from stencil_tpu.bin import lbm

    rc = lbm.main(["16", "16", "16", "--iters", "1", "--steps", "4"])
    assert rc == 0
    io = capsys.readouterr()
    row = io.out.strip().splitlines()[-1].split(",")
    assert row[0] == "lbm" and row[3:6] == ["16", "16", "16"] and float(row[-1]) > 0
    (said,) = [l for l in io.err.splitlines() if l.startswith("mesh: ")]
    assert "route=" in said and "read_sides=30" in said, said
