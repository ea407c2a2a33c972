"""``AcousticWave`` (models/acoustic.py) against its plain reference
(models/acoustic_reference.py): the so-8 acoustic propagator on the stream
engine's plane route and on the XLA engine, in process, interpreted, small
(24^3 with ``nbl`` 4: 8 physical cells, a 4-cell sponge and the 4-cell zero
frame on every side).

Tolerance.  The model and the reference sum the 25 points in the same order,
so they differ by the two compilers' roundings only (fused multiply-adds, the
seeded fill evaluated per shard against globally): a few ulp of the
wavefield's amplitude per step, 2e-7 measured after six steps at amplitude
0.25.  ``ATOL`` 2e-6 leaves ten times that and lies 500 times under what bf16
storage does to the same run (1e-3, 2^-9 of the amplitude per store), which
``test_bf16_storage_fails_the_tolerance`` holds it to.
"""

import os

import jax
import numpy as np
import pytest

import seeded_blocks

from stencil_tpu import telemetry
from stencil_tpu.models import acoustic_reference as ref
from stencil_tpu.models.acoustic import QUANTITIES, AcousticWave
from stencil_tpu.telemetry import names as tm
from stencil_tpu.ops import stream_plan as sp
from stencil_tpu.ops import stream_pass as spass

N, NBL, DISPATCH = 24, 4, 3
ATOL = 2e-6
WORDS = np.asarray([0x1234ABCD, 77, 0xDEADBEEF, 2024], dtype=np.uint32)


_BUILT = {}


def _built(extent, impl, devices, kw):
    """One realized model a configuration -- extent, engine, devices, options and
    the ``STENCIL_HALO_BLEND`` of the moment (the plan reads it) --, its seeded
    blocks put back for the case that asks: the cases differ in what they look at,
    and a model's programs (one a dispatch size) are most of a case's time."""
    key = (extent, impl, len(devices), tuple(sorted(kw.items())),
           os.environ.get("STENCIL_HALO_BLEND"))
    if key not in _BUILT:
        sim = AcousticWave(*extent, nbl=NBL, kernel_impl=impl, interpret=True,
                           devices=devices, seed_words=WORDS, **kw)
        sim.realize()
        _BUILT[key] = (sim, seeded_blocks.snapshot(sim.dd))
    sim, blocks = _BUILT[key]
    seeded_blocks.restore(sim.dd, blocks)
    return sim


def _sim(impl="pallas", devices=None, **kw):
    return _built((N, N, N), impl, devices or jax.devices()[:1], kw)


def _reference(grid, steps):
    f = ref.global_fields(grid, WORDS)
    u, u_prev = ref.steps_framed(grid, f["u"], f["u_prev"], f["m"], f["damp"], steps)
    return np.asarray(u), np.asarray(u_prev), f


def _errors(sim, dispatches):
    for _ in range(dispatches):
        sim.step(DISPATCH)
    u, u_prev, _ = _reference(sim.grid, dispatches * DISPATCH)
    assert np.max(np.abs(u)) > 0.05  # the wave is there: the comparison means something
    return (float(np.max(np.abs(sim.field("u") - u))),
            float(np.max(np.abs(sim.field("u_prev") - u_prev))))


@pytest.mark.parametrize("dispatches", [1, 2])
@pytest.mark.parametrize("impl", ["pallas", "jnp"])
def test_model_matches_the_reference(impl, dispatches):
    err_u, err_prev = _errors(_sim(impl), dispatches)
    assert err_u <= ATOL and err_prev <= ATOL, (err_u, err_prev)


def test_bf16_storage_fails_the_tolerance():
    """The control: the same run on the program's bf16 storage axis lies far
    outside ``ATOL`` -- the tolerance would catch a lower precision."""
    sim = _sim("pallas", storage_dtype="bf16")
    assert sim.dd.storage_dtype() == "bf16"
    err_u, _ = _errors(sim, 1)
    assert err_u > 100 * ATOL, err_u


def test_model_matches_the_reference_across_devices():
    """Mesh [2,2,2]: every sweep crosses a wire, the frame is found from
    wrapped global coordinates on every shard."""
    sim = _sim("pallas", devices=jax.devices()[:8])
    assert sim.dd.num_subdomains() == 8
    err_u, err_prev = _errors(sim, 1)
    assert err_u <= ATOL and err_prev <= ATOL, (err_u, err_prev)


def test_one_chip_sweeps_are_self_wraps_at_radius_4(monkeypatch):
    """On one device every axis is unsplit: with the blend kernels on (as on
    the chip) all three sweeps take ``wrap_halo`` at radius 4, every step."""
    monkeypatch.setenv("STENCIL_HALO_BLEND", "1")
    sim = _sim("pallas")
    assert set(sim.dd._wrap_axes) == {"x", "y", "z"}, sim.dd._wrap_axes
    err_u, err_prev = _errors(sim, 1)
    assert err_u <= ATOL and err_prev <= ATOL, (err_u, err_prev)


@pytest.mark.parametrize(
    "blend,mesh,wrapped,wired",
    [("0", (1, 1, 1), "", ""), ("1", (1, 1, 1), "yz", ""), ("1", (2, 2, 1), "z", "xy")],
    ids=["cpu", "as-on-the-chip", "as-on-four-chips"],
)
def test_route_is_plane_and_the_span_says_so(blend, mesh, wrapped, wired, monkeypatch):
    """``wrapped`` (ISSUE 34): with the blend kernels on, as on the chip, the
    y and z self-wraps of the one device ride in the pass; a plain CPU run
    keeps the program it had.  ``wired`` / ``wire_bytes`` (ISSUE 37): on mesh
    [2,2,1] the x and y sweeps of ``u`` cross to another shard, four radius-4
    faces of the raw block a step, and the pass wraps z alone; on one device
    nothing crosses a wire."""
    monkeypatch.setenv("STENCIL_HALO_BLEND", blend)
    sim = _sim("pallas") if mesh == (1, 1, 1) else _shot()
    assert tuple(sim.dd.mesh_dim()) == mesh
    raw = N + 2 * ref.RADIUS
    wire_bytes = len(wired) * 2 * ref.RADIUS * raw * raw * 4
    # two wired sweeps in a row fly jointly (ISSUE 50): behind each y face the
    # corner relay, both x halos on its four rows
    joint = wired if len(wired) == 2 else ""
    if joint:
        wire_bytes += 2 * (2 * ref.RADIUS) * ref.RADIUS * raw * 4
    plan = sim._step._stream_plan
    assert plan["route"] == "plane" and plan["m"] == 1 and plan["grouping"] == "joint", plan
    assert plan["alias"] is True, plan  # the plane route writes in place (ISSUE 28)
    assert sim._step._span_args() == {
        "route": "plane", "x_radius": 4, "grouping": "joint", "streamed": 4,
        "aliased": 4, "exchanged": 1,  # u alone is read off-centre (ISSUE 30)
        "written": 1,  # m and damp are never returned: inputs only (ISSUE 32)
        "renamed": 1,  # and u_prev <- u swaps two handles: nothing to write (ISSUE 36)
        "wrapped": wrapped,
        "wired": wired, "wire_bytes": wire_bytes,  # what crosses to another shard (ISSUE 37)
        "joint": joint,
        # what the kernel reads against what the exchange serves (ISSUE 39): u
        # alone, along the axes only, on all six sides
        "quantities": 4, "offcentre": 1, "diagonal": 0, "read_sides": 6, "exchanged_sides": 6,
        "steps_per_trip": 2,  # one swap a step: the handles are home after two (ISSUE 44 says it)
        # 24 + 2 x 8 = 40 cells of interior a side here, 600 in the cell (4 x 128
        # + 88 lanes): no whole vector tile, the passes keep the raw plane (ISSUE 45)
        "plane_window": "raw",
        "plane_strip": 0,  # ... and their kernel runs over it whole (ISSUE 46)
        "tile_rows": 0, "y_tiles": 1,  # ... and their pipeline moves whole planes (ISSUE 51)
        "plane_lanes": "raw",  # ... every call of a dispatch (ISSUE 54)
        "wired_edges": "",  # a star reads no edge: nothing crosses two wires in turn (ISSUE 47)
    }
    assert plan["halo_readers"] == ("u",), plan
    assert plan["writers"] == ("u",) and plan["renamed"] == ("u_prev",), plan
    assert plan["stages"][0]["passes"][0]["renames"] == (("u_prev", "u"),), plan
    assert plan["pass_wrap_axes"] == wrapped, plan
    seen = []
    real = telemetry.span

    def spy(name, *a, **kw):
        seen.append((name, kw))
        return real(name, *a, **kw)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(telemetry, "span", spy)
        sim.step(2)
    (kw,) = [kw for name, kw in seen if name == tm.SPAN_STEP]
    assert kw["label"] == "acoustic" and kw["steps"] == 2 and kw["route"] == "plane"
    assert (kw["streamed"], kw["aliased"], kw["exchanged"], kw["written"]) == (4, 4, 1, 1)
    assert kw["renamed"] == 1
    assert kw["wrapped"] == wrapped
    assert (kw["wired"], kw["wire_bytes"], kw["joint"]) == (wired, wire_bytes, joint)


@pytest.mark.parametrize("devices", [1, 2, 8])
def test_plane_route_is_bitwise_the_xla_engine_on_every_quantity(devices):
    """The plane route exchanges ``u`` alone; the XLA slice engine exchanges
    all four.  Three dispatches on, every interior cell of every quantity is
    bitwise the same -- on one device, on a mesh that splits one axis and on
    one that splits all three."""
    sims = [_sim(impl, devices=jax.devices()[:devices]) for impl in ("pallas", "jnp")]
    assert sims[0]._step._stream_plan["halo_readers"] == ("u",)
    for sim in sims:
        for _ in range(3):
            sim.step(DISPATCH)
    for q in QUANTITIES:
        a, b = (sim.field(q) for sim in sims)
        assert np.any(a != 0.0), q
        np.testing.assert_array_equal(a, b, err_msg=q)


def test_the_step_program_exchanges_u_alone(monkeypatch):
    """The step as the chip runs it (blend kernels on: every sweep of one
    device is a self-wrap): per step ONE ``stream_plane_pass`` and ONE Pallas
    call under an ``exchange.*`` scope, the x wrap of ``u`` -- the y and z
    wraps ride in the pass (ISSUE 34: 4 Pallas calls a step -> 2, no
    ``blend_slab`` left; three wraps before, twelve before ISSUE 30).  Five
    steps trace as THREE: a loop trip of two (``u_prev <- u`` is a rename, so
    the carry is back in place every second step: ISSUE 36) and one behind it."""
    from stencil_tpu.analysis import jaxpr as jx

    monkeypatch.setenv("STENCIL_HALO_BLEND", "1")
    sim = _sim("pallas")
    closed = jax.make_jaxpr(sim._step._resilience.built(), static_argnums=1)(sim.dd._curr, 5)
    calls = [e for e in jx.iter_eqns(closed) if e.primitive.name == "pallas_call"]
    passes = [e for e in calls if e.params.get("name") == tm.KERNEL_STREAM_PLANE_PASS]
    wraps = [jx.name_stack_str(e) for e in calls if "exchange." in jx.name_stack_str(e)]
    assert len(passes) == 3 and len(calls) == 6, [e.params.get("name") for e in calls]
    assert wraps == ["exchange.x/exchange.x.wrap/blend_planes"] * 3, wraps
    whiles = [e for e in jx.iter_eqns(closed) if e.primitive.name in ("while", "scan")]
    assert len(whiles) == 1  # two trips of two steps, then the fifth step
    assert not [e for e in jx.iter_eqns(closed) if e.primitive.name == "ppermute"]
    assert sim._step._stream_plan["pass_wrap_axes"] == "yz"


# --- the shot decomposed over a mesh (ISSUE 37) ---------------------------------
#
# The benchmark's four-chip cell is 1200 x 1200 x 600 on mesh [2,2,1]: x = y =
# 2z, the x and y seams through the middle of the wave packet, z whole.  Here
# the same shape at 48 x 48 x 24, on the mesh the partitioner picks for it.

SHOT = (2 * N, 2 * N, N)


def _shot(impl="pallas", **kw):
    sim = _built(SHOT, impl, jax.devices()[:4], kw)
    assert tuple(sim.dd.mesh_dim()) == (2, 2, 1), sim.dd.mesh_dim()  # nobody asked for it
    return sim


@pytest.mark.parametrize("dispatches", [1, 2])
@pytest.mark.parametrize("blend", ["0", "1"], ids=["cpu", "as-on-the-chip"])
def test_the_decomposed_shot_matches_the_reference(blend, dispatches, monkeypatch):
    """The non-cubic shot on mesh [2,2,1] against the plain reference on the
    whole array, every cell of ``u`` and ``u_prev``, at ``ATOL`` (the header
    says why that and not bitwise) -- with the sweeps as the CPU has them and
    as the chip has them (blends on: the pass fills z itself, beside a y halo
    that arrived from another shard)."""
    monkeypatch.setenv("STENCIL_HALO_BLEND", blend)
    sim = _shot()
    assert sim._step._stream_plan["pass_wrap_axes"] == ("z" if blend == "1" else "")
    err_u, err_prev = _errors(sim, dispatches)
    assert err_u <= ATOL and err_prev <= ATOL, (err_u, err_prev)


def test_the_decomposed_shot_in_bf16_storage_fails_the_tolerance():
    sim = _shot(storage_dtype="bf16")
    assert sim.dd.storage_dtype() == "bf16"
    err_u, _ = _errors(sim, 1)
    assert err_u > 100 * ATOL, err_u


@pytest.mark.parametrize("blend", ["0", "1"], ids=["cpu", "as-on-the-chip"])
def test_the_decomposed_shot_is_bitwise_the_xla_engine(blend, monkeypatch):
    """Three dispatches on, every interior cell of every quantity bitwise the
    XLA slice engine's on the same mesh (which exchanges all four, on all
    three axes)."""
    monkeypatch.setenv("STENCIL_HALO_BLEND", blend)
    sims = [_shot(impl) for impl in ("pallas", "jnp")]
    for sim in sims:
        for _ in range(3):
            sim.step(DISPATCH)
    for q in QUANTITIES:
        a, b = (sim.field(q) for sim in sims)
        assert np.any(a != 0.0), q
        np.testing.assert_array_equal(a, b, err_msg=q)


@pytest.mark.parametrize("devices", [1, 4])
def test_the_wires_carry_four_faces_of_u_and_nothing_else(devices, monkeypatch):
    """The step as the chip runs it: on mesh [2,2,1] the FACE ``ppermute``s of
    one step are four -- ``exchange.x.low`` / ``.high``, ``exchange.y.low`` /
    ``.high`` --, behind each y face flies the corner relay of the joint x-y
    sweep (ISSUE 50: both x halos on the face's four rows), and their cells
    are exactly ONE quantity's four radius-4 faces of the raw block and those
    two strips, which is what the plan and the span report as ``wire_bytes``;
    the z sweep is gone into the pass.  On one device nothing is sent at
    all."""
    from stencil_tpu.analysis import jaxpr as jx

    monkeypatch.setenv("STENCIL_HALO_BLEND", "1")
    sim = _shot() if devices == 4 else _sim("pallas")
    closed = jax.make_jaxpr(sim._step._resilience.built(), static_argnums=1)(sim.dd._curr, 1)
    sends = [e for e in jx.iter_eqns(closed) if e.primitive.name == "ppermute"]
    cells = sum(int(np.prod(v.aval.shape)) for e in sends for v in e.invars)
    args = sim._step._span_args()
    assert args["wire_bytes"] == 4 * cells
    raw = N + 2 * ref.RADIUS
    if devices == 1:
        assert not sends and (args["wired"], args["wrapped"]) == ("", "yz"), args
        return
    assert cells == 4 * ref.RADIUS * raw * raw + 2 * (2 * ref.RADIUS) * ref.RADIUS * raw
    assert sorted(jx.name_stack_str(e).split("/")[-1] for e in sends) == sorted(
        [tm.exchange_direction_span(a, side) for a in "xy" for side in ("high", "low")]
        + [tm.exchange_direction_span("y", side) for side in ("high", "low")])
    assert args["joint"] == "xy"
    assert (args["wired"], args["wrapped"], args["exchanged"], args["renamed"]) == ("xy", "z", 1, 1)
    assert not [e for e in jx.iter_eqns(closed) if "exchange.z" in jx.name_stack_str(e)]


def _plane_passes(fn, curr):
    """Every ``stream_plane_pass`` equation of the traced step."""
    from test_plane_stencil import _pass_calls

    return _pass_calls(jax.make_jaxpr(fn, static_argnums=1)(curr, 1))


def _plane_pass_aliases(fn, curr):
    """``input_output_aliases`` of every ``stream_plane_pass`` in the traced step."""
    from test_plane_stencil import _alias_pairs

    return [_alias_pairs(e) for e in _plane_passes(fn, curr)]


def test_the_step_program_says_the_pass_is_in_place():
    """The step as built: ONE plane pass over the four raw blocks with ONE
    output, the new ``u``, aliased onto raw ``u_prev`` (operand 2; operand 0
    is ``origin``): ``u_prev <- u`` is a rename (ISSUE 36), ``m`` and
    ``damp`` are inputs and nothing else (ISSUE 32); the same plan forced off
    carries no alias and no rename, and says so in the plan and on the span."""
    from stencil_tpu.ops import stream as sm

    sim = _sim("pallas")
    (call,) = _plane_passes(sim._step._resilience.built(), sim.dd._curr)
    assert len(call.outvars) == 1 and len(call.invars) == 1 + 4, call
    assert _plane_pass_aliases(sim._step._resilience.built(), sim.dd._curr) == [((2, 0),)]
    plan = sp.resolve_stream_plan(
        sim.dd, sim._kernel, ref.RADIUS,
        dict(sim._step._stream_plan, alias=False, alias_forced=True), True,
    )
    off = sm._build_stream_step(sim.dd, sim._kernel, ref.RADIUS, plan, interpret=True)
    assert _plane_pass_aliases(off, sim.dd._curr) == [()]
    assert plan["alias"] is False and plan["renamed"] == () and plan["writers"] == ("u", "u_prev")
    # the split schedule keeps fresh outputs whatever the plan resolves: the
    # interior pass and the exchange both read the pre-exchange blocks
    plan = sp.resolve_stream_plan(
        sim.dd, sim._kernel, ref.RADIUS,
        dict(sim._step._stream_plan, overlap="split", overlap_forced=True), True,
    )
    split = sm._build_stream_step(sim.dd, sim._kernel, ref.RADIUS, plan, interpret=True)
    passes = _plane_pass_aliases(split, sim.dd._curr)
    assert len(passes) == 7 and set(passes) == {()}, passes  # interior + six bands
    assert {len(e.outvars) for e in _plane_passes(split, sim.dd._curr)} == {2}
    assert plan["overlap"] == "split" and plan["alias"] is True
    assert sp._plan_passes_in_place(plan) is False and plan["renamed"] == ()


def test_plane_pass_sits_under_its_scope():
    """``step.pass`` around the plane pass, in the traced program's name
    stacks (what the profiler's ``op_name`` is made of)."""
    sim = _sim("pallas")
    impl = sim._step._resilience.built()
    lowered = impl.lower(sim.dd._curr, 1).as_text(debug_info=True)
    assert f"{tm.SPAN_STEP_PASS}/{tm.KERNEL_STREAM_PLANE_PASS}" in lowered


def test_frame_equals_devitos_zero_halo_exactly():
    """The pinned frame on the periodic array and the zero-padded frameless
    array (Devito's arrangement) give the same cells, bit for bit."""
    grid = ref.AcousticGrid((N, N, N), nbl=NBL)
    u, u_prev, f = _reference(grid, 2 * DISPATCH)
    inner = [ref.interior(f[k]) for k in QUANTITIES]
    pu, pu_prev = ref.steps_padded(grid, *inner, 2 * DISPATCH)
    np.testing.assert_array_equal(np.asarray(pu), ref.interior(u))
    np.testing.assert_array_equal(np.asarray(pu_prev), ref.interior(u_prev))


@pytest.mark.parametrize("impl", ["pallas", "jnp"])
def test_frame_stays_exactly_zero(impl):
    sim = _sim(impl)
    sim.step(2 * DISPATCH)
    frame = np.broadcast_to(np.asarray(ref.frame_mask((N, N, N))), (N, N, N))
    for q in ("u", "u_prev"):
        a = sim.field(q)
        assert np.all(a[frame] == 0.0) and np.any(a[~frame] != 0.0), q
    # the model fields are read, never written
    f = ref.global_fields(sim.grid, WORDS)
    for q in ("m", "damp"):
        np.testing.assert_allclose(sim.field(q), np.asarray(f[q]), rtol=0, atol=1e-6)


def test_seeded_fields_are_what_the_configuration_says():
    grid = ref.AcousticGrid((N, N, N), nbl=NBL)
    f = {k: np.asarray(v) for k, v in ref.global_fields(grid, WORDS).items()}
    lo, hi = ref.FRAME + NBL, N - ref.FRAME - NBL
    vp = 1.0 / np.sqrt(f["m"])
    assert vp.min() >= 1.5 - 1e-5 and vp.max() <= 3.5 + 1e-5
    assert np.all(np.diff(vp[0, 0, :]) >= 0)  # layers get faster with depth
    assert np.all(f["damp"][lo:hi, lo:hi, lo:hi] == 0.0) and f["damp"].max() > 0
    outside = np.ones((N, N, N), bool)
    outside[lo:hi, lo:hi, lo:hi] = False
    assert np.all(f["u"][outside] == 0.0) and np.all(f["u_prev"][outside] == 0.0)
    assert np.max(np.abs(f["u"])) < ref.AMPLITUDE_BOUND
    assert abs(grid.dt - 0.38 * 20.0 / 3.5) < 1e-12
    other = ref.global_fields(grid, WORDS + 1)
    assert np.max(np.abs(np.asarray(other["u"]) - f["u"])) > 1e-3  # the seed matters


def test_fill_takes_the_seed_as_an_argument():
    """A second seed reuses the fill program: the words are traced arguments,
    not constants baked into a new program (PERF.md: `setup_s` is seed-bound)."""
    sim = _sim("pallas")
    u0 = ref.seeded_fields(sim.grid)["u"]
    c = (np.arange(4)[:, None, None], np.arange(4)[None, :, None], np.arange(4)[None, None, :])
    programs = {jax.jit(u0).lower(*c, w).as_text() for w in (WORDS, WORDS + 5)}
    assert len(programs) == 1
    sim.fill({"u": u0}, (WORDS + 5,))
    want = np.asarray(ref.global_fields(sim.grid, WORDS + 5)["u"])
    np.testing.assert_allclose(sim.field("u"), want, rtol=0, atol=1e-6)


@pytest.mark.parametrize("extent", [("8", "8", "8"), ("32", "32", "8")], ids=["cubic", "x=y=2z"])
def test_driver_runs_on_the_cpu(extent, capsys):
    """``stencil-acoustic`` takes the PHYSICAL extents, equal or not, and says
    on stderr which mesh the partitioner gave it and what a step wires."""
    from stencil_tpu.bin import acoustic

    rc = acoustic.main([*extent, "--nbl", "4", "--iters", "1", "--steps", "2"])
    assert rc == 0
    io = capsys.readouterr()
    row = io.out.strip().splitlines()[-1].split(",")
    assert row[0] == "acoustic" and row[3:7] == [*extent, "4"]
    (said,) = [l for l in io.err.splitlines() if l.startswith("mesh: ")]
    devices = int(row[2])
    mesh = [int(d) for d in said.split()[1].split(",")]
    assert int(np.prod(mesh)) == devices and "wired=" in said and "wrapped=" in said, said


# --- u_prev <- u is a rename (ISSUE 36) ----------------------------------------
#
# The kernel returns ``u_prev`` as ``u``'s centre plane, so the pass writes
# ``u`` alone, into ``u_prev``'s block, and the step swaps the two handles
# (ops/stream_plan.py trace_plane_kernel).  Held to the same model with the rule
# off (the parent's program) bit for bit, and to the plain reference within
# ``ATOL`` (the two compilers round differently, see the header: bitwise
# against the reference is not to be had on the CPU).


def _renames_off(monkeypatch):
    from test_plane_stencil import _renames_off as off

    off(monkeypatch)


_MESH_SIMS = {}


def _mesh_sim(mesh, renames=True):
    """One realized model a (mesh, rule), its seeded blocks put back for the
    case that asks: its programs -- one a step count -- serve every such case."""
    if (mesh, renames) not in _MESH_SIMS:
        with pytest.MonkeyPatch.context() as mp:
            if not renames:
                _renames_off(mp)
            sim = AcousticWave(N, N, N, nbl=NBL, interpret=True, seed_words=WORDS,
                               devices=jax.devices()[: int(np.prod(mesh))])
            sim.dd.set_partition(*mesh)
            sim.realize()
        _MESH_SIMS[mesh, renames] = (sim, seeded_blocks.snapshot(sim.dd))
    sim, blocks = _MESH_SIMS[mesh, renames]
    seeded_blocks.restore(sim.dd, blocks)
    return sim


@pytest.mark.parametrize("mesh", [(1, 1, 1), (2, 1, 1), (2, 2, 1)], ids=lambda m: "x".join(map(str, m)))
@pytest.mark.parametrize("steps", [1, 2, 3, 8])
def test_the_renamed_step_is_bitwise_the_copying_one(steps, mesh):
    """``steps`` in one dispatch and again in a second one (an odd count hands
    the dispatch's outputs on permuted against its donated inputs): every
    interior cell of ``u`` AND ``u_prev`` bitwise the rule-off program's and
    within ``ATOL`` of the plain reference; after ``dd.exchange()`` every raw
    cell of every quantity bitwise too.  1 is a step behind an empty loop, 2 a
    trip, 3 a trip and a step behind it.  8 is four trips: the program of 2
    with four times the ``length`` on its loop and nothing else changed -- the
    traced programs are held to that, which lowers nothing (ISSUE 55) -- and
    runs as four dispatches of the trip."""

    def run(renames):
        sim = _mesh_sim(mesh, renames)
        plan = sim._step._stream_plan
        if steps == 8:
            from program_fingerprint import step_loop_and_text

            built = sim._step._resilience.built()
            (trips, of_two), (trips8, of_eight) = (
                step_loop_and_text(jax.make_jaxpr(built, static_argnums=1)(sim.dd._curr, n)) for n in (2, 8))
            assert trips8 == 4 * trips and of_eight == of_two
        seen = []
        for _ in range(2):
            for n in (2,) * 4 if steps == 8 else (steps,):
                sim.step(n)
            seen.append({q: sim.field(q) for q in ("u", "u_prev")})
        sim.dd.exchange()
        return seen, {q: np.asarray(sim.dd._curr[q]) for q in QUANTITIES}, plan, sim.grid

    seen, raws, plan, grid = run(True)
    assert plan["renamed"] == ("u_prev",) and plan["writers"] == ("u",), plan
    seen_off, raws_off, plan_off, _ = run(False)
    assert plan_off["renamed"] == () and plan_off["writers"] == ("u", "u_prev"), plan_off
    for k, (got, want) in enumerate(zip(seen, seen_off)):
        u, u_prev, _ = _reference(grid, (k + 1) * steps)
        for q, r in (("u", u), ("u_prev", u_prev)):
            np.testing.assert_array_equal(got[q], want[q], err_msg=f"{q} after dispatch {k}")
            assert float(np.max(np.abs(got[q] - r))) <= ATOL, (q, k)
    for q in QUANTITIES:
        np.testing.assert_array_equal(raws[q], raws_off[q], err_msg=q)


def _loop_and_body(closed):
    """The step loop of a traced stream step and its body's jaxpr, with the
    body's carried invars and outvars (a static trip count traces as a
    ``scan``: constants first, then the carry)."""
    from stencil_tpu.analysis import jaxpr as jx

    (loop,) = [e for e in jx.iter_eqns(closed) if e.primitive.name == "scan"]
    body = loop.params["jaxpr"].jaxpr
    return loop, body, body.invars[loop.params["num_consts"]:], body.outvars


def _home_of(body, var):
    """Follow ``var`` back through the in-place Pallas calls that made it (an
    output IS the operand it aliases) to the value whose buffer it lives in."""
    producers = {id(o): e for e in body.eqns for o in e.outvars}
    while id(var) in producers:
        eqn = producers[id(var)]
        if eqn.primitive.name != "pallas_call":
            return None  # a fresh buffer: no home among the carry
        k = [id(o) for o in eqn.outvars].index(id(var))
        src = [i for i, o in eqn.params["input_output_aliases"] if o == k]
        if not src:
            return None
        var = eqn.invars[src[0]]
    return var


@pytest.mark.parametrize("steps,trips,behind", [(8, 4, 0), (9, 4, 1), (1, 0, 1)])
def test_a_loop_trip_returns_every_block_to_its_place(steps, trips, behind, monkeypatch):
    """The step loop as the chip runs it (blend kernels on: the x wrap is an
    in-place kernel): a trip holds TWO steps, and followed back through the
    in-place calls every carried block comes out of the trip in the buffer it
    went in as -- so XLA has nothing to copy (a body that returned its carry
    permuted made it copy three whole arrays a trip; PERF.md, PR 36).  An odd
    count runs its last step behind the loop."""
    monkeypatch.setenv("STENCIL_HALO_BLEND", "1")
    sim = _sim("pallas")
    closed = jax.make_jaxpr(sim._step._resilience.built(), static_argnums=1)(sim.dd._curr, steps)
    from test_plane_stencil import _pass_calls

    assert len(_pass_calls(closed)) == (2 if trips else 0) + behind
    if not trips:
        return
    loop, body, carried, out = _loop_and_body(closed)
    assert loop.params["length"] == trips
    blocks = [(i, o) for i, o in zip(carried, out) if getattr(i.aval, "ndim", 0) == 3]
    assert len(blocks) == 2  # u and u_prev: m and damp never change and ride as constants
    for i, o in blocks:
        assert _home_of(body, o) is i


def _leapfrog_program(steps, period=None, monkeypatch=None):
    """A cellwise two-level leapfrog through the REAL step builder, its plane
    pass stood in for by plain ``jnp`` over whole blocks -- what the CPU
    compiler can run in place, as the chip runs the aliased Pallas call (the
    interpreted call lowers to loops that copy blocks whatever the program
    around them does, so its CPU HLO says nothing)."""
    import jax.numpy as jnp

    from test_plane_stencil import _plane_domain

    from stencil_tpu.ops import stream as sm

    def kernel(views, info):
        u = views["u"].center()
        return {"u": 1.5 * u - 0.5 * views["v"].center(), "v": u}

    def stand_in(pass_kernel, names, raws, *a, writers=None, renames=(), **kw):
        views = {nm: spass.PlaneView((b,), None) for nm, b in zip(names, raws)}
        vals = pass_kernel(views, spass.PlaneInfo(None, None, None, None, 1))  # coordinates unread
        out = list(raws)
        for p, q in renames:
            out[names.index(p)] = raws[names.index(q)]
        for nm, v in vals.items():
            out[names.index(nm)] = v
        return out

    monkeypatch.setattr(sm, "stream_plane_pass", stand_in)
    if period is not None:
        monkeypatch.setattr(sp, "_carry_period", lambda names, stages: period)
    dd, hs = _plane_domain(["u", "v"], 1, 1, extent=(32, 32, 32))
    plan = sp.resolve_stream_plan(dd, kernel, 1, sp.plan_stream(dd, 1, "plane", False), True)
    step = sm._build_stream_step(dd, kernel, 1, plan, interpret=True)
    assert plan["renamed"] == ("v",) and plan["halo_readers"] == (), plan
    want = {h.name: dd.quantity_to_host(h) for h in hs}
    for _ in range(steps):
        want = {"u": 1.5 * want["u"] - 0.5 * want["v"], "v": want["u"]}
    text = step.lower(dd._curr, steps).compile().as_text()
    dd.run_step(step, steps)
    for h in hs:
        np.testing.assert_allclose(dd.quantity_to_host(h), want[h.name], rtol=1e-6, atol=1e-6)
    return text


def _block_copies_in_loops(text, shape="f32[34,34,34]"):
    """``copy`` ops of a whole block inside the computations a ``while`` runs."""
    import re

    comps = dict(re.findall(r"\n(?:ENTRY )?%?([\w.\-]+) \([^\n]*\) -> [^\n]*\{\n(.*?)\n\}", text, re.S))
    bodies = set(re.findall(r"\bwhile\([^\n]*body=%?([\w.\-]+)", text))
    assert bodies, "the step loop is gone"
    seen, todo = set(), list(bodies)
    while todo:  # the body and whatever it calls
        name = todo.pop()
        if name in seen or name not in comps:
            continue
        seen.add(name)
        todo += re.findall(r"(?:calls|to_apply|body|condition)=%?([\w.\-]+)", comps[name])
    return sum(
        len(re.findall(rf"= {re.escape(shape)}\S* copy\(", comps[name])) for name in seen
    )


def test_an_even_dispatch_compiles_to_a_loop_without_a_block_copy(monkeypatch):
    """The compiled (CPU) program of an even ``steps``: no whole-block ``copy``
    inside the ``while`` body -- and the same step with a trip of ONE step,
    its carry returned permuted, has them: the check can see what it guards."""
    assert _block_copies_in_loops(_leapfrog_program(8, monkeypatch=monkeypatch)) == 0
    assert _block_copies_in_loops(_leapfrog_program(8, 1, monkeypatch)) > 0


def test_an_odd_dispatch_is_correct(monkeypatch):
    """Nine steps: four trips and one step behind the loop, its outputs
    permuted against the donated inputs -- correct; XLA may copy at the
    program's edge, once a dispatch (three blocks on the chip's compiler)."""
    assert _block_copies_in_loops(_leapfrog_program(9, monkeypatch=monkeypatch)) == 0


def _fingerprint_of(step, curr):
    from program_fingerprint import fingerprint

    return fingerprint(jax.make_jaxpr(step, static_argnums=1)(curr, 3))


@pytest.mark.parametrize("variant", ["split", "fused", "fresh-output"])
def test_the_other_schedules_rename_nothing_and_keep_their_programs(variant, monkeypatch):
    """``overlap="split"`` keeps fresh outputs, ``halo="fused"`` writes every
    quantity, a plan resolved un-aliased has no block to land in: ``renamed``
    0, and the traced program is, by its fingerprint, the one the rule-off
    build traces."""
    from stencil_tpu.ops import stream as sm

    plan_kw = {
        "split": {"overlap": "split", "overlap_forced": True},
        "fused": {"halo": "fused", "halo_forced": True},
        "fresh-output": {"alias": False, "alias_forced": True},
    }[variant]

    def build():
        sim = AcousticWave(N, N, N, nbl=NBL, interpret=True, seed_words=WORDS,
                           devices=jax.devices()[: 4 if variant == "fused" else 1])
        if variant == "fused":  # the packed routes need a wire on y and z
            sim.dd.set_partition(1, 2, 2)
            sim.dd.set_exchange_route("yzpack_xla")
        sim.realize()
        plan = sp.resolve_stream_plan(
            sim.dd, sim._kernel, ref.RADIUS,
            dict(sp.plan_stream(sim.dd, ref.RADIUS, "plane", False), **plan_kw), True,
        )
        step = sm._build_stream_step(sim.dd, sim._kernel, ref.RADIUS, plan, interpret=True)
        return plan, _fingerprint_of(step, sim.dd._curr)

    plan, fp = build()
    for key, want in plan_kw.items():  # the variant engaged, it did not degrade
        assert plan[key] == want, plan
    assert plan["renamed"] == () and "u_prev" in plan["writers"], plan
    _renames_off(monkeypatch)
    plan_off, fp_off = build()
    assert plan_off["renamed"] == () and fp == fp_off
