"""``ElasticWave`` (models/elastic.py) against its plain reference
(models/elastic_reference.py), and the stream engine's staged plane-route
step it forced (ISSUE 33): the so-8 velocity-stress propagator on the plane
route and on the XLA engine, in process, interpreted, small (24^3 with ``nbl``
4: 8 physical cells, a 4-cell sponge and the 4-cell zero frame on every side).

Tolerance.  The model and the reference build every staggered difference in
the same order, so they differ by the two compilers' roundings only (fused
multiply-adds): 5e-7 measured after three steps at a stress amplitude of 1.6.
``ATOL`` 5e-6 leaves ten times that and lies far under what bf16 storage does
to the same run, which ``test_bf16_storage_fails_the_tolerance`` holds it to.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import seeded_blocks

from stencil_tpu import telemetry
from stencil_tpu.core.dim3 import Dim3
from stencil_tpu.models import elastic_reference as ref
from stencil_tpu.models.elastic import ElasticWave
from stencil_tpu.ops import stream as sm
from stencil_tpu.ops import stream_plan as sp
from stencil_tpu.telemetry import names as tm

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N, NBL, DISPATCH = 24, 4, 3
ATOL = 5e-6
WORDS = np.asarray([0x1234ABCD, 77, 0xDEADBEEF, 2024], dtype=np.uint32)


_BUILT = {}


def _sim(impl="pallas", devices=None, partition=None, fresh=False, **kw):
    """One realized model a configuration -- engine, devices, partition, options
    and the ``STENCIL_HALO_BLEND`` / ``STENCIL_VMEM_LIMIT_BYTES`` of the moment
    (the plan reads them) --, its seeded blocks put back for the case that asks
    (ISSUE 55: a model's programs, one a dispatch size, are most of a case's
    time; ``fresh`` for the case that looks at what a NEW domain allocates)."""
    devices = devices or jax.devices()[:1]
    key = (impl, len(devices), partition, tuple(sorted(kw.items())),
           os.environ.get("STENCIL_HALO_BLEND"), os.environ.get("STENCIL_VMEM_LIMIT_BYTES"))
    if fresh or key not in _BUILT:
        sim = ElasticWave(N, N, N, nbl=NBL, kernel_impl=impl, interpret=True,
                          devices=devices, seed_words=WORDS, **kw)
        if partition:
            sim.dd.set_partition(*partition)
        sim.realize()
        if fresh:
            return sim
        _BUILT[key] = (sim, seeded_blocks.snapshot(sim.dd))
    sim, blocks = _BUILT[key]
    seeded_blocks.restore(sim.dd, blocks)
    return sim


def _reference(grid, steps):
    return {q: np.asarray(a) for q, a in
            ref.steps_framed(grid, ref.global_fields(grid, WORDS), steps).items()}


def _errors(sim, dispatches):
    for _ in range(dispatches):
        sim.step(DISPATCH)
    want = _reference(sim.grid, dispatches * DISPATCH)
    assert min(np.max(np.abs(want[q])) for q in ref.WAVEFIELDS) > 0.05  # all nine have moved
    errs = {}
    for q in ref.WAVEFIELDS:
        got = sim.field(q)
        frame = np.asarray(ref.frame_mask(sim.grid.shape))
        assert not np.any(got[frame]), q  # the outer frame is exactly 0
        errs[q] = float(np.max(np.abs(got - want[q])))
    return errs


@pytest.mark.parametrize("dispatches", [1, 2])
@pytest.mark.parametrize("impl", ["pallas", "jnp"])
def test_model_matches_the_reference(impl, dispatches):
    errs = _errors(_sim(impl), dispatches)
    assert max(errs.values()) <= ATOL, errs


def test_bf16_storage_fails_the_tolerance():
    sim = _sim("pallas", storage_dtype="bf16")
    assert sim.dd.storage_dtype() == "bf16"
    errs = _errors(sim, 1)
    assert min(errs.values()) > 100 * ATOL, errs


@pytest.mark.parametrize("impl", ["pallas", "jnp"])
def test_model_matches_the_reference_on_a_2x2x1_mesh(impl):
    """Mesh [2,2,1]: the x and y sweeps of both in-step exchanges cross a
    wire, the z sweep wraps on the chip, the frame is found from wrapped
    global coordinates on every shard."""
    sim = _sim(impl, devices=jax.devices()[:4], partition=(2, 2, 1))
    assert tuple(sim.dd.mesh_dim()) == (2, 2, 1)
    if impl == "pallas":
        # both stages' messages: 6 stresses, then 3 velocities, four radius-4
        # faces of the raw block each (ISSUE 37) and, behind each y face, the
        # corner relay of the joint x-y sweep: both x halos on its four rows (ISSUE 50)
        raw = sim.dd.local_spec().raw_size()
        args = sim._step._span_args()
        assert (args["wired"], args["joint"], args["exchanged"]) == ("xy", "xy", "6/3"), args
        cells = 2 * 4 * (raw.y * raw.z + raw.x * raw.z) + 2 * 8 * 4 * raw.z
        assert args["wire_bytes"] == (6 + 3) * cells * 4, args
    errs = _errors(sim, 1)
    assert max(errs.values()) <= ATOL, errs


@pytest.mark.parametrize("devices,partition", [(1, None), (4, (2, 2, 1)), (8, None)])
def test_plane_route_is_bitwise_the_xla_engine_on_every_quantity(devices, partition):
    """The staged plane step exchanges six quantities, then three, and forms
    its passes from the footprints; the XLA slice engine exchanges all
    thirteen before each stage.  Two dispatches on, every interior cell of
    every quantity is bitwise the same."""
    sims = [_sim(impl, devices=jax.devices()[:devices], partition=partition)
            for impl in ("pallas", "jnp")]
    for sim in sims:
        for _ in range(2):
            sim.step(DISPATCH)
    for q in ref.QUANTITIES:
        a, b = (sim.field(q) for sim in sims)
        assert np.any(a != 0.0), q
        np.testing.assert_array_equal(a, b, err_msg=q)


def test_one_chip_sweeps_are_self_wraps(monkeypatch):
    """On one device with the blend kernels on (as on the chip) every sweep of
    both exchanges is ``wrap_halo`` at radius 4."""
    monkeypatch.setenv("STENCIL_HALO_BLEND", "1")
    sim = _sim("pallas")
    assert set(sim.dd._wrap_axes) == {"x", "y", "z"}
    errs = _errors(sim, 1)
    assert max(errs.values()) <= ATOL, errs


# --- the plan: stages, passes, footprints --------------------------------------


def test_the_plan_is_staged_and_the_span_says_so():
    sim = _sim("pallas")
    plan = sim._step._stream_plan
    assert (plan["route"], plan["m"], plan["grouping"], plan["alias"]) == ("plane", 1, "joint", True)
    v, t = plan["stages"]
    assert v["readers"] == ref.STRESSES and t["readers"] == ref.VELOCITIES
    assert [p["writes"] for p in v["passes"]] == [ref.VELOCITIES]
    assert [p["writes"] for p in t["passes"]] == [ref.STRESSES]
    # a ring only where the kernel reads off-centre ALONG X
    assert v["passes"][0]["rings"] == ("txx", "txy", "txz")
    assert t["passes"][0]["rings"] == ref.VELOCITIES
    assert set(v["passes"][0]["reads"]) == set(ref.WAVEFIELDS) | {"b", "damp"}
    assert set(t["passes"][0]["reads"]) == set(ref.WAVEFIELDS) | {"lam", "mu", "damp"}
    assert plan["halo_readers"] == ref.WAVEFIELDS and plan["writers"] == ref.WAVEFIELDS
    assert sim._step._span_args() == {
        "route": "plane", "x_radius": 4, "grouping": "joint", "streamed": 13,
        "stages": 2, "passes": 2, "exchanged": "6/3", "written": "3/6", "aliased": "11/12",
        "renamed": "0/0",  # every output is masked by the frame: none is a centre plane (ISSUE 36)
        "wrapped": "",  # a plain CPU run: the blend kernels are off (ISSUE 34)
        "wired": "", "wire_bytes": 0, "joint": "",  # one device: nothing crosses to another shard (ISSUE 37)
        # what the two kernels read against what the exchanges serve (ISSUE 39):
        # nine wavefields, along the axes only
        "quantities": 13, "offcentre": 9, "diagonal": 0, "read_sides": 36,  # 9 (stress, axis) + 9 (velocity, axis) pairs, both sides
        "exchanged_sides": 54,
        "steps_per_trip": 1,  # no rename: every trip of the step loop is one step (ISSUE 44)
        "plane_window": "raw",  # 24 cells of interior a side: no whole vector tile (ISSUE 45)
        "plane_strip": 0,  # ... so the kernels run over the plane whole (ISSUE 46)
        "tile_rows": 0, "y_tiles": 1,  # ... and their pipeline moves whole planes (ISSUE 51)
        "plane_lanes": "raw",  # ... every call of a dispatch (ISSUE 54)
        "wired_edges": "", "wire_bytes_by_stage": "0/0",  # one device: no wire (ISSUE 47)
    }
    seen = []
    real = telemetry.span

    def spy(name, *a, **kw):
        seen.append((name, kw))
        return real(name, *a, **kw)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(telemetry, "span", spy)
        sim.step(2)
    (kw,) = [kw for name, kw in seen if name == tm.SPAN_STEP]
    assert (kw["label"], kw["steps"], kw["stages"]) == ("elastic", 2, 2)
    assert (kw["exchanged"], kw["written"], kw["wrapped"]) == ("6/3", "3/6", "")


def _real_size_traces():
    """Both stages traced at the benchmark's plane size, 608 x 608."""
    sim = ElasticWave(N, N, N, nbl=NBL, interpret=True, devices=jax.devices()[:1])
    plane = jax.ShapeDtypeStruct((608, 608), jnp.float32)
    return [
        sp.trace_plane_kernel(stage, ref.QUANTITIES, [plane] * 13, 4, Dim3(600, 600, 600), False)
        for stage in (sim._stage_v, sim._stage_t)
    ]


def test_the_plan_at_the_benchmark_size_fits_the_vmem_model_pass_by_pass():
    """At 608 x 608 planes no stage fits one pass (stage V jointly: 11
    quantities, 3 rings); the planner makes two a stage, each inside the
    budget, and they are the passes the benchmark's configuration states."""
    from stencil_tpu.ops.jacobi_pallas import _padded_plane_bytes, _vmem_budget

    plane_bytes = {q: _padded_plane_bytes(608, 608, 4) for q in ref.QUANTITIES}
    passes = [p for t in _real_size_traces() for p in sp.plan_plane_passes(t, plane_bytes)]
    assert [p["writes"] for p in passes] == [
        ("vx", "vy"), ("vz",), ("txx", "tyy", "tzz", "txy"), ("txz", "tyz"),
    ]
    assert [p["rings"] for p in passes] == [("txx", "txy"), ("txz",), ("vx", "vy"), ("vz",)]
    for p in passes:
        assert p["vmem_bytes"] <= _vmem_budget(), p
        assert p["vmem_bytes"] == sp.plane_pass_vmem_bytes(
            plane_bytes, 4, p["reads"], p["rings"], p["writes"])
    # every quantity whole in one pass would not fit
    whole = sp.plane_pass_vmem_bytes(plane_bytes, 4, ref.QUANTITIES, ref.QUANTITIES, ref.WAVEFIELDS)
    assert whole > 2 * _vmem_budget()
    with open(os.path.join(ROOT, "benchmark", "configs", "elastic-so8-600.json")) as f:
        stated = json.load(f)["passes"]
    assert [(c["reads"], c["writes"], c["rings"]) for c in stated] == [
        (len(p["reads"]), len(p["writes"]), len(p["rings"])) for p in passes
    ]


def _fourteen_kernel(views, info):
    """ONE output that reads fourteen quantities, every one off-centre along x."""
    acc = None
    for v in views.values():
        term = v.sh(1, 0, 0) - v.sh(-1, 0, 0)
        acc = term if acc is None else acc + term
    return {"q0": acc}


def test_a_step_that_fits_in_no_pass_raises_at_plan_time(monkeypatch):
    """Fourteen quantities jointly, nothing to split: ``make_step`` raises
    before anything is built and says which quantities and how many bytes
    (the parent handed such a plan to Mosaic to fail)."""
    from test_stream import _mk

    from stencil_tpu.core.radius import Radius

    names = [f"q{i}" for i in range(14)]
    plane_bytes = {q: 1_556_480 for q in names}  # a 608 x 640 f32 plane
    trace = sp.trace_plane_kernel(
        _fourteen_kernel, names, [jax.ShapeDtypeStruct((608, 608), jnp.float32)] * 14, 1,
        Dim3(600, 600, 600), True,
    )
    with pytest.raises(ValueError, match=r"writes \('q0',\) reads 14 quantities .*14 of them "
                       r"off-centre along x.* bytes of VMEM.*fits no pass"):
        sp.plan_plane_passes(trace, plane_bytes)
    # ... and through make_step, on a domain whose budget is that tight
    monkeypatch.setenv("STENCIL_VMEM_LIMIT_BYTES", "200000")
    dd, _ = _mk(16, 16, 16, Radius.constant(1), names, jax.devices()[:1])
    with pytest.raises(ValueError, match=r"reads 14 quantities.*fits no pass"):
        dd.make_step(_fourteen_kernel, engine="stream", stream_path="plane", interpret=True)


def test_a_stage_whose_passes_would_read_their_own_writes_raises(monkeypatch):
    """Passes run in place one after the other: a stage that does not fit one
    pass and whose later output reads a quantity an earlier pass has written
    INTO ITS OWN BLOCK cannot be split (``b <- 2 a`` after ``a`` was advanced
    would read the NEW ``a``), and the refusal says which block.  Where the
    later output is the earlier one's centre plane itself -- a leapfrog's
    ``b <- a`` -- nothing clashes since ISSUE 57: the new ``a`` lands in ``b``'s
    block (a rename), one pass writes and nothing else reads it."""
    from test_stream import _mk

    from stencil_tpu.core.radius import Radius

    def advance(views):
        a = views["a"]
        return 0.5 * (a.sh(1, 0, 0) + a.sh(-1, 0, 0))

    def scaled(views, info):
        return {"a": advance(views), "b": 2.0 * views["a"].center()}

    def leapfrog(views, info):
        return {"a": advance(views), "b": views["a"].center()}

    dd, hs = _mk(16, 16, 16, Radius.constant(1), ["a", "b"], jax.devices()[:1])
    step = dd.make_step(scaled, engine="stream", stream_path="plane", interpret=True)
    assert len(step._stream_plan["stages"][0]["passes"]) == 1  # fits: one pass
    # 24 x 128-lane planes of 12,288 B and 3 MB of stack a quantity read: a
    # alone 3.07 MB, b alone 6.07 MB, the two jointly 6.12 MB
    monkeypatch.setenv("STENCIL_VMEM_LIMIT_BYTES", "6100000")
    with pytest.raises(ValueError, match=(
            r"writes \('b',\) \(into the blocks of \('b',\)\) reads \('a',\), whose block an "
            r"earlier pass.*stage of its own")):
        dd.make_step(scaled, engine="stream", stream_path="plane", interpret=True)
    (p,) = dd.make_step(
        leapfrog, engine="stream", stream_path="plane", interpret=True)._stream_plan["stages"][0]["passes"]
    assert (p["writes"], p["reads"], p["renames"]) == (("a",), ("a", "b"), (("b", "a"),))


def test_passes_split_under_a_tight_budget_and_stay_bitwise(monkeypatch):
    """The same model under a budget that forces the split the real size
    forces (several passes a stage, lagged inputs, in place): bitwise the
    one-pass-a-stage run."""
    whole = _sim("pallas")
    # 32 x 128-lane f32 planes are 16 KB: room for (vx, vy) but not all of V
    monkeypatch.setenv("STENCIL_VMEM_LIMIT_BYTES", str(27_700_000))
    split = _sim("pallas")
    passes = [len(st["passes"]) for st in split._step._stream_plan["stages"]]
    assert passes[0] > 1 and passes[1] > 1, split._step._stream_plan["stages"]
    assert split._step._span_args()["passes"] == sum(passes)
    for sim in (whole, split):
        sim.step(DISPATCH)
    for q in ref.WAVEFIELDS:
        np.testing.assert_array_equal(whole.field(q), split.field(q), err_msg=q)


# --- the program: exchanges and scopes per stage ---------------------------------


def _stage_eqns(sim, steps=1):
    from stencil_tpu.analysis import jaxpr as jx

    closed = jax.make_jaxpr(sim._step._resilience.built(), static_argnums=1)(sim.dd._curr, steps)
    by_stage = {0: [], 1: []}
    for e in jx.iter_eqns(closed):
        stack = jx.name_stack_str(e)
        for k in by_stage:
            if tm.step_stage_span(k) in stack:
                by_stage[k].append((e, stack))
    return by_stage


def test_each_stage_sends_only_what_it_reads_off_centre():
    """Mesh [2,2,1] on the CPU: the ``ppermute`` cells under ``step.stage.0``
    are the six stresses' slabs, under ``step.stage.1`` the three
    velocities' -- two to one, as ``tests/test_plane_stencil.py`` counts them
    for one stage."""
    sim = _sim("pallas", devices=jax.devices()[:4], partition=(2, 2, 1))
    cells = {
        k: sum(int(np.prod(v.aval.shape)) for e, _ in eqns if e.primitive.name == "ppermute"
               for v in e.invars)
        for k, eqns in _stage_eqns(sim).items()
    }
    assert cells[1] > 0 and cells[0] == 2 * cells[1], cells


def test_the_step_program_has_two_stages_of_passes_and_wraps(monkeypatch):
    """The step as the chip runs it (blend kernels on): under ``step.stage.0``
    one pass and the 6 x wraps of the stresses, under ``step.stage.1`` one
    pass and the 3 of the velocities -- the y and z wraps ride in the passes
    (ISSUE 34: 6 x 3 and 3 x 3 before; at 608 x 608 planes the four passes make
    it 13 Pallas calls a step where it was 31); every pass under ``step.pass``
    inside its stage, every pass output aliased onto its input."""
    monkeypatch.setenv("STENCIL_HALO_BLEND", "1")
    sim = _sim("pallas")
    assert sim._step._stream_plan["pass_wrap_axes"] == "yz"
    assert sim._step._span_args()["wrapped"] == "yz"
    for k, (wraps, outs) in enumerate(((6, 3), (3, 6))):
        calls = [(e, s) for e, s in _stage_eqns(sim)[k] if e.primitive.name == "pallas_call"]
        passes = [(e, s) for e, s in calls if e.params.get("name") == tm.KERNEL_STREAM_PLANE_PASS]
        (one,) = passes
        assert tm.SPAN_STEP_PASS in one[1]
        assert len(one[0].outvars) == outs == len(one[0].params["input_output_aliases"])
        assert sum("exchange.x/exchange.x.wrap" in s for _, s in calls) == wraps == len(calls) - 1
        assert not [s for _, s in calls if "exchange.y" in s or "exchange.z" in s]


def test_a_one_stage_step_has_no_stage_scope():
    """Acoustic's program keeps the scopes it had: no ``step.stage`` anywhere."""
    from stencil_tpu.analysis import jaxpr as jx
    from stencil_tpu.models.acoustic import AcousticWave

    sim = AcousticWave(N, N, N, nbl=NBL, interpret=True, devices=jax.devices()[:1])
    sim.realize()
    closed = jax.make_jaxpr(sim._step._resilience.built(), static_argnums=1)(sim.dd._curr, 1)
    assert not [e for e in jx.iter_eqns(closed) if tm.SPAN_STEP_STAGE in jx.name_stack_str(e)]
    assert "stages" not in sim._step._span_args()


def test_acoustic_drops_the_rings_nothing_reads_and_is_bitwise_unchanged(monkeypatch):
    """The per-quantity ring rule reaches acoustic through the same code: its
    pass keeps ONE ring (``u``) where it had four, and every raw cell of every
    quantity is bitwise what the every-quantity-ringed pass gives (the
    parent's program, here the fail-closed trace) -- but the shell of
    ``u_prev``, which is a rename of ``u`` since ISSUE 36 and carries ``u``'s
    shell where the fail-closed pass wrote its own back: its interior."""
    from stencil_tpu.models.acoustic import QUANTITIES, AcousticWave

    def run():
        sim = AcousticWave(N, N, N, nbl=NBL, interpret=True, devices=jax.devices()[:1],
                           seed_words=WORDS)
        sim.realize()
        sim.step(5)
        (p,) = sim._step._stream_plan["stages"][0]["passes"]
        return {q: np.asarray(sim.dd._curr[q]) for q in QUANTITIES}, p

    new, p = run()
    assert (p["rings"], p["writes"], p["reads"]) == (("u",), ("u",), QUANTITIES)
    assert p["renames"] == (("u_prev", "u"),)
    monkeypatch.setattr(
        sp, "trace_plane_kernel",
        lambda kernel, names, *a: sp.PlaneTrace(
            tuple(names), ("u",), ("u", "u_prev"), 4, None, kernel
        ),
    )
    old, p_old = run()
    assert p_old["rings"] == QUANTITIES and p_old["renames"] == ()
    inner = (slice(4, -4),) * 3
    for q in QUANTITIES:
        cells = inner if q == "u_prev" else ...
        np.testing.assert_array_equal(new[q][cells], old[q][cells], err_msg=q)


# --- make_step's contract for stages ------------------------------------------------


def test_a_staged_step_runs_the_plane_route_only():
    from test_stream import _mk

    from stencil_tpu.core.radius import Radius

    def a(views, info):
        return {"u": views["u"].sh(1, 0, 0)}

    dd, _ = _mk(16, 16, 16, Radius.constant(1), ["u"], jax.devices()[:1])
    step = dd.make_step([a, a], engine="stream", interpret=True)  # auto: not wrap
    assert step._stream_plan["route"] == "plane" and step._span_args()["stages"] == 2
    with pytest.raises(ValueError, match="stages runs the plane route"):
        dd.make_step([a, a], engine="stream", stream_path="wavefront", interpret=True)
    dd2, _ = _mk(16, 16, 16, Radius.constant(1), ["u"], jax.devices()[:1], mult=2)
    with pytest.raises(ValueError, match="halo multiplier"):
        dd2.make_step([a, a])


def test_two_stages_are_two_steps_of_one(monkeypatch):
    """``make_step([k, k])`` advanced once IS ``make_step(k)`` advanced twice,
    bitwise, on both engines (8 devices: every exchange crosses wires)."""
    from test_stream import _mk, star_kernel

    from stencil_tpu.core.radius import Radius

    k = star_kernel(2)
    out = {}
    for label, kernel, steps in (("staged", [k, k], 2), ("single", k, 4)):
        for engine in ("stream", "xla"):
            dd, hs = _mk(16, 16, 16, Radius.constant(2), ["u"], jax.devices()[:8])
            kw = {"engine": "stream", "stream_path": "plane", "interpret": True} \
                if engine == "stream" else {"overlap": False}
            dd.run_step(dd.make_step(kernel, **kw), steps)
            out[label, engine] = dd.quantity_to_host(hs[0])
    base = out["single", "xla"]
    assert np.ptp(base) > 0
    for key, a in out.items():
        np.testing.assert_array_equal(a, base, err_msg=str(key))


# --- the second slot ------------------------------------------------------------------


def test_the_next_slot_is_allocated_on_first_use_where_two_do_not_fit(monkeypatch):
    """Thirteen 608^3 quantities fit a 16.9 GB chip once, not twice: a domain
    whose two slots do not fit its device allocates ``next`` on first use."""
    from stencil_tpu.domain import DistributedDomain

    monkeypatch.setattr(DistributedDomain, "_both_slots_fit", lambda self: False)
    sim = _sim("pallas", fresh=True)
    assert sim.dd._next == {} and len(sim.dd._curr) == 13
    sim.step(1)
    assert sim.dd._next == {}  # a built step carries curr in place
    h = sim.handles["vx"]
    assert not np.any(np.asarray(sim.dd.get_next(h))) and len(sim.dd._next) == 13
    before = sim.field("vx")
    sim.dd.swap()
    sim.dd.swap()
    np.testing.assert_array_equal(sim.field("vx"), before)


def test_both_slots_are_allocated_where_they_fit():
    sim = _sim("jnp")
    assert sim.dd._both_slots_fit() and len(sim.dd._next) == 13
