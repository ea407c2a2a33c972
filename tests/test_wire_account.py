"""The account of the wires is a measurement (ISSUE 49): for every step
builder the package's models reach, and for ``dd.exchange()``, the
``exchange.hop.*.bytes`` counters move over one dispatch by exactly the bytes
of the ``ppermute`` operands of the traced program, hop by hop, times the
subdomains; hops on unsplit axes read 0; ``wire_bytes`` on the span is the
per-shard sum; a packed sweep's ``exchange.packed.*`` move with the messages
it packs.  One function (``ops/exchange.py exchange_account``) is behind the counters, the spans and the drivers' tables, and this file
holds it to the programs."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from stencil_tpu import DistributedDomain, Radius, telemetry
from stencil_tpu.ops import exchange as ex
from stencil_tpu.parallel.mesh import MESH_AXES
from stencil_tpu.telemetry import names as tm

from stencil_tpu.analysis.programs import mean6_kernel

HOPS = [(axis, side) for axis in MESH_AXES for side in ("low", "high")]


def traced_hops(closed, mesh_shape) -> dict:
    """``{(axis, side): bytes one shard sends}`` over the wires of a traced
    program: every ``ppermute`` on a SPLIT mesh axis (on an unsplit one the
    shard sends to itself: no wire), its operands' bytes, times the static
    trip counts of the loops around it; the side is the direction scope the
    equation sits under.  A ``ppermute`` inside a loop of unknown length, or
    under no direction scope, fails the test that asks."""
    from stencil_tpu.analysis import jaxpr as jx

    size = dict(zip(MESH_AXES, mesh_shape))
    out = {}

    def holds_wire(jaxpr):
        return any(e.primitive.name == "ppermute" for e in jx.iter_eqns(jaxpr))

    def visit(jaxpr, times):
        for e in jaxpr.eqns:
            name = e.primitive.name
            if name == "ppermute":
                axis = e.params["axis_name"]
                axis = axis[0] if isinstance(axis, tuple) else axis
                if size[axis] == 1:
                    continue
                scopes = jx.name_stack_str(e).split("/")
                (side,) = [s for s in ("low", "high") if tm.exchange_direction_span(axis, s) in scopes]
                n, shift = size[axis], +1 if side == "low" else -1
                assert sorted(e.params["perm"]) == sorted((k, (k + shift) % n) for k in range(n)), e
                nbytes = sum(int(np.prod(v.aval.shape)) * v.aval.dtype.itemsize for v in e.invars)
                out[axis, side] = out.get((axis, side), 0) + times * nbytes
                continue
            if name in jx.OPAQUE_PRIMITIVES:
                continue
            for sub in jx.eqn_subjaxprs(e):
                if name == "scan":
                    visit(sub, times * e.params["length"])
                elif name in ("while", "cond"):
                    assert not holds_wire(sub), f"a ppermute under a {name}: no static count"
                else:
                    visit(sub, times)

    visit(getattr(closed, "jaxpr", closed), 1)
    return out


def hop_counters() -> dict:
    counters = telemetry.snapshot()["counters"]
    return {hop: counters[tm.EXCHANGE_HOP_BYTES[hop]] for hop in HOPS}


def moved(before: dict, after: dict) -> dict:
    return {hop: after[hop] - before[hop] for hop in HOPS if after[hop] != before[hop]}


def spied_spans(run, name):
    """The keyword arguments of every ``name`` span ``run()`` opens."""
    seen, real = [], telemetry.span

    def spy(span, *a, **kw):
        seen.append((span, kw))
        return real(span, *a, **kw)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(telemetry, "span", spy)
        run()
    return [kw for span, kw in seen if span == name]


def _devices(mesh):
    return jax.devices()[: int(np.prod(mesh))]


# --- the builders: each returns (domain, traced program of `steps`, dispatch) ----


def _model(sim, mesh, steps, traceable=None):
    sim.dd.set_partition(*mesh)
    sim.realize()
    assert tuple(sim.dd.mesh_dim()) == mesh
    built = traceable(sim) if traceable else sim._step._resilience.built()
    closed = jax.make_jaxpr(built, static_argnums=1)(sim.dd._curr, steps)
    return sim.dd, sim._step, closed, lambda: sim.step(steps)


def acoustic(mesh, steps):
    from stencil_tpu.models.acoustic import AcousticWave

    return _model(AcousticWave(48, 48, 32, nbl=4, interpret=True, devices=_devices(mesh)), mesh, steps)


def elastic(mesh, steps):
    from stencil_tpu.models.elastic import ElasticWave

    return _model(ElasticWave(24, 24, 24, nbl=4, interpret=True, devices=_devices(mesh)), mesh, steps)


def mhd(mesh, steps):
    from stencil_tpu.models import astaroth_mhd_reference as ref
    from stencil_tpu.models.astaroth_mhd import AstarothMHD

    shape = (32, 32, 16)
    setup = ref.MhdSetup(shape, box=tuple(2.0 * np.pi * n / 16 for n in shape), max_waves=2)
    return _model(AstarothMHD(*shape, setup=setup, interpret=True, seed_words=None,
                              devices=_devices(mesh)), mesh, steps)


def lbm(mesh, steps):
    from stencil_tpu.models.lbm import LatticeBoltzmann

    return _model(LatticeBoltzmann(16, 16, 16, interpret=True, devices=_devices(mesh)), mesh, steps)


def astaroth(mesh, steps, extent=(32, 32, 32), **kw):
    """``AstarothSim``'s stream-engine step: the wavefront route (z slabs where
    they fit), and what its constructor can ask of it -- the split schedule,
    the fused halo over a packed exchange route."""
    from stencil_tpu.models.astaroth import AstarothSim

    want = kw.pop("want", {})
    sim = AstarothSim(*extent, num_quantities=2, kernel_impl="pallas", interpret=True,
                      devices=_devices(mesh), **kw)
    out = _model(sim, mesh, steps)
    plan = sim._step._stream_plan
    assert {k: plan[k] for k in want} == want, plan
    return out


def jacobi(mesh, steps, path="wavefront", extent=(32, 32, 256), want=(), **kw):
    from stencil_tpu.models.jacobi import Jacobi3D

    def traceable(sim):
        assert sim._pallas_path == path, sim._pallas_path
        assert {k: getattr(sim, k) for k in dict(want)} == dict(want)
        return sim._step

    kw.setdefault("kernel_impl", "pallas")
    sim = Jacobi3D(*extent, interpret=True, devices=_devices(mesh), **kw)
    return _model(sim, mesh, steps, traceable)


def xla_engine(mesh, steps, mult=2, extent=(16, 16, 16), route=None):
    """``make_step``'s own engine at a halo multiplier: ``steps`` macros of
    ``mult`` raw steps, one exchange of the wide shell each."""
    dd = DistributedDomain(*extent)
    dd.set_radius(Radius.constant(1))
    dd.set_halo_multiplier(mult)
    dd.set_devices(_devices(mesh))
    dd.set_partition(*mesh)
    if route is not None:
        dd.set_exchange_route(route)
    dd.add_data("u", dtype=jnp.float32)
    dd.add_data("v", dtype=jnp.float32)
    dd.realize()
    step = dd.make_step(mean6_kernel, overlap=False)
    closed = jax.make_jaxpr(step, static_argnums=1)(dd._curr, steps)
    return dd, step, closed, lambda: dd.run_step(step, steps)


CASES = {
    # the issue's scratch case: u alone rides the exchange, x and y over wires
    "acoustic-plane[2,2,1]": (acoustic, (2, 2, 1), 2),
    "acoustic-plane[2,1,1]": (acoustic, (2, 1, 1), 2),
    "elastic-staged[2,2,1]": (elastic, (2, 2, 1), 1),
    "mhd-staged[2,2,1]": (mhd, (2, 2, 1), 1),
    "lbm[2,2,1]": (lbm, (2, 2, 1), 2),
    "astaroth-zslabs[2,2,1]": (
        lambda mesh, steps: astaroth(mesh, steps, want={"route": "wavefront", "z_slabs": True}),
        (2, 2, 1), 7),
    "astaroth-split[2,2,2]": (
        lambda mesh, steps: astaroth(mesh, steps, stream_overlap="split",
                                     want={"route": "wavefront", "overlap": "split"}),
        (2, 2, 2), 3),
    "astaroth-fused[2,2,2]": (
        lambda mesh, steps: astaroth(mesh, steps, stream_halo="fused", exchange_route="yzpack_xla",
                                     want={"halo": "fused"}),
        (2, 2, 2), 3),
    "astaroth-per-step[2,2,1]": (
        lambda mesh, steps: astaroth(mesh, steps, schedule="per-step", want={"route": "plane"}),
        (2, 2, 1), 2),
    "jacobi-zring[2,2,1]": (
        lambda mesh, steps: jacobi(mesh, steps, want={"_wavefront_z_ring": True}), (2, 2, 1), 8),
    "jacobi-zring[1,1,4]": (
        lambda mesh, steps: jacobi(mesh, steps, extent=(32, 32, 512), want={"_wavefront_z_ring": True}),
        (1, 1, 4), 9),
    "jacobi-zslab[2,2,1]": (
        lambda mesh, steps: jacobi(mesh, steps, z_ring=False,
                                   want={"_wavefront_z_ring": False, "_wavefront_z_slabs": True}),
        (2, 2, 1), 8),
    "jacobi-slab[2,2,1]": (lambda mesh, steps: jacobi(mesh, steps, "slab", pallas_path="slab"), (2, 2, 1), 3),
    "jacobi-shell[2,2,1]": (lambda mesh, steps: jacobi(mesh, steps, "shell", pallas_path="shell"),
                            (2, 2, 1), 3),
    "jacobi-ragged[2,2,1]": (
        lambda mesh, steps: jacobi(mesh, steps, extent=(19, 21, 16), want={"_wavefront_z_slabs": False}),
        (2, 2, 1), 3),
    "jacobi-jnp[2,2,1]": (
        lambda mesh, steps: jacobi(mesh, steps, None, extent=(16, 16, 16), kernel_impl="jnp"),
        (2, 2, 1), 3),
    "xla-mult2[2,2,1]": (xla_engine, (2, 2, 1), 3),
    "xla-mult2[2,2,2]": (xla_engine, (2, 2, 2), 2),
    "xla-zpack[2,2,2]": (lambda mesh, steps: xla_engine(mesh, steps, route="zpack_xla"), (2, 2, 2), 2),
}
#: the axes whose sweep a case's exchange PACKS (every other case packs none)
PACKED = {"astaroth-fused[2,2,2]": "yz", "xla-zpack[2,2,2]": "z"}
#: the pair of wired axes a case's exchanges sweep JOINTLY: x and y wherever
#: both are split and ``halo_exchange_multi`` sweeps them (the relay behind the
#: y faces is in ``traced_hops`` like any other ``ppermute``); every other
#: case's sweeps run in turn -- one wired axis, the fused shell exchange, the
#: bespoke slab step's own permutes
JOINT = {case: "xy" for case in (
    "acoustic-plane[2,2,1]", "elastic-staged[2,2,1]", "mhd-staged[2,2,1]", "lbm[2,2,1]",
    "astaroth-zslabs[2,2,1]", "astaroth-split[2,2,2]", "astaroth-per-step[2,2,1]",
    "jacobi-zring[2,2,1]", "jacobi-zslab[2,2,1]", "jacobi-shell[2,2,1]", "jacobi-ragged[2,2,1]",
    "jacobi-jnp[2,2,1]",
    "xla-mult2[2,2,1]", "xla-mult2[2,2,2]", "xla-zpack[2,2,2]",
)}


def packed_counters() -> tuple:
    counters = telemetry.snapshot()["counters"]
    return counters[tm.EXCHANGE_PACKED_BYTES], counters[tm.EXCHANGE_PACKED_KERNELS]


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_hop_counters_move_by_the_bytes_of_the_steps_ppermutes(case):
    build, mesh, steps = CASES[case]
    dd, step, closed, dispatch = build(mesh, steps)
    sent = traced_hops(closed, mesh)
    assert all(mesh[MESH_AXES.index(axis)] > 1 for axis, _ in sent), sent
    assert (sum(sent.values()) > 0) == (int(np.prod(mesh)) > 1), sent
    before, packed = hop_counters(), packed_counters()
    exchanges = telemetry.snapshot()["counters"][tm.EXCHANGE_COUNT]
    total = telemetry.snapshot()["counters"][tm.EXCHANGE_BYTES]
    joint_sweeps = telemetry.snapshot()["counters"][tm.EXCHANGE_JOINT_SWEEPS]
    (said,) = spied_spans(dispatch, tm.SPAN_STEP)
    n_sub = dd.num_subdomains()
    # hop by hop, and nothing on an axis the mesh does not split
    assert moved(before, hop_counters()) == {hop: nb * n_sub for hop, nb in sent.items()}
    after = telemetry.snapshot()["counters"]
    assert after[tm.EXCHANGE_BYTES] - total == sum(sent.values()) * n_sub
    # the span and the counters read one declaration
    account = step._wire_account()
    raw = said["steps"]
    assert after[tm.EXCHANGE_COUNT] - exchanges == account.units(raw) * account.exchanges > 0
    assert said["wired"] == "".join(a for a in MESH_AXES if any(axis == a for axis, _ in sent))
    # a z-slab step also says on which axes its slab extension is the self-wrap kernel: on
    # none with the blend kernels off, as here (ISSUE 56; on: tests/test_slab_step.py)
    assert said.get("slab_wrap") == ("" if "z_halo_patch" in said else None)
    if raw % account.every == 0:  # whole macros: the per-step figure is exact
        assert said["wire_bytes"] * raw == sum(sent.values()), (said, sent)
    # the sweeps that flew jointly: said on the span, counted from the same account
    assert said["joint"] == account.joint[0] == JOINT.get(case, "")
    assert after[tm.EXCHANGE_JOINT_SWEEPS] - joint_sweeps == (
        account.units(raw) * account.joint[1] * n_sub)
    assert (account.joint[1] > 0) == bool(account.joint[0])
    # a packed sweep on a split axis packs the messages it sends, two kernels
    # (pack, unpack) a quantity a side; no other sweep packs anything
    axes = PACKED.get(case, "")
    assert dd.exchange_route() != "direct" or not axes
    on_packed = sum(nb for (axis, _), nb in sent.items() if axis in axes)
    n_exchanges = after[tm.EXCHANGE_COUNT] - exchanges
    assert tuple(b - a for a, b in zip(packed, packed_counters())) == (
        on_packed * n_sub, 2 * len(dd._handles) * 2 * len(axes) * n_exchanges * n_sub)


def test_the_scratch_case_of_the_issue_reads_a_quarter_of_what_it_read():
    """``AcousticWave(48, 48, 32, nbl=4)`` on mesh [2,2,1], two steps: four
    faces of 20,480 B and, behind each y face, the corner relay of the joint
    x-y sweep (both x halos' four y rows, 8 x 4 x 40 cells: 5,120 B) a shard a
    step on the span; 163,840 B an x hop and 204,800 B a y hop over four shards
    and both steps -- the domain-wide model charged four quantities, 655,360 a
    hop."""
    dd, step, _, dispatch = acoustic((2, 2, 1), 2)
    before = hop_counters()
    dispatch()
    assert step._span_args()["wire_bytes"] == 4 * 20_480 + 2 * 5_120
    assert step._span_args()["joint"] == "xy"
    assert moved(before, hop_counters()) == {
        (axis, side): 163_840 + (40_960 if axis == "y" else 0)
        for axis in "xy" for side in ("low", "high")
    }


def _exchange_domain(extent, mesh, route=None, components=0):
    dd = DistributedDomain(*extent)
    dd.set_radius(Radius.constant(3))
    dd.set_devices(_devices(mesh))
    dd.set_partition(*mesh)
    if route is not None:
        dd.set_exchange_route(route)
    dd.add_data("a", dtype=jnp.float32)
    dd.add_data("b", dtype=jnp.float64)
    if components:
        dd.add_data("c", dtype=jnp.float32, components=(components,))
    dd.realize()
    return dd


@pytest.mark.parametrize("case,extent,mesh,kw", [
    ("even[2,2,1]", (16, 16, 16), (2, 2, 1), {}),
    ("ragged[2,2,1]", (19, 21, 16), (2, 2, 1), {}),
    ("ragged[2,2,2]", (19, 21, 17), (2, 2, 2), {}),
    ("vector-field[2,1,1]", (16, 16, 16), (2, 1, 1), {"components": 3}),
    ("zpack[2,2,2]", (16, 16, 16), (2, 2, 2), {"route": "zpack_xla"}),
    ("yzpack[2,2,2]", (16, 16, 16), (2, 2, 2), {"route": "yzpack_xla"}),
    # the joint sweep's relay is a hop with bytes: y and z behind an unsplit x,
    # four shards along x, a ragged pair; x and z with y between them fly in turn
    ("even[1,2,2]", (16, 16, 16), (1, 2, 2), {"joint": "yz"}),
    ("even[4,2,1]", (32, 16, 16), (4, 2, 1), {}),
    ("ragged[4,2,1]", (33, 21, 16), (4, 2, 1), {}),
    ("even[2,1,2]", (16, 16, 16), (2, 1, 2), {"joint": ""}),
])
def test_exchange_counts_its_own_ppermutes(case, extent, mesh, kw):
    """``dd.exchange()`` and ``exchange_many()``, even and ragged: the hop
    counters against the traced exchange, the span's ``wire_bytes`` the
    per-shard sum, ``exchange_hop_bytes()`` (the drivers' table) the same
    numbers with the unsplit hops at 0.  On the packed routes the padded
    message is what travels and what is counted."""
    # x and y fly jointly wherever both are split and neither is packed
    joint = kw.pop("joint", "" if mesh[:2] != (2, 2) and mesh[:2] != (4, 2)
                   or kw.get("route") == "yzpack_xla" else "xy")
    dd = _exchange_domain(extent, mesh, **kw)
    assert (dd.exchange_route() == kw.get("route", dd.exchange_route())), dd.exchange_route()
    sent = traced_hops(jax.make_jaxpr(dd._exchange_fn)(dd._curr), mesh)
    n_sub = dd.num_subdomains()
    assert dd.exchange_hop_bytes() == {hop: sent.get(hop, 0) * n_sub for hop in HOPS}
    before, packed, total = hop_counters(), packed_counters(), telemetry.snapshot()["counters"][tm.EXCHANGE_BYTES]
    joint_sweeps = telemetry.snapshot()["counters"][tm.EXCHANGE_JOINT_SWEEPS]
    (said,) = spied_spans(dd.exchange, tm.SPAN_EXCHANGE)
    assert moved(before, hop_counters()) == {hop: nb * n_sub for hop, nb in sent.items()}
    assert said["wire_bytes"] == sum(sent.values()) and said["count"] == 1
    assert said["joint"] == joint
    assert telemetry.snapshot()["counters"][tm.EXCHANGE_JOINT_SWEEPS] - joint_sweeps == (
        n_sub if joint else 0)
    # ``domain.exchange.bytes`` is the sum of the hops here as for a step; the
    # analytic figure (every shell cell) is the span's ``nbytes``
    assert telemetry.snapshot()["counters"][tm.EXCHANGE_BYTES] - total == sum(sent.values()) * n_sub
    assert said["nbytes"] == dd.exchange_bytes_total() != sum(sent.values()) * n_sub
    axes = {"zpack_xla": "z", "yzpack_xla": "yz"}.get(kw.get("route"), "")
    assert packed_counters()[0] - packed[0] == n_sub * sum(
        nb for (axis, _), nb in sent.items() if axis in axes)
    before = hop_counters()
    (said,) = spied_spans(lambda: dd.exchange_many(3), tm.SPAN_EXCHANGE)
    assert moved(before, hop_counters()) == {hop: 3 * nb * n_sub for hop, nb in sent.items()}
    assert said["wire_bytes"] == sum(sent.values()) and said["count"] == 3


def test_a_step_that_declares_nothing_falls_back_on_the_domains_model():
    """A caller's own step callable: one exchange of every quantity a macro,
    as before -- the fallback, and the only place the model is charged to a
    step."""
    dd = _exchange_domain((16, 16, 16), (2, 2, 1))
    before, count = hop_counters(), telemetry.snapshot()["counters"][tm.EXCHANGE_COUNT]
    dd.run_step(lambda curr, steps: curr, 5)
    assert telemetry.snapshot()["counters"][tm.EXCHANGE_COUNT] - count == 5
    assert moved(before, hop_counters()) == {
        hop: 5 * nb for hop, nb in dd.exchange_hop_bytes().items() if nb
    }


def test_an_account_says_the_axes_and_the_sum_of_its_hops():
    radius = Radius.constant(2)
    hops = ex.exchange_account((2, 1, 2), radius, (20, 20, 20), [jnp.float32, jnp.float64]).hops
    assert sorted(hops) == [("x", "high"), ("x", "low"), ("z", "high"), ("z", "low")]
    assert ex.WireAccount(1, hops).said() == ("xz", sum(hops.values()))
    assert ex.exchange_account((1, 1, 1), radius, (20, 20, 20), [jnp.float32]) == ex.WireAccount(1, {})
    account = ex.WireAccount(1, hops, every=4)
    assert account.units(8) == 2 and account.units(9) == 3
    assert account.said() == ("xz", sum(hops.values()) // 4)
    # a packed z sweep on an UNSPLIT axis packs its own wrap: packed traffic, no hop
    one = ex.exchange_account((2, 1, 1), radius, (20, 20, 20), [jnp.float32], route="zpack_xla")
    assert sorted(one.hops) == [("x", "high"), ("x", "low")]
    assert one.packed == ex.zpack_message_stats((20, 20, 20), 2, 2, [4]) and one.packed[1] == 4
    both = ex.sum_accounts([one, one], every=3)
    assert (both.exchanges, both.every, both.packed) == (2, 3, (2 * one.packed[0], 8))
    assert both.hops == {hop: 2 * nb for hop, nb in one.hops.items()}
    assert one.joint == ("", 0) == both.joint and one.span_args()["joint"] == ""
    # two wired sweeps in a row: the second axis's hops carry the corner relay,
    # both received slabs of the first on the side's rows, the third axis whole
    pair = ex.exchange_account((2, 2, 1), radius, (20, 20, 20), [jnp.float32, jnp.float64])
    face, relay = 2 * 20 * 20 * 12, (2 + 2) * 2 * 20 * 12
    assert pair.hops == {("x", "low"): face, ("x", "high"): face,
                         ("y", "low"): face + relay, ("y", "high"): face + relay}
    assert pair.joint == ("xy", 1) and pair.span_args() == {
        "wired": "xy", "wire_bytes": 4 * face + 2 * relay, "joint": "xy"}
    assert ex.sum_accounts([pair, one, pair]).joint == ("xy", 2)
    assert ex.exchange_account((1, 2, 2), radius, (20, 20, 20), [jnp.float32]).joint == ("yz", 1)
