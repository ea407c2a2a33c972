"""The jacobi macro loops run two macros a ``fori_loop`` trip (ISSUE 38).

``jacobi_wrap_step`` and the wavefront kernels write a FRESH result, and a
``while`` wants its carry back in the buffer it came in: with one call a trip
XLA copies a whole block every trip.  ``models/jacobi.py _macro_loop`` runs as
many macros a trip as it takes for the carry to come home -- 2 for a fresh
result, 1 in place -- and these tests hold it to a one-a-trip control built
from the same kernels, bitwise, on every raw cell, for every split of
``steps`` into trips, a macro behind the loop and a ``steps % k`` remainder.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import seeded_blocks

from stencil_tpu import telemetry
from stencil_tpu.models import jacobi as jm
from stencil_tpu.models.jacobi import Jacobi3D
from stencil_tpu.telemetry import names as tm

K = 2  # temporal depth of every build here: a macro is two raw steps

# the four loops of models/jacobi.py: "wrap" is _make_pallas_step's, "ring"
# macro_ring, "zslab" macro and "plain" macro_plain of _make_wavefront_step
CASES = [("wrap", (1, 1, 1))] + [
    (route, mesh)
    for route in ("ring", "zslab", "plain")
    for mesh in ((2, 1, 1), (2, 2, 1), (2, 2, 2))
]


# what ``domain.step`` says beside ``macros_per_trip``: the z-slab routes patch
# their z halo inside its lane tiles on these lane-aligned planes (ISSUE 40),
# and no slab extension of theirs is a self-wrap with the blend kernels off
# (ISSUE 56: tests/test_slab_step.py has them on)
Z_HALO_PATCH = {"ring": {"z_halo_patch": "tile", "slab_wrap": ""},
                "zslab": {"z_halo_patch": "tile", "slab_wrap": ""}}


def _seeded(x, y, z):
    return (jnp.sin(12.9898 * x + 78.233 * y + 37.719 * z) * 0.5 + 0.5).astype(jnp.float32)


def _build(route, mesh, monkeypatch, per_trip=None, **kw):
    """``Jacobi3D`` at depth ``K`` on ``route`` over ``mesh``, 8 x 8 cells a
    shard in x and y; ``per_trip`` forces the macros a trip (the control)."""
    if per_trip is not None:
        monkeypatch.setattr(jm, "_macros_per_trip", lambda in_place: per_trip)
    monkeypatch.delenv("STENCIL_Z_RING", raising=False)
    monkeypatch.delenv("STENCIL_WAVEFRONT_ALIAS", raising=False)
    if route == "plain":
        monkeypatch.setenv("STENCIL_Z_SLABS", "0")
    else:
        monkeypatch.delenv("STENCIL_Z_SLABS", raising=False)
    devices = jax.devices()[: mesh[0] * mesh[1] * mesh[2]]
    if route == "wrap":
        sim = Jacobi3D(8, 8, 8, kernel_impl="pallas", interpret=True, devices=devices,
                       temporal_k=K, **kw)
    else:
        z = 8 if route == "plain" else 128  # the ring wants a lane-aligned z interior
        if route == "zslab":
            kw.setdefault("z_ring", False)
        sim = Jacobi3D(8 * mesh[0], 8 * mesh[1], z * mesh[2], kernel_impl="pallas",
                       interpret=True, devices=devices, pallas_path="wavefront",
                       temporal_k=K, **kw)
        sim.dd.set_partition(*mesh)
    sim.realize()
    sim.dd.init_by_coords(sim.h, _seeded)
    if route == "wrap":
        assert sim._pallas_path == "wrap" and sim._wrap_k == K
    else:
        assert sim._pallas_path == "wavefront" and sim._wavefront_m == K
        assert (sim._wavefront_z_ring, sim._wavefront_z_slabs) == {
            "ring": (True, True), "zslab": (False, True), "plain": (False, False),
        }[route]
    return sim


def _raw(sim):
    return np.asarray(sim.dd._curr[sim.h.name])


def _said(sim) -> dict:
    """The step's span arguments but the wires' (``wired`` / ``wire_bytes``,
    ISSUE 49, and ``joint``, ISSUE 50: ``tests/test_wire_account.py`` holds
    those to the program)."""
    return {k: v for k, v in sim._step._span_args().items() if k not in ("wired", "wire_bytes", "joint")}


_BUILT = {}


def _shared(route, mesh, monkeypatch, per_trip=None):
    """One realized build a (route, mesh, macros a trip) for every case, its
    seeded raw blocks put back for the case that asks: a case then traces the
    one program of ITS step count."""
    key = (route, mesh, per_trip)
    if key not in _BUILT:
        with pytest.MonkeyPatch.context() as mp:
            sim = _build(route, mesh, mp, per_trip=per_trip)
        _BUILT[key] = (sim, seeded_blocks.snapshot(sim.dd))
    if route == "plain":  # (the route's switch, as ``_build`` had it while it built)
        monkeypatch.setenv("STENCIL_Z_SLABS", "0")
    sim, blocks = _BUILT[key]
    seeded_blocks.restore(sim.dd, blocks)
    return sim


@pytest.mark.parametrize("macros,rem", [(m, r) for m in range(6) for r in (0, 1) if m or r])
@pytest.mark.parametrize("route,mesh", CASES, ids=[f"{r}-{'x'.join(map(str, m))}" for r, m in CASES])
def test_two_macros_a_trip_is_bitwise_one_a_trip(route, mesh, macros, rem, monkeypatch):
    """Every raw cell (shell included) after one dispatch of ``macros`` whole
    macros and ``rem`` steps more, and after a second such dispatch, against
    the same kernels with no loop around them at all: the control build (one
    macro a trip) dispatched a macro at a time and then the remainder, so its
    two programs a (route, mesh) serve every step count (ISSUE 55; it ran
    ``steps`` in one dispatch before, a program a case, and on the z-slab
    route it still does).  Trips, the odd macro behind the loop and the
    remainder run the same kernel calls in the same order.  Three of the
    eleven counts run as two dispatches (``_as_a_second_trip``)."""
    steps = macros * K + rem
    two = _shared(route, mesh, monkeypatch)
    one = _shared(route, mesh, monkeypatch, per_trip=1)
    assert _said(two) == {"macros_per_trip": 2, **Z_HALO_PATCH.get(route, {})}
    assert _said(one) == {"macros_per_trip": 1, **Z_HALO_PATCH.get(route, {})}
    seeded = _raw(two)
    np.testing.assert_array_equal(seeded, _raw(one))
    dispatches = _as_a_second_trip(two, macros, rem) or (steps,)
    for _ in range(2):
        for n in dispatches:
            two.step(n)
        if route == "zslab":
            # its z halo rides in the slab carry: the block's z-shell columns hold
            # what the kernels left there since the DISPATCH began, so the control
            # must begin and end where the dispatch does (a program a dispatch)
            for n in dispatches:
                one.step(n)
        else:
            for _ in range(macros):
                one.step(K)
            if rem:
                one.step(rem)
        np.testing.assert_array_equal(_raw(two), _raw(one))
    assert not np.array_equal(_raw(two), seeded)


def _as_a_second_trip(sim, macros, rem):
    """``(4, 0)``, ``(4, 1)`` and ``(5, 0)`` are the programs of ``(2, 0)``,
    ``(2, 1)`` and ``(3, 0)`` with TWO trips on the loop and nothing else
    changed: the traced programs are held to exactly that (tracing lowers
    nothing), and the case runs as two dispatches whose programs those cases
    lower anyway -- one trip, then what is left.  ``(5, 1)`` stays ONE dispatch:
    two trips, a macro behind the loop and the remainder.  None for every other
    count."""
    from program_fingerprint import step_loop_and_text

    if macros < 4 or (macros, rem) == (5, 1):
        return None
    rest = (macros - 2) * K + rem
    (trips, text), (trips_rest, text_rest) = (
        step_loop_and_text(jax.make_jaxpr(sim._step, static_argnums=1)(sim.dd._curr, n))
        for n in (macros * K + rem, rest))
    assert (trips, trips_rest) == (2, 1) and text == text_rest
    return (2 * K, rest)


def _stencil_calls(jaxpr):
    from stencil_tpu.analysis import jaxpr as jx

    return [
        e for e in jx.iter_eqns(jaxpr)
        if e.primitive.name == "pallas_call" and str(e.params.get("name")).startswith("jacobi_")
    ]


@pytest.mark.parametrize("macros,rem", [(4, 0), (5, 1), (1, 0), (2, 1)])
@pytest.mark.parametrize("route,mesh", [("wrap", (1, 1, 1)), ("ring", (2, 2, 1)), ("zslab", (2, 1, 1)),
                                        ("plain", (2, 2, 2))])
def test_a_trip_holds_two_kernel_calls(route, mesh, macros, rem, monkeypatch):
    """The traced program: ``macros // 2`` trips of TWO stencil calls, the odd
    macro and the remainder as calls of their own behind the loop -- and no
    call aliases an operand (``pallas_hbm_pct`` reads only fresh results)."""
    from stencil_tpu.analysis import jaxpr as jx

    sim = _build(route, mesh, monkeypatch)
    closed = jax.make_jaxpr(sim._step, static_argnums=1)(sim.dd._curr, macros * K + rem)
    calls = _stencil_calls(closed)
    trips = macros // 2
    assert len(calls) == (2 if trips else 0) + macros % 2 + bool(rem)
    loops = [e for e in jx.iter_eqns(closed) if e.primitive.name == "scan"]
    in_loops = [(e.params["length"], len(_stencil_calls(e.params["jaxpr"]))) for e in loops]
    assert [lc for lc in in_loops if lc[1]] == ([(trips, 2)] if trips else [])
    for eqn in calls:
        assert not eqn.params["input_output_aliases"], eqn.params["input_output_aliases"]


def _parent_loop(macro, macros, carry, per_trip):
    """The loop every jacobi route ran before ISSUE 38."""
    from jax import lax

    return lax.fori_loop(0, macros, lambda _, c: macro(c), carry)


@pytest.mark.parametrize("route,mesh", [("ring", (2, 2, 1)), ("zslab", (2, 1, 1)), ("plain", (2, 2, 2))])
def test_an_in_place_kernel_keeps_the_parents_program(route, mesh, monkeypatch):
    """``alias`` resolved true: the kernel hands the carry back where it came
    in, a trip is ONE macro, and the traced program is -- by its fingerprint
    -- the one the parent's ``fori_loop`` traces."""
    from program_fingerprint import fingerprint

    def traced(alias):
        sim = _build(route, mesh, monkeypatch, wavefront_alias=alias)
        assert _said(sim) == {"macros_per_trip": 1 if alias else 2, **Z_HALO_PATCH.get(route, {})}
        return fingerprint(jax.make_jaxpr(sim._step, static_argnums=1)(sim.dd._curr, 3 * K + 1))

    ours, fresh = traced(True), traced(False)
    monkeypatch.setattr(jm, "_macro_loop", _parent_loop)
    assert traced(True) == ours
    assert traced(False) != fresh  # un-aliased they differ: the check can see what it guards


def _mean6(b, **kw):
    """``jacobi_wrap_step`` stood in for by plain ``jnp``: a kernel that reads
    its neighbours cannot run in place, so its result is fresh -- as the
    Pallas call's is on the chip (the interpreted call lowers to loops that
    copy blocks whatever the program around them does, so its CPU HLO says
    nothing: ``tests/test_acoustic.py`` stands its pass in the same way)."""
    return sum(jnp.roll(b, s, ax) for ax in range(3) for s in (1, -1)) / 6.0


def _stand_in_program(steps, monkeypatch, per_trip=None):
    from stencil_tpu.ops import jacobi_pallas

    monkeypatch.setattr(jacobi_pallas, "jacobi_wrap_step", _mean6)
    if per_trip is not None:
        monkeypatch.setattr(jm, "_macros_per_trip", lambda in_place: per_trip)
    sim = Jacobi3D(32, 32, 32, kernel_impl="pallas", interpret=True,
                   devices=jax.devices()[:1], temporal_k=K)
    sim.realize()
    sim.dd.init_by_coords(sim.h, _seeded)
    want = np.asarray(sim.temperature())
    for _ in range(steps // K + steps % K):  # the stand-in advances one level a call
        want = np.asarray(_mean6(jnp.asarray(want)))
    text = sim._step.lower(sim.dd._curr, steps).compile().as_text()
    sim.step(steps)
    np.testing.assert_allclose(sim.temperature(), want, rtol=1e-6, atol=1e-6)
    return text


def test_an_even_count_compiles_to_a_loop_without_a_block_copy(monkeypatch):
    """The compiled (CPU) program of an even macro count, through the REAL
    step builder and loop helper: no whole-block ``copy`` inside the ``while``
    body -- and the same step with ONE macro a trip has one: the check can see
    what it guards."""
    from test_acoustic import _block_copies_in_loops

    shape = "f32[32,32,32]"
    assert _block_copies_in_loops(_stand_in_program(8 * K, monkeypatch), shape) == 0
    assert _block_copies_in_loops(_stand_in_program(8 * K, monkeypatch, per_trip=1), shape) > 0


def test_an_odd_count_keeps_the_loop_clean(monkeypatch):
    """Nine macros and a remainder: four trips, one macro and the remainder
    behind the loop; whatever XLA copies, it copies at the program's edge."""
    from test_acoustic import _block_copies_in_loops

    assert _block_copies_in_loops(_stand_in_program(9 * K + 1, monkeypatch), "f32[32,32,32]") == 0


@pytest.mark.parametrize("route,mesh,alias,want", [
    ("wrap", (1, 1, 1), None, 2),
    ("ring", (2, 2, 1), None, 2),
    ("ring", (2, 2, 1), True, 1),
    ("zslab", (2, 1, 1), None, 2),
    ("plain", (2, 1, 1), True, 1),
])
def test_the_step_span_says_the_macros_a_trip(route, mesh, alias, want, monkeypatch):
    """``domain.step`` carries ``macros_per_trip`` (registered under
    ``SPAN_STEP``): the counter that says the mechanism engaged -- and, on
    the z-slab routes alone, ``z_halo_patch`` (ISSUE 40)."""
    kw = {} if alias is None else {"wavefront_alias": alias}
    sim = _build(route, mesh, monkeypatch, **kw)
    seen = []
    real = telemetry.span

    def spy(name, *a, **kw):
        seen.append((name, kw))
        return real(name, *a, **kw)

    monkeypatch.setattr(telemetry, "span", spy)
    sim.step(2 * K)
    (kw,) = [kw for name, kw in seen if name == tm.SPAN_STEP]
    assert kw["label"] == "jacobi" and kw["steps"] == 2 * K and kw["macros_per_trip"] == want
    assert kw.get("z_halo_patch") == Z_HALO_PATCH.get(route, {}).get("z_halo_patch")


def test_the_counter_is_registered_and_the_names_lint_passes():
    import inspect

    from stencil_tpu import lint

    registered = inspect.getsource(tm).split('SPAN_STEP = "domain.step"')[0]
    assert "macros_per_trip" in registered and "z_halo_patch" in registered
    assert lint.run_lint(select=["telemetry-name"]) == []
