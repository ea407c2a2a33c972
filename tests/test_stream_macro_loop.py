"""The stream engine's wrap route runs two macros a ``fori_loop`` trip (ISSUE 39).

``stream_wrap_pass`` writes FRESH results, and a ``while`` wants its carry back
in the buffers it came in: with one call a trip XLA copies every quantity's
whole block every trip (nineteen 67 MB copies a macro in the lattice-Boltzmann
step compiled at 256^3, as much as the pass itself moves).  ``ops/stream.py
macro_loop`` -- the loop ``models/jacobi.py`` got in ISSUE 38 -- runs as many
macros a trip as it takes for the carry to come home, and these tests hold the
wrap route to a one-a-trip control built from the same kernel, bitwise, on
every raw cell, for every split of ``steps`` into trips, a macro behind the
loop and a ``steps % m`` remainder."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import seeded_blocks

from stencil_tpu import DistributedDomain, Radius
from stencil_tpu.ops import stream_plan as sp

M = 2  # temporal depth of every build here: a macro is two raw steps
N = 8


def _mean6(views, info):
    out = {}
    for name, v in views.items():
        out[name] = (
            ((v.sh(1, 0, 0) + v.sh(-1, 0, 0)) + (v.sh(0, 1, 0) + v.sh(0, -1, 0)))
            + (v.sh(0, 0, 1) + v.sh(0, 0, -1))
        ) / 6.0
    return out


def _coupled(views, info):
    """Two quantities, each output reading BOTH, one of them diagonally."""
    a, b = views["q0"], views["q1"]
    return {
        "q0": 0.5 * (a.sh(1, 1, 0) + b.sh(0, -1, -1)),
        "q1": 0.25 * (a.center() + b.sh(-1, 0, 1)) + 0.5 * b.center(),
    }


def _seeded(q):
    def f(x, y, z):
        return (jnp.sin(12.9898 * x + 78.233 * y + 37.719 * z + q) * 0.5 + 0.5).astype(jnp.float32)

    return f


def _build(kernel, nq, monkeypatch, per_trip=None):
    if per_trip is not None:
        monkeypatch.setattr(sp, "macros_per_trip", lambda in_place: per_trip)
    dd = DistributedDomain(N, N, N)
    dd.set_radius(Radius.constant(1))
    dd.set_devices(jax.devices()[:1])
    hs = [dd.add_data(f"q{q}") for q in range(nq)]
    dd.realize()
    for q, h in enumerate(hs):
        dd.init_by_coords(h, _seeded(q))
    step = dd.make_step(kernel, engine="stream", x_radius=1, interpret=True, stream_depth=M)
    plan = step._stream_plan
    assert (plan["route"], plan["m"]) == ("wrap", M), plan
    return dd, step


def _raw(dd):
    return [np.asarray(dd._curr[h.name]) for h in dd._handles]


_BUILT = {}


def _shared(kernel, nq, per_trip=None):
    """One build a (kernel, macros a trip), its seeded raw blocks put back for
    the case that asks."""
    key = (kernel.__name__, per_trip)
    if key not in _BUILT:
        with pytest.MonkeyPatch.context() as mp:
            dd, step = _build(kernel, nq, mp, per_trip=per_trip)
        _BUILT[key] = (dd, step, seeded_blocks.snapshot(dd))
    dd, step, blocks = _BUILT[key]
    seeded_blocks.restore(dd, blocks)
    return dd, step


@pytest.mark.parametrize("macros,rem", [(m, r) for m in range(6) for r in (0, 1) if m or r])
@pytest.mark.parametrize("kernel,nq", [(_mean6, 1), (_coupled, 2)], ids=["mean6", "coupled"])
def test_two_macros_a_trip_is_bitwise_one_a_trip(kernel, nq, macros, rem):
    """Every raw cell (shell included) after one dispatch of ``macros`` whole
    macros and ``rem`` steps more, and after a second such dispatch, against
    the same kernel with no loop around it: the one-a-trip build dispatched a
    macro at a time and then the remainder (its two programs serve every step
    count: ISSUE 55, as ``tests/test_jacobi_macro_loop.py``)."""
    steps = macros * M + rem
    two_dd, two = _shared(kernel, nq)
    one_dd, one = _shared(kernel, nq, per_trip=1)
    assert two._span_args()["macros_per_trip"] == 2 and one._span_args()["macros_per_trip"] == 1
    seeded = _raw(two_dd)
    for _ in range(2):
        two_dd.run_step(two, steps)
        for _ in range(macros):
            one_dd.run_step(one, M)
        if rem:
            one_dd.run_step(one, rem)
        for a, b in zip(_raw(two_dd), _raw(one_dd)):
            np.testing.assert_array_equal(a, b)
    assert not all(np.array_equal(a, b) for a, b in zip(_raw(two_dd), seeded))


@pytest.mark.parametrize("macros,rem", [(4, 0), (5, 1), (1, 0), (2, 1)])
def test_a_trip_holds_two_kernel_calls(macros, rem, monkeypatch):
    """The traced program: ``macros // 2`` trips of TWO ``stream_wrap_pass``
    calls, the odd macro and the remainder as calls of their own behind the
    loop, and no call aliases an operand."""
    from stencil_tpu.analysis import jaxpr as jx

    def calls_of(jaxpr):
        return [e for e in jx.iter_eqns(jaxpr) if e.primitive.name == "pallas_call"
                and e.params.get("name") == "stream_wrap_pass"]

    dd, step = _build(_coupled, 2, monkeypatch)
    closed = jax.make_jaxpr(step._resilience.built(), static_argnums=1)(dd._curr, macros * M + rem)
    trips = macros // 2
    assert len(calls_of(closed)) == (2 if trips else 0) + macros % 2 + bool(rem)
    loops = [e for e in jx.iter_eqns(closed) if e.primitive.name == "scan"]
    in_loops = [(e.params["length"], len(calls_of(e.params["jaxpr"]))) for e in loops]
    assert in_loops == ([(trips, 2)] if trips else [])
    assert not any(e.params.get("input_output_aliases") for e in calls_of(closed))
