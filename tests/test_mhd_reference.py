"""``models/astaroth_mhd_reference.py`` against the physics, no program run:
every single term switched off is seen at the benchmark's coefficients, the
difference operators are sixth order and the integrator third, a box is one
side or three.  Split out of ``tests/test_astaroth_mhd.py`` (ISSUE 55)."""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from test_astaroth_mhd import WORDS, _config

from stencil_tpu.models import astaroth_mhd_reference as ref


def test_a_box_is_one_side_or_three():
    """A float keeps its meaning (every axis that side: the one-chip cell's
    set-up, its program and its fingerprint do not move); a triple is a side
    an axis, hashable as the float is (``_substeps`` caches on the set-up)."""
    cube = ref.MhdSetup((8, 16, 32))
    assert cube.box == 2.0 * np.pi and cube.sides == (cube.box,) * 3
    assert cube.spacing == (cube.box / 8, cube.box / 16, cube.box / 32)
    wide = ref.MhdSetup((8, 16, 32), box=[1.0, 2, 4.0])
    assert wide.box == wide.sides == (1.0, 2.0, 4.0) and wide.spacing == (0.125,) * 3
    assert hash(wide) == hash(ref.MhdSetup((8, 16, 32), box=(1.0, 2.0, 4.0)))
    assert ref.dt_of(wide) == wide.courant * 0.125 / (wide.cs0 + np.sqrt(3.0) * wide.amplitude)
    with pytest.raises(ValueError, match="one side or one an axis"):
        ref.MhdSetup((8, 8, 8), box=(1.0, 2.0))


# --- every term is seen -------------------------------------------------------------------


@pytest.mark.parametrize("term", ["nu", "eta", "chi", "zeta", "lorentz", "pressure", "advection"])
def test_every_single_term_moves_the_state_far_beyond_the_limit(term):
    """The benchmark's coefficients, fixed ``dt`` and dispatch, its seeded
    state, on the same box at 24^3 (the terms are those of the low modes: what
    they add up to over the dispatch's TIME is the same on any grid that
    resolves them): the update with ONE term switched off against the full one
    differs by more than 100 times the cell's ``max_abs_err`` after one
    dispatch's worth of steps, so a program that skipped it would not be
    ``correct``.  The update is ``astaroth_mhd_reference.substep``, the one the
    program's kernels run (held to each other above), on whole arrays: a
    seventh of the compiles the program would take (the benchmark's rehearsal
    switches a term off in the program itself, tests/test_bench_mhd.py)."""
    config = _config()
    s = config["setup"]
    full = ref.MhdSetup(
        (24, 24, 24), nu=s["nu"], eta=s["eta"], chi=s["chi"], zeta=s["zeta"], gamma=s["gamma"],
        cp=s["cp"], cs0=s["cs0"], mu0=s["mu0"], lnrho0=s["lnrho0"], lnT0=s["lnT0"], box=s["box"],
        dt=s["dt"], amplitude=s["amplitude"], modes=s["modes"], max_waves=s["max_waves"],
    )
    off = {term: 0.0} if term in ("nu", "eta", "chi", "zeta") else {"off": (term,)}
    steps = config["dispatch"]["bulk"]
    state = ref.global_fields(full, np.asarray(WORDS, dtype=np.uint32))
    want = ref.steps(full, state, steps)
    got = ref.steps(dataclasses.replace(full, **off), state, steps)
    worst = max(float(jnp.abs(got[q] - want[q]).max()) for q in ref.QUANTITIES)
    assert worst > 100 * config["limits"]["max_abs_err"], (term, worst)
    assert all(bool(jnp.isfinite(want[q]).all()) for q in ref.QUANTITIES)


def test_unknown_terms_are_refused():
    with pytest.raises(ValueError, match="unknown terms"):
        ref.MhdSetup((8, 8, 8), off=("gravity",))


# --- the operators and the integrator ---------------------------------------------------


def _sine_error(which: str, n: int) -> float:
    """max error of one difference of ``sin(2x + y - z + 0.3)`` on ``n^3``
    cells of the 2 pi box, in float64."""
    h = 2.0 * np.pi / n
    x = np.arange(n) * h
    arg = 2.0 * x[:, None, None] + x[None, :, None] - x[None, None, :] + 0.3
    f = {"f": np.sin(arg)}
    taps = ref.Taps(lambda q, dx, dy, dz: np.roll(f[q], (-dx, -dy, -dz), (0, 1, 2)))
    if which == "first":
        return float(np.abs(ref.der1(taps, "f", 0, 1.0 / h) - 2.0 * np.cos(arg)).max())
    if which == "second":
        return float(np.abs(ref.der2(taps, "f", 1, 1.0 / h) + np.sin(arg)).max())
    mixed = ref.der_mixed(taps, "f", 0, 2, 1.0 / h, 1.0 / h)  # d_x d_z = +2 sin
    return float(np.abs(mixed - 2.0 * np.sin(arg)).max())


@pytest.mark.parametrize("which", ["first", "second", "mixed"])
def test_the_differences_are_sixth_order(which):
    coarse, fine = _sine_error(which, 32), _sine_error(which, 64)
    assert fine < 1e-5 and 45.0 < coarse / fine < 80.0, (coarse, fine)  # 2^6 = 64


def test_the_two_buffer_runge_kutta_is_third_order():
    """``y' = -y`` over one time unit in Astaroth's two-buffer form: halving
    the step cuts the error eightfold, and the second buffer holds the value
    before the last substep."""

    def integrate(steps):
        h, cur, prev = 1.0 / steps, 1.0, 1.0
        for _ in range(steps):
            for s, (ratio, beta) in enumerate(ref.COEFFS):
                cur, prev = ref.two_buffer(cur, prev if s else None, h * -cur, ratio, beta), cur
        return cur, prev

    errors = [abs(integrate(n)[0] - np.exp(-1.0)) for n in (10, 20, 40)]
    assert 7.0 < errors[0] / errors[1] < 9.0 and 7.0 < errors[1] / errors[2] < 9.0, errors
    cur, prev = integrate(10)
    assert prev != cur and abs(prev - cur) < 0.1
    assert ref.ALPHA[0] == 0.0 and abs(sum(ref.BETA[s] for s in range(3)) - 1.8041666) < 1e-6
