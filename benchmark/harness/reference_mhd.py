"""The benchmark's own copy of Astaroth's MHD step and of its seeded state
(configuration ``astaroth-mhd-256``).

jax/numpy only: nothing here imports the program under test, and every
number comes from the configuration file (its sizes and its ``setup`` group).
The program may change, this may not.  Written apart from the program's
``models/astaroth_mhd_reference.py`` and in another shape -- whole-array
operators (``grad``, ``curl``, ``laplace``, ``grad_div``) over ``jnp.roll``,
each written out from the formula, nothing shared between them but what XLA
finds -- so that the two agree to rounding and a slip in either shows.

Astaroth's ``acc-runtime/samples/mhd_modular/mhdsolver.ac`` (the Pencil
Code's equations; Comput. Phys. Commun. 217 (2017), arXiv:2103.01597), eight
f32 fields ``lnrho, ux uy uz, ax ay az, ss`` on a periodic box of side ``box``:

    d lnrho/dt = -u.grad lnrho - div u
    du/dt      = -(u.grad)u - cs2 (grad ss / cp + grad lnrho) + (j x B)/rho
                 + nu [lap u + (1/3) grad div u + 2 S.grad lnrho] + zeta grad div u
    dA/dt      = u x B - eta (grad div A - lap A)
    ds/dt      = -u.grad ss + (1/(rho T)) [eta mu0 j.j + 2 rho nu S:S + zeta rho (div u)^2]
                 + cp chi [gamma lap ss / cp + (gamma - 1) lap lnrho
                           + (gamma grad ss/cp + (gamma - 1) grad lnrho)
                             . (gamma (grad ss/cp + grad lnrho))]

    rho = exp(lnrho), B = curl A, j = (grad div A - lap A) / mu0,
    S_ij = (d_i u_j + d_j u_i)/2 - delta_ij div u / 3,
    cs2 = cs0^2 exp(gamma ss/cp + (gamma - 1)(lnrho - lnrho0)),
    lnT = lnT0 + gamma ss/cp + (gamma - 1)(lnrho - lnrho0)

sixth-order central differences (first, second, and the mixed ones in Pencil's
diagonal ``derij`` form), Williamson's RK3 in Astaroth's two-buffer form:

    new = cur + beta_s (alpha_s (cur - prev) / beta_{s-1} + dt F(cur)),
    prev <- cur, cur <- new,   s = 0, 1, 2

``jnp.roll`` on whole global arrays: the box is periodic and nowhere zero, so
every cell of every field is compared and an unfilled halo, edge or corner, a
wrong wrap or a skipped term shows.  The configuration's ``assumed`` group
lists the departures from the source.
"""

from __future__ import annotations

import dataclasses
import functools
import math

FIELDS = ("lnrho", "ux", "uy", "uz", "ax", "ay", "az", "ss")
QUANTITIES = FIELDS + tuple(f + "_prev" for f in FIELDS)
ALPHA = (0.0, -5.0 / 9.0, -153.0 / 128.0)
BETA = (1.0 / 3.0, 15.0 / 16.0, 8.0 / 15.0)


@dataclasses.dataclass(frozen=True)
class Setup:
    """The numbers of one run, from the configuration alone (``setup_from``)."""

    shape: tuple
    nu: float
    eta: float
    chi: float
    zeta: float
    gamma: float
    cp: float
    cs0: float
    mu0: float
    lnrho0: float
    lnT0: float
    box: float
    dt: float
    amplitude: float
    modes: int
    max_waves: int
    envelope: float

    @property
    def spacing(self) -> tuple:
        return tuple(self.box / n for n in self.shape)


def setup_from(config: dict, shape) -> Setup:
    """``shape`` is given apart because a rehearsal shrinks it (``dt`` stays
    the configuration's: fixed, and stable on any coarser grid)."""
    s = config["setup"]
    return Setup(
        shape=tuple(int(n) for n in shape),
        **{k: float(s[k]) for k in ("nu", "eta", "chi", "zeta", "gamma", "cp", "cs0", "mu0",
                                    "lnrho0", "lnT0", "box", "dt", "amplitude", "envelope")},
        modes=int(s["modes"]), max_waves=int(s["max_waves"]),
    )


# --- differences on whole periodic arrays -------------------------------------------


def _up(f, axis, k):
    """``f`` read ``k`` cells up ``axis``: the value at x + k comes to x."""
    import jax.numpy as jnp

    return jnp.roll(f, -k, axis)


def d1(f, axis, dx):
    return (
        0.75 * (_up(f, axis, 1) - _up(f, axis, -1))
        - 0.15 * (_up(f, axis, 2) - _up(f, axis, -2))
        + (1.0 / 60.0) * (_up(f, axis, 3) - _up(f, axis, -3))
    ) / dx


def d2(f, axis, dx):
    return (
        (-49.0 / 18.0) * f
        + 1.5 * (_up(f, axis, 1) + _up(f, axis, -1))
        - 0.15 * (_up(f, axis, 2) + _up(f, axis, -2))
        + (1.0 / 90.0) * (_up(f, axis, 3) + _up(f, axis, -3))
    ) / (dx * dx)


def d11(f, a, b, da, db):
    """The mixed difference on the diagonals of the ``(a, b)`` plane."""
    acc = 0.0
    for k, c in ((1, 270.0), (2, -27.0), (3, 2.0)):
        acc = acc + c * (
            _up(_up(f, a, k), b, k) + _up(_up(f, a, -k), b, -k)
            - _up(_up(f, a, k), b, -k) - _up(_up(f, a, -k), b, k)
        )
    return acc / (720.0 * da * db)


def rates(s: Setup, f: dict) -> dict:
    """``dF/dt`` of the eight fields."""
    import jax.numpy as jnp

    h = s.spacing
    grad = lambda q: [d1(q, a, h[a]) for a in range(3)]  # noqa: E731
    laplace = lambda q: d2(q, 0, h[0]) + d2(q, 1, h[1]) + d2(q, 2, h[2])  # noqa: E731
    dot = lambda p, q: p[0] * q[0] + p[1] * q[1] + p[2] * q[2]  # noqa: E731

    def cross(p, q):
        return [p[1] * q[2] - p[2] * q[1], p[2] * q[0] - p[0] * q[2], p[0] * q[1] - p[1] * q[0]]

    def grad_div(v):
        return [
            sum(d2(v[j], i, h[i]) if i == j else d11(v[j], i, j, h[i], h[j]) for j in range(3))
            for i in range(3)
        ]

    lnrho, ss = f["lnrho"], f["ss"]
    u = [f["ux"], f["uy"], f["uz"]]
    a = [f["ax"], f["ay"], f["az"]]
    g_lnrho, g_ss = grad(lnrho), grad(ss)
    du = [grad(c) for c in u]  # du[i][j] = d_j u_i
    da = [grad(c) for c in a]
    div_u = du[0][0] + du[1][1] + du[2][2]
    gd_u = grad_div(u)
    b = [da[2][1] - da[1][2], da[0][2] - da[2][0], da[1][0] - da[0][1]]
    gd_a = grad_div(a)
    mu0_j = [gd_a[i] - laplace(a[i]) for i in range(3)]
    strain = [
        [0.5 * (du[i][j] + du[j][i]) - (div_u / 3.0 if i == j else 0.0) for j in range(3)]
        for i in range(3)
    ]
    thermo = s.gamma * ss / s.cp + (s.gamma - 1.0) * (lnrho - s.lnrho0)
    cs2 = s.cs0 * s.cs0 * jnp.exp(thermo)
    rho = jnp.exp(lnrho)
    temperature = jnp.exp(s.lnT0 + thermo)
    j = [c / s.mu0 for c in mu0_j]
    jxb = cross(j, b)
    uxb = cross(u, b)

    out = {"lnrho": -dot(u, g_lnrho) - div_u}
    for i, name in enumerate(("ux", "uy", "uz")):
        out[name] = (
            -dot(u, du[i])
            - cs2 * (g_ss[i] / s.cp + g_lnrho[i])
            + jxb[i] / rho
            + s.nu * (laplace(u[i]) + gd_u[i] / 3.0 + 2.0 * dot(strain[i], g_lnrho))
            + s.zeta * gd_u[i]
        )
    for i, name in enumerate(("ax", "ay", "az")):
        out[name] = uxb[i] - s.eta * mu0_j[i]
    s2 = sum(strain[i][k] * strain[i][k] for i in range(3) for k in range(3))
    heating = (
        s.eta * s.mu0 * dot(j, j) + 2.0 * rho * s.nu * s2 + s.zeta * rho * div_u * div_u
    ) / (rho * temperature)
    g_lnt = [s.gamma * g_ss[i] / s.cp + (s.gamma - 1.0) * g_lnrho[i] for i in range(3)]
    g_sum = [s.gamma * (g_ss[i] / s.cp + g_lnrho[i]) for i in range(3)]
    conduction = s.cp * s.chi * (
        s.gamma * laplace(ss) / s.cp + (s.gamma - 1.0) * laplace(lnrho) + dot(g_lnt, g_sum)
    )
    out["ss"] = -dot(u, g_ss) + heating + conduction
    return out


def substep(s: Setup, cur: dict, prev: dict, ratio, beta) -> tuple:
    """One substep with ``ratio = alpha_k / beta_{k-1}`` (0 in a step's first
    substep) and ``beta = beta_k``: ``(cur, prev)`` after it."""
    rate = rates(s, cur)
    new = {q: cur[q] + beta * (ratio * (cur[q] - prev[q]) + s.dt * rate[q]) for q in FIELDS}
    return new, cur


#: per substep ``(alpha_k / beta_{k-1}, beta_k)``
RK3 = tuple((ALPHA[k] / BETA[k - 1] if k else 0.0, BETA[k]) for k in range(3))


# --- the seeded state: functions of the global coordinate and four seed words -------


def _unit(words, i: int):
    """A float32 in [0, 1) from the seed words and a salt (32-bit mix)."""
    import jax.numpy as jnp

    u = jnp.uint32
    w = jnp.asarray(words, dtype=u)
    h = w[i % 4] ^ u((0x9E3779B9 * (i + 1)) & 0xFFFFFFFF)
    h = (h ^ (h >> 16)) * u(0x7FEB352D)
    h = (h ^ (h >> 15)) * u(0x846CA68B)
    h = h ^ (h >> 16)
    return (h >> 8).astype(jnp.float32) * jnp.float32(1.0 / (1 << 24))


def seeded_field(setup: Setup, q: int):
    """Field ``q``: ``modes`` plane waves, wave ``j`` with ``+-(1..max_waves)``
    whole waves along every axis and a seeded phase, ``amplitude / modes`` each
    (``lnrho`` about ``lnrho0``)."""
    import jax.numpy as jnp

    def f(x, y, z, words):
        coords = [c.astype(jnp.float32) for c in (x, y, z)]
        acc = 0.0
        for j in range(setup.modes):
            salt = 8 * (q * setup.modes + j)
            arg = 2.0 * math.pi * _unit(words, salt + 6)
            for a in range(3):
                waves = 1.0 + jnp.floor(_unit(words, salt + a) * setup.max_waves)
                sign = jnp.where(_unit(words, salt + 3 + a) < 0.5, -1.0, 1.0)
                arg = arg + (sign * waves * (2.0 * math.pi / setup.shape[a])) * coords[a]
            acc = acc + jnp.cos(arg)
        base = setup.lnrho0 if FIELDS[q] == "lnrho" else 0.0
        return (base + (setup.amplitude / setup.modes) * acc).astype(jnp.float32)

    return f


def seeded_fields(setup: Setup) -> dict:
    """``{quantity: f(x, y, z, words)}`` for all sixteen -- the fills the program
    is handed; each ``*_prev`` starts as its field (the first substep of a step
    reads none of them)."""
    out = {f: seeded_field(setup, q) for q, f in enumerate(FIELDS)}
    out.update({f + "_prev": out[f] for f in FIELDS})
    return out


# --- the reference and the state's envelope ------------------------------------------


@functools.lru_cache(maxsize=None)
def _reference(setup: Setup, steps: int, sharding):
    import jax
    import jax.numpy as jnp
    from jax import lax

    fields = seeded_fields(setup)

    def run(words):
        X, Y, Z = setup.shape
        c = (jnp.arange(X)[:, None, None], jnp.arange(Y)[None, :, None],
             jnp.arange(Z)[None, None, :])
        cur = {
            q: jnp.broadcast_to(fields[q](*c, words), setup.shape).astype(jnp.float32)
            for q in FIELDS
        }
        rk3 = jnp.asarray(RK3, dtype=jnp.float32)

        def body(k, state):
            # one loop over SUBSTEPS, the two coefficients looked up by k mod 3:
            # a third of the program a loop over whole steps would be
            return substep(setup, *state, rk3[k % 3, 0], rk3[k % 3, 1])

        with jax.default_matmul_precision("highest"):
            cur, prev = lax.fori_loop(0, 3 * steps, body, (cur, dict(cur)))
        return tuple(cur[q] for q in FIELDS) + tuple(prev[q] for q in FIELDS)

    return jax.jit(run, out_shardings=(sharding,) * len(QUANTITIES))


def reference(setup: Setup, steps: int, sharding, words):
    """The sixteen quantities (``QUANTITIES``' order) after ``steps`` time
    steps from the seeded state, whole global arrays placed as ``sharding``
    says."""
    import numpy as np

    return _reference(setup, int(steps), sharding)(np.asarray(words, dtype=np.uint32))


@functools.lru_cache(maxsize=None)
def _outside(setup: Setup):
    import jax
    import jax.numpy as jnp

    def run(*arrays):
        bad = jnp.int32(0)
        for q, a in zip(QUANTITIES, arrays):
            a = a.astype(jnp.float32)
            rest = setup.lnrho0 if q.startswith("lnrho") else 0.0
            inside = jnp.isfinite(a) & (jnp.abs(a - rest) <= setup.envelope)
            bad = bad + jnp.sum((~inside).astype(jnp.int32))
        return bad

    return jax.jit(run)


def state_bad_cells(setup: Setup, arrays) -> int:
    """Cells of the sixteen interior arrays that are not finite or lie more
    than ``envelope`` from their field's rest value."""
    return int(_outside(setup)(*arrays))
