"""Plain reference of Astaroth's MHD step (``acc-runtime/samples/mhd_modular``).

Astaroth (Pekkilä, Väisälä et al.; the library paper Comput. Phys. Commun. 217
(2017), the scaling study arXiv:2103.01597) integrates the Pencil Code's
compressible MHD equations -- continuity, momentum, induction, entropy -- with
sixth-order central differences and Williamson's 2N-storage third-order
Runge-Kutta, eight fields ``lnrho, ux uy uz, ax ay az, ss`` on a periodic box,
256^3 a device in its scaling runs.  This file is that update in ``jax.numpy``
and float32, ``jnp.roll`` on whole global arrays, under
``jax.default_matmul_precision("highest")`` (nothing multiplies matrices; the
references all set it).  No kernel, no domain: it imports nothing of ``ops/``,
``domain.py`` or the model (``models/astaroth_mhd.py`` imports the operators,
the right-hand side, the set-up and the seeded state from HERE, never the
other way), written down as remembered (no network here).

Differences, sixth order, ``f+k`` the value ``k`` cells up an axis:

    first   (1/dx)   [3/4 (f+1 - f-1) - 3/20 (f+2 - f-2) + 1/60 (f+3 - f-3)]
    second  (1/dx^2) [-49/18 f0 + 3/2 (f+1 + f-1) - 3/20 (f+2 + f-2) + 1/90 (f+3 + f-3)]
    mixed   (1/(720 dx dy)) sum_k c_k [f(+k,+k) + f(-k,-k) - f(+k,-k) - f(-k,+k)],
            c = (270, -27, 2)                  (Pencil's ``derij``, diagonal form)

The right-hand side, ``rho = exp(lnrho)``, ``B = curl A``,
``j = (grad div A - lap A) / mu0``, ``S_ij = (d_i u_j + d_j u_i)/2 - delta_ij
div u / 3``:

    d lnrho/dt = -u.grad lnrho - div u
    du/dt      = -(u.grad)u - cs2 (grad ss / cp + grad lnrho) + (j x B)/rho
                 + nu [lap u + (1/3) grad div u + 2 S.grad lnrho] + zeta grad div u
                 cs2 = cs0^2 exp(gamma ss/cp + (gamma - 1)(lnrho - lnrho0))
    dA/dt      = u x B - eta (grad div A - lap A)
    ds/dt      = -u.grad ss + (1/(rho T)) [eta mu0 j.j + 2 rho nu S:S + zeta rho (div u)^2]
                 + cp chi [gamma lap ss / cp + (gamma - 1) lap lnrho
                           + (gamma grad ss/cp + (gamma - 1) grad lnrho)
                             . (gamma (grad ss/cp + grad lnrho))]
                 lnT = lnT0 + gamma ss/cp + (gamma - 1)(lnrho - lnrho0)

Time, Williamson's RK3 in Astaroth's TWO-BUFFER form: with ``alpha = (0, -5/9,
-153/128)``, ``beta = (1/3, 15/16, 8/15)``, substep ``s`` of a step makes

    new = cur + beta_s (alpha_s (cur - prev) / beta_{s-1} + dt F(cur))

then ``prev <- cur``, ``cur <- new`` -- the 2N scheme ``w <- alpha_s w + dt F``,
``f <- f + beta_s w`` with ``w`` recovered as ``(cur - prev) / beta_{s-1}``
(``alpha_0 = 0``: the first substep of a step reads no ``prev``).  The eight
``*_prev`` are state the scheme defines: after a step they hold the fields as
they were before its third substep.

Departures from the source, each stated again in the benchmark's
configuration:

* a FIXED ``dt = courant dx / (cs0 + |u|max)`` from the set-up's numbers where
  Astaroth's own benchmark steps with ``dt = FLT_EPSILON`` (under which a
  skipped right-hand side would pass) and its simulations recompute ``dt``
  from the state every step;
* no upwinding of ``lnrho``, no forcing, no sink particle, no heating or
  cooling: the plain equations above;
* the coefficients (``MhdSetup``): ``nu``, ``zeta`` the source's sample values as
  remembered; ``eta``, ``chi``, ``mu0``, ``lnrho0``, ``lnT0`` chosen -- an ideal
  gas consistent with ``cs0`` (``cs0^2 = (gamma - 1) cp T0``), ``gamma = 5/3``,
  and every transport term large enough to move the state far beyond rounding
  within a few steps;
* the initial state: a seeded superposition of ``modes`` plane waves a field
  (``seeded_fields``) where the source starts from random or file data.

Every read of a field at an offset goes through a ``Taps`` -- one read an
offset, shared by every operator that needs it -- and the operators add their
taps in one fixed order, the order ``AstarothMHD``'s kernel uses too (it calls
the same ``substep``), so program and reference differ by the compilers'
roundings only.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Union

#: the evolved fields, then the second buffer of each (the previous substep's
#: value); the quantities of a domain, in the order they are added
FIELDS = ("lnrho", "ux", "uy", "uz", "ax", "ay", "az", "ss")
PREV = tuple(f + "_prev" for f in FIELDS)
QUANTITIES = FIELDS + PREV
VELOCITY = ("ux", "uy", "uz")
POTENTIAL = ("ax", "ay", "az")
RADIUS = 3

#: Williamson (1980) third-order 2N-storage Runge-Kutta
ALPHA = (0.0, -5.0 / 9.0, -153.0 / 128.0)
BETA = (1.0 / 3.0, 15.0 / 16.0, 8.0 / 15.0)
SUBSTEPS = 3

#: sixth-order central differences: first (distance 1..3), second (0..3),
#: mixed on the diagonals (1..3)
D1 = (3.0 / 4.0, -3.0 / 20.0, 1.0 / 60.0)
D2 = (-49.0 / 18.0, 3.0 / 2.0, -3.0 / 20.0, 1.0 / 90.0)
DM = (270.0 / 720.0, -27.0 / 720.0, 2.0 / 720.0)

#: the terms ``MhdSetup.off`` may switch off (the ablation tests)
TERMS = ("advection", "pressure", "lorentz")


@dataclasses.dataclass(frozen=True)
class MhdSetup:
    """The numbers of one set-up: a periodic box of side ``box`` on every axis
    (one float), or of sides ``box[a]`` (a triple: a weak-scaled run keeps the
    CELL and grows the box with the grid), ``shape`` cells, code units
    (``cs0 = 1``)."""

    shape: tuple
    nu: float = 5e-3  # kinematic viscosity
    eta: float = 8e-3  # magnetic diffusivity
    chi: float = 5e-3  # thermal diffusivity
    zeta: float = 1e-2  # bulk viscosity
    gamma: float = 5.0 / 3.0
    cp: float = 1.0
    cs0: float = 1.0
    mu0: float = 1.0
    lnrho0: float = 0.0
    lnT0: float = math.log(1.5)  # cs0^2 / ((gamma - 1) cp)
    box: Union[float, tuple] = 2.0 * math.pi  # one side, or a side an axis
    courant: float = 0.3  # of dx / (cs0 + |u|max)
    dt: float = None  # the fixed time step; None = ``dt_of`` this set-up
    amplitude: float = 0.05  # bound on every seeded field (Mach 0.05 a component)
    modes: int = 3  # seeded plane waves a field
    max_waves: int = 4  # whole waves an axis, 1..max_waves, either sign
    off: tuple = ()  # of ``TERMS``: the ablation tests' switches

    def __post_init__(self):
        unknown = set(self.off) - set(TERMS)
        if unknown:
            raise ValueError(f"unknown terms {sorted(unknown)}; there are {TERMS}")
        if not isinstance(self.box, (int, float)):
            if len(self.box) != 3:
                raise ValueError(f"box is one side or one an axis, not {self.box!r}")
            object.__setattr__(self, "box", tuple(float(b) for b in self.box))  # hashable

    @property
    def sides(self) -> tuple:
        """The box's side along each axis."""
        return self.box if isinstance(self.box, tuple) else (self.box,) * 3

    @property
    def spacing(self) -> tuple:
        return tuple(b / n for b, n in zip(self.sides, self.shape))


#: Williamson's RK3 is stable on the negative real axis down to -2.51, and the
#: sixth-order second difference reaches -6.04 / dx^2 an axis (272 / 45)
RK3_REAL_BOUND = 2.51
D2_SPECTRAL_RADIUS = 272.0 / 45.0
#: the share of that bound the fixed step takes of the stiffest diffusion
DIFFUSIVE_SHARE = 0.8


def dt_of(setup: MhdSetup) -> float:
    """The fixed time step, by Astaroth's own rule the smaller of the advective
    and the diffusive limit: ``courant`` times the smallest ``dx`` over ``cs0 +
    |u|max``, ``|u|max = sqrt(3) amplitude`` the seeded state's bound (the
    Alfven speed of the seeded field stays under a third of ``cs0``) -- and
    ``DIFFUSIVE_SHARE`` of RK3's bound over the stiffest of the three diffusion
    operators on that ``dx``: ``nu lap + (nu/3 + zeta) grad div`` of the
    momentum, ``gamma chi lap`` of the entropy, ``eta lap`` of the induction
    equation.  The advective limit goes as ``dx`` and the diffusive as ``dx^2``:
    on a 2 pi box with the default coefficients the first binds up to some 280
    cells an axis (every grid the tests and the 256^3 cells run: there the
    viscous number reads 1.81 of 2.51), the second beyond (512^3: 1.876e-3 where
    ``courant`` alone gives 3.388e-3, at which the viscous number reads 3.62)."""
    if setup.dt is not None:
        return float(setup.dt)
    dx = min(setup.spacing)
    advective = setup.courant * dx / (setup.cs0 + math.sqrt(3.0) * setup.amplitude)
    stiffest = D2_SPECTRAL_RADIUS * max(
        3.0 * setup.nu + setup.nu / 3.0 + setup.zeta, 3.0 * setup.gamma * setup.chi, 3.0 * setup.eta
    )
    if stiffest <= 0.0:  # (the ablation tests switch every diffusion off)
        return advective
    return min(advective, DIFFUSIVE_SHARE * RK3_REAL_BOUND * dx * dx / stiffest)


# --- reads and differences ------------------------------------------------------------


class Taps:
    """``taps(field, dx, dy, dz)``: the field at an offset, READ ONCE -- every
    operator that needs the same ``(dx, dy, dz)`` of a field shares the value
    (a first and a second difference share their six taps; the trace and the
    compile of a kernel then grow with the reads, not with the taps)."""

    def __init__(self, read):
        self._read, self._got = read, {}

    def __call__(self, field, dx=0, dy=0, dz=0):
        key = (field, dx, dy, dz)
        if key not in self._got:
            self._got[key] = self._read(field, dx, dy, dz)
        return self._got[key]

    def __len__(self):
        return len(self._got)


def _at(axis: int, k: int, axis2: int = None, k2: int = 0) -> tuple:
    off = [0, 0, 0]
    off[axis] = k
    if axis2 is not None:
        off[axis2] = k2
    return tuple(off)


def der1(taps, f, axis: int, inv_dx: float):
    acc = None
    for k in (1, 2, 3):
        term = (D1[k - 1] * inv_dx) * (taps(f, *_at(axis, k)) - taps(f, *_at(axis, -k)))
        acc = term if acc is None else acc + term
    return acc


def der2(taps, f, axis: int, inv_dx: float):
    acc = (D2[0] * inv_dx * inv_dx) * taps(f)
    for k in (1, 2, 3):
        acc = acc + (D2[k] * inv_dx * inv_dx) * (taps(f, *_at(axis, k)) + taps(f, *_at(axis, -k)))
    return acc


def der_mixed(taps, f, a: int, b: int, inv_da: float, inv_db: float):
    acc = None
    for k in (1, 2, 3):
        same = taps(f, *_at(a, k, b, k)) + taps(f, *_at(a, -k, b, -k))
        cross = taps(f, *_at(a, k, b, -k)) + taps(f, *_at(a, -k, b, k))
        term = (DM[k - 1] * inv_da * inv_db) * (same - cross)
        acc = term if acc is None else acc + term
    return acc


# --- the right-hand side --------------------------------------------------------------


def _dot(a, b):
    return (a[0] * b[0] + a[1] * b[1]) + a[2] * b[2]


def _cross(a, b):
    return (
        a[1] * b[2] - a[2] * b[1],
        a[2] * b[0] - a[0] * b[2],
        a[0] * b[1] - a[1] * b[0],
    )


def _sum3(v):
    return (v[0] + v[1]) + v[2]


def rhs(setup: MhdSetup, taps: Taps) -> dict:
    """``{field: dF/dt}`` of the module docstring's equations from the reads of
    ``taps`` (whole arrays or planes)."""
    from jax.numpy import exp

    inv = [1.0 / d for d in setup.spacing]
    gamma, cp = setup.gamma, setup.cp

    def grad(f):
        return [der1(taps, f, a, inv[a]) for a in range(3)]

    def second(f):
        return [der2(taps, f, a, inv[a]) for a in range(3)]

    def grad_div(v, d2):
        """``d_i sum_j d_j v_j``: the second differences on the diagonal, the
        mixed ones beside it."""
        return [
            _sum3([
                d2[j][i] if j == i else der_mixed(taps, v[j], i, j, inv[i], inv[j])
                for j in range(3)
            ])
            for i in range(3)
        ]

    lnrho, ss = taps("lnrho"), taps("ss")
    u = [taps(c) for c in VELOCITY]
    g_lnrho, g_ss = grad("lnrho"), grad("ss")
    du = [grad(c) for c in VELOCITY]  # du[i][j] = d_j u_i
    d2u = [second(c) for c in VELOCITY]
    d2a = [second(c) for c in POTENTIAL]
    div_u = _sum3([du[i][i] for i in range(3)])
    lap_u = [_sum3(d2u[i]) for i in range(3)]
    gd_u = grad_div(VELOCITY, d2u)
    # B = curl A from the six off-diagonal first differences; mu0 j
    da = {
        (i, j): der1(taps, POTENTIAL[i], j, inv[j]) for i in range(3) for j in range(3) if i != j
    }
    b = (da[2, 1] - da[1, 2], da[0, 2] - da[2, 0], da[1, 0] - da[0, 1])
    gd_a = grad_div(POTENTIAL, d2a)
    mu0_j = [gd_a[i] - _sum3(d2a[i]) for i in range(3)]
    # the rate-of-strain tensor, traceless
    third = div_u * (1.0 / 3.0)
    s = [[None] * 3 for _ in range(3)]
    for i in range(3):
        s[i][i] = du[i][i] - third
        for j in range(i + 1, 3):
            s[i][j] = s[j][i] = 0.5 * (du[i][j] + du[j][i])

    thermo = (gamma / cp) * ss + (gamma - 1.0) * (lnrho - setup.lnrho0)
    inv_rho = exp(-lnrho)
    out = {}

    # continuity
    out["lnrho"] = -_dot(u, g_lnrho) - div_u if "advection" not in setup.off else -div_u

    # momentum
    cs2 = (setup.cs0 * setup.cs0) * exp(thermo)
    lorentz = _cross(mu0_j, b)
    for i, c in enumerate(VELOCITY):
        acc = setup.nu * (
            (lap_u[i] + (1.0 / 3.0) * gd_u[i]) + 2.0 * _dot(s[i], g_lnrho)
        ) + setup.zeta * gd_u[i]
        if "advection" not in setup.off:
            acc = acc - _dot(u, du[i])
        if "pressure" not in setup.off:
            acc = acc - cs2 * (g_ss[i] * (1.0 / cp) + g_lnrho[i])
        if "lorentz" not in setup.off:
            acc = acc + lorentz[i] * (inv_rho * (1.0 / setup.mu0))
        out[c] = acc

    # induction
    uxb = _cross(u, b)
    for i, c in enumerate(POTENTIAL):
        out[c] = uxb[i] - setup.eta * mu0_j[i]

    # entropy
    inv_t = exp(-(setup.lnT0 + thermo))
    s2 = _sum3([s[i][i] * s[i][i] for i in range(3)]) + 2.0 * (
        (s[0][1] * s[0][1] + s[0][2] * s[0][2]) + s[1][2] * s[1][2]
    )
    heating = inv_t * (
        ((setup.eta / setup.mu0) * inv_rho) * _dot(mu0_j, mu0_j)
        + (2.0 * setup.nu) * s2
        + setup.zeta * (div_u * div_u)
    )
    g_lnt = [(gamma / cp) * g_ss[i] + (gamma - 1.0) * g_lnrho[i] for i in range(3)]
    g_sum = [gamma * (g_ss[i] * (1.0 / cp) + g_lnrho[i]) for i in range(3)]
    conduction = (cp * setup.chi) * (
        ((gamma / cp) * _sum3(second("ss")) + (gamma - 1.0) * _sum3(second("lnrho")))
        + _dot(g_lnt, g_sum)
    )
    acc = heating + conduction
    if "advection" not in setup.off:
        acc = acc - _dot(u, g_ss)
    out["ss"] = acc
    return out


def two_buffer(cur, prev, dt_rate, ratio, beta):
    """``cur + beta (ratio (cur - prev) + dt F)``: one substep of the two-buffer
    Runge-Kutta for one field (``ratio = alpha_s / beta_{s-1}``, ``beta =
    beta_s``: ``COEFFS``; floats, or traced scalars).  ``prev=None`` is the
    first substep of a step, which reads no second buffer (``alpha_0 = 0``)."""
    w = dt_rate if prev is None else ratio * (cur - prev) + dt_rate
    return cur + beta * w


def substep(setup: MhdSetup, taps: Taps, prev, ratio, beta) -> dict:
    """One substep of all eight fields: ``{field: new}`` from the reads of the
    current fields and ``prev(field)``, the previous substep's value at the
    centre (``prev=None`` in the first substep of a step)."""
    dt = dt_of(setup)
    rate = rhs(setup, taps)
    return {
        f: two_buffer(
            taps(f), None if prev is None else prev(f), dt * rate[f], ratio, beta
        )
        for f in FIELDS
    }


#: per substep ``(alpha_s / beta_{s-1}, beta_s)``
COEFFS = tuple((ALPHA[s] / BETA[s - 1] if s else 0.0, BETA[s]) for s in range(SUBSTEPS))


# --- the plain step on whole arrays ---------------------------------------------------


def _roll_taps(cur: dict) -> Taps:
    import jax.numpy as jnp

    def read(f, dx, dy, dz):
        if not (dx or dy or dz):
            return cur[f]
        # the value AT x + d comes to x by a roll of -d
        return jnp.roll(cur[f], (-dx, -dy, -dz), (0, 1, 2))

    return Taps(read)


@functools.lru_cache(maxsize=None)
def _substeps(setup: MhdSetup, count: int):
    """``count`` substeps as ONE loop over a single traced substep, its two
    coefficients looked up by ``s mod 3`` (a third of the program a loop over
    whole steps would be; ``0 * (cur - prev)`` in a step's first substep adds
    nothing to a finite state)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    def run(cur, prev):
        coeffs = jnp.asarray(COEFFS, dtype=jnp.float32)

        def body(s, state):
            cur, prev = state
            ratio, beta = coeffs[s % SUBSTEPS, 0], coeffs[s % SUBSTEPS, 1]
            return substep(setup, _roll_taps(cur), prev.__getitem__, ratio, beta), cur

        with jax.default_matmul_precision("highest"):
            return lax.fori_loop(0, count, body, (cur, prev))

    return jax.jit(run)


def steps(setup: MhdSetup, state: dict, n: int) -> dict:
    """``n`` time steps (three substeps each) on whole periodic arrays:
    ``state`` maps the sixteen ``QUANTITIES`` to arrays, and so does the
    result."""
    cur, prev = _substeps(setup, SUBSTEPS * int(n))(
        {f: state[f] for f in FIELDS}, {f: state[f + "_prev"] for f in FIELDS}
    )
    return {**cur, **{f + "_prev": prev[f] for f in FIELDS}}


# --- the seeded state: functions of the global coordinate and four seed words ---------


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


def seeded_field(setup: MhdSetup, q: int):
    """Field ``q`` of the seeded state: ``modes`` plane waves, wave ``j`` with
    ``n_a`` in ``+-(1..max_waves)`` whole waves along EVERY axis (so no field is
    constant along any line) and a seeded phase,

        f = lnrho0 [lnrho only] + (amplitude / modes) sum_j cos(n_j . x 2 pi / N + p_j)

    a function of the global integer coordinate (broadcastable pieces) and the
    four seed words, so a sharded fill and a global one see the same
    expression and ``words`` can be a traced argument."""
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


def seeded_fields(setup: MhdSetup) -> dict:
    """``{quantity: f(x, y, z, words)}`` for all sixteen: the eight fields, and
    each ``*_prev`` equal to its field (the first substep reads none of them;
    a run that did would not start from garbage)."""
    out = {f: seeded_field(setup, q) for q, f in enumerate(FIELDS)}
    out.update({f + "_prev": out[f] for f in FIELDS})
    return out


def global_fields(setup: MhdSetup, words) -> dict:
    """The sixteen seeded quantities as whole float32 arrays of ``shape``."""
    import jax.numpy as jnp

    X, Y, Z = setup.shape
    c = (jnp.arange(X)[:, None, None], jnp.arange(Y)[None, :, None], jnp.arange(Z)[None, None, :])
    w = jnp.asarray(words, dtype=jnp.uint32)
    return {
        nm: jnp.broadcast_to(fn(*c, w), setup.shape).astype(jnp.float32)
        for nm, fn in seeded_fields(setup).items()
    }
