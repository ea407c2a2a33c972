"""The benchmark's own copy of the D3Q19 BGK lattice-Boltzmann step and of its
seeded state (configuration ``lbm-d3q19-256``).

jax/numpy only: nothing here imports the program under test, and every
number comes from the configuration file (its sizes and its ``setup`` group).
The program may change, this may not.

FluidX3D's ``benchmark`` set-up (``src/setup.cpp``, arXiv:2112.08926): D3Q19,
single relaxation time, FP32, a box periodic on every side.  One step, pull
form, ``c_s^2 = 1/3``:

    g_i(x)  = f_i(x - c_i)
    rho     = sum_i g_i,      rho u = sum_i c_i g_i
    feq_i   = w_i rho (1 + 3 c_i.u + 4.5 (c_i.u)^2 - 1.5 u.u)
    f_i'(x) = g_i - omega (g_i - feq_i),      omega = 1 / (3 nu + 0.5)

``jnp.roll`` on whole global arrays: the box is periodic and nowhere zero, so
every cell of every population is compared and an unfilled halo, a wrong wrap
or a skipped relaxation shows.  The configuration's ``assumed`` group lists
the departures from the source (plain ``f_i``, ``nu``, no flags, the seeded
Taylor-Green state, the direction order).
"""

from __future__ import annotations

import dataclasses
import functools
import math

#: rest, six axis neighbours, twelve diagonal ones (xy, xz, yz planes)
C = (
    (0, 0, 0),
    (1, 0, 0), (-1, 0, 0), (0, 1, 0), (0, -1, 0), (0, 0, 1), (0, 0, -1),
    (1, 1, 0), (-1, -1, 0), (1, -1, 0), (-1, 1, 0),
    (1, 0, 1), (-1, 0, -1), (1, 0, -1), (-1, 0, 1),
    (0, 1, 1), (0, -1, -1), (0, 1, -1), (0, -1, 1),
)  # fmt: skip
W = (1.0 / 3.0,) + (1.0 / 18.0,) * 6 + (1.0 / 36.0,) * 12
Q = 19
NAMES = tuple(f"f{i}" for i in range(Q))


@dataclasses.dataclass(frozen=True)
class Setup:
    """The numbers of one run, from the configuration alone (``setup_from``)."""

    shape: tuple
    nu: float
    u0: float
    modes: int
    rho0: float
    max_waves: int
    rho_band: tuple
    u_max: float

    @property
    def omega(self) -> float:
        return 1.0 / (3.0 * self.nu + 0.5)


def setup_from(config: dict, shape) -> Setup:
    """``shape`` is given apart because a rehearsal shrinks it."""
    s = config["setup"]
    return Setup(
        shape=tuple(int(n) for n in shape), nu=float(s["nu"]), u0=float(s["u0"]),
        modes=int(s["modes"]), rho0=float(s["rho0"]), max_waves=int(s["max_waves"]),
        rho_band=tuple(float(v) for v in s["rho_band"]), u_max=float(s["u_max"]),
    )


def _signed(terms):
    acc = None
    for s, v in terms:
        if acc is None:
            acc = v if s > 0 else -v
        else:
            acc = acc + v if s > 0 else acc - v
    return acc


def moments(g):
    """``(rho, ux, uy, uz)`` of nineteen populations, summed in index order."""
    rho = g[0]
    for i in range(1, Q):
        rho = rho + g[i]
    inv = 1.0 / rho
    return (rho,) + tuple(
        _signed((C[i][a], g[i]) for i in range(Q) if C[i][a]) * inv for a in range(3)
    )


def equilibrium(rho, ux, uy, uz):
    """``feq_1..18`` as written above, ``feq_0 = rho - sum_{i>0} feq_i``: equal
    to ``w_0 rho (1 - 1.5 u.u)`` in exact arithmetic, and the form that keeps
    ``sum_i feq_i = rho`` in float32 (1/3, 1/18, 1/36 round up and sum to 1 +
    1.49e-8: the configuration's ``assumed`` group)."""
    u = (ux, uy, uz)
    base = 1.0 - 1.5 * ((ux * ux + uy * uy) + uz * uz)
    out, moving = [None], None
    for i in range(1, Q):
        cu = _signed((C[i][a], u[a]) for a in range(3) if C[i][a])
        feq = (W[i] * rho) * (base + cu * (3.0 + 4.5 * cu))
        out.append(feq)
        moving = feq if moving is None else moving + feq
    out[0] = rho - moving
    return out


def _step(f, omega: float):
    import jax.numpy as jnp

    g = [f[0]] + [jnp.roll(f[i], C[i], (0, 1, 2)) for i in range(1, Q)]
    feq = equilibrium(*moments(g))
    return tuple(g[i] - omega * (g[i] - feq[i]) for i in range(Q))


# --- the seeded state ---------------------------------------------------------------


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


def seeded_velocity(setup: Setup, x, y, z, words):
    """``modes`` Taylor-Green modes: mode ``j`` in the plane of axes ``(a, b) =
    (j, j + 1) mod 3``, 1..``max_waves`` whole waves an axis and seeded phases,
    ``u_a = A (k_b/k) sin cos cos``, ``u_b = -A (k_a/k) cos sin cos``,
    ``k = max(k_a, k_b)``, ``A = u0 / modes``: divergence-free, every
    component within ``u0``."""
    import jax.numpy as jnp

    coords = [c.astype(jnp.float32) for c in (x, y, z)]
    u = [0.0, 0.0, 0.0]
    amp = setup.u0 / setup.modes
    for j in range(setup.modes):
        a, b, c = j % 3, (j + 1) % 3, (j + 2) % 3
        k, arg = {}, {}
        for axis in (a, b, c):
            waves = 1.0 + jnp.floor(_unit(words, 8 * j + axis) * setup.max_waves)
            k[axis] = (2.0 * math.pi / setup.shape[axis]) * waves
            arg[axis] = k[axis] * coords[axis] + 2.0 * math.pi * _unit(words, 8 * j + 3 + axis)
        kmax = jnp.maximum(k[a], k[b])
        u[a] = u[a] + (amp * k[b] / kmax) * jnp.sin(arg[a]) * jnp.cos(arg[b]) * jnp.cos(arg[c])
        u[b] = u[b] - (amp * k[a] / kmax) * jnp.cos(arg[a]) * jnp.sin(arg[b]) * jnp.cos(arg[c])
    return tuple(u)


def seeded_fields(setup: Setup) -> dict:
    """``{f_i: f(x, y, z, words)}``: the equilibrium of ``rho0`` and the seeded
    velocity, functions of the global integer coordinate (broadcastable
    pieces) and the four seed words -- the fills the program is handed."""
    import jax.numpy as jnp

    def population(i):
        def f(x, y, z, words):
            u = seeded_velocity(setup, x, y, z, words)
            return equilibrium(jnp.float32(setup.rho0), *u)[i].astype(jnp.float32)

        return f

    return {NAMES[i]: population(i) for i in range(Q)}


# --- the reference and the state's moments -----------------------------------------


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
        f = tuple(
            jnp.broadcast_to(fields[nm](*c, words), setup.shape).astype(jnp.float32)
            for nm in NAMES
        )
        with jax.default_matmul_precision("highest"):
            return lax.fori_loop(0, steps, lambda _, f: _step(f, setup.omega), f)

    return jax.jit(run, out_shardings=(sharding,) * Q)


def reference(setup: Setup, steps: int, sharding, words):
    """The nineteen populations after ``steps`` steps from the seeded state,
    whole global arrays placed as ``sharding`` says."""
    import numpy as np

    return _reference(setup, int(steps), sharding)(np.asarray(words, dtype=np.uint32))


@functools.lru_cache(maxsize=None)
def _state(setup: Setup):
    import jax
    import jax.numpy as jnp

    def run(*f):
        f = [a.astype(jnp.float32) for a in f]
        rho, ux, uy, uz = moments(f)
        speed2 = (ux * ux + uy * uy) + uz * uz
        finite = jnp.isfinite(rho) & jnp.isfinite(speed2)
        for a in f:
            finite = finite & jnp.isfinite(a)
        inside = (rho >= setup.rho_band[0]) & (rho <= setup.rho_band[1])
        inside = inside & (speed2 < setup.u_max * setup.u_max)
        bad = jnp.sum((~(finite & inside)).astype(jnp.int32))
        # the mass as per-pencil partial sums: the host adds them in float64
        return bad, jnp.sum(jnp.where(finite, rho, 0.0), axis=2)

    return jax.jit(run)


def state_counts(setup: Setup, f) -> tuple:
    """``(bad cells, total mass)`` of nineteen interior arrays: cells that are
    not finite or whose moments leave the guardband (``rho`` outside
    ``rho_band``, ``|u| >= u_max``), and ``sum_x rho`` in float64."""
    import numpy as np

    bad, pencils = _state(setup)(*f)
    return int(bad), float(np.asarray(pencils, dtype=np.float64).sum())


def seeded_mass(setup: Setup) -> float:
    """``sum_x rho`` of the seeded state: ``sum_i feq_i = rho0`` in every cell."""
    return setup.rho0 * float(setup.shape[0]) * float(setup.shape[1]) * float(setup.shape[2])
