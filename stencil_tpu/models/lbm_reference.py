"""Plain reference of the D3Q19 BGK lattice-Boltzmann step (FluidX3D's benchmark).

FluidX3D's ``benchmark`` set-up (``src/setup.cpp``: ``LBM lbm(256u, 256u, 256u,
1.0f/6.0f)``; Lehmann et al., arXiv:2112.08926): D3Q19, single-relaxation-time
(BGK) collision, FP32 storage and arithmetic, a box periodic on every side, no
extension.  One step in pull form, ``c_s^2 = 1/3``:

    g_i(x)  = f_i(x - c_i)                                        stream
    rho     = sum_i g_i,      rho u = sum_i c_i g_i               moments
    feq_i   = w_i rho (1 + 3 c_i.u + 4.5 (c_i.u)^2 - 1.5 u.u)     equilibrium
    f_i'(x) = g_i - omega (g_i - feq_i),   omega = 1/(3 nu + 0.5) collide

in ``jax.numpy`` and float32, ``jnp.roll`` on whole global arrays, under
``jax.default_matmul_precision("highest")`` (nothing multiplies matrices; the
references all set it).  No kernel, no domain: it imports nothing of ``ops/``,
``domain.py`` or the model (``models/lbm.py`` imports the lattice, the set-up
and the seeded state from HERE, never the other way).

Mass ``sum_x rho`` and momentum ``sum_x rho u`` are conserved on the periodic
box; a shear wave of wave number ``k`` decays as ``exp(-nu k^2 t)``
(tests/test_lbm.py holds the reference to both).

Departures from the source, each stated again in the benchmark's
configuration:

* plain populations ``f_i`` are stored where FluidX3D stores ``f_i - w_i``
  (its rounding trick for FP16 storage, arXiv:2112.08926 section 3.2);
* ``nu = 1/30`` (``omega = 5/3``) where the benchmark sets 1/6: that makes
  ``omega`` exactly 1, where the relaxation collapses to ``f_i' = feq_i`` and a
  program that skipped it would pass;
* no flag byte: the box has no boundary to flag;
* the initial state: the source's benchmark starts at rest, which stays at
  rest and checks nothing; a seeded superposition of Taylor-Green modes (the
  source's own ``Taylor-Green vortices`` set-up is one such mode) stands in;
* two copies of the populations here (``jnp.roll`` makes a new array), one
  streamed in place in the source (Esoteric Pull): storage, not arithmetic;
* ``feq_0 = rho - sum_{i>0} feq_i`` where the source writes ``w_0 rho (1 - 1.5
  u.u)``: equal in exact arithmetic, mass-conserving in float32 on plain
  ``f_i`` (``equilibrium``);
* the order of the nineteen directions, as remembered (``C`` below).

The sums run in index order, the equilibrium is grouped as
``(w_i rho) (base + cu (3 + 4.5 cu))`` with ``base = 1 - 1.5 u.u`` and the rest
population's is ``rho`` less the other eighteen (``equilibrium`` says why) --
the order ``LatticeBoltzmann._kernel`` uses too, so the two differ by the
compilers' roundings only.
"""

from __future__ import annotations

import dataclasses
import functools
import math

#: the nineteen directions: rest, the six axis neighbours, the twelve diagonal
#: ones in the xy, xz and yz planes; ``C[i + 1] == -C[i]`` for odd ``i``
C = (
    (0, 0, 0),
    (1, 0, 0), (-1, 0, 0), (0, 1, 0), (0, -1, 0), (0, 0, 1), (0, 0, -1),
    (1, 1, 0), (-1, -1, 0), (1, -1, 0), (-1, 1, 0),
    (1, 0, 1), (-1, 0, -1), (1, 0, -1), (-1, 0, 1),
    (0, 1, 1), (0, -1, -1), (0, 1, -1), (0, -1, 1),
)  # fmt: skip
Q = len(C)
W = (1.0 / 3.0,) + (1.0 / 18.0,) * 6 + (1.0 / 36.0,) * 12
#: the quantities, in the order they are added to a domain
NAMES = tuple(f"f{i}" for i in range(Q))
#: the model's guardband on the moments (low-Mach LBM: the weakly compressible
#: limit holds while the density stays near 1 and ``|u|`` well under c_s = 0.577)
RHO_BAND = (0.9, 1.1)
U_MAX = 0.1


@dataclasses.dataclass(frozen=True)
class LbmSetup:
    """The numbers of one set-up, in lattice units (cell 1, step 1)."""

    shape: tuple
    nu: float = 1.0 / 30.0  # kinematic viscosity; tau = 3 nu + 0.5 = 0.6
    u0: float = 0.05  # bound on each velocity component of the seeded state
    modes: int = 4  # seeded Taylor-Green modes
    rho0: float = 1.0
    max_waves: int = 4  # whole waves an axis, 1..max_waves

    def __post_init__(self):
        if not self.nu > 0.0:
            raise ValueError(f"nu must be positive, got {self.nu}")

    @property
    def omega(self) -> float:
        return 1.0 / (3.0 * self.nu + 0.5)


# --- the update ---------------------------------------------------------------------


def signed_sum(terms):
    """``sum_j s_j v_j`` over ``(s_j, v_j)`` with ``s_j`` in ``(+1, -1)``, left
    to right, as adds and subtracts (no multiply by one)."""
    acc = None
    for s, v in terms:
        if acc is None:
            acc = v if s > 0 else -v
        else:
            acc = acc + v if s > 0 else acc - v
    return acc


def moments(g):
    """``(rho, ux, uy, uz)`` of nineteen populations (whole arrays or planes)."""
    rho = g[0]
    for i in range(1, Q):
        rho = rho + g[i]
    inv = 1.0 / rho
    u = tuple(
        signed_sum((C[i][a], g[i]) for i in range(Q) if C[i][a]) * inv for a in range(3)
    )
    return (rho,) + u


def equilibrium(rho, ux, uy, uz):
    """The nineteen ``feq_i`` of the layer equations.  The rest population's
    is taken from conservation, ``feq_0 = rho - sum_{i>0} feq_i``: the same
    number in exact arithmetic (``sum_i w_i (1 + 3 cu + 4.5 cu^2 - 1.5 uu) =
    1``), and in float32 the form that keeps ``sum_i feq_i = rho`` to an
    unbiased rounding -- 1/3, 1/18 and 1/36 all round UP and sum to 1 +
    1.49e-8, which ``w_0 rho (1 - 1.5 uu)`` would multiply into the mass every
    step (2.5e-8 a step until rounding absorbs it near 1e-5; the source meets
    the same bias by storing ``f_i - w_i``)."""
    u = (ux, uy, uz)
    base = 1.0 - 1.5 * ((ux * ux + uy * uy) + uz * uz)
    out, moving = [None], None
    for i in range(1, Q):
        cu = signed_sum((C[i][a], u[a]) for a in range(3) if C[i][a])
        feq = (W[i] * rho) * (base + cu * (3.0 + 4.5 * cu))
        out.append(feq)
        moving = feq if moving is None else moving + feq
    out[0] = rho - moving
    return out


def collide(g, omega: float):
    """BGK relaxation of the streamed populations ``g`` towards equilibrium."""
    feq = equilibrium(*moments(g))
    return [g[i] - omega * (g[i] - feq[i]) for i in range(Q)]


def _step(f, omega: float):
    import jax.numpy as jnp

    # pull: g_i(x) = f_i(x - c_i) -- roll BY +c_i moves the value at x - c_i to x
    g = [f[0]] + [jnp.roll(f[i], C[i], (0, 1, 2)) for i in range(1, Q)]
    return collide(g, omega)


@functools.lru_cache(maxsize=None)
def _steps(omega: float, steps: int):
    import jax
    from jax import lax

    def run(*f):
        with jax.default_matmul_precision("highest"):
            return lax.fori_loop(0, steps, lambda _, f: tuple(_step(f, omega)), tuple(f))

    return jax.jit(run)


def steps(setup: LbmSetup, f, n: int):
    """``n`` steps on the whole periodic arrays: the nineteen populations
    (a sequence in ``NAMES``' order) after them."""
    return list(_steps(float(setup.omega), int(n))(*f))


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


def seeded_velocity(setup: LbmSetup, x, y, z, words):
    """``(ux, uy, uz)``: ``modes`` Taylor-Green modes, mode ``j`` in the plane of
    axes ``(a, b) = (j, j + 1) mod 3`` with ``n_a, n_b, n_c`` whole waves across
    the box (1..``max_waves``, seeded) and seeded phases,

        u_a =  A (k_b / k) sin(k_a x_a + p_a) cos(k_b x_b + p_b) cos(k_c x_c + p_c)
        u_b = -A (k_a / k) cos(k_a x_a + p_a) sin(k_b x_b + p_b) cos(k_c x_c + p_c)

    (``k = max(k_a, k_b)``: divergence-free), ``A = u0 / modes``: every
    component stays within ``u0`` and ``|u|`` within ``sqrt(2) u0``."""
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


def seeded_fields(setup: LbmSetup) -> dict:
    """``{f_i: f(x, y, z, words)}``: the equilibrium of ``rho0`` and the seeded
    velocity, functions of the global integer coordinate (broadcastable
    pieces) and the four seed words, so a sharded fill and a global one see
    the same expression and ``words`` can be a traced argument."""
    import jax.numpy as jnp

    def population(i):
        def f(x, y, z, words):
            ux, uy, uz = seeded_velocity(setup, x, y, z, words)
            rho = jnp.float32(setup.rho0)
            return equilibrium(rho, ux, uy, uz)[i].astype(jnp.float32)

        return f

    return {NAMES[i]: population(i) for i in range(Q)}


def global_fields(setup: LbmSetup, words) -> list:
    """The nineteen seeded populations as whole float32 arrays of ``shape``."""
    import jax.numpy as jnp

    X, Y, Z = setup.shape
    c = (jnp.arange(X)[:, None, None], jnp.arange(Y)[None, :, None], jnp.arange(Z)[None, None, :])
    w = jnp.asarray(words, dtype=jnp.uint32)
    fields = seeded_fields(setup)
    return [jnp.broadcast_to(fields[nm](*c, w), setup.shape).astype(jnp.float32) for nm in NAMES]
