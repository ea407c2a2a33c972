"""The benchmark's own copy of the acoustic so-8 propagator and of its seeded
fields (configuration ``acoustic-so8-600``).

jax/numpy only: nothing here imports the program under test, and every
number comes from the configuration file (its sizes and its ``setup`` group).
The program may change, this may not.

    m u_tt + eta u_t = laplace(u),   m = 1/vp^2,   eta = sponge damping
    u+ = [ 2 m u - (m - s) u- + dt^2 L8(u) ] / (m + s),     s = eta dt / 2
    L8(u) = (1/h^2) sum_axes [ c0 u + sum_{k=1..4} c_k (u(+k e_a) + u(-k e_a)) ]

Devito's ``examples/seismic/acoustic`` (``iso_stencil``, kernel ``OT2``) at
space order 8.  The grid is physical + ``nbl`` sponge cells + a ``frame``-cell
outer frame pinned to zero on every side.  The reference is Devito's own
arrangement: beyond the array's edge it reads 0.  The program has a periodic
array only, and the frame is what makes the two the same: its read across the
seam lands in the other side's frame.  Every cell is compared, so a frame that
did not hold, or a halo that read anything else, shows.

Every seeded field is made of 1-D profiles along the axes, so the reference
carries ``m`` and ``damp`` as three short vectors and builds them inside the
update: beside the program's 7.6 GB it holds the two time levels only.
"""

from __future__ import annotations

import dataclasses
import functools
import math

COEFFS = (-205.0 / 72.0, 8.0 / 5.0, -1.0 / 5.0, 8.0 / 315.0, -1.0 / 560.0)
RADIUS = 4


@dataclasses.dataclass(frozen=True)
class Setup:
    """The numbers of one run, from the configuration alone (``setup_from``)."""

    shape: tuple
    frame: int
    nbl: int
    spacing: float  # m
    vp_min: float  # km/s = m/ms
    vp_max: float
    nlayers: int
    modes: int
    cfl: float

    @property
    def dt(self) -> float:
        """``cfl * h / max(vp)``, Devito's ``critical_dt``, in ms."""
        return self.cfl * self.spacing / self.vp_max

    @property
    def dt2_h2(self) -> float:
        return self.dt * self.dt / (self.spacing * self.spacing)

    @property
    def physical(self) -> tuple:
        return tuple(n - 2 * (self.nbl + self.frame) for n in self.shape)

    @property
    def amplitude_bound(self) -> float:
        """Sup of the seeded wavefield: each mode's amplitude is under 0.5."""
        return 0.5 * self.modes


def setup_from(config: dict, shape) -> Setup:
    """``shape`` is given apart because a rehearsal shrinks it, and ``nbl``
    with it (at least 4 physical cells are kept)."""
    s = config["setup"]
    shape = tuple(int(n) for n in shape)
    frame = int(s["frame"])
    return Setup(
        shape=shape, frame=frame,
        nbl=max(0, min(int(config["nbl"]), (min(shape) - 2 * frame - 4) // 2)),
        spacing=float(s["spacing_m"]), vp_min=float(s["vp_min_km_s"]),
        vp_max=float(s["vp_max_km_s"]), nlayers=int(s["nlayers"]), modes=int(s["modes"]),
        cfl=float(s["cfl"]),
    )


def _unit(words, i: int):
    """A float32 in [0, 1) from the seed's words and a salt."""
    import jax.numpy as jnp

    u = jnp.uint32
    w = jnp.asarray(words, dtype=u)
    h = w[i % 4] ^ u((0x9E3779B9 * (i + 1)) & 0xFFFFFFFF)
    h = (h ^ (h >> 16)) * u(0x7FEB352D)
    h = (h ^ (h >> 15)) * u(0x846CA68B)
    h = h ^ (h >> 16)
    return (h >> 8).astype(jnp.float32) * jnp.float32(1.0 / (1 << 24))


# --- 1-D profiles: each takes the integer coordinate along ONE axis (any shape) ----


def m_profile(s: Setup, z, words):
    """``1/vp^2`` of the layered model: ``nlayers`` flat layers along z from
    ``vp_min`` to ``vp_max``, interfaces at seeded depths, edge-extended
    through the sponge and the frame."""
    import jax.numpy as jnp

    nz, L = s.physical[2], s.nlayers
    zc = jnp.clip(z - (s.frame + s.nbl), 0, nz - 1)
    layer = jnp.zeros_like(zc)
    for i in range(1, L):
        depth = jnp.round(nz * (i + 0.7 * (_unit(words, i) - 0.5)) / L).astype(zc.dtype)
        layer = layer + (zc >= depth).astype(zc.dtype)
    vp = s.vp_min + (s.vp_max - s.vp_min) * layer.astype(jnp.float32) / (L - 1)
    return 1.0 / (vp * vp)


def damp_profile(s: Setup, c, axis: int):
    """One axis' share of Devito's sponge (``initialize_damp``): over ``nbl``
    cells from each outer edge ``pos = (nbl - i + 1)/nbl``, ``val = coeff
    (pos - sin(2 pi pos)/(2 pi))``, ``coeff = 1.5 ln(1000)/nbl``, added as
    ``val / spacing``; 0 elsewhere (frame included: the kernel pins it)."""
    import jax.numpy as jnp

    if s.nbl == 0:
        return jnp.zeros(c.shape, jnp.float32)
    coeff = 1.5 * math.log(1.0 / 0.001) / s.nbl
    d = c - s.frame
    n_pad = s.shape[axis] - 2 * s.frame
    total = 0.0
    for i in (d, n_pad - 1 - d):
        pos = (s.nbl - i + 1).astype(jnp.float32) / s.nbl
        val = coeff * (pos - jnp.sin(2 * math.pi * pos) / (2 * math.pi))
        total = total + jnp.where((i >= 0) & (i < s.nbl), val / s.spacing, 0.0)
    return total


def wave_profiles(s: Setup, c, axis: int, words):
    """Per mode j the factor ``cos(k_j p + phase_j)`` along this axis (whole
    cycles across the physical extent), times the Hann window that is zero
    outside the physical region; and ``k_j`` in 1/m."""
    import jax.numpy as jnp

    n = s.physical[axis]
    p = c - (s.frame + s.nbl)
    hann = jnp.sin(math.pi * (p.astype(jnp.float32) + 0.5) / n) ** 2
    window = jnp.where((p >= 0) & (p < n), hann, 0.0)
    factors, ks = [], []
    for j in range(s.modes):
        cycles = 1.0 + jnp.floor(_unit(words, 17 + 8 * j + axis) * max(1, min(8, n // 4)))
        k = 2 * math.pi * cycles / n
        phase = 2 * math.pi * _unit(words, 20 + 8 * j + axis)
        factors.append(jnp.cos(k * p.astype(jnp.float32) + phase) * window)
        ks.append(k / s.spacing)
    return factors, ks


def wavefield(s: Setup, x, y, z, words, time_shift: float):
    """``modes`` standing product modes ``a_j cos(theta_j - omega_j t) X_j Y_j
    Z_j`` with ``omega_j = mean(vp) |k_j|``, each under the Hann window of
    every axis: band-limited, zero outside the physical region."""
    import jax.numpy as jnp

    per_axis = [wave_profiles(s, c, axis, words) for axis, c in enumerate((x, y, z))]
    v_mean = 0.5 * (s.vp_min + s.vp_max)
    total = 0.0
    for j in range(s.modes):
        amp = 0.25 + 0.25 * _unit(words, 16 + 8 * j)
        omega = v_mean * jnp.sqrt(sum(per_axis[a][1][j] ** 2 for a in range(3)))
        theta = 2 * math.pi * _unit(words, 23 + 8 * j)
        coef = amp * jnp.cos(theta - omega * time_shift)
        total = total + (coef * per_axis[0][0][j]) * per_axis[1][0][j] * per_axis[2][0][j]
    return total


def seeded_fields(s: Setup) -> dict:
    """``{quantity: f(x, y, z, words)}``: functions of the global integer
    coordinate (broadcastable pieces) and the seed's four words, which may be
    traced -- one compiled fill serves every seed."""
    return {
        "u": lambda x, y, z, w: wavefield(s, x, y, z, w, 0.0),
        "u_prev": lambda x, y, z, w: wavefield(s, x, y, z, w, -s.dt),
        "m": lambda x, y, z, w: m_profile(s, z, w),
        "damp": lambda x, y, z, w: (
            damp_profile(s, x, 0) + damp_profile(s, y, 1)
        ) + damp_profile(s, z, 2),
    }


# --- the update --------------------------------------------------------------------


def _coords(shape):
    import jax.numpy as jnp

    X, Y, Z = shape
    return (jnp.arange(X)[:, None, None], jnp.arange(Y)[None, :, None], jnp.arange(Z)[None, None, :])


def _frame(s: Setup):
    """True on the ``frame`` outer cells of every side (broadcast pieces)."""
    out = False
    for c, n in zip(_coords(s.shape), s.shape):
        out = out | (c < s.frame) | (c >= n - s.frame)
    return out


@functools.lru_cache(maxsize=None)
def _reference(s: Setup, steps: int, sharding):
    import jax
    import jax.numpy as jnp
    from jax import lax

    def run(words):
        x, y, z = _coords(s.shape)
        f = seeded_fields(s)
        u0 = jnp.broadcast_to(f["u"](x, y, z, words), s.shape).astype(jnp.float32)
        p0 = jnp.broadcast_to(f["u_prev"](x, y, z, words), s.shape).astype(jnp.float32)
        # the model fields stay three short vectors; built inside the update
        mz = m_profile(s, z, words)
        dx, dy, dz = (damp_profile(s, c, a) for a, c in enumerate((x, y, z)))

        def body(_, carry):
            u, u_prev = carry
            m = mz
            half = ((dx + dy) + dz) * (0.5 * s.dt)
            # a read beyond the array's edge gives 0, as Devito's halo does
            # (on the program's periodic array it lands in the other side's
            # frame and reads the same 0).  Zero pad + slices fuse into the
            # update; 24 jnp.rolls compile to 24 whole arrays alive at once
            # (22.9 GB at 600^3 on the chip's compiler, against 2.8 GB)
            around = jnp.pad(u, RADIUS)

            def at(axis, k):
                start = [RADIUS] * 3
                start[axis] += k
                return lax.slice(around, start, [a + n for a, n in zip(start, s.shape)])

            acc = (3.0 * COEFFS[0]) * u
            for k in range(1, RADIUS + 1):
                acc = acc + COEFFS[k] * (
                    ((at(0, k) + at(0, -k)) + (at(1, k) + at(1, -k))) + (at(2, k) + at(2, -k))
                )
            new = (2.0 * m * u - (m - half) * u_prev + s.dt2_h2 * acc) / (m + half)
            frame = _frame(s)
            return jnp.where(frame, 0.0, new), jnp.where(frame, 0.0, u)

        with jax.default_matmul_precision("highest"):
            return lax.fori_loop(0, steps, body, (u0, p0))

    return jax.jit(run, out_shardings=(sharding, sharding))


def reference(s: Setup, steps: int, sharding, words):
    """``(u, u_prev)`` after ``steps`` updates of the seeded fields on the
    whole periodic array, frame pinned to zero; one compiled program serves
    every seed."""
    import numpy as np

    return _reference(s, steps, sharding)(np.asarray(words, dtype=np.uint32))


@functools.lru_cache(maxsize=None)
def _frame_nonzero(s: Setup):
    import jax
    import jax.numpy as jnp

    return jax.jit(lambda a: jnp.sum((_frame(s) & (a.astype(jnp.float32) != 0.0)).astype(jnp.int32)))


def frame_nonzero(s: Setup, arr) -> int:
    """Cells of the outer frame of ``arr`` that are not exactly 0."""
    return int(_frame_nonzero(s)(arr))
