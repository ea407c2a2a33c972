"""Plain reference of the isotropic acoustic propagator, space order 8.

The forward kernel of seismic modelling, RTM and FWI as Devito's
``examples/seismic/acoustic`` writes it (``iso_stencil``, kernel ``OT2``:
second order in time, here eighth order in space) and Minimod's "acoustic
isotropic" (arXiv:1807.03032, arXiv:2007.06048):

    m u_tt + eta u_t = laplace(u),   m = 1/vp^2,   eta = sponge damping
    u+ = [ 2 m u - (m - s) u- + dt^2 L8(u) ] / (m + s),     s = eta dt / 2
    L8(u) = (1/h^2) sum_axes [ c0 u + sum_{k=1..4} c_k (u(+k e_a) + u(-k e_a)) ]

in ``jax.numpy`` and float32, ``jnp.roll`` on the global array, under
``jax.default_matmul_precision("highest")``.  It imports nothing of ``ops/``,
``domain.py`` or the model (``models/acoustic.py`` imports the grid and the
seeded fields from HERE, never the other way).

Grid: ``physical`` cells + ``nbl`` sponge cells on every side (Devito's padded
grid) + a ``FRAME``-cell outer frame pinned to zero.  The frame is Devito's
zero halo -- a Dirichlet edge outside the sponge -- on a runtime that is
periodic only: a read of distance <= 4 across the periodic seam lands in the
other side's frame and reads the 0 that Devito's halo holds.
``steps_padded`` is the same update on the frameless, zero-padded array
(Devito's own arrangement); the two agree bit for bit (tests/test_acoustic.py).

Departures from Devito, all of them stated again in the benchmark's
configuration: no source injection and no receivers (a seeded band-limited
``u``, ``u_prev`` inside the physical region stands for the shot); the
damping profile and the layered ``vp`` as remembered from
``examples/seismic/model.py`` (there is no network here).

The sum is grouped by distance, ``c_k`` times the six neighbours at distance
``k`` -- the order ``AcousticWave._kernel`` uses too, so the two differ by
the compilers' roundings only.
"""

from __future__ import annotations

import dataclasses
import functools
import math

#: central second-derivative weights of order 8 (c0, c1..c4)
COEFFS = (-205.0 / 72.0, 8.0 / 5.0, -1.0 / 5.0, 8.0 / 315.0, -1.0 / 560.0)
RADIUS = 4
#: outer cells pinned to zero on every side (= the read distance)
FRAME = RADIUS
#: Devito's critical-dt coefficient for the 3D acoustic OT2 kernel
CFL = 0.38
#: product modes in the seeded initial wavefield; each has amplitude < 0.5
MODES = 4
AMPLITUDE_BOUND = 0.5 * MODES


@dataclasses.dataclass(frozen=True)
class AcousticGrid:
    """The numbers of one set-up: ``shape`` is the whole periodic array
    (physical + 2 nbl + 2 FRAME per axis), ``spacing`` in m, ``vp`` in km/s
    (= m/ms), time in ms -- Devito's units, so ``m = 1/vp^2`` is in ms^2/m^2."""

    shape: tuple
    nbl: int = 40
    spacing: float = 20.0
    vp_min: float = 1.5
    vp_max: float = 3.5
    nlayers: int = 6

    def __post_init__(self):
        if min(self.physical) < 1:
            raise ValueError(
                f"shape {self.shape} leaves no physical cell inside nbl={self.nbl} "
                f"and the {FRAME}-cell frame"
            )

    @property
    def physical(self) -> tuple:
        return tuple(n - 2 * (self.nbl + FRAME) for n in self.shape)

    @property
    def dt(self) -> float:
        """``CFL * h / max(vp)`` (Devito's ``critical_dt``), in ms."""
        return CFL * self.spacing / self.vp_max

    @property
    def dt2_h2(self) -> float:
        return self.dt * self.dt / (self.spacing * self.spacing)


# --- seeded fields: functions of the global coordinate and four seed words ------


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


def layered_vp(grid: AcousticGrid, z, words):
    """``vp`` (km/s) of the layered model at integer depth ``z``: ``nlayers``
    flat layers along z, ``vp_min`` at the top to ``vp_max`` at the bottom,
    each interface at a seeded depth; edge-extended through the sponge and
    the frame (Devito pads its model fields the same way)."""
    import jax.numpy as jnp

    nz, L = grid.physical[2], grid.nlayers
    zc = jnp.clip(z - (FRAME + grid.nbl), 0, nz - 1)
    layer = jnp.zeros_like(zc)
    for i in range(1, L):
        depth = jnp.round(nz * (i + 0.7 * (_unit(words, i) - 0.5)) / L).astype(zc.dtype)
        layer = layer + (zc >= depth).astype(zc.dtype)
    return grid.vp_min + (grid.vp_max - grid.vp_min) * layer.astype(jnp.float32) / (L - 1)


def m_field(grid: AcousticGrid):
    """Squared slowness ``1/vp^2`` of the layered model (``layered_vp``)."""

    def f(x, y, z, words):
        del x, y
        vp = layered_vp(grid, z, words)
        return 1.0 / (vp * vp)

    return f


def damp_field(grid: AcousticGrid):
    """Devito's sponge (``examples/seismic/model.py initialize_damp``, as
    remembered): per axis and side, over ``nbl`` cells counted from the
    outer edge, ``pos = (nbl - i + 1)/nbl``, ``val = dampcoeff (pos -
    sin(2 pi pos)/(2 pi))`` with ``dampcoeff = 1.5 ln(1000)/nbl``, added as
    ``val / spacing``; 0 in the physical region.  The frame holds 0 (the
    kernel pins ``u`` there whatever ``damp`` says)."""

    def f(x, y, z, words):
        import jax.numpy as jnp

        del words
        nbl = grid.nbl
        coeff = 1.5 * math.log(1.0 / 0.001) / nbl
        total = 0.0
        for axis, c in enumerate((x, y, z)):
            d = c - FRAME  # index on Devito's padded grid
            n_pad = grid.shape[axis] - 2 * FRAME
            for i in (d, n_pad - 1 - d):  # distance from the left / right outer edge
                pos = (nbl - i + 1).astype(jnp.float32) / nbl
                val = coeff * (pos - jnp.sin(2 * math.pi * pos) / (2 * math.pi))
                total = total + jnp.where((i >= 0) & (i < nbl), val / grid.spacing, 0.0)
        return total

    return f


def _wavefield(grid: AcousticGrid, time_shift: float):
    """``MODES`` standing product modes under a Hann window over the
    physical region, zero outside it: mode j is ``a_j cos(theta_j - omega_j
    t) X_j(x) Y_j(y) Z_j(z)`` with whole cycles across the physical extent
    and ``omega_j = mean(vp) |k_j|``.  Built from 1-D profiles, so the 3-D
    work is multiplies and adds."""

    def f(x, y, z, words):
        import jax.numpy as jnp

        # per axis: the coordinate inside the physical region, as float
        inner = [(c - (FRAME + grid.nbl)).astype(jnp.float32) for c in (x, y, z)]
        window = 1.0
        for p, n in zip(inner, grid.physical):
            hann = jnp.sin(math.pi * (p + 0.5) / n) ** 2
            window = window * jnp.where((p >= 0) & (p < n), hann, 0.0)
        total = 0.0
        v_mean = 0.5 * (grid.vp_min + grid.vp_max)
        for j in range(MODES):
            amp = 0.25 + 0.25 * _unit(words, 16 + 8 * j)
            mode, k2 = amp, 0.0
            for axis, (p, n) in enumerate(zip(inner, grid.physical)):
                cycles = 1.0 + jnp.floor(_unit(words, 17 + 8 * j + axis) * max(1, min(8, n // 4)))
                k = 2 * math.pi * cycles / n  # per cell
                phase = 2 * math.pi * _unit(words, 20 + 8 * j + axis)
                mode = mode * jnp.cos(k * p + phase)
                k2 = k2 + (k / grid.spacing) ** 2
            theta = 2 * math.pi * _unit(words, 23 + 8 * j)
            total = total + jnp.cos(theta - v_mean * jnp.sqrt(k2) * time_shift) * mode
        return total * window

    return f


def seeded_fields(grid: AcousticGrid) -> dict:
    """``{quantity: f(x, y, z, words)}`` for ``u``, ``u_prev``, ``m`` and
    ``damp``: functions of the global integer coordinate (broadcastable
    pieces) and the four seed words, so a sharded fill and a global one see
    the same expression and ``words`` can be a traced argument."""
    return {
        "u": _wavefield(grid, 0.0),
        "u_prev": _wavefield(grid, -grid.dt),
        "m": m_field(grid),
        "damp": damp_field(grid),
    }


def global_fields(grid: AcousticGrid, words) -> dict:
    """The four seeded fields as whole float32 arrays of ``grid.shape``."""
    import jax.numpy as jnp

    X, Y, Z = grid.shape
    c = (jnp.arange(X)[:, None, None], jnp.arange(Y)[None, :, None], jnp.arange(Z)[None, None, :])
    w = jnp.asarray(words, dtype=jnp.uint32)
    return {
        k: jnp.broadcast_to(f(*c, w), grid.shape).astype(jnp.float32)
        for k, f in seeded_fields(grid).items()
    }


# --- the update ---------------------------------------------------------------------


def _update(u, u_prev, m, damp, shifted, grid: AcousticGrid):
    """``u+`` from the equations above; ``shifted(axis, k)`` is ``u`` read at
    ``+k`` along ``axis``."""
    acc = (3.0 * COEFFS[0]) * u
    for k in range(1, RADIUS + 1):
        acc = acc + COEFFS[k] * (
            ((shifted(0, k) + shifted(0, -k)) + (shifted(1, k) + shifted(1, -k)))
            + (shifted(2, k) + shifted(2, -k))
        )
    s = damp * (0.5 * grid.dt)
    return (2.0 * m * u - (m - s) * u_prev + grid.dt2_h2 * acc) / (m + s)


def frame_mask(shape):
    """True on the ``FRAME`` outer cells of every side."""
    import jax.numpy as jnp

    out = False
    for axis, n in enumerate(shape):
        c = jnp.arange(n).reshape([-1 if a == axis else 1 for a in range(3)])
        out = out | (c < FRAME) | (c >= n - FRAME)
    return out


@functools.lru_cache(maxsize=None)
def _steps_framed(grid: AcousticGrid, steps: int):
    import jax
    import jax.numpy as jnp
    from jax import lax

    def run(u, u_prev, m, damp):
        frame = frame_mask(grid.shape)

        def body(_, carry):
            u, u_prev = carry
            new = _update(u, u_prev, m, damp, lambda a, k: jnp.roll(u, -k, a), grid)
            return jnp.where(frame, 0.0, new), jnp.where(frame, 0.0, u)

        with jax.default_matmul_precision("highest"):
            return lax.fori_loop(0, steps, body, (u, u_prev))

    return jax.jit(run)


def steps_framed(grid: AcousticGrid, u, u_prev, m, damp, steps: int):
    """``steps`` updates on the whole periodic array with its explicit zero
    frame: ``(u, u_prev)`` after them."""
    return _steps_framed(grid, steps)(u, u_prev, m, damp)


@functools.lru_cache(maxsize=None)
def _steps_padded(grid: AcousticGrid, steps: int):
    import jax
    import jax.numpy as jnp
    from jax import lax

    def run(u, u_prev, m, damp):
        def body(_, carry):
            u, u_prev = carry
            halo = jnp.pad(u, RADIUS)  # Devito's zero halo

            def shifted(axis, k):
                start = [RADIUS] * 3
                start[axis] += k
                return lax.dynamic_slice(halo, start, u.shape)

            return _update(u, u_prev, m, damp, shifted, grid), u

        with jax.default_matmul_precision("highest"):
            return lax.fori_loop(0, steps, body, (u, u_prev))

    return jax.jit(run)


def steps_padded(grid: AcousticGrid, u, u_prev, m, damp, steps: int):
    """The same update on the FRAMELESS array (``shape - 2 FRAME`` per axis:
    Devito's padded grid), reading zeros beyond its edge -- what the frame
    stands for.  Arguments are the framed arrays' interiors."""
    return _steps_padded(grid, steps)(u, u_prev, m, damp)


def interior(a):
    """A framed array without its frame."""
    return a[FRAME:-FRAME, FRAME:-FRAME, FRAME:-FRAME]
