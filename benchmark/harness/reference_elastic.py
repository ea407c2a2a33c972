"""The benchmark's own copy of the elastic so-8 propagator and of its seeded
fields (configuration ``elastic-so8-600``).

jax/numpy only: nothing here imports the program under test, and every
number comes from the configuration file (its sizes and its ``setup`` group).
The program may change, this may not.

Devito's ``examples/seismic/elastic`` (``ForwardOperator``): velocity-stress
on a staggered grid, first order in time, eighth order in space --

    v_i+    = damp * ( v_i    + dt * b  * sum_j D_j tau_ij )                    (stage V)
    tau_ij+ = damp * ( tau_ij + dt * ( lam * delta_ij * sum_k D_k v_k+
                                       + mu * (D_i v_j+ + D_j v_i+) ) )         (stage T)

``txx tyy tzz`` (and ``lam mu b damp``) sit at the nodes ``(i, j, k)``, ``vx``
at ``(i+1/2, j, k)``, ``vy`` at ``(i, j+1/2, k)``, ``vz`` at ``(i, j,
k+1/2)``, ``txy`` at ``(i+1/2, j+1/2, k)``, ``txz`` at ``(i+1/2, j, k+1/2)``,
``tyz`` at ``(i, j+1/2, k+1/2)``.  ``D`` is the staggered first difference
with weights ``1225/1024, -245/3072, 49/5120, -5/7168`` over ``h``: FORWARD
``sum_k c_k (f[i+k] - f[i-k+1])`` where the result sits half a cell above
its operand along that axis, BACKWARD ``sum_k c_k (f[i+k-1] - f[i-k])`` where
it sits below (``TERMS`` writes every one out).

The reference is Devito's own arrangement: beyond the array's edge it reads
0 (one zero pad per stage, slices of it).  The program has a periodic array
only; its ``frame``-cell outer frame pinned to zero makes the two the same.

**In x-slabs.**  The program's thirteen 600^3 quantities are 12.3 GB of a
16.9 GB chip, and nine whole reference wavefields with their temporaries do
not fit beside them.  A time step reads 8 planes to either side (two stages
of radius 4), so ``steps`` steps of a slab of ``width`` planes are exact when
computed on ``width + 2 x 8 x steps`` planes (``slab_halo``): what a cut edge
gets wrong travels inward 8 planes a step and never reaches the slab.  The
fields are functions of the coordinate, so a slab is seeded directly, and one
compiled program (the slab's first plane is an argument) serves every slab
and seed.  ``width`` = the whole extent with no overlap is the whole-array
reference (``selftest_elastic.py`` holds the two equal).
"""

from __future__ import annotations

import dataclasses
import functools
import math

COEFFS = (1225.0 / 1024.0, -245.0 / 3072.0, 49.0 / 5120.0, -5.0 / 7168.0)
RADIUS = 4
VELOCITIES = ("vx", "vy", "vz")
STRESSES = ("txx", "tyy", "tzz", "txy", "txz", "tyz")
WAVEFIELDS = VELOCITIES + STRESSES
#: every difference of a time step: output -> ((operand, axis, direction), ...),
#: direction +1 forward, -1 backward
TERMS = {
    "vx": (("txx", 0, +1), ("txy", 1, -1), ("txz", 2, -1)),
    "vy": (("txy", 0, -1), ("tyy", 1, +1), ("tyz", 2, -1)),
    "vz": (("txz", 0, -1), ("tyz", 1, -1), ("tzz", 2, +1)),
    "div": (("vx", 0, -1), ("vy", 1, -1), ("vz", 2, -1)),
    "txy": (("vx", 1, +1), ("vy", 0, +1)),
    "txz": (("vx", 2, +1), ("vz", 0, +1)),
    "tyz": (("vy", 2, +1), ("vz", 1, +1)),
}
NORMAL = {"txx": 0, "tyy": 1, "tzz": 2}  # which term of ``div`` takes 2 mu


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
    cfl: float  # fraction of the staggered stability limit
    vs_over_vp: float
    gardner: tuple  # rho = a (1000 vp)^b, g/cm^3

    @property
    def dt(self) -> float:
        """``cfl * h / (sqrt(3) max(vp) sum|c_k|)``, in ms."""
        return self.cfl * self.spacing / (
            math.sqrt(3.0) * self.vp_max * sum(abs(c) for c in COEFFS)
        )

    @property
    def physical(self) -> tuple:
        return tuple(n - 2 * (self.nbl + self.frame) for n in self.shape)

    @property
    def amplitude_bound(self) -> float:
        """Sup of a seeded velocity: each mode's amplitude is under 0.5."""
        return 0.5 * self.modes

    @property
    def impedance_max(self) -> float:
        a, b = self.gardner
        return a * (1000.0 * self.vp_max) ** b * self.vp_max


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
        cfl=float(s["cfl"]), vs_over_vp=float(s["vs_over_vp"]),
        gardner=(float(s["gardner_a"]), float(s["gardner_b"])),
    )


def slab_halo(steps: int) -> int:
    """Planes of overlap a slab needs on each side for ``steps`` time steps."""
    return 2 * RADIUS * steps


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


def model_profiles(s: Setup, z, words) -> dict:
    """``lam = rho (vp^2 - 2 vs^2)``, ``mu = rho vs^2``, ``b = 1/rho`` of the
    layered model: ``nlayers`` flat layers along z from ``vp_min`` to
    ``vp_max``, interfaces at seeded depths, edge-extended through sponge and
    frame; ``vs = vs_over_vp vp``, Gardner's ``rho``."""
    import jax.numpy as jnp

    nz, L = s.physical[2], s.nlayers
    zc = jnp.clip(z - (s.frame + s.nbl), 0, nz - 1)
    layer = jnp.zeros_like(zc)
    for i in range(1, L):
        depth = jnp.round(nz * (i + 0.7 * (_unit(words, i) - 0.5)) / L).astype(zc.dtype)
        layer = layer + (zc >= depth).astype(zc.dtype)
    vp = s.vp_min + (s.vp_max - s.vp_min) * layer.astype(jnp.float32) / (L - 1)
    rho = s.gardner[0] * (1000.0 * vp) ** s.gardner[1]
    vs = s.vs_over_vp * vp
    return {"lam": rho * (vp * vp - 2.0 * (vs * vs)), "mu": rho * (vs * vs), "b": 1.0 / rho}


def damp_profile(s: Setup, c, axis: int):
    """One axis' share of what Devito's MASK sponge takes off 1
    (``initialize_damp(abc_type="mask")``): over ``nbl`` cells from each outer
    edge ``pos = (nbl - i + 1)/nbl``, ``val = coeff (pos - sin(2 pi pos)/(2
    pi))``, ``coeff = 1.5 ln(1000)/nbl``, as ``val / spacing``; 0 elsewhere."""
    import jax.numpy as jnp

    nbl = max(s.nbl, 1)
    coeff = 1.5 * math.log(1.0 / 0.001) / nbl
    d = c - s.frame
    n_pad = s.shape[axis] - 2 * s.frame
    total = 0.0
    for i in (d, n_pad - 1 - d):
        pos = (s.nbl - i + 1).astype(jnp.float32) / nbl
        val = coeff * (pos - jnp.sin(2 * math.pi * pos) / (2 * math.pi))
        total = total + jnp.where((i >= 0) & (i < s.nbl), val / s.spacing, 0.0)
    return total


def damp(s: Setup, x, y, z):
    """The mask: 1 minus the three axes' shares, taken off in x, y, z order."""
    return ((1.0 - damp_profile(s, x, 0)) - damp_profile(s, y, 1)) - damp_profile(s, z, 2)


def velocity(s: Setup, component: int, x, y, z, words):
    """``modes`` product modes of whole cycles across the physical extent
    under the Hann window of every axis, zero outside the physical region;
    each component draws its own numbers (salt ``64 (component + 1)``)."""
    import jax.numpy as jnp

    salt = 64 * (component + 1)
    inner = [(c - (s.frame + s.nbl)).astype(jnp.float32) for c in (x, y, z)]
    window = 1.0
    for p, n in zip(inner, s.physical):
        hann = jnp.sin(math.pi * (p + 0.5) / n) ** 2
        window = window * jnp.where((p >= 0) & (p < n), hann, 0.0)
    total = 0.0
    for j in range(s.modes):
        mode = 0.25 + 0.25 * _unit(words, salt + 8 * j)
        for axis, (p, n) in enumerate(zip(inner, s.physical)):
            cycles = 1.0 + jnp.floor(_unit(words, salt + 1 + 8 * j + axis) * max(1, min(8, n // 4)))
            phase = 2 * math.pi * _unit(words, salt + 4 + 8 * j + axis)
            mode = mode * jnp.cos((2 * math.pi * cycles / n) * p + phase)
        total = total + mode
    return total * window


def seeded_fields(s: Setup) -> dict:
    """``{quantity: f(x, y, z, words)}`` for all thirteen: functions of the
    global integer coordinate (broadcastable pieces) and the seed's four
    words, which may be traced -- one compiled fill serves every seed."""
    import jax.numpy as jnp

    out = {
        v: (lambda x, y, z, w, i=i: velocity(s, i, x, y, z, w)) for i, v in enumerate(VELOCITIES)
    }
    out.update({t: (lambda x, y, z, w: jnp.zeros((), jnp.float32)) for t in STRESSES})
    out.update({
        m: (lambda x, y, z, w, m=m: model_profiles(s, z, w)[m]) for m in ("lam", "mu", "b")
    })
    out["damp"] = lambda x, y, z, w: damp(s, x, y, z)
    return out


# --- the update --------------------------------------------------------------------


def _staggered(at, direction: int):
    """``h D f`` from ``at(offset)``; nearest pair first."""
    acc = None
    for k in range(1, RADIUS + 1):
        pair = (at(k) - at(1 - k)) if direction > 0 else (at(k - 1) - at(-k))
        acc = COEFFS[k - 1] * pair if acc is None else acc + COEFFS[k - 1] * pair
    return acc


def _time_step(s: Setup, wave: dict, model: dict, pinned):
    """One time step of the arrays in ``wave`` (any box of the grid): reads
    beyond the box's edge give 0, ``pinned`` (True on the frame and beyond)
    cells of every wavefield are set to 0."""
    import jax.numpy as jnp
    from jax import lax

    dt_h = s.dt / s.spacing
    shape = wave["vx"].shape

    def differ(fields):
        # a read beyond the edge gives 0, as Devito's halo does: ONE zero pad
        # per operand, slices of it (they fuse into the update)
        around = {q: jnp.pad(a, RADIUS) for q, a in fields.items()}

        def diff(q, axis, direction):
            def at(o):
                start = [RADIUS] * 3
                start[axis] += o
                return lax.slice(around[q], start, [a + n for a, n in zip(start, shape)])

            return _staggered(at, direction)

        return diff

    wave = dict(wave)
    diff = differ({q: wave[q] for q in STRESSES})
    new = {}
    for v in VELOCITIES:
        a, b, c = TERMS[v]
        rhs = (diff(*a) + diff(*b)) + diff(*c)
        new[v] = model["damp"] * (wave[v] + (dt_h * model["b"]) * rhs)
    wave.update({v: jnp.where(pinned, 0.0, a) for v, a in new.items()})
    diff = differ({q: wave[q] for q in VELOCITIES})
    new = {}
    for t in STRESSES:
        if t in NORMAL:
            a, b, c = TERMS["div"]
            div = (diff(*a) + diff(*b)) + diff(*c)
            rhs = model["lam"] * div + (2.0 * model["mu"]) * diff(*TERMS["div"][NORMAL[t]])
        else:
            a, b = TERMS[t]
            rhs = model["mu"] * (diff(*a) + diff(*b))
        new[t] = model["damp"] * (wave[t] + dt_h * rhs)
    wave.update({t: jnp.where(pinned, 0.0, a) for t, a in new.items()})
    return wave


@functools.lru_cache(maxsize=None)
def _slab(s: Setup, steps: int, width: int, halo: int):
    import jax
    import jax.numpy as jnp
    from jax import lax

    X, Y, Z = s.shape

    def run(words, first):
        # global coordinates of the slab with its overlap; planes outside the
        # grid hold Devito's zero halo (the frame test below covers them)
        x = (first - halo + jnp.arange(width + 2 * halo, dtype=jnp.int32))[:, None, None]
        y = jnp.arange(Y, dtype=jnp.int32)[None, :, None]
        z = jnp.arange(Z, dtype=jnp.int32)[None, None, :]
        box = (width + 2 * halo, Y, Z)
        pinned = False
        for c, n in zip((x, y, z), s.shape):
            pinned = pinned | (c < s.frame) | (c >= n - s.frame)
        model = dict(model_profiles(s, z, words), damp=damp(s, x, y, z))
        wave = {
            v: jnp.where(pinned, 0.0, jnp.broadcast_to(velocity(s, i, x, y, z, words), box))
            for i, v in enumerate(VELOCITIES)
        }
        wave.update({t: jnp.zeros(box, jnp.float32) for t in STRESSES})
        with jax.default_matmul_precision("highest"):
            wave = lax.fori_loop(0, steps, lambda _, w: _time_step(s, w, model, pinned), wave)
        return tuple(wave[q][halo : halo + width] for q in WAVEFIELDS)

    return jax.jit(run)


def reference_slab(s: Setup, steps: int, words, first: int, width: int, halo: int = None):
    """The nine wavefields (``WAVEFIELDS``' order) on planes ``first ..
    first + width`` of the grid after ``steps`` time steps of the seeded
    fields.  ``halo`` planes of overlap are computed on either side and
    dropped (``slab_halo(steps)`` unless given; 0 is right only for a slab
    that is the whole extent)."""
    import numpy as np

    halo = slab_halo(steps) if halo is None else halo
    return _slab(s, steps, width, halo)(np.asarray(words, dtype=np.uint32), np.int32(first))


def slab_starts(extent: int, width: int) -> list:
    """First planes of the slabs that cover ``extent`` at ``width`` planes
    each; the last slab is moved back to end at the extent (it overlaps)."""
    width = min(width, extent)
    return sorted({min(f, extent - width) for f in range(0, extent, width)})


@functools.lru_cache(maxsize=None)
def _slab_errors(shape, width: int, bound: float):
    import jax
    import jax.numpy as jnp
    from jax import lax

    def run(raw, lo, first, want):
        """max |got - want| over one slab of one quantity, and the slab's
        cells that are not finite or beyond ``bound``; ``raw`` is the
        program's shell-carrying array, ``lo`` its shell width."""
        at = [jnp.int32(lo) + first.astype(jnp.int32), jnp.int32(lo), jnp.int32(lo)]
        got = lax.dynamic_slice(raw, at, (width, shape[1], shape[2])).astype(jnp.float32)
        d = jnp.abs(got - want.astype(jnp.float32))
        err = jnp.max(jnp.where(jnp.isnan(d), jnp.inf, d))
        bad = jnp.sum((~jnp.isfinite(got)) | (jnp.abs(got) > bound))
        return err, bad

    return jax.jit(run, static_argnums=1)


def slab_errors(raw, lo: int, shape, first: int, want, bound: float):
    """``(max_abs_err, bad_cells)`` of planes ``first .. first + width`` of
    one quantity against ``want`` (a ``reference_slab`` array)."""
    import numpy as np

    err, bad = _slab_errors(tuple(shape), int(want.shape[0]), float(bound))(
        raw, int(lo), np.int32(first), want
    )
    return float(err), int(bad)


def sup(arr) -> float:
    """max |arr| of a device array."""
    import jax.numpy as jnp

    return float(jnp.max(jnp.abs(arr)))


@functools.lru_cache(maxsize=None)
def _state(shape, frame: int, bound: float):
    import jax
    import jax.numpy as jnp
    from jax import lax

    def run(raw, lo):
        """Of the program's shell-carrying array: interior cells not finite
        or beyond ``bound``, and cells of the outer frame not exactly 0."""
        a = lax.slice(raw, (lo,) * 3, tuple(lo + n for n in shape)).astype(jnp.float32)
        pinned = False
        for axis, n in enumerate(shape):
            c = jnp.arange(n).reshape([-1 if i == axis else 1 for i in range(3)])
            pinned = pinned | (c < frame) | (c >= n - frame)
        bad = jnp.sum((~jnp.isfinite(a)) | (jnp.abs(a) > bound))
        return bad, jnp.sum(pinned & (a != 0.0))

    return jax.jit(run, static_argnums=1)


def state_counts(s: Setup, raw, lo: int, bound: float) -> tuple:
    """``(bad cells, non-zero frame cells)`` of one wavefield of the program."""
    bad, frame = _state(tuple(s.shape), s.frame, float(bound))(raw, int(lo))
    return int(bad), int(frame)
