"""Plain reference of the isotropic elastic propagator, space order 8.

Devito's ``examples/seismic/elastic`` (``ForwardOperator``,
``elastic_stencil``; ``benchmarks/user/benchmark.py -P elastic -so 8``), the
second of the four propagators of arXiv:1807.03032, and Minimod's "elastic
isotropic": the velocity-stress scheme on a staggered grid (Virieux 1986,
Levander 1988), first order in time -- every quantity is updated in place,
no older level is kept -- and eighth order in space:

    v_i+    = damp * ( v_i    + dt * b  * sum_j D_j tau_ij )                    (stage V)
    tau_ij+ = damp * ( tau_ij + dt * ( lam * delta_ij * sum_k D_k v_k+
                                       + mu * (D_i v_j+ + D_j v_i+) ) )         (stage T)

in ``jax.numpy`` and float32, ``jnp.roll`` on the whole periodic array, under
``jax.default_matmul_precision("highest")``.  Stage T reads the velocities
stage V has just written.  It imports nothing of ``ops/``, ``domain.py`` or
the model (``models/elastic.py`` imports the numbers and the seeded fields
from HERE, never the other way); the grid and the seed hash are the acoustic
reference's.

Where a quantity sits (index ``(i, j, k)`` of its array, in cells):

    txx tyy tzz   (i,     j,     k    )   the nodes; lam, mu, b, damp too
    vx            (i+1/2, j,     k    )
    vy            (i,     j+1/2, k    )
    vz            (i,     j,     k+1/2)
    txy           (i+1/2, j+1/2, k    )
    txz           (i+1/2, j,     k+1/2)
    tyz           (i,     j+1/2, k+1/2)

``D`` is the eighth-order staggered first difference over ``h``, weights
``C = (1225/1024, -245/3072, 49/5120, -5/7168)``:

    forward   D+ f [i] = sum_k C_k (f[i+k]   - f[i-k+1]) / h    offsets -3..+4
    backward  D- f [i] = sum_k C_k (f[i+k-1] - f[i-k]  ) / h    offsets -4..+3

forward where the result sits half a cell ABOVE its operand along that axis
(``D_x txx`` for ``vx``), backward where it sits below (``D_x vx`` for
``txx``): ``STAGE_V`` and ``STAGE_T`` below write every term out.

Departures from Devito, all of them stated again in the benchmark's
configuration: no source injection and no receivers (a seeded band-limited
packet in the three velocities stands for the shot, the stresses start at
zero); ``lam``, ``mu`` and ``b`` are read at the nodes, without the averaging
to the staggered points a production code may add (Devito's example reads
them where they are stored too); ``damp`` is Devito's MASK flavour of its
sponge (1 in the physical region, falling below 1 through the layer), as
remembered from ``examples/seismic/model.py``; ``vs = vp / 2`` and Gardner's
``rho = 0.31 (1000 vp)^(1/4)`` as its ``demo_model('layers-elastic')`` has
them, as remembered; ``dt`` is 0.95 of the staggered stability limit
``h / (sqrt(3) max(vp) sum|C_k|)`` (``SeismicModel._cfl_coeff``, as
remembered).  The outer ``FRAME`` cells of every wavefield are pinned to
zero: Devito's zero halo on a runtime that is periodic only
(``acoustic_reference``'s account).
"""

from __future__ import annotations

import functools
import math

from stencil_tpu.models.acoustic_reference import (
    FRAME,
    MODES,
    RADIUS,
    AcousticGrid,
    _unit,
    frame_mask,
    layered_vp,
    interior,  # noqa: F401 -- re-exported for the tests
)

#: staggered first-derivative weights of order 8 (distance 1/2, 3/2, 5/2, 7/2)
COEFFS = (1225.0 / 1024.0, -245.0 / 3072.0, 49.0 / 5120.0, -5.0 / 7168.0)
#: fraction of the staggered stability limit ``h / (sqrt(3) vp_max sum|C_k|)``
CFL = 0.95
VELOCITIES = ("vx", "vy", "vz")
STRESSES = ("txx", "tyy", "tzz", "txy", "txz", "tyz")
WAVEFIELDS = VELOCITIES + STRESSES
MODEL_FIELDS = ("lam", "mu", "b", "damp")
#: the quantities in the order the model adds them
QUANTITIES = WAVEFIELDS + MODEL_FIELDS
#: sup of a seeded velocity component (each mode's amplitude is under 0.5)
AMPLITUDE_BOUND = 0.5 * MODES

#: stage V: ``{output: ((stress, axis, direction), ...)}`` -- the three terms
#: of ``sum_j D_j tau_ij``, direction +1 = forward, -1 = backward
STAGE_V = {
    "vx": (("txx", 0, +1), ("txy", 1, -1), ("txz", 2, -1)),
    "vy": (("txy", 0, -1), ("tyy", 1, +1), ("tyz", 2, -1)),
    "vz": (("txz", 0, -1), ("tyz", 1, -1), ("tzz", 2, +1)),
}
#: the divergence of stage T, ``sum_k D_k v_k`` at the nodes
DIVERGENCE = (("vx", 0, -1), ("vy", 1, -1), ("vz", 2, -1))
#: stage T, normal stresses: the term that takes ``2 mu`` on top of ``lam div``
STAGE_T_NORMAL = {"txx": DIVERGENCE[0], "tyy": DIVERGENCE[1], "tzz": DIVERGENCE[2]}
#: stage T, shear stresses: the two terms of ``D_i v_j + D_j v_i``
STAGE_T_SHEAR = {
    "txy": (("vx", 1, +1), ("vy", 0, +1)),
    "txz": (("vx", 2, +1), ("vz", 0, +1)),
    "tyz": (("vy", 2, +1), ("vz", 1, +1)),
}


def dt_of(grid: AcousticGrid) -> float:
    """``CFL * h / (sqrt(3) max(vp) sum|C_k|)``, in ms."""
    return CFL * grid.spacing / (math.sqrt(3.0) * grid.vp_max * sum(abs(c) for c in COEFFS))


def staggered(read, direction: int):
    """``h * D f`` from ``read(offset)`` = ``f`` at ``offset`` cells along the
    axis of the difference; grouped by distance, nearest pair first -- the
    order ``ElasticWave`` uses too."""
    acc = None
    for k in range(1, RADIUS + 1):
        pair = (read(k) - read(1 - k)) if direction > 0 else (read(k - 1) - read(-k))
        acc = COEFFS[k - 1] * pair if acc is None else acc + COEFFS[k - 1] * pair
    return acc


def update_v(name, f, diff, dt_h):
    """``v_i+``; ``f`` holds the centre values, ``diff(q, axis, direction)``
    is ``h D_axis q``."""
    (a, b, c) = STAGE_V[name]
    return f["damp"] * (f[name] + (dt_h * f["b"]) * ((diff(*a) + diff(*b)) + diff(*c)))


def update_t(name, f, diff, dt_h):
    """``tau_ij+`` from the NEW velocities."""
    if name in STAGE_T_NORMAL:
        div = (diff(*DIVERGENCE[0]) + diff(*DIVERGENCE[1])) + diff(*DIVERGENCE[2])
        rhs = f["lam"] * div + (2.0 * f["mu"]) * diff(*STAGE_T_NORMAL[name])
    else:
        (a, b) = STAGE_T_SHEAR[name]
        rhs = f["mu"] * (diff(*a) + diff(*b))
    return f["damp"] * (f[name] + dt_h * rhs)


# --- seeded fields: functions of the global coordinate and four seed words ------


def _rho(vp):
    """Gardner: ``0.31 (vp in m/s)^(1/4)`` g/cm^3."""
    return 0.31 * (1000.0 * vp) ** 0.25


def model_field(grid: AcousticGrid, which: str):
    """``lam = rho (vp^2 - 2 vs^2)``, ``mu = rho vs^2`` (GPa), ``b = 1/rho``
    with ``vs = vp / 2``."""

    def f(x, y, z, words):
        del x, y
        vp = layered_vp(grid, z, words)
        rho, vs = _rho(vp), 0.5 * vp
        if which == "b":
            return 1.0 / rho
        return rho * (vs * vs) if which == "mu" else rho * (vp * vp - 2.0 * (vs * vs))

    return f


def damp_mask(grid: AcousticGrid):
    """Devito's sponge as a MASK (``initialize_damp(abc_type="mask")``, as
    remembered): 1 in the physical region; per axis and side, over ``nbl``
    cells from the outer edge, ``pos = (nbl - i + 1)/nbl``, ``val = coeff
    (pos - sin(2 pi pos)/(2 pi))``, ``coeff = 1.5 ln(1000)/nbl``, subtracted
    as ``val / spacing``.  The frame holds 1 (the kernel pins it)."""

    def f(x, y, z, words):
        import jax.numpy as jnp

        del words
        nbl = grid.nbl
        coeff = 1.5 * math.log(1.0 / 0.001) / max(nbl, 1)
        total = 1.0
        for axis, c in enumerate((x, y, z)):
            d = c - FRAME
            n_pad = grid.shape[axis] - 2 * FRAME
            for i in (d, n_pad - 1 - d):
                pos = (nbl - i + 1).astype(jnp.float32) / max(nbl, 1)
                val = coeff * (pos - jnp.sin(2 * math.pi * pos) / (2 * math.pi))
                total = total - jnp.where((i >= 0) & (i < nbl), val / grid.spacing, 0.0)
        return total

    return f


def velocity_packet(grid: AcousticGrid, component: int):
    """``MODES`` product modes of whole cycles across the physical extent
    under a Hann window, zero outside it; each component draws its own
    amplitudes, cycles and phases (salt ``64 (component + 1)``)."""

    def f(x, y, z, words):
        import jax.numpy as jnp

        salt = 64 * (component + 1)
        inner = [(c - (FRAME + grid.nbl)).astype(jnp.float32) for c in (x, y, z)]
        window = 1.0
        for p, n in zip(inner, grid.physical):
            hann = jnp.sin(math.pi * (p + 0.5) / n) ** 2
            window = window * jnp.where((p >= 0) & (p < n), hann, 0.0)
        total = 0.0
        for j in range(MODES):
            mode = 0.25 + 0.25 * _unit(words, salt + 8 * j)
            for axis, (p, n) in enumerate(zip(inner, grid.physical)):
                cycles = 1.0 + jnp.floor(_unit(words, salt + 1 + 8 * j + axis) * max(1, min(8, n // 4)))
                phase = 2 * math.pi * _unit(words, salt + 4 + 8 * j + axis)
                mode = mode * jnp.cos((2 * math.pi * cycles / n) * p + phase)
            total = total + mode
        return total * window

    return f


def zero_field(x, y, z, words):
    import jax.numpy as jnp

    del x, y, z, words
    return jnp.zeros((), jnp.float32)


def seeded_fields(grid: AcousticGrid) -> dict:
    """``{quantity: f(x, y, z, words)}`` for all thirteen quantities."""
    out = {v: velocity_packet(grid, i) for i, v in enumerate(VELOCITIES)}
    out.update({t: zero_field for t in STRESSES})
    out.update({m: model_field(grid, m) for m in ("lam", "mu", "b")})
    out["damp"] = damp_mask(grid)
    return out


def global_fields(grid: AcousticGrid, words) -> dict:
    """The thirteen seeded fields as whole float32 arrays of ``grid.shape``."""
    import jax.numpy as jnp

    X, Y, Z = grid.shape
    c = (jnp.arange(X)[:, None, None], jnp.arange(Y)[None, :, None], jnp.arange(Z)[None, None, :])
    w = jnp.asarray(words, dtype=jnp.uint32)
    return {
        k: jnp.broadcast_to(f(*c, w), grid.shape).astype(jnp.float32)
        for k, f in seeded_fields(grid).items()
    }


# --- the update ---------------------------------------------------------------------


def time_step(grid: AcousticGrid, f: dict) -> dict:
    """One whole time step of the whole periodic arrays ``f`` (all thirteen):
    stage V, then stage T on the new velocities, the frame pinned to zero."""
    import jax.numpy as jnp

    dt_h = dt_of(grid) / grid.spacing
    frame = frame_mask(grid.shape)

    def diff_of(fields):
        def diff(q, axis, direction):
            return staggered(lambda o: jnp.roll(fields[q], -o, axis), direction)

        return diff

    f = dict(f)
    new_v = {v: jnp.where(frame, 0.0, update_v(v, f, diff_of(f), dt_h)) for v in VELOCITIES}
    f.update(new_v)
    new_t = {t: jnp.where(frame, 0.0, update_t(t, f, diff_of(f), dt_h)) for t in STRESSES}
    f.update(new_t)
    return f


@functools.lru_cache(maxsize=None)
def _steps_framed(grid: AcousticGrid, steps: int):
    import jax
    from jax import lax

    def run(fields):
        model = {m: fields[m] for m in MODEL_FIELDS}

        def body(_, wave):
            out = time_step(grid, {**wave, **model})
            return {w: out[w] for w in WAVEFIELDS}

        with jax.default_matmul_precision("highest"):
            return lax.fori_loop(0, steps, body, {w: fields[w] for w in WAVEFIELDS})

    return jax.jit(run)


def steps_framed(grid: AcousticGrid, fields: dict, steps: int) -> dict:
    """The nine wavefields after ``steps`` whole time steps of ``fields`` (all
    thirteen, whole periodic arrays with their explicit zero frame)."""
    return _steps_framed(grid, steps)(fields)
