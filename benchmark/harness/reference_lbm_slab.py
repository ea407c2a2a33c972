"""The plain D3Q19 reference of ``reference_lbm.py`` in X-SLABS (configuration
``lbm-d3q19-512``): the same equations, tables and seeded fields, for a box
whose nineteen populations fill the device, so that no second copy of them --
let alone the reference's own -- fits beside the program's.

jax/numpy only: nothing here imports the program under test; the tables,
``moments`` / ``equilibrium`` / ``_step`` and the seeded fields are
``reference_lbm``'s own.  A slab of ``width`` planes after ``steps`` steps is
computed from ``width + 2 steps`` planes: the seeded fields evaluated by their
global coordinates (x taken modulo the box: the box is periodic), ``steps``
plain pull-stream-and-collide steps with ``jnp.roll`` on all three axes -- y and
z whole and periodic, x wrapping at the SLAB's ends, which spoils one plane a
side a step --, the middle ``width`` planes kept: every one of them has seen
true neighbours only.  The program's state is read slab by slab too
(``slab_error``, ``state_slab``): raw, shell-carrying arrays of one chip, cut
at a traced plane so that one compiled reader serves every slab.
"""

from __future__ import annotations

import functools

from benchmark.harness import reference_lbm as lbm


def slab_starts(extent: int, width: int) -> list:
    """First planes of the slabs that cover ``extent`` at ``width`` planes each;
    the last slab is moved back to end at the extent (it overlaps)."""
    width = min(width, extent)
    return sorted({min(f, extent - width) for f in range(0, extent, width)})


@functools.lru_cache(maxsize=None)
def _slab(setup: lbm.Setup, steps: int, width: int):
    import jax
    import jax.numpy as jnp
    from jax import lax

    fields = lbm.seeded_fields(setup)
    X, Y, Z = setup.shape
    halo = steps  # a step reads one plane further: what the slab's own wrap spoils

    def run(words, first):
        x = jnp.mod(first - halo + jnp.arange(width + 2 * halo, dtype=jnp.int32), jnp.int32(X))
        c = (x[:, None, None], jnp.arange(Y, dtype=jnp.int32)[None, :, None],
             jnp.arange(Z, dtype=jnp.int32)[None, None, :])
        box = (width + 2 * halo, Y, Z)
        f = tuple(
            jnp.broadcast_to(fields[nm](*c, words), box).astype(jnp.float32) for nm in lbm.NAMES
        )
        with jax.default_matmul_precision("highest"):
            f = lax.fori_loop(0, steps, lambda _, f: lbm._step(f, setup.omega), f)
        return tuple(a[halo : halo + width] for a in f)

    return jax.jit(run)


def reference_slab(setup: lbm.Setup, steps: int, words, first: int, width: int):
    """The nineteen populations on planes ``first .. first + width`` of the box
    after ``steps`` steps from the seeded state."""
    import numpy as np

    return _slab(setup, int(steps), int(width))(np.asarray(words, dtype=np.uint32), np.int32(first))


@functools.lru_cache(maxsize=None)
def _slab_error(shape, width: int, lo: int):
    import jax
    import jax.numpy as jnp
    from jax import lax

    def run(raw, first, want):
        at = [jnp.int32(lo) + first.astype(jnp.int32), jnp.int32(lo), jnp.int32(lo)]
        got = lax.dynamic_slice(raw, at, (width, shape[1], shape[2])).astype(jnp.float32)
        d = jnp.abs(got - want.astype(jnp.float32))
        return jnp.max(jnp.where(jnp.isnan(d), jnp.inf, d))

    return jax.jit(run)


def slab_error(raw, lo: int, shape, first: int, want) -> float:
    """max |got - want| over planes ``first .. first + width`` of ONE population:
    ``raw`` is the program's shell-carrying array, ``lo`` its shell width,
    ``want`` a ``reference_slab`` array."""
    import numpy as np

    return float(_slab_error(tuple(shape), int(want.shape[0]), int(lo))(raw, np.int32(first), want))


@functools.lru_cache(maxsize=None)
def _state_slab(setup: lbm.Setup, width: int, lo: int):
    import jax
    import jax.numpy as jnp
    from jax import lax

    _, Y, Z = setup.shape

    def run(first, *raws):
        at = [jnp.int32(lo) + first.astype(jnp.int32), jnp.int32(lo), jnp.int32(lo)]
        f = [lax.dynamic_slice(a, at, (width, Y, Z)).astype(jnp.float32) for a in raws]
        rho, ux, uy, uz = lbm.moments(f)
        speed2 = (ux * ux + uy * uy) + uz * uz
        finite = jnp.isfinite(rho) & jnp.isfinite(speed2)
        for a in f:
            finite = finite & jnp.isfinite(a)
        inside = (rho >= setup.rho_band[0]) & (rho <= setup.rho_band[1])
        inside = inside & (speed2 < setup.u_max * setup.u_max)
        # per plane: the bad cells, and the mass as per-pencil partial sums
        return (jnp.sum((~(finite & inside)).astype(jnp.int32), axis=(1, 2)),
                jnp.sum(jnp.where(finite, rho, 0.0), axis=2))

    return jax.jit(run)


def state_counts(setup: lbm.Setup, raws, lo: int, width: int) -> tuple:
    """``(bad cells, total mass, planes seen)`` of the program's nineteen raw
    arrays, slab by slab (``reference_lbm.state_counts`` on whole interiors):
    cells that are not finite or whose moments leave the guardband, and ``sum_x
    rho`` in float64.  A plane two slabs share is counted once."""
    import numpy as np

    X = setup.shape[0]
    width = min(int(width), X)
    bad, mass, seen = 0, 0.0, np.zeros(X, bool)
    for first in slab_starts(X, width):
        b, pencils = _state_slab(setup, width, int(lo))(np.int32(first), *raws)
        new = ~seen[first : first + width]
        bad += int(np.asarray(b)[new].sum())
        mass += float(np.asarray(pencils, dtype=np.float64)[new].sum())
        seen[first : first + width] = True
    return bad, mass, int(seen.sum())
