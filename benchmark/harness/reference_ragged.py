"""The owned-cell comparison of a shell-carrying sharded array whose mesh
does NOT divide the global extent (pad-and-mask shards), counted on the
device.

jax only: nothing here imports the program under test, and the geometry is
worked out from the configuration's own numbers (global extent, mesh, shard
width, radius), never from what the program says about itself.

``harness/reference.py ripple_mismatches`` assumes every shard owns its whole
raw block.  On an axis the mesh splits unevenly the shards are padded to
``ceil(size / dim)`` cells and the LAST one owns only the remainder ``v``:
of its raw block the cells ``k < r_lo + v + r_hi`` are owned (low halo,
valid interior, high halo right after it) and the rest is padding that
belongs to no one -- unspecified, and not compared here.  An even-geometry
comparison counts exactly those pad cells as mismatches
(``pad_cells`` below: 8,236 for four quantities of 23^3 on mesh [2,2,1] at
radius 3).
"""

from __future__ import annotations

import functools
import math


def shard_width(gsize, mesh_shape) -> tuple:
    """Padded interior cells of every shard: ``ceil(size / dim)`` per axis."""
    return tuple(-(-g // m) for g, m in zip(gsize, mesh_shape))


def valid_last(gsize, mesh_shape) -> tuple:
    """Valid interior cells of the last shard on each axis."""
    return tuple(g - (m - 1) * n for g, m, n in zip(gsize, mesh_shape, shard_width(gsize, mesh_shape)))


def owned_cells(gsize, mesh_shape, lo, hi) -> int:
    """Owned raw cells of ONE quantity over all shards: per axis every shard
    owns its valid cells and both halos, and the valid cells add up to the
    global extent -- prod(size + dim x (r_lo + r_hi))."""
    return math.prod(g + m * (a + b) for g, m, a, b in zip(gsize, mesh_shape, lo, hi))


def pad_cells(gsize, mesh_shape, lo, hi) -> int:
    """Raw cells of one quantity that no shard owns: all raw cells less the
    owned ones."""
    n = shard_width(gsize, mesh_shape)
    raw = math.prod(m * (w + a + b) for m, w, a, b in zip(mesh_shape, n, lo, hi))
    return raw - owned_cells(gsize, mesh_shape, lo, hi)


@functools.lru_cache(maxsize=None)
def _counter(mesh, raw, n, lo, hi, gsize):
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from benchmark.harness import reference as ref

    names = mesh.axis_names
    dims = tuple(mesh.shape[a] for a in names)
    last = valid_last(gsize, dims)

    def per_shard(block, q, phase):
        owned, coord = [], []
        for i, a in enumerate(names):
            idx = lax.axis_index(a)
            k = jnp.arange(raw[i])
            valid = jnp.where(idx == dims[i] - 1, last[i], n[i])
            owned.append(k < lo[i] + valid + hi[i])
            coord.append((idx * n[i] - lo[i] + k) % gsize[i])
        want = ref.ripple(q, phase)(
            coord[0][:, None, None], coord[1][None, :, None], coord[2][None, None, :])
        mask = owned[0][:, None, None] & owned[1][None, :, None] & owned[2][None, None, :]
        # one fused pass over the block: compare, mask, count -- no whole-array
        # temporary (f32 on both sides: a narrower storage type fails here)
        bad = jnp.sum(mask & (block.astype(jnp.float32) != want), dtype=jnp.int32)
        checked = jnp.sum(mask, dtype=jnp.int32)
        return jnp.stack([bad, checked]).reshape((1, 1, 1, 2))

    spec = P(*names)
    fn = jax.shard_map(per_shard, mesh=mesh, in_specs=(spec, P(), P()), out_specs=P(*names, None))
    return jax.jit(fn)


def owned_mismatches(arr, mesh, gsize, lo, hi, q: int, phase: int) -> tuple:
    """``(mismatches, checked_cells)`` of one quantity: the owned cells of
    every shard of the shell-carrying array ``arr`` -- interior and both
    halos, pad cells left out -- that differ, read as f32, from the analytic
    field ``reference.ripple(q, phase)`` at their periodically wrapped global
    coordinate, and how many cells were compared.  Per-shard counts come back
    as int32 (a shard holds under 2^31 cells) and are summed on the host."""
    import numpy as np

    dims = tuple(mesh.shape[a] for a in mesh.axis_names)
    n = shard_width(gsize, dims)
    raw = tuple(s // m for s, m in zip(arr.shape, dims))
    assert raw == tuple(w + a + b for w, a, b in zip(n, lo, hi)), (raw, n, lo, hi)
    out = _counter(mesh, raw, n, tuple(lo), tuple(hi), tuple(gsize))(
        arr, np.int32(q), np.int32(phase % (1 << 20)))
    counts = np.asarray(out, dtype=np.int64).reshape(-1, 2).sum(axis=0)
    return int(counts[0]), int(counts[1])
