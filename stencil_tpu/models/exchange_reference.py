"""Plain reference of the halo exchange: from a global array, a mesh extent
and a radius, what every shard's raw block must hold after ``exchange()`` --
and which of its cells the shard OWNS.

Straightforward ``jax.numpy`` in float32: ``jnp.pad(mode="wrap")`` and plain
slices.  It imports nothing of ``ops/``, ``domain.py`` or
``parallel/partition.py``; the geometry is restated here:

* a mesh axis of ``d`` shards over ``size`` cells pads every shard to
  ``n = ceil(size / d)`` interior cells (XLA shards are equal); shard ``i``
  starts at global cell ``i * n``; shards ``0 .. d-2`` hold ``n`` valid
  cells, the LAST holds the remainder ``size - (d - 1) * n`` (the reference
  spreads the remainder one cell at a time over the first shards instead,
  partition.hpp:83-114 -- PARITY.md);
* a raw block is ``r_lo + n + r_hi`` cells an axis; raw cell ``k`` of shard
  ``i`` stands for global cell ``i * n - r_lo + k``, wrapped periodically;
* a shard OWNS raw cells ``k < r_lo + valid + r_hi``: its low halo, its
  valid interior and the high halo right after it.  On the last shard of an
  unevenly split axis the cells beyond are padding: they belong to no one,
  an exchange may leave anything there, and nothing may read them.

Tier-1 (tests/test_exchange_reference.py) holds ``dd.exchange()`` to this on
seeded random fields: every owned cell equal, exactly.
"""

from __future__ import annotations

from typing import Sequence, Tuple

import jax.numpy as jnp
import numpy as np


def shard_width(size: int, dim: int) -> int:
    """Padded interior cells of every shard on one axis."""
    return -(-size // dim)


def valid_cells(size: int, dim: int, index: int) -> int:
    """Valid interior cells of shard ``index`` on one axis."""
    n = shard_width(size, dim)
    return n if index < dim - 1 else size - (dim - 1) * n


def raw_extent(size: int, dim: int, r_lo: int, r_hi: int) -> int:
    """Cells of every shard's raw block on one axis."""
    return r_lo + shard_width(size, dim) + r_hi


def exchanged_blocks(
    field, mesh_dim: Sequence[int], r_lo: Sequence[int], r_hi: Sequence[int]
) -> np.ndarray:
    """``out[ix, iy, iz]`` is the raw block of shard ``(ix, iy, iz)`` after an
    exchange of the global ``field`` (X, Y, Z), read as float32; cells the
    shard does not own are 0 there and mean nothing."""
    size = field.shape
    wrapped = jnp.pad(
        jnp.asarray(field, dtype=jnp.float32),
        [(r_lo[a], r_hi[a]) for a in range(3)],
        mode="wrap",
    )  # wrapped[g + r_lo] is global cell g, for g in [-r_lo, size + r_hi)
    raw = [raw_extent(size[a], mesh_dim[a], r_lo[a], r_hi[a]) for a in range(3)]
    out = np.zeros(tuple(mesh_dim) + tuple(raw), dtype=np.float32)
    for idx in np.ndindex(*mesh_dim):
        cut, put = [], []
        for a in range(3):
            start = idx[a] * shard_width(size[a], mesh_dim[a])  # = global start - r_lo + r_lo
            owned = r_lo[a] + valid_cells(size[a], mesh_dim[a], idx[a]) + r_hi[a]
            cut.append(slice(start, start + owned))
            put.append(slice(0, owned))
        out[idx][tuple(put)] = np.asarray(wrapped[tuple(cut)])
    return out


def owned_mask(
    size: Sequence[int], mesh_dim: Sequence[int], r_lo: Sequence[int], r_hi: Sequence[int]
) -> np.ndarray:
    """Boolean array shaped like ``exchanged_blocks``: True on the raw cells
    their shard owns (``k < r_lo + valid + r_hi`` on every axis)."""
    raw = [raw_extent(size[a], mesh_dim[a], r_lo[a], r_hi[a]) for a in range(3)]
    mask = np.zeros(tuple(mesh_dim) + tuple(raw), dtype=bool)
    for idx in np.ndindex(*mesh_dim):
        owned = [
            np.arange(raw[a]) < r_lo[a] + valid_cells(size[a], mesh_dim[a], idx[a]) + r_hi[a]
            for a in range(3)
        ]
        mask[idx] = owned[0][:, None, None] & owned[1][None, :, None] & owned[2][None, None, :]
    return mask


def split_blocks(raw_global, mesh_dim: Sequence[int]) -> np.ndarray:
    """A shell-carrying global array (``mesh_dim * raw`` per axis, shard
    blocks side by side) as ``mesh_dim + raw``, like ``exchanged_blocks``."""
    a = np.asarray(raw_global)
    dx, dy, dz = mesh_dim
    rx, ry, rz = a.shape[0] // dx, a.shape[1] // dy, a.shape[2] // dz
    return a.reshape(dx, rx, dy, ry, dz, rz).transpose(0, 2, 4, 1, 3, 5)


def pad_cells(
    size: Sequence[int], mesh_dim: Sequence[int], r_lo: Sequence[int], r_hi: Sequence[int]
) -> Tuple[int, int]:
    """``(pad, owned)`` raw cells of one quantity over all shards, as
    arithmetic: owned is ``prod(size + dim x (r_lo + r_hi))`` (the valid
    cells of an axis add up to its extent), pad is the rest of ``prod(dim x
    raw)``.  A comparison that takes every raw cell for owned counts exactly
    ``pad`` mismatches on a sound exchange of a random field."""
    owned = int(np.prod([size[a] + mesh_dim[a] * (r_lo[a] + r_hi[a]) for a in range(3)]))
    total = int(np.prod([
        mesh_dim[a] * raw_extent(size[a], mesh_dim[a], r_lo[a], r_hi[a]) for a in range(3)
    ]))
    return total - owned, owned
