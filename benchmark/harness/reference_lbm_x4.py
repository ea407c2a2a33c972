"""The plain D3Q19 reference of ``reference_lbm.py`` for a box DECOMPOSED over a
mesh whose every chip is full (configuration ``lbm-d3q19-512x4``: FluidX3D's
multi-GPU benchmark line, 1024 x 1024 x 512 on mesh [2,2,1], 512^3 and 13.0 GB
a chip): the same equations, tables and seeded fields, laid out so that no
second copy of a chip's nineteen populations -- let alone the reference's own --
is ever made, and nothing crosses a chip.

jax/numpy only: nothing here imports the program under test; the tables,
``moments`` / ``equilibrium`` / ``_step`` and the seeded fields are
``reference_lbm``'s own.  EVERY CHIP computes PIECES of ITS block straight from
the seed (the seeded state is a function of the global coordinate): a piece is
``width`` x planes of the block, whole along y and z, evaluated together with a
margin of ``steps`` cells on x (a piece's own ends) and on every further axis
the mesh cuts, by global coordinates taken modulo the box; ``steps`` plain
pull-stream-and-collide steps with ``jnp.roll`` on all three axes -- an axis the
mesh leaves whole is rolled whole and its wraparound is the box's, a cut axis
wraps at the piece's own ends, which spoils one cell a side a step: the margin
is what that can reach --; the middle kept, every cell of which has seen true
neighbours only.  The program's state is read piece by piece too, from each
chip's own raw, shell-carrying shard, inside the same ``shard_map``: the cells
beside every chip seam and the x-y shard edges are compared like any other.
(``reference_mhd_x4.py`` lays its own update out the same way and says what
XLA's partitioner makes of a whole-array reference cut along one axis.)
"""

from __future__ import annotations

import functools

from benchmark.harness import reference_lbm as lbm


def piece_width(extent: int, asked: int) -> int:
    """The largest divisor of a block's x ``extent`` that is at most ``asked``
    planes: the pieces tile the block, so every cell is read exactly once."""
    return max(w for w in range(1, max(1, min(int(asked), extent)) + 1) if extent % w == 0)


def _margins(setup: lbm.Setup, mesh_shape, steps: int, width: int) -> tuple:
    """Cells of margin a side, per axis: ``steps`` along x (a piece is a slab of
    its block: its ends are no period of anything, unless it IS the whole
    uncut axis) and along every further axis the mesh cuts, 0 on an axis that
    is whole in the piece (rolled whole: the box's own wraparound)."""
    whole_x = mesh_shape[0] == 1 and width == setup.shape[0]
    return (0 if whole_x else steps,) + tuple(steps if m > 1 else 0 for m in mesh_shape[1:])


def _piece(setup: lbm.Setup, steps: int, mesh_shape, width: int):
    """``piece(first, words)``: the nineteen populations after ``steps`` steps on
    the ``width`` x planes from global cell ``first`` (three traced int32) of a
    chip's block -- ``(width, block_y, block_z)`` each."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    fields = lbm.seeded_fields(setup)
    block = tuple(n // m for n, m in zip(setup.shape, mesh_shape))
    kept = (width,) + block[1:]
    margins = _margins(setup, mesh_shape, steps, width)
    padded = tuple(n + 2 * m for n, m in zip(kept, margins))

    def piece(first, words):
        coords = []
        for a in range(3):
            c = jnp.mod(first[a] - margins[a] + jnp.arange(padded[a], dtype=jnp.int32),
                        jnp.int32(setup.shape[a]))
            coords.append(c.reshape([-1 if b == a else 1 for b in range(3)]))
        f = tuple(
            jnp.broadcast_to(fields[nm](*coords, words), padded).astype(jnp.float32)
            for nm in lbm.NAMES
        )
        with jax.default_matmul_precision("highest"):
            f = lax.fori_loop(0, steps, lambda _, f: lbm._step(f, setup.omega), f)
        keep = tuple(slice(m, m + n) for m, n in zip(margins, kept))
        return tuple(a[keep] for a in f)

    return piece


@functools.lru_cache(maxsize=None)
def _reference_piece(setup: lbm.Setup, steps: int, mesh_shape, width: int):
    import jax

    return jax.jit(_piece(setup, steps, mesh_shape, width))


def reference_piece(setup: lbm.Setup, steps: int, mesh_shape, first, width: int, words):
    """One piece on its own (tests): the nineteen populations after ``steps``
    steps on ``width`` x planes x the block's y and z extents from global cell
    ``first`` of a box cut as ``mesh_shape`` says."""
    import numpy as np

    return _reference_piece(setup, int(steps), tuple(mesh_shape), int(width))(
        np.asarray(first, dtype=np.int32), np.asarray(words, dtype=np.uint32))


def _cut(raw, k, lo: int, kept):
    """Piece ``k`` of one chip's raw shard: the interior cells, the shell left out."""
    import jax.numpy as jnp
    from jax import lax

    at = [jnp.int32(lo) + k * jnp.int32(kept[0]), jnp.int32(lo), jnp.int32(lo)]
    return lax.dynamic_slice(raw, at, kept).astype(jnp.float32)


@functools.lru_cache(maxsize=None)
def _piece_error(setup: lbm.Setup, steps: int, mesh, width: int, lo: int):
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    names = mesh.axis_names
    mesh_shape = tuple(mesh.shape[a] for a in names)
    block = tuple(n // m for n, m in zip(setup.shape, mesh_shape))
    piece = _piece(setup, steps, mesh_shape, width)
    kept = (width,) + block[1:]

    def per_chip(words, k, *raws):
        first = [lax.axis_index(a) * jnp.int32(b) for a, b in zip(names, block)]
        first[0] = first[0] + k * jnp.int32(width)
        worst = jnp.float32(0.0)
        for raw, want in zip(raws, piece(first, words)):
            d = jnp.abs(_cut(raw, k, lo, kept) - want)
            worst = jnp.maximum(worst, jnp.max(jnp.where(jnp.isnan(d), jnp.inf, d)))
        return worst.reshape(1, 1, 1)

    spec = P(*names)
    return jax.jit(jax.shard_map(
        per_chip, mesh=mesh, in_specs=(P(), P()) + (spec,) * lbm.Q, out_specs=spec,
        check_vma=False))


def piece_errors(setup: lbm.Setup, steps: int, mesh, words, raws, lo: int, width: int) -> tuple:
    """``(max |got - want| over every chip and piece, cells compared)``: ``raws``
    are the program's nineteen shell-carrying GLOBAL arrays, cut over ``mesh``
    axis for axis, ``lo`` their shell width; every chip reads its own shard and
    computes its own pieces of ``width`` planes (a divisor of the block's x
    extent: ``piece_width``)."""
    import numpy as np

    mesh_shape = tuple(mesh.shape[a] for a in mesh.axis_names)
    block_x = setup.shape[0] // mesh_shape[0]
    assert block_x % width == 0, (block_x, width)
    run = _piece_error(setup, int(steps), mesh, int(width), int(lo))
    w = np.asarray(words, dtype=np.uint32)
    errs = [run(w, np.int32(k), *raws) for k in range(block_x // width)]
    worst = max(float(np.asarray(e).max()) for e in errs)
    cells = len(errs) * width * (setup.shape[1] // mesh_shape[1]) * (setup.shape[2] // mesh_shape[2])
    return worst, cells * int(np.prod(mesh_shape)) * lbm.Q


@functools.lru_cache(maxsize=None)
def _piece_state(setup: lbm.Setup, mesh, width: int, lo: int):
    import jax
    from jax.sharding import PartitionSpec as P

    names = mesh.axis_names
    mesh_shape = tuple(mesh.shape[a] for a in names)
    kept = (width,) + tuple(n // m for n, m in zip(setup.shape, mesh_shape))[1:]

    def per_chip(k, *raws):
        # ``reference_lbm``'s own count of a state, on this chip's piece: its bad
        # cells, and its mass as per-pencil partial sums
        bad, pencils = lbm._state(setup)(*[_cut(raw, k, lo, kept) for raw in raws])
        return bad.reshape(1, 1, 1), pencils[..., None]

    spec = P(*names)
    return jax.jit(jax.shard_map(
        per_chip, mesh=mesh, in_specs=(P(),) + (spec,) * lbm.Q, out_specs=(spec, spec),
        check_vma=False))


def state_counts(setup: lbm.Setup, mesh, raws, lo: int, width: int) -> tuple:
    """``(bad cells, total mass, cells seen)`` of the program's nineteen raw
    GLOBAL arrays, every chip its own shard piece by piece
    (``reference_lbm.state_counts`` on whole interiors): cells that are not
    finite or whose moments leave the guardband, and the GLOBAL ``sum_x rho`` in
    float64."""
    import numpy as np

    mesh_shape = tuple(mesh.shape[a] for a in mesh.axis_names)
    block_x = setup.shape[0] // mesh_shape[0]
    assert block_x % width == 0, (block_x, width)
    run = _piece_state(setup, mesh, int(width), int(lo))
    bad, mass, cells = 0, 0.0, 0
    for k in range(block_x // width):
        b, pencils = run(np.int32(k), *raws)
        bad += int(np.asarray(b).sum())
        pencils = np.asarray(pencils, dtype=np.float64)
        mass += float(pencils.sum())
        cells += pencils.size * (setup.shape[2] // mesh_shape[2])
    return bad, mass, cells
