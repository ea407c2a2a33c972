"""Astaroth's MHD step on a box with a side of its own an axis, decomposed over
a mesh (configuration ``astaroth-mhd-256x4``: the source's weak scaling, 256^3
a device, the CELL kept and the box grown with the grid -- 512 x 512 x 256
cells on 4 pi x 4 pi x 2 pi, mesh [2,2,1]).

jax/numpy only: nothing here imports the program under test.  The equations,
the differences, the Runge-Kutta substep and the seeded state are
``harness/reference_mhd.py``'s, which reads a set-up's ``spacing`` and nothing
else of its box; this file brings the set-up whose spacing comes from the
configuration's per-axis ``setup.box`` -- and the way the whole-array update
is laid over four chips that already hold the program's sixteen arrays:

``reference_mhd.reference`` at 256^3 takes 7.5 GB of temporaries on one chip
(XLA keeps some 110 shifted arrays alive); cut in four along ONE axis of the
512 x 512 x 256 grid, as ``factories/acoustic_x4.py`` cuts its reference, XLA's
partitioner makes that 15.4 GB (x) or 14.4 GB (z) a chip -- 96 halo exchanges a
substep with results of their own (compiled for a described v5e:2x2, PR 47) --
beside 3.4 GB of the program's state on a 16.9 GB chip.  So here every chip
computes its own part and NOTHING crosses a chip: the seeded state is a
function of the global coordinate, so a chip fills a PIECE of its block
together with a margin of ``3 x substeps`` cells on every cut axis straight
from the seed, runs the same ``substep`` on it with ``jnp.roll`` -- whose
wraparound is wrong at the piece's own edge, and wrong cells spread three
cells a substep, the differences' radius: the margin is what they can reach --
and keeps the piece, every cell of which saw only true neighbours.  An axis
the mesh leaves whole is rolled whole: its wraparound is the box's.
"""

from __future__ import annotations

import dataclasses
import functools
import itertools
import math

from benchmark.harness import reference_mhd as mhd

FIELDS, QUANTITIES = mhd.FIELDS, mhd.QUANTITIES
seeded_fields = mhd.seeded_fields
state_bad_cells = mhd.state_bad_cells

#: how far a wrong cell reaches in one substep: the differences' radius
REACH = 3
#: cells of a piece with its margins: 272 x 272 x 256 at the cell's size, where
#: one substep's temporaries are 1.13 times the one-chip reference's
PIECE_CELLS = 20_000_000


@dataclasses.dataclass(frozen=True)
class Setup(mhd.Setup):
    """``reference_mhd.Setup`` with ``box`` a triple, one side an axis."""

    @property
    def spacing(self) -> tuple:
        return tuple(b / n for b, n in zip(self.box, self.shape))


def setup_from(config: dict, shape) -> Setup:
    """``shape`` is given apart because a rehearsal shrinks it -- every axis by
    the same factor, so the cell stays uniform; ``box`` and ``dt`` stay the
    configuration's."""
    s = config["setup"]
    box = tuple(float(b) for b in s["box"])
    if len(box) != 3:
        raise ValueError(f"setup.box is one side an axis, not {s['box']!r}")
    return Setup(
        shape=tuple(int(n) for n in shape), box=box,
        **{k: float(s[k]) for k in ("nu", "eta", "chi", "zeta", "gamma", "cp", "cs0", "mu0",
                                    "lnrho0", "lnT0", "dt", "amplitude", "envelope")},
        modes=int(s["modes"]), max_waves=int(s["max_waves"]),
    )


def piece_plan(shape, mesh_shape, margin: int) -> tuple:
    """``(pieces, piece, padded)`` per axis: into how many pieces a chip's
    block is cut along each axis the mesh cuts (halved, all cut axes alike,
    until a piece with its margins holds at most ``PIECE_CELLS``), the
    piece's extent, and its extent with a ``margin`` a side (no margin on an
    axis the mesh leaves whole: that one is rolled whole)."""
    cut = [m > 1 for m in mesh_shape]
    block = [n // m for n, m in zip(shape, mesh_shape)]
    p = 1
    while True:
        piece = [b // p if c else b for b, c in zip(block, cut)]
        padded = [n + 2 * margin if c else n for n, c in zip(piece, cut)]
        halvable = all(n % 2 == 0 for n, c in zip(piece, cut) if c)
        if math.prod(padded) <= PIECE_CELLS or not halvable or not any(cut):
            return tuple(p if c else 1 for c in cut), tuple(piece), tuple(padded)
        p *= 2


@functools.lru_cache(maxsize=None)
def _reference(setup: Setup, steps: int, sharding):
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    mesh = sharding.mesh
    names = mesh.axis_names
    mesh_shape = tuple(mesh.shape[a] for a in names)
    substeps = 3 * steps
    margin = REACH * substeps
    pieces, piece, padded = piece_plan(setup.shape, mesh_shape, margin)
    block = tuple(n // m for n, m in zip(setup.shape, mesh_shape))
    # the piece's own set-up: its extent, on the configuration's CELL
    local = dataclasses.replace(
        setup, shape=padded, box=tuple(d * n for d, n in zip(setup.spacing, padded)))
    fields = seeded_fields(setup)  # of the GLOBAL coordinate

    def one_piece(at, words):
        """The sixteen quantities of the piece whose first cell is ``at``."""
        coords = []
        for a in range(3):
            lead = (padded[a] - piece[a]) // 2
            c = (at[a] - lead + jnp.arange(padded[a])) % setup.shape[a]
            coords.append(c.reshape([-1 if b == a else 1 for b in range(3)]))
        cur = {q: jnp.broadcast_to(fields[q](*coords, words), padded).astype(jnp.float32)
               for q in FIELDS}
        coeff = jnp.asarray(mhd.RK3, dtype=jnp.float32)

        def body(k, state):
            return mhd.substep(local, *state, coeff[k % 3, 0], coeff[k % 3, 1])

        cur, prev = lax.fori_loop(0, substeps, body, (cur, dict(cur)))
        keep = tuple(slice((p - n) // 2, (p - n) // 2 + n) for p, n in zip(padded, piece))
        return tuple(cur[q][keep] for q in FIELDS) + tuple(prev[q][keep] for q in FIELDS)

    def per_chip(words):
        first = [lax.axis_index(a) * b for a, b in zip(names, block)]
        offsets = jnp.asarray(
            [[i * n for i, n in zip(ijk, piece)] for ijk in itertools.product(*map(range, pieces))],
            dtype=jnp.int32)

        def place(k, blocks):  # one piece after another, each written where it lies
            off = tuple(offsets[k, a] for a in range(3))
            part = one_piece([f + o for f, o in zip(first, off)], words)
            return tuple(lax.dynamic_update_slice(b, p, off) for b, p in zip(blocks, part))

        empty = tuple(jnp.zeros(block, jnp.float32) for _ in QUANTITIES)
        with jax.default_matmul_precision("highest"):
            return lax.fori_loop(0, len(offsets), place, empty)

    spec = P(*names)
    return jax.jit(jax.shard_map(
        per_chip, mesh=mesh, in_specs=P(), out_specs=(spec,) * len(QUANTITIES), check_vma=False))


def reference(setup: Setup, steps: int, sharding, words):
    """The sixteen quantities (``QUANTITIES``' order) after ``steps`` time
    steps from the seeded state, whole global arrays cut over ``sharding``'s
    mesh axis for axis (``NamedSharding(mesh, P(*mesh.axis_names))``), every
    chip's block computed on that chip."""
    import numpy as np

    return _reference(setup, int(steps), sharding)(np.asarray(words, dtype=np.uint32))
