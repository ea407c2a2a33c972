"""Astaroth's MHD step of ``reference_mhd.py`` in PIECES of a box that fills the
device (configuration ``astaroth-mhd-512``): sixteen 512^3 f32 quantities are
11.0 GB in the program's one slot, and ``reference_mhd.reference`` on whole
arrays keeps some 450 B a cell alive (7.5 GB at 256^3), so neither a second
copy of the state nor the whole-array update fits beside it.

jax/numpy only: nothing here imports the program under test.  The equations,
the differences, the Runge-Kutta substep and the seeded state are
``harness/reference_mhd.py``'s, and the way a piece is made is
``harness/reference_mhd_x4.py``'s (whose ``Setup`` with a side an axis gives a
padded piece the box's own cell): the seeded state is a function of the global
coordinate, so a piece is filled together with a margin of ``3 x substeps``
cells a side on every axis it cuts straight from the seed -- no copy of the
initial state --, run through the same ``substep`` with ``jnp.roll`` -- whose
wraparound is wrong at the piece's own edge, and wrong cells spread three cells
a substep, the differences' radius: the margin is what they can reach --, and
its middle kept, every cell of which saw only true neighbours.  An axis a piece
spans whole is rolled whole: its wraparound is the box's.  Nothing of a piece
outlives its comparison with the program's raw, shell-carrying arrays
(``piece_error``), which are cut at a traced offset, so one compiled reference
and one compiled reader serve every piece.
"""

from __future__ import annotations

import dataclasses
import functools
import itertools
import math

from benchmark.harness import reference_mhd as mhd
from benchmark.harness import reference_mhd_x4 as pieces

FIELDS, QUANTITIES = mhd.FIELDS, mhd.QUANTITIES
REACH = pieces.REACH


def piece_plan(shape, margin: int, cells: int) -> tuple:
    """``(piece, padded)``: the extents of the pieces a box of ``shape`` is cut
    into and of a piece with its margins (``margin`` a side on every axis the
    piece does not span; none on one it spans: that one is rolled whole).  Of
    all cuts into equal pieces whose padded extent holds at most ``cells`` cells
    the one that computes the fewest cells in all; the box whole where that
    fits."""
    best = None
    options = [[n // k for k in range(1, n + 1) if n % k == 0] for n in shape]
    for piece in itertools.product(*options):
        padded = tuple(p if p == n else p + 2 * margin for p, n in zip(piece, shape))
        if math.prod(padded) > cells:
            continue
        cost = math.prod(padded) * math.prod(n // p for n, p in zip(shape, piece))
        if best is None or (cost, padded) < best[0]:
            best = ((cost, padded), tuple(piece), padded)
    if best is None:
        raise ValueError(f"no piece of {tuple(shape)} with margins of {margin} holds {cells} cells")
    return best[1], best[2]


def piece_starts(shape, piece) -> list:
    """The first cell of every piece, x outermost."""
    return list(itertools.product(*[range(0, n, p) for n, p in zip(shape, piece)]))


@functools.lru_cache(maxsize=None)
def _piece(setup: mhd.Setup, steps: int, piece: tuple, padded: tuple):
    import jax
    import jax.numpy as jnp
    from jax import lax

    substeps = 3 * steps
    fields = mhd.seeded_fields(setup)  # of the GLOBAL coordinate
    # the piece's own set-up: its padded extent, on the box's CELL
    local = pieces.Setup(
        **{**dataclasses.asdict(setup), "shape": padded,
           "box": tuple(d * n for d, n in zip(setup.spacing, padded))})

    def run(words, at):
        coords = []
        for a in range(3):
            lead = (padded[a] - piece[a]) // 2
            c = jnp.mod(at[a] - lead + jnp.arange(padded[a], dtype=jnp.int32), jnp.int32(setup.shape[a]))
            coords.append(c.reshape([-1 if b == a else 1 for b in range(3)]))
        cur = {q: jnp.broadcast_to(fields[q](*coords, words), padded).astype(jnp.float32)
               for q in FIELDS}
        coeff = jnp.asarray(mhd.RK3, dtype=jnp.float32)

        def body(k, state):
            return mhd.substep(local, *state, coeff[k % 3, 0], coeff[k % 3, 1])

        with jax.default_matmul_precision("highest"):
            cur, prev = lax.fori_loop(0, substeps, body, (cur, dict(cur)))
        keep = tuple(slice((p - n) // 2, (p - n) // 2 + n) for p, n in zip(padded, piece))
        return tuple(cur[q][keep] for q in FIELDS) + tuple(prev[q][keep] for q in FIELDS)

    return jax.jit(run)


def reference_piece(setup: mhd.Setup, steps: int, words, at, piece, padded):
    """The sixteen quantities (``QUANTITIES``' order) on the ``piece`` cells from
    ``at`` on after ``steps`` time steps from the seeded state."""
    import numpy as np

    fn = _piece(setup, int(steps), tuple(piece), tuple(padded))
    return fn(np.asarray(words, dtype=np.uint32), np.asarray(at, dtype=np.int32))


@functools.lru_cache(maxsize=None)
def _piece_error(piece: tuple, lo: int):
    import jax
    import jax.numpy as jnp
    from jax import lax

    def run(at, raw, want):
        got = lax.dynamic_slice(raw, [jnp.int32(lo) + at[a] for a in range(3)], piece)
        d = jnp.abs(got.astype(jnp.float32) - want.astype(jnp.float32))
        return jnp.max(jnp.where(jnp.isnan(d), jnp.inf, d))

    return jax.jit(run)


def piece_error(raw, lo: int, at, want) -> float:
    """max |got - want| over one piece of ONE quantity: ``raw`` is the program's
    shell-carrying array, ``lo`` its shell width, ``want`` a ``reference_piece``
    array whose first cell is the box's cell ``at``."""
    import numpy as np

    fn = _piece_error(tuple(int(n) for n in want.shape), int(lo))
    return float(fn(np.asarray(at, dtype=np.int32), raw, want))


@functools.lru_cache(maxsize=None)
def _outside(shape: tuple, envelope: float, rest: float, lo: int):
    import jax
    import jax.numpy as jnp

    def run(raw):
        a = raw[tuple(slice(lo, lo + n) for n in shape)].astype(jnp.float32)
        inside = jnp.isfinite(a) & (jnp.abs(a - rest) <= envelope)
        return jnp.sum((~inside).astype(jnp.int32))

    return jax.jit(run)


def state_bad_cells(setup: mhd.Setup, raws, lo: int) -> int:
    """Interior cells of the program's sixteen raw arrays that are not finite or
    lie more than ``envelope`` from their field's rest value
    (``reference_mhd.state_bad_cells`` on interiors nobody materialises: one
    quantity at a time, the cut fused into the count)."""
    bad = 0
    for q, raw in zip(QUANTITIES, raws):
        rest = setup.lnrho0 if q.startswith("lnrho") else 0.0
        bad += int(_outside(tuple(setup.shape), setup.envelope, rest, int(lo))(raw))
    return bad
