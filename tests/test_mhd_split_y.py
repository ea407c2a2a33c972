"""Astaroth's MHD step beside a SPLIT y on the aligned window (ISSUE 48): the
plane route on a CPU mesh [2,2,1] at a shard the window takes, against the plain
reference (``models/astaroth_mhd_reference.py``).  A file of its own: the case
interprets four shards of sixteen quantities and would lengthen
``tests/test_astaroth_mhd.py``, which one worker runs whole."""

import numpy as np

import jax
import jax.numpy as jnp

from test_astaroth_mhd import TOL, WORDS, _errors, _load

from stencil_tpu.models import astaroth_mhd_reference as ref
from stencil_tpu.models.astaroth_mhd import AstarothMHD


def test_the_window_beside_a_split_y_matches_the_reference(monkeypatch):
    """8 x 128 x 128 cells on mesh [2,2,1], 4 x 64 x 128 a shard (interiors of
    whole tiles, eight tiles of rows for a six-row y shell), the blend kernels
    on as on the chip, so the pass is handed the z fills alone and the y halo is
    the neighbour shard's rows: the passes work on the aligned ``"interior-z"``
    window, two strips of four tiles a plane -- the z halo the rotates'
    wraparound, the y halo rows in the margin tiles' last sublane, the
    x-y edge halo (two wires in turn) and the y-z corner (the z fill over the
    received rows) among them -- and every cell of all sixteen quantities
    matches the reference after a trip of two steps and one behind the loop.
    The seeded state is periodic over the GLOBAL box and nowhere zero: a pass
    that wrapped y onto its own shard would be wrong by far more than the
    limit."""
    monkeypatch.setenv("STENCIL_HALO_BLEND", "1")
    shape = (8, 128, 128)
    setup = ref.MhdSetup(shape, max_waves=2)
    sim = AstarothMHD(*shape, setup=setup, interpret=True, seed_words=None,
                      devices=jax.devices()[:4])
    sim.dd.set_partition(2, 2, 1)
    sim.realize()
    state = ref.global_fields(setup, np.asarray(WORDS, dtype=np.uint32))
    _load(sim, state)
    sim.step(3)
    said = sim._step._span_args()
    assert (said["route"], said["wired"], said["wrapped"]) == ("plane", "xy", "z")
    assert (said["plane_window"], said["plane_strip"]) == ("interior-z", 32), said
    assert (said["renamed"], said["steps_per_trip"], said["wired_edges"]) == ("8/8/8", 2, "xy")
    want = ref.steps(setup, state, 3)
    moved = min(float(jnp.abs(want[q] - state[q]).max()) for q in ref.FIELDS)
    assert moved > 100 * TOL, moved  # every field advanced: the comparison sees the step
    assert max(_errors(sim, want).values()) < TOL
    # a shard's y neighbour is no copy of the shard: wrapping y onto itself is another state
    halves = np.asarray(state["uy"]).reshape(8, 2, 64, 128)
    assert float(np.abs(halves[:, 0] - halves[:, 1]).max()) > 100 * TOL
