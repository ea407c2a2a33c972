"""Astaroth's MHD step beside a SPLIT y on the aligned window (ISSUE 48): the
plane route on a CPU mesh [2,2,1] at a shard the window takes, against the plain
reference (``models/astaroth_mhd_reference.py``).  A file of its own: its cases
interpret four shards of sixteen quantities and would lengthen
``tests/test_astaroth_mhd.py``, which one worker runs whole."""

import numpy as np

import jax
import jax.numpy as jnp

from test_astaroth_mhd import TOL, WORDS, _errors, _load

from stencil_tpu.models import astaroth_mhd_reference as ref
from stencil_tpu.models.astaroth_mhd import AstarothMHD


SHAPE = (8, 128, 128)
_BUILT = []


def _model(monkeypatch):
    """The one model of this file, built by whichever case comes first: 8 x 128
    x 128 cells on mesh [2,2,1], 4 x 64 x 128 a shard (interiors of whole tiles,
    eight tiles of rows for a six-row y shell), the blend kernels on as on the
    chip, three times the Courant number (see the second case)."""
    monkeypatch.setenv("STENCIL_HALO_BLEND", "1")
    if not _BUILT:
        setup = ref.MhdSetup(SHAPE, max_waves=2, courant=0.9)
        sim = AstarothMHD(*SHAPE, setup=setup, interpret=True, seed_words=None,
                          devices=jax.devices()[:4])
        sim.dd.set_partition(2, 2, 1)
        sim.realize()
        _BUILT.append(sim)
    return _BUILT[0]


def test_the_step_beside_a_split_y_takes_the_aligned_window(monkeypatch):
    """The pass is handed the z fills alone and the y halo is the neighbour
    shard's rows: the planner puts the passes on the aligned ``"interior-z"``
    window, two strips of four tiles a plane, three stages of eight renames,
    x and y over the wires and the x-y edge over both in turn.  (Building and
    planning the model is a fifth of this file's time: a case of its own keeps
    either under the 60 s a case may take, ROADMAP D13.)"""
    said = _model(monkeypatch)._step._span_args()
    assert (said["route"], said["wired"], said["wrapped"]) == ("plane", "xy", "z")
    assert (said["plane_window"], said["plane_strip"]) == ("interior-z", 32), said
    assert (said["renamed"], said["steps_per_trip"], said["wired_edges"]) == ("8/8/8", 2, "xy")


def test_the_window_beside_a_split_y_matches_the_reference(monkeypatch):
    """The passes on the ``"interior-z"`` window -- the z halo the rotates'
    wraparound, the y halo rows in the margin tiles' last sublane, the
    x-y edge halo (two wires in turn) and the y-z corner (the z fill over the
    received rows) among them -- and every cell of all sixteen quantities
    matches the reference after ONE time step of three times the Courant number
    (the time three steps covered until ISSUE 55: a strip-form call of sixteen
    quantities costs 8 s of lowering and 3 s of compile, a step holds three, and
    the window is the same in every one; ``tests/test_astaroth_mhd.py`` holds the
    dispatch of a trip and a step behind it).
    The seeded state is periodic over the GLOBAL box and nowhere zero: a pass
    that wrapped y onto its own shard would be wrong by far more than the
    limit."""
    sim = _model(monkeypatch)
    setup = sim.setup
    state = ref.global_fields(setup, np.asarray(WORDS, dtype=np.uint32))
    _load(sim, state)
    sim.step(1)
    assert sim._step._span_args()["plane_window"] == "interior-z"
    want = ref.steps(setup, state, 1)
    moved = min(float(jnp.abs(want[q] - state[q]).max()) for q in ref.FIELDS)
    assert moved > 100 * TOL, moved  # every field advanced: the comparison sees the step
    assert max(_errors(sim, want).values()) < TOL
    # a shard's y neighbour is no copy of the shard: wrapping y onto itself is another state
    halves = np.asarray(state["uy"]).reshape(8, 2, 64, 128)
    assert float(np.abs(halves[:, 0] - halves[:, 1]).max()) > 100 * TOL
