"""``AstarothMHD`` on a box with a side of its own an axis (ISSUE 47: the
weak-scaled grid of ``astaroth-mhd-256x4``), what ``domain.step`` says of the
wires stage by stage, and the driver.  Split out of
``tests/test_astaroth_mhd.py`` (ISSUE 55), which one worker runs whole: these
cases build three models of their own and two more inside the driver."""

import jax
import numpy as np
import pytest

from test_astaroth_mhd import N, TOL, WORDS, _SIMS, _errors, _load, _setup, _shared

from stencil_tpu.models import astaroth_mhd_reference as ref
from stencil_tpu.models.astaroth_mhd import RADIUS, AstarothMHD
from stencil_tpu.telemetry import names as tm

#: a weak-scaled grid as ``astaroth-mhd-256x4`` has it: x = y = 2z cells on ONE
#: cell, so the box has a side of its own an axis (4 pi x 4 pi x 2 pi at N = 16)
WIDE = (2 * N, 2 * N, N)


def _wide_setup():
    return ref.MhdSetup(WIDE, box=tuple(2.0 * np.pi * n / N for n in WIDE), max_waves=2)


def _wide_sim(mesh, impl):
    if ("wide", mesh, impl) not in _SIMS:
        sim = AstarothMHD(*WIDE, setup=_wide_setup(), interpret=True, seed_words=None,
                          kernel_impl=impl, devices=jax.devices()[: int(np.prod(mesh))])
        sim.dd.set_partition(*mesh)
        sim.realize()
        _SIMS["wide", mesh, impl] = sim
    return _SIMS["wide", mesh, impl]


@pytest.mark.parametrize("steps", [1, 2, 3, 4])
@pytest.mark.parametrize("mesh,impl", [((2, 2, 1), "pallas"), ((2, 2, 2), "pallas"), ((2, 2, 1), "jnp")])
def test_a_box_with_a_side_of_its_own_an_axis_matches_the_reference(mesh, impl, steps):
    """32 x 32 x 16 cells on a uniform cell (ISSUE 47: weak scaling keeps the
    CELL, the box grows with the grid), plane route and XLA slice engine, on
    the meshes where the x-y edge halo crosses two wires: every cell of all
    sixteen quantities after 1-4 time steps, a step a dispatch."""
    setup = _wide_setup()
    assert setup.spacing == (2.0 * np.pi / N,) * 3 and ref.dt_of(setup) == ref.dt_of(_setup())
    sim = _wide_sim(mesh, impl)
    state = ref.global_fields(setup, np.asarray(WORDS, dtype=np.uint32))
    _load(sim, state)
    for _ in range(steps):
        sim.step(1)
    assert tuple(sim.dd.mesh_dim()) == mesh
    assert max(_errors(sim, ref.steps(setup, state, steps)).values()) < TOL



def _stage_sends(sim, steps=1):
    """Per stage and swept axis, the bytes of the ``ppermute`` equations of the
    traced step under ``step.stage.<k>/.../exchange.<axis>``."""
    from stencil_tpu.analysis import jaxpr as jx

    closed = jax.make_jaxpr(sim._step._resilience.built(), static_argnums=1)(sim.dd._curr, steps)
    sent = {}
    for e in jx.iter_eqns(closed):
        if e.primitive.name != "ppermute":
            continue
        stack = jx.name_stack_str(e)
        (k,) = [k for k in range(ref.SUBSTEPS) if tm.step_stage_span(k) in stack.split("/")]
        (axis,) = [a for a in "xyz" if tm.exchange_axis_span(a) in stack.split("/")]
        nbytes = sum(int(np.prod(v.aval.shape)) * v.aval.dtype.itemsize for v in e.invars)
        sent[k, axis] = sent.get((k, axis), 0) + nbytes
    return sent


@pytest.mark.parametrize("chip_sweeps", [False, True])
def test_the_span_says_what_crosses_the_wires_stage_by_stage(chip_sweeps, monkeypatch):
    """``domain.step`` on mesh [2,2,1] (ISSUE 47): ``wired`` "xy", ``wrapped``
    "z" with the sweeps as the chip has them ("" as the CPU has them), the
    raw window and no strip beside a y halo that arrives over a wire,
    ``wire_bytes`` = the bytes of the traced ``ppermute``s, which sit under
    ``step.stage.<k>/exchange.x|y`` -- three equal stages, as
    ``wire_bytes_by_stage`` says --, and ``wired_edges`` "xy": the mixed
    differences read ``sh(+-k, +-k, 0)``, an edge that reaches a shard over
    two wires in turn."""
    if chip_sweeps:
        monkeypatch.setenv("STENCIL_HALO_BLEND", "1")
        sim = AstarothMHD(*WIDE, setup=_wide_setup(), interpret=True, seed_words=None,
                          devices=jax.devices()[:4])
        sim.realize()  # the partitioner's own pick
    else:
        sim = _wide_sim((2, 2, 1), "pallas")
    assert tuple(sim.dd.mesh_dim()) == (2, 2, 1)
    said = sim._step._span_args()
    raw = N + 2 * RADIUS
    faces = 2 * 8 * 2 * RADIUS * raw * raw * 4  # two axes, eight fields, six planes of the raw block
    # x and y fly jointly: behind each y face the corner relay, both x halos on its three rows
    stage = faces + 2 * 8 * 2 * RADIUS * RADIUS * raw * 4
    assert (said["wired"], said["wrapped"]) == ("xy", "z" if chip_sweeps else "")
    assert (said["plane_window"], said["plane_strip"], said["exchanged"]) == ("raw", 0, "8/8/8")
    assert said["wire_bytes"] == 3 * stage == 557_568 + 76_032 and said["joint"] == "xy"
    assert said["wire_bytes_by_stage"] == "/".join([str(stage)] * 3)
    assert said["wired_edges"] == "xy"
    sent = _stage_sends(sim)  # every send inside its stage, under its sweep's scope
    # (the CPU's sweeps also "send" the unsplit z axis's wrap to the shard itself: no wire)
    assert sorted(sent) == [(k, a) for k in range(3) for a in ("xy" if chip_sweeps else "xyz")]
    assert [sum(sent[k, a] for a in "xy") for k in range(3)] == [stage] * 3
    assert sum(v for (_, a), v in sent.items() if a in "xy") == said["wire_bytes"]
    # one split axis, or none: no edge crosses two wires
    line = _shared(mesh=(2, 1, 1))._step._span_args()
    assert (line["wired"], line["wired_edges"]) == ("x", "")
    assert line["wire_bytes_by_stage"] == "/".join([str(faces // 2)] * 3) and line["joint"] == ""
    alone = _shared()._step._span_args()
    assert (alone["wired"], alone["wired_edges"], alone["wire_bytes_by_stage"]) == ("", "", "0/0/0")
    # ... and all three pairs where all three axes are split
    assert _shared(mesh=(2, 2, 2))._step._span_args()["wired_edges"] == "xy/xz/yz"


# --- the driver -------------------------------------------------------------------------


def test_driver_runs_on_the_cpu(capsys, tmp_path):
    """``stencil-astaroth-mhd`` takes the box, prints the cell's figure of merit,
    says on stderr what the planner made of the three substeps and writes its
    metrics where it is told."""
    from stencil_tpu.bin import astaroth_mhd

    out = tmp_path / "metrics.json"
    rc = astaroth_mhd.main(["16", "16", "16", "--iters", "1", "--steps", "1",
                            "--metrics-out", str(out)])
    assert rc == 0
    io = capsys.readouterr()
    row = io.out.strip().splitlines()[-1].split(",")
    assert row[0] == "astaroth_mhd" and row[3:6] == ["16", "16", "16"] and float(row[-1]) > 0
    (said,) = [l for l in io.err.splitlines() if l.startswith("mesh: ")]
    assert "route='plane'" in said and "stages=3" in said and "renamed=8/8/8" in said, said
    assert out.exists() and out.stat().st_size > 0
    # the host's eight devices: every axis split, every edge over two wires
    assert said.startswith("mesh: 2,2,2 wired='xyz' ") and "wired_edges='xy/xz/yz'" in said, said


def test_driver_keeps_the_cell_on_a_weak_scaled_grid(capsys, monkeypatch):
    """``stencil-astaroth-mhd 32 32 16`` on four devices: the partitioner's
    own mesh 2,2,1, the CELL of ``16 16 16`` (so its time step), and what
    crosses the wires on stderr as ``stencil-acoustic`` says it."""
    from stencil_tpu.bin import astaroth_mhd

    monkeypatch.setattr(jax, "devices", lambda *a, real=jax.devices: real(*a)[:4])
    rc = astaroth_mhd.main(["32", "32", "16", "--iters", "1", "--steps", "1"])
    assert rc == 0
    io = capsys.readouterr()
    row = io.out.strip().splitlines()[-1].split(",")
    assert row[3:6] == ["32", "32", "16"] and float(row[-1]) > 0
    assert abs(float(row[6]) - ref.dt_of(_setup())) < 1e-15  # the cube's time step
    (said,) = [l for l in io.err.splitlines() if l.startswith("mesh: ")]
    assert said.startswith("mesh: 2,2,1 wired='xy' wrapped='' wire_bytes=633600 wired_edges='xy'"), said
