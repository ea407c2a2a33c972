"""The benchmark's cell ``acoustic-so8-600.bulk`` on the CPU: its rehearsal
through ``harness/window.py`` (the control flow of a chip run, interpreted,
16^3) with the bf16-storage control coming out not correct, the reducer and
the byte count of ``plane_pass_hbm_pct``, and the configuration's numbers
against the model's and the ISSUE's."""

import json
import os
import sys
import time
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import bytes_plane, reference_acoustic as wave  # noqa: E402
from benchmark.reducers import named_roofline_hbm  # noqa: E402

CELL = "acoustic-so8-600.bulk"


def _config():
    with open(os.path.join(ROOT, "benchmark", "configs", "acoustic-so8-600.json")) as f:
        return json.load(f)


def _rehearse(capsys, **flags):
    from benchmark.harness import window

    opts = types.SimpleNamespace(
        workload=CELL, seed=2**31 + 27, seconds=0.2, trace=0, lower_precision=False,
        describe_trace=False, also_verify=[], rehearse=16, dispatch_size=6)
    vars(opts).update(flags)
    capsys.readouterr()
    assert window.run(opts, time.perf_counter()) == 0
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines() if x.startswith("{")]
    checks = {x["name"]: x for x in lines if x.get("bench") == "check"}
    plan = next(x for x in lines if x.get("bench") == "plan")
    return lines[-1], checks, plan


def test_rehearsal_is_sound(capsys):
    line, checks, plan = _rehearse(capsys)
    assert line["rehearsal"]["checks_ok"] is True and line["failed"] == 0, (line, checks)
    assert line["correct"] is False and line["metrics"] == {}  # a rehearsal is never a result
    assert plan["ran"]["route"] == "plane" and plan["ran"]["depth"] == 1 and plan["ran"]["descents"] == 0
    assert checks["max_abs_err"]["value"] <= 1e-6  # far inside the cell's limit on the CPU
    assert checks["frame_nonzero_cells"]["value"] == 0 and checks["window_state_bad_cells"]["value"] == 0


@pytest.mark.parametrize("seed", [101, 2**31 + 102])
def test_control_comes_out_not_correct(capsys, seed):
    """bf16 storage, the program's own lower-precision axis: every check but
    ``max_abs_err`` holds, and that one fails by orders of magnitude."""
    line, checks, plan = _rehearse(capsys, lower_precision=True, seed=seed)
    assert plan["ran"]["storage"] == "bf16"
    assert line["rehearsal"]["checks_ok"] is False
    assert [n for n, c in checks.items() if not c["ok"]] == ["max_abs_err"]
    assert checks["max_abs_err"]["value"] > 20 * checks["max_abs_err"]["limit"]


def test_configuration_states_the_issues_sizes():
    from stencil_tpu.models.acoustic_reference import CFL, COEFFS, FRAME, MODES, AcousticGrid

    c = _config()
    assert c["global_extent"] == c["extent_per_chip"] == [512 + 2 * 40 + 2 * 4] * 3
    assert (c["radius"], c["space_order"], c["nbl"], c["quantities"], c["fields"]) == (4, 8, 40, 4, 1)
    assert c["reduced"] == [] and c["mesh"] == [1, 1, 1] and c["dtype"] == "float32"
    assert c["expect"] == {"route": "plane", "depth": 1, "storage": "native"}
    grid, s = AcousticGrid(tuple(c["global_extent"])), wave.setup_from(c, c["global_extent"])
    # the benchmark's copy and the model agree on every number they share
    assert (s.nbl, s.frame, s.spacing, s.vp_min, s.vp_max, s.nlayers, s.modes, s.cfl) == (
        grid.nbl, FRAME, grid.spacing, grid.vp_min, grid.vp_max, grid.nlayers, MODES, CFL)
    assert s.dt == grid.dt and s.physical == (512, 512, 512) and wave.COEFFS == COEFFS
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert sum(w["chips"] == 4 for w in bench["workloads"]) * 2 <= len(bench["workloads"])
    assert CELL in next(m for m in bench["end_to_end"] if m["name"] == "mcells_per_s_chip")["workloads"]


def test_two_copies_of_the_update_agree():
    """``harness/reference_acoustic.py`` (zero halo, fused profiles) against
    ``models/acoustic_reference.py`` (periodic ``jnp.roll``, whole arrays) on
    the benchmark's seeded fields: written apart, they differ by roundings."""
    import jax.numpy as jnp

    from stencil_tpu.models import acoustic_reference as ref

    c = _config()
    s = wave.setup_from(c, [24] * 3)
    assert s.nbl == 6 and s.physical == (4, 4, 4)
    words = np.asarray([9, 8, 7, 6], dtype=np.uint32)
    x, y, z = wave._coords(s.shape)
    f = {k: jnp.broadcast_to(fn(x, y, z, words), s.shape).astype(jnp.float32)
         for k, fn in wave.seeded_fields(s).items()}
    grid = ref.AcousticGrid(s.shape, nbl=s.nbl)
    want = ref.steps_framed(grid, f["u"], f["u_prev"], f["m"], f["damp"], 5)
    got = wave.reference(s, 5, None, words)
    assert float(jnp.max(jnp.abs(want[0]))) > 1e-3
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=0, atol=1e-6)
    assert wave.frame_nonzero(s, got[0]) == 0 and wave.frame_nonzero(s, f["m"]) > 0


def test_plane_pass_bytes_come_from_the_configuration_alone():
    c = _config()
    assert bytes_plane.plane_pass_bytes(c) == (4 + 2) * 608**3 * 4
    c["pass"] = {"reads": 1, "writes": 1}
    c["extent_per_chip"], c["radius"] = [8, 8, 120], 4
    assert bytes_plane.plane_pass_bytes(c) == 2 * 16 * 16 * 128 * 4


def test_named_roofline_on_the_fixture_timeline():
    """The fixture's ``stream_wavefront_pass`` runs once per chip, 100 ns on
    chip 0 and 300 ns on chip 1: 2 calls x bytes / peak over 400 ns."""
    from benchmark import selftest_timeline as st

    c = _config()
    per_call = bytes_plane.plane_pass_bytes(c)
    peak = 2 * per_call / 400e-9 * 4  # so that the share is 25 %
    ctx = {"timeline": st.fixture(), "table": None, "peaks": {"hbm_bytes_per_s": peak}, "config": c}
    args = {"kernel": "^stream_wavefront_pass$", "bytes_fn": "benchmark.harness.bytes_plane:plane_pass_bytes"}
    assert abs(named_roofline_hbm.reduce(ctx, **args) - 25.0) < 1e-9
    # nothing to read: no such kernel, a program that names nothing, no trace
    assert named_roofline_hbm.reduce(ctx, **{**args, "kernel": "^stream_plane_pass$"}) is None
    assert named_roofline_hbm.reduce({**ctx, "timeline": st.fixture("parent")}, **args) is None
    assert named_roofline_hbm.reduce({**ctx, "timeline": None}, **args) is None
