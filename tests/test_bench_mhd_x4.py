"""The benchmark's cell ``astaroth-mhd-256x4.bulk`` on the CPU: its rehearsal
through ``harness/window.py`` on four of the host's devices (16^3 a chip = 32 x
32 x 16 on mesh [2,2,1], a NON-CUBIC grid on a uniform cell) -- sound traced and
untraced, not correct with bf16 storage by ``max_abs_err`` alone, and NOT
CORRECT WITH ONE STAGE'S Y SWEEP PATCHED OUT (the y seam, and the x-y edge the
y sweep carries over its second wire: the box is periodic and nowhere zero) --,
the configuration's numbers against the ISSUE's and the one-chip file's, the
benchmark's reference (every chip its own block, in pieces with margins)
against the whole-array update and the program's own reference on a per-axis
box, and the twelve ``.mhdx4`` per-layer metrics."""

import contextlib
import io
import itertools
import json
import math
import os
import sys
import time
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import bytes_mhd, flops_mhd, reference_mhd as mhd  # noqa: E402
from benchmark.harness import reference_mhd_x4 as x4  # noqa: E402

CELL, TWIN = "astaroth-mhd-256x4.bulk", "astaroth-mhd-256.bulk"
N, DISPATCH = 16, 2  # rehearsal extent a chip, time steps per dispatch (one trip of the step loop)
MHDX4 = ["plane_pass_pct.mhdx4", "mhd_pass_hbm_pct.mhdx4", "mhd_pass_flops_pct.mhdx4",
         "exchange_dev_pct.mhdx4", "exchange_x_pct.mhdx4", "exchange_y_pct.mhdx4",
         "collective_pct.mhdx4", "slab_ops_pct.mhdx4", "step_glue_pct.mhdx4",
         "kernel_named_pct.mhdx4", "enqueue_ms_p90.mhdx4", "compiles_in_window.mhdx4"]


def _config(name="astaroth-mhd-256x4"):
    with open(os.path.join(ROOT, "benchmark", "configs", name + ".json")) as f:
        return json.load(f)


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _rehearse(patch=None, own_cell=False, **flags):
    """One rehearsal in process: (last line, checks by name, plan line).  The
    runs share one built cell a storage (``rehearsal_cells``) but the one that
    asks for its ``own_cell`` to break."""
    from rehearsal_cells import shared_build

    from benchmark.harness import window

    opts = types.SimpleNamespace(
        workload=CELL, seed=2**31 + 47, seconds=0.2, trace=0, lower_precision=False,
        describe_trace=False, also_verify=[], rehearse=N, dispatch_size=DISPATCH)
    vars(opts).update(flags)
    out = io.StringIO()
    shared = contextlib.nullcontext() if own_cell else shared_build("benchmark.factories.mhd_x4")
    with contextlib.redirect_stdout(out), shared:
        rc = window.run(opts, time.perf_counter(), patch=patch)
    assert rc == 0
    lines = [json.loads(x) for x in out.getvalue().splitlines() if x.startswith("{")]
    checks = {x["name"]: x for x in lines if x.get("bench") == "check"}
    return lines[-1], checks, next(x for x in lines if x.get("bench") == "plan")


@pytest.mark.parametrize("trace", [0, 1])
def test_the_rehearsed_cell_comes_out_sound(trace):
    line, checks, plan = _rehearse(trace=trace)
    assert line["rehearsal"]["checks_ok"] is True and line["failed"] == 0, (line, checks)
    assert line["correct"] is False and line["metrics"] == {}  # a rehearsal is never a result
    assert line["device"]["count"] == 4
    ran = plan["ran"]
    assert (ran["mesh"], ran["route"], ran["storage"], ran["descents"]) == ([2, 2, 1], "plane", "native", 0)
    assert (ran["quantities"], ran["stages"], ran["passes"], ran["renamed"]) == (16, 3, 3, 8)
    assert (ran["renamed_by_stage"], ran["exchanged"], ran["steps_per_trip"]) == ("8/8/8", "8/8/8", 2)
    # the program's own word for what crossed a wire: three exchanges of eight
    # fields' radius-3 x and y faces of the raw 22^3 block, and behind each y
    # face the corner relay of the joint x-y sweep (both x halos' three rows)
    stage_bytes = (2 * 8 * 6 * 22 * 22 + 2 * 8 * 6 * 3 * 22) * 4
    assert (ran["wired"], ran["wire_bytes"]) == ("xy", 3 * stage_bytes), ran
    assert checks["max_abs_err"]["value"] <= 2e-7 and checks["window_state_bad_cells"]["value"] == 0
    want = {"mcells_per_s_chip", "setup_s"} if not trace else {"compiles_in_window.mhdx4", "compile_s"}
    assert want <= set(line["rehearsal"]["would_report"])
    if trace:  # the program's own spans, as the benchmark's reader finds them
        from benchmark.harness import timeline

        spans = [h[3] for h in timeline.host_spans(timeline.load(), "domain.step")]
        assert spans and all(
            (a["stages"], a["wired"], a["joint"], a["wired_edges"], a["wire_bytes_by_stage"], a["steps"]) == (
                3, "xy", "xy", "xy", "/".join([str(stage_bytes)] * 3), DISPATCH)
            for a in spans
        ), spans[:2]


def test_the_bf16_control_is_not_correct_by_max_abs_err_alone():
    line, checks, plan = _rehearse(lower_precision=True, seed=2**31 + 147)
    assert plan["ran"]["storage"] == "bf16"
    assert line["rehearsal"]["checks_ok"] is False
    assert [n for n, c in checks.items() if not c["ok"]] == ["max_abs_err"], checks
    assert checks["max_abs_err"]["value"] > 20 * checks["max_abs_err"]["limit"]


def test_a_step_whose_third_stage_skips_its_y_sweep_is_not_correct(monkeypatch):
    """The cell's step rebuilt with the y sweep of ONE stage of three patched
    out (the third substep's: its y halo, and the x-y edge that sweep carries
    on from the x sweep, keep what an earlier substep left there -- values one
    or two substeps old, a small error a step on this coarse grid, so six
    steps a dispatch): the step runs, the state stays finite and inside its
    envelope -- and ``max_abs_err`` alone says not correct, dozens of times
    over a sound run's reading: the y seam and the four shard edges lie in a
    box that is nowhere zero."""
    from stencil_tpu.ops import exchange

    real = exchange._sweep_group
    y_sweeps = itertools.count()

    def third_stage_skips_y(blocks, group, *rest):
        # x and y fly as one group on this mesh; the group cut to x is the x sweep alone
        if [s.axis for s in group] == [0, 1] and next(y_sweeps) % 3 == 2:  # stages trace in order, three a step
            group = group[:1]
        return real(blocks, group, *rest)

    def patch(cell):
        monkeypatch.setattr(exchange, "_sweep_group", third_stage_skips_y)
        cell.sim.rebuild_after_reshard()

    line, checks, plan = _rehearse(own_cell=True, patch=patch, seed=2**31 + 247, dispatch_size=6)
    assert next(y_sweeps) >= 3  # the patched sweep was traced
    assert plan["ran"]["route"] == "plane" and line["failed"] == 0
    assert line["rehearsal"]["checks_ok"] is False
    assert [n for n, c in checks.items() if not c["ok"]] == ["max_abs_err"], checks
    assert checks["max_abs_err"]["value"] > 2 * checks["max_abs_err"]["limit"], checks["max_abs_err"]


def test_configuration_states_the_issues_sizes():
    from stencil_tpu.models import astaroth_mhd_reference as ref

    c, one = _config(), _config("astaroth-mhd-256")
    assert (c["chips"], c["mesh"], c["extent_per_chip"], c["global_extent"]) == (
        4, [2, 2, 1], [256, 256, 256], [512, 512, 256])
    assert c["extent_per_chip"] == one["extent_per_chip"]  # the two cells' ratio is weak-scaling efficiency
    assert c["dispatch"] == one["dispatch"] == {"bulk": 8} and c["dispatch"]["bulk"] % 2 == 0
    for key in ("fields", "quantities", "radius", "dtype", "itemsize", "pass", "model"):
        assert c[key] == one[key], key
    assert c["reduced"] == [] and c["pass"] == {"reads": 16, "writes": 8}
    assert c["exchange"] == {"quantities": 8, "stages": 3, "wired_axes": "xy"}
    # the route, the mesh and the wires -- and NEITHER the window, NOR the strip,
    # NOR a depth: those are the planner's answers (a pinned one would shut out
    # the PR that changes it)
    assert c["expect"] == {"route": "plane", "storage": "native", "quantities": 16, "stages": 3,
                           "renamed": 8, "mesh": [2, 2, 1], "wired": "xy", "wrapped": "z"}
    # the CELL is the one-chip file's: the box grows with the grid, dt stays
    assert c["setup"]["box"] == [4 * math.pi, 4 * math.pi, 2 * math.pi]
    assert {k: v for k, v in c["setup"].items() if k != "box"} == {
        k: v for k, v in one["setup"].items() if k != "box"}
    s, s1 = x4.setup_from(c, c["global_extent"]), mhd.setup_from(one, one["global_extent"])
    assert s.spacing == s1.spacing == (2 * math.pi / 256,) * 3 and s.dt == s1.dt
    model = ref.MhdSetup(s.shape, box=s.box)
    assert model.spacing == s.spacing and abs(ref.dt_of(model) - s.dt) < 1e-15
    assert c["guarantees"].startswith(one["guarantees"]) and "shard edges" in c["guarantees"]
    assert set(one["assumed"]) < set(c["assumed"])
    assert {"decomposition", "setup.box", "exchange"} <= set(c["assumed"])
    assert set(c["limits"]) == {"max_abs_err"} and c["limits_why"] and c["resident_bytes_per_chip"]
    # what a chip receives over ICI a substep and a time step
    per_stage = c["exchange"]["quantities"] * len(c["exchange"]["wired_axes"]) * 2 * 3 * 262 * 262 * 4
    assert (per_stage, c["exchange"]["stages"] * per_stage) == (26_359_296, 79_077_888)
    bench = _bench()
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("astaroth-mhd-256x4", "bulk", 4)
    entry = next(k for k in bench["configs"] if k["name"] == "astaroth-mhd-256x4")
    assert entry["source"] == c["source"] and len(entry["source"]) <= 200 and entry["reduced"] == []
    assert "mhdsolver.ac" in entry["source"] and "2103.01597" in entry["source"]
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    assert f"{c['dispatch']['bulk']}-step" in cell["why"]
    # appended behind what was there (PR 51's one-chip cell stands behind it since)
    assert bench["workloads"][10] is cell and bench["configs"][10] is entry
    assert next(m for m in bench["end_to_end"] if m["name"] == "mcells_per_s_chip")["workloads"][8] == CELL


def test_four_chip_cells_are_at_most_half():
    cells = _bench()["workloads"]
    # the cap is judged on the benchmark a PR leaves: 5 of 11 (11 // 2) when this cell came,
    # 5 of 12 against 6 since PR 51's one-chip cell, 6 of 13 -- the cap -- since PR 53's four-chip one,
    # 6 of 14 against 7 since PR 57's one-chip cell
    assert (sum(w["chips"] == 4 for w in cells), len(cells)) == (6, 14)
    assert sum(w["chips"] == 4 for w in cells[:11]) == 5 == 11 // 2


def test_the_references_agree_on_a_box_with_a_side_an_axis():
    """``harness/reference_mhd_x4.py`` on four devices -- every chip its own
    block, in pieces with margins, nothing crossing a chip -- against the
    whole-array update it stands for (``harness/reference_mhd.py``'s, on the
    same per-axis set-up: bit for bit, with one piece a chip and with four)
    and against the program's own reference, written apart (to rounding)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from stencil_tpu.models import astaroth_mhd_reference as ref

    s = x4.setup_from(_config(), [2 * N, 2 * N, N])
    assert s.spacing == (2 * math.pi / N,) * 3  # a rehearsal keeps the cell uniform
    words = np.asarray([9, 8, 7, 6], dtype=np.uint32)
    mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(2, 2, 1), ("x", "y", "z"))
    sharding = NamedSharding(mesh, P("x", "y", "z"))
    steps = 2
    whole = mhd.reference(s, steps, None, words)  # jnp.roll on the whole global arrays
    got = x4.reference(s, steps, sharding, words)
    assert x4.piece_plan(s.shape, (2, 2, 1), 18) == ((1, 1, 1), (N, N, N), (N + 36, N + 36, N))
    for q, a, b in zip(x4.QUANTITIES, got, whole):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=q)
    with pytest.MonkeyPatch.context() as mp:  # ... and cut in four pieces a chip
        mp.setattr(x4, "PIECE_CELLS", (N // 2 + 36) ** 2 * N)
        x4._reference.cache_clear()
        assert x4.piece_plan(s.shape, (2, 2, 1), 18)[:2] == ((2, 2, 1), (N // 2, N // 2, N))
        for q, a, b in zip(x4.QUANTITIES, x4.reference(s, steps, sharding, words), whole):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=q)
    x4._reference.cache_clear()
    model = ref.MhdSetup(s.shape, box=s.box, dt=s.dt)
    state = ref.global_fields(model, words)
    theirs = ref.steps(model, state, steps)
    for q, a in zip(x4.QUANTITIES, got):
        assert float(jnp.max(jnp.abs(a - state[q]))) > 1e-4, q  # it has moved
        np.testing.assert_allclose(np.asarray(a), np.asarray(theirs[q]), rtol=0, atol=5e-7, err_msg=q)
    # the cell's own plan: four pieces of 128 x 128 x 256 a chip, 72 cells of margin
    assert x4.piece_plan((512, 512, 256), (2, 2, 1), 3 * 3 * 8) == (
        (2, 2, 1), (128, 128, 256), (272, 272, 256))
    with pytest.raises(ValueError, match="one side an axis"):
        x4.setup_from({"setup": {**_config()["setup"], "box": [1.0, 2.0]}}, [8, 8, 8])


def test_bytes_and_operations_are_the_twins():
    """The SAME functions over the same 256^3 a chip: the two cells read one
    yardstick whatever later implements the pass."""
    c, one = _config(), _config("astaroth-mhd-256")
    assert bytes_mhd.pass_bytes(c) == bytes_mhd.pass_bytes(one) == 24 * 256**3 * 4
    assert flops_mhd.pass_flops(c) == flops_mhd.pass_flops(one) == 837 * 256**3

    def args_of(name):
        with open(os.path.join(ROOT, "benchmark", "layer_metrics", name + ".json")) as f:
            return json.load(f)

    for mine, twin in (("mhd_pass_hbm_pct.mhdx4", "mhd_pass_hbm_pct"),
                       ("mhd_pass_flops_pct.mhdx4", "mhd_pass_flops_pct"),
                       ("plane_pass_pct.mhdx4", "plane_pass_pct.mhd")):
        a, b = args_of(mine), args_of(twin)
        assert (a["reducer"], a["args"]) == (b["reducer"], b["args"]), mine


def test_the_mhdx4_metrics_are_declared_for_the_cell_alone():
    import importlib

    from benchmark.harness.window import layer_metrics_for

    bench = _bench()
    declared = {m["name"]: m for m in bench["per_layer"]}
    order = [m["name"] for m in bench["per_layer"]]
    assert order[order.index(MHDX4[0]):][: len(MHDX4)] == MHDX4  # one block, appended behind what was there
    reported = {"mcells_per_s_chip", "setup_s"}
    mine = {m["name"]: m for m in layer_metrics_for(CELL, reported)}
    assert set(MHDX4) <= set(mine)
    others = [w["name"] for w in bench["workloads"] if w["name"] != CELL]
    for name in MHDX4:
        assert declared[name]["workloads"] == [CELL] and declared[name]["moves"] == "mcells_per_s_chip"
        assert mine[name]["cells"] == [CELL]
        for key in ("unit", "better", "source", "layer"):
            assert declared[name][key] == mine[name][key], (name, key)
        assert hasattr(importlib.import_module("benchmark.reducers." + mine[name]["reducer"]), "reduce")
        for other in others:
            assert name not in {m["name"] for m in layer_metrics_for(other, reported | {"halo_gbps_chip"})}
    # ... and every other cell's named metrics stay theirs: neither the twin's nor the wired six
    assert not {n for n in mine if n.endswith((".mhd", ".wired", ".plane", ".staged", ".bulk", ".lbm"))
                and "idle" not in n}
    assert not {"mhd_pass_hbm_pct", "mhd_pass_flops_pct"} & set(mine)
    assert not {n for n in (m["name"] for m in layer_metrics_for(TWIN, reported)) if n.endswith(".mhdx4")}
