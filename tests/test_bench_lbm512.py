"""The benchmark's cell ``lbm-d3q19-512.bulk`` on the CPU (ISSUE 51): its
rehearsal through ``harness/window.py`` at 16^3 (sound; the bf16-storage control
and a frozen dispatch each coming out not correct), a program whose planner
refuses the box ending the run cleanly, the slab reference against the
whole-array one for every slab start, the slab readers of the program's state,
the configuration's numbers against the model's and the ISSUE's, the byte count
of ``lbm_pass_hbm_pct.lbm512`` and the ``.lbm512`` per-layer metrics on the
fixture timeline."""

import contextlib
import io
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

from benchmark.harness import bytes_lbm, reference_lbm as lbm  # noqa: E402
from benchmark.harness import reference_lbm_slab as slab  # noqa: E402

CELL = "lbm-d3q19-512.bulk"
N, DISPATCH = 16, 2  # rehearsal extent, steps per dispatch
LBM512 = ["stencil_kernel_pct.lbm512", "lbm_pass_hbm_pct.lbm512", "exchange_dev_pct.lbm512",
          "step_glue_pct.lbm512", "kernel_named_pct.lbm512", "enqueue_ms_p90.lbm512",
          "compiles_in_window.lbm512"]


def _config(name="lbm-d3q19-512"):
    with open(os.path.join(ROOT, "benchmark", "configs", name + ".json")) as f:
        return json.load(f)


def _rehearse(patch=None, **flags):
    """One rehearsal in process: (last line, checks by name, plan line).  The
    runs share one built cell a storage (``rehearsal_cells``)."""
    from rehearsal_cells import shared_build

    from benchmark.harness import window

    opts = types.SimpleNamespace(
        workload=CELL, seed=2**31 + 51, seconds=0.2, trace=0, lower_precision=False,
        describe_trace=False, also_verify=[], rehearse=N, dispatch_size=DISPATCH)
    vars(opts).update(flags)
    out = io.StringIO()
    with contextlib.redirect_stdout(out), shared_build("benchmark.factories.lbm_slab"):
        rc = window.run(opts, time.perf_counter(), patch=patch)
    assert rc == 0
    lines = [json.loads(x) for x in out.getvalue().splitlines() if x.startswith("{")]
    checks = {x["name"]: x for x in lines if x.get("bench") == "check"}
    return lines[-1], checks, next(x for x in lines if x.get("bench") == "plan")


def test_the_rehearsed_cell_comes_out_sound():
    line, checks, plan = _rehearse()
    assert line["rehearsal"]["checks_ok"] is True and line["failed"] == 0, (line, checks)
    assert line["correct"] is False and line["metrics"] == {}  # a rehearsal is never a result
    ran = plan["ran"]
    assert (ran["storage"], ran["descents"], ran["quantities"], ran["diagonal"]) == ("native", 0, 19, 12)
    assert {"tile_rows", "y_tiles", "aliased", "plane_window"} <= set(ran)  # the plan line says them
    assert plan["planned"] == {"storage": "native", "quantities": 19, "diagonal": 12, "aliased": 19}
    assert checks["max_abs_err"]["value"] <= 1e-6 and checks["window_state_bad_cells"]["value"] == 0
    assert checks["mass_drift"]["value"] < checks["mass_drift"]["limit"]
    assert checks["uncompared_cells"]["value"] == checks["unseen_planes"]["value"] == 0
    assert {"mcells_per_s_chip", "setup_s"} <= set(line["rehearsal"]["would_report"])


def test_the_bf16_control_is_not_correct():
    line, checks, plan = _rehearse(lower_precision=True, seed=2**31 + 102)
    assert plan["ran"]["storage"] == "bf16"
    assert line["rehearsal"]["checks_ok"] is False
    assert "max_abs_err" in [n for n, c in checks.items() if not c["ok"]], checks
    assert checks["max_abs_err"]["value"] > 20 * checks["max_abs_err"]["limit"]


def test_a_frozen_dispatch_is_not_correct(monkeypatch):
    def freeze(cell):  # the step returns its state unchanged (on the shared cell: undone behind the test)
        monkeypatch.setattr(cell, "dispatch", lambda n: None, raising=False)

    line, checks, _ = _rehearse(patch=freeze, seed=7)
    bad = [n for n, c in checks.items() if not c["ok"]]
    assert line["rehearsal"]["checks_ok"] is False and "max_abs_err" in bad, (line, checks)


def test_a_program_whose_planner_refuses_the_box_ends_the_run_cleanly(monkeypatch):
    """The parent of the PR that added the cell raises ``ValueError`` at plan
    time: the factory asks a model that allocates nothing and exits with the
    planner's own words, before 13 GB are allocated."""
    import jax

    from benchmark.factories import lbm_slab
    from stencil_tpu.domain import DistributedDomain
    from stencil_tpu.models.lbm import LatticeBoltzmann

    def refuse(self):
        raise ValueError("the plane pass that writes ('f0',) ... it fits no pass")

    allocated = []
    real = DistributedDomain.realize
    monkeypatch.setattr(LatticeBoltzmann, "_build_step", refuse)
    monkeypatch.setattr(DistributedDomain, "realize",
                        lambda self, allocate=True: (allocated.append(allocate), real(self, allocate))[1])
    config = dict(_config(), global_extent=[N] * 3, extent_per_chip=[N] * 3)
    with pytest.raises(SystemExit, match=r"planner refuses the box \(16, 16, 16\).*it fits no pass"):
        lbm_slab.build(config, jax.devices()[:1], True)
    assert allocated == [False]
    with pytest.raises(SystemExit, match="ONE chip"):
        lbm_slab.build(config, jax.devices()[:2], True)


# --- the slab reference and the slab readers --------------------------------------------

#: one small box for both tests: the compiled references are shared
_SMALL = (lbm.setup_from(_config(), [12, 8, 16]), np.asarray([5, 1, 5, 1], dtype=np.uint32), 3, 4)


def test_the_slab_reference_is_the_whole_array_one_for_every_slab_start():
    """``reference_slab`` on planes ``first .. first + width`` against the same
    planes of ``reference_lbm.reference`` on whole arrays: every start of a
    12-plane box, the ones whose overlap wraps around the box's ends included;
    written apart (coordinates modulo the box where the other rolls whole
    arrays), the same numbers."""
    s, words, steps, width = _SMALL
    whole = [np.asarray(a) for a in lbm.reference(s, steps, None, words)]
    c = (np.arange(12)[:, None, None], np.arange(8)[None, :, None], np.arange(16)[None, None, :])
    seeded = [np.broadcast_to(np.asarray(lbm.seeded_fields(s)[q](*c, words)), s.shape) for q in lbm.NAMES]
    assert max(np.abs(a - b).max() for a, b in zip(whole, seeded)) > 1e-4  # it has moved
    for first in range(12 - width + 1):
        got = slab.reference_slab(s, steps, words, first, width)
        assert len(got) == lbm.Q and got[0].shape == (width, 8, 16)
        for q, g, w in zip(lbm.NAMES, got, whole):
            np.testing.assert_allclose(
                np.asarray(g), w[first : first + width], rtol=0, atol=2e-7, err_msg=f"{q}@{first}")
    assert slab.slab_starts(12, 4) == [0, 4, 8] and slab.slab_starts(10, 4) == [0, 4, 6]
    assert slab.slab_starts(3, 16) == [0]


def test_the_slab_readers_see_every_cell_of_the_programs_arrays_once():
    """``slab_error`` and ``state_counts`` on shell-carrying arrays: the interior
    cut at a traced plane, the shell never read, a plane two slabs share counted
    once, a spoiled cell found wherever it sits."""
    s, words, steps, width = _SMALL  # (the test above has compiled both references)
    state = [np.asarray(a) for a in lbm.reference(s, steps, None, words)]
    lo, starts = 1, (0, 4, 6, 8)  # the slabs at 4 and 6 share planes 6 and 7
    raws = [np.pad(a, lo, constant_values=np.nan) for a in state]  # a shell nobody may read
    wants = {first: slab.reference_slab(s, steps, words, first, width) for first in starts}
    for first in starts:
        assert max(slab.slab_error(r, lo, s.shape, first, w) for r, w in zip(raws, wants[first])) < 2e-7
    short = lbm.setup_from(_config(), [10, 8, 16])  # ten planes in slabs of four: 0, 4, 6
    cut = [r[: 10 + 2 * lo] for r in raws]
    bad, mass, planes = slab.state_counts(short, cut, lo, width)
    whole_bad, whole_mass = lbm.state_counts(short, [a[:10] for a in state])
    assert (bad, planes) == (whole_bad, 10) == (0, 10) and abs(mass - whole_mass) < 1e-9 * whole_mass
    spoiled = [r.copy() for r in cut]
    spoiled[3][lo + 7, lo + 2, lo + 3] = np.nan  # in the overlap of the last two slabs
    spoiled[5][lo + 0, lo + 0, lo + 0] += 0.5
    assert slab.state_counts(short, spoiled, lo, width)[0] == 2
    assert slab.slab_error(spoiled[3], lo, s.shape, 6, wants[6][3]) == float("inf")
    assert abs(slab.slab_error(spoiled[5], lo, s.shape, 0, wants[0][5]) - 0.5) < 1e-6


# --- the configuration --------------------------------------------------------------------


def test_configuration_states_the_issues_sizes():
    from stencil_tpu.models import lbm_reference as ref

    c, small = _config(), _config("lbm-d3q19-256")
    assert c["global_extent"] == c["extent_per_chip"] == [512, 512, 512]
    assert (c["radius"], c["quantities"], c["fields"], c["chips"]) == (1, 19, 1, 1)
    assert c["reduced"] == [] and c["mesh"] == [1, 1, 1] and c["dtype"] == "float32"
    assert c["pass"] == {"reads": 19, "writes": 19} and c["factory"] == "benchmark.factories.lbm_slab:build"
    # the deployment's shapes are the 256 box's, letter for letter: only the scale differs
    for key in ("setup", "pass", "fields", "quantities", "dtype", "itemsize", "radius", "model", "guarantees"):
        assert c[key] == small[key], key
    assert set(small) - set(c) == set() and set(c) - set(small) == {"reference"}
    assert set(small["assumed"]) <= set(c["assumed"])
    assert {"extent", "dispatch.bulk", "reference.slab_planes"} <= set(c["assumed"])
    # ``expect`` pins what the deployment fixes and nothing the planner answers
    assert c["expect"] == {"storage": "native", "quantities": 19, "diagonal": 12, "aliased": 19}
    assert c["dispatch"]["bulk"] % 2 == 0 and c["reference"]["slab_planes"] >= 1
    assert set(c["limits"]) == {"max_abs_err", "mass_drift"} and "TBD" not in json.dumps(c)
    s, model = lbm.setup_from(c, c["global_extent"]), ref.LbmSetup(tuple(c["global_extent"]))
    assert (s.nu, s.u0, s.modes, s.rho0, s.max_waves) == (
        model.nu, model.u0, model.modes, model.rho0, model.max_waves)
    assert (s.rho_band, s.u_max) == (ref.RHO_BAND, ref.U_MAX) and s.omega == model.omega
    # one slot of nineteen raw blocks as the domain stores them: 13.0 GB
    assert 19 * 514 * 520 * 640 * 4 == 13_000_499_200
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    # the chip-share cap is judged on the benchmark a PR leaves: 5 of 12 against 6
    # when this cell came (PR 51), 6 of 13 since PR 53's four-chip cell stands behind it, 6 of
    # 14 against 7 since PR 57's one-chip cell
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 6 <= len(bench["workloads"]) // 2 == 7
    assert CELL in next(m for m in bench["end_to_end"] if m["name"] == "mcells_per_s_chip")["workloads"]
    entry = next(x for x in bench["configs"] if x["name"] == "lbm-d3q19-512")
    assert entry["source"] == c["source"] and len(entry["source"]) == 181 and entry["reduced"] == []
    assert entry["file"] == "benchmark/configs/lbm-d3q19-512.json"
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("lbm-d3q19-512", "bulk", 1)
    # appended behind what was there (PR 53's four-chip cell stands behind it since)
    assert len(cell["why"]) <= 200 and bench["workloads"][11] is cell and bench["configs"][11] is entry


def test_pass_bytes_at_the_card_filling_box():
    c = _config()
    assert bytes_lbm.pass_bytes(c) == 38 * 512**3 * 4 == 20_401_094_656
    # 24.9 ms a call at the v5e's 819 GB/s: one level a call caps the cell at 5,388 Mcells/s
    with open(os.path.join(ROOT, "benchmark", "harness", "peaks.json")) as f:
        peak = json.load(f)["TPU v5 lite"]["hbm_bytes_per_s"]
    assert abs(512**3 / (bytes_lbm.pass_bytes(c) / peak) / 1e6 - 5388) < 1


def test_the_lbm512_metrics_are_declared_for_the_cell_alone():
    import importlib

    from benchmark.harness.window import layer_metrics_for

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        declared = {m["name"]: m for m in json.load(f)["per_layer"]}
    mine = {m["name"]: m for m in layer_metrics_for(CELL, {"mcells_per_s_chip", "setup_s"})}
    assert set(LBM512) <= set(mine) and list(declared)[-30:-23] == LBM512  # (PR 53's eleven and PR 57's twelve behind them)
    for name in LBM512:
        assert declared[name]["workloads"] == [CELL] and declared[name]["moves"] == "mcells_per_s_chip"
        assert mine[name]["cells"] == [CELL] and set(declared[name]) == {
            "name", "unit", "better", "source", "layer", "moves", "workloads"}
        for key in ("unit", "better", "source", "layer"):
            assert declared[name][key] == mine[name][key], (name, key)
        assert hasattr(importlib.import_module("benchmark.reducers." + mine[name]["reducer"]), "reduce")
        for other in ("lbm-d3q19-256.bulk", "astaroth-mhd-256.bulk", "jacobi3d-512.bulk"):
            assert name not in {m["name"] for m in layer_metrics_for(other, {"mcells_per_s_chip", "setup_s"})}
    # ... the 256 cell's named metrics stay its own, and every accepted metric
    # with no list of cells is reported here too
    assert not {n for n in mine if n.endswith((".lbm", ".plane", ".staged", ".wired", ".mhd"))}
    assert {"pallas_pct", "glue_pct", "dispatch_ms_p90", "device_idle_pct.bulk"} <= set(mine)


def test_the_lbm512_shares_read_the_stream_kernels_by_name():
    """On the fixture timeline: the kernel's share, the roofline share against
    ``bytes_lbm.pass_bytes`` of THIS configuration, and nothing on a program that
    names nothing (the parent's line leaves the metric out)."""
    from benchmark import selftest_timeline as st
    from benchmark.reducers import named_roofline_hbm, named_share

    def args_of(name):
        with open(os.path.join(ROOT, "benchmark", "layer_metrics", name + ".json")) as f:
            return json.load(f)["args"]

    for mine, theirs in zip(LBM512, ("stencil_kernel_pct.lbm", "lbm_pass_hbm_pct", "exchange_dev_pct.lbm",
                                     "step_glue_pct.lbm", "kernel_named_pct.lbm", "enqueue_ms_p90.lbm",
                                     "compiles_in_window.lbm")):
        assert args_of(mine) == args_of(theirs)  # the accepted cell's readers, no new reducer
    c = _config()
    per_call = bytes_lbm.pass_bytes(c)
    peak = 2 * per_call / 400e-9 * 4  # so that the share is 25 %
    ctx = {"timeline": st.fixture(), "table": None, "peaks": {"hbm_bytes_per_s": peak}, "config": c}
    assert abs(named_roofline_hbm.reduce(ctx, **args_of("lbm_pass_hbm_pct.lbm512")) - 25.0) < 1e-9
    parent = {**ctx, "timeline": st.fixture("parent")}
    assert named_roofline_hbm.reduce(parent, **args_of("lbm_pass_hbm_pct.lbm512")) is None
    assert 0 < named_share.reduce(ctx, **args_of("stencil_kernel_pct.lbm512")) <= 100
    for name in LBM512[:1] + LBM512[2:5]:
        assert named_share.reduce(parent, **args_of(name)) is None
