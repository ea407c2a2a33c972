"""The benchmark's cell ``astaroth-mhd-512.bulk`` on the CPU (ISSUE 57): its
rehearsal through ``harness/window.py`` at 16^3 (sound; the bf16-storage control
and a frozen dispatch each coming out not correct), a program whose planner
refuses the box ending the run cleanly, the piece reference against the
whole-array one for every piece start, the readers of the program's raw arrays,
the configuration's numbers against the model's and the ISSUE's, the two
yardsticks of a time step's work by hand and the ``.mhd512`` per-layer metrics on
the fixture timeline and on a hand-made one that holds the ``pass.<i>`` scopes."""

import contextlib
import io
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

from benchmark.harness import bytes_mhd, bytes_mhd_step, flops_mhd, flops_mhd_step  # noqa: E402
from benchmark.harness import reference_mhd as mhd  # noqa: E402
from benchmark.harness import reference_mhd_slab as slab  # noqa: E402

CELL = "astaroth-mhd-512.bulk"
N, DISPATCH = 16, 2  # rehearsal extent, time steps per dispatch (one trip of the step loop)
MHD512 = ["plane_pass_pct.mhd512", "exchange_dev_pct.mhd512", "step_glue_pct.mhd512",
          "kernel_named_pct.mhd512", "enqueue_ms_p90.mhd512", "compiles_in_window.mhd512",
          "mhd_pass_hbm_pct.mhd512", "mhd_pass_flops_pct.mhd512"] + [
              f"pass_pct.mhd512.{i}" for i in range(4)]


def _config(name="astaroth-mhd-512"):
    with open(os.path.join(ROOT, "benchmark", "configs", name + ".json")) as f:
        return json.load(f)


def _rehearse(patch=None, **flags):
    """One rehearsal in process: (last line, checks by name, plan line).  The
    runs share one built cell a storage (``rehearsal_cells``)."""
    from rehearsal_cells import shared_build

    from benchmark.harness import window

    opts = types.SimpleNamespace(
        workload=CELL, seed=2**31 + 57, seconds=0.2, trace=0, lower_precision=False,
        describe_trace=False, also_verify=[], rehearse=N, dispatch_size=DISPATCH)
    vars(opts).update(flags)
    out = io.StringIO()
    with contextlib.redirect_stdout(out), shared_build("benchmark.factories.mhd_slab"):
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
    assert (ran["route"], ran["storage"], ran["descents"], ran["quantities"]) == ("plane", "native", 0, 16)
    assert (ran["stages"], ran["renamed"], ran["renamed_by_stage"], ran["steps_per_trip"]) == (3, 8, "8/8/8", 2)
    # the plan line says what the ISSUE asks of it (a 16^3 box is one whole-plane pass a stage)
    assert {"plane_window", "plane_strip", "tile_rows", "passes", "plane_lanes", "passes_by_stage"} <= set(ran)
    assert (ran["tile_rows"], ran["passes"], ran["passes_by_stage"]) == ("0/0/0", 3, None)
    assert plan["planned"] == {"route": "plane", "storage": "native", "quantities": 16,
                               "stages": 3, "renamed": 8, "passes": 12}
    assert checks["max_abs_err"]["ok"] and checks["max_abs_err"]["value"] < 1e-7
    assert "in pieces of 16x16x16" in checks["max_abs_err"]["what"]  # (the box whole: it fits)
    assert checks["window_state_bad_cells"]["value"] == checks["uncompared_cells"]["value"] == 0
    assert {"mcells_per_s_chip", "setup_s"} <= set(line["rehearsal"]["would_report"])


def test_the_bf16_control_is_not_correct():
    line, checks, plan = _rehearse(lower_precision=True, seed=2**31 + 114)
    assert plan["ran"]["storage"] == "bf16"
    assert line["rehearsal"]["checks_ok"] is False
    assert "max_abs_err" in [n for n, c in checks.items() if not c["ok"]], checks
    assert checks["max_abs_err"]["value"] > 100 * checks["max_abs_err"]["limit"]


def test_a_frozen_dispatch_is_not_correct(monkeypatch):
    def freeze(cell):  # the step returns its state unchanged (on the shared cell: undone behind the test)
        monkeypatch.setattr(cell, "dispatch", lambda n: None, raising=False)

    line, checks, _ = _rehearse(patch=freeze, seed=7)
    bad = [n for n, c in checks.items() if not c["ok"]]
    assert line["rehearsal"]["checks_ok"] is False and "max_abs_err" in bad, (line, checks)
    assert checks["max_abs_err"]["value"] > 100 * checks["max_abs_err"]["limit"]


def test_a_program_whose_planner_refuses_the_box_ends_the_run_cleanly(monkeypatch):
    """The parent of the PR that added the cell raises ``FitsNoPass`` (a
    ``ValueError``) at plan time: the factory asks a model that allocates
    nothing and exits with the planner's own words, before 11 GB are allocated."""
    import jax

    from benchmark.factories import mhd_slab
    from stencil_tpu.domain import DistributedDomain
    from stencil_tpu.models.astaroth_mhd import AstarothMHD

    def refuse(self):
        raise ValueError("the plane pass that writes ('ux', ...) reads ('lnrho',), which an earlier "
                         "pass of the same stage has already written in place")

    allocated = []
    real = DistributedDomain.realize
    monkeypatch.setattr(AstarothMHD, "_build_step", refuse)
    monkeypatch.setattr(DistributedDomain, "realize",
                        lambda self, allocate=True: (allocated.append(allocate), real(self, allocate))[1])
    config = dict(_config(), global_extent=[N] * 3, extent_per_chip=[N] * 3)
    with pytest.raises(SystemExit, match=r"planner refuses the box \(16, 16, 16\).*already written in place"):
        mhd_slab.build(config, jax.devices()[:1], True)
    assert allocated == [False]
    with pytest.raises(SystemExit, match="ONE chip"):
        mhd_slab.build(config, jax.devices()[:2], True)


# --- the piece reference and the readers -------------------------------------------------

#: one small box for both tests: the compiled references are shared
_SMALL = (mhd.setup_from(_config(), [48, 48, 8]), np.asarray([5, 7, 5, 7], dtype=np.uint32), 1)


def test_the_piece_reference_is_the_whole_array_one_for_every_piece_start():
    """``reference_piece`` against the same cells of ``reference_mhd.reference`` on
    whole arrays after one time step (three substeps: a margin of nine cells): a
    48 x 48 x 8 box cut along x and y at once into sixteen and into twelve pieces
    (the margins of the outer ones wrap around the box's ends; z rolled whole),
    and the box whole; written apart (coordinates modulo the box, a padded piece
    on the box's own cell), the same numbers."""
    s, words, steps = _SMALL
    whole = [np.asarray(a) for a in mhd.reference(s, steps, None, words)]
    margin = slab.REACH * 3 * steps
    assert margin == 9
    for cells, piece, padded in (
        (7500, (12, 12, 8), (30, 30, 8)),
        (9000, (12, 16, 8), (30, 34, 8)),
        (48 * 48 * 8, (48, 48, 8), (48, 48, 8)),
    ):
        assert slab.piece_plan(s.shape, margin, cells) == (piece, padded)
        starts = slab.piece_starts(s.shape, piece)
        assert len(starts) == math.prod(n // p for n, p in zip(s.shape, piece))
        seen = np.zeros(s.shape, bool)
        for at in starts:
            got = slab.reference_piece(s, steps, words, at, piece, padded)
            cut = tuple(slice(a, a + p) for a, p in zip(at, piece))
            assert len(got) == 16 and got[0].shape == piece
            for q, g, w in zip(mhd.QUANTITIES, got, whole):
                np.testing.assert_allclose(np.asarray(g), w[cut], rtol=0, atol=2e-7, err_msg=f"{q}@{at}")
            seen[cut] = True
        assert seen.all()
    c = (np.arange(48)[:, None, None], np.arange(48)[None, :, None], np.arange(8)[None, None, :])
    seeded = np.broadcast_to(np.asarray(mhd.seeded_fields(s)["ux"](*c, words)), s.shape)
    assert np.abs(whole[1] - seeded).max() > 1e-5  # (it has moved)
    with pytest.raises(ValueError, match="no piece of"):
        slab.piece_plan(s.shape, margin, 100)


def test_the_piece_plan_at_the_real_size():
    """What ``verify`` cuts the 512^3 box into under the configuration's cap,
    for a dispatch of the configuration's length: the fewest cells computed in
    all among the equal cuts whose padded piece fits."""
    c = _config()
    steps = c["dispatch"]["bulk"]
    margin = slab.REACH * 3 * steps
    piece, padded = slab.piece_plan([512] * 3, margin, c["reference"]["piece_cells"])
    assert math.prod(padded) <= c["reference"]["piece_cells"] and all(512 % p == 0 for p in piece)
    assert padded == tuple(p if p == 512 else p + 2 * margin for p in piece)
    # a 2-step dispatch: 64 pieces of 128^3 with 18-cell margins, 2.1 times the box
    assert (steps, piece, padded) == (2, (128, 128, 128), (164, 164, 164))
    assert abs(math.prod(padded) / math.prod(piece) - 2.103) < 1e-3
    # (a 4-step dispatch would be 128 pieces of 64 x 128 x 128, 5.2 times: the margin is what costs)
    assert slab.piece_plan([512] * 3, slab.REACH * 12, c["reference"]["piece_cells"]) == (
        (64, 128, 128), (136, 200, 200))


def test_the_readers_see_every_cell_of_the_programs_arrays_once():
    """``piece_error`` and ``state_bad_cells`` on shell-carrying arrays: a piece
    cut at a traced offset, the shell never read, a spoiled cell found wherever
    it sits."""
    s, words, steps = _SMALL  # (the test above has compiled the whole-array reference)
    state = [np.asarray(a) for a in mhd.reference(s, steps, None, words)]
    lo = 3
    raws = [np.pad(a, lo, constant_values=np.nan) for a in state]  # a shell nobody may read
    piece, padded = (12, 16, 8), (30, 34, 8)
    for at in ((0, 0, 0), (12, 32, 0)):
        want = slab.reference_piece(s, steps, words, at, piece, padded)
        assert max(slab.piece_error(r, lo, at, w) for r, w in zip(raws, want)) < 2e-7
    assert slab.state_bad_cells(s, raws, lo) == mhd.state_bad_cells(s, state) == 0
    spoiled = [r.copy() for r in raws]
    spoiled[3][lo + 13, lo + 34, lo + 3] = np.nan
    spoiled[9][lo + 0, lo + 0, lo + 0] += 0.5
    assert slab.state_bad_cells(s, spoiled, lo) == 2
    want = slab.reference_piece(s, steps, words, (12, 32, 0), piece, padded)
    assert slab.piece_error(spoiled[3], lo, (12, 32, 0), want[3]) == float("inf")
    want = slab.reference_piece(s, steps, words, (0, 0, 0), piece, padded)
    assert abs(slab.piece_error(spoiled[9], lo, (0, 0, 0), want[9]) - 0.5) < 1e-6


# --- the configuration --------------------------------------------------------------------


def test_configuration_states_the_issues_sizes():
    from stencil_tpu.models import astaroth_mhd_reference as ref

    c, small = _config(), _config("astaroth-mhd-256")
    assert c["global_extent"] == c["extent_per_chip"] == [512, 512, 512]
    assert (c["radius"], c["quantities"], c["fields"], c["chips"]) == (3, 16, 8, 1)
    assert c["reduced"] == [] and c["mesh"] == [1, 1, 1] and c["dtype"] == "float32"
    assert c["factory"] == "benchmark.factories.mhd_slab:build"
    # the 256 file's letter wherever the size does not force otherwise
    for key in ("pass", "fields", "quantities", "dtype", "itemsize", "radius", "model", "chips", "mesh"):
        assert c[key] == small[key], key
    differs = {k for k in small["setup"] if c["setup"][k] != small["setup"][k]}
    assert differs == {"dt", "max_waves", "envelope"} and set(c["setup"]) == set(small["setup"])
    assert set(small) - set(c) == set() and set(c) - set(small) == {"reference", "substeps", "passes"}
    assert set(small["assumed"]) <= set(c["assumed"])
    assert {"extent", "setup.dt", "dispatch.bulk", "reference.piece_cells", "passes"} <= set(c["assumed"])
    assert c["expect"] == {**small["expect"], "passes": 12} and c["substeps"] * len(c["passes"]) == 12
    assert sum(p["writes"] for p in c["passes"]) == c["pass"]["writes"] == 8
    assert c["dispatch"]["bulk"] % 2 == 0 and c["dispatch"]["bulk"] >= 2  # whole trips of the step loop
    assert set(c["limits"]) == {"max_abs_err"} and "TBD" not in json.dumps(c)
    # the time step: the smaller of the advective and the diffusive limit, the latter
    # at the 256 file's margin (dt / dx^2 as there) -- and binding
    dx = 2 * math.pi / 512
    advective = c["setup"]["courant"] * dx / (1 + math.sqrt(3) * c["setup"]["amplitude"])
    diffusive = small["setup"]["dt"] / 4
    assert abs(c["setup"]["dt"] - min(advective, diffusive)) < 1e-15 and diffusive < advective
    viscous = lambda s, h: (18.1 * s["nu"] + 6.04 * (s["nu"] / 3 + s["zeta"])) * s["dt"] / h**2  # noqa: E731
    assert abs(viscous(c["setup"], dx) - viscous(small["setup"], 2 * dx)) < 1e-12 and viscous(c["setup"], dx) < 2.51
    assert viscous({**c["setup"], "dt": advective}, dx) > 2.51  # the 256 file's rule alone: unstable
    # 64 cells the shortest seeded wave on either grid
    assert 512 // c["setup"]["max_waves"] == 256 // small["setup"]["max_waves"] == 64
    s = mhd.setup_from(c, c["global_extent"])
    model = ref.MhdSetup(tuple(c["global_extent"]), dt=s.dt, max_waves=s.max_waves)
    for key in ("nu", "eta", "chi", "zeta", "gamma", "cp", "cs0", "mu0", "lnrho0", "lnT0", "box",
                "amplitude", "modes", "max_waves"):
        assert getattr(s, key) == getattr(model, key), key
    # (eight waves a box: the Lorentz force accelerates the flow through the window, assumed."setup.envelope")
    assert ref.dt_of(model) == s.dt and s.envelope == 10 * s.amplitude
    # one slot of sixteen raw blocks as the domain stores them: 11.03 GB
    assert 16 * 518 * 520 * 640 * 4 == 11_032_985_600
    assert "11,032,985,600" in c["resident_bytes_per_chip"] and "memory_peak_bytes" in c["resident_bytes_per_chip"]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    # the chip-share cap on the benchmark this PR leaves: 6 of 14 against 7
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 6 <= len(bench["workloads"]) // 2 == 7
    assert bench["end_to_end"][0]["workloads"][-1] == CELL
    entry = bench["configs"][-1]
    assert (entry["name"], entry["file"]) == ("astaroth-mhd-512", "benchmark/configs/astaroth-mhd-512.json")
    assert entry["source"] == c["source"] and len(entry["source"]) <= 200 and entry["reduced"] == []
    assert len({x["source"] for x in bench["configs"] if x["name"].startswith("astaroth-mhd")}) == 3
    cell = bench["workloads"][-1]  # appended behind what was there
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == (CELL, "astaroth-mhd-512", "bulk", 1)
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    assert f"{c['dispatch']['bulk']}-step" in cell["why"]


@pytest.mark.parametrize("term", ["nu", "eta", "chi", "zeta", "lorentz", "pressure", "advection"])
def test_every_single_term_moves_the_state_far_beyond_the_limit(term):
    """``tests/test_mhd_reference.py``'s rule for THIS file: its coefficients, its
    fixed ``dt`` and its dispatch, its seeded state (eight waves an axis at the
    most: on 48^3, six cells the shortest), the update with ONE term switched off
    against the full one differs by more than 30 times the cell's
    ``max_abs_err`` after one dispatch's worth of steps -- not the 100 the 256 file
    holds: at Mach 0.05 the advective term moves the state by 6.7e-5 within the
    two steps of a dispatch where the sound error reads up to 6.4e-7 on the chip,
    and a longer dispatch grows both (the configuration's ``limits_why``)."""
    import dataclasses

    import jax.numpy as jnp

    from stencil_tpu.models import astaroth_mhd_reference as ref

    config = _config()
    s = config["setup"]
    full = ref.MhdSetup(
        (48, 48, 48), nu=s["nu"], eta=s["eta"], chi=s["chi"], zeta=s["zeta"], gamma=s["gamma"],
        cp=s["cp"], cs0=s["cs0"], mu0=s["mu0"], lnrho0=s["lnrho0"], lnT0=s["lnT0"], box=s["box"],
        dt=s["dt"], amplitude=s["amplitude"], modes=s["modes"], max_waves=s["max_waves"],
    )
    off = {term: 0.0} if term in ("nu", "eta", "chi", "zeta") else {"off": (term,)}
    steps = config["dispatch"]["bulk"]
    state = ref.global_fields(full, np.asarray([3, 1, 4, 1], dtype=np.uint32))
    want = ref.steps(full, state, steps)
    got = ref.steps(dataclasses.replace(full, **off), state, steps)
    worst = max(float(jnp.abs(got[q] - want[q]).max()) for q in ref.QUANTITIES)
    assert worst > 30 * config["limits"]["max_abs_err"], (term, worst)
    assert worst > 100 * config["limits"]["max_abs_err"] or term in ("advection", "nu", "chi", "zeta")
    assert all(bool(jnp.isfinite(want[q]).all()) for q in ref.QUANTITIES)


# --- the yardsticks and the per-layer metrics -----------------------------------------------


def test_the_yardsticks_count_a_time_steps_own_work_over_its_calls():
    c = _config()
    assert bytes_mhd_step.calls_per_step(c) == 12
    assert bytes_mhd_step.step_bytes(c) == 3 * 24 * 512**3 * 4 == 38_654_705_664
    assert bytes_mhd_step.pass_bytes(c) == 3 * 24 * 512**3 * 4 / 12
    assert flops_mhd_step.step_flops(c) == 3 * 837 * 512**3
    assert flops_mhd_step.pass_flops(c) == 3 * 837 * 512**3 / 12
    # the same work whatever number of passes implements a substep: six, or one
    six = {**c, "passes": c["passes"] + c["passes"][:2]}
    one = {**c, "passes": c["passes"][:1]}
    for other in (six, one):
        calls = bytes_mhd_step.calls_per_step(other)
        assert calls * bytes_mhd_step.pass_bytes(other) == bytes_mhd_step.step_bytes(c)
        assert calls * flops_mhd_step.pass_flops(other) == flops_mhd_step.step_flops(c)
    assert bytes_mhd_step.pass_bytes(one) == bytes_mhd.pass_bytes(c)  # the 256 cells' count, one pass a substep
    assert flops_mhd_step.pass_flops(one) == flops_mhd.pass_flops(c)
    # ``calls x`` the one-pass count would read four times the work
    assert 12 * bytes_mhd.pass_bytes(c) == 4 * bytes_mhd_step.step_bytes(c)
    # a time step at the v5e's 819 GB/s: 47.2 ms, 22,750 Mcells/s the HBM's bound on the cell
    with open(os.path.join(ROOT, "benchmark", "harness", "peaks.json")) as f:
        peak = json.load(f)["TPU v5 lite"]["hbm_bytes_per_s"]
    assert abs(8 * 512**3 / (bytes_mhd_step.step_bytes(c) / peak) / 1e6 - 22_750) < 1


def test_the_mhd512_metrics_are_declared_for_the_cell_alone():
    import importlib

    from benchmark.harness.window import layer_metrics_for

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        declared = {m["name"]: m for m in json.load(f)["per_layer"]}
    mine = {m["name"]: m for m in layer_metrics_for(CELL, {"mcells_per_s_chip", "setup_s"})}
    assert set(MHD512) <= set(mine) and list(declared)[-len(MHD512):] == MHD512  # appended, one block
    for name in MHD512:
        assert declared[name]["workloads"] == [CELL] and declared[name]["moves"] == "mcells_per_s_chip"
        assert mine[name]["cells"] == [CELL] and set(declared[name]) == {
            "name", "unit", "better", "source", "layer", "moves", "workloads"}
        for key in ("unit", "better", "source", "layer"):
            assert declared[name][key] == mine[name][key], (name, key)
        assert hasattr(importlib.import_module("benchmark.reducers." + mine[name]["reducer"]), "reduce")
        for other in ("astaroth-mhd-256.bulk", "lbm-d3q19-512.bulk", "elastic-so8-600.bulk"):
            assert name not in {m["name"] for m in layer_metrics_for(other, {"mcells_per_s_chip", "setup_s"})}
    # ... the 256 cells' named metrics stay theirs, and every accepted metric with no
    # list of cells is reported here too
    assert not {n for n in mine if n.endswith((".mhd", ".mhdx4", ".lbm512", ".plane", ".staged", ".wired"))}
    assert not {"mhd_pass_hbm_pct", "mhd_pass_flops_pct"} & set(mine)
    assert {"pallas_pct", "glue_pct", "dispatch_ms_p90", "device_idle_pct.bulk"} <= set(mine)


def _args_of(name):
    with open(os.path.join(ROOT, "benchmark", "layer_metrics", name + ".json")) as f:
        return json.load(f)["args"]


def test_the_mhd512_shares_read_the_plane_pass_by_name():
    """On the fixture timeline: the accepted cell's readers (no new reducer), the
    two roofline shares against THIS configuration's per-call counts, and nothing
    on a program that names nothing (the parent's line leaves the metric out)."""
    from benchmark import selftest_timeline as st
    from benchmark.reducers import named_roofline_flops, named_roofline_hbm, named_share

    for mine, theirs in zip(MHD512[:6], ("plane_pass_pct.mhd", "exchange_dev_pct.mhd", "step_glue_pct.mhd",
                                         "kernel_named_pct.mhd", "enqueue_ms_p90.mhd", "compiles_in_window.mhd")):
        # (a 1.3 s dispatch: the 4 s traced stretch holds four or five spans, not the default's ten)
        extra = {"min_samples": 3} if mine.startswith("enqueue_ms_p90") else {}
        assert _args_of(mine) == {**_args_of(theirs), **extra}
    hbm, flops = _args_of("mhd_pass_hbm_pct.mhd512"), _args_of("mhd_pass_flops_pct.mhd512")
    assert hbm == {"kernel": "^stream_plane_pass$", "bytes_fn": "benchmark.harness.bytes_mhd_step:pass_bytes"}
    assert flops == {"kernel": "^stream_plane_pass$", "peak": "bf16_flops_per_s",
                     "flops_fn": "benchmark.harness.flops_mhd_step:pass_flops"}
    c = _config()
    # two calls in 400 ns of the fixture: peaks under which the shares read 25 and 50 %
    ctx = {"timeline": st.fixture(), "table": None, "config": c,
           "peaks": {"bf16_flops_per_s": 2 * flops_mhd_step.pass_flops(c) / 400e-9 * 4,
                     "hbm_bytes_per_s": 2 * bytes_mhd_step.pass_bytes(c) / 400e-9 * 2}}
    here = {"kernel": "^stream_wavefront_pass$"}  # (the kernel the fixture holds)
    assert abs(named_roofline_flops.reduce(ctx, **{**flops, **here}) - 25.0) < 1e-9
    assert abs(named_roofline_hbm.reduce(ctx, **{**hbm, **here}) - 50.0) < 1e-9
    assert named_roofline_flops.reduce(ctx, **flops) is None  # no plane pass in the fixture
    parent = {**ctx, "timeline": st.fixture("parent")}
    assert named_roofline_hbm.reduce(parent, **{**hbm, **here}) is None
    for name in MHD512[:4] + MHD512[8:]:
        assert named_share.reduce(parent, **_args_of(name)) is None


def test_the_pass_shares_read_the_pass_scopes():
    """``pass_pct.mhd512.<i>`` on a hand-made timeline of two stages of two
    renaming passes: the ops under ``pass.<i>`` of EVERY stage -- the kernel and a
    copy the compiler adds to feed it -- over busy time; a pass the program does
    not run reads 0; an exchange's op is in no pass."""
    from benchmark.harness import timeline
    from benchmark.reducers import named_share

    def call(k, at):
        return [f"%stream_plane_pass.{k} = f32[8,8,128]{{2,1,0}} custom-call(f32[8,8,128]{{2,1,0}} %x), "
                'custom_call_target="tpu_custom_call"', at, 100]

    def hlo(stage, i):
        return {"opcode": "custom-call", "operands": [], "op_name":
                f"jit(step)/shard_map/while/body/step.stage.{stage}/pass.{i}/step.pass/stream_plane_pass/pallas_call"}

    raw = {
        "ops": {"/device:TPU:0": [
            call(1, 0), call(2, 100), ["%blend_planes.9 = f32[8,8,128]{2,1,0} custom-call(f32[8,8,128]{2,1,0} %x), "
                                       'custom_call_target="tpu_custom_call"', 200, 50],
            call(3, 250), call(4, 350), ["%copy.5 = f32[8,8,128]{2,1,0} copy(f32[8,8,128]{2,1,0} %x)", 450, 50],
        ]},
        "modules": {"/device:TPU:0": [["jit_step(7)", 0, 500]]},
        "hlo": {"jit_step(7)": {
            "stream_plane_pass.1": hlo(0, 0), "stream_plane_pass.2": hlo(0, 1),
            "stream_plane_pass.3": hlo(1, 0), "stream_plane_pass.4": {**hlo(1, 1), "operands": ["copy.5"]},
            "copy.5": {"opcode": "copy", "op_name": "", "operands": []},
            "blend_planes.9": {"opcode": "custom-call", "operands": [], "op_name":
                               "jit(step)/shard_map/while/body/step.stage.1/exchange.x/exchange.x.wrap/blend_planes/pallas_call"},
        }},
        "host": [],
    }
    ctx = {"timeline": timeline.build(raw, workload=CELL), "table": None, "peaks": None}
    assert abs(named_share.reduce(ctx, **_args_of("pass_pct.mhd512.0")) - 100 * 200 / 500) < 1e-9
    assert abs(named_share.reduce(ctx, **_args_of("pass_pct.mhd512.1")) - 100 * 250 / 500) < 1e-9  # + its copy
    assert named_share.reduce(ctx, **_args_of("pass_pct.mhd512.2")) == 0.0
    assert abs(named_share.reduce(ctx, **_args_of("exchange_dev_pct.mhd512")) - 100 * 50 / 500) < 1e-9
    assert abs(named_share.reduce(ctx, **_args_of("plane_pass_pct.mhd512")) - 100 * 400 / 500) < 1e-9
