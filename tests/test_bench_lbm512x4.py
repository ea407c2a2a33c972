"""The benchmark's cell ``lbm-d3q19-512x4.bulk`` on the CPU (ISSUE 53): its
rehearsal through ``harness/window.py`` on four of the host's devices (8^3 a
chip = 16 x 16 x 8 on mesh [2,2,1]) -- sound; the bf16-storage control, a
frozen dispatch and a step whose exchange is switched off each not correct --, a
program whose planner refuses the box ending the run cleanly, the benchmark's
reference (every chip its own block, in pieces with margins) against the
whole-array one for every piece start on both cut axes, the piece-wise readers
of the program's raw shards, the configuration's numbers against the ISSUE's
and the one-chip file's, and the eleven ``.lbm512x4`` per-layer metrics."""

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

from benchmark.harness import bytes_lbm, reference as bref, reference_lbm as lbm  # noqa: E402
from benchmark.harness import reference_lbm_x4 as x4  # noqa: E402

CELL, TWIN = "lbm-d3q19-512x4.bulk", "lbm-d3q19-512.bulk"
N, DISPATCH = 8, 2  # rehearsal extent a chip, steps per dispatch
LBM512X4 = ["stencil_kernel_pct.lbm512x4", "kernel_named_pct.lbm512x4", "lbm_pass_hbm_pct.lbm512x4",
            "exchange_dev_pct.lbm512x4", "exchange_x_pct.lbm512x4", "exchange_y_pct.lbm512x4",
            "collective_pct.lbm512x4", "slab_ops_pct.lbm512x4", "step_glue_pct.lbm512x4",
            "enqueue_ms_p90.lbm512x4", "compiles_in_window.lbm512x4"]
WIRES = ["wire_inflight_pct.bulk4", "wire_hidden_pct.bulk4", "wire_ici_pct.bulk4",
         "wire_counted_pct.bulk4", "wire_named_pct.bulk4"]


def _config(name="lbm-d3q19-512x4"):
    with open(os.path.join(ROOT, "benchmark", "configs", name + ".json")) as f:
        return json.load(f)


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


_BUILT = []  # the sound rehearsal's cell: its compiled objects serve the broken ones


def _rehearse(patch=None, **flags):
    """One rehearsal in process: (last line, checks by name, plan line)."""
    from benchmark.harness import window

    opts = types.SimpleNamespace(
        workload=CELL, seed=2**31 + 53, seconds=0.2, trace=0, lower_precision=False,
        describe_trace=False, also_verify=[], rehearse=N, dispatch_size=DISPATCH)
    vars(opts).update(flags)
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = window.run(opts, time.perf_counter(), patch=patch)
    assert rc == 0
    lines = [json.loads(x) for x in out.getvalue().splitlines() if x.startswith("{")]
    checks = {x["name"]: x for x in lines if x.get("bench") == "check"}
    return lines[-1], checks, next(x for x in lines if x.get("bench") == "plan")


def _cell():
    if not _BUILT:
        _rehearse(patch=_BUILT.append)
    return _BUILT[0]


def _verdict(cell, seed):
    """``max_abs_err`` of one dispatch from ``seed`` through the cell's compiled objects."""
    checks = {c["name"]: c for c in cell.verify(bref.seed_words(seed), 1, DISPATCH)}
    assert checks["uncompared_cells"]["value"] == 0
    return checks["max_abs_err"]


def test_the_rehearsed_cell_comes_out_sound():
    line, checks, plan = _rehearse(patch=_BUILT.append)
    assert line["rehearsal"]["checks_ok"] is True and line["failed"] == 0, (line, checks)
    assert line["correct"] is False and line["metrics"] == {}  # a rehearsal is never a result
    assert line["device"]["count"] == 4
    ran = plan["ran"]
    assert (ran["mesh"], ran["route"], ran["storage"], ran["descents"]) == ([2, 2, 1], "plane", "native", 0)
    assert (ran["quantities"], ran["diagonal"], ran["aliased"], ran["exchanged"]) == (19, 12, 19, 18)
    assert (ran["read_sides"], ran["exchanged_sides"], ran["wrapped"]) == (30, 108, "")
    # the program's own word for what crossed a wire: eighteen populations' x and
    # y faces of the raw 10^3 block, and behind each y face the corner relay
    assert (ran["wired"], ran["wire_bytes"]) == ("xy", (4 * 18 * 10 * 10 + 2 * 18 * 2 * 10) * 4), ran
    assert {"plane_window", "tile_rows", "y_tiles"} <= set(ran)  # the plan line says them
    assert plan["planned"] == _config()["expect"]
    assert checks["max_abs_err"]["value"] <= 1e-6 and checks["window_state_bad_cells"]["value"] == 0
    assert checks["mass_drift"]["value"] < checks["mass_drift"]["limit"]
    assert checks["uncompared_cells"]["value"] == checks["unseen_cells"]["value"] == 0
    assert {"mcells_per_s_chip", "setup_s"} <= set(line["rehearsal"]["would_report"])


def test_a_frozen_dispatch_is_not_correct(monkeypatch):
    cell = _cell()
    monkeypatch.setattr(cell, "dispatch", lambda n: None)  # the step returns its state unchanged
    frozen = _verdict(cell, 11)
    assert not frozen["ok"] and frozen["value"] > 20 * frozen["limit"], frozen


def test_a_step_whose_exchange_is_switched_off_is_not_correct(monkeypatch):
    """The cell's step rebuilt with its exchange patched out: every halo keeps
    what the fill put there -- the seeded state's own periodic image, right for
    the first step and one step stale for the second -- the state stays finite
    and inside its guardband, and ``max_abs_err`` says not correct: the seams
    and the four x-y shard edges lie in a box that is nowhere zero."""
    from stencil_tpu.ops import exchange

    cell = _cell()
    monkeypatch.setattr(exchange, "halo_exchange_multi", lambda blocks, *a, **kw: list(blocks))
    cell.sim.rebuild_after_reshard()
    try:
        unfilled = _verdict(cell, 12)
    finally:
        monkeypatch.undo()
        cell.sim.rebuild_after_reshard()
    assert not unfilled["ok"] and unfilled["value"] > 20 * unfilled["limit"], unfilled


def test_the_bf16_control_is_not_correct():
    import jax

    from benchmark.factories import lbm_x4

    config = dict(_config(), extent_per_chip=[N] * 3, global_extent=[2 * N, 2 * N, N])
    cell = lbm_x4.build(config, jax.devices()[:4], True, lower_precision=True)
    assert cell.plan()["storage"] == "bf16"
    control = _verdict(cell, 2**31 + 153)
    assert not control["ok"] and control["value"] > 20 * control["limit"], control


def test_a_program_whose_planner_refuses_the_box_ends_the_run_cleanly(monkeypatch):
    """The parent of the PR that added the cell raises ``FitsNoPass`` (a
    ``ValueError``) at plan time: the factory asks a model that allocates nothing
    and exits with the planner's own words, before 13 GB a chip are allocated."""
    import jax

    from benchmark.factories import lbm_x4
    from stencil_tpu.domain import DistributedDomain
    from stencil_tpu.models.lbm import LatticeBoltzmann

    def refuse(self):
        raise ValueError("the plane pass that writes ('f0',) ... it fits no pass")

    allocated = []
    real = DistributedDomain.realize
    monkeypatch.setattr(LatticeBoltzmann, "_build_step", refuse)
    monkeypatch.setattr(DistributedDomain, "realize",
                        lambda self, allocate=True: (allocated.append(allocate), real(self, allocate))[1])
    config = dict(_config(), extent_per_chip=[N] * 3, global_extent=[2 * N, 2 * N, N])
    with pytest.raises(SystemExit, match=(
            r"planner refuses the box \(16, 16, 8\) on mesh \[2, 2, 1\].*it fits no pass")):
        lbm_x4.build(config, jax.devices()[:4], True)
    assert allocated == [False]
    with pytest.raises(SystemExit, match=r"cuts \(16, 16, 8\) over 1 device\(s\) as \[1, 1, 1\]"):
        lbm_x4.build(config, jax.devices()[:1], True)


# --- the pieces and the piece-wise readers ------------------------------------------------

#: one small box for both tests: 16 x 12 x 8 on mesh [2,2,1], blocks of 8 x 6 x 8
_SMALL = (lbm.setup_from(_config(), [16, 12, 8]), (2, 2, 1),
          np.asarray([5, 1, 5, 1], dtype=np.uint32), 3, 4)


def test_the_pieces_are_the_whole_array_reference_for_every_piece_start():
    """``reference_piece`` against the same cells of ``reference_lbm.reference``
    on whole arrays: every x start of the box and both y blocks -- the pieces
    whose margins wrap around the box's ends included; written apart
    (coordinates modulo the box, margins eaten a cell a step, where the other
    rolls whole arrays), the same numbers."""
    s, mesh_shape, words, steps, width = _SMALL
    whole = [np.asarray(a) for a in lbm.reference(s, steps, None, words)]
    c = (np.arange(16)[:, None, None], np.arange(12)[None, :, None], np.arange(8)[None, None, :])
    seeded = [np.broadcast_to(np.asarray(lbm.seeded_fields(s)[q](*c, words)), s.shape) for q in lbm.NAMES]
    assert max(np.abs(a - b).max() for a, b in zip(whole, seeded)) > 1e-4  # it has moved
    for x0 in range(16 - width + 1):
        for y0 in (0, 6):
            got = x4.reference_piece(s, steps, mesh_shape, (x0, y0, 0), width, words)
            assert len(got) == lbm.Q and got[0].shape == (width, 6, 8)
            for q, g, w in zip(lbm.NAMES, got, whole):
                np.testing.assert_allclose(
                    np.asarray(g), w[x0 : x0 + width, y0 : y0 + 6], rtol=0, atol=2e-7,
                    err_msg=f"{q}@{x0},{y0}")
    assert [x4.piece_width(512, 16), x4.piece_width(8, 16), x4.piece_width(12, 5)] == [16, 8, 4]
    # an axis the mesh leaves whole is rolled whole; x always carries a margin
    # unless the piece IS the whole uncut axis
    assert x4._margins(s, (2, 2, 1), 3, 4) == (3, 3, 0) and x4._margins(s, (1, 2, 1), 3, 16) == (0, 3, 0)


def test_the_readers_see_every_cell_of_every_chips_block_once():
    """``piece_errors`` and ``state_counts`` on shell-carrying arrays cut over a
    [2,2,1] mesh of the host's devices: every chip reads ITS shard's interior
    (the shell, NaN here, is never read), every cell of every population once, a
    spoiled cell found on whichever chip it sits."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    s, mesh_shape, words, steps, width = _SMALL
    mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(mesh_shape), ("x", "y", "z"))
    state = [np.asarray(a) for a in lbm.reference(s, steps, None, words)]
    lo, block = 1, (8, 6, 8)

    def raw_global(a):  # every block with a NaN shell around it, side by side
        out = np.full([m * (b + 2 * lo) for m, b in zip(mesh_shape, block)], np.nan, np.float32)
        for i in range(2):
            for j in range(2):
                out[i * 10 + lo : i * 10 + lo + 8, j * 8 + lo : j * 8 + lo + 6, lo : lo + 8] = (
                    a[i * 8 : i * 8 + 8, j * 6 : j * 6 + 6])
        return out

    sharding = NamedSharding(mesh, P("x", "y", "z"))
    raws = [jax.device_put(raw_global(a), sharding) for a in state]
    worst, seen = x4.piece_errors(s, steps, mesh, words, raws, lo, width)
    assert worst < 2e-7 and seen == lbm.Q * 16 * 12 * 8
    bad, mass, cells = x4.state_counts(s, mesh, raws, lo, width)
    whole_bad, whole_mass = lbm.state_counts(s, state)
    assert (bad, cells) == (whole_bad, 16 * 12 * 8) == (0, 1536) and abs(mass - whole_mass) < 1e-9 * whole_mass
    spoiled = [raw_global(a) for a in state]
    spoiled[3][10 + lo + 7, 8 + lo + 5, lo + 3] = np.nan  # the last cell of chip (1, 1)'s block
    spoiled[5][lo, 8 + lo, lo] += 0.5  # the first cell of chip (0, 1)'s
    spoiled = [jax.device_put(a, sharding) for a in spoiled]
    assert x4.state_counts(s, mesh, spoiled, lo, width)[0] == 2
    assert x4.piece_errors(s, steps, mesh, words, spoiled, lo, width)[0] == float("inf")
    only = list(raws)
    only[5] = spoiled[5]
    assert abs(x4.piece_errors(s, steps, mesh, words, only, lo, width)[0] - 0.5) < 1e-6


# --- the configuration ----------------------------------------------------------------------


def test_configuration_states_the_issues_sizes():
    c, one = _config(), _config("lbm-d3q19-512")
    assert (c["chips"], c["mesh"], c["extent_per_chip"], c["global_extent"]) == (
        4, [2, 2, 1], [512, 512, 512], [1024, 1024, 512])
    assert c["extent_per_chip"] == one["extent_per_chip"]  # the two cells' ratio is weak-scaling efficiency
    # the deployment's shapes are the one-chip box's, letter for letter: only the scale differs
    for key in ("setup", "pass", "fields", "quantities", "dtype", "itemsize", "radius", "model"):
        assert c[key] == one[key], key
    assert c["reduced"] == [] and c["factory"] == "benchmark.factories.lbm_x4:build"
    assert set(one) - set(c) == set() and set(c) == set(one)
    assert set(one["assumed"]) - {"reference.slab_planes"} <= set(c["assumed"])
    assert {"multi-GPU line", "lbm_N", "transfers", "dispatch.bulk", "reference.piece_planes"} <= set(c["assumed"])
    assert "as remembered" in c["assumed"]["multi-GPU line"] and "// 4 GPUs" in c["assumed"]["multi-GPU line"]
    # ``expect`` pins what the DEPLOYMENT fixes and nothing the planner answers
    assert c["expect"] == {"storage": "native", "quantities": 19, "diagonal": 12, "aliased": 19,
                           "mesh": [2, 2, 1], "wired": "xy"}
    assert c["dispatch"]["bulk"] % 2 == 0 and c["reference"] == {"piece_planes": 16}
    assert set(c["limits"]) == {"max_abs_err", "mass_drift"} and "TBD" not in json.dumps(c)
    assert "shard edges" in c["guarantees"] and "GLOBAL" in c["guarantees"]
    s, s1 = lbm.setup_from(c, c["global_extent"]), lbm.setup_from(one, one["global_extent"])
    assert (s.omega, s.u0, s.modes, s.rho_band) == (s1.omega, s1.u0, s1.modes, s1.rho_band)
    # one slot of nineteen raw blocks a chip as the domain stores them: 13.0 GB of 16.9
    assert 19 * 514 * 520 * 640 * 4 == 13_000_499_200 and "13,0" in c["resident_bytes_per_chip"]
    assert bytes_lbm.pass_bytes(c) == bytes_lbm.pass_bytes(one) == 20_401_094_656  # a call and CHIP
    bench = _bench()
    # the chip-share cap is judged on the benchmark a PR leaves: 6 of 13, the cap
    # (6 of 13 when this cell came: the cap; 6 of 14 since PR 57's one-chip cell stands behind it)
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 6 <= len(bench["workloads"]) // 2 == 7
    assert bench["end_to_end"][0]["name"] == "mcells_per_s_chip" and bench["end_to_end"][0]["workloads"][10] == CELL
    entry = next(x for x in bench["configs"] if x["name"] == "lbm-d3q19-512x4")
    assert entry["source"] == c["source"] and len(entry["source"]) == 199 and entry["reduced"] == []
    assert "// 4 GPUs" in entry["source"] and "2112.08926" in entry["source"]
    assert entry["file"] == "benchmark/configs/lbm-d3q19-512x4.json"
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("lbm-d3q19-512x4", "bulk", 4)
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    assert f"{c['dispatch']['bulk']}-step" in cell["why"]
    assert bench["workloads"][12] is cell and bench["configs"][12] is entry  # appended (PR 57's behind it since)


def test_the_eleven_metrics_are_declared_for_the_cell_alone():
    import importlib

    from benchmark.harness.window import layer_metrics_for

    bench = _bench()
    declared = {m["name"]: m for m in bench["per_layer"]}
    reported = {"mcells_per_s_chip", "setup_s"}
    mine = {m["name"]: m for m in layer_metrics_for(CELL, reported)}
    assert set(LBM512X4) <= set(mine) and list(declared)[-23:-12] == LBM512X4  # (PR 57's twelve behind them)
    for name in LBM512X4:
        assert declared[name]["workloads"] == [CELL] and declared[name]["moves"] == "mcells_per_s_chip"
        assert mine[name]["cells"] == [CELL] and set(declared[name]) == {
            "name", "unit", "better", "source", "layer", "moves", "workloads"}
        for key in ("unit", "better", "source", "layer"):
            assert declared[name][key] == mine[name][key], (name, key)
        assert hasattr(importlib.import_module("benchmark.reducers." + mine[name]["reducer"]), "reduce")
        for other in (TWIN, "astaroth-mhd-256x4.bulk", "acoustic-so8-1200x4.bulk"):
            assert name not in {m["name"] for m in layer_metrics_for(other, reported)}
    # every reader is one an accepted cell already has, argument for argument
    def args_of(name):
        with open(os.path.join(ROOT, "benchmark", "layer_metrics", name + ".json")) as f:
            return json.load(f)["args"]

    for name in LBM512X4:
        stem = name[: -len(".lbm512x4")]
        theirs = stem + (".mhdx4" if stem.startswith(("exchange_", "collective", "slab_ops")) else ".lbm512")
        assert args_of(name) == args_of(theirs), name
    # the five wire shares of the four-chip bulk cells hold the cell by their
    # files' pattern, and BENCHMARK.json's lists say so; no other named family does
    for name in WIRES:
        assert name in mine and declared[name]["workloads"][-1] == CELL
    assert not {n for n in mine if n.endswith((".lbm", ".lbm512", ".mhdx4", ".plane", ".staged", ".exch4"))}
    assert {"pallas_pct", "glue_pct", "dispatch_ms_p90", "device_idle_pct.bulk"} <= set(mine)
