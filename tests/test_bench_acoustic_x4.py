"""The benchmark's cell ``acoustic-so8-1200x4.bulk`` on the CPU: its rehearsal
through ``harness/window.py`` on four devices (the control flow of a chip
run, interpreted, 16^3 a chip = 32 x 32 x 16 on mesh [2,2,1]) -- sound as it
stands, not correct with bf16 storage, and NOT CORRECT WITH THE STEP'S
EXCHANGE PATCHED OUT: the x and y seams run through the middle of the wave
packet, so this is the first wave cell whose ``correct`` can see a halo that
was never filled (the one-chip cells' only seam lies in the zero frame:
ROADMAP M9) -- and the configuration's numbers against the ISSUE's."""

import json
import os
import sys
import time
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import bytes_plane, reference_acoustic as wave  # noqa: E402

CELL = "acoustic-so8-1200x4.bulk"


def _config(name="acoustic-so8-1200x4"):
    with open(os.path.join(ROOT, "benchmark", "configs", name + ".json")) as f:
        return json.load(f)


def _rehearse(capsys, patch=None, **flags):
    from benchmark.harness import window

    opts = types.SimpleNamespace(
        workload=CELL, seed=2**31 + 37, seconds=0.2, trace=0, lower_precision=False,
        describe_trace=False, also_verify=[], rehearse=16, dispatch_size=6)
    vars(opts).update(flags)
    capsys.readouterr()
    assert window.run(opts, time.perf_counter(), patch=patch) == 0
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines() if x.startswith("{")]
    checks = {x["name"]: x for x in lines if x.get("bench") == "check"}
    plan = next(x for x in lines if x.get("bench") == "plan")
    return lines[-1], checks, plan


def test_rehearsal_is_sound(capsys):
    line, checks, plan = _rehearse(capsys)
    assert line["rehearsal"]["checks_ok"] is True and line["failed"] == 0, (line, checks)
    assert line["correct"] is False and line["metrics"] == {}  # a rehearsal is never a result
    assert line["device"]["count"] == 4
    ran = plan["ran"]
    assert (ran["mesh"], ran["route"], ran["depth"], ran["descents"]) == ([2, 2, 1], "plane", 1, 0), ran
    # the program's own word for what crossed a wire: four radius-4 faces of u's raw block
    # and, behind each y face, the corner relay of the joint x-y sweep (both x halos' four rows)
    assert (ran["wired"], ran["wire_bytes"]) == ("xy", (2 * 2 * 4 * 24 * 24 + 2 * 8 * 4 * 24) * 4), ran
    assert checks["max_abs_err"]["value"] <= 1e-6  # far inside the cell's limit on the CPU
    assert checks["frame_nonzero_cells"]["value"] == 0 and checks["window_state_bad_cells"]["value"] == 0


@pytest.mark.parametrize("seed", [137, 2**31 + 138])
def test_control_comes_out_not_correct(capsys, seed):
    """bf16 storage, the program's own lower-precision axis: every check but
    ``max_abs_err`` holds, and that one fails by orders of magnitude."""
    line, checks, plan = _rehearse(capsys, lower_precision=True, seed=seed)
    assert plan["ran"]["storage"] == "bf16"
    assert line["rehearsal"]["checks_ok"] is False
    assert [n for n, c in checks.items() if not c["ok"]] == ["max_abs_err"]
    assert checks["max_abs_err"]["value"] > 20 * checks["max_abs_err"]["limit"]


def test_a_step_that_exchanges_nothing_comes_out_not_correct(capsys, monkeypatch):
    """The cell's step rebuilt with its exchange patched out (the halo of
    ``u`` keeps what the fill and the last pass left there): the step still
    runs, the state stays finite, the frame stays zero -- and ``max_abs_err``
    alone says not correct, because the seams lie inside the wave."""
    from stencil_tpu.ops import exchange

    def no_exchange(cell):
        monkeypatch.setattr(exchange, "halo_exchange_multi", lambda blocks, *a, **kw: list(blocks))
        cell.sim.rebuild_after_reshard()

    line, checks, plan = _rehearse(capsys, patch=no_exchange)
    assert plan["ran"]["route"] == "plane" and line["failed"] == 0
    assert line["rehearsal"]["checks_ok"] is False
    assert [n for n, c in checks.items() if not c["ok"]] == ["max_abs_err"]
    assert checks["max_abs_err"]["value"] > 100 * checks["max_abs_err"]["limit"]


def test_configuration_states_the_issues_sizes():
    c, one = _config(), _config("acoustic-so8-600")
    assert c["global_extent"] == [1112 + 2 * 40 + 2 * 4] * 2 + [512 + 2 * 40 + 2 * 4] == [1200, 1200, 600]
    assert (c["chips"], c["mesh"], c["extent_per_chip"]) == (4, [2, 2, 1], [600, 600, 600])
    assert c["extent_per_chip"] == one["extent_per_chip"]  # the two cells' ratio is weak-scaling efficiency
    assert c["dispatch"] == one["dispatch"] == {"bulk": 8} and c["dispatch"]["bulk"] % 2 == 0
    for key in ("radius", "space_order", "nbl", "quantities", "fields", "dtype", "itemsize", "setup",
                "model", "limits"):
        assert c[key] == one[key], key
    assert c["guarantees"].startswith(one["guarantees"])
    assert set(one["assumed"]) < set(c["assumed"]) and {"physical extent", "topology"} <= set(c["assumed"])
    assert c["reduced"] == [] and c["pass"] == {"reads": 4, "writes": 1}
    assert c["exchange"] == {"quantities": 1, "wired_axes": "xy"}
    assert c["expect"] == {"mesh": [2, 2, 1], "route": "plane", "depth": 1, "storage": "native",
                           "wired": "xy", "wrapped": "z"}
    s = wave.setup_from(c, c["global_extent"])
    assert s.physical == (1112, 1112, 512) and s.nbl == 40
    # five arrays a call, and what a chip receives over ICI a step
    assert bytes_plane.plane_pass_bytes(c) == 5 * 608**3 * 4
    assert c["exchange"]["quantities"] * len(c["exchange"]["wired_axes"]) * 2 * 4 * 608 * 608 * 4 == 23_658_496
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("acoustic-so8-1200x4", "bulk", 4)
    entry = next(k for k in bench["configs"] if k["name"] == "acoustic-so8-1200x4")
    assert entry["source"] == c["source"] and entry["reduced"] == []
    assert sum(w["chips"] == 4 for w in bench["workloads"]) * 2 <= len(bench["workloads"])
    assert CELL in next(m for m in bench["end_to_end"] if m["name"] == "mcells_per_s_chip")["workloads"]


def test_the_cell_reports_the_acoustic_shares_and_the_wired_six():
    """``run.py`` finds a cell's per-layer metrics by pattern: the five
    ``acoustic-*`` files and the six ``.wired`` ones apply, and
    ``BENCHMARK.json`` lists the cell under each of them."""
    from benchmark.harness import window

    got = {m["name"] for m in window.layer_metrics_for(CELL, {"mcells_per_s_chip", "setup_s"})}
    named = {"plane_pass_pct", "plane_pass_hbm_pct", "exchange_dev_pct.plane", "step_glue_pct.plane",
             "kernel_named_pct.plane", "collective_pct.wired", "exchange_x_pct.wired",
             "exchange_y_pct.wired", "slab_ops_pct.wired", "enqueue_ms_p90.wired",
             "compiles_in_window.wired"}
    assert named <= got
    assert not {n for n in got if n.endswith((".staged", ".ragged", ".exchange", ".bulk"))
                and not n.startswith("device_idle_pct")}
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        listed = {m["name"]: m.get("workloads") for m in json.load(f)["per_layer"]}
    for name in named:
        assert CELL in listed[name], name
    # the one-chip cell takes none of the six
    one = {m["name"] for m in window.layer_metrics_for("acoustic-so8-600.bulk", {"mcells_per_s_chip", "setup_s"})}
    assert not {n for n in one if n.endswith(".wired")}
