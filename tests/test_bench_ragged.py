"""The benchmark's cell ``weak-r3-750x4.exchange-only`` on the CPU: its
rehearsal through ``harness/window.py`` on four devices (the control flow of
a chip run, 16^3 per chip: 33 x 33 x 16 over mesh [2,2,1], so x and y are
uneven as on the chip), the bf16-storage control and a dispatch that
exchanges nothing each coming out not correct, every new ``layer_metrics``
file on the fixture timeline, ``bytes_blend`` against hand arithmetic, and
the configuration's numbers against the ISSUE's."""

import glob
import importlib
import json
import os
import sys
import time
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import bytes as work  # noqa: E402
from benchmark.harness import bytes_blend, reference_ragged as ragged  # noqa: E402

CELL = "weak-r3-750x4.exchange-only"
N = 16  # rehearsal extent per chip
IMPLIED = 4 * (33 + 12) * (33 + 12) * (16 + 6)  # fields x prod(size + mesh x 2r)


def _config():
    with open(os.path.join(ROOT, "benchmark", "configs", "weak-r3-750x4.json")) as f:
        return json.load(f)


def _rehearse(capsys, patch=None, **flags):
    from benchmark.harness import window

    opts = types.SimpleNamespace(
        workload=CELL, seed=2**31 + 31, seconds=0.2, trace=0, lower_precision=False,
        describe_trace=False, also_verify=[], rehearse=N, dispatch_size=3)
    vars(opts).update(flags)
    capsys.readouterr()
    assert window.run(opts, time.perf_counter(), patch=patch) == 0
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines() if x.startswith("{")]
    checks = {x["name"]: x for x in lines if x.get("bench") == "check"}
    plan = next(x for x in lines if x.get("bench") == "plan")
    return lines[-1], checks, plan


def test_rehearsal_is_uneven_and_sound(capsys):
    line, checks, plan = _rehearse(capsys)
    assert line["rehearsal"]["checks_ok"] is True and line["failed"] == 0, (line, checks)
    assert line["correct"] is False and line["metrics"] == {}  # a rehearsal is never a result
    assert plan["ran"]["route"] == "direct" and plan["ran"]["descents"] == 0
    assert plan["ran"]["valid_last"] == [N, N, None]  # two uneven axes, as on the chip
    assert checks["valid_last"]["ok"] and checks["valid_last"]["limit"] == [N, N, None]
    for state in ("window_state", "refilled"):
        assert checks[f"{state}_mismatches"]["value"] == 0
        assert checks[f"{state}_checked_cells"]["value"] == IMPLIED == checks[f"{state}_checked_cells"]["limit"]


@pytest.mark.parametrize("seed", [101, 2**31 + 102])
def test_control_comes_out_not_correct(capsys, seed):
    """bf16 storage, the program's own lower-precision axis: the geometry
    checks hold, and about every cell differs (20-bit integers do not
    survive bf16)."""
    line, checks, plan = _rehearse(capsys, lower_precision=True, seed=seed)
    assert plan["ran"]["storage"] == "bf16"
    assert line["rehearsal"]["checks_ok"] is False
    assert sorted(n for n, c in checks.items() if not c["ok"]) == ["refilled_mismatches", "window_state_mismatches"]
    assert checks["refilled_mismatches"]["value"] > 0.9 * IMPLIED
    assert checks["refilled_checked_cells"]["value"] == IMPLIED


def test_a_dispatch_that_exchanges_nothing_comes_out_not_correct(capsys):
    def freeze(cell):
        cell.dispatch = lambda n: None

    line, checks, _ = _rehearse(capsys, patch=freeze)
    assert line["rehearsal"]["checks_ok"] is False
    # the shells were never filled: every owned halo cell of every quantity differs
    shell = IMPLIED - 4 * 33 * 33 * 16
    assert checks["refilled_mismatches"]["value"] == checks["window_state_mismatches"]["value"] == shell


def test_a_mask_that_hides_cells_fails_the_count(capsys, monkeypatch):
    """``checked_cells`` is held to the configuration's arithmetic: a
    comparison that took the interiors alone for owned would count fewer."""
    real = ragged.owned_mismatches
    monkeypatch.setattr(ragged, "owned_mismatches", lambda *a: (real(*a)[0], real(*a)[1] - 1))
    line, checks, _ = _rehearse(capsys)
    assert line["rehearsal"]["checks_ok"] is False and not checks["refilled_checked_cells"]["ok"]


def test_configuration_states_the_sources_shapes():
    from benchmark.factories.exchange_ragged import ragged_extent
    from stencil_tpu.models.jacobi import weak_scaled_size

    c = _config()
    assert weak_scaled_size(c["model"]["base"], c["chips"]) == 1191  # weak.cu:63-65 on four devices
    assert c["global_extent"] == [1191] * 3 == ragged_extent(c["extent_per_chip"], c["mesh"])
    assert (c["radius"], c["fields"], c["dtype"], c["mesh"], c["reduced"]) == (3, 4, "float32", [2, 2, 1], [])
    assert c["extent_per_chip"] == [595, 595, 1191] == list(ragged.valid_last(c["global_extent"], c["mesh"]))
    assert ragged.shard_width(c["global_extent"], c["mesh"]) == (596, 596, 1191)
    # the smallest shard's halo, never a padded cell: 171.4 MB an exchange
    assert work.halo_bytes_per_chip(c["extent_per_chip"], 3, 4, 4) == (601 * 601 * 1197 - 595 * 595 * 1191) * 16 == 171_421_152
    r = (3, 3, 3)
    assert 4 * ragged.owned_cells(c["global_extent"], c["mesh"], r, r) == 4 * 1203 * 1203 * 1197 == 6_929_236_692
    assert 8 * 602 * 602 * 1197 * 4 == 13_881_522_816  # the fields, 13.88 GB of cells a chip
    assert ragged.pad_cells((23, 23, 23), (2, 2, 1), r, r) * 4 == 8236  # the ISSUE's even-geometry count
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert sum(w["chips"] == 4 for w in bench["workloads"]) * 2 <= len(bench["workloads"])
    assert CELL in next(m for m in bench["end_to_end"] if m["name"] == "halo_gbps_chip")["workloads"]
    entry = next(x for x in bench["configs"] if x["name"] == "weak-r3-750x4")
    assert entry["source"] == c["source"] and len(entry["source"]) <= 200 and entry["reduced"] == c["reduced"]


def test_blend_bytes_come_from_the_configuration_alone():
    c = _config()
    assert bytes_blend.received_slab_bytes(c) == 3 * 602 * 1197 * 4  # x slab 3 x 602 x 1197 = y slab 602 x 3 x 1197
    assert bytes_blend.blend_dynamic_bytes(c) == 2 * 3 * 602 * 1197 * 4 == 17_294_256
    c.update(mesh=[2, 1, 1], global_extent=[15, 8, 120], radius=1)
    assert bytes_blend.blend_dynamic_bytes(c) == 2 * 1 * 10 * 122 * 4
    c.update(mesh=[2, 2, 1], global_extent=[15, 9, 120])  # x and y slabs of different sizes: refuse to guess
    with pytest.raises(AssertionError):
        bytes_blend.blend_dynamic_bytes(c)


def test_new_layer_metrics_read_the_fixture_timeline():
    """Each new ``layer_metrics`` file through its reducer on the recorded
    fixture (a ``blend_slab`` of 50 ns and a fusion and an inherited copy of
    20 + 30 ns under ``exchange.z`` on chip 0 of 380 busy ns; nothing named
    ``blend_slab_dynamic`` and nothing under ``exchange.x|y``), then the two
    selections pointed at what the fixture does hold."""
    from benchmark import selftest_timeline as st

    near = lambda a, b: abs(a - b) < 1e-9 * max(1.0, abs(b))  # noqa: E731
    ctx = {"timeline": st.fixture(), "table": None, "peaks": {"hbm_bytes_per_s": 1e12},
           "clocks": {}, "samples": {}, "config": _config()}
    want = {
        "exchange_z_pct.ragged": 100 * 140 / 380 / 2, "kernel_named_pct.ragged": (100 + 100 * 300 / 390) / 2,
        "enqueue_ms_p90.ragged": None, "compiles_in_window.ragged": 2,
        "blend_dynamic_pct": 0.0, "blend_dynamic_hbm_pct": None, "uneven_cut_pct": 0.0,
    }
    files = {}
    for path in glob.glob(os.path.join(ROOT, "benchmark", "layer_metrics", "*.json")):
        with open(path) as f:
            m = json.load(f)
        if m.get("cells") == ["weak-r3-750x4*"]:
            files[m["name"]] = m
    assert set(files) == set(want) | {"collective_pct.ragged", "idle_in_program_pct.ragged"}
    reduce = lambda m, **over: importlib.import_module(  # noqa: E731
        "benchmark.reducers." + m["reducer"]).reduce(ctx, **{**m.get("args", {}), **over})
    for name, value in want.items():
        got = reduce(files[name])
        assert (got is None and value is None) or near(got, value), (name, got)
    # the same files on a program that names nothing (the parent) and on no trace: nothing, and no raise
    for tl in (st.fixture("parent"), None):
        ctx["timeline"] = tl
        for name in ("blend_dynamic_pct", "blend_dynamic_hbm_pct", "uneven_cut_pct", "exchange_z_pct.ragged"):
            assert reduce(files[name]) is None
    ctx["timeline"] = st.fixture()
    assert near(reduce(files["blend_dynamic_pct"], kernel=["^blend_slab$"]), 100 * 50 / 380 / 2)
    assert near(reduce(files["uneven_cut_pct"], scope=[r"(^|/)exchange\.z(/|$)"]), 100 * 50 / 380 / 2)
    # one 50 ns call of 17,294,256 B at 1e12 B/s
    assert near(reduce(files["blend_dynamic_hbm_pct"], kernel="^blend_slab$"), 100 * (17_294_256 / 1e12) / 50e-9)
