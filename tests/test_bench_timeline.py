"""The benchmark's named-timeline readers (``benchmark/harness/timeline.py``
and the reducers over it), case by case from ``benchmark/selftest_timeline.py``
— plus what keeps the benchmark's copy of the program's registry, and the
new per-layer metrics' declarations, true."""

import importlib.util
import json
import os

import pytest

from stencil_tpu.telemetry import names as tm

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _selftest():
    spec = importlib.util.spec_from_file_location(
        "bench_selftest_timeline", os.path.join(ROOT, "benchmark", "selftest_timeline.py")
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.mark.parametrize("part", list("abcde"))
def test_selftest_timeline(part, capsys):
    getattr(_selftest(), "part_" + part)()
    assert f"{part} " in capsys.readouterr().out


def test_program_names_file_equals_the_registry():
    """``harness/timeline.py`` imports nothing of the program; its data file
    has to say what the registry says."""
    with open(os.path.join(ROOT, "benchmark", "harness", "program_names.json")) as f:
        names = json.load(f)
    assert set(names["kernels"]) == set(tm.ALL_KERNELS)
    assert set(names["sweep_scopes"]) == set(tm.EXCHANGE_AXIS_SPANS.values())
    host_spans = {
        tm.SPAN_STEP, tm.SPAN_EXCHANGE, tm.SPAN_SWAP, tm.SPAN_REALIZE, tm.SPAN_INIT,
        tm.SPAN_NUMERICS_SNAPSHOT, tm.EVENT_COMPILE, tm.EVENT_RETRY, tm.EVENT_CHECKPOINT_SAVE,
    }
    assert set(names["spans"]) == host_spans and host_spans <= tm.ALL_SPANS


def test_new_metrics_are_declared_and_read_names_not_shapes():
    """Every per-layer metric this PR adds sits in BENCHMARK.json with its
    cells listed, and the old metrics' files are as PR 24 left them (their
    reducers read opcode + shape only, which names and scopes do not move)."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        declared = {m["name"]: m for m in json.load(f)["per_layer"]}
    new = {
        "kernel_named_pct.bulk", "kernel_named_pct.exchange", "stencil_kernel_pct",
        "exchange_dev_pct.bulk", "exchange_dev_pct.exchange", "step_glue_pct",
        "enqueue_ms_p90.bulk", "enqueue_ms_p90.exchange", "exchange_z_pct.exchange",
        "compiles_in_window.bulk", "compiles_in_window.exchange", "idle_in_program_pct.exchange",
    }
    assert new <= set(declared)
    for name in new:
        with open(os.path.join(ROOT, "benchmark", "layer_metrics", name + ".json")) as f:
            m = json.load(f)
        assert m["reducer"] in ("named_share", "span_percentile", "span_count", "idle_by_span"), name
        assert m["source"] in ("device_trace", "program_span") and m["cells"] == declared[name]["workloads"]
    # PR 27's: the plane route's shares for the acoustic cells, by name too,
    # applied by pattern so that a later acoustic cell needs no edit
    plane = {
        "plane_pass_pct", "plane_pass_hbm_pct", "exchange_dev_pct.plane",
        "step_glue_pct.plane", "kernel_named_pct.plane",
    }
    assert plane <= set(declared)
    for name in plane:
        with open(os.path.join(ROOT, "benchmark", "layer_metrics", name + ".json")) as f:
            m = json.load(f)
        assert m["reducer"] in ("named_share", "named_roofline_hbm") and m["cells"] == ["acoustic-*"], name
        # the cells the pattern matches, in the order they joined the benchmark (PR 37 the second)
        assert declared[name]["workloads"] == ["acoustic-so8-600.bulk", "acoustic-so8-1200x4.bulk"], name
    # PR 31's: the ragged weak cell's shares, by pattern too
    ragged = {
        "exchange_z_pct.ragged", "collective_pct.ragged", "kernel_named_pct.ragged", "enqueue_ms_p90.ragged",
        "compiles_in_window.ragged", "idle_in_program_pct.ragged", "blend_dynamic_pct",
        "blend_dynamic_hbm_pct", "uneven_cut_pct",
    }
    assert ragged <= set(declared)
    for name in ragged:
        with open(os.path.join(ROOT, "benchmark", "layer_metrics", name + ".json")) as f:
            m = json.load(f)
        assert m["cells"] == ["weak-r3-750x4*"] and m["moves"] == "halo_gbps_chip", name
        assert declared[name]["workloads"] == ["weak-r3-750x4.exchange-only"], name
    # PR 33's: the staged plane step's shares for the elastic cells, by pattern
    staged = {
        "plane_pass_pct.staged", "plane_pass_hbm_pct.staged", "exchange_dev_pct.staged",
        "step_glue_pct.staged", "kernel_named_pct.staged", "stage_pct.v", "stage_pct.t",
        "enqueue_ms_p90.staged", "compiles_in_window.staged",
    }
    assert staged <= set(declared)
    for name in staged:
        with open(os.path.join(ROOT, "benchmark", "layer_metrics", name + ".json")) as f:
            m = json.load(f)
        assert m["reducer"] in ("named_share", "named_roofline_hbm", "span_percentile", "span_count"), name
        assert m["cells"] == ["elastic-*"] and m["moves"] == "mcells_per_s_chip", name
        assert declared[name]["workloads"] == ["elastic-so8-600.bulk"], name
    # PR 35's: the set-up account's seven, for every cell (tests/test_bench_setup.py holds them)
    setup = {n for n in declared if n.startswith("setup_") or n == "steady_compiles"}
    assert len(setup) == 7
    # PR 37's: the plane step across chips, by pattern (tests/test_bench_acoustic_x4.py holds them)
    wired = {n for n in declared if n.endswith(".wired")}
    assert len(wired) == 6
    for name in wired:
        with open(os.path.join(ROOT, "benchmark", "layer_metrics", name + ".json")) as f:
            m = json.load(f)
        assert m["reducer"] in ("trace_share", "named_share", "span_percentile", "span_count"), name
        assert m["cells"] == ["acoustic-so8-1200x4*"] and m["moves"] == "mcells_per_s_chip", name
        assert declared[name]["workloads"] == ["acoustic-so8-1200x4.bulk"], name
    wired -= {"collective_pct.wired"}  # a trace_share: it reads opcodes, as PR 24's do
    # PR 39's: the joint lattice-Boltzmann step's shares, their files listing the cell by name
    # (tests/test_bench_lbm.py holds them)
    lbm = {n for n in declared if n.endswith(".lbm") or n == "lbm_pass_hbm_pct"}
    assert len(lbm) == 7
    for name in lbm:
        with open(os.path.join(ROOT, "benchmark", "layer_metrics", name + ".json")) as f:
            m = json.load(f)
        assert m["reducer"] in ("named_share", "named_roofline_hbm", "span_percentile", "span_count"), name
        assert m["cells"] == ["lbm-d3q19-256.bulk"] == declared[name]["workloads"], name
        assert m["moves"] == "mcells_per_s_chip", name
    # PR 44's: the MHD step's shares and its two rooflines, their files listing the cell by name
    # (tests/test_bench_mhd.py holds them)
    mhd = {n for n in declared if n.endswith(".mhd") or n in ("mhd_pass_hbm_pct", "mhd_pass_flops_pct")}
    assert len(mhd) == 8
    for name in mhd:
        with open(os.path.join(ROOT, "benchmark", "layer_metrics", name + ".json")) as f:
            m = json.load(f)
        assert m["reducer"] in ("named_share", "named_roofline_hbm", "named_roofline_flops",
                                "span_percentile", "span_count"), name
        assert m["cells"] == ["astaroth-mhd-256.bulk"] == declared[name]["workloads"], name
        assert m["moves"] == "mcells_per_s_chip", name
    # PR 47's: the MHD step across chips, their files listing the cell by name
    # (tests/test_bench_mhd_x4.py holds them)
    mhdx4 = {n for n in declared if n.endswith(".mhdx4")}
    assert len(mhdx4) == 12
    for name in mhdx4:
        with open(os.path.join(ROOT, "benchmark", "layer_metrics", name + ".json")) as f:
            m = json.load(f)
        assert m["reducer"] in ("trace_share", "named_share", "named_roofline_hbm", "named_roofline_flops",
                                "span_percentile", "span_count"), name
        assert m["cells"] == ["astaroth-mhd-256x4.bulk"] == declared[name]["workloads"], name
        assert m["moves"] == "mcells_per_s_chip", name
    mhdx4 -= {"collective_pct.mhdx4"}  # a trace_share: it reads opcodes, as PR 24's do
    # PR 49's: the wires' own intervals (tests/test_bench_wires.py holds them)
    wires = {n for n in declared if n.startswith("wire_")}
    assert len(wires) == 10
    # PR 51's: the card-filling lattice-Boltzmann cell's shares, the 256 cell's readers under
    # a suffix of its own (tests/test_bench_lbm512.py holds them)
    lbm512 = {n for n in declared if n.endswith(".lbm512")}
    assert len(lbm512) == 7
    for name in lbm512:
        with open(os.path.join(ROOT, "benchmark", "layer_metrics", name + ".json")) as f:
            m = json.load(f)
        assert m["reducer"] in ("named_share", "named_roofline_hbm", "span_percentile", "span_count"), name
        assert m["cells"] == ["lbm-d3q19-512.bulk"] == declared[name]["workloads"], name
        assert m["moves"] == "mcells_per_s_chip", name
    # PR 53's: the four-chip lattice-Boltzmann cell's shares, the .lbm512 and .mhdx4 readers
    # under a suffix of its own (tests/test_bench_lbm512x4.py holds them)
    lbm512x4 = {n for n in declared if n.endswith(".lbm512x4")}
    assert len(lbm512x4) == 11
    for name in lbm512x4:
        with open(os.path.join(ROOT, "benchmark", "layer_metrics", name + ".json")) as f:
            m = json.load(f)
        assert m["reducer"] in ("trace_share", "named_share", "named_roofline_hbm", "span_percentile",
                                "span_count"), name
        assert m["cells"] == ["lbm-d3q19-512x4.bulk"] == declared[name]["workloads"], name
        assert m["moves"] == "mcells_per_s_chip", name
    lbm512x4 -= {"collective_pct.lbm512x4"}  # a trace_share: it reads opcodes, as PR 24's do
    # PR 57's: the card-filling MHD cell's shares, the .mhd readers under a suffix of its own, the
    # two rooflines over a time step's work and the four passes by scope (tests/test_bench_mhd512.py
    # holds them)
    mhd512 = {n for n in declared if ".mhd512" in n}
    assert len(mhd512) == 12
    for name in mhd512:
        with open(os.path.join(ROOT, "benchmark", "layer_metrics", name + ".json")) as f:
            m = json.load(f)
        assert m["reducer"] in ("named_share", "named_roofline_hbm", "named_roofline_flops",
                                "span_percentile", "span_count"), name
        assert m["cells"] == ["astaroth-mhd-512.bulk"] == declared[name]["workloads"], name
        assert m["moves"] == "mcells_per_s_chip", name
    for name in (set(declared) - new - plane - staged - setup - wired - lbm - lbm512 - lbm512x4 - mhd - mhdx4
                 - mhd512 - wires - (ragged - {"collective_pct.ragged"})):
        with open(os.path.join(ROOT, "benchmark", "layer_metrics", name + ".json")) as f:
            assert json.load(f)["reducer"] in ("host_clock", "host_percentile", "trace_share",
                                               "trace_roofline_hbm", "trace_idle"), name
