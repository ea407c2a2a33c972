"""The benchmark's readers of the set-up account (PR 35):
``benchmark/reducers/program_total.py`` and the seven per-layer metrics of
``setup_s``, part by part from ``benchmark/selftest_setup.py`` — and what
keeps their declarations and the program's registry saying the same."""

import importlib.util
import json
import os

import pytest

from stencil_tpu.telemetry import names as tm

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEVEN = {
    "setup_realize_span_s": "program_span", "setup_init_s": "program_span",
    "setup_first_dispatch_s": "program_span", "setup_trace_s": "program_counter",
    "setup_backend_s": "program_counter", "setup_cold_compiles": "program_counter",
    "steady_compiles": "program_counter",
}


def _selftest():
    spec = importlib.util.spec_from_file_location(
        "bench_selftest_setup", os.path.join(ROOT, "benchmark", "selftest_setup.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.mark.parametrize("part", list("abcd"))
def test_selftest_setup(part, capsys):
    getattr(_selftest(), "part_" + part)()
    assert f"{part} " in capsys.readouterr().out


def _declared():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_the_seven_are_declared_for_every_cell_in_one_run_of_the_list():
    """Appended whole by PR 35; later PRs append behind them (a new entry put
    first or in the middle reads as a change to what was there)."""
    per_layer = _declared()["per_layer"]
    first = [m["name"] for m in per_layer].index(list(SEVEN)[0])
    seven = per_layer[first:first + 7]
    assert [m["name"] for m in seven] == list(SEVEN)
    for m in seven:
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves"}, m  # no `workloads`: every cell
        assert (m["layer"], m["moves"], m["better"], m["source"]) == (
            "entry points", "setup_s", "lower", SEVEN[m["name"]]), m
        assert m["unit"] == ("count" if m["name"].endswith("compiles") else "s"), m


@pytest.mark.parametrize("name", sorted(SEVEN))
def test_a_file_names_the_reducer_no_cells_and_registered_series(name):
    with open(os.path.join(ROOT, "benchmark", "layer_metrics", name + ".json")) as f:
        m = json.load(f)
    assert m["reducer"] == "program_total" and "cells" not in m and m["source"] == SEVEN[name]
    args = m["args"]
    assert set(args) <= {"series", "minus", "phases"} and set(args["phases"]) <= set(tm.PHASES)
    for base in args["series"] + args.get("minus", []):
        epoch, total = base.split(".")
        for phase in args["phases"]:  # a constant of the program's registry, not a free string
            assert tm.PHASE_SERIES[epoch, total, phase] == f"{base}.{phase}" in tm.ALL_COUNTERS
    if name != "steady_compiles":  # the start's account: the program's own phases of the set-up epoch
        assert all(b.startswith(tm.EPOCH_SETUP + ".") for b in args["series"])
        assert set(args["phases"]) <= set(tm.TIMED_PHASES)
    else:  # the whole run's: both epochs, the steady phase alone
        assert args == {"series": ["setup.backend_compiles", "run.backend_compiles"], "phases": [tm.PHASE_STEADY]}
