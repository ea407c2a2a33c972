"""The set-up account's readers (PR 35: ``reducers/program_total.py`` and the
seven ``layer_metrics/setup_*.json`` / ``steady_compiles.json``); run by hand
on the CPU before any chip call, and part by part from
``tests/test_bench_setup.py``.

    python3 benchmark/selftest_setup.py          # everything
    python3 benchmark/selftest_setup.py b c      # only those parts

(a) a CPU rehearsal of one ``.bulk`` and one ``.exchange-only`` cell through
    ``harness/window.py``, traced: the seven metrics are under
    ``would_report``; read straight after the run they say what a rehearsal
    can check without a chip -- every program phase was timed, nothing
    compiled in a steady dispatch, and the inside view fits inside the
    harness's outside one (``setup_realize_span_s + setup_init_s <=
    realize_s``; ``setup_first_dispatch_s`` under the first dispatch's wall
    time);
(b) ``program_total.reduce`` on a hand-made registry against hand-computed
    sums, ``minus`` included;
(c) a registry without the series (the parent commit, with these files laid
    over it) reads as nothing and raises nothing, series by series;
(d) the seven are declared in ``BENCHMARK.json`` for every cell, their files
    name the ``program_total`` reducer and no ``cells``, and every series a
    file names is a counter of the program's registry.
"""

import contextlib
import glob
import io
import json
import os
import sys
import time
import types

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(ROOT, ".jax_cache"))

from benchmark.reducers import program_total  # noqa: E402

N, DISPATCH = 16, 6  # rehearsal extent per chip, steps / exchanges per dispatch
CELLS = ("jacobi3d-512.bulk", "weak-r3-512x4.exchange-only")
SEVEN = ("setup_realize_span_s", "setup_init_s", "setup_first_dispatch_s", "setup_trace_s",
         "setup_backend_s", "setup_cold_compiles", "steady_compiles")


def files():
    out = {}
    for path in glob.glob(os.path.join(ROOT, "benchmark", "layer_metrics", "*.json")):
        with open(path) as f:
            m = json.load(f)
        if m["reducer"] == "program_total":
            out[m["name"]] = m
    return out


def read(name, ctx=None):
    return program_total.reduce(ctx if ctx is not None else {}, **files()[name]["args"])


def rehearse(workload):
    """One traced rehearsal in process: (last line, the ``plan`` info line)."""
    from benchmark.harness import window
    from stencil_tpu import telemetry

    telemetry.reset()  # a fresh account: this process may have dispatched before
    opts = types.SimpleNamespace(
        workload=workload, seed=2**31 + 35, seconds=0.2, trace=1, lower_precision=False,
        describe_trace=False, also_verify=[], rehearse=N, dispatch_size=DISPATCH)
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = window.run(opts, time.perf_counter())
    assert rc == 0
    lines = [json.loads(x) for x in out.getvalue().splitlines() if x.startswith("{")]
    return lines[-1], next(x for x in lines if x.get("bench") == "plan")


def part_a():
    for workload in CELLS:
        line, plan = rehearse(workload)
        assert line["rehearsal"]["checks_ok"] is True and line["failed"] == 0, line
        assert line["correct"] is False and line["metrics"] == {}  # a rehearsal is never a result
        assert set(SEVEN) <= set(line["rehearsal"]["would_report"]), line["rehearsal"]
        got = {name: read(name) for name in SEVEN}
        assert all(v is not None for v in got.values()), got
        assert got["setup_realize_span_s"] > 0 and got["setup_init_s"] > 0, got
        assert got["setup_first_dispatch_s"] > 0 and got["setup_trace_s"] > 0, got
        assert got["steady_compiles"] == 0 and got["setup_cold_compiles"] >= 0, got
        assert got["setup_realize_span_s"] + got["setup_init_s"] <= plan["realize_s"], (got, plan)
        first_wall = plan["compile_s"] + plan["warm_dispatch_s"]  # the harness's first dispatch, to ready
        assert got["setup_first_dispatch_s"] <= first_wall + 1e-3, (got, plan)
        print("a", workload, "would report the seven;", {k: round(v, 4) for k, v in got.items()})


def part_b():
    counters = {
        "setup.span_seconds.realize": 2.5, "setup.span_seconds.init": 1.25,
        "setup.span_seconds.compile": 0.5, "setup.span_seconds.first_dispatch": 3.0,
        "run.span_seconds.init": 40.0,  # a re-fill after the window: not the start's
    }
    for phase, (trace, backend, compiles, hits) in {
        "realize": (0.1, 0.2, 3, 3), "init": (0.3, 0.4, 2, 1), "compile": (0.5, 0.6, 1, 0),
        "first_dispatch": (0.7, 0.8, 2, 2), "steady": (9.0, 9.0, 0, 0), "outside": (5.0, 5.0, 7, 0),
    }.items():
        counters["setup.trace_seconds." + phase] = trace
        counters["setup.backend_seconds." + phase] = backend
        counters["setup.backend_compiles." + phase] = compiles
        counters["setup.cache_hits." + phase] = hits
        counters["run.backend_compiles." + phase] = 0
    counters["run.backend_compiles.steady"] = 1
    ctx = {"program_counters": counters}
    near = lambda a, b: abs(a - b) < 1e-12  # noqa: E731
    assert read("setup_realize_span_s", ctx) == 2.5 and read("setup_init_s", ctx) == 1.25
    assert read("setup_first_dispatch_s", ctx) == 3.0
    assert near(read("setup_trace_s", ctx), 0.1 + 0.3 + 0.5 + 0.7)  # program phases: no steady, no outside
    assert near(read("setup_backend_s", ctx), 0.2 + 0.4 + 0.6 + 0.8)
    assert read("setup_cold_compiles", ctx) == (3 + 2 + 1 + 2) - (3 + 1 + 0 + 2)
    assert read("steady_compiles", ctx) == 1  # the run's too, not only the start's
    print("b program_total agrees with the hand-computed sums")


def part_c():
    parent = {"domain.step.dispatches": 12, "resilience.retry.attempts": 0}  # a registry from before PR 35
    for name in SEVEN:
        assert read(name, {"program_counters": parent}) is None, name
        assert read(name, {"program_counters": {}}) is None, name
    half = {"setup.backend_compiles.realize": 1}  # one series of several: still nothing
    assert read("setup_cold_compiles", {"program_counters": half}) is None
    real, program_total.program_counters = program_total.program_counters, lambda: None
    try:  # a program whose facade cannot be read at all
        assert all(read(name) is None for name in SEVEN)
    finally:
        program_total.program_counters = real
    print("c a registry without the series reads as nothing")


def part_d():
    from stencil_tpu.telemetry import names as tm

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        declared = {m["name"]: m for m in json.load(f)["per_layer"]}
    mine = files()
    assert set(mine) == set(SEVEN), set(mine) ^ set(SEVEN)
    for name, m in mine.items():
        d = declared[name]
        assert "workloads" not in d and "cells" not in m, name  # every cell, and every later one
        assert (m["layer"], m["moves"], m["better"]) == ("entry points", "setup_s", "lower") == (
            d["layer"], d["moves"], d["better"]), name
        assert m["source"] == d["source"] and m["source"] in ("program_span", "program_counter"), name
        args = m["args"]
        series = [f"{b}.{p}" for b in args["series"] + args.get("minus", []) for p in args["phases"]]
        assert series and set(series) <= tm.ALL_COUNTERS, set(series) - tm.ALL_COUNTERS
        assert set(args["phases"]) <= set(tm.PHASES), name
    print("d", len(mine), "set-up metrics are declared for every cell and read registered series")


if __name__ == "__main__":
    parts = sys.argv[1:] or list("abcd")
    for p in parts:
        globals()["part_" + p]()
    print("selftest_setup ok:", " ".join(parts))
