"""The benchmark's own tests of the cell ``elastic-so8-600.bulk``; run by hand
on the CPU before any chip call (``tests/test_bench_elastic.py`` runs every
part too).

    python3 benchmark/selftest_elastic.py        # everything
    python3 benchmark/selftest_elastic.py d e    # only those parts

(a) the cell's control flow through ``harness/window.py`` (a rehearsal: 24^3,
    interpreted), untraced and traced: sound, every cell of nine wavefields
    compared in x-slabs; the traced run's `domain.step` spans say `stages` 2,
    `exchanged` "6/3", `written` "3/6";
(b) the control: the program's bf16 storage axis comes out NOT correct, on
    three seeds, by ``max_abs_err`` and nothing else;
(c) the timed path broken underneath (a dispatch that returns its state
    unchanged) comes out NOT correct;
(d) the slab reference equals the whole-array reference, bit for bit, at a
    small size -- slabs of every width, at both edges of the grid, with the
    stated overlap (8 planes a step: two stages of radius 4; a step really
    reaches 7 -- a forward difference reads -3..+4, the backward one it feeds
    -4..+3 -- so 8 is safe).  With ONE stage's reach of overlap a slab cut
    inside the wave does NOT compare equal: the overlap is needed;
(e) ``harness/bytes_staged.py`` from the configuration alone.
"""

import contextlib
import io
import json
import os
import sys
import time
import types

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(ROOT, ".jax_cache"))
CELL = "elastic-so8-600.bulk"
N, DISPATCH = 24, 2  # rehearsal extent, time steps per dispatch


def config():
    with open(os.path.join(ROOT, "benchmark", "configs", "elastic-so8-600.json")) as f:
        return json.load(f)


def rehearse(patch=None, **flags):
    """One rehearsal in process: (last line, checks by name, plan line)."""
    from benchmark.harness import window

    opts = types.SimpleNamespace(
        workload=CELL, seed=2**31 + 33, seconds=0.2, trace=0, lower_precision=False,
        describe_trace=False, also_verify=[], rehearse=N, dispatch_size=DISPATCH)
    vars(opts).update(flags)
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = window.run(opts, time.perf_counter(), patch=patch)
    assert rc == 0
    lines = [json.loads(x) for x in out.getvalue().splitlines() if x.startswith("{")]
    checks = {x["name"]: x for x in lines if x.get("bench") == "check"}
    return lines[-1], checks, next(x for x in lines if x.get("bench") == "plan")


def part_a():
    for trace in (0, 1):
        line, checks, plan = rehearse(trace=trace)
        assert line["rehearsal"]["checks_ok"] is True and line["failed"] == 0, (line, checks)
        assert line["correct"] is False and line["metrics"] == {}  # a rehearsal is never a result
        ran = plan["ran"]
        assert (ran["route"], ran["depth"], ran["storage"], ran["descents"]) == ("plane", 1, "native", 0)
        assert [st["exchanged"] for st in ran["stages"]] == [6, 3]
        assert [sum(p["writes"] for p in st["passes"]) for st in ran["stages"]] == [3, 6]
        assert checks["max_abs_err"]["value"] <= 1e-6 and checks["uncompared_cells"]["value"] == 0
        assert checks["reference_stress_sup"]["value"] > 0.05
        assert checks["frame_nonzero_cells"]["value"] == 0 and checks["window_state_bad_cells"]["value"] == 0
        if trace:  # the program's own spans, as the benchmark's reader finds them
            from benchmark.harness import timeline

            spans = [h[3] for h in timeline.host_spans(timeline.load(), "domain.step")]
            assert spans and all(
                (a["stages"], a["exchanged"], a["written"], a["steps"]) == (2, "6/3", "3/6", DISPATCH)
                for a in spans
            ), spans[:2]
        print("a", CELL, "trace", trace, "would report", line["rehearsal"]["would_report"])


def part_b():
    for seed in (101, 2**31 + 102, 103):
        line, checks, plan = rehearse(lower_precision=True, seed=seed)
        assert plan["ran"]["storage"] == "bf16"
        assert line["rehearsal"]["checks_ok"] is False
        assert [n for n, c in checks.items() if not c["ok"]] == ["max_abs_err"], checks
        assert checks["max_abs_err"]["value"] > 20 * checks["max_abs_err"]["limit"]
    print("b", CELL, "bf16 storage: not correct on three seeds, by max_abs_err alone")


def part_c():
    def freeze(cell):  # the step returns its state unchanged
        cell.dispatch = lambda n: None

    line, checks, _ = rehearse(patch=freeze, seed=7)
    bad = [n for n, c in checks.items() if not c["ok"]]
    assert line["rehearsal"]["checks_ok"] is False and "max_abs_err" in bad, (line, checks)
    print("c", CELL, "frozen step: not correct, failing", bad)


def part_d():
    import numpy as np

    from benchmark.harness import reference_elastic as wave

    s = wave.setup_from(config(), [64, 24, 24])  # cut edges fall inside the wave
    words, steps = np.asarray([5, 6, 7, 8], dtype=np.uint32), 2
    X = s.shape[0]
    whole = [np.asarray(a) for a in wave.reference_slab(s, steps, words, 0, X, halo=0)]
    assert min(float(np.max(np.abs(a))) for a in whole) > 1e-3  # all nine have moved
    for width in (7, 16, X):
        starts = wave.slab_starts(X, width)
        assert starts[0] == 0 and starts[-1] == X - width
        covered = np.zeros(X, bool)
        for first in starts:
            got = wave.reference_slab(s, steps, words, first, width)
            for q, g, w in zip(wave.WAVEFIELDS, got, whole):
                assert np.array_equal(np.asarray(g), w[first : first + width]), (q, width, first)
            covered[first : first + width] = True
        assert covered.all()
    # the overlap is needed: at 7 planes a step (docstring) an inner slab is
    # exact, with one stage's reach alone it is wrong at its edges
    for halo, exact in ((7 * steps, True), (wave.RADIUS, False)):
        got = wave.reference_slab(s, steps, words, 28, 8, halo=halo)
        same = all(np.array_equal(np.asarray(g), w[28:36]) for g, w in zip(got, whole))
        assert same is exact, (halo, same)
    print("d slab reference == whole-array reference at", s.shape, "widths 7, 16,", X)


def part_e():
    from benchmark.harness import bytes_staged

    c = config()
    assert bytes_staged.step_bytes(c) == (9 + 2 + 6 + 1 + 10 + 4 + 7 + 2) * 608**3 * 4
    assert bytes_staged.plane_pass_bytes(c) == bytes_staged.step_bytes(c) / 4
    c["passes"] = [{"reads": 1, "writes": 1}, {"reads": 3, "writes": 0}]
    c["extent_per_chip"], c["radius"] = [8, 8, 120], 4
    assert bytes_staged.step_bytes(c) == 5 * 16 * 16 * 128 * 4
    assert bytes_staged.plane_pass_bytes(c) == 2.5 * 16 * 16 * 128 * 4
    print("e bytes_staged.py counts the configured passes")


if __name__ == "__main__":
    parts = sys.argv[1:] or list("abcde")
    for p in parts:
        globals()["part_" + p]()
    print("selftest_elastic ok:", " ".join(parts))
