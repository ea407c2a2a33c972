"""The named-timeline readers' own tests (``selftest.py`` is PR 24's and is
not edited); run by hand on the CPU before any chip call, and case by case
from ``tests/test_bench_timeline.py``.

    python3 benchmark/selftest_timeline.py          # everything
    python3 benchmark/selftest_timeline.py a c      # only those parts

(a) the join on ``harness/fixture_timeline.json``: kernel names, scope
    paths, and the compiler-added copy that inherits its neighbour's scope;
(b) every new reducer on that fixture against hand-computed answers: shares
    <= 100, an op under ``exchange.z/blend_slab/pallas_call`` counts for
    ``exchange_dev_pct`` and not for ``stencil_kernel_pct``, a span series
    under 10 samples reads as nothing;
(c) the shape of a CPU rehearsal's trace (no device plane) and of a program
    from before PR 25 (nothing named): every reducer reads nothing, none
    raises;
(d) ``read_xplane`` on a real ``*.xplane.pb``: a live ``jax.profiler``
    session on the CPU, a jitted program under a named scope and two
    ``TraceAnnotation``s written through the file and read back;
(e) the new ``layer_metrics/*.json`` as the harness would apply them to the
    fixture: the metrics of the ISSUE's table come out, each under its name.
"""

import json
import os
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from benchmark.harness import timeline, trace  # noqa: E402
from benchmark.reducers import idle_by_span, named_share, span_count, span_percentile  # noqa: E402

PALLAS = "^custom-call(-inplace)?_"
SWEEP = r"(^|/)exchange\.[xyz](/|$)"
near = lambda a, b: abs(a - b) < 1e-9 * max(1.0, abs(b))  # noqa: E731


def fixture(which="traced"):
    with open(os.path.join(ROOT, "benchmark", "harness", "fixture_timeline.json")) as f:
        return timeline.build(json.load(f)[which], workload="fixture." + which)


def part_a():
    tl = fixture()
    assert tl["named"] is True
    chip0 = {o[0].split("_")[0] + "@" + str(int(o[1])): o for o in tl["devices"]["/device:TPU:0"]}
    kern = chip0["custom-call@10"]
    assert kern[3] == "stream_wavefront_pass" and kern[4].endswith("stream_wavefront_pass/pallas_call"), kern
    blend = chip0["custom-call-inplace@200"]
    assert blend[3] == "blend_slab" and "/exchange.z/blend_slab/" in blend[4] and blend[5] is False, blend
    copy7 = chip0["copy@130"]  # no op_name of its own: its user reshape.4 is under exchange.z
    assert copy7[4].endswith("exchange.z/reshape") and copy7[5] is True and copy7[3] is None, copy7
    copy9 = chip0["copy@250"]  # feeds the kernel: inherits ITS path, is no kernel and no exchange
    assert copy9[4].endswith("stream_wavefront_pass/pallas_call") and copy9[5] is True and copy9[3] is None, copy9
    nameless = tl["devices"]["/device:TPU:1"][1]
    assert nameless[3] == "shard_map" and nameless[3] not in timeline.program_names()["kernels"]
    print("a the join names kernels, scopes and inherited copies")


def part_b():
    tl = fixture()
    ctx = {"timeline": tl, "table": None, "peaks": None, "clocks": {}, "samples": {}}
    # chip 0: busy = the while's [10, 390) = 380; chip 1: busy [0, 300) + [310, 400) = 390
    assert trace.busy_ns(tl["devices"]["/device:TPU:0"]) == 380
    stencil = named_share.reduce(ctx, label=[PALLAS], kernel=["^(jacobi|stream|mean6)_"])
    assert near(stencil, (100 * 100 / 380 + 100 * 300 / 390) / 2), stencil  # the blend kernel is NOT in it
    exchange = named_share.reduce(ctx, scope=[SWEEP], absent="zero")
    assert near(exchange, (100 * (20 + 30 + 40 + 50) / 380 + 0) / 2), exchange  # fusion + inherited copy + wire + blend
    own_scope_only = named_share.reduce(ctx, scope=[SWEEP], inherit=False, absent="zero")
    assert near(own_scope_only, (100 * (20 + 40 + 50) / 380 + 0) / 2), own_scope_only
    glue = named_share.reduce(ctx, not_label=[PALLAS, "^collective-permute"], not_scope=[SWEEP], absent="zero")
    assert near(glue, (100 * (60 + 40) / 380 + 0) / 2), glue  # copy.9 + pad.1
    named = named_share.reduce(ctx, label=[PALLAS], registered_kernels=True, over=[PALLAS], absent="zero")
    assert near(named, (100.0 + 100 * 300 / 390) / 2), named  # chip 1's closed_call is nameless
    for share in (stencil, exchange, glue, named):
        assert 0.0 <= share <= 100.0
    assert named_share.reduce(ctx, scope=[r"(^|/)exchange\.y(/|$)"]) is None  # nothing under y
    assert named_share.reduce(ctx, scope=[r"(^|/)exchange\.y(/|$)"], absent="zero") == 0.0
    # 12 domain.step spans of 1..12 us: nearest-rank p90 is the 11th
    assert near(span_percentile.reduce(ctx, span="domain.step", q=90, scale=1000.0), 0.011)
    assert span_percentile.reduce(ctx, span="domain.exchange", q=90) is None  # 3 samples < 10
    assert span_count.reduce(ctx, names=["domain.compile", "backend_compile"]) == 2
    assert span_count.reduce(ctx, names=["domain.compile"], requires="domain.swap") is None
    s = timeline.summary(tl)
    assert s["domain_step_spans"] == 12 and s["domain_step_steps_sum"] == 192, s
    assert s["domain_exchange_count_sum"] == 3 and near(s["traced_elapsed_s"], 400e-9), s
    assert near(s["inherited_scope_pct"], 100 * (30 + 60) / (340 + 390)), s  # over LEAF busy: [10,310)+[320,360) and 390
    # chip 0 has no gap (the while covers its own); chip 1's [300, 310), midpoint 305, lies inside
    # domain.exchange [302, 802), the innermost (latest to open) of the spans that cover it
    with tempfile.TemporaryDirectory() as d:
        root, timeline.ROOT = timeline.ROOT, d
        try:
            assert idle_by_span.reduce(ctx) == 100.0
            with open(os.path.join(d, ".bench_out", "idle_by_span.fixture.traced.json")) as f:
                assert json.load(f) == {"domain.exchange": 10 / 1e9 / 2}
        finally:
            timeline.ROOT = root
    print("b the new reducers agree with the hand-computed answers")


def part_c():
    for which in ("rehearsal", "parent"):
        tl = fixture(which)
        assert tl["named"] is False
        ctx = {"timeline": tl}
        assert named_share.reduce(ctx, scope=[SWEEP], absent="zero") is None
        assert named_share.reduce(ctx, label=[PALLAS], registered_kernels=True, over=[PALLAS], absent="zero") is None
        assert idle_by_span.reduce(ctx) is None
        assert span_percentile.reduce(ctx, span="domain.exchange", q=90) is None
    assert near(span_percentile.reduce({"timeline": fixture("rehearsal")}, span="domain.step", q=90, scale=1000.0), 0.002)
    assert span_count.reduce({"timeline": fixture("parent")}, names=["domain.compile"]) is None
    assert span_count.reduce({"timeline": fixture("rehearsal")}, names=["domain.compile"]) == 0
    assert named_share.reduce({"timeline": None}, scope=[SWEEP]) is None
    print("c a rehearsal's trace and a nameless program read as nothing")


def part_d():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def program(x):
        with jax.named_scope("exchange.z"):
            return jnp.roll(x, 1, axis=0) * 2.0

    x = jnp.ones((8, 128))
    program(x).block_until_ready()
    with tempfile.TemporaryDirectory() as d:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(d, profiler_options=options)
        with jax.profiler.TraceAnnotation("domain.step", label="selftest", steps=16):
            with jax.profiler.TraceAnnotation("not.in.the.registry"):
                program(x).block_until_ready()
        jax.profiler.stop_trace()
        raw = timeline.read_xplane(trace.find_xplane(d))
    steps = [h for h in raw["host"] if h[0] == "domain.step"]
    assert len(steps) == 1 and steps[0][2] > 0, raw["host"]
    assert steps[0][3] == {"label": "selftest", "steps": 16}, steps[0][3]
    assert not any(h[0] == "not.in.the.registry" for h in raw["host"])
    module = next(m for name, m in raw["hlo"].items() if name.startswith("jit_program"))
    assert any("exchange.z" in inst["op_name"] for inst in module.values()), module
    assert all(set(inst) == {"opcode", "op_name", "operands"} for inst in module.values())
    tl = timeline.build(raw)
    assert tl["devices"] == {} and tl["named"] is False  # the CPU has no device plane
    print("d read_xplane reads spans with their args and the HLO name map from a real xplane")


def part_e():
    import glob
    import importlib

    tl = fixture()
    ctx = {"timeline": tl, "table": None, "peaks": None, "clocks": {}, "samples": {}}
    want = {
        "kernel_named_pct.bulk": (100 + 100 * 300 / 390) / 2, "kernel_named_pct.exchange": (100 + 100 * 300 / 390) / 2,
        "stencil_kernel_pct": (100 * 100 / 380 + 100 * 300 / 390) / 2,
        "exchange_dev_pct.bulk": 100 * 140 / 380 / 2, "exchange_dev_pct.exchange": 100 * 140 / 380 / 2,
        "exchange_z_pct.exchange": 100 * 140 / 380 / 2,
        "step_glue_pct": 100 * 100 / 380 / 2, "enqueue_ms_p90.bulk": 0.011, "enqueue_ms_p90.exchange": None,
        "compiles_in_window.bulk": 2, "compiles_in_window.exchange": 2,
    }
    seen = set()
    for path in sorted(glob.glob(os.path.join(ROOT, "benchmark", "layer_metrics", "*.json"))):
        with open(path) as f:
            m = json.load(f)
        if m["name"] not in want:
            continue
        seen.add(m["name"])
        got = importlib.import_module("benchmark.reducers." + m["reducer"]).reduce(ctx, **m.get("args", {}))
        assert (got is None and want[m["name"]] is None) or near(got, want[m["name"]]), (m["name"], got)
    assert seen == set(want), set(want) - seen
    print("e", len(seen), "new layer metrics read the fixture as computed by hand")


if __name__ == "__main__":
    parts = sys.argv[1:] or list("abcde")
    for p in parts:
        globals()["part_" + p]()
    print("selftest_timeline ok:", " ".join(parts))
