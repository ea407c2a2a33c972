"""The benchmark's own tests; run by hand on the CPU before any chip call.

    python3 benchmark/selftest.py            # everything
    python3 benchmark/selftest.py b d        # only those parts

(a) every cell's control flow through ``run.py --rehearse`` (the x4 cells on
    four virtual devices), untraced and traced;
(b) every reducer on ``harness/fixture_trace.json`` against hand-computed
    answers;
(c) ``harness/bytes.py`` against ``dd.exchange_bytes_total()``;
(d) ``layer_metrics/*.json`` against ``BENCHMARK.json`` and the allowed
    characters;
(e) the control: the program's bf16 storage axis comes out NOT correct in
    every cell, on three seeds;
(f) the timed path broken underneath (a dispatch that returns its state
    unchanged) comes out NOT correct in every cell.
"""

import contextlib
import glob
import io
import json
import os
import re
import subprocess
import sys
import time
import types

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(ROOT, ".jax_cache"))
N, DISPATCH = 16, 6  # rehearsal extent per chip, steps/exchanges per dispatch


def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def rehearse(workload, seed, *flags, seconds=1):
    cmd = [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--workload", workload,
           "--seed", str(seed), "--seconds", str(seconds), "--rehearse", str(N),
           "--dispatch-size", str(DISPATCH), *flags]
    p = subprocess.run(cmd, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, (cmd, p.stderr[-2000:])
    return json.loads(p.stdout.strip().splitlines()[-1])


def part_a():
    for w in bench()["workloads"]:
        for trace in ("0", "1"):
            line = rehearse(w["name"], 2**31 + 11, "--trace", trace)
            assert line["correct"] is False and line["metrics"] == {}, line
            assert line["rehearsal"]["checks_ok"] is True, line
            assert line["failed"] == 0 and line["attempted"] >= 1, line
            assert line["device"]["platform"] == "cpu", line
            print("a", w["name"], "trace", trace, "would report", line["rehearsal"]["would_report"])


def part_b():
    from benchmark.harness import trace
    from benchmark.reducers import (host_clock, host_percentile, trace_idle,
                                    trace_roofline_hbm, trace_share)

    with open(os.path.join(ROOT, "benchmark", "harness", "fixture_trace.json")) as f:
        table = json.load(f)
    ctx = {"table": table, "peaks": {"hbm_bytes_per_s": 1e12},
           "clocks": {"compile_s": 1.5}, "samples": {"dispatch_s": [i / 1000 for i in range(1, 21)]}}
    near = lambda a, b: abs(a - b) < 1e-9 * max(1.0, abs(b))  # noqa: E731
    chip0 = table["devices"]["/device:TPU:0"]
    # chip 0 busy: [0,140] (the collective overlaps the copy) + [150,250] = 240
    assert trace.busy_ns(chip0) == 240
    assert [o[0] for o in trace.leaf_ops(chip0)].count("while__s32____f32_8_8_128___") == 0
    s = trace.device_summary(table)
    assert near(s["busy_s"], (240 + 250) / 2 / 1e9) and near(s["window_s"], 250e-9), s
    assert s["idle_gaps"] == [["block", 5e-9]], s  # the 10 ns gap, halved over two chips
    assert near(trace_idle.reduce(ctx), 100 * (1 - 245 / 250))  # 2 %
    pallas = trace_share.reduce(ctx, include=["^custom-call"])
    assert near(pallas, (100 * 160 / 240 + 100) / 2), pallas  # 83.33 %
    coll = trace_share.reduce(ctx, include=["^collective-permute"])
    assert near(coll, (100 * 30 / 240 + 0) / 2), coll  # 6.25 %
    glue = trace_share.reduce(ctx, include=[".*"], exclude=["^custom-call", "^collective-permute"])
    assert near(glue, (100 * 50 / 240 + 0) / 2), glue
    assert trace_share.reduce(ctx, include=["^all-to-all"]) is None
    assert trace_share.reduce(ctx, include=["^all-to-all"], absent="zero") == 0.0
    # three calls of f32[8,8,128]: 2 x 32768 B each at 1e12 B/s, over 160 + 250 ns
    roof = trace_roofline_hbm.reduce(ctx, include=["^custom-call"])
    assert near(roof, 100 * (3 * 2 * 32768 / 1e12) / 410e-9), roof  # 47.95 %
    assert host_percentile.reduce(ctx, series="dispatch_s", q=90, scale=1000.0) == 18.0
    assert host_percentile.reduce(ctx, series="absent", q=90) is None
    assert host_clock.reduce(ctx, clock="compile_s") == 1.5
    hlo = ('%closed_call.4 = f32[512,512,512]{2,1,0:T(8,128)} custom-call(f32[512,512,512]{2,1,0:T(8,128)} %copy.12, '
           's32[512,512]{1,0:T(8,128)S(1)} %f.2), custom_call_target="tpu_custom_call", operand_layout_constraints={f32[512,512,512]{2,1,0}}')
    assert trace.op_label(hlo) == "custom-call_f32_512_512_512_"
    assert trace.op_label(hlo + ", output_to_operand_aliasing={{0}: (1, {})}") == "custom-call-inplace_f32_512_512_512_"
    assert trace.op_label(hlo.replace("tpu_custom_call", "Sharding")) == "custom-call.Sharding_f32_512_512_512_"
    loop = "%while = (s32[]{:T(128)}, f32[512,512,512]{2,1,0:T(8,128)}, s32[]{:T(128)}) while((s32[], f32[512,512,512]) %t), body=%b"
    assert trace.op_label(loop) == "while__s32____f32_512_512_512___s32___"
    assert trace_roofline_hbm.operand_bytes("custom-call__f32_518_518_640___f32_518_6_518__") == 4 * (518 * 518 * 640 + 518 * 6 * 518)
    print("b reducers agree with the hand-computed answers")


def part_c():
    import jax
    import jax.numpy as jnp

    from benchmark.harness import bytes as work
    from stencil_tpu import DistributedDomain, Radius

    dd = DistributedDomain(2 * N, 2 * N, N)
    dd.set_radius(Radius.constant(3))
    dd.set_devices(jax.devices()[:4])
    for i in range(4):
        dd.add_data(f"q{i}", dtype=jnp.float32)
    dd.realize()
    mine = 4 * work.halo_bytes_per_chip([N, N, N], 3, 4, 4)
    assert mine == dd.exchange_bytes_total(), (mine, dd.exchange_bytes_total())
    assert work.halo_cells_per_chip([512] * 3, 3) == 4774104  # ISSUE 24; x4 = PR 21's 19,096,416
    assert work.cell_updates_per_chip([512] * 3, 8, 21) == 512**3 * 8 * 21
    print("c bytes.py agrees with dd.exchange_bytes_total():", mine)


def part_d():
    b = bench()
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
    unit = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
    cells = {w["name"]: w for w in b["workloads"]}
    traffic = {}
    for w in b["workloads"]:
        with open(os.path.join(ROOT, "benchmark", "traffic", w["traffic"] + ".json")) as f:
            traffic[w["name"]] = json.load(f)["end_to_end"]["name"]
    e2e = {m["name"]: m for m in b["end_to_end"]}
    for cell, metric in traffic.items():
        assert cell in e2e[metric].get("workloads", cells), (cell, metric)
    declared = {m["name"]: m for m in b["per_layer"]}
    files = {}
    for path in glob.glob(os.path.join(ROOT, "benchmark", "layer_metrics", "*.json")):
        with open(path) as f:
            m = json.load(f)
        assert os.path.basename(path) == m["name"] + ".json", path
        files[m["name"]] = m
        assert os.path.exists(os.path.join(ROOT, "benchmark", "reducers", m["reducer"] + ".py")), m
    assert set(files) == set(declared), set(files) ^ set(declared)
    from benchmark.harness.window import layer_metrics_for

    for n, m in files.items():
        d = declared[n]
        for key in ("unit", "better", "source", "layer", "moves"):
            assert m[key] == d[key], (n, key)
        assert name.match(n) and unit.match(m["unit"]) and m["moves"] in e2e, n
        # the cells the harness would report it in == the cells BENCHMARK.json promises
        mine = {c for c in cells if n in {x["name"] for x in layer_metrics_for(c, {traffic[c], "setup_s"})}}
        promised = set(d.get("workloads") or [c for c in cells if m["moves"] in (traffic[c], "setup_s")])
        assert mine == promised, (n, mine, promised)
        for c in promised:  # every cell reporting it also reports the metric it moves
            assert m["moves"] in (traffic[c], "setup_s"), (n, c)
    for x in b["configs"] + b["workloads"] + b["end_to_end"]:
        assert name.match(x["name"]), x["name"]
    print("d", len(files), "layer metrics agree with BENCHMARK.json")


def part_e():
    for w in bench()["workloads"]:
        for seed in (101, 2**31 + 102, 103):
            line = rehearse(w["name"], seed, "--lower-precision")
            assert line["rehearsal"]["checks_ok"] is False, (w["name"], seed, line)
        print("e", w["name"], "bf16 storage: not correct on three seeds")


def part_f():
    from benchmark.harness import window

    def freeze(cell):  # the step returns its state unchanged
        cell.dispatch = lambda n: None

    for w in bench()["workloads"]:
        opts = types.SimpleNamespace(
            workload=w["name"], seed=7, seconds=0.2, trace=0, lower_precision=False,
            describe_trace=False, also_verify=[], rehearse=N, dispatch_size=DISPATCH)
        out = io.StringIO()
        with contextlib.redirect_stdout(out):
            rc = window.run(opts, time.perf_counter(), patch=freeze)
        line = json.loads(out.getvalue().strip().splitlines()[-1])
        assert rc == 0 and line["rehearsal"]["checks_ok"] is False, (w["name"], line)
        bad = [json.loads(x)["name"] for x in out.getvalue().splitlines()
               if '"bench": "check"' in x and '"ok": false' in x]
        print("f", w["name"], "frozen step: not correct, failing", bad)


if __name__ == "__main__":
    parts = sys.argv[1:] or list("abcdef")
    for p in parts:
        globals()["part_" + p]()
    print("selftest ok:", " ".join(parts))
