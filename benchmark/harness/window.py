"""One run of one cell: set-up, the timed window, the comparison that decides
``correct``, and the last line.

The window (ISSUE 24): whole dispatches enqueued ``depth`` deep so the
device never waits for the host; the clock starts after the
``block_until_ready`` that ends the warm-up, no dispatch is enqueued once
``--seconds`` have passed, and the clock stops after ``block_until_ready``
on the last one.  Work *completed* over time *elapsed*: no partial dispatch
is counted and none is cut.
"""

from __future__ import annotations

import collections
import fnmatch
import glob
import importlib
import json
import os
import shutil
import sys
import time

from benchmark.harness import bytes as work

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
OUT = os.path.join(ROOT, ".bench_out")  # git-ignored: traces, trace summaries


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def say(kind: str, **fields) -> None:
    """One JSON info line (never the last line of a run)."""
    print(json.dumps({"bench": kind, **fields}, default=str), flush=True)


def find_cell(workload: str) -> tuple:
    """(cell entry, configuration file, traffic file) by the names in
    ``BENCHMARK.json``; nothing is registered in Python."""
    bench = load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; BENCHMARK.json has {sorted(cells)}")
    cell = cells[workload]
    cfg_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = load_json(ROOT, cfg_entry["file"])
    traffic = load_json(BENCH, "traffic", cell["traffic"] + ".json")
    return cell, config, traffic


def layer_metrics_for(workload: str, reported: set) -> list:
    """Every ``layer_metrics/*.json`` that applies to this cell: the cell
    reports the end-to-end metric it moves, and its ``cells`` patterns (all
    cells when absent) match the cell's name."""
    out = []
    for path in sorted(glob.glob(os.path.join(BENCH, "layer_metrics", "*.json"))):
        m = load_json(path)
        if m["moves"] not in reported:
            continue
        if any(fnmatch.fnmatchcase(workload, p) for p in m.get("cells", ["*"])):
            out.append(m)
    return out


def work_per_dispatch(config: dict, traffic: dict, n: int) -> float:
    """Work one chip completes per dispatch, by the benchmark's own count."""
    if traffic["work"] == "cell_updates":
        return work.cell_updates_per_chip(config["extent_per_chip"], config["fields"], n)
    if traffic["work"] == "halo_bytes":
        return n * work.halo_bytes_per_chip(
            config["extent_per_chip"], config["radius"], config["itemsize"], config["fields"]
        )
    raise ValueError(f"unknown work kind {traffic['work']!r}")


def timed_window(cell, n: int, depth: int, seconds: float, annotate) -> dict:
    """The closed loop of one client.  Returns attempted/completed/raised,
    elapsed seconds and each dispatch's service time (from the later of its
    enqueue and the previous completion, to its own completion)."""
    pending = collections.deque()
    service, raised, attempted, completed = [], 0, 0, 0
    last_done = t0 = time.perf_counter()

    def finish_oldest():
        nonlocal last_done, completed
        enq, token = pending.popleft()
        with annotate("bench.block"):
            token.block_until_ready()
        done = time.perf_counter()
        service.append(done - max(enq, last_done))
        last_done = done
        completed += 1

    try:
        while time.perf_counter() - t0 < seconds:
            enq = time.perf_counter()
            attempted += 1
            with annotate("bench.enqueue"):
                cell.dispatch(n)
                pending.append((enq, cell.token()))
            if len(pending) >= depth:
                finish_oldest()
        while pending:
            finish_oldest()
    except Exception as e:  # noqa: BLE001 -- a dispatch that raises is a failed run, reported
        import traceback

        traceback.print_exc()
        raised += 1
        say("dispatch_raised", error=f"{type(e).__name__}: {e}"[:500])
    return {
        "attempted": attempted, "completed": completed, "raised": raised,
        "elapsed_s": time.perf_counter() - t0, "service_s": service,
    }


def device_report(devices) -> dict:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    peaks = [p for p in peaks if p is not None]
    return {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices), "memory_peak_bytes": max(peaks) if peaks else None,
    }


def run(opts, t_start: float, patch=None) -> int:
    """Drive one run; print the last line; return the exit code.  ``patch``
    (tests only) gets the built cell before the warm-up, to break it."""
    cell_entry, config, traffic = find_cell(opts.workload)
    chips = cell_entry["chips"]

    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    devices = jax.devices()
    platform = devices[0].platform
    if opts.rehearse:
        if platform != "cpu":
            raise SystemExit("--rehearse is for the CPU (JAX_PLATFORMS=cpu); measure without it")
        config = dict(config)
        mesh = config["mesh"]
        config["extent_per_chip"] = [opts.rehearse] * 3
        config["global_extent"] = [opts.rehearse * m for m in mesh]
    elif platform != "tpu":
        print(f"benchmark: needs a tpu backend, jax found {platform!r} "
              f"({len(devices)} device(s)); nothing was built", file=sys.stderr)
        return 2
    if len(devices) < chips:
        print(f"benchmark: {opts.workload} needs {chips} chip(s), jax found "
              f"{len(devices)}; nothing was built", file=sys.stderr)
        return 2
    devices = list(devices[:chips])
    interpret = platform != "tpu"
    n = opts.dispatch_size if opts.rehearse and opts.dispatch_size else config["dispatch"][cell_entry["traffic"]]
    depth = traffic["pipeline_depth"]
    peaks = None
    if not opts.rehearse:
        known = load_json(BENCH, "harness", "peaks.json")
        if devices[0].device_kind not in known:
            raise SystemExit(f"no peaks for device kind {devices[0].device_kind!r} in harness/peaks.json")
        peaks = known[devices[0].device_kind]

    from benchmark.factories.common import failure_counters
    from benchmark.harness import reference

    module, _, function = config["factory"].partition(":")
    build = getattr(importlib.import_module(module), function)
    words = reference.seed_words(opts.seed)
    clocks = {}

    # --- set-up: realize, seeded fill, compile, warm-up ------------------------
    t = time.perf_counter()
    cell = build(config, devices, interpret, lower_precision=opts.lower_precision)
    cell.init(words)
    cell.token().block_until_ready()
    clocks["realize_s"] = time.perf_counter() - t
    if patch is not None:
        patch(cell)
    t = time.perf_counter()
    cell.dispatch(n)
    cell.token().block_until_ready()
    first = time.perf_counter() - t
    t = time.perf_counter()
    for _ in range(depth):  # the window's own rhythm, compiled and warm
        cell.dispatch(n)
        token = cell.token()
    token.block_until_ready()
    clocks["warm_dispatch_s"] = (time.perf_counter() - t) / depth
    clocks["compile_s"] = max(first - clocks["warm_dispatch_s"], 0.0)
    plan = cell.plan()
    say("plan", workload=opts.workload, seed=opts.seed, dispatch=n, depth=depth,
        interpret=interpret, cache_dir=jax.config.jax_compilation_cache_dir,
        planned=config.get("expect"), ran=plan, **clocks)

    # --- the window --------------------------------------------------------------
    import contextlib

    untraced = lambda name: contextlib.nullcontext()  # noqa: E731
    annotate = untraced
    trace_dir = None
    seconds = opts.seconds
    if opts.trace:
        annotate = jax.profiler.TraceAnnotation
        trace_dir = os.path.join(OUT, "trace", opts.workload)
        shutil.rmtree(trace_dir, ignore_errors=True)
        os.makedirs(trace_dir, exist_ok=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        seconds = min(seconds, traffic["trace_seconds"])
    before = failure_counters()
    setup_s = time.perf_counter() - t_start
    win = timed_window(cell, n, depth, seconds, annotate)
    if opts.trace:
        jax.profiler.stop_trace()
        # the traced stretch is short: go on untraced to the window's full length
        # (the first dispatch after stop_trace starts on an empty queue: left out)
        rest = timed_window(cell, n, depth, max(opts.seconds - win["elapsed_s"], 0.0), untraced)
        samples = win["service_s"] + rest["service_s"][1:]
        win["raised"] += rest["raised"]
        win["attempted"] += rest["attempted"]
        win["completed"] += rest["completed"]
    else:
        samples = win["service_s"]
    after = failure_counters()
    device = device_report(devices)  # the program's peak: the reference has not run yet
    degraded = {k: after[k] - before.get(k, 0) for k in after if after[k] != before.get(k, 0)}
    plan_after = cell.plan()

    # --- correct -----------------------------------------------------------------
    t = time.perf_counter()
    checks = cell.state_checks() + cell.verify(words, traffic["verify_dispatches"], n)
    for seed in opts.also_verify:  # more seeds through the same compiled objects
        for c in cell.verify(reference.seed_words(seed), traffic["verify_dispatches"], n):
            checks.append({**c, "name": f"{c['name']}.seed{seed}"})
    expect = config.get("expect", {})
    if not opts.rehearse and not opts.lower_precision:
        for key, want in expect.items():
            got = plan_after.get(key)
            checks.append({"name": f"ran_{key}", "value": got, "limit": want, "ok": got == want,
                           "what": "the route the configuration states"})
    failed = win["raised"] + sum(degraded.values()) + plan_after["descents"]
    checks.append(reference.check("failed_dispatches", failed, 0,
                                  "raised + resilience retries + ladder descents"))
    checks.append({"name": "completed_dispatches", "value": win["completed"], "limit": ">=1",
                   "ok": win["completed"] >= 1, "what": "the window finished at least one"})
    for c in checks:
        say("check", **c)
    say("after_window", reference_s=time.perf_counter() - t, degraded=degraded,
        memory_peak_bytes=device["memory_peak_bytes"],
        memory_peak_gib=(device["memory_peak_bytes"] or 0) / 2**30)
    correct = all(c["ok"] for c in checks)

    # --- metrics -----------------------------------------------------------------
    e2e = traffic["end_to_end"]
    reported = {e2e["name"], "setup_s"}
    metrics = {}
    if not opts.trace:
        per_dispatch = work_per_dispatch(config, traffic, n)
        rate = per_dispatch * win["completed"] / win["elapsed_s"]
        metrics[e2e["name"]] = {"value": rate * e2e["scale"], "unit": e2e["unit"]}
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}
        say("window", elapsed_s=win["elapsed_s"], completed=win["completed"],
            work_per_dispatch_per_chip=per_dispatch)
    else:
        from benchmark.harness import trace

        # a CPU rehearsal's trace has no device plane: the host's samples only
        table = None if opts.rehearse else trace.load(trace_dir)
        if opts.describe_trace:
            os.makedirs(OUT, exist_ok=True)
            with open(os.path.join(OUT, f"trace_describe.{opts.workload}.json"), "w") as f:
                json.dump(trace.describe(trace_dir), f, indent=1, default=str)
        summary = {"device_ops": [], "idle_gaps": []}
        if table is not None:
            summary = trace.device_summary(table)
            device["busy_s"], device["window_s"] = summary["busy_s"], summary["window_s"]
        ctx = {"table": table, "peaks": peaks, "clocks": clocks,
               "samples": {"dispatch_s": samples}}
        for m in layer_metrics_for(opts.workload, reported):
            reducer = importlib.import_module("benchmark.reducers." + m["reducer"])
            value = reducer.reduce(ctx, **m.get("args", {}))
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result = {
        "correct": bool(correct) and not interpret,  # a rehearsal is never a result
        "attempted": win["attempted"], "failed": failed, "metrics": metrics, "device": device,
    }
    if opts.rehearse:  # a CPU run prints no number under a device metric's name
        result["rehearsal"] = {"checks_ok": bool(correct), "would_report": sorted(metrics)}
        result["metrics"] = {}
    if opts.trace:
        result["breakdown"] = {"device_ops": summary["device_ops"], "idle_gaps": summary["idle_gaps"]}
    print(json.dumps(result), flush=True)
    return 0
