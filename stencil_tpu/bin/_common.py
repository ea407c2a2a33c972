"""Shared driver plumbing: method flags, timing loops, CSV emission."""

from __future__ import annotations

import argparse
import os
import time

import jax

from jax import shard_map

from stencil_tpu.utils.config import MethodFlags, PlacementStrategy


def _requested_platforms() -> str:
    """The platform list this process was asked to use ("" = jax's pick)."""
    return jax.config.jax_platforms or os.environ.get("JAX_PLATFORMS", "")


def require_platform(who: str) -> bool:
    """CPU only when asked for — the one platform check every device-driving
    entry point shares (the ``bin`` mains, ``python -m stencil_tpu.fabric``,
    ``bench.py``).  A run that was not told ``JAX_PLATFORMS=cpu`` (env or
    ``jax.config.jax_platforms``) and finds itself on ``cpu`` lost its
    accelerator at start-up; its timings would be the interpreter's, so it
    exits non-zero naming what it found instead of printing them.  Logs
    platform / device kind / device count / interpret once, and returns the
    interpret flag (``utils.config.pallas_interpret``)."""
    from stencil_tpu.utils.config import pallas_interpret
    from stencil_tpu.utils.logging import log_info

    backend = jax.default_backend()
    requested = _requested_platforms()
    if backend == "cpu" and "cpu" not in requested.split(","):
        raise SystemExit(
            f"{who}: jax found no accelerator and fell back to platform "
            f"{backend!r} (JAX_PLATFORMS={requested!r}); refusing to run — "
            "set JAX_PLATFORMS=cpu to run on the CPU on purpose"
        )
    interpret = pallas_interpret()
    devices = jax.devices()
    log_info(
        f"{who}: platform={backend} device_kind={devices[0].device_kind} "
        f"devices={len(devices)} interpret={interpret}"
    )
    return interpret


def add_method_flags(p: argparse.ArgumentParser) -> None:
    """The reference's transport-selection flags (jacobi3d.cu:111-120).  All
    map onto the collective exchange on TPU; they are accepted (and echoed in
    the CSV method string) so reference run scripts keep working."""
    p.add_argument("--staged", action="store_true", help="Enable RemoteSender/Recver (ppermute on TPU)")
    p.add_argument("--cuda-aware-mpi", action="store_true", help="Enable CudaAwareMpiSender/Recver (ppermute)")
    p.add_argument("--colo", action="store_true", help="Enable ColocatedHaloSender/Recver (ppermute)")
    p.add_argument("--peer", action="store_true", help="Enable PeerAccessSender (ppermute)")
    p.add_argument("--kernel", action="store_true", help="Enable PeerCopySender (ppermute)")
    p.add_argument("--trivial", action="store_true", help="Skip node-aware placement")


def parse_methods(args) -> MethodFlags:
    m = MethodFlags.Non
    if args.staged:
        m |= MethodFlags.CudaMpi
    if getattr(args, "cuda_aware_mpi", False):
        m |= MethodFlags.CudaAwareMpi
    if args.colo:
        m |= MethodFlags.CudaMpiColocated
    if args.peer:
        m |= MethodFlags.CudaMemcpyPeer
    if args.kernel:
        m |= MethodFlags.CudaKernel
    if m == MethodFlags.Non:
        m = MethodFlags.All
    return m


def method_str(args) -> str:
    """jacobi3d.cu:355-374 method string."""
    parts = []
    if args.staged:
        parts.append("staged")
    if getattr(args, "cuda_aware_mpi", False):
        parts.append("cuda-aware")
    if args.colo:
        parts.append("colo")
    if args.peer:
        parts.append("peer")
    if args.kernel:
        parts.append("kernel")
    if not parts:
        parts.append("ppermute")  # TPU default method
    return "/".join(parts)


def parse_strategy(args) -> PlacementStrategy:
    return PlacementStrategy.Trivial if args.trivial else PlacementStrategy.NodeAware


def add_telemetry_flags(p: argparse.ArgumentParser) -> None:
    """Every driver grows ``--metrics-out``: write the telemetry snapshot
    (counters/gauges/histogram stats, JSON) to PATH at exit.  Passing it
    turns telemetry on for the run; with ``STENCIL_TELEMETRY_DIR`` also set,
    the run additionally leaves a JSONL event log and a Chrome trace there."""
    p.add_argument(
        "--metrics-out",
        default=None,
        metavar="PATH",
        help="write a telemetry snapshot JSON to PATH at exit (enables "
        "telemetry; see docs/observability.md)",
    )


def add_profile_flags(p: argparse.ArgumentParser) -> None:
    """``--profile-dir``: cadence-gated ``jax.profiler`` captures around the
    driver's dispatches (``STENCIL_PROFILE_EVERY`` sets the cadence; unset
    = one capture).  At exit the device rows are merged into the Chrome
    trace and a per-phase roofline report lands next to the captures —
    docs/observability.md "Device-time attribution".  Degrades to a warning
    on backends with no profiler."""
    p.add_argument(
        "--profile-dir",
        default=None,
        metavar="DIR",
        help="capture jax.profiler traces here on the STENCIL_PROFILE_EVERY "
        "cadence; device rows are merged into the Chrome trace and a "
        "roofline report is written at exit (see docs/observability.md)",
    )


def profile_capture_for(args):
    """A configured ``ProfileCapture`` from ``add_profile_flags``'s choice
    (environment fills an unset flag), or None — profiling is opt-in."""
    from stencil_tpu.telemetry.device import ProfileCapture

    return ProfileCapture.from_env(dir=getattr(args, "profile_dir", None))


def profile_finalize(args, capture, chrome_path: str = None) -> None:
    """End-of-run device-truth artifacts: merge the newest capture's device
    rows into the host Chrome trace at ``chrome_path`` (one Perfetto
    timeline) and write the per-phase roofline report into the profile
    dir.  Runs AFTER the final host-trace dump (``telemetry_end`` orders
    this) so nothing re-dumps over the merged rows.  Best-effort — a
    missing trace (no profiler backend) degrades to nothing, never an
    error on the driver's exit path."""
    if capture is None or capture.captures == 0:
        return
    import sys

    from stencil_tpu.telemetry.device import merge_into_chrome_trace
    from stencil_tpu.telemetry.roofline import capture_report, render_markdown
    from stencil_tpu.utils.artifact import atomic_write_json, atomic_write_text

    try:
        if chrome_path is not None:
            merge_into_chrome_trace(chrome_path, capture.dir)
        from stencil_tpu.tune.key import chip_kind

        report = capture_report(capture, chip=chip_kind())
        if report is None:
            print(
                f"profile: no device rows under {capture.dir} (backend "
                "without a device profiler?) — no roofline report; "
                "scripts/perf_report.py can build a host-span fallback",
                file=sys.stderr,
            )
            return
        atomic_write_json(os.path.join(capture.dir, "roofline.json"), report)
        atomic_write_text(
            os.path.join(capture.dir, "roofline.md"), render_markdown(report)
        )
        print(f"profile: roofline report in {capture.dir}", file=sys.stderr)
    except Exception as e:  # noqa: BLE001 — observability must not fail the run
        print(f"profile finalize failed: {e!r}", file=sys.stderr)


def add_tune_flags(p: argparse.ArgumentParser) -> None:
    """Autotuner knobs shared by the model drivers (docs/tuning.md):
    ``--tune`` runs the on-device search for this driver's workload before
    the model builds (zero trials when the persistent cache is warm),
    ``--no-tune`` pins the static calibrated picks, ``--tune-cache``
    redirects the persistent config cache for this run."""
    g = p.add_mutually_exclusive_group()
    g.add_argument(
        "--tune",
        action="store_true",
        help="autotune this workload on-device first (cached: second run "
        "does zero trials)",
    )
    g.add_argument(
        "--no-tune",
        action="store_true",
        help="ignore tuned configs; use the static calibrated defaults",
    )
    p.add_argument(
        "--tune-cache",
        default=None,
        metavar="DIR",
        help="tuned-config cache dir (default: STENCIL_TUNE_CACHE or "
        "~/.cache/stencil_tpu/tune)",
    )


def add_exchange_route_flag(p: argparse.ArgumentParser) -> None:
    """``--exchange-route``: pin the halo exchange's y/z-sweep route for
    this run (docs/tuning.md "Exchange routes").  ``auto`` (default) keeps
    the planner resolution: ``STENCIL_EXCHANGE_ROUTE`` > tuned config > the
    static ``direct`` fallback."""
    from stencil_tpu.ops.exchange import EXCHANGE_ROUTES

    p.add_argument(
        "--exchange-route",
        default="auto",
        choices=("auto",) + EXCHANGE_ROUTES,
        help="y/z-sweep exchange route: direct slabs vs the packed z-shell "
        "(zpack_*) or y+z-shell (yzpack_*) messages (auto = env > tuned "
        "config > direct)",
    )


def apply_exchange_route(args, dd) -> None:
    """Apply ``add_exchange_route_flag``'s choice to a pre-realize domain."""
    route = getattr(args, "exchange_route", "auto")
    if route != "auto":
        dd.set_exchange_route(route)


def add_kernel_axis_flags(p: argparse.ArgumentParser) -> None:
    """``--storage-dtype``: pin the field buffers' storage dtype for this
    run (docs/tuning.md "Storage dtype").  ``auto`` (default) keeps the
    planner resolution: ``STENCIL_STORAGE_DTYPE`` > tuned config > the
    static ``native`` fallback; structural guards (non-f32 fields, routes
    with no f32-accumulate kernels) degrade with a warning."""
    p.add_argument(
        "--storage-dtype",
        default="auto",
        choices=("auto", "native", "bf16"),
        help="field-buffer storage: native dtype vs bf16 storage with f32 "
        "accumulation in-kernel — half the bytes/cell (auto = env > tuned "
        "config > native)",
    )


def kernel_axis_kwargs(args) -> dict:
    """Model ctor kwargs from ``add_kernel_axis_flags``'s choices (``auto``
    maps to None = consult the resolution chain)."""
    sd = getattr(args, "storage_dtype", "auto")
    return {} if sd == "auto" else {"storage_dtype": sd}


def add_stream_overlap_flag(p: argparse.ArgumentParser) -> None:
    """``--stream-overlap``: pin the stream engine's split-step overlap
    schedule for this run (docs/tuning.md "Stream overlap").  ``auto``
    (default) keeps the planner resolution: ``STENCIL_STREAM_OVERLAP`` >
    tuned config > the static ``off``."""
    p.add_argument(
        "--stream-overlap",
        default="auto",
        choices=("auto", "off", "split"),
        help="stream-engine overlap schedule: off = exchange-then-compute, "
        "split = interior pass concurrent with the shell ppermutes plus a "
        "narrow exterior fix-up (bitwise-identical; auto = env > tuned "
        "config > off)",
    )


def add_stream_halo_flag(p: argparse.ArgumentParser) -> None:
    """``--stream-halo``: pin the stream engine's halo consumption mode for
    this run (docs/tuning.md "Fused halo consumption").  ``auto`` (default)
    keeps the planner resolution: ``STENCIL_STREAM_HALO`` > tuned config >
    the static ``array``."""
    p.add_argument(
        "--stream-halo",
        default="auto",
        choices=("auto", "array", "fused"),
        help="stream-engine halo consumption: array = unpack received "
        "shells into the big arrays, fused = land the packed yzpack_* "
        "messages directly in the pass's VMEM planes (bitwise-identical; "
        "needs --exchange-route yzpack_*; auto = env > tuned config > "
        "array)",
    )


def step_hops_str(step) -> str:
    """``x.low:N/x.high:N/...``: the bytes one shard receives over each wired
    hop per exchanging unit of ``step`` (a step; a wavefront's macro) -- the
    step's own ``WireAccount`` (``ops/exchange.py``), which ``run_step``
    counts ``exchange.hop.*.bytes`` from and whose sum a raw step the step's
    span says as ``wire_bytes``: the drivers print the counters' source.  ""
    for a step that declares none, or wires nothing."""
    account = getattr(step, "_wire_account", None)
    if account is None:
        return ""
    return "/".join(f"{axis}.{side}:{nb}" for (axis, side), nb in sorted(account().hops.items()))


def add_numerics_flag(p: argparse.ArgumentParser) -> None:
    """``--numerics-every``: the numerics observatory's snapshot cadence
    (docs/observability.md "Numerics observatory").  Every N raw steps ONE
    fused on-device dispatch computes per-quantity interior health
    (min/max/absmax/mean/L2/non-finite count + first-non-finite
    coordinate), lands it in the snapshot ring (heartbeats and crash
    reports carry it), and runs the registered invariant guardbands —
    observe-only unless ``STENCIL_NUMERICS_ABORT=1``.  Unset falls back to
    ``STENCIL_NUMERICS_EVERY``; 0 disables."""
    p.add_argument(
        "--numerics-every",
        type=int,
        default=None,
        metavar="N",
        help="fused on-device field-health snapshot every N raw steps "
        "(default: STENCIL_NUMERICS_EVERY; 0 = off; see "
        "docs/observability.md 'Numerics observatory')",
    )


def apply_numerics(args, dd) -> None:
    """Apply ``add_numerics_flag``'s choice to a domain (the env default
    is already read by the domain's constructor)."""
    every = getattr(args, "numerics_every", None)
    if every is not None:
        dd.set_numerics_every(max(every, 0))


def add_checkpoint_flags(p: argparse.ArgumentParser) -> None:
    """Long-run survival knobs shared by the model drivers
    (docs/resilience.md "Long-run operation"): ``--checkpoint-dir`` turns
    on the checkpoint/resume supervisor for the run (retention ring of
    atomic checkpoints, SIGTERM-preemption final save + resumable exit,
    FATAL/STALL restart budget), ``--checkpoint-every`` sets the step
    cadence, ``--resume`` continues from the newest valid ring entry.
    Unset knobs fall back to the ``STENCIL_CHECKPOINT_*`` /
    ``STENCIL_SUPERVISOR_RESTARTS`` environment (validated reads)."""
    p.add_argument(
        "--checkpoint-dir",
        default=None,
        metavar="DIR",
        help="checkpoint ring directory; enables the run supervisor "
        "(reuse an existing ring only together with --resume)",
    )
    p.add_argument(
        "--checkpoint-every",
        type=int,
        default=None,
        metavar="N",
        help="checkpoint every N iterations (default: STENCIL_CHECKPOINT_EVERY)",
    )
    p.add_argument(
        "--checkpoint-keep",
        type=int,
        default=None,
        metavar="K",
        help="retention-ring size (default: STENCIL_CHECKPOINT_KEEP or 3)",
    )
    p.add_argument(
        "--resume",
        action="store_true",
        help="resume from the newest valid checkpoint in --checkpoint-dir "
        "(corrupt entries fall back to older ones)",
    )


def supervisor_for(args, dd, label: str, run_state=None, on_mesh_change=None):
    """A configured ``RunSupervisor`` from ``add_checkpoint_flags``'s
    choices (environment knobs fill unset flags), or None when no
    checkpoint dir is configured anywhere — supervision is opt-in.
    ``on_mesh_change`` is the elastic-capacity rebuild hook (the models'
    ``rebuild_after_reshard``): called after a drain-and-reshard or a
    cross-mesh restore so steps closed over the old mesh are re-traced."""
    from stencil_tpu.resilience.supervisor import RunSupervisor, SupervisorConfig

    overrides = {}
    if getattr(args, "checkpoint_every", None) is not None:
        overrides["every_steps"] = max(args.checkpoint_every, 0)
    if getattr(args, "checkpoint_keep", None) is not None:
        overrides["keep"] = max(args.checkpoint_keep, 1)
    cfg = SupervisorConfig.from_env(
        dir=getattr(args, "checkpoint_dir", None), **overrides
    )
    if cfg is None:
        return None
    return RunSupervisor(
        dd, cfg, label=label, run_state=run_state,
        on_mesh_change=on_mesh_change,
    )


def tune_begin(args) -> None:
    """Apply the ``add_tune_flags`` choices to the tune facade; call right
    after ``parse_args`` (before any model/planner construction).  Pair
    with ``tune_end`` on the exit path — the overrides are process-global
    and sequential in-process driver runs (tests) must not inherit a prior
    run's ``--no-tune``/``--tune-cache``."""
    from stencil_tpu import tune

    args._tune_restore = tune.overrides()
    if getattr(args, "tune_cache", None):
        tune.set_cache_dir(args.tune_cache)
    if getattr(args, "no_tune", False):
        tune.set_enabled(False)
    elif getattr(args, "tune", False):
        tune.set_enabled(True)


def tune_end(args) -> None:
    from stencil_tpu import tune

    state = getattr(args, "_tune_restore", None)
    if state is not None:
        tune.restore(state)
        args._tune_restore = None


def tune_report_stderr(report) -> None:
    """One stderr line summarizing a driver's autotune outcome."""
    import sys

    print(
        f"tune[{report.key.route}]: source={report.source} "
        f"config={report.config} trials={report.trials} "
        f"pruned={report.pruned}",
        file=sys.stderr,
    )


def _write_snapshot(path: str) -> None:
    from stencil_tpu import telemetry
    from stencil_tpu.utils.artifact import atomic_write_json

    atomic_write_json(path, telemetry.snapshot())


def telemetry_begin(args) -> None:
    """Enable telemetry when ``--metrics-out`` asked for it (env knobs may
    have enabled it already); call right after ``parse_args``.

    An owned run starts from zeroed metrics (sequential in-process driver
    mains must not bleed counters into each other's snapshots), and the
    snapshot write is ALSO registered via ``atexit`` so a CLI run that dies
    on an exception still leaves its post-mortem artifact — the failed runs
    are the ones whose retry/descent counters matter most.  The clean path
    (``telemetry_end``) writes and unregisters."""
    from stencil_tpu import telemetry

    path = getattr(args, "metrics_out", None)
    if path and not telemetry.enabled():
        telemetry.enable()
        telemetry.reset()
        args._telemetry_owned = True
    if path:
        import atexit

        args._telemetry_atexit = lambda: _write_snapshot(path)
        atexit.register(args._telemetry_atexit)


def telemetry_end(args, profile_capture=None) -> None:
    """Flush telemetry artifacts and write the ``--metrics-out`` snapshot on
    ``main``'s clean exit path (the atexit hook covers crashed CLI runs).
    ``profile_capture`` hands the driver's ``ProfileCapture`` in so the
    device-row merge runs AFTER the final Chrome-trace dump — the other
    order would re-dump host-only spans over the merged timeline."""
    from stencil_tpu import telemetry

    arts = {}
    if telemetry.enabled():
        arts = telemetry.write_artifacts()
    if profile_capture is not None:
        profile_finalize(args, profile_capture, chrome_path=arts.get("trace"))
    path = getattr(args, "metrics_out", None)
    if path:
        _write_snapshot(path)
    hook = getattr(args, "_telemetry_atexit", None)
    if hook is not None:
        import atexit

        atexit.unregister(hook)
        args._telemetry_atexit = None
    if getattr(args, "_telemetry_owned", False):
        # leave the process-global state as we found it (in-process callers:
        # tests drive driver mains directly)
        telemetry.disable()


def host_round_trip_s() -> float:
    """Latency of one device->host readback (subtracted from device-looped
    timings — see bench.py)."""
    import jax.numpy as jnp

    x = jnp.zeros((8,))
    float(jnp.sum(x))
    t0 = time.perf_counter()
    for _ in range(5):
        float(jnp.sum(x))
    return (time.perf_counter() - t0) / 5


def timed_inner_loop(run, inner: int, rt: float, n_iters: int,
                     min_ratio: float = 5.0, max_inner: int = 1 << 14):
    """Per-iteration seconds for a device-looped benchmark, with the host
    round trip ``rt`` subtracted SAFELY.

    ``run(k)`` must execute one synchronous dispatch of ``k`` inner
    iterations (jit-cached per static ``k``).  The measured rt varies from
    call to call (a one-chip machine shares its host's cores), so a fixed
    ``inner`` can make ``t - rt`` go negative and clamp to 0.0 (infinite B/s).  This helper auto-scales
    ``inner`` until one dispatch takes >= ``min_ratio * rt``, re-warming
    after each growth so compiles stay out of the timing; if the threshold
    is unreachable it reports the raw (un-subtracted) time with a warning
    rather than a clamped sample.  Returns (samples, inner_used).
    """
    import sys

    run(inner)  # compile/warm at this inner count
    while True:
        t0 = time.perf_counter()
        run(inner)
        t = time.perf_counter() - t0
        if t >= min_ratio * rt or inner >= max_inner:
            break
        grow = max(2 * inner, int(inner * min_ratio * rt / max(t, 1e-9)))
        inner = min(grow, max_inner)
        run(inner)  # compile at the new static count before re-measuring
    samples = []
    subtract = t >= min_ratio * rt
    if not subtract:
        print(
            f"warning: dispatch ({t:.3g}s at inner={inner}) not >> host rt "
            f"({rt:.3g}s); reporting raw per-iter time (rt not subtracted)",
            file=sys.stderr,
        )
    for _ in range(n_iters):
        t0 = time.perf_counter()
        run(inner)
        t = time.perf_counter() - t0
        samples.append(((t - rt) if subtract else t) / inner)
    return samples, inner


def ranks_and_devcount():
    """(MPI size, per-process device count) analogs."""
    return jax.process_count(), jax.local_device_count()


def fit_to_mesh(x: int, y: int, z: int, radius, devices=None):
    """Round each axis to the NEAREST multiple of the mesh dim (reference
    subdomains may be uneven, partition.hpp:83-114; XLA shards may not — the
    nearest divisible size keeps weak-scaled runs comparable).  The per-axis
    shard is clamped up to the radius shell so realize() cannot reject it."""
    from stencil_tpu.parallel.mesh import choose_partition

    if devices is None:
        devices = jax.devices()
    part = choose_partition((x, y, z), radius, devices)
    dim = part.dim()
    lo, hi = radius.lo(), radius.hi()
    min_shard = max(lo.x, lo.y, lo.z, hi.x, hi.y, hi.z, 1)
    return tuple(
        max(round(v / d), min_shard) * d for v, d in zip((x, y, z), dim)
    )


def make_edge_transfer(mesh, n_dev: int, src: int, dst: int, n_elems: int):
    """Jitted single-edge ``lax.ppermute`` src->dst of ``n_elems`` f32 per
    shard, plus a matching input array.  The shared point-to-point primitive
    under pingpong / bench-alltoallv / measure-buf-exchange."""
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import NamedSharding, PartitionSpec as P

    sharding = NamedSharding(mesh, P("d"))

    @jax.jit
    def go(x):
        def f(blk):
            return lax.ppermute(blk, "d", [(src, dst)])

        return shard_map(f, mesh=mesh, in_specs=P("d"), out_specs=P("d"))(x)

    x = jax.device_put(jnp.ones((n_elems * n_dev,), jnp.float32), sharding)
    return go, x


def _dst_unique_rounds(pairs):
    """Split (src, dst, nbytes) pairs into minimal groups where each source
    and each destination appears at most once — ``lax.ppermute`` requires
    unique sources and destinations per collective.  All groups still launch
    in ONE dispatch."""
    rounds = []
    for p in pairs:
        for r in rounds:
            if all(q[1] != p[1] and q[0] != p[0] for q in r):
                r.append(p)
                break
        else:
            rounds.append([p])
    return rounds


def make_matrix_transfer(mesh, comm):
    """Jitted CONTENDED traversal of a bytes matrix: every pair's transfer is
    in flight in one dispatch, so the fabric sees all copies at once — the
    TPU expression of the reference's batch-started concurrent copies
    (bench_alltoallv.cu:139-168 all-pairs streams, measure_buf_exchange.cu:
    120-159 latch-kernel batch start).  Pairs are grouped by payload size
    (one input buffer per size class, shared by its collectives) and by
    unique-destination rounds (a ppermute constraint); XLA's async collective
    scheduling overlaps the lot.  Returns (go, bufs): ``go(*bufs)`` runs one
    traversal; time it with block_until_ready."""
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import NamedSharding, PartitionSpec as P

    n_dev = comm.shape[0]
    pairs = [
        (i, j, int(comm[i, j]))
        for i in range(n_dev)
        for j in range(n_dev)
        if i != j and comm[i, j] > 0
    ]
    if not pairs:
        return None, ()
    sizes = sorted({sz for _, _, sz in pairs})
    rounds_by_size = {
        sz: _dst_unique_rounds([p for p in pairs if p[2] == sz]) for sz in sizes
    }
    sharding = NamedSharding(mesh, P("d"))
    bufs = tuple(
        jax.device_put(
            jnp.ones((max(sz // 4, 1) * n_dev,), jnp.float32), sharding
        )
        for sz in sizes
    )

    @jax.jit
    def go(*arrs):
        def f(*blks):
            outs = []
            for blk, sz in zip(blks, sizes):
                for rnd in rounds_by_size[sz]:
                    outs.append(
                        lax.ppermute(blk, "d", [(i, j) for i, j, _ in rnd])
                    )
            return tuple(outs)

        return shard_map(
            f,
            mesh=mesh,
            in_specs=tuple(P("d") for _ in arrs),
            out_specs=tuple(
                P("d") for sz in sizes for _ in rounds_by_size[sz]
            ),
        )(*arrs)

    return go, bufs


def measure_matrix_concurrent(mesh, comm, n_iters: int) -> float:
    """Seconds for one CONTENDED traversal of the bytes matrix (all pairs in
    flight together; see make_matrix_transfer).  Compile excluded."""
    go, bufs = make_matrix_transfer(mesh, comm)
    if go is None:
        return 0.0
    jax.block_until_ready(go(*bufs))
    t0 = time.perf_counter()
    for _ in range(n_iters):
        out = go(*bufs)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n_iters


def measure_edge(mesh, n_dev: int, src: int, dst: int, nbytes: int, n_iters: int) -> float:
    """Seconds per single-edge transfer of ``nbytes`` (compile excluded)."""
    import time

    go, x = make_edge_transfer(mesh, n_dev, src, dst, max(int(nbytes) // 4, 1))
    go(x).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(n_iters):
        y = go(x)
    y.block_until_ready()
    return (time.perf_counter() - t0) / n_iters


class WallTimer:
    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *a):
        self.elapsed = time.perf_counter() - self.t0
