"""jacobi3d driver — the flagship benchmark.

Parity target: reference bin/jacobi3d.cu.  Same CLI shape (positional x y z
base size, weak-scaled by numSubdoms^(1/3); --no-overlap; --trivial; method
flags; --paraview/--prefix/--period) and the same CSV row:

    jacobi3d,<methods>,ranks,devCount,x,y,z,min(s),trimean(s)

(jacobi3d.cu:378-379).  Per-iteration time is the max across processes of the
wall time around step+sync (jacobi3d.cu:265-341).
"""

from __future__ import annotations

import argparse
import contextlib
import sys
import time

import jax
import jax.numpy as jnp

from stencil_tpu.bin import _common
from stencil_tpu.models.jacobi import Jacobi3D, weak_scaled_size
from stencil_tpu.utils.statistics import Statistics


def main(argv=None) -> int:
    p = argparse.ArgumentParser("jacobi3d")
    _common.add_method_flags(p)
    p.add_argument("--no-overlap", action="store_true", help="Don't overlap communication and computation")
    p.add_argument("--prefix", default="", help="prefix for paraview files")
    p.add_argument("--paraview", action="store_true", help="dump paraview files")
    p.add_argument("--iters", "-n", type=int, default=30, help="number of iterations")
    p.add_argument("--period", "-q", type=int, default=-1, help="iterations between checkpoints")
    p.add_argument("--no-weak-scale", action="store_true", help="use x y z as the global size directly")
    p.add_argument("--trace", default=None, help="write a jax.profiler trace to this dir (nsys analog)")
    p.add_argument("--plan", action="store_true", help="dump the communication plan (plan_<rank>.txt analog)")
    p.add_argument("--halo-multiplier", type=int, default=1, help="exchange every k steps with k*r halos")
    p.add_argument(
        "--kernel-impl",
        choices=["pallas", "jnp"],
        default="pallas",
        help="pallas plane-streaming kernel (fast) or XLA slices",
    )
    p.add_argument(
        "--dtype",
        choices=["float32", "bfloat16"],
        default="float32",
        help="quantity dtype (bfloat16: precision-reduced, ~1.6x on v5e)",
    )
    p.add_argument(
        "--pallas-path",
        choices=["auto", "wrap", "slab", "shell", "wavefront"],
        default="auto",
        help="force a specific pallas route (auto: wrap single-device, "
        "temporally-blocked wavefront multi-device, slab/shell fallbacks)",
    )
    p.add_argument(
        "--overlap-report",
        action="store_true",
        help="time overlap=True vs overlap=False (jnp kernel) and report the "
        "achieved-overlap delta (reference --no-overlap A/B, jacobi3d.cu:265-337)",
    )
    _common.add_telemetry_flags(p)
    _common.add_profile_flags(p)
    _common.add_tune_flags(p)
    _common.add_exchange_route_flag(p)
    _common.add_kernel_axis_flags(p)
    _common.add_numerics_flag(p)
    _common.add_checkpoint_flags(p)
    p.add_argument("x", type=int, nargs="?", default=512)
    p.add_argument("y", type=int, nargs="?", default=512)
    p.add_argument("z", type=int, nargs="?", default=512)
    args = p.parse_args(argv)
    args.interpret = _common.require_platform("jacobi3d")
    _common.telemetry_begin(args)
    _common.tune_begin(args)
    try:
        # the tune overrides are process-global; restore them whatever
        # happens so sequential in-process runs (tests) never inherit a
        # prior run's --no-tune/--tune-cache
        return _run(args)
    finally:
        _common.tune_end(args)


def _run(args) -> int:
    x, y, z = _global_size(args)
    if args.overlap_report:
        rc = _overlap_report(args, x, y, z)
        _common.telemetry_end(args)
        return rc

    checkpoint_period = args.period if args.period > 0 else max(args.iters // 10, 1)

    # uneven sizes are padded-and-masked by realize(); no size adjustment
    kernel_impl = args.kernel_impl
    if kernel_impl == "pallas" and (args.halo_multiplier > 1 or args.no_overlap):
        # the pallas path is a fused radius-1 single-exchange kernel; the
        # halo multiplier and the overlap on/off comparison only exist in the
        # generic make_step machinery
        print(
            "halo-multiplier/--no-overlap force --kernel-impl jnp", file=sys.stderr
        )
        kernel_impl = "jnp"
    if (
        args.tune
        and kernel_impl == "pallas"
        and args.pallas_path in ("auto", "wrap", "wavefront")
    ):  # slab/shell routes have no tunable axes — nothing would consult
        # populate the tuned-config cache for THIS workload before the model
        # builds (the build consults it); a warm cache runs zero trials.
        # Gated on the POST-force kernel_impl: a jnp run never consults the
        # tuner, so searching for it would be pure wasted device work.
        # Search selection follows the route the MODEL will take (the wrap
        # route only exists single-device; auto picks wrap there and the
        # wavefront otherwise) — searching a route the build won't consult
        # would burn device work on an orphaned cache entry.
        from stencil_tpu.tune import runners as tune_runners

        single = len(jax.devices()) == 1
        if args.pallas_path == "wrap" or (args.pallas_path == "auto" and single):
            if not single:
                print(
                    "--tune skipped: pallas_path='wrap' needs a single "
                    "device (the model build will reject it too)",
                    file=sys.stderr,
                )
                report = None
            else:
                report = tune_runners.autotune_jacobi_wrap(
                    x, y, z, dtype=jnp.dtype(args.dtype), interpret=args.interpret
                )
        else:  # forced wavefront, or auto on a multi-device mesh
            report = tune_runners.autotune_jacobi_wavefront(
                x, y, z, dtype=jnp.dtype(args.dtype), interpret=args.interpret,
                # same placement as the model built below — a strategy
                # mismatch would re-key the workload and orphan the search
                strategy=_common.parse_strategy(args),
            )
        if report is not None:
            _common.tune_report_stderr(report)
    elif args.tune and kernel_impl == "jnp":
        # the jnp engine's macro step runs the GENERIC exchange — tune its
        # z-sweep route (direct vs packed z-shell, docs/tuning.md "Exchange
        # routes") so the model's realize picks the measured winner up.  The
        # cache is checked BEFORE the probe domain realizes (tune_key works
        # pre-realize), so a warm-cache --tune run does zero device work;
        # the probe is freed before the model allocates.
        from stencil_tpu import tune
        from stencil_tpu.core.radius import Radius
        from stencil_tpu.domain import DistributedDomain
        from stencil_tpu.tune import runners as tune_runners

        probe = DistributedDomain(x, y, z)
        r = Radius.constant(0)
        r.set_face(1)  # the jacobi radius (jacobi3d.cu:205-214)
        probe.set_radius(r)
        probe.set_placement(_common.parse_strategy(args))
        probe.add_data("temp", dtype=jnp.dtype(args.dtype))
        if args.halo_multiplier > 1:
            probe.set_halo_multiplier(args.halo_multiplier)
        if tune.best_config(probe.tune_key("exchange")) is not None:
            print("tune[exchange]: source=cache (warm; zero trials)", file=sys.stderr)
        else:
            probe.realize()
            _common.tune_report_stderr(tune_runners.autotune_exchange(probe))
        del probe
    model = Jacobi3D(
        x,
        y,
        z,
        overlap=not args.no_overlap,
        strategy=_common.parse_strategy(args),
        methods=_common.parse_methods(args),
        kernel_impl=kernel_impl,
        interpret=args.interpret,
        pallas_path=args.pallas_path,
        dtype=jnp.dtype(args.dtype),
        **_common.kernel_axis_kwargs(args),
    )
    if args.halo_multiplier > 1:
        model.dd.set_halo_multiplier(args.halo_multiplier)
    _common.apply_exchange_route(args, model.dd)
    _common.apply_numerics(args, model.dd)
    model.realize()
    if args.plan:
        print(f"wrote {model.dd.write_plan(args.prefix + 'plan')}", file=sys.stderr)

    iter_time = Statistics()
    prof = _common.profile_capture_for(args)
    sup = _common.supervisor_for(
        args,
        model.dd,
        label="jacobi",
        run_state=lambda: {
            "model": "jacobi3d",
            "kernel_impl": kernel_impl,
            "iters": args.iters,
        },
        # elastic capacity: a drain-and-reshard (or cross-mesh restore)
        # re-traces the step for the new geometry
        on_mesh_change=model.rebuild_after_reshard,
    )
    mult = args.halo_multiplier
    dispatch_index = [0]

    def timed_iter():
        # cadence device-profile capture around the dispatch (a captured
        # iteration's timing sample carries profiler overhead — profiling
        # is opt-in and the steady-state stats absorb one outlier)
        idx = dispatch_index[0]
        dispatch_index[0] += 1
        with (prof.maybe(idx) if prof is not None else contextlib.nullcontext()):
            t0 = time.perf_counter()
            model.step(mult)
            model.block_until_ready()
            # one macro (halo_multiplier raw iterations) per timed step; the
            # CSV stays per-iteration so rows are comparable across multipliers
            iter_time.insert((time.perf_counter() - t0) / mult)

    from stencil_tpu.telemetry import trace

    rc = 0
    if sup is not None:
        # supervised long run: no separate warm-up dispatch — a resumed
        # process must advance EXACTLY (iters - restored) iterations for
        # kill/resume runs to stay bitwise comparable to unkilled ones
        # (scripts/run_soak.py); the first timed sample absorbs the compile
        def advance(n):
            for _ in range(n):
                timed_iter()

        def on_chunk(done, n):
            # same 0-based frame indices as the unsupervised loop (chunk=1:
            # `it = done - n` is the iteration that just completed)
            it = done - n
            if args.paraview and it % checkpoint_period == 0:
                from stencil_tpu.io.paraview import write_paraview

                write_paraview(model.dd, f"{args.prefix}jacobi3d_{it}")

        with trace(args.trace):
            out = sup.run(
                args.iters,
                advance,
                start_step=None if args.resume else 0,
                chunk=1,
                on_chunk=on_chunk,
            )
        rc = out.exit_code
    else:
        model.step(mult)  # compile outside the timed loop
        model.block_until_ready()
        with trace(args.trace):
            for it in range(args.iters):
                timed_iter()
                if args.paraview and it % checkpoint_period == 0:
                    from stencil_tpu.io.paraview import write_paraview

                    write_paraview(model.dd, f"{args.prefix}jacobi3d_{it}")
    if args.paraview:
        from stencil_tpu.io.paraview import write_paraview

        write_paraview(model.dd, f"{args.prefix}jacobi3d_final")

    if jax.process_index() == 0 and iter_time.count() > 0:
        ranks, dev_count = _common.ranks_and_devcount()
        print(
            f"jacobi3d,{_common.method_str(args)},{ranks},{dev_count},"
            f"{x},{y},{z},{iter_time.min()},{iter_time.trimean()}"
        )
    _common.telemetry_end(args, profile_capture=prof)
    return rc


def _global_size(args):
    """CLI base size -> global size, weak-scaled by numSubdoms^(1/3)
    (jacobi3d.cu:167-169) unless --no-weak-scale."""
    if args.no_weak_scale:
        return args.x, args.y, args.z
    n = len(jax.devices())
    return tuple(weak_scaled_size(v, n) for v in (args.x, args.y, args.z))


def _overlap_report(args, x, y, z) -> int:
    """A/B the interior/exterior overlap split on this hardware: identical
    jnp-kernel models, overlap on vs off, one timing line each plus the
    ratio.  The scheduled-HLO interleaving itself is pinned by
    tests/test_overlap_schedule.py; this reports the achieved wall-clock
    effect (the reference measures the same thing by rerunning with
    --no-overlap)."""
    rt = _common.host_round_trip_s()

    def measure(overlap):
        # scoped so the first model's HBM is freed before the second
        # realize() allocates (the A/B must fit where a single run fits)
        model = Jacobi3D(
            x, y, z,
            overlap=overlap,
            strategy=_common.parse_strategy(args),
            methods=_common.parse_methods(args),
            kernel_impl="jnp",
        )
        model.realize()

        def run(k):
            model.step(k)
            model.block_until_ready()

        samples, _ = _common.timed_inner_loop(run, 10, rt, args.iters)
        return min(samples)

    results = {overlap: measure(overlap) for overlap in (True, False)}
    if jax.process_index() == 0:
        t_on, t_off = results[True], results[False]
        print(
            f"overlap-report,{x},{y},{z},{t_on},{t_off},"
            f"{(t_off - t_on) / t_off if t_off > 0 else 0.0:.4f}"
        )
        print(
            f"# overlap=True {t_on*1e3:.3f} ms/iter; overlap=False "
            f"{t_off*1e3:.3f} ms/iter; saved {(t_off-t_on)*1e3:.3f} ms "
            f"({100*(t_off-t_on)/t_off if t_off > 0 else 0:.1f}%)",
            file=sys.stderr,
        )
    return 0


if __name__ == "__main__":
    sys.exit(main())
