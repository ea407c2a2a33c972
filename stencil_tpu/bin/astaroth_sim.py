"""astaroth-sim driver — Astaroth MHD proxy benchmark.

Parity target: reference bin/astaroth_sim.cu: radius-3 26-direction halos,
sin-wave init, 6-point averaging stencil, interior/exchange/exterior overlap
loop, 5 fixed iterations (astaroth_sim.cu:184,223-274).  The reference prints
progress to stderr only; we additionally emit one jacobi3d-style CSV row so
runs are comparable:

    astaroth,<methods>,ranks,devCount,x,y,z,min(s),trimean(s)
"""

from __future__ import annotations

import argparse
import sys
import time

import jax

from stencil_tpu.bin import _common
from stencil_tpu.core.radius import Radius
from stencil_tpu.models.astaroth import AstarothSim
from stencil_tpu.utils.statistics import Statistics


def main(argv=None) -> int:
    p = argparse.ArgumentParser("astaroth-sim")
    # cxxopts options (astaroth_sim.cu:89-110): x/y/z size, transport flags
    p.add_argument("--x", type=int, default=512)
    p.add_argument("--y", type=int, default=512)
    p.add_argument("--z", type=int, default=512)
    p.add_argument("--iters", type=int, default=5)  # astaroth_sim.cu:223 fixed 5
    p.add_argument("--quantities", type=int, default=1, help="exchanged fields (real Astaroth: 8)")
    p.add_argument("--remote", dest="staged", action="store_true")
    p.add_argument("--cuda-aware-mpi", dest="cuda_aware_mpi", action="store_true")
    p.add_argument("--colocated", dest="colo", action="store_true")
    p.add_argument("--peer-copy", dest="peer", action="store_true")
    p.add_argument("--kernel", action="store_true")
    p.add_argument("--no-overlap", action="store_true")
    p.add_argument("--trivial", action="store_true")
    p.add_argument(
        "--kernel-impl",
        choices=["pallas", "jnp"],
        default="pallas",
        help="pallas plane-streaming kernel (fast) or XLA slices",
    )
    p.add_argument(
        "--schedule",
        choices=["auto", "per-step", "wavefront"],
        default="auto",
        help="auto (default): exchange every m<=3 steps with an m-level "
        "temporal wavefront kernel when shards are even (same field values "
        "up to last-ulp fusion effects, ~1/m the traffic; ~2.6x at 512^3), "
        "per-step otherwise; per-step: reference exchange-cadence parity "
        "(one exchange per iteration, modeling Astaroth's real comm volume); "
        "wavefront: force the temporal schedule (error when not viable)",
    )
    _common.add_telemetry_flags(p)
    _common.add_tune_flags(p)
    _common.add_stream_overlap_flag(p)
    _common.add_stream_halo_flag(p)
    _common.add_exchange_route_flag(p)
    _common.add_kernel_axis_flags(p)
    _common.add_numerics_flag(p)
    _common.add_checkpoint_flags(p)
    args = p.parse_args(argv)
    args.interpret = _common.require_platform("astaroth_sim")
    _common.telemetry_begin(args)
    _common.tune_begin(args)
    try:
        # restore the process-global tune overrides whatever happens —
        # sequential in-process runs must not inherit --no-tune/--tune-cache
        return _run(args)
    finally:
        _common.tune_end(args)


def _run(args) -> int:
    num_subdoms = len(jax.devices())
    print(f"assuming {num_subdoms} subdomains", file=sys.stderr)
    x, y, z = _common.fit_to_mesh(args.x, args.y, args.z, Radius.constant(3))
    print(f"domain: {x},{y},{z}", file=sys.stderr)

    kernel_impl = args.kernel_impl
    if args.no_overlap and kernel_impl == "pallas":
        print("--no-overlap forces --kernel-impl jnp", file=sys.stderr)
        kernel_impl = "jnp"
    if args.tune and kernel_impl == "pallas" and args.schedule != "auto":
        # a forced schedule maps to a forced stream path, and plan_stream
        # only consults the tuned plan on the unconstrained auto path — a
        # search here would be device work nothing ever reads
        print(
            f"--tune has no effect with --schedule {args.schedule} "
            "(forced route; tuned plans apply to schedule=auto only)",
            file=sys.stderr,
        )
    if args.tune and kernel_impl == "pallas" and args.schedule == "auto":
        # tune the generic stream engine's plan for this workload on a
        # throwaway model (the trials never advance its state), then let the
        # real build below consult the now-warm cache.  The cache is checked
        # BEFORE the throwaway model realizes — tune_key works pre-realize,
        # so a warm-cache --tune run really does zero work here (no field
        # allocation, no exchange compile)
        from stencil_tpu import tune
        from stencil_tpu.tune import runners as tune_runners

        tuner_sim = AstarothSim(
            x, y, z, num_quantities=args.quantities,
            strategy=_common.parse_strategy(args), kernel_impl="pallas",
            interpret=args.interpret, schedule=args.schedule,
        )
        if tune.best_config(tuner_sim.dd.tune_key("stream")) is not None:
            print("tune[stream]: source=cache (warm; zero trials)", file=sys.stderr)
        else:
            tuner_sim.realize()
            report = tune_runners.autotune_stream(
                tuner_sim.dd, tuner_sim._kernel, x_radius=1, separable=True,
                interpret=args.interpret,
            )
            _common.tune_report_stderr(report)
        del tuner_sim
    sim = AstarothSim(
        x,
        y,
        z,
        num_quantities=args.quantities,
        overlap=not args.no_overlap,
        strategy=_common.parse_strategy(args),
        kernel_impl=kernel_impl,
        interpret=args.interpret,
        schedule=args.schedule,
        stream_overlap=args.stream_overlap,
        stream_halo=args.stream_halo,
        exchange_route=(
            None if args.exchange_route == "auto" else args.exchange_route
        ),
        **_common.kernel_axis_kwargs(args),
    )
    _common.apply_numerics(args, sim.dd)
    sim.realize()

    iter_time = Statistics()

    def timed_iter():
        t0 = time.perf_counter()
        sim.step()
        sim.block_until_ready()
        iter_time.insert(time.perf_counter() - t0)
        print(f"iter {iter_time.count() - 1}: {iter_time.max():e}s", file=sys.stderr)

    sup = _common.supervisor_for(
        args, sim.dd, label="astaroth",
        run_state=lambda: {"model": "astaroth", "quantities": args.quantities},
        on_mesh_change=sim.rebuild_after_reshard,
    )
    rc = 0
    if sup is not None:
        # supervised: no separate warm-up dispatch (bitwise kill/resume
        # comparability — see bin/jacobi3d.py); first sample absorbs compile
        def advance(n):
            for _ in range(n):
                timed_iter()

        out = sup.run(
            args.iters, advance,
            start_step=None if args.resume else 0, chunk=1,
        )
        rc = out.exit_code
    else:
        sim.step()  # compile
        sim.block_until_ready()
        for it in range(args.iters):
            timed_iter()

    if jax.process_index() == 0 and iter_time.count() > 0:
        ranks, dev_count = _common.ranks_and_devcount()
        print(
            f"astaroth,{_common.method_str(args)},{ranks},{dev_count},"
            f"{x},{y},{z},{iter_time.min()},{iter_time.trimean()}"
        )
    _common.telemetry_end(args)
    return rc


if __name__ == "__main__":
    sys.exit(main())
