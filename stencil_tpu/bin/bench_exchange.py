"""bench-exchange — microbenchmark sweep of radius shapes + route A/B.

Parity target: reference bin/bench_exchange.cu: on a global compute-domain
extent (default 128^3, bench_exchange.cu:21,84 — ``fit_to_mesh`` rescales it
to the mesh, so per-device extent SHRINKS as devices grow, exactly the
reference semantics), run exchange+swap under a sweep of radius
configurations — +x-only, ±x, faces-only, faces+edges(eR), uniform —
and report the reference's exact CSV (bench_exchange.cu:57-64):

    name,count,trimean (S),trimean (B/s),stddev,min,avg,max

Beyond the reference: ``--route`` pins the y/z-sweep exchange route
(ops/exchange.py ``EXCHANGE_ROUTES``) for the sweep, and a direct-vs-packed
A/B section measures every engageable route under the burst-aware protocol
(``tune.trial.measure_alternating``: alternate within one process, drop the
post-idle-burst rep 0, steady-state median) with a per-axis (x/y/z) ms
breakdown — so the ~64×-amplified thin-z claim (PERF_NOTES "Thin z-region
access") AND the ~8/(2r) sublane-amplified thin-y claim ("Thin y-region
access") are re-measurable per chip generation.  Legs a route does not
change (x always; y on the z-only packed routes) are measured once under
``direct`` and shared — ``shared_legs_with_direct`` records exactly which,
per route.  The section is emitted as one machine-readable JSON line on
stdout (the bench.py convention).
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import jax
import jax.numpy as jnp

from stencil_tpu.bin import _common
from stencil_tpu.core.dim3 import Dim3
from stencil_tpu.core.radius import Radius
from stencil_tpu.domain import DistributedDomain
from stencil_tpu.utils.statistics import Statistics

#: sweep axes of the per-axis breakdown, exchange-axis index by name
_AXES = {"x": 0, "y": 1, "z": 2}


def bench(n_iters: int, n_quants: int, ext, radius: Radius, inner: int = 1,
          rt: float = 0.0, route: str = None):
    """One config: returns (Statistics of per-iter seconds, exchanged bytes
    per the 26-message model, swept wire bytes).

    ``inner > 1`` runs that many exchanges per device dispatch
    (``exchange_many``) and divides, with the measured host round trip ``rt``
    subtracted — the protocol for hosts where a per-call sync would swamp
    the exchange (see bench.py).  ``route`` pins the z-sweep exchange
    route (None = planner resolution)."""
    x, y, z = _common.fit_to_mesh(ext[0], ext[1], ext[2], radius)
    dd = DistributedDomain(x, y, z)
    dd.set_radius(radius)
    if route is not None:
        dd.set_exchange_route(route)
    for i in range(n_quants):
        dd.add_data(f"d{i}", dtype=jnp.float32)
    dd.realize()
    stats = Statistics()
    from stencil_tpu.core.geometry import sweep_bytes

    swept = sweep_bytes(dd.local_spec(), [jnp.dtype(jnp.float32).itemsize] * n_quants) * dd.num_subdomains()
    if inner > 1:
        def run(k):
            dd.exchange_many(k)
            dd.block_until_ready()

        # auto-scaled so the rt subtraction can never clamp to 0.0
        samples, _ = _common.timed_inner_loop(run, inner, rt, n_iters)
        for s in samples:
            stats.insert(s)
        return stats, dd.exchange_bytes_total(), swept
    dd.exchange()  # compile
    dd.swap()
    dd.block_until_ready()
    for _ in range(n_iters):
        t0 = time.perf_counter()
        dd.exchange()
        dd.swap()
        dd.block_until_ready()
        stats.insert(time.perf_counter() - t0)
    return stats, dd.exchange_bytes_total(), swept


def report_header() -> str:
    # reference columns (bench_exchange.cu:57-64) + one honesty column: the
    # 3-axis sweeps send full-extent slabs, so actual wire bytes exceed the
    # 26-message model for sparse radii (core/geometry.py sweep_bytes)
    return "name,count,trimean (S),trimean (B/s),stddev,min,avg,max,trimean (B/s swept)"


def report(cfg: str, bytes_: int, stats: Statistics, swept: int = 0) -> str:
    tm = stats.trimean()
    bps = bytes_ / tm if tm else float("nan")
    sps = swept / tm if tm else float("nan")
    return (
        f"{cfg},{stats.count()},{tm:e},{bps:e},"
        f"{stats.stddev():e},{stats.min():e},{stats.avg():e},{stats.max():e},{sps:e}"
    )


def sweep_configs(ext, fR: int, eR: int):
    """The five radius shapes of bench_exchange.cu:121-195."""
    tag = f"{ext[0]}-{ext[1]}-{ext[2]}"

    r = Radius.constant(0)
    r.set_dir(Dim3(1, 0, 0), fR)
    yield f"{tag}/px/{fR}", r

    r = Radius.constant(0)
    r.set_dir(Dim3(1, 0, 0), fR)
    r.set_dir(Dim3(-1, 0, 0), fR)
    yield f"{tag}/x/{fR}", r

    r = Radius.constant(0)
    r.set_face(fR)
    yield f"{tag}/faces/{fR}", r

    r = Radius.constant(fR)
    for sx in (1, -1):
        for sy in (1, -1):
            for sz in (1, -1):
                r.set_dir(Dim3(sx, sy, sz), eR)
    yield f"{tag}/face&edge/{fR}/{eR}", r

    yield f"{tag}/uniform/2", Radius.constant(2)


def _route_measured_axes(route: str) -> list:
    """The per-axis legs a route must measure ITSELF: a leg may only be
    shared from ``direct`` when the route compiles a byte-identical program
    for that sweep.  The x sweep is identical on every route (nothing packs
    x-plane slabs); the y sweep differs on the ``yzpack_*`` routes (the
    packed sublane-major message) and the z sweep on every packed route."""
    from stencil_tpu.ops.exchange import Y_PACK_ROUTES

    if route == "direct":
        return ["x", "y", "z"]
    if route in Y_PACK_ROUTES:
        return ["y", "z"]
    return ["z"]


def route_ab(ext, fR: int, n_quants: int, reps: int, rt: float, inner: int = 4) -> dict:
    """Direct-vs-packed steady-state A/B at the uniform radius — every
    engageable route's full exchange plus its per-axis (x/y/z) sweeps, all
    alternating in ONE process under the trial protocol (rep-0 drop,
    steady-state median).  Returns the JSON section."""
    from jax import lax
    from functools import partial

    from stencil_tpu.ops.exchange import EXCHANGE_ROUTES, route_supported
    from stencil_tpu.tune.trial import measure_alternating

    radius = Radius.constant(fR)
    x, y, z = _common.fit_to_mesh(ext[0], ext[1], ext[2], radius)
    dd = DistributedDomain(x, y, z)
    dd.set_radius(radius)
    for i in range(n_quants):
        dd.add_data(f"d{i}", dtype=jnp.float32)
    dd.realize()
    dtypes = [h.dtype for h in dd._handles]
    routes = [
        r
        for r in EXCHANGE_ROUTES
        if r == "direct" or route_supported(r, dtypes, dd._valid_last)
    ]
    packed_ok = len(routes) > 1

    def make_run(fn):
        @partial(jax.jit, static_argnums=1)
        def many(arrays, s):
            return lax.fori_loop(0, s, lambda _, a: fn(a), arrays)

        def run(n):
            jax.block_until_ready(many(dd._curr, n))

        return run

    labels, runs = [], []
    for route in routes:
        labels.append((route, "all"))
        runs.append(make_run(dd.make_exchange_route_fn(route, donate=False)))
        # a route measures only the sweeps it CHANGES; the still-identical
        # legs (x always; y for the z-only packed routes) compile
        # byte-identical programs and are measured once under direct, then
        # shared into the breakdown below — with the shared legs recorded
        # per route in ``shared_legs_with_direct``
        for ax_name in _route_measured_axes(route):
            labels.append((route, ax_name))
            runs.append(
                make_run(
                    dd.make_exchange_route_fn(
                        route, donate=False, axes=(_AXES[ax_name],)
                    )
                )
            )
    # calibrate the dispatch size once on the first run (shared workload —
    # one inner count keeps rounds comparable), re-warm the rest at it
    _, inner = _common.timed_inner_loop(runs[0], inner, rt, 1)
    for run in runs[1:]:
        run(inner)
    rounds = measure_alternating(runs, inner, rt, reps)
    import statistics as _st

    section: dict = {
        "fit_extent": [x, y, z],
        "radius": fR,
        "quantities": n_quants,
        "packed_eligible": packed_ok,
        "measurement_protocol": {
            "alternating_within_process": True,
            "drop_rep0": True,
            "statistic": "median",
            "reps": reps,
            "inner": inner,
        },
        "routes": {},
    }
    for (route, part), samples in zip(labels, rounds):
        entry = section["routes"].setdefault(
            route, {"ms_per_exchange": None, "per_axis_ms": {}}
        )
        ms = _st.median(samples) * 1e3
        if part == "all":
            entry["ms_per_exchange"] = ms
        else:
            entry["per_axis_ms"][part] = ms
    # fill the unmeasured legs from direct's figures (identical programs)
    # and record WHICH legs were shared, per route — the provenance a
    # reader needs before trusting a leg that was never re-measured
    shared: dict = {}
    for route, entry in section["routes"].items():
        if route == "direct":
            continue
        shared[route] = [
            ax for ax in ("x", "y", "z") if ax not in entry["per_axis_ms"]
        ]
        for ax_name in shared[route]:
            entry["per_axis_ms"][ax_name] = section["routes"]["direct"][
                "per_axis_ms"
            ][ax_name]
    section["measurement_protocol"]["shared_legs_with_direct"] = shared
    direct = section["routes"]["direct"]["ms_per_exchange"]
    section["speedup_vs_direct"] = {
        route: (direct / e["ms_per_exchange"]) if e["ms_per_exchange"] else None
        for route, e in section["routes"].items()
        if route != "direct"
    }
    return section


def main(argv=None) -> int:
    p = argparse.ArgumentParser("bench-exchange")
    p.add_argument("--iters", type=int, default=30)
    p.add_argument("--quantities", type=int, default=1)
    p.add_argument("--x", type=int, default=128)
    p.add_argument("--y", type=int, default=128)
    p.add_argument("--z", type=int, default=128)
    p.add_argument("--face-radius", type=int, default=2, dest="fR")
    p.add_argument("--edge-radius", type=int, default=1, dest="eR")
    from stencil_tpu.ops.exchange import EXCHANGE_ROUTES

    p.add_argument(
        "--route",
        default="auto",
        choices=("auto",) + EXCHANGE_ROUTES,
        help="y/z-sweep exchange route for the CSV sweep (auto = planner "
        "resolution: env > tuned config > direct; see docs/tuning.md "
        "'Exchange routes')",
    )
    p.add_argument(
        "--ab-reps",
        type=int,
        default=3,
        metavar="N",
        help="steady-state reps for the direct-vs-packed route A/B section "
        "(alternating protocol, rep 0 dropped; 0 skips the section)",
    )
    p.add_argument(
        "--inner",
        type=int,
        default=None,
        help="exchanges per device dispatch (use >1 where the host sync is slow; "
        "per-iter time = (dispatch - host_rt) / inner; default: 1, or "
        "auto-raised when the host round trip would swamp the exchange)",
    )
    _common.add_telemetry_flags(p)
    args = p.parse_args(argv)
    _common.require_platform("bench-exchange")
    _common.telemetry_begin(args)

    rt = _common.host_round_trip_s()
    if args.inner is None:
        args.inner = 1
        if rt > 10e-3:
            # unset --inner + a slow host round trip (a local chip's is
            # ~us): a per-iteration sync would swamp the exchange,
            # so switch to the exchanges-per-dispatch protocol
            args.inner = 16
            if jax.process_index() == 0:
                print(
                    f"host round trip {rt*1e3:.0f} ms: auto --inner 16 "
                    "(per-iter time = (dispatch - rt) / inner)",
                    file=sys.stderr,
                )
    if args.inner == 1:
        rt = 0.0
    ext = (args.x, args.y, args.z)
    route = None if args.route == "auto" else args.route
    if jax.process_index() == 0:
        print(report_header())
    for name, radius in sweep_configs(ext, args.fR, args.eR):
        stats, bytes_, swept = bench(
            args.iters, args.quantities, ext, radius, args.inner, rt, route
        )
        if jax.process_index() == 0:
            print(report(name, bytes_, stats, swept))
    result = {
        "bench": "exchange",
        "extent": list(ext),
        "quantities": args.quantities,
        "route_flag": args.route,
        "host_round_trip_s": rt,
    }
    if args.ab_reps > 0:
        ab_rt = rt if args.inner > 1 else 0.0
        result["route_ab"] = route_ab(
            ext, args.fR, args.quantities, args.ab_reps, ab_rt
        )
    if jax.process_index() == 0:
        print(json.dumps(result))
    _common.telemetry_end(args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
