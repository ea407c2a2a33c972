"""weak — weak-scaling benchmark: exchange-only parity CSV + overlap A/B.

Parity target: reference bin/weak.cu.  Same shape: positional ``x y z nIters``
base size weak-scaled by ``numGpus^(1/3)`` (weak.cu:63-65), radius 3, four
float quantities (weak.cu:120,132-135), nIters of exchange+swap, then one CSV
row of bytes-per-method + all setup/exchange timers (weak.cu:173-194):

    weak,<methods>,x,y,z,s,MPI(B),Colocated(B),cudaMemcpyPeer(B),direct(B),
    iters,gpus,nodes,ranks,mpi_topo,node_gpus,peer_en,placement,realize,plan,
    create,exchange,swap

On TPU all exchange bytes ride the collective path, so they are reported in
the MPI(B) column (the reference's "All"-method column layout is preserved for
script compatibility); peer_en/node_gpus phases don't exist and report 0.

Beyond the reference: ``--overlap`` switches to the REAL weak-scaling
measurement this repo was missing — a full stream-engine stencil step
(radius-1 mean6, the jacobi kernel) A/B'd between ``overlap=off`` and the
split-step schedule (ops/stream.py; docs/tuning.md "Stream overlap") under
the burst-aware protocol (alternate within one process, drop the post-idle
rep 0, steady-state median), with the bare exchange alternated in the same
rounds for the per-mesh exchange-ms figure.  The result is one
machine-readable JSON document (stdout line + ``--json PATH`` artifact):
per-mesh Mcells/s, exchange ms, and the split-vs-off delta — the per-mesh
rows of the weak-scaling story (scripts/run_weak_scaling.py sweeps meshes
[2,1,1] → [2,2,2] and collects one such artifact per shape).  ``--mesh
MX,MY,MZ`` forces the process grid on the first ``MX*MY*MZ`` devices and
weak-scales the per-chip base size per AXIS (512³/chip on [2,2,1] is a
1024×1024×512 global), so non-cubic meshes stay 512³/chip exactly.
Dryrun-capable: on a non-TPU backend the steps build in interpret mode and
the artifact records ``"dryrun": true`` — the schema is exercised
everywhere, the numbers mean something on hardware.
"""

from __future__ import annotations

import argparse
import json
import statistics
import sys

import jax
import jax.numpy as jnp

from stencil_tpu.bin import _common
from stencil_tpu.core.radius import Radius
from stencil_tpu.domain import DistributedDomain
from stencil_tpu.models.jacobi import weak_scaled_size
from stencil_tpu.utils.config import MethodFlags, pallas_interpret


def run(x: int, y: int, z: int, n_iters: int, args, name: str = "weak") -> str:
    def build_domain():
        dd = DistributedDomain(x, y, z)
        dd.set_methods(_common.parse_methods(args))
        dd.set_radius(Radius.constant(3))  # weak.cu:120
        dd.set_placement(_common.parse_strategy(args))
        _common.apply_exchange_route(args, dd)
        for i in range(4):  # weak.cu:132-135
            dd.add_data(f"d{i}", dtype=jnp.float32)
        dd.enable_exchange_stats(True)
        dd.realize()
        return dd

    dd = build_domain()
    if getattr(args, "tune", False):
        # the exchange-route axis gives weak/strong a search of their own
        # (PR 3 excluded them: nothing here consulted the tuner then).  The
        # winner persists for the workload; when it differs from the route
        # this realize resolved from a cold cache, re-realize so the
        # measured loop runs the tuned pick.
        from stencil_tpu.tune.runners import autotune_exchange

        report = autotune_exchange(dd)
        _common.tune_report_stderr(report)
        tuned_route = (report.config or {}).get("exchange_route")
        if tuned_route and tuned_route != dd.exchange_route():
            dd = build_domain()

    for _ in range(n_iters):
        dd.exchange()
        dd.swap()

    ranks, dev_count = _common.ranks_and_devcount()
    num_gpus = ranks * dev_count
    num_nodes = ranks
    s = dd.stats
    # Colocated/Peer/Direct byte columns are literal 0: those transports do
    # not exist on TPU — every byte rides the collective and is reported in
    # the MPI(B) column (the reference sums per-method counters,
    # src/stencil.cu:260-361)
    row = (
        f"{name},{_common.method_str(args)},{x},{y},{z},{x * y * z},"
        f"{dd.exchange_bytes_for_method(MethodFlags.CudaMpi)},"
        f"0,0,0,"
        f"{n_iters},{num_gpus},{num_nodes},{ranks},"
        f"{s.time_topo:e},{0.0:e},{0.0:e},{s.time_placement:e},"
        f"{s.time_realize:e},{s.time_plan:e},{s.time_create:e},"
        f"{s.time_exchange:e},{s.time_swap:e}"
    )
    return row


def _mean6_kernel(views, info):
    """The radius-1 jacobi stencil, written against the public kernel API —
    the overlap A/B's workload (the flagship kernel on the generic engine)."""
    out = {}
    for name, src in views.items():
        out[name] = (
            src.sh(-1, 0, 0)
            + src.sh(1, 0, 0)
            + src.sh(0, -1, 0)
            + src.sh(0, 1, 0)
            + src.sh(0, 0, -1)
            + src.sh(0, 0, 1)
        ) / 6.0
    return out


def parse_mesh(spec):
    """``"MX,MY,MZ"`` -> (mx, my, mz), or None."""
    if spec is None:
        return None
    parts = [int(v) for v in spec.split(",")]
    if len(parts) != 3 or any(v < 1 for v in parts):
        raise ValueError(f"--mesh wants MX,MY,MZ positive ints, got {spec!r}")
    return tuple(parts)


def overlap_domain_size(args, mesh, devices, weak_scale: bool):
    """Global extent for the overlap A/B.  Mesh mode weak-scales the
    per-chip base PER AXIS (512³/chip stays exact on non-cubic meshes);
    strong mode keeps the global size, rounded to the grid."""
    shell = max(args.halo_mult, 1)  # radius 1 x multiplier
    if mesh is not None:
        if weak_scale:
            return (args.x * mesh[0], args.y * mesh[1], args.z * mesh[2])
        return tuple(
            max(round(v / d), shell) * d
            for v, d in zip((args.x, args.y, args.z), mesh)
        )
    radius = Radius.constant(1)
    if weak_scale:
        n = len(devices)
        return _common.fit_to_mesh(
            weak_scaled_size(args.x, n),
            weak_scaled_size(args.y, n),
            weak_scaled_size(args.z, n),
            radius,
            devices=devices,
        )
    return _common.fit_to_mesh(args.x, args.y, args.z, radius, devices=devices)


def _hop_table(dd, s_exch: float) -> list:
    """The per-hop attribution table every per-mesh artifact carries: the
    bytes ``exchange()`` sends over each mesh hop by its message plan
    (``DistributedDomain.exchange_hop_bytes`` = ``ops/exchange.py exchange_account``,
    the function behind the ``exchange.hop.*.bytes`` counters and a step's
    ``wire_bytes``; hops on unsplit axes report 0), with the measured
    per-exchange time apportioned by byte share.
    Tagged ``source: "analytic"`` — a profiler trace upgrades these to
    measured per-direction device time (``scripts/perf_report.py``)."""
    hop_bytes = dd.exchange_hop_bytes()
    total = sum(hop_bytes.values())
    return [
        {
            "axis": axis,
            "side": side,
            "bytes": nb,
            "share_of_bytes": round(nb / total, 4) if total else None,
            "est_ms": round(s_exch * 1e3 * nb / total, 6) if total else None,
            "source": "analytic",
        }
        for (axis, side), nb in sorted(hop_bytes.items())
    ]


def run_overlap(args, name: str = "weak", weak_scale: bool = True) -> dict:
    """The stream-engine overlap A/B at this mesh: build ``overlap=off`` and
    ``overlap=split`` steps over ONE realized domain (non-donating, the
    autotuner's trial pattern — the domain state never advances), alternate
    them with the bare exchange under the trial protocol, and return the
    per-mesh JSON document."""
    from stencil_tpu.tune.trial import measure_alternating

    interpret = pallas_interpret()
    mesh = parse_mesh(args.mesh)
    devices = jax.devices()
    if mesh is not None:
        need = mesh[0] * mesh[1] * mesh[2]
        if need > len(devices):
            raise SystemExit(
                f"--mesh {args.mesh} needs {need} devices, have {len(devices)}"
            )
        devices = devices[:need]
    x, y, z = overlap_domain_size(args, mesh, devices, weak_scale)
    print(f"{name}-overlap domain: {x},{y},{z} on {len(devices)} chips",
          file=sys.stderr)

    dd = DistributedDomain(x, y, z)
    dd.set_radius(Radius.constant(1))
    dd.set_devices(devices)
    if mesh is not None:
        dd.set_partition(*mesh)
    dd.set_placement(_common.parse_strategy(args))
    if args.halo_mult > 1:
        dd.set_halo_multiplier(args.halo_mult)
    _common.apply_exchange_route(args, dd)
    hs = [dd.add_data(f"d{i}", dtype=jnp.float32) for i in range(args.quantities)]
    dd.realize()
    for i, h in enumerate(hs):
        dd.init_by_coords(h, lambda cx, cy, cz, i=i: jnp.sin(0.13 * (cx + 2 * cy + 3 * cz) + i))

    tune_section = None
    if getattr(args, "tune", False):
        # both new axes give weak/strong a tuner hook: the exchange route
        # (consulted by this realize's successor) and the stream plan incl.
        # overlap (consulted by auto-mode step builds)
        from stencil_tpu.tune.runners import autotune_exchange, autotune_stream

        ex_report = autotune_exchange(dd)
        _common.tune_report_stderr(ex_report)
        st_report = autotune_stream(
            dd, _mean6_kernel, x_radius=1, interpret=interpret,
        )
        _common.tune_report_stderr(st_report)
        tune_section = {
            "exchange": ex_report.to_json(),
            "stream": st_report.to_json(),
        }

    steps = {}
    for ov in ("off", "split"):
        steps[ov] = dd.make_step(
            _mean6_kernel,
            engine="stream",
            donate=False,
            interpret=interpret,
            stream_overlap=ov,
        )

    contracts_verified = None
    if getattr(args, "verify", False):
        # machine-check the property this A/B is about to measure: the
        # split step really is ppermute-independent in its interior, the
        # exchange really is the fused <=6-permute structure — a harness
        # that times a broken schedule produces a confidently wrong artifact
        from stencil_tpu import analysis
        from stencil_tpu.analysis.programs import tpu_shaped_trace

        with tpu_shaped_trace():  # verify the TPU-shaped lowering even on
            # a CPU dryrun (blend kernels on, as production traces them)
            arts = [
                analysis.step_artifact(
                    dd,
                    steps[ov],
                    label=f"{name}-overlap:{ov}",
                    axes={"overlap": ov, "exchange_route": dd.exchange_route()},
                )
                for ov in ("off", "split")
            ]
        findings = analysis.check_artifacts(arts)
        if findings:
            for f in findings:
                print(f.render(), file=sys.stderr)
            raise SystemExit(
                f"{len(findings)} program-contract finding(s) on the built "
                "steps — refusing to measure a schedule that is not what it "
                "claims (python -m stencil_tpu.analysis for the catalog)"
            )
        from stencil_tpu.analysis.framework import applied_contracts

        contracts_verified = applied_contracts(arts)

    def make_step_run(step):
        def go(ninner):
            jax.block_until_ready(step(dd._curr, ninner))

        return go

    exch_fn = dd.make_exchange_route_fn(dd.exchange_route(), donate=False)

    from functools import partial

    from jax import lax

    @partial(jax.jit, static_argnums=1)
    def exch_many(arrays, s):
        return lax.fori_loop(0, s, lambda _, a: exch_fn(a), arrays)

    def exch_run(ninner):
        jax.block_until_ready(exch_many(dd._curr, ninner))

    rt = _common.host_round_trip_s()
    runs = [make_step_run(steps["off"]), make_step_run(steps["split"]), exch_run]
    # the step twins share one dispatch size (same workload; calibrated on
    # off, split re-warmed at it), but the bare exchange is many times
    # cheaper and needs its OWN count — at the step's count its dispatch can
    # undershoot the host round trip and the rt subtraction goes negative
    # (the bench.py headline-vs-exchange sizing, measure_alternating's
    # per-run ``inner`` form)
    _, inner_step = _common.timed_inner_loop(runs[0], 2, rt, 1)
    runs[1](inner_step)
    _, inner_exch = _common.timed_inner_loop(exch_run, inner_step, rt, 1)
    rounds = measure_alternating(
        runs, [inner_step, inner_step, inner_exch], rt, args.ab_reps
    )
    s_off, s_split, s_exch = (statistics.median(r) for r in rounds)

    fabric_summary = None
    if getattr(args, "fabric_probe", False):
        # after the measured rounds: the probe's own dispatches must not
        # land inside the A/B timing.  Warm cache (same topology/chip/
        # payload under STENCIL_FABRIC_CACHE) = zero device work here.
        from stencil_tpu.telemetry import fabric as _fabric

        fdoc = _fabric.ensure(
            dd.mesh,
            nbytes=(1 << 16) if interpret else _fabric.DEFAULT_NBYTES,
        )
        fabric_summary = _fabric.summary(fdoc)

    cells = x * y * z
    dim = dd.placement.dim()
    doc = {
        "bench": f"{name}_overlap",
        "dryrun": interpret,
        "mesh": [dim.x, dim.y, dim.z],
        "chips": dd.num_subdomains(),
        "global": [x, y, z],
        "cells_per_chip": cells // dd.num_subdomains(),
        "quantities": args.quantities,
        "radius": 1,
        "halo_mult": args.halo_mult,
        "exchange_route": dd.exchange_route(),
        "plans": {
            ov: {
                k: steps[ov]._stream_plan.get(k)
                for k in ("route", "m", "z_slabs", "grouping", "overlap")
            }
            for ov in ("off", "split")
        },
        "measurement_protocol": {
            "alternating_within_process": True,
            "drop_rep0": True,
            "statistic": "median",
            "reps": args.ab_reps,
            "inner": {"step": inner_step, "exchange": inner_exch},
            "host_round_trip_s": rt,
        },
        "overlap": {
            ov: {
                "s_per_iter": s,
                "mcells_per_s": (cells / s / 1e6) if s > 0 else None,
                "mcells_per_s_per_chip": (
                    cells / s / 1e6 / dd.num_subdomains() if s > 0 else None
                ),
            }
            for ov, s in (("off", s_off), ("split", s_split))
        },
        "split_speedup": (s_off / s_split) if s_split > 0 else None,
        "exchange": {
            "s_per_exchange": s_exch,
            "ms_per_exchange": s_exch * 1e3,
            "bytes_per_exchange": dd.exchange_bytes_total(),
            "hops": _hop_table(dd, s_exch),
        },
    }
    if fabric_summary is not None:
        doc["fabric"] = fabric_summary
    if contracts_verified is not None:
        doc["contracts_verified"] = contracts_verified
    if tune_section is not None:
        doc["tune"] = tune_section
    return doc


def emit_overlap(doc, args) -> None:
    line = json.dumps(doc)
    if jax.process_index() != 0:
        return  # multi-host: one writer, or N processes race on the artifact
    print(line)
    if args.json:
        from stencil_tpu.utils.artifact import atomic_write_text

        atomic_write_text(args.json, line + "\n")


def build_parser(name: str, overlap_flags: bool = True) -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(name)
    p.add_argument("x", type=int, nargs="?", default=512)
    p.add_argument("y", type=int, nargs="?", default=512)
    p.add_argument("z", type=int, nargs="?", default=512)
    p.add_argument("n_iters", type=int, nargs="?", default=30)
    p.add_argument("--kernel", action="store_true")
    p.add_argument("--peer", action="store_true")
    p.add_argument("--colo", action="store_true")
    p.add_argument("--naive", action="store_true", help="trivial placement (weak.cu --naive)")
    p.add_argument("--cuda-aware", dest="cuda_aware_mpi", action="store_true")
    p.add_argument("--staged", action="store_true")
    if not overlap_flags:
        # weak_exchange shares the base CSV parser but has no overlap A/B
        # and no tuner consult of its own — accepting --overlap/--tune there
        # would be a silent no-op, so the flags don't exist there at all
        _common.add_exchange_route_flag(p)
        _common.add_telemetry_flags(p)
        return p
    p.add_argument(
        "--overlap",
        action="store_true",
        help="run the stream-engine overlap A/B (off vs split-step) instead "
        "of the exchange-only CSV; emits one per-mesh JSON document "
        "(docs/tuning.md 'Stream overlap')",
    )
    p.add_argument(
        "--mesh",
        default=None,
        metavar="MX,MY,MZ",
        help="force the process grid on the first MX*MY*MZ devices; with "
        "--overlap the per-chip base size weak-scales per axis "
        "(512³/chip stays exact on non-cubic meshes)",
    )
    p.add_argument(
        "--json",
        default=None,
        metavar="PATH",
        help="with --overlap: also write the JSON document to PATH (the "
        "per-mesh weak-scaling artifact scripts/run_weak_scaling.py collects)",
    )
    p.add_argument(
        "--ab-reps",
        type=int,
        default=3,
        metavar="N",
        help="steady-state reps for the overlap A/B (alternating protocol, "
        "rep 0 dropped, median)",
    )
    p.add_argument(
        "--fabric-probe",
        action="store_true",
        help="with --overlap: probe (or warm-load from STENCIL_FABRIC_CACHE) "
        "the per-link fabric matrix for this mesh and embed its summary in "
        "the artifact (telemetry/fabric.py; docs/observability.md 'Fabric "
        "observatory')",
    )
    p.add_argument(
        "--verify",
        action="store_true",
        help="with --overlap: run the program-contract verifier "
        "(stencil_tpu.analysis) over the built off/split steps before "
        "timing them — abort instead of measuring a schedule that is not "
        "what it claims; the JSON doc records contracts_verified",
    )
    p.add_argument(
        "--halo-mult",
        type=int,
        default=2,
        metavar="K",
        help="halo multiplier for the overlap A/B domain (K*radius shells; "
        "K>=2 makes the wavefront route eligible)",
    )
    p.add_argument(
        "--quantities",
        type=int,
        default=1,
        metavar="N",
        help="fields exchanged/streamed in the overlap A/B",
    )
    # the exchange planner consults the tuned exchange-route config at
    # realize(); --exchange-route pins it per run, and --tune now runs the
    # exchange-route (and, with --overlap, stream-plan) searches here — the
    # overlap and route axes gave weak/strong planners of their own
    _common.add_exchange_route_flag(p)
    _common.add_tune_flags(p)
    _common.add_telemetry_flags(p)
    return p


def main(argv=None) -> int:
    args = build_parser("weak").parse_args(argv)
    _common.require_platform("weak")
    args.trivial = args.naive
    _common.telemetry_begin(args)
    _common.tune_begin(args)
    try:
        if args.overlap:
            emit_overlap(run_overlap(args, name="weak", weak_scale=True), args)
            _common.telemetry_end(args)
            return 0
        devs = len(jax.devices())
        # weak.cu:63-65 round-to-nearest scaling, and the extent stays as
        # the reference rounds it (750 on four chips is 1191): a mesh that
        # does not divide it gets padded shards whose last one owns the
        # remainder (domain.py), where the reference's differ by one cell
        x = weak_scaled_size(args.x, devs)
        y = weak_scaled_size(args.y, devs)
        z = weak_scaled_size(args.z, devs)
        print(
            f"{devs} subdomains: {x},{y},{z}={x * y * z}",
            file=sys.stderr,
        )
        row = run(x, y, z, args.n_iters, args, name="weak")
        if jax.process_index() == 0:
            print(row)
        _common.telemetry_end(args)
        return 0
    finally:
        _common.tune_end(args)


if __name__ == "__main__":
    sys.exit(main())
