"""lbm driver — D3Q19 BGK lattice Boltzmann on a fully periodic box.

Parity target: FluidX3D's ``benchmark`` set-up (``src/setup.cpp``: ``LBM
lbm(256u, 256u, 256u, 1.0f/6.0f)``, D3Q19, single relaxation time, FP32, no
extension; arXiv:2112.08926) — the run behind its published table of devices.
``x y z`` is the box; a seeded superposition of Taylor-Green modes stands for
the source's fluid at rest, and ``--nu`` defaults to 1/30 where the source sets
1/6 (docs/lbm.md says why).  One CSV row, like the other drivers, with the
source's own figure of merit (MLUPs/s: million lattice-cell updates a second):

    lbm,ranks,devCount,x,y,z,nu,min(s),trimean(s),mlups
"""

from __future__ import annotations

import argparse
import sys
import time

import jax

from stencil_tpu.bin import _common
from stencil_tpu.core.radius import Radius
from stencil_tpu.models.lbm import RADIUS, LatticeBoltzmann
from stencil_tpu.utils.statistics import Statistics


def main(argv=None) -> int:
    p = argparse.ArgumentParser("lbm")
    p.add_argument("x", type=int, nargs="?", default=256, help="the box (FluidX3D's benchmark: 256^3)")
    p.add_argument("y", type=int, nargs="?", default=256)
    p.add_argument("z", type=int, nargs="?", default=256)
    p.add_argument("--nu", type=float, default=1.0 / 30.0, help="kinematic viscosity, lattice units")
    p.add_argument("--iters", "-n", type=int, default=5, help="timed dispatches")
    p.add_argument("--steps", type=int, default=96,
                   help="steps per dispatch (an even count of the temporal depth keeps the "
                        "wrap route's loop free of copies)")
    p.add_argument("--seed", type=int, default=0, help="seed of the Taylor-Green modes")
    p.add_argument(
        "--kernel-impl",
        choices=["pallas", "jnp"],
        default="pallas",
        help="pallas plane-streaming kernel (fast) or XLA slices",
    )
    _common.add_telemetry_flags(p)
    _common.add_numerics_flag(p)
    _common.add_checkpoint_flags(p)
    args = p.parse_args(argv)
    args.interpret = _common.require_platform("lbm")
    _common.telemetry_begin(args)

    x, y, z = _common.fit_to_mesh(args.x, args.y, args.z, Radius.constant(RADIUS))
    print(f"domain: {x},{y},{z}", file=sys.stderr)
    words = [int(w) for w in jax.random.bits(jax.random.key(args.seed), (4,), "uint32")]
    sim = LatticeBoltzmann(
        x, y, z, nu=args.nu, kernel_impl=args.kernel_impl, interpret=args.interpret,
        seed_words=words,
    )
    _common.apply_numerics(args, sim.dd)
    sim.realize()
    # the route the planner took and what the kernel reads against what the
    # route serves (docs/lbm.md): wrap on one device, plane on a mesh -- and at
    # a box whose planes fit VMEM in y tiles only (512^3 a device), on one device
    # or beside a split y (``plane_window`` "interior" / "interior-z")
    mesh = ",".join(str(int(d)) for d in sim.dd.mesh_dim())
    plan = getattr(sim._step, "_span_args", dict)()
    print(
        f"mesh: {mesh} route={plan.get('route')!r} depth={getattr(sim._step, '_stream_plan', {}).get('m')} "
        f"read_sides={plan.get('read_sides')} exchanged_sides={plan.get('exchanged_sides')} "
        f"aliased={plan.get('aliased')} tile_rows={plan.get('tile_rows', 0)} "
        f"y_tiles={plan.get('y_tiles', 1)} plane_window={plan.get('plane_window')!r} "
        f"plane_lanes={plan.get('plane_lanes')!r} "
        f"wired={plan.get('wired', '')!r} wire_bytes={plan.get('wire_bytes', 0)}",
        file=sys.stderr,
    )

    iter_time = Statistics()

    def timed_iter():
        t0 = time.perf_counter()
        sim.step(args.steps)
        sim.block_until_ready()
        iter_time.insert(time.perf_counter() - t0)

    sup = _common.supervisor_for(
        args, sim.dd, label="lbm",
        run_state=lambda: {"model": "lbm", "nu": args.nu, "seed": args.seed},
        on_mesh_change=sim.rebuild_after_reshard,
    )
    rc = 0
    if sup is not None:
        # supervised: no separate warm-up dispatch (bin/jacobi3d.py)
        def advance(n):
            for _ in range(n):
                timed_iter()

        rc = sup.run(
            args.iters, advance, start_step=None if args.resume else 0, chunk=1
        ).exit_code
    else:
        sim.step(args.steps)  # compile
        sim.block_until_ready()
        for _ in range(args.iters):
            timed_iter()

    if jax.process_index() == 0 and iter_time.count() > 0:
        ranks, dev_count = _common.ranks_and_devcount()
        mlups = x * y * z * args.steps / iter_time.trimean() / 1e6
        print(
            f"lbm,{ranks},{dev_count},{x},{y},{z},{args.nu},"
            f"{iter_time.min()},{iter_time.trimean()},{mlups}"
        )
    _common.telemetry_end(args)
    return rc


if __name__ == "__main__":
    sys.exit(main())
