"""astaroth_mhd driver — Astaroth's compressible MHD step on a periodic box.

Parity target: Astaroth's standard solver (``acc-runtime/samples/mhd_modular/
mhdsolver.ac``: the Pencil Code's continuity, momentum, induction and entropy
equations, sixth-order differences, Williamson's RK3; 256^3 a device in its
scaling study, arXiv:2103.01597) — the step ``bin/astaroth_sim.py`` runs a
proxy of.  ``x y z`` is the grid, on a UNIFORM cell: its shortest axis spans
2 pi and the box grows with the others, as the source's weak scaling grows it
(``512 512 256`` on four devices: 256^3 a device on mesh 2,2,1, the box 4 pi x
4 pi x 2 pi, the cell and the time step those of ``256 256 256``); a seeded
superposition of plane waves stands for the source's random or file data, and
the time step is fixed (docs/astaroth-mhd.md).  One CSV row, like the other drivers, with the
figure of merit the proxy's cell reports (million cell updates a second over
the eight evolved fields, a time step = three substeps):

    astaroth_mhd,ranks,devCount,x,y,z,dt,min(s),trimean(s),mcells_per_s
"""

from __future__ import annotations

import argparse
import dataclasses
import sys
import time

import jax

from stencil_tpu.bin import _common
from stencil_tpu.core.radius import Radius
from stencil_tpu.models.astaroth_mhd import RADIUS, AstarothMHD
from stencil_tpu.models.astaroth_mhd_reference import FIELDS, MhdSetup, dt_of
from stencil_tpu.utils.statistics import Statistics


def main(argv=None) -> int:
    p = argparse.ArgumentParser("astaroth_mhd")
    p.add_argument("x", type=int, nargs="?", default=256, help="the box (Astaroth's scaling study: 256^3 a device)")
    p.add_argument("y", type=int, nargs="?", default=256)
    p.add_argument("z", type=int, nargs="?", default=256)
    p.add_argument("--iters", "-n", type=int, default=5, help="timed dispatches")
    p.add_argument("--steps", type=int, default=8,
                   help="time steps per dispatch (an even count keeps the plane route's "
                        "loop free of copies: three renames a step, two steps a trip)")
    p.add_argument("--seed", type=int, default=0, help="seed of the plane waves")
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
    args.interpret = _common.require_platform("astaroth_mhd")
    _common.telemetry_begin(args)

    x, y, z = _common.fit_to_mesh(args.x, args.y, args.z, Radius.constant(RADIUS))
    print(f"domain: {x},{y},{z}", file=sys.stderr)
    words = [int(w) for w in jax.random.bits(jax.random.key(args.seed), (4,), "uint32")]
    setup = MhdSetup((x, y, z))
    if not x == y == z:  # one cell on every axis: the shortest spans the cube's side
        cell = setup.box / min(x, y, z)
        setup = dataclasses.replace(setup, box=(cell * x, cell * y, cell * z))
    sim = AstarothMHD(
        x, y, z, setup=setup, kernel_impl=args.kernel_impl, interpret=args.interpret,
        seed_words=words,
    )
    _common.apply_numerics(args, sim.dd)
    sim.realize()
    # what the planner made of the three substeps (docs/astaroth-mhd.md)
    mesh = ",".join(str(int(d)) for d in sim.dd.mesh_dim())
    plan = getattr(sim._step, "_span_args", dict)()
    print(
        f"mesh: {mesh} wired={plan.get('wired', '')!r} wrapped={plan.get('wrapped', '')!r} "
        f"wire_bytes={plan.get('wire_bytes', 0)} wired_edges={plan.get('wired_edges', '')!r} "
        f"route={plan.get('route')!r} stages={plan.get('stages')} renamed={plan.get('renamed')} "
        f"plane_window={plan.get('plane_window')!r} plane_strip={plan.get('plane_strip')} "
        f"passes={plan.get('passes')} passes_by_stage={plan.get('passes_by_stage', '')!r} "
        f"read_sides={plan.get('read_sides')} exchanged_sides={plan.get('exchanged_sides')} "
        f"hops={_common.step_hops_str(sim._step)!r}",
        file=sys.stderr,
    )

    iter_time = Statistics()

    def timed_iter():
        t0 = time.perf_counter()
        sim.step(args.steps)
        sim.block_until_ready()
        iter_time.insert(time.perf_counter() - t0)

    sup = _common.supervisor_for(
        args, sim.dd, label="astaroth_mhd",
        run_state=lambda: {"model": "astaroth_mhd", "seed": args.seed},
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
        rate = x * y * z * len(FIELDS) * args.steps / iter_time.trimean() / 1e6
        print(
            f"astaroth_mhd,{ranks},{dev_count},{x},{y},{z},{dt_of(sim.setup)},"
            f"{iter_time.min()},{iter_time.trimean()},{rate}"
        )
    _common.telemetry_end(args)
    return rc


if __name__ == "__main__":
    sys.exit(main())
