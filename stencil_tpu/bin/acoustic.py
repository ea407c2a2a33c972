"""acoustic driver — isotropic acoustic wave propagation, space order 8.

Parity target: Devito's ``benchmarks/user/benchmark.py -P acoustic -so 8 -d X
Y Z`` (``examples/seismic/acoustic``: ``iso_stencil``, kernel ``OT2``, a
``--nbl``-point damping layer) — the forward propagator of seismic modelling.
``x y z`` are the PHYSICAL extents, as Devito's ``-d``, and need not be equal
(``1112 1112 512`` on four chips is the benchmark's decomposed shot: mesh
2,2,1, printed on stderr beside the domain); the run adds the sponge and the
4-cell zero frame on every side (docs/acoustic.md).  No source
and no receivers: a seeded wave packet inside the physical region stands for
the shot.  One CSV row, like the other drivers, plus Devito's own figure of
merit (GPts/s over the whole padded grid):

    acoustic,ranks,devCount,x,y,z,nbl,min(s),trimean(s),gpts_per_s
"""

from __future__ import annotations

import argparse
import sys
import time

import jax

from stencil_tpu.bin import _common
from stencil_tpu.core.radius import Radius
from stencil_tpu.models.acoustic import AcousticWave
from stencil_tpu.models.acoustic_reference import FRAME, RADIUS
from stencil_tpu.utils.statistics import Statistics


def run(argv, name: str, model, steps: int) -> int:
    """The driver of a wave propagator on ``AcousticGrid`` (``model`` is
    ``AcousticWave`` or ``ElasticWave``: same grid, frame and sponge
    geometry, same constructor); ``name`` labels the row and the run."""
    p = argparse.ArgumentParser(name)
    p.add_argument("x", type=int, nargs="?", default=512, help="physical extent (Devito's -d)")
    p.add_argument("y", type=int, nargs="?", default=512)
    p.add_argument("z", type=int, nargs="?", default=512)
    p.add_argument("--nbl", type=int, default=40, help="sponge cells per side")
    p.add_argument("--iters", "-n", type=int, default=5, help="timed dispatches")
    p.add_argument("--steps", type=int, default=steps, help="time steps per dispatch")
    p.add_argument("--seed", type=int, default=0, help="seed of vp's layers and the wave packet")
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
    args.interpret = _common.require_platform(name)
    _common.telemetry_begin(args)

    pad = 2 * (args.nbl + FRAME)
    x, y, z = _common.fit_to_mesh(
        args.x + pad, args.y + pad, args.z + pad, Radius.constant(RADIUS)
    )
    print(f"domain: {x},{y},{z} ({x - pad},{y - pad},{z - pad} physical)", file=sys.stderr)
    words = [int(w) for w in jax.random.bits(jax.random.key(args.seed), (4,), "uint32")]
    sim = model(
        x, y, z, nbl=args.nbl, kernel_impl=args.kernel_impl,
        interpret=args.interpret, seed_words=words,
    )
    _common.apply_numerics(args, sim.dd)
    sim.realize()
    # the mesh the partitioner picked for this (maybe non-cubic) extent, and
    # what a step sends over it: Devito's 2 x 2 x 1 for 1112 1112 512 on four
    # chips, "xy" wired and z wrapped in the pass (docs/acoustic.md)
    mesh = ",".join(str(int(d)) for d in sim.dd.mesh_dim())
    plan = getattr(sim._step, "_span_args", dict)()
    print(
        f"mesh: {mesh} wired={plan.get('wired', '')!r} "
        f"wrapped={plan.get('wrapped', '')!r} wire_bytes={plan.get('wire_bytes', 0)} "
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
        args, sim.dd, label=name,
        run_state=lambda: {"model": name, "nbl": args.nbl, "seed": args.seed},
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
        gpts = x * y * z * args.steps / iter_time.trimean() / 1e9
        print(
            f"{name},{ranks},{dev_count},{x - pad},{y - pad},{z - pad},{args.nbl},"
            f"{iter_time.min()},{iter_time.trimean()},{gpts}"
        )
    _common.telemetry_end(args)
    return rc


def main(argv=None) -> int:
    return run(argv, "acoustic", AcousticWave, steps=8)


if __name__ == "__main__":
    sys.exit(main())
