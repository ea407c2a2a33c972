"""measure-buf-exchange — feedback controller equalizing per-pair copy times.

Parity target: reference bin/measure_buf_exchange.cu: find per-pair message
sizes that make every device<->device transfer take the same target time
(4 ms), by gradient descent on the sizes over 50 iterations
(measure_buf_exchange.cu:32,189-223).  The TPU equivalent adjusts per-pair
``lax.ppermute`` payload sizes.  Per iteration it prints the size matrix ``x``
(MiB), measured times ``y``, and the adjustment ``dx``
(measure_buf_exchange.cu:91-96,180-185,209-214), then the final sizes.
"""

from __future__ import annotations

import argparse
import sys

import jax
import numpy as np
from jax.sharding import Mesh

from stencil_tpu.bin._common import measure_edge, measure_matrix_concurrent

MiB = 1024 * 1024


def print_mat(label: str, m: np.ndarray, fmt) -> None:
    print(label)
    for i in range(m.shape[0]):
        print(" ".join(fmt(m[i, j]) for j in range(m.shape[1])))


def main(argv=None) -> int:
    p = argparse.ArgumentParser("measure-buf-exchange")
    p.add_argument("--target", type=float, default=4e-3, help="target seconds per pair")
    p.add_argument("--iters", type=int, default=50, help="controller iterations")
    p.add_argument("--sub-iters", type=int, default=3, help="timing reps per measurement")
    p.add_argument("--init-mib", type=float, default=1.0, help="initial size (MiB)")
    p.add_argument(
        "--max-mib", type=float, default=256.0,
        help="per-pair size cap (MiB): a fast edge (e.g. a self-edge on one "
        "chip, ~hundreds of GB/s) would otherwise need GB-scale buffers to "
        "reach the 4 ms target and exhaust HBM before converging",
    )
    p.add_argument("--tol", type=float, default=0.05, help="relative convergence tolerance")
    from stencil_tpu.bin import _common

    _common.add_telemetry_flags(p)
    args = p.parse_args(argv)
    _common.require_platform("measure-buf-exchange")
    _common.telemetry_begin(args)

    devices = jax.devices()
    n = len(devices)
    mesh = Mesh(np.array(devices), ("d",))

    x = np.zeros((n, n))  # per-pair sizes in bytes
    init_mib = min(args.init_mib, args.max_mib)  # the cap binds the init too
    for i in range(n):
        for j in range(n):
            if i != j or n == 1:
                x[i, j] = init_mib * MiB

    for it in range(args.iters):
        y = np.zeros((n, n))
        for i in range(n):
            for j in range(n):
                if x[i, j] == 0:
                    continue
                y[i, j] = measure_edge(mesh, n, i, j, int(x[i, j]), args.sub_iters)
        # multiplicative update toward the target time (the reference's
        # per-pair gradient step, measure_buf_exchange.cu:189-223)
        active = x > 0
        ratio = np.ones_like(x)
        ratio[active] = args.target / y[active]
        ratio = ratio.clip(0.5, 2.0)  # damp
        dx = (x * ratio - x).astype(np.int64)
        print_mat("x", x / MiB, lambda v: f"{v:.2f}")
        print_mat("y", y, lambda v: f"{v:.4e}")
        print_mat("dx", dx, lambda v: f"{int(v)}")
        # contended traversal at the current sizes: all pairs in flight in one
        # dispatch (the reference's latch-kernel batch start equalizes exactly
        # these concurrent copies, measure_buf_exchange.cu:120-159; TPU has no
        # per-collective event timers, so the per-pair y stays sequential and
        # the contention shows up in this total)
        print(
            f"y_concurrent {measure_matrix_concurrent(mesh, x.astype(np.int64), args.sub_iters):.4e}"
        )
        # a capped pair that is still UNDER the target cannot converge (the
        # size it needs is disallowed) — excuse it; an over-target pair can
        # always shrink, so it must still meet tolerance
        at_cap = (x >= args.max_mib * MiB) & (y < args.target)
        converged = np.all(
            (np.abs(y[active] - args.target) <= args.tol * args.target)
            | at_cap[active]
        )
        if converged:
            break
        x = (x + dx).clip(4096, args.max_mib * MiB) * active

    print("final x (MiB)")
    for i in range(n):
        print(" ".join(f"{x[i, j] / MiB:.2f}" for j in range(n)))
    _common.telemetry_end(args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
