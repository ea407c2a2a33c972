"""bench-alltoallv — all-pairs transfer bandwidth under traffic matrices.

Parity target: reference bin/bench_alltoallv.cu: raw ``cudaMemcpyPeerAsync``
all-pairs bandwidth under 5 traffic matrices — a real stencil matrix,
all-to-all 8 MiB, all-to-all 1 GiB, block-local 1 GiB, local 1 GiB + remote
100 M (bench_alltoallv.cu:139-187).  The TPU equivalent drives the same
matrices over single-edge ``lax.ppermute`` transfers (the ICI point-to-point
path).  For the stencil matrix it prints per-pair ``bw`` and ``time``
matrices (bench_alltoallv.cu:101-113); every matrix also reports the total
seconds for one full traversal.
"""

from __future__ import annotations

import argparse
import sys

import jax
import numpy as np
from jax.sharding import Mesh

from stencil_tpu.bin import _common


def measure_pairs(devices, comm: np.ndarray, n_iters: int):
    """Per-pair transfer times for a bytes matrix; returns (times, total)."""
    n = len(devices)
    mesh = Mesh(np.array(devices), ("d",))
    times = np.zeros_like(comm, dtype=float)
    total = 0.0
    for i in range(n):
        for j in range(n):
            if i == j or comm[i, j] == 0:
                continue
            dt = _common.measure_edge(mesh, n, i, j, int(comm[i, j]), n_iters)
            times[i, j] = dt
            total += dt
    return times, total


def stencil_matrix(n: int, face: int, edge: int, corner: int) -> np.ndarray:
    """A real halo-traffic matrix: 3D-decompose n devices, neighbor weights by
    direction class (the reference embeds a measured 6-GPU matrix,
    bench_alltoallv.cu:139-150; we generate the same structure for any n)."""
    from stencil_tpu.core.dim3 import Dim3
    from stencil_tpu.parallel.partition import RankPartition

    part = RankPartition(Dim3(64, 64, 64), n)
    dim = part.dim()
    comm = np.zeros((n, n))
    for a in range(n):
        ia = part.dimensionize(a)
        for b in range(n):
            if a == b:
                continue
            d = part.dimensionize(b) - ia
            # periodic wrap (partition.hpp:777-790)
            vals = []
            for ax in range(3):
                v = d[ax]
                if v != 0 and v == dim[ax] - 1:
                    v = -1
                if v != 0 and v == 1 - dim[ax]:
                    v = 1
                vals.append(v)
            d = Dim3(*vals)
            if d == Dim3(0, 0, 0) or d.any_gt(1) or d.any_lt(-1):
                continue
            nz = sum(1 for v in (d.x, d.y, d.z) if v != 0)
            comm[a, b] = {1: face, 2: edge, 3: corner}[nz]
    return comm


def main(argv=None) -> int:
    p = argparse.ArgumentParser("bench-alltoallv")
    p.add_argument("--iters", type=int, default=5)
    p.add_argument("--scale", type=float, default=1.0, help="scale all matrix sizes")
    _common.add_telemetry_flags(p)
    args = p.parse_args(argv)
    _common.require_platform("bench-alltoallv")
    _common.telemetry_begin(args)

    devices = jax.devices()
    n = len(devices)
    MiB = int(1024 * 1024 * args.scale)
    GiB = int(1024 * 1024 * 1024 * args.scale)

    mesh = Mesh(np.array(devices), ("d",))

    # 1) stencil matrix with per-pair bw/time report
    comm = stencil_matrix(n, face=8 * MiB, edge=MiB, corner=MiB // 4)
    times, total = measure_pairs(devices, comm, args.iters)
    print("bw")
    for i in range(n):
        print(" ".join(f"{(comm[i, j] / times[i, j]) if times[i, j] else 0:.4e}" for j in range(n)))
    print("time")
    for i in range(n):
        print(" ".join(f"{times[i, j]:.4e}" for j in range(n)))
    print("stencil")
    print(f"{total:e}")
    # the number this driver exists to produce: all pairs IN FLIGHT TOGETHER
    # (the reference batch-starts every pair on its own stream and times the
    # contended traversal, bench_alltoallv.cu:139-168); the sequential total
    # above is the uncontended baseline
    print("stencil concurrent")
    print(f"{_common.measure_matrix_concurrent(mesh, comm, args.iters):e}")

    # 2-5) aggregate-only matrices (bench_alltoallv.cu:173-187)
    ones = np.ones((n, n)) - np.eye(n)
    local = np.zeros((n, n))
    half = max(n // 2, 1)
    local[:half, :half] = 1
    local[half:, half:] = 1
    np.fill_diagonal(local, 0)
    remote = (ones - local).clip(0)
    for name, m in [
        ("All-to-all 8MiB", ones * 8 * MiB),
        ("All-to-all 1GiB", ones * GiB / max(n - 1, 1)),
        ("Local 1GiB", local * GiB / max(half, 1)),
        ("Local 1GiB Remote 100M", local * GiB / max(half, 1) + remote * 100 * MiB // 8),
    ]:
        _, total = measure_pairs(devices, m, args.iters)
        print(name)
        print(f"{total:e}")
        print(f"{name} concurrent")
        print(f"{_common.measure_matrix_concurrent(mesh, m.astype(np.int64), args.iters):e}")
    _common.telemetry_end(args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
