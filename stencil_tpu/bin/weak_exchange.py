"""weak-exchange — weak.cu variant timing the whole loop with one wall clock.

Parity target: reference bin/weak_exchange.cu (one elapsed wall time over all
iterations instead of per-phase stats; weak_exchange.cu:125-179).  Row layout
matches weak.cu's bytes columns with a single trailing elapsed-seconds field:

    weak,<methods>,x,y,z,s,MPI(B),Colocated(B),cudaMemcpyPeer(B),direct(B),
    iters,gpus,nodes,ranks,elapsed
"""

from __future__ import annotations

import sys
import time

import jax
import jax.numpy as jnp

from stencil_tpu.bin import _common
from stencil_tpu.bin.weak import build_parser
from stencil_tpu.core.radius import Radius
from stencil_tpu.domain import DistributedDomain
from stencil_tpu.models.jacobi import weak_scaled_size
from stencil_tpu.utils.config import MethodFlags


def main(argv=None) -> int:
    args = build_parser("weak-exchange", overlap_flags=False).parse_args(argv)
    _common.require_platform("weak-exchange")
    args.trivial = args.naive
    _common.telemetry_begin(args)
    devs = len(jax.devices())
    x = weak_scaled_size(args.x, devs)
    y = weak_scaled_size(args.y, devs)
    z = weak_scaled_size(args.z, devs)
    x, y, z = _common.fit_to_mesh(x, y, z, Radius.constant(3))

    dd = DistributedDomain(x, y, z)
    dd.set_methods(_common.parse_methods(args))
    dd.set_radius(Radius.constant(3))
    dd.set_placement(_common.parse_strategy(args))
    _common.apply_exchange_route(args, dd)
    for i in range(4):
        dd.add_data(f"d{i}", dtype=jnp.float32)
    dd.realize()

    # one warm call so jit compilation stays out of the wall clock
    dd.exchange()
    dd.swap()
    dd.block_until_ready()

    t0 = time.perf_counter()
    for _ in range(args.n_iters):
        dd.exchange()
        dd.swap()
    dd.block_until_ready()
    elapsed = time.perf_counter() - t0

    if jax.process_index() == 0:
        ranks, dev_count = _common.ranks_and_devcount()
        print(
            f"weak,{_common.method_str(args)},{x},{y},{z},{x * y * z},"
            f"{dd.exchange_bytes_for_method(MethodFlags.CudaMpi)},0,0,0,"
            f"{args.n_iters},{ranks * dev_count},{ranks},{ranks},{elapsed:e}"
        )
    _common.telemetry_end(args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
