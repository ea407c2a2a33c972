"""pingpong — point-to-point latency/bandwidth sweep.

Parity target: reference bin/pingpong.cu: MPI host-buffer pingpong between
node pairs for sizes 2^min..2^max bytes (pingpong.cu:56-99).  The TPU-native
equivalent measures a chip<->chip round trip: a paired ``lax.ppermute``
(dev0 -> dev1 -> dev0) over the device mesh — the fabric the halo exchange
rides — for the same size sweep.  With one device the permute wraps to self
(the intra-chip copy path).  Output: one row per device pair,
one column per size:

    <src>-<dst> <t(2^min)> <t(2^min+1)> ...
"""

from __future__ import annotations

import argparse
import sys
import time

import jax

from stencil_tpu.bin import _common
from jax import shard_map
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def pingpong_times(devices, min_n: int, max_n: int, n_iters: int):
    """For each adjacent device pair, time a there-and-back single-edge
    ppermute (src -> dst -> src) per message size."""
    n_dev = len(devices)
    mesh = Mesh(np.array(devices), ("d",))

    def make_rt(src: int, dst: int, n_elems: int):
        sharding = NamedSharding(mesh, P("d"))

        @jax.jit
        def rt(x):
            def f(blk):
                fwd = lax.ppermute(blk, "d", [(src, dst)])
                return lax.ppermute(fwd, "d", [(dst, src)])

            return shard_map(f, mesh=mesh, in_specs=P("d"), out_specs=P("d"))(x)

        x = jax.device_put(jnp.zeros((n_elems * n_dev,), jnp.float32), sharding)
        return rt, x

    rows = []
    for pair in range(max(n_dev - 1, 1)):
        src, dst = pair, (pair + 1) % n_dev
        times = []
        for p in range(min_n, max_n + 1):
            nbytes = 1 << p
            rt, x = make_rt(src, dst, max(nbytes // 4, 1))
            rt(x).block_until_ready()  # compile
            t0 = time.perf_counter()
            for _ in range(n_iters):
                x = rt(x)
            x.block_until_ready()
            times.append((time.perf_counter() - t0) / n_iters)
        rows.append((f"{devices[src].id}-{devices[dst].id}", times))
    return rows


def main(argv=None) -> int:
    p = argparse.ArgumentParser("pingpong")
    p.add_argument("ranks_per_node", type=int, nargs="?", default=1)
    p.add_argument("--min", type=int, default=0, help="log2 of smallest message")
    p.add_argument("--max", type=int, default=27, help="log2 of largest message")
    p.add_argument("--iters", type=int, default=30)
    _common.add_telemetry_flags(p)
    args = p.parse_args(argv)
    _common.require_platform("pingpong")
    _common.telemetry_begin(args)

    rows = pingpong_times(jax.devices(), args.min, args.max, args.iters)
    for name, times in rows:
        print(name + " " + " ".join(f"{t:e}" for t in times))
    _common.telemetry_end(args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
