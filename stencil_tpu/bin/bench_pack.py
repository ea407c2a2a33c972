"""bench-pack — pack/unpack kernel bandwidth.

Parity target: reference bin/bench_pack.cu: for a 512^3 float quantity with
radius 3, time packing/unpacking the x, y, and z face slabs on one chip
(bench_pack.cu:91-107).  Output format matches the reference
(``<ext> <dir> <bytes> <packTime> <unpackTime>``), plus a GB/s column (the
BASELINE.md metric).  ``--backend pallas`` uses the explicit-DMA Pallas
kernels; ``xla`` (default) the fused slice/concat path.
"""

from __future__ import annotations

import argparse
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from stencil_tpu.core.dim3 import Dim3
from stencil_tpu.core.geometry import LocalSpec
from stencil_tpu.core.radius import Radius
from stencil_tpu.ops.pack import (
    make_pack_fn,
    make_pack_fn_pallas,
    make_unpack_fn,
    make_unpack_fn_pallas,
)


def bench(sz: Dim3, direction: Dim3, n_iters: int, backend: str, interpret: bool):
    """Returns (bytes, pack_s_per_iter, unpack_s_per_iter)."""
    spec = LocalSpec.make(sz, Dim3(0, 0, 0), Radius.constant(3))
    raw = tuple(spec.raw_size())
    rng = np.random.default_rng(0)
    block = jnp.asarray(rng.random(raw), dtype=jnp.float32)

    if backend == "pallas":
        pack, plan = make_pack_fn_pallas(spec, [direction], jnp.float32, interpret=interpret)
        unpack, _ = make_unpack_fn_pallas(spec, [direction], jnp.float32, interpret=interpret)
        packed = pack(block)
        jax.block_until_ready(packed)

        def run_pack():
            jax.block_until_ready(pack(block))

        def run_unpack():
            jax.block_until_ready(unpack(block, packed))

    else:
        pack, plan = make_pack_fn(spec, [direction], [jnp.float32])
        unpack, _ = make_unpack_fn(spec, [direction], [jnp.float32])
        packed = pack([block])
        jax.block_until_ready(packed)
        # unpack donates its blocks; chain them so the buffer is reused in
        # place and the timed loop measures only the halo scatter
        state = {"blocks": [block + 0]}

        def run_pack():
            jax.block_until_ready(pack([block]))

        def run_unpack():
            state["blocks"] = unpack(packed, state["blocks"])
            jax.block_until_ready(state["blocks"])

    run_pack()
    run_unpack()  # compile both outside timing
    t0 = time.perf_counter()
    for _ in range(n_iters):
        run_pack()
    pack_t = (time.perf_counter() - t0) / n_iters
    t0 = time.perf_counter()
    for _ in range(n_iters):
        run_unpack()
    unpack_t = (time.perf_counter() - t0) / n_iters
    return plan.size, pack_t, unpack_t


def bench_roundtrip(sz: Dim3, direction: Dim3, n_iters: int, inner: int, backend: str, interpret: bool, rt: float):
    """pack->unpack round trips, ``inner`` per device dispatch with the host
    round trip subtracted — the protocol for hosts where a per-call sync
    would swamp the kernel (see bench.py).  Returns
    (bytes, seconds per round trip)."""
    from functools import partial

    from jax import lax

    spec = LocalSpec.make(sz, Dim3(0, 0, 0), Radius.constant(3))
    raw = tuple(spec.raw_size())
    rng = np.random.default_rng(0)
    block = jnp.asarray(rng.random(raw), dtype=jnp.float32)

    if backend == "pallas":
        pack, plan = make_pack_fn_pallas(spec, [direction], jnp.float32, interpret=interpret)
        unpack, _ = make_unpack_fn_pallas(spec, [direction], jnp.float32, interpret=interpret)

        def one(b):
            return unpack(b, pack(b))

    else:
        pack, plan = make_pack_fn(spec, [direction], [jnp.float32])
        unpack, _ = make_unpack_fn(spec, [direction], [jnp.float32])

        def one(b):
            return unpack(pack([b]), [b])[0]

    @partial(jax.jit, donate_argnums=0, static_argnums=1)
    def loop(b, s):
        return lax.fori_loop(0, s, lambda _, x: one(x), b)

    from stencil_tpu.bin import _common

    state = {"b": block}

    def run(k):
        state["b"] = loop(state["b"], k)
        float(jnp.sum(state["b"][0, 0, 0:1]))  # force completion

    # auto-scaled inner: rt subtraction can never clamp to 0.0, and every
    # timed dispatch reuses the executable warmed at the SAME static count
    samples, _ = _common.timed_inner_loop(run, inner, rt, max(n_iters, 3))
    return plan.size, min(samples)


def main(argv=None) -> int:
    p = argparse.ArgumentParser("bench-pack")
    p.add_argument("--iters", type=int, default=30)
    p.add_argument("--size", type=int, default=512)
    p.add_argument("--backend", choices=["xla", "pallas"], default="xla")
    p.add_argument(
        "--interpret",
        action="store_true",
        help="run pallas kernels in interpreter mode (CPU testing)",
    )
    p.add_argument(
        "--inner",
        type=int,
        default=1,
        help="pack+unpack round trips per device dispatch (use >1 where the "
        "host sync is slow; prints roundtrip time instead of pack/unpack)",
    )
    from stencil_tpu.bin import _common

    _common.add_telemetry_flags(p)
    args = p.parse_args(argv)
    _common.require_platform("bench-pack")
    _common.telemetry_begin(args)

    ext = Dim3(args.size, args.size, args.size)
    if args.inner > 1:
        rt = _common.host_round_trip_s()
        for d in (Dim3(1, 0, 0), Dim3(0, 1, 0), Dim3(0, 0, 1)):
            nbytes, rt_t = bench_roundtrip(
                ext, d, max(args.iters, 3), args.inner, args.backend, args.interpret, rt
            )
            gbps = 2 * nbytes / rt_t / 1e9  # payload packed + unpacked
            print(f"{ext} {d} {nbytes} roundtrip {rt_t:g} {gbps:.2f}GB/s")
        _common.telemetry_end(args)
        return 0
    for d in (Dim3(1, 0, 0), Dim3(0, 1, 0), Dim3(0, 0, 1)):
        nbytes, pack_t, unpack_t = bench(ext, d, args.iters, args.backend, args.interpret)
        gbps = nbytes / min(pack_t, unpack_t) / 1e9
        print(f"{ext} {d} {nbytes} {pack_t:g} {unpack_t:g} {gbps:.2f}GB/s")
    _common.telemetry_end(args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
