"""Probe24: where does the wavefront macro's time go at 512^3 m=16?
Times (a) the full macro, (b) kernel pass only, (c) x/y exchange only,
(d) slab permute+extend only — all self-permuted on one chip."""
import functools, time
import jax, jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P
from stencil_tpu.bin._common import host_round_trip_s
from stencil_tpu.core.radius import Radius
from stencil_tpu.models.jacobi import Jacobi3D
from stencil_tpu.ops.exchange import halo_exchange_shard
from stencil_tpu.ops.jacobi_pallas import (
    jacobi_shell_wavefront_step, pack_d2, yz_dist2_plane)
from stencil_tpu.ops.stream import (
    make_slab_extenders, permute_and_extend_z_slabs, prime_z_slabs)
from stencil_tpu.ops.stream_pass import lane_pad_width
from stencil_tpu.parallel.mesh import MESH_AXES

def main():
    rt = host_round_trip_s()
    n, m = 512, 16
    model = Jacobi3D(n, n, n, devices=jax.devices()[:1], kernel_impl="pallas",
                     pallas_path="wavefront", temporal_k=m)
    model.realize()
    dd = model.dd
    raw = dd.local_spec().raw_size()
    Xr, Yr, Zr = raw.x, raw.y, raw.z
    Zp = lane_pad_width(Zr)
    mesh_shape = (1, 1, 1)
    gsize = tuple(dd.size())
    shell = dd._shell_radius
    mesh = dd.mesh
    yext, xext = make_slab_extenders(Xr, Yr, m, mesh_shape)

    def shard_fn(body):
        def f(*args):
            return body(*args)
        return f

    def run(label, fn_body, args_builder, iters_per_call):
        spec = P(*MESH_AXES)
        nargs = len(args_builder)
        @functools.partial(jax.jit, static_argnums=0, donate_argnums=tuple(range(1, nargs+1)))
        def go(reps, *arrs):
            f = jax.shard_map(fn_body, mesh=mesh,
                              in_specs=tuple(spec for _ in arrs),
                              out_specs=tuple(spec for _ in arrs) if nargs > 1 else spec,
                              check_vma=False)
            def body(_, a):
                out = f(*a) if nargs > 1 else f(a[0])
                return tuple(out) if nargs > 1 else (out,)
            arrs = lax.fori_loop(0, reps, body, tuple(arrs))
            return arrs
        arrs = [jnp.zeros(s, jnp.float32) + 0.5 for s in args_builder]
        reps = 12
        out = go(reps, *arrs)
        jax.block_until_ready(out); float(jnp.sum(out[0][0,0,0:1]))
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            out = go(reps, *out)
            float(jnp.sum(out[0][0,0,0:1]))
            best = min(best, (time.perf_counter() - t0 - rt) / reps)
        eff = n**3 * iters_per_call / best / 1e6
        print(f"{label}: {best*1e3:.2f} ms/call ({eff:,.0f} Mcells/s-equivalent)", flush=True)
        return best

    # (b) kernel pass only (z-slab form, fixed slab input)
    d2 = pack_d2(yz_dist2_plane(-m, -m, (Yr, Zp), gsize), gsize)
    origin = jnp.zeros((3,), jnp.int32)
    def kernel_only(b, zs):
        out, zout = jacobi_shell_wavefront_step(
            b, m, origin, d2, gsize, interior_offset=m, z_slabs=zs,
            z_valid=Zr, alias=False)
        return out, zout
    run("kernel pass only (m=16)", kernel_only,
        [(Xr, Yr, Zp), (Xr, 2*m, Yr)], m)

    # (c) x/y exchange only
    def exch_only(b):
        return halo_exchange_shard(b, shell, mesh_shape, axes=(0, 1))
    run("x/y exchange only", exch_only, [(Xr, Yr, Zp)], m)

    # (d) slab permute + extend only
    def slabs_only(zout):
        zlo = permute_and_extend_z_slabs(zout, m, mesh_shape, yext, xext)
        return zlo[:, :2*m, :]
    run("slab permute+extend only", slabs_only, [(Xr, 2*m, Yr)], m)

    # (a) the full model macro for comparison
    steps = 96
    model.step(steps)
    float(jnp.sum(dd.get_curr(model.h)[0,0,0:1]))
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        model.step(steps)
        float(jnp.sum(dd.get_curr(model.h)[0,0,0:1]))
        best = min(best, (time.perf_counter() - t0 - rt) / steps)
    print(f"full wavefront model: {n**3/best/1e6:,.0f} Mcells/s "
          f"({best*m*1e3:.2f} ms/macro)", flush=True)

if __name__ == "__main__":
    main()
