"""chip_smoke.py — the quickest proof that the program still starts, compiles
and gives right answers on the chip.

    python chip_smoke.py

Drives the stencil main path once through the entry points a user calls
(``DistributedDomain`` -> ``realize()`` -> ``make_step(engine="stream")`` /
``run_step``; ``models.Jacobi3D``; ``models.AstarothSim``;
``stencil_tpu.bin.jacobi3d.main``) at the size its users run — 512^3 f32 per
chip — with default routes, default axes and compiled kernels, and checks
every result against a plain reference that imports nothing from
``stencil_tpu`` and runs outside any timed region.

One process; it drives every visible chip itself.  With one device it runs
legs A, B and E; with four it also runs the four-chip legs D and C on the
[2,2,1] mesh (first, while each device's peak-memory counter is still
clean).  Per leg it prints one JSON line: device, planned vs ran route and
depth, interpret, ladder descents and retries (both must be zero — a
descent is a failure here, not a recovery), shard placement, compile and
run seconds, the compile-cache directory, and the verdict.  Timings are
smoke output, not metrics.

Exit 0 and a last stdout line ``{"ok": true, "device": {...}}`` only when
every leg passed.  Anything but a ``tpu`` backend exits 2 before building
anything and prints no result.
"""

from __future__ import annotations

import gc
import json
import sys
import time
from functools import partial

#: tier-1's pinned bound for a compiled/interpreted pallas path against a
#: plain XLA formulation of the same update (tests/test_stream.py TOL,
#: tests/test_jacobi_pallas.py); each leg also reports whether the match
#: was bitwise
TOL = dict(rtol=1e-6, atol=1e-6)
#: AstarothSim wavefront vs per-step (tests/test_compiled_tpu.py)
ASTAROTH_ATOL = 1e-6
#: deepest temporal depth the planners pick (ops/jacobi_pallas._WRAP_MAX_K);
#: the user-kernel four-chip leg asks for a shell this wide
MAX_DEPTH = 16


# --- plain references: jax/numpy only, nothing from stencil_tpu ---------------


def field0(x, y, z):
    """Initial condition of the user-kernel legs, from global integer
    coordinates; a dyadic rational, so exact in f32 however it is fused."""
    import jax.numpy as jnp

    return ((x * 7 + y * 13 + z * 29) % 64).astype(jnp.float32) / 64.0


def ripple(q: int):
    """Analytic integer-valued field of quantity ``q`` (exact in f32 below
    2^24), distinct per quantity and per coordinate triple modulo 2^20."""

    def f(x, y, z):
        import jax.numpy as jnp

        return ((x * 1009 + y * 31 + z + q * 7919) % (1 << 20)).astype(jnp.float32)

    return f


def _global_coords(shape):
    import jax.numpy as jnp

    X, Y, Z = shape
    return (
        jnp.arange(X)[:, None, None],
        jnp.arange(Y)[None, :, None],
        jnp.arange(Z)[None, None, :],
    )


def ref_mean6(shape, steps: int, sharding):
    """``steps`` periodic mean-of-6 updates of ``field0`` on the global
    array, summed in the user kernel's own order (x+1, x-1, y+1, ...)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    def run():
        u = jnp.broadcast_to(field0(*_global_coords(shape)), shape)

        def body(_, u):
            return (
                jnp.roll(u, -1, 0) + jnp.roll(u, 1, 0)
                + jnp.roll(u, -1, 1) + jnp.roll(u, 1, 1)
                + jnp.roll(u, -1, 2) + jnp.roll(u, 1, 2)
            ) / 6.0

        return lax.fori_loop(0, steps, body, u)

    return jax.jit(run, out_shardings=sharding)()


def ref_jacobi(shape, steps: int, sharding):
    """``steps`` jacobi3d updates (reference bin/jacobi3d.cu) restated on
    the global array: field at 0.5; each step the mean of the six face
    neighbours (lower neighbour first along each axis), then the hot sphere
    (centre X/3, Y/2, Z/2, radius X/10, membership floor(dist) <= r, i.e.
    d^2 < (r+1)^2) clamped to 1 and the cold one (centre 2X/3) to 0."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    X, Y, Z = shape

    def run():
        x, y, z = _global_coords(shape)
        yz = (y - Y // 2) ** 2 + (z - Z // 2) ** 2
        in_r2 = (X // 10 + 1) ** 2
        hot = (x - X // 3) ** 2 + yz < in_r2
        cold = (x - X * 2 // 3) ** 2 + yz < in_r2

        def body(_, u):
            v = (
                jnp.roll(u, 1, 0) + jnp.roll(u, -1, 0)
                + jnp.roll(u, 1, 1) + jnp.roll(u, -1, 1)
                + jnp.roll(u, 1, 2) + jnp.roll(u, -1, 2)
            ) / 6.0
            return jnp.where(cold, 0.0, jnp.where(hot, 1.0, v))

        return lax.fori_loop(0, steps, body, jnp.full(shape, 0.5, jnp.float32))

    return jax.jit(run, out_shardings=sharding)()


def ripple_mismatches(arr, mesh, interior, lo, gsize, f) -> int:
    """Cells of the shell-carrying sharded array ``arr`` (interior AND
    shell, every shard) that differ from the analytic field ``f`` at their
    periodically wrapped global coordinate — counted on the device."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    names = mesh.axis_names
    raw = tuple(s // mesh.shape[a] for s, a in zip(arr.shape, names))

    def per_shard(block):
        c = [
            (lax.axis_index(a) * interior[i] - lo[i] + jnp.arange(raw[i])) % gsize[i]
            for i, a in enumerate(names)
        ]
        want = f(c[0][:, None, None], c[1][None, :, None], c[2][None, None, :])
        bad = jnp.sum(block != jnp.broadcast_to(want, raw).astype(block.dtype))
        return lax.psum(bad, names)

    fn = jax.shard_map(per_shard, mesh=mesh, in_specs=P(*names), out_specs=P())
    return int(jax.jit(fn)(arr))


def compare(got, want, rtol: float, atol: float) -> dict:
    """Verdict of one host array against its reference."""
    import numpy as np

    got, want = np.asarray(got), np.asarray(want)
    ok_shape = got.shape == want.shape and got.dtype == want.dtype
    finite = bool(np.isfinite(got).all())
    bitwise = ok_shape and bool(np.array_equal(got, want))
    err = float(np.abs(got - want).max()) if ok_shape else float("inf")
    close = bitwise or (ok_shape and bool(np.allclose(got, want, rtol=rtol, atol=atol)))
    return {
        "shape": list(got.shape), "dtype": str(got.dtype), "finite": finite,
        "bitwise": bitwise, "max_abs_err": err,
        "bound": {"rtol": rtol, "atol": atol},
        "ok": ok_shape and finite and close,
    }


# --- leg plumbing --------------------------------------------------------------


def _counters() -> dict:
    from stencil_tpu import telemetry
    from stencil_tpu.telemetry import names as tm

    c = telemetry.snapshot()["counters"]
    return {
        "descents": int(c.get(tm.LADDER_DESCENTS, 0)),
        "retries": int(c.get(tm.RETRY_ATTEMPTS, 0)),
    }


def _timed_twice(run) -> tuple:
    """Run the same dispatch twice: the first call compiles, the second
    does not.  Returns (compile seconds, run seconds)."""
    t0 = time.perf_counter()
    run()
    first = time.perf_counter() - t0
    t0 = time.perf_counter()
    run()
    second = time.perf_counter() - t0
    return max(first - second, 0.0), second


def _device_report(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "device_kind": d.device_kind, "count": len(devices)}


def _placement_report(dd, devices) -> dict:
    """Mesh dims, where each array's shards sit, and which coordinates (if
    any) placement scored distances with."""
    from stencil_tpu.parallel import topology

    shard_devs = [
        sorted(s.device.id for s in a.addressable_shards) for a in dd._curr.values()
    ]
    return {
        "mesh": list(dd.mesh_dim()),
        "shard_devices": shard_devs[0],
        "distinct_shard_devices": min(len(set(ids)) for ids in shard_devs),
        "coords": [topology.device_coords(d) for d in devices],
        "torus_dims": topology.torus_dims(devices),
    }


def _finish(leg: dict, before: dict, interpret: bool, expect_devices: int) -> dict:
    """Fold the cross-leg checks into ``leg['checks']`` and set ``ok``."""
    import jax

    after = _counters()
    leg["interpret"] = interpret
    leg["descent_counter"] = after["descents"] - before["descents"]
    leg["retry_counter"] = after["retries"] - before["retries"]
    leg["cache_dir"] = jax.config.jax_compilation_cache_dir
    checks = leg.setdefault("checks", {})
    checks["no_descents"] = not leg.get("descents") and leg["descent_counter"] == 0
    checks["no_retries"] = leg["retry_counter"] == 0
    checks["ran_is_planned"] = leg["ran"] == leg["planned"]
    place = leg.get("placement")
    if place is not None:
        checks["shards_on_distinct_devices"] = (
            place["distinct_shard_devices"] == expect_devices
        )
        if expect_devices == 4:
            checks["mesh_is_2x2x1"] = place["mesh"] == [2, 2, 1]
            if leg["device"]["platform"] == "tpu":
                # QAP placement scored real torus coordinates, not indices
                checks["placement_used_coords"] = place["torus_dims"] is not None
    leg["ok"] = all(checks.values())
    return leg


def mean6_kernel(views, info):
    """The README quickstart kernel: periodic 7-point average."""
    src = views["temperature"]
    return {
        "temperature": (
            src.sh(1, 0, 0) + src.sh(-1, 0, 0) + src.sh(0, 1, 0)
            + src.sh(0, -1, 0) + src.sh(0, 0, 1) + src.sh(0, 0, -1)
        ) / 6.0
    }


def _interior_sharding(dd):
    from jax.sharding import NamedSharding, PartitionSpec as P

    return NamedSharding(dd.mesh, P(*dd.mesh.axis_names))


# --- the legs --------------------------------------------------------------------


def leg_user_mean6(devices, shape, interpret: bool = False, name="A user-mean6"):
    """README-quickstart kernel through ``make_step(engine="stream")``: the
    exchange-free wrap route on one device; on several, a MAX_DEPTH-wide
    shell (``set_halo_multiplier``) feeding the exchanging wavefront."""
    import jax.numpy as jnp

    from stencil_tpu import DistributedDomain
    from stencil_tpu.ops.stream_plan import plan_stream

    before = _counters()
    leg = {"leg": name, "device": _device_report(devices), "global": list(shape)}
    dd = DistributedDomain(*shape)
    dd.set_radius(1)
    dd.set_devices(devices)
    if len(devices) > 1:
        per_chip = min(shape) // 2
        dd.set_halo_multiplier(min(MAX_DEPTH, per_chip // 4))
    h = dd.add_data("temperature", dtype=jnp.float32)
    dd.realize()
    dd.init_by_coords(h, field0)
    dd.block_until_ready()
    leg["placement"] = _placement_report(dd, devices)
    peaks = leg["peak_bytes_after_realize"] = [
        (d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices
    ]
    planned = plan_stream(dd, 1)
    step = dd.make_step(mean6_kernel, engine="stream", interpret=interpret)
    steps = 2 * planned["m"] + 1  # two macro dispatches and a remainder

    def run():
        dd.run_step(step, steps)
        dd.block_until_ready()

    leg["compile_s"], leg["run_s"] = _timed_twice(run)
    leg["steps"] = 2 * steps
    leg["planned"] = {k: planned[k] for k in ("route", "m", "z_slabs")}
    leg["ran"] = {k: step._stream_plan[k] for k in ("route", "m", "z_slabs")}
    leg["exchange_route"] = dd.exchange_route()
    leg["descents"] = [(r, c.value) for r, c in step._resilience.descents]
    got = dd.quantity_to_host(h)
    sharding = _interior_sharding(dd)
    del dd, step
    gc.collect()
    leg["verdict"] = compare(got, ref_mean6(shape, 2 * steps, sharding), **TOL)
    leg["checks"] = {"matches_reference": leg["verdict"]["ok"]}
    if len(devices) > 1:
        leg["checks"]["route_is_wavefront"] = leg["ran"]["route"] == "wavefront"
        if all(p is not None for p in peaks):
            # realize() allocated sharded: device 0 peaked at its share, not
            # at a global array built there first (readable only while this
            # is the first leg to touch the devices — see run_legs)
            leg["checks"]["device0_holds_only_its_share"] = (
                peaks[0] <= 1.25 * sorted(peaks)[1]
            )
    return _finish(leg, before, interpret, len(devices))


def leg_jacobi3d(devices, shape, interpret: bool = False, name="B jacobi3d",
                 main_argv=None):
    """``Jacobi3D(kernel_impl="pallas")`` on its default route, then (when
    ``main_argv`` is given) once through ``bin.jacobi3d.main`` in-process —
    the driver takes every visible device, so the caller passes it only
    where those are this leg's devices."""
    import contextlib
    import io

    from stencil_tpu.models.jacobi import Jacobi3D

    before = _counters()
    leg = {"leg": name, "device": _device_report(devices), "global": list(shape)}
    model = Jacobi3D(*shape, devices=devices, kernel_impl="pallas", interpret=interpret)
    model.realize()
    model.block_until_ready()
    leg["placement"] = _placement_report(model.dd, devices)

    def plan_of():
        path = model._pallas_path
        if path == "wrap":
            return {"route": path, "depth": model._wrap_k}
        if path == "wavefront":
            return {
                "route": path,
                "depth": getattr(model, "_wavefront_depth", model._wavefront_m),
                "z_slabs": model._wavefront_z_slabs,
                "z_ring": model._wavefront_z_ring,
            }
        return {"route": path, "depth": 1}

    planned = plan_of()
    steps = 2 * planned["depth"] + 1

    def run():
        model.step(steps)
        model.block_until_ready()

    leg["compile_s"], leg["run_s"] = _timed_twice(run)
    leg["steps"] = 2 * steps
    leg["planned"], leg["ran"] = planned, plan_of()
    leg["exchange_route"] = model.dd.exchange_route()
    leg["descents"] = [(r, c.value) for r, c in model._ladder.descents]
    got = model.temperature()
    sharding = _interior_sharding(model.dd)
    del model
    gc.collect()
    leg["verdict"] = compare(got, ref_jacobi(shape, 2 * steps, sharding), **TOL)
    leg["checks"] = {
        "matches_reference": leg["verdict"]["ok"],
        # the forcing really fired: both clamps are present
        "spheres_active": float(got.max()) == 1.0 and float(got.min()) == 0.0,
        "route_is_default": leg["ran"]["route"]
        == ("wrap" if len(devices) == 1 else "wavefront"),
    }
    del got
    if main_argv is not None:
        from stencil_tpu.bin import jacobi3d

        out = io.StringIO()
        t0 = time.perf_counter()
        with contextlib.redirect_stdout(out):
            rc = jacobi3d.main(main_argv)
        leg["main"] = {
            "argv": main_argv, "rc": rc, "seconds": time.perf_counter() - t0,
            "csv": out.getvalue().strip().splitlines()[-1:],
        }
        row = (leg["main"]["csv"] or [""])[0].split(",")
        leg["checks"]["main_ran"] = (
            rc == 0
            and len(row) == 9
            and row[0] == "jacobi3d"
            and [int(v) for v in row[4:7]] == list(shape)
            and float(row[7]) > 0.0
        )
        gc.collect()
    return _finish(leg, before, interpret, len(devices))


def leg_ripple(devices, shape, interpret: bool = False, name="C ripple-4"):
    """Radius 3 in all 26 directions, two quantities: fill the interiors
    from global coordinates, ``exchange()``, and compare every cell of every
    shard's shell with the analytic field on the device."""
    import jax.numpy as jnp

    from stencil_tpu import DistributedDomain, Radius

    del interpret  # the exchange picks its own blend path from the backend
    before = _counters()
    leg = {"leg": name, "device": _device_report(devices), "global": list(shape)}
    dd = DistributedDomain(*shape)
    dd.set_radius(Radius.constant(3))
    dd.set_devices(devices)
    hs = [dd.add_data(f"q{i}", dtype=jnp.float32) for i in range(2)]
    t0 = time.perf_counter()
    dd.realize()  # compiles the exchange eagerly
    leg["compile_s"] = time.perf_counter() - t0
    for q, h in enumerate(hs):
        dd.init_by_coords(h, ripple(q))
    dd.block_until_ready()
    leg["placement"] = _placement_report(dd, devices)
    leg["planned"] = leg["ran"] = {"exchange_route": dd.exchange_route()}
    leg["descents"] = []
    t0 = time.perf_counter()
    dd.exchange()
    dd.block_until_ready()
    leg["run_s"] = time.perf_counter() - t0
    spec = dd.local_spec()
    interior = tuple(spec.sz)
    cells = len(devices) * spec.raw_size().flatten()
    shell = cells - len(devices) * spec.sz.flatten()
    bad = [
        ripple_mismatches(dd.get_curr(h), dd.mesh, interior, (3, 3, 3), shape, ripple(q))
        for q, h in enumerate(hs)
    ]
    leg["verdict"] = {
        "cells_compared_per_quantity": cells, "shell_cells_per_quantity": shell,
        "mismatches": bad, "bound": "exact", "ok": not any(bad),
    }
    leg["checks"] = {"shell_exact": not any(bad)}
    del dd
    gc.collect()
    return _finish(leg, before, False, len(devices))


def leg_astaroth(devices, n: int, interpret: bool = False, name="E astaroth-8q"):
    """``AstarothSim(n^3, 8 quantities, schedule="wavefront")`` against its
    own ``schedule="per-step"`` — one model resident at a time, the first's
    fields held on the host."""
    from stencil_tpu.models.astaroth import AstarothSim

    before = _counters()
    leg = {"leg": name, "device": _device_report(devices), "global": [n, n, n]}
    steps = 7  # two 3-level macros and a remainder

    def run_model(schedule):
        sim = AstarothSim(n, n, n, num_quantities=8, devices=devices,
                          kernel_impl="pallas", schedule=schedule, interpret=interpret)
        sim.realize()
        planned = dict(sim._step._stream_plan)

        def run():
            sim.step(steps)
            sim.block_until_ready()

        return sim, planned, _timed_twice(run)

    ref, _, (leg["reference_compile_s"], leg["reference_run_s"]) = run_model("per-step")
    want = [ref.field(i) for i in range(8)]
    del ref
    gc.collect()
    sim, planned, (leg["compile_s"], leg["run_s"]) = run_model("wavefront")
    keys = ("route", "m", "grouping")
    leg["steps"] = 2 * steps
    leg["planned"] = {k: planned[k] for k in keys}
    leg["ran"] = {k: sim._step._stream_plan[k] for k in keys}
    leg["exchange_route"] = sim.dd.exchange_route()
    leg["descents"] = [(r, c.value) for r, c in sim._step._resilience.descents]
    leg["placement"] = _placement_report(sim.dd, devices)
    verdicts = [compare(sim.field(i), want[i], 0.0, ASTAROTH_ATOL) for i in range(8)]
    del sim
    gc.collect()
    leg["verdict"] = {
        "fields": 8, "bitwise": all(v["bitwise"] for v in verdicts),
        "finite": all(v["finite"] for v in verdicts),
        "max_abs_err": max(v["max_abs_err"] for v in verdicts),
        "bound": verdicts[0]["bound"], "ok": all(v["ok"] for v in verdicts),
    }
    leg["checks"] = {
        "matches_reference": leg["verdict"]["ok"],
        "route_is_wavefront": leg["ran"]["route"] == "wavefront" and leg["ran"]["m"] == 3,
    }
    return _finish(leg, before, interpret, len(devices))


# --- driver --------------------------------------------------------------------------


def run_legs(devices, n: int = 512, interpret: bool = False, emit=print) -> list:
    """Every leg the visible devices can run, in order; a leg that raises is
    recorded as failed and the rest still run."""
    one = list(devices[:1])
    four = list(devices[:4]) if len(devices) >= 4 else None
    plan = []
    if four is not None:
        g4 = (2 * n, 2 * n, n)
        main4 = [str(v) for v in g4] + ["--no-weak-scale", "--iters", "3"]
        # four-chip legs first: each device's peak-memory counter is still
        # clean, so "nothing global was first built on device 0" is readable
        plan += [
            partial(leg_user_mean6, four, g4, interpret, name="D user-mean6-4"),
            partial(leg_jacobi3d, four, g4, interpret, name="D jacobi3d-4",
                    main_argv=main4 if len(devices) == 4 and not interpret else None),
            partial(leg_ripple, four, g4, interpret, name="C ripple-4"),
        ]
    main1 = [str(n)] * 3 + ["--iters", "3"]
    plan += [
        partial(leg_user_mean6, one, (n, n, n), interpret, name="A user-mean6"),
        partial(leg_jacobi3d, one, (n, n, n), interpret, name="B jacobi3d",
                main_argv=main1 if len(devices) == 1 and not interpret else None),
        partial(leg_astaroth, one, n, interpret, name="E astaroth-8q"),
    ]
    legs = []
    for run_leg in plan:
        t0 = time.perf_counter()
        try:
            leg = run_leg()
        except Exception as e:  # noqa: BLE001 — record, keep going, fail at exit
            import traceback

            traceback.print_exc()
            leg = {"leg": run_leg.keywords["name"], "ok": False,
                   "error": f"{type(e).__name__}: {e}"[:2000]}
        leg["leg_seconds"] = round(time.perf_counter() - t0, 3)
        legs.append(leg)
        emit(json.dumps(leg, default=str))
        gc.collect()
    return legs


def main(argv=None) -> int:
    if argv:
        print(f"chip_smoke.py takes no arguments (got {argv})", file=sys.stderr)
        return 2
    import jax

    backend = jax.default_backend()
    if backend != "tpu":
        print(
            f"chip_smoke: needs a tpu backend, jax found platform {backend!r} "
            f"({len(jax.devices())} device(s)) — nothing was built",
            file=sys.stderr,
        )
        return 2
    from stencil_tpu.bin import _common
    from stencil_tpu.parallel.qap import _native

    _common.require_platform("chip_smoke")  # logs platform / kind / count / interpret
    devices = jax.devices()
    legs = run_legs(devices, n=512, interpret=False)
    multichip = "ran" if len(devices) >= 4 else f"skipped: {len(devices)} device"
    ok = bool(legs) and all(leg["ok"] for leg in legs)
    print(json.dumps({
        "summary": [(leg["leg"], leg["ok"]) for leg in legs],
        "multichip": multichip,
        "qap_solver": "native" if _native() is not None else "python",
        "cache_dir": jax.config.jax_compilation_cache_dir,
        "jax": jax.__version__,
        "claim": None,
    }))
    print(json.dumps({
        "ok": ok,
        "device": {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
        },
    }))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
