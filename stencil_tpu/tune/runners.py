"""Concrete autotune searches for the shipped workloads.

Each runner builds the workload key, computes the STATIC pick under
``tune.disabled()`` (the fallback a search must beat — never its own cached
result), generates the candidate space, and hands ``tune.ensure`` a
``build_run`` that compiles/executes the candidate on the device under the
burst-aware protocol.  All candidate state (models, buffers) stays alive for
the whole search — the alternating rounds require every candidate resident
in one process (PERF_NOTES "Measurement discipline").
"""

from __future__ import annotations

from functools import partial
from typing import Optional

from stencil_tpu import tune
from stencil_tpu.tune import space
from stencil_tpu.tune.key import WorkloadKey, chip_kind
from stencil_tpu.tune.trial import TuneReport


def autotune_jacobi_wrap(
    x: int,
    y: int,
    z: int,
    dtype=None,
    interpret: bool = False,
    reps: int = 3,
    ks=None,
    rt: Optional[float] = None,
) -> TuneReport:
    """Tune the single-device wrap kernel's temporal depth ``k`` for this
    chip/shape/dtype.  Candidates span the measured plateau grid plus the
    static ``choose_temporal_k`` pick; a Mosaic VMEM_OOM prunes the failing
    depth and everything deeper."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from stencil_tpu.ops.jacobi_pallas import choose_temporal_k, jacobi_wrap_step

    dtype = jnp.dtype(dtype or jnp.float32)
    key = WorkloadKey(
        chip=chip_kind(),
        domain=(x, y, z),
        dtype=dtype.name,
        n_fields=1,
        mesh=(1, 1, 1),
        radius=1,
        route="jacobi-wrap",
    )
    with tune.disabled():
        static_k = choose_temporal_k((x, y, z), dtype.itemsize)
    candidates, prefiltered = space.jacobi_wrap_space(
        (x, y, z), dtype.itemsize, static_k, ks=ks, dtype=dtype
    )
    # trial buffers allocate lazily at the FIRST candidate build needing
    # them (one per storage dtype — the bf16 twin streams narrow planes):
    # a warm-cache call must not touch device memory at all
    state = {}

    def build_run(cand):
        storage = cand.get("storage_dtype", "native")
        bdt = jnp.bfloat16 if storage == "bf16" else dtype
        if storage not in state:
            state[storage] = jnp.full((x, y, z), 0.5, bdt)
        block = state[storage]
        k = cand["k"]
        f32_acc = storage == "bf16"

        @partial(jax.jit, static_argnums=1)
        def steps(b, n):
            blocked, rem = divmod(n, k)
            if blocked:
                b = lax.fori_loop(
                    0,
                    blocked,
                    lambda _, bb: jacobi_wrap_step(
                        bb, interpret=interpret, k=k, f32_accumulate=f32_acc
                    ),
                    b,
                )
            if rem:
                b = jacobi_wrap_step(
                    b, interpret=interpret, k=rem, f32_accumulate=f32_acc
                )
            return b

        def run(n):
            steps(block, n).block_until_ready()

        return run

    return tune.ensure(
        key,
        candidates,
        build_run,
        depth_key="k",
        static={"k": static_k, "storage_dtype": "native"},
        reps=reps,
        rt=rt,
        prefiltered=prefiltered,
    )


def autotune_jacobi_wavefront(
    x: int,
    y: int,
    z: int,
    dtype=None,
    devices=None,
    interpret: bool = False,
    reps: int = 3,
    ms=None,
    rt: Optional[float] = None,
    strategy=None,  # placement strategy — MUST match the model the caller
    # will build (a different strategy can place a different mesh, which
    # re-keys the workload and orphans the search's cache entry)
) -> TuneReport:
    """Tune the multi-device jacobi wavefront: depth ``m`` (== the halo
    multiplier), ``input_output_aliases`` on/off, and the z-ring vs padded
    layout.  Each candidate is a fully realized ``Jacobi3D`` — expensive by
    design (this is the re-qualification pass), cached so it runs once per
    workload/toolchain."""
    import jax
    import jax.numpy as jnp

    from stencil_tpu.models.jacobi import Jacobi3D

    dtype = jnp.dtype(dtype or jnp.float32)

    def make_model(temporal_k="auto", alias=None, z_ring=None,
                   storage_dtype=None):
        kwargs = {} if strategy is None else {"strategy": strategy}
        return Jacobi3D(
            x,
            y,
            z,
            devices=devices,
            dtype=dtype,
            kernel_impl="pallas",
            pallas_path="wavefront",
            temporal_k=temporal_k,
            interpret=interpret,
            wavefront_alias=alias,
            z_ring=z_ring,
            storage_dtype=storage_dtype,
            **kwargs,
        )

    probe = make_model()
    key = probe.dd.tune_key("jacobi-wavefront")
    with tune.disabled():
        static_m = probe._plan_wavefront()  # stashes _wavefront_plan_info
    info = probe._wavefront_plan_info
    # z-ring needs z-slab mode plus a lane-aligned shard z interior
    z_ring_eligible = (
        getattr(probe, "_wavefront_z_planned", False)
        and info["n"][2] % 128 == 0
    )
    from stencil_tpu.ops.jacobi_pallas import bf16_supported

    candidates, prefiltered = space.jacobi_wavefront_space(
        static_m,
        # structural caps only (a shard must fill an m-wide halo from valid
        # cells, and the kernel's periodic-coordinate rem needs 2m < the
        # global extent) — deeper than the static shell-traffic heuristic
        # is allowed, measuring past it is the point
        depth_cap=min(info["n_min"], (min(x, y, z) - 1) // 2),
        z_ring_eligible=z_ring_eligible,
        static_z_ring=True,
        ms=ms,
        bf16_ok=bf16_supported([dtype]),
    )
    models = {}

    def build_run(cand):
        model = make_model(
            temporal_k=cand["m"], alias=cand["alias"], z_ring=cand.get("z_ring"),
            storage_dtype=cand.get("storage_dtype"),
        )
        model.realize()
        models[space.candidate_label(cand)] = model  # keep resident

        def run(n):
            model.step(n)
            model.block_until_ready()

        return run

    report = tune.ensure(
        key,
        candidates,
        build_run,
        depth_key="m",
        static={
            "m": static_m,
            "halo_multiplier": static_m,
            "alias": False,
            "z_ring": z_ring_eligible,
            "storage_dtype": "native",
        },
        reps=reps,
        rt=rt,
        prefiltered=prefiltered,
    )
    models.clear()  # free candidate HBM before the caller builds the real model
    return report


def autotune_exchange(
    dd,
    reps: int = 3,
    rt: Optional[float] = None,
) -> TuneReport:
    """Tune the halo exchange's z-sweep route (direct vs the packed z-shell
    routes — ops/exchange.py ``EXCHANGE_ROUTES``) for a REALIZED domain.
    Each candidate is a non-donating exchange compiled over the domain's
    live buffers, looped device-side (the ``exchange_many`` protocol) and
    measured under the burst-aware alternating rounds; the domain's state is
    never advanced (exchanging is idempotent on a filled domain).  The
    winner feeds the very next ``realize()`` of this workload via the
    persistent cache — ``DistributedDomain._resolve_exchange_route``
    consults it, with ``direct`` as the static cold-cache fallback."""
    import jax
    from functools import partial as _partial

    from jax import lax

    key = dd.tune_key("exchange")
    candidates, prefiltered = space.exchange_space(dd)
    fns = {}  # keep every candidate's executable resident for the rounds

    def build_run(cand):
        route = cand["exchange_route"]
        fn = dd.make_exchange_route_fn(route, donate=False)
        fns[route] = fn

        @_partial(jax.jit, static_argnums=1)
        def many(arrays, s):
            return lax.fori_loop(0, s, lambda _, a: fn(a), arrays)

        def run(n):
            jax.block_until_ready(many(dd._curr, n))

        return run

    report = tune.ensure(
        key,
        candidates,
        build_run,
        depth_key=None,
        static={"exchange_route": "direct"},
        reps=reps,
        rt=rt,
        prefiltered=prefiltered,
    )
    fns.clear()
    return report


def autotune_stream(
    dd,
    kernel,
    x_radius: int = 1,
    separable: bool = False,
    interpret: bool = False,
    reps: int = 3,
    rt: Optional[float] = None,
) -> TuneReport:
    """Tune the generic stream engine's plan (route, depth, alias, overlap,
    fused halo) for a REALIZED domain + user kernel.  Trials run
    non-donating steps over the
    domain's live buffers (the domain state is never advanced), so the
    tuned plan feeds the very next ``make_step(engine="stream")`` on the
    same process via the cache."""
    import jax

    from stencil_tpu.ops.stream import _build_stream_step
    from stencil_tpu.ops.stream_plan import plan_stream, resolve_stream_plan

    key = dd.tune_key("stream")
    with tune.disabled():
        static_plan = plan_stream(dd, x_radius, "auto", separable)
    candidates, prefiltered = space.stream_space(
        dd, x_radius, separable, static_plan
    )

    def build_run(cand):
        plan = dict(cand)
        plan.pop("halo_multiplier", None)
        if "alias" in plan:
            # candidate builds must be forcible — the alias A/B has to
            # compile two DIFFERENT kernels even under STENCIL_STREAM_ALIAS
            # (the marker stays out of the persisted config: `cand` wins)
            plan["alias_forced"] = True
        if "overlap" in plan:
            # same for the overlap A/B under STENCIL_STREAM_OVERLAP: the
            # off and split candidates must build their own schedules
            plan["overlap_forced"] = True
        if "halo" in plan:
            # and for the fused-halo A/B under STENCIL_STREAM_HALO
            plan["halo_forced"] = True
        step = _build_stream_step(
            dd, kernel, x_radius,
            resolve_stream_plan(dd, kernel, x_radius, plan, interpret),
            interpret, donate=False,
        )

        def run(n):
            jax.block_until_ready(step(dd._curr, n))

        return run

    static = dict(static_plan)
    static.setdefault("halo_multiplier", static.get("m", 1))
    static.setdefault("overlap", "off")
    static.setdefault("halo", "array")
    return tune.ensure(
        key,
        candidates,
        build_run,
        depth_key="m",
        static=static,
        reps=reps,
        rt=rt,
        prefiltered=prefiltered,
    )
