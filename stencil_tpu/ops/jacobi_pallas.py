"""Pallas plane-streaming 7-point Jacobi kernel — the flagship fast path.

XLA compiles the 6-shifted-slice Jacobi update to ~6 HBM reads of the block
per iteration (each shifted operand is re-read; no stencil reuse), measured at
~5-7.5 Gcells/s on v5e for 512^3 — far below HBM bandwidth.  This kernel
streams x-planes through VMEM with a 2-plane ring buffer so every plane is
read from HBM ONCE and written ONCE (~8 B/cell), the classic stencil
optimization (reference analog: the fused stencil kernels of jacobi3d.cu:
65-108, which get the same effect from the GPU cache hierarchy).

Grid: ``X + 1`` sequential steps over the raw block's x-planes.  At step i the
pipeline delivers input plane ``min(i, X-1)``; VMEM scratch holds the two
previous planes; step i >= 2 computes output plane ``i-1`` from planes
``i-2, i-1, i``.  Steps 0 and X pass the x-halo planes through unchanged, and
each computed plane keeps its y/z halo ring (the exchange owns halo cells).

Semantics match ``models.jacobi.Jacobi3D._kernel`` exactly: mean of 6 face
neighbors, hot/cold sphere forcing.  Sphere membership uses the integer
predicate ``d2 < (r+1)^2``, exactly equivalent to the reference's
truncated-float-sqrt test (jacobi3d.cu:31-33) for these magnitudes — see
models/jacobi.py.  The y/z part of ``d2`` (both spheres share the same y/z
center, jacobi3d.cu:44-63) is precomputed once per shard and parked in VMEM
via a constant-index block, so the per-plane forcing is two compares and two
selects.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from stencil_tpu.core.dim3 import Dim3
from stencil_tpu.telemetry import names as tm

HOT_TEMP = 1.0
COLD_TEMP = 0.0

#: storage-dtype axis for field buffers — ``native`` keeps the user dtype
#: end to end; ``bf16`` stores f32 fields as bfloat16 (HBM planes, VMEM
#: pipeline blocks, exchange messages all narrow to 2 B/cell) while the
#: level kernels accumulate at f32 (load → upcast → compute → downcast on
#: the final store; the ``f32_accumulate`` kernel contract).
STORAGE_DTYPES = ("native", "bf16")


def bf16_supported(native_dtypes) -> bool:
    """Structural gate for bf16 storage: only f32 fields narrow — the
    downcast keeps the full f32 exponent range (losslessly-enough per the
    analytic bound: one round-to-nearest of ≤ 2^-9 relative per store).
    f64 would shed 45 mantissa bits (no analytic contract worth having),
    and integer/bool fields have no bf16 form."""
    return all(jnp.dtype(dt) == jnp.float32 for dt in native_dtypes)


def _resolve_axis_value(request, tuned, env_name: str, choices, static: str):
    """The storage-dtype axis's precedence chain (mirrors the
    exchange-route and stream-overlap rules): an explicit
    request wins and never consults further; then the validated env knob;
    then the tuned config's field (garbage warns and falls through); then
    the static fallback.  Returns ``(value, source)`` pre-structural."""
    from stencil_tpu.utils.config import env_choice

    if request not in (None, "auto"):
        if request not in choices:
            raise ValueError(f"unknown value {request!r} (one of {choices})")
        return request, "explicit"
    env = env_choice(env_name, "auto", ("auto",) + tuple(choices))
    if env != "auto":
        return env, "env"
    if tuned is not None:
        if tuned in choices:
            return str(tuned), "tuned"
        from stencil_tpu.utils.logging import log_warn

        log_warn(
            f"tuned {env_name.lower()} value {tuned!r} is not one of "
            f"{choices}; using the static {static!r} fallback"
        )
    return static, "static"


def resolve_storage_dtype(
    request, tuned, native_dtypes, where: str = "kernel",
    engine_ok: bool = True,
    engine_why: str = (
        "this engine accumulates at the storage dtype (no f32-accumulate "
        "kernel)"
    ),
):
    """Resolve the storage-dtype axis for one model build: precedence
    explicit > ``STENCIL_STORAGE_DTYPE`` > tuned > static ``native``, then
    the structural guard — ``bf16`` on non-f32 fields, or on an engine
    whose kernels would accumulate at bf16 instead of f32 (the XLA slice
    route), degrades to ``native`` with a warning.  Every resolution is a
    ``kernel.storage_dtype`` telemetry event.  Returns ``(sd, source)``."""
    val, source = _resolve_axis_value(
        request, tuned, "STENCIL_STORAGE_DTYPE", STORAGE_DTYPES, "native"
    )
    if val == "bf16" and not (engine_ok and bf16_supported(native_dtypes)):
        from stencil_tpu.utils.logging import log_warn

        why = (
            engine_why
            if not engine_ok
            else f"fields are {[jnp.dtype(d).name for d in native_dtypes]}, not f32"
        )
        log_warn(
            f"storage_dtype=bf16 ({source}) cannot engage for {where} ({why}); "
            "degrading to native"
        )
        val, source = "native", source + "/degraded"
    from stencil_tpu import telemetry

    telemetry.emit_event(
        tm.EVENT_KERNEL_STORAGE_DTYPE, storage=val, source=source, where=where
    )
    return val, source


def _level_sum(roll, prev, vals, cent):
    """The per-level 6-neighbor numerator: the roll+add chain, in the
    left-fold order tier-1 pins bitwise."""
    return (
        prev
        + vals
        + roll(cent, 1, 0)
        + roll(cent, -1, 0)
        + roll(cent, 1, 1)
        + roll(cent, -1, 1)
    )


def sphere_params(gx: int):
    """hot/cold sphere x-centers and the integer membership bound
    d2 < (r+1)^2 (the truncated-float-sqrt test, jacobi3d.cu:31-33 — see
    models/jacobi.py for the exact-equivalence bound)."""
    return gx // 3, gx * 2 // 3, (gx // 10 + 1) ** 2


def yz_dist2_plane(origin_y, origin_z, shape_yz: Tuple[int, int], global_size) -> jax.Array:
    """(y - gy/2)^2 + (z - gz/2)^2 over the interior plane, wrapped
    periodically; shared by both spheres (same y/z center)."""
    gy, gz = global_size[1], global_size[2]
    cy, cz = gy // 2, gz // 2
    y = (origin_y + jnp.arange(shape_yz[0])) % gy
    z = (origin_z + jnp.arange(shape_yz[1])) % gz
    return ((y - cy) ** 2)[:, None] + ((z - cz) ** 2)[None, :]


#: The scoped-VMEM budget REQUESTED from the compiler
#: (``CompilerParams(vmem_limit_bytes=...)``) and the stack margin its
#: temporaries (rolls, selects) claim beyond the block buffers.  Mosaic's
#: 16 MB default is only a default: v5e physically carries 128 MB of VMEM and
#: raising the request to 100 MB compiles and RUNS FASTER at every depth
#: probed (scripts/probe20*, 512^3 f32: k=3 97 -> k=12 190 -> k=16 ~200
#: Gcells/s; k=32 at a 120 MB request regresses to 152 — leave headroom for
#: the pipeline's double buffers).  The r04 calibration anchors (16 MB
#: pass/fail points, probe10/14/17) describe the DEFAULT budget and survive
#: as the behavior when ``STENCIL_VMEM_LIMIT_BYTES`` forces the old value.
_VMEM_BUDGET_DEFAULT = 100 * 1024 * 1024
_VMEM_STACK_MARGIN = 3_000_000


_vmem_warned: set = set()


def _vmem_budget() -> int:
    """Requested scoped-VMEM bytes; ``STENCIL_VMEM_LIMIT_BYTES`` overrides
    (read per call so tests can force an over-budget compile).  The read is
    VALIDATED (``utils.config.env_int``): a malformed value raises a message
    naming the env var instead of a bare ``ValueError`` deep inside
    planning, a zero/negative value (which would silently disable every
    streaming route) is rejected, and a value under Mosaic's 16 MB default
    warns once per distinct value."""
    from stencil_tpu.utils.config import env_int

    val = env_int("STENCIL_VMEM_LIMIT_BYTES", _VMEM_BUDGET_DEFAULT, minimum=1)
    if val < 16 * 1024 * 1024 and val not in _vmem_warned:
        _vmem_warned.add(val)
        from stencil_tpu.utils.logging import log_warn

        log_warn(
            f"STENCIL_VMEM_LIMIT_BYTES={val} is below Mosaic's 16 MB default "
            "scoped-VMEM budget; deep streaming routes will degrade to "
            "shallow/plane rungs"
        )
    return val

#: deepest depth validated on hardware and the measured plateau: probe20b/c/d
#: (512^3, 100 MB budget) k=8 128-132, k=12 190, k=16 142-202, k=20 190,
#: k=24 190, k=32 152 Gcells/s — the plateau spans ~12-24 with run-to-run
#: contention noise; 16 sits mid-plateau at modest (40 MB) VMEM
_WRAP_MAX_K = 16


def _tpu_compiler_params(interpret: bool):
    """kwargs dict requesting the calibrated scoped-VMEM budget — empty in
    interpret mode (no Mosaic, nothing to budget)."""
    if interpret:
        return {}
    from jax.experimental.pallas import tpu as pltpu

    return {
        "compiler_params": pltpu.CompilerParams(
            vmem_limit_bytes=_vmem_budget()
        )
    }


def _padded_plane_bytes(plane_y: int, plane_z: int, itemsize: int) -> int:
    """HBM/VMEM bytes of one (plane_y, plane_z) plane after (sublane, 128)
    tile padding — lane padding is what the naive y*z*itemsize model misses
    (516 lanes really occupy 640)."""
    sub = max(8, 32 // itemsize)  # f32 -> 8, bf16 -> 16, i8 -> 32
    return (-(-plane_y // sub) * sub) * (-(-plane_z // 128) * 128) * itemsize


def wavefront_vmem_bytes(
    k: int,
    plane_y: int,
    plane_z: int,
    itemsize: int,
    z_slabs: bool = False,
    d2_itemsize: int = 4,
    ring_itemsize: int = None,
) -> int:
    """Modeled VMEM footprint of a k-level plane wavefront: 2k ring planes,
    4 pipeline (in/out double-buffer) planes, the resident d2 plane
    (``d2_itemsize`` 2 when ``pack_d2`` can clamp to int16), and (z-slab
    variant) 4 double-buffered packed-slab blocks.  ``ring_itemsize``
    overrides the ring planes' itemsize: bf16 STORAGE (``f32_accumulate``)
    streams 2-byte pipeline planes but carries its level ring at f32, so
    the ring must be modeled at 4 bytes or the gate lies."""
    ring_it = itemsize if ring_itemsize is None else ring_itemsize
    plane = _padded_plane_bytes(plane_y, plane_z, itemsize)
    est = 2 * k * _padded_plane_bytes(plane_y, plane_z, ring_it) + 4 * plane
    if d2_itemsize:  # 0 = kernel variant with no resident d2 plane
        est += _padded_plane_bytes(plane_y, plane_z, d2_itemsize)
    if z_slabs:
        # z-major (1, 2k, plane_y) blocks: sublane-pad the 2k rows
        est += 4 * _padded_plane_bytes(2 * k, plane_y, itemsize)
    return est


def wavefront_vmem_fits(
    k: int,
    plane_y: int,
    plane_z: int,
    itemsize: int,
    z_slabs: bool = False,
    d2_itemsize: int = 4,
    ring_itemsize: int = None,
) -> bool:
    est = wavefront_vmem_bytes(
        k, plane_y, plane_z, itemsize, z_slabs, d2_itemsize, ring_itemsize
    )
    return est + _VMEM_STACK_MARGIN <= _vmem_budget()


def pack_d2(yz_d2: jax.Array, global_size) -> jax.Array:
    """The d2 plane as int32.  (An int16 clamp would halve the resident
    plane and is numerically exact for gx < ~1800, but Mosaic on v5e
    rejects 16-bit vector comparisons — "Target does not support this
    comparison" — so the narrow form is not usable today.)"""
    del global_size
    return yz_d2.astype(jnp.int32)


def warn_if_over_vmem_budget(k: int, plane_y: int, plane_z: int, itemsize: int,
                             ring_itemsize: int = None) -> None:
    if not wavefront_vmem_fits(k, plane_y, plane_z, itemsize,
                               ring_itemsize=ring_itemsize):
        est = wavefront_vmem_bytes(k, plane_y, plane_z, itemsize,
                                   ring_itemsize=ring_itemsize)
        from stencil_tpu.utils.logging import log_warn

        log_warn(
            f"temporal depth {k} models {est / 1e6:.1f} MB of VMEM blocks "
            f"(+{_VMEM_STACK_MARGIN / 1e6:.0f} stack > {_vmem_budget() / 1e6:.0f} budget); "
            "expect a compile failure on real TPU (fine in interpret mode)"
        )


def choose_temporal_k(
    shape: Tuple[int, int, int], itemsize: int, requested="auto",
    tune_key=None, ring_itemsize: int = None,
) -> int:
    """Pick the wrap kernel's temporal blocking depth: the deepest k whose
    VMEM footprint fits the calibrated budget (``auto``), or a validated
    explicit int.  Measured sweep (scripts/probe10b, v5e f32): 512^3
    41 -> 94 Gcells/s (k=3), 384^3 -> 120 (k=6), 256^3 -> 134 (k=6).

    ``tune_key`` (a ``tune.WorkloadKey``) consults the measurement-driven
    autotuner first: a persisted on-device-measured depth for this
    chip/shape/dtype wins over the static model below (which is the v5e
    calibration, kept as the no-tune/cold-cache fallback — docs/tuning.md).
    A tuned depth may legitimately exceed ``_WRAP_MAX_K``: the plateau is a
    property of the probed chip, not the kernel.

    ``ring_itemsize`` overrides the level ring's itemsize in the VMEM
    model: under bf16 STORAGE the pipeline planes stream at 2 B but the
    ring carries the f32 accumulator (the ``f32_accumulate`` contract), so
    a storage-itemsize-only model would admit depths whose f32 ring blows
    the budget."""
    X, Y, Z = shape
    if requested != "auto":
        k = int(requested)
        if not 1 <= k <= max(1, X // 2):
            raise ValueError(f"temporal_k={k} needs 1 <= k <= X//2 = {X // 2}")
        warn_if_over_vmem_budget(k, Y, Z, itemsize, ring_itemsize)
        return k
    if tune_key is not None:
        from stencil_tpu import tune

        cfg = tune.best_config(tune_key)
        if cfg is not None:
            k = cfg.get("k")
            if isinstance(k, int) and 1 <= k <= max(1, X // 2):
                return k
            from stencil_tpu.utils.logging import log_warn

            log_warn(
                f"tuned config {cfg} for {tune_key.label()} is structurally "
                f"invalid here (need 1 <= k <= {max(1, X // 2)}); using the "
                "static pick"
            )
    k = 1
    for cand in range(2, _WRAP_MAX_K + 1):
        if cand <= X // 2 and wavefront_vmem_fits(
            cand, Y, Z, itemsize, ring_itemsize=ring_itemsize
        ):
            k = cand
    return k


def _make_roll(interpret: bool):
    """Interpret-aware plane rotate shared by the streaming kernels: jnp.roll
    in interpret mode, pltpu.roll (amount normalized into range) compiled.
    Mosaic's rotate is 32-bit-only ("Rotate with non-32-bit data"): narrower
    FLOAT dtypes upcast to f32 (value-exact for bf16/f16) and stay f32 on
    return, so the caller's stencil sum accumulates in f32 and downcasts
    once at its existing per-level astype — better accuracy than a narrow
    sum and fewer converts than a per-roll round trip (Mosaic CSEs the
    repeated upcast of the same plane).  8-byte dtypes are not silently
    truncated; they fail loudly in Mosaic.

    Mosaic additionally rejects its rotate on planes that are not natively
    tiled ("unsupported unaliged shape": second-minor % 8 / minor % 128 for
    the 32-bit tiling) — exactly the shape class of shell-padded multi-chip
    blocks (e.g. 132x132 raw planes) and the split-step overlap schedule's
    narrow band sub-blocks (ops/stream.py).  A STATIC python amount (stencil
    offsets, wrap closures — every streaming-kernel site) on an unaligned
    plane therefore takes an equivalent two-static-slices + concatenate form
    instead, which Mosaic accepts at any alignment; aligned planes keep the
    single rotate instruction (the measured single-chip fast path), and
    TRACED amounts (the slab route's per-plane column rotate) have no
    static-slice form and stay on Mosaic's rotate."""
    from jax.experimental.pallas import tpu as pltpu

    def roll(v, amt, axis):
        if interpret:
            return jnp.roll(v, amt, axis)
        if v.dtype.itemsize < 4 and jnp.issubdtype(v.dtype, jnp.floating):
            v = v.astype(jnp.float32)
        aligned = v.shape[-1] % 128 == 0 and (
            v.ndim < 2 or v.shape[-2] % 8 == 0
        )
        if aligned or not isinstance(amt, int):
            return pltpu.roll(v, amt % v.shape[axis], axis)
        n = v.shape[axis]
        k = amt % n
        if k == 0:
            return v
        return jax.lax.concatenate(
            [
                jax.lax.slice_in_dim(v, n - k, n, axis=axis),
                jax.lax.slice_in_dim(v, 0, n - k, axis=axis),
            ],
            dimension=axis,
        )

    return roll


#: lanes of one vector register: the unit the z-halo patch works in
_LANES = 128


def z_halo_patch_form(width: int, s: int) -> str:
    """Which form ``patch_z_halo`` takes on a working plane ``width`` lanes
    wide under an ``s``-wide z halo, read off the static shapes alone:
    ``"tile"`` where the plane is whole lane tiles and the slab's ``2s``
    columns fit one, ``"plane"`` otherwise (an unpadded shell plane).
    ``domain.step`` says it as ``z_halo_patch``."""
    return "tile" if width % _LANES == 0 and 2 * s <= _LANES else "plane"


def patch_z_halo(plane, zst, s: int, lo_at: int, hi_at: int, roll):
    """``plane`` (Yr, W) with lanes [lo_at, lo_at + s) <- ``zst[:, :s]`` and
    lanes [hi_at, hi_at + s) <- ``zst[:, s:2s]`` -- the z-halo patch of the
    z-slab wavefront kernels, ``zst`` (Yr, 2s) being the streamed slab block
    after its one small transpose.  Every other lane keeps its value.

    On a lane-aligned plane (``z_halo_patch_form`` says ``"tile"``) only the
    128-lane tiles that hold a halo lane are touched.  The slab's ``2s``
    columns sit in lanes [0, 2s) of one tile after the transpose; ONE lane
    rotate of that tile per distinct shift puts them at their lanes modulo
    128 (a halo that straddles a multiple of 128 lands in both tiles from the
    same rotate), one masked select per (halo, tile) merges them into the
    tile sliced out at a multiple of 128, and the tiles go back by a
    lane-aligned concatenate.

    The whole-plane form this replaces took one column of ``zst`` at a time,
    broadcast it along the lanes and selected it into EVERY vreg of the plane,
    ``2s`` times: 2.07 of the z-ring kernel's 20.72 ms a 16-level macro and
    0.50 of the engine's 3.69 ms a 3-level field pass (TPU v5 lite, PERF.md
    PR 40).  The same ``2s`` selects confined to the halo's tile gave nothing
    back (20.81 ms): it is the per-column broadcasts that cost, and the
    rotate makes none.  A plane that is not whole lane tiles keeps the
    whole-plane form: it has no tile to slice out at a multiple of 128."""
    Yr, W = plane.shape
    halos = ((lo_at, 0), (hi_at, s))  # (first lane, first slab column)
    if z_halo_patch_form(W, s) == "plane":
        col = jax.lax.broadcasted_iota(jnp.int32, (Yr, W), 1)
        for j in range(s):
            for at, j0 in halos:
                plane = jnp.where(col == at + j, zst[:, j0 + j][:, None], plane)
        return plane
    src = jnp.pad(zst, ((0, 0), (0, _LANES - 2 * s)))  # one (Yr, 128) tile
    lane = jax.lax.broadcasted_iota(jnp.int32, (Yr, _LANES), 1)
    rolled, tiles = {}, {}
    for at, j0 in halos:
        amt = (at - j0) % _LANES  # slab column j0 + j -> lane (at + j) % 128
        if amt not in rolled:
            # (the compiled rotate hands narrow floats back as f32)
            rolled[amt] = (roll(src, amt, 1) if amt else src).astype(plane.dtype)
        for t in range(at // _LANES, (at + s - 1) // _LANES + 1):
            a = max(at - t * _LANES, 0)
            b = min(at + s - t * _LANES, _LANES)
            tile = tiles.get(t)
            if tile is None:
                tile = plane[:, t * _LANES : (t + 1) * _LANES]
            tiles[t] = jnp.where((lane >= a) & (lane < b), rolled[amt], tile)
    pieces, done = [], 0
    for t in sorted(tiles):
        if t * _LANES > done:
            pieces.append(plane[:, done : t * _LANES])
        pieces.append(tiles[t])
        done = (t + 1) * _LANES
    if done < W:
        pieces.append(plane[:, done:])
    return pieces[0] if len(pieces) == 1 else jnp.concatenate(pieces, axis=1)


def jacobi_wrap_step(
    block: jax.Array,
    interpret: bool = False,
    k: int = 1,
    f32_accumulate: bool = False,  # bf16-STORAGE variant: the block streams
    # at its (narrow) dtype but the kernel upcasts at load, carries the
    # level ring and all arithmetic at f32, and downcasts ONCE at the final
    # store — one round-to-nearest per k levels instead of one per level
) -> jax.Array:
    """``k`` Jacobi iterations over the WHOLE (unsharded) domain with the
    periodic wrap folded into the kernel — the single-device fast path.

    With one device there is no neighbor: the reference still runs its
    same-GPU ``PeerAccessSender`` translate kernels to fill the shell
    (tx_cuda.cuh:39-104); here the shell disappears entirely.  The x-wrap
    rides the block index map (planes are re-fetched modulo X after the last
    plane so every level can close its ring), and the y/z wrap is a
    lane/sublane rotate of the resident plane.

    ``k > 1`` is TEMPORAL BLOCKING (a wavefront over time steps): each HBM
    plane is read ONCE and the output written ONCE per ``k`` iterations —
    ~8/k bytes/cell.  This chip's DMA fabric caps pallas pipelines at
    ~350 GB/s (scripts/probe9e/9f: one giant HBM->HBM DMA, multi-queue, and
    multi-buffer all plateau there, while XLA vector-core fusions stream
    ~720), so at k=1 the plane pipeline is already AT its hardware ceiling
    and only temporal reuse can pass it.  Level ``s`` consumes the planes of
    level ``s-1`` as they emerge; each level keeps a 2-plane ring; the replay
    (grid X + 2k) recomputes each level's early planes so the x-wrap closes
    for every level — the k=1 schedule is exactly the original wrap kernel.

    ``block`` is the bare (X, Y, Z) logical domain; semantics match ``k``
    applications of ``models.jacobi.Jacobi3D._kernel`` exactly (bit-exact:
    summation order is identical per level).
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    X, Y, Z = block.shape
    assert 1 <= k <= X // 2, (k, X)
    gx = X
    hot_x, cold_x, in_r2 = sphere_params(gx)

    roll = _make_roll(interpret)
    acc_dtype = jnp.float32 if f32_accumulate else block.dtype

    def kernel(in_ref, d2_ref, out_ref, ring):
        # ring[s] holds the two most recent level-s planes (level 0 = input)
        i = pl.program_id(0)
        d2 = d2_ref[...]
        vals = in_ref[0].astype(acc_dtype)  # level-0 plane i (mod X)
        for s in range(1, k + 1):
            # level-s plane (i - s) from level-(s-1) planes (i-s-1, i-s,
            # i-s+1); early steps compute garbage that the replay rewrites
            prev = ring[s - 1, i % 2]  # plane i-s-1
            cent = ring[s - 1, (i + 1) % 2]  # plane i-s
            ring[s - 1, i % 2] = vals  # push plane i-s+1 (after prev read)
            val = _level_sum(roll, prev, vals, cent) / 6.0
            x_g = (i - s) % X
            val = jnp.where(d2 < in_r2 - (x_g - hot_x) ** 2, HOT_TEMP, val)
            val = jnp.where(d2 < in_r2 - (x_g - cold_x) ** 2, COLD_TEMP, val)
            vals = val.astype(acc_dtype)
        # level-k plane (i - k) % X; last write is valid.  The one downcast
        # of the f32_accumulate contract happens here.
        out_ref[0] = vals.astype(block.dtype)

    d2 = yz_dist2_plane(0, 0, (Y, Z), block.shape)

    const = lambda a, b: pl.BlockSpec((a, b), lambda i: (0, 0))
    in_specs = [
        pl.BlockSpec((1, Y, Z), lambda i: (i % X, 0, 0)),
        # constant index map: fetched once, stays resident in VMEM
        const(Y, Z),
    ]
    args = [block, d2.astype(jnp.int32)]
    return pl.pallas_call(
        kernel,
        name=tm.KERNEL_JACOBI_WRAP,
        grid=(X + 2 * k,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, Y, Z), lambda i: ((i - k) % X, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((X, Y, Z), block.dtype),
        scratch_shapes=[pltpu.VMEM((k, 2, Y, Z), acc_dtype)],
        interpret=interpret,
        **_tpu_compiler_params(interpret),
    )(*args)


def jacobi_shell_wavefront_step(
    raw: jax.Array,  # (X+2s, Y+2s, Z+2s) block with FILLED s-wide shell, s >= m
    m: int,  # levels to advance (<= the shell width)
    origin: jax.Array,  # (3,) int32 global coords of the shard's interior start
    d2: jax.Array,  # (Y+2s, Z+2s) int32 yz_dist2_plane over the RAW plane
    global_size: Tuple[int, int, int],
    interior_offset: int = None,  # raw index of the interior start (= shell
    # width s; defaults to m — pass it when advancing FEWER levels than the
    # shell is wide, e.g. a steps%m remainder dispatch)
    interpret: bool = False,
    alias: bool = True,  # in-place (input_output_aliases); False trades the
    # aliasing for a fresh output buffer (uninitialized high shell)
    z_slabs: jax.Array = None,  # (Xr, 2s, Yr) TRANSPOSED, s = the shell
    # width: the z-halo content, kept OUT of the big array (a z halo
    # write/read on the tiled layout costs a whole (8,128)-tile column
    # pass, ~64x amplification — scripts/probe12d).  Rows [0, s) = my low
    # halo (zlo), [s, 2s) = my high halo (zhi) — ONE packed buffer, stored
    # z-major so each streamed (1, 2s, Yr) block pads to (8, lanes) instead
    # of (sublanes, 128): ~20 KB/block vs 266 — a 13x VMEM saving per
    # double-buffered block that still matters for deep-m budgets (and was
    # what fit 516^2 planes under Mosaic's old 16 MB default, kept reachable
    # via STENCIL_VMEM_LIMIT_BYTES).  The kernel transposes
    # the small block in VMEM, patches the z columns of every streamed
    # plane -- inside the lane tiles that hold them, tile 0 for the low halo
    # and the one or two tiles over [z_valid - s, z_valid) for the high one,
    # where the plane is whole lane tiles; over the whole plane where it is
    # not (``patch_z_halo``) -- and, when set, ALSO emits the next macro
    # step's outgoing slabs in the same layout, returning (out, z_out) with
    # z_out rows [0, s) = my top interior cols [Zr-2s, Zr-s) (the -z-bound
    # message) and [s, 2s) = my bottom interior cols [s, 2s) (the +z-bound
    # message).
    z_valid: int = None,  # logical z extent of the raw planes (shell incl.);
    # columns [z_valid, Zr) are DEAD LANE PADDING that rounds the plane width
    # up to a 128 multiple.  Ragged lane extents cripple the plane DMA
    # (probe22: 512x512x516 streams 30% slower than 512x512x512 while
    # 512x512x640 runs at full per-byte rate), so the caller pads the array
    # and the kernel treats [z_valid, Zr) as outside the domain.  Dead-column
    # garbage rolls into halo column 0 / z_valid-1 at level 1 — columns that
    # are only valid at level 0 anyway, so the shrinking-validity argument is
    # unchanged: level s remains valid on [s, z_valid - s).
    f32_accumulate: bool = False,  # bf16-storage variant: upcast at load,
    # f32 level ring + arithmetic, ONE downcast at the final store/emit
) -> jax.Array:
    """``m`` Jacobi levels over an m-shell-carrying shard in ONE pass — the
    multi-device temporal-blocking path.

    The halo-multiplier machinery (domain.set_halo_multiplier) already
    exchanges ``m*r``-wide shells every ``m`` steps; this kernel is its
    compute half done the wrap-kernel way: a wavefront over time steps where
    each HBM plane is read once and written once per ``m`` iterations
    (~8/m B/cell), instead of ``m`` separate full passes.  Validity shrinks
    exactly one cell per level from each face — the roll wraparound at the
    y/z plane edges and the missing planes at the x ends contaminate only
    the cells the shell was sized to sacrifice: level ``s`` is valid on
    ``[s, ext-s)`` per axis, and the interior ``[m, ext-m)`` is exactly
    level ``m``'s guarantee.  Unlike ``jacobi_wrap_step`` there is no ring
    closure, hence no replay: the grid is one step per raw plane.

    The interior lands advanced ``m`` levels; shell cells hold garbage
    (low-x planes) or their pre-step values (aliased high-x planes) — the
    caller re-exchanges before the next wavefront and marks the shell stale
    for readback, so no consumer ever observes them.

    Reference analog: the halo-multiplier idea the reference lists as future
    work (README.md:157-176 "exchange every k steps"); here it is what makes
    the multi-GPU pipeline's traffic match the single-device fast path.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    Xr, Yr, Zr = raw.shape
    zv = Zr if z_valid is None else z_valid
    s_off = m if interior_offset is None else interior_offset
    # raw must carry a shell at least m wide plus >= 1 interior cell per axis
    assert 1 <= m <= s_off and 2 * s_off < min(Xr, Yr, zv), (m, s_off, raw.shape, zv)
    assert zv <= Zr, (zv, Zr)
    gx = global_size[0]
    # the in-kernel lax.rem relies on its operand being non-negative:
    # i - s - s_off >= -2*s_off > -gx, so one added gx suffices.  Enforce the
    # precondition instead of assuming it (an x-unsharded mesh with a deep
    # explicit temporal_k could otherwise silently mis-force shell planes).
    assert 2 * s_off < gx, (s_off, gx)
    hot_x, cold_x, in_r2 = sphere_params(gx)

    roll = _make_roll(interpret)
    acc_dtype = jnp.float32 if f32_accumulate else raw.dtype

    def kernel(origin_ref, in_ref, d2_ref, *rest):
        if z_slabs is not None:
            zs_ref, out_ref, zout_ref, ring = rest
        else:
            out_ref, ring = rest
        # ring[s] holds the two most recent level-s planes (level 0 = input)
        i = pl.program_id(0)
        d2v = d2_ref[...]
        vals = in_ref[0].astype(acc_dtype)  # level-0 raw plane i
        if z_slabs is not None:
            # patch the z-shell columns in VMEM — they are never stored in
            # the big array.  One small (2s, Yr) -> (Yr, 2s) transpose per
            # plane turns the z-major block into the columns patch_z_halo
            # moves to their lanes.
            zst = jnp.swapaxes(zs_ref[0], 0, 1).astype(acc_dtype)  # (Yr, 2s)
            vals = patch_z_halo(vals, zst, s_off, 0, zv - s_off, roll)
        for s in range(1, m + 1):
            prev = ring[s - 1, i % 2]  # level-(s-1) plane i-s-1
            cent = ring[s - 1, (i + 1) % 2]  # level-(s-1) plane i-s
            ring[s - 1, i % 2] = vals  # push plane i-s+1 (after prev read)
            val = _level_sum(roll, prev, vals, cent) / 6.0
            # global x of level-s plane i-s (raw index -> interior-origin
            # coords; + gx keeps lax.rem's operand non-negative:
            # i-s-s_off >= -2*s_off > -gx).  Shell planes matter too: their
            # intermediate-level values feed valid higher-level cells, so
            # forcing must follow the periodic global coordinate everywhere.
            x_g = jax.lax.rem(
                origin_ref[0] + jnp.int32(gx) + i - jnp.int32(s + s_off), jnp.int32(gx)
            )

            val = jnp.where(d2v < in_r2 - (x_g - hot_x) ** 2, HOT_TEMP, val)
            val = jnp.where(d2v < in_r2 - (x_g - cold_x) ** 2, COLD_TEMP, val)
            vals = val.astype(acc_dtype)
        # level-m plane i-m; valid for interior planes.  The f32_accumulate
        # contract's ONE downcast happens at this store (and the slab emit).
        out_ref[0] = vals.astype(raw.dtype)
        if z_slabs is not None:
            # emit next macro's outgoing z slabs: my interior z-boundary
            # columns at the output level (shell planes/rows carry garbage
            # here; the caller's slab extensions overwrite them), packed
            # [(-z)-bound message | (+z)-bound message], z-major
            emit = jnp.concatenate(
                [vals[:, zv - 2 * s_off : zv - s_off], vals[:, s_off : 2 * s_off]],
                axis=1,
            ).astype(raw.dtype)  # (Yr, 2s)
            zout_ref[0] = jnp.swapaxes(emit, 0, 1)

    out_idx = lambda i: (jnp.maximum(i - m, 0), 0, 0)
    assert jnp.issubdtype(d2.dtype, jnp.integer), d2.dtype
    in_specs = [
        pl.BlockSpec(memory_space=pltpu.SMEM),
        pl.BlockSpec((1, Yr, Zr), lambda i: (i, 0, 0)),
        # constant index map: fetched once, stays resident in VMEM
        pl.BlockSpec((Yr, Zr), lambda i: (0, 0)),
    ]
    out_specs = pl.BlockSpec((1, Yr, Zr), out_idx)
    out_shape = jax.ShapeDtypeStruct((Xr, Yr, Zr), raw.dtype)
    args = [origin.astype(jnp.int32), raw, d2]
    if z_slabs is not None:
        assert z_slabs.shape == (Xr, 2 * s_off, Yr), (z_slabs.shape, raw.shape)
        in_specs += [pl.BlockSpec((1, 2 * s_off, Yr), lambda i: (i, 0, 0))]
        out_specs = (
            out_specs,
            pl.BlockSpec((1, 2 * s_off, Yr), out_idx),
        )
        out_shape = (
            out_shape,
            jax.ShapeDtypeStruct((Xr, 2 * s_off, Yr), raw.dtype),
        )
        args += [z_slabs]
    return pl.pallas_call(
        kernel,
        name=tm.KERNEL_JACOBI_SHELL_WAVEFRONT,
        grid=(Xr,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        # in-place: the write of plane i-m trails the fetch of plane i+1 by
        # m+1 planes, so aliasing is hazard-free; unwritten high-shell planes
        # keep their pre-step bytes
        input_output_aliases={1: 0} if alias else {},
        scratch_shapes=[pltpu.VMEM((m, 2, Yr, Zr), acc_dtype)],
        interpret=interpret,
        **_tpu_compiler_params(interpret),
    )(*args)


#: lane offset of the interior segment in the z-ring working plane; the lo
#: halo sits immediately below it, the hi halo wraps to lane 0 (see
#: jacobi_zring_wavefront_step) — must stay a multiple of 128 so the
#: staging/output slices are lane-aligned, and >= 2*s_off
_ZRING_OFF = 128


def zring_dist2_plane(origin_y, origin_z, s_off: int, shape_y: int, z_interior: int, global_size):
    """``yz_dist2_plane`` for the z-RING working layout: lanes [0, s_off)
    hold the hi halo (z = Zi..Zi+s_off), lanes [_ZRING_OFF - s_off,
    _ZRING_OFF) the lo halo, lanes [_ZRING_OFF, _ZRING_OFF + Zi) the
    interior — the linear formula covers interior+lo contiguously and one
    select fixes the wrapped hi segment (dead lanes get harmless wrapped
    values)."""
    gy, gz = global_size[1], global_size[2]
    W = _ZRING_OFF + z_interior
    y = (origin_y + jnp.arange(shape_y)) % gy
    c = jnp.arange(W)
    z_lin = origin_z + c - _ZRING_OFF
    z_hi = origin_z + z_interior + c
    z = jnp.where(c < s_off, z_hi, z_lin) % gz
    cy, cz = gy // 2, gz // 2
    return ((y - cy) ** 2)[:, None] + ((z - cz) ** 2)[None, :]


def jacobi_zring_wavefront_step(
    raw: jax.Array,  # (Xr, Yr, Zi): x/y FILLED shell carried in-array, z
    # INTERIOR-ONLY (the 20%-of-DMA z-shell/lane-pad columns are gone from
    # HBM entirely); Zi % 128 == 0
    m: int,  # levels to advance (<= the shell width)
    origin: jax.Array,  # (3,) int32 global coords of the shard's interior start
    d2: jax.Array,  # (Yr, Zi + 128) int32 from zring_dist2_plane
    global_size: Tuple[int, int, int],
    z_slabs: jax.Array,  # (Xr, 2s, Yr) z-major: rows [0, s) = my lo halo,
    # [s, 2s) = my hi halo (same convention as jacobi_shell_wavefront_step)
    interior_offset: int = None,
    alias: bool = False,
    interpret: bool = False,
    f32_accumulate: bool = False,  # bf16-storage variant (see
    # jacobi_shell_wavefront_step)
):
    """``m`` Jacobi levels per pass with the z halo in a RING-layout VMEM
    working plane — the deep-wavefront path that streams NO z padding.

    In z-slab mode the in-array z-shell columns are never read (the kernel
    patches halos from the slab buffers), yet they force either ragged-lane
    DMA (~30% slower, probe22) or 640-wide lane padding.  Here HBM stores
    only the Zi interior columns; each streamed (Yr, Zi) plane is staged
    into a (Yr, Zi + 128) working plane at lane offset 128 whose LANE WRAP
    is periodic-consistent by construction:

        lanes [0, s)            hi halo  (z = Zi .. Zi+s)   } the RING TILE,
        lanes [s, 128 - s)      dead (zero)                 } built on its own
        lanes [128 - s, 128)    lo halo  (z = -s .. 0)      } by patch_z_halo
        lanes [128, 128 + Zi)   interior (z = c - 128): the streamed plane

    The ring tile is one (Yr, 128) tile made from the slab block alone --
    its one small transpose, ONE lane rotate by -s, two masked selects
    (``patch_z_halo`` on a zero tile) -- and concatenated, lane-aligned, in
    front of the interior plane: no operation of the staging touches the
    interior's vregs.

    What the pass costs against ``jacobi_wrap_step`` on the same 512^3 of
    interior (TPU v5 lite, PERF.md PR 40): both make 544 grid steps a
    16-level macro, and a level works on 544 x 640 = 340 vregs here (the
    16-deep y shell, the ring tile) against 512 x 512 = 256 there: x 1.328
    in level work, 13.2 -> 17.5 ms.  On top of that came the staging --
    until ISSUE 40 ``2s`` lane-broadcasts of a slab column, each selected
    into the WHOLE plane: 2.07 ms a macro -- and what the slab blocks' two
    DMAs a grid step and the emit cost (~1.1 ms).

    ``roll(plane, -1)`` brings lane 0 (hi halo z=Zi) to lane 127+Zi
    (interior z=Zi-1) — its true +z neighbor; ``roll(plane, +1)`` brings
    lane 127 (lo halo z=-1) to lane 128 (interior z=0).  Both seams are
    neighbor-correct, the hi/lo outermost halo lanes border dead lanes and
    are valid only at level 0 — exactly the shrinking-validity contract —
    and every staging/output slice sits at a 128-aligned lane offset.
    Returns ``(out, z_out)`` with the same z_out convention as the
    shell-layout kernel."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    Xr, Yr, Zi = raw.shape
    s_off = m if interior_offset is None else interior_offset
    OFF = _ZRING_OFF
    W = OFF + Zi
    assert Zi % 128 == 0 and 2 * s_off <= OFF, (Zi, s_off)
    assert 1 <= m <= s_off and 2 * s_off < min(Xr, Yr), (m, s_off, raw.shape)
    gx = global_size[0]
    assert 2 * s_off < gx, (s_off, gx)
    assert d2.shape == (Yr, W) and jnp.issubdtype(d2.dtype, jnp.integer), d2.shape
    assert z_slabs.shape == (Xr, 2 * s_off, Yr), (z_slabs.shape, raw.shape)
    hot_x, cold_x, in_r2 = sphere_params(gx)
    roll = _make_roll(interpret)
    acc_dtype = jnp.float32 if f32_accumulate else raw.dtype

    def kernel(origin_ref, in_ref, d2_ref, zs_ref, out_ref, zout_ref, ring):
        i = pl.program_id(0)
        d2v = d2_ref[...]
        # the ring tile from the slab block alone (one small transpose per
        # plane; lo halo at lanes [OFF - s, OFF), hi halo at [0, s), zeros
        # between), then the interior plane behind it at lane offset OFF
        zst = jnp.swapaxes(zs_ref[0], 0, 1).astype(acc_dtype)  # (Yr, 2s)
        ring_tile = patch_z_halo(
            jnp.zeros((Yr, OFF), acc_dtype), zst, s_off, OFF - s_off, 0, roll
        )
        vals = jnp.concatenate([ring_tile, in_ref[0].astype(acc_dtype)], axis=1)
        for s in range(1, m + 1):
            prev = ring[s - 1, i % 2]
            cent = ring[s - 1, (i + 1) % 2]
            ring[s - 1, i % 2] = vals
            val = _level_sum(roll, prev, vals, cent) / 6.0
            x_g = jax.lax.rem(
                origin_ref[0] + jnp.int32(gx) + i - jnp.int32(s + s_off), jnp.int32(gx)
            )
            val = jnp.where(d2v < in_r2 - (x_g - hot_x) ** 2, HOT_TEMP, val)
            val = jnp.where(d2v < in_r2 - (x_g - cold_x) ** 2, COLD_TEMP, val)
            vals = val.astype(acc_dtype)
        # level-m plane i-m, interior lanes (the f32_accumulate downcast)
        out_ref[0] = vals[:, OFF:].astype(raw.dtype)
        # outgoing slabs: top interior cols [Zi-s, Zi) = lanes [W-s, W)
        # (the -z-bound message), bottom cols [0, s) = lanes [OFF, OFF+s)
        emit = jnp.concatenate(
            [vals[:, W - s_off : W], vals[:, OFF : OFF + s_off]], axis=1
        ).astype(raw.dtype)
        zout_ref[0] = jnp.swapaxes(emit, 0, 1)

    out_idx = lambda i: (jnp.maximum(i - m, 0), 0, 0)
    in_specs = [
        pl.BlockSpec(memory_space=pltpu.SMEM),
        pl.BlockSpec((1, Yr, Zi), lambda i: (i, 0, 0)),
        pl.BlockSpec((Yr, W), lambda i: (0, 0)),  # resident d2
        pl.BlockSpec((1, 2 * s_off, Yr), lambda i: (i, 0, 0)),
    ]
    args = [origin.astype(jnp.int32), raw, d2, z_slabs]
    return pl.pallas_call(
        kernel,
        name=tm.KERNEL_JACOBI_ZRING_WAVEFRONT,
        grid=(Xr,),
        in_specs=in_specs,
        out_specs=(
            pl.BlockSpec((1, Yr, Zi), out_idx),
            pl.BlockSpec((1, 2 * s_off, Yr), out_idx),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((Xr, Yr, Zi), raw.dtype),
            jax.ShapeDtypeStruct((Xr, 2 * s_off, Yr), raw.dtype),
        ),
        input_output_aliases={1: 0} if alias else {},
        scratch_shapes=[pltpu.VMEM((m, 2, Yr, W), acc_dtype)],
        interpret=interpret,
        **_tpu_compiler_params(interpret),
    )(*args)


def jacobi_slab_step(
    block: jax.Array,  # (X, Y, Z) bare interior — NO carried shell
    xlo: jax.Array,  # (Y, Z)  received from -x neighbor (its top plane)
    xhi: jax.Array,  # (Y, Z)  received from +x neighbor (its bottom plane)
    ylo: jax.Array,  # (X, Z)  received from -y neighbor (its top row per plane)
    yhi: jax.Array,  # (X, Z)  received from +y neighbor
    zlo: jax.Array,  # (Y, X)  received from -z neighbor, TRANSPOSED
    zhi: jax.Array,  # (Y, X)  received from +z neighbor, TRANSPOSED
    origin: jax.Array,  # (3,) int32 global coords of block start
    yz_d2: jax.Array,  # (Y, Z) int32 from yz_dist2_plane over the FULL plane
    global_size: Tuple[int, int, int],
    interpret: bool = False,
    f32_accumulate: bool = False,  # bf16-storage variant: the six-neighbor
    # mean is computed at f32 and rounded once at the store (single-level
    # kernel, so "accumulate" here is just the mean's arithmetic dtype)
) -> jax.Array:
    """One Jacobi iteration consuming received halo slabs DIRECTLY as kernel
    inputs — the multi-device fast path.

    The shell-carrying formulation pays for its generality twice per step:
    halo slabs are blended into the block (extra HBM writes + tile-local
    kernels) and the compute kernel then re-reads them as part of the
    (X+2r)-sized raw block.  Here the block is the bare interior; the six
    ppermuted face slabs ride into VMEM as small resident blocks and the
    plane-streaming kernel patches the boundary rows/columns with selects —
    one HBM read + one write per plane, zero halo writes, exactly the traffic
    of the single-device wrap kernel.  This is the TPU expression of the
    reference's overlapped multi-GPU pipeline (jacobi3d.cu:265-337): where
    the GPU hides exchange latency behind interior kernels, the TPU folds the
    received bytes into the one pass that was already reading the domain.

    Slab layouts are chosen for the TPU tiled memory model: y-slabs are
    (X, Z) 2D arrays (plane-major, lanes on z) and z-slabs arrive TRANSPOSED
    as (Y, X) (lanes on x) — a (X, Y, 1) column slab would lane-pad 128x in
    HBM and VMEM.  Per output plane the kernel reads one dynamic row/column
    from each resident slab.

    Summation order matches ``jacobi_wrap_step``/``jacobi_plane_step``:
    (x-1) + (x+1) + (y-1) + (y+1) + (z-1) + (z+1), so a mesh-[1,1,1] run
    (self-permuted slabs = periodic wrap) is bit-identical to the wrap path.
    """
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    X, Y, Z = block.shape
    # at X == 1 the i == 1 and i == X branches both fire and the second reads
    # ring[1], which is never written — shards must carry >= 2 x-planes
    assert X >= 2, f"jacobi_slab_step requires X >= 2 planes per shard, got {X}"
    gx = global_size[0]
    hot_x, cold_x, in_r2 = sphere_params(gx)

    roll = _make_roll(interpret)

    def kernel(
        origin_ref, in_ref, xlo_ref, xhi_ref, ylo_ref, yhi_ref, zlo_ref, zhi_ref,
        d2_ref, out_ref, ring,
    ):
        i = pl.program_id(0)
        cur = in_ref[0]

        def compute(prev, cent, nxt, o):
            up = roll(cent, 1, 0)
            down = roll(cent, -1, 0)
            left = roll(cent, 1, 1)
            right = roll(cent, -1, 1)
            row = lax.broadcasted_iota(jnp.int32, (Y, Z), 0)
            col = lax.broadcasted_iota(jnp.int32, (Y, Z), 1)
            # boundary rows/cols: the roll wrapped within the block; patch
            # with the neighbor's received face cells
            up = jnp.where(row == 0, ylo_ref[pl.ds(o, 1), :], up)
            down = jnp.where(row == Y - 1, yhi_ref[pl.ds(o, 1), :], down)
            # dynamic LANE slicing is not supported (lane offsets must be
            # 128-aligned); rotate column o to lane 0 and slice statically
            def zcol(ref):
                if interpret:
                    return jnp.roll(ref[...], -o, axis=1)[:, 0:1]
                return roll(ref[...], X - o, 1)[:, 0:1]

            left = jnp.where(col == 0, zcol(zlo_ref), left)
            right = jnp.where(col == Z - 1, zcol(zhi_ref), right)
            if f32_accumulate:
                prev, nxt, up, down, left, right = (
                    t.astype(jnp.float32)
                    for t in (prev, nxt, up, down, left, right)
                )
            val = (prev + nxt + up + down + left + right) / 6.0
            x_g = (origin_ref[0] + o) % gx
            d2 = d2_ref[...]
            val = jnp.where(d2 < in_r2 - (x_g - hot_x) ** 2, HOT_TEMP, val)
            val = jnp.where(d2 < in_r2 - (x_g - cold_x) ** 2, COLD_TEMP, val)
            out_ref[0] = val.astype(cur.dtype)

        @pl.when(i == 1)
        def _():
            compute(xlo_ref[...], ring[0], cur, 0)

        @pl.when(jnp.logical_and(i >= 2, i <= X - 1))
        def _():
            compute(ring[i % 2], ring[(i + 1) % 2], cur, i - 1)

        @pl.when(i == X)
        def _():
            compute(ring[i % 2], ring[(i + 1) % 2], xhi_ref[...], X - 1)

        @pl.when(i <= X - 1)
        def _():
            ring[i % 2] = cur

    const = lambda *shape: pl.BlockSpec(shape, lambda i: (0,) * len(shape))
    return pl.pallas_call(
        kernel,
        name=tm.KERNEL_JACOBI_SLAB,
        grid=(X + 1,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, Y, Z), lambda i: (jnp.minimum(i, X - 1), 0, 0)),
            const(Y, Z),  # xlo — fetched once, resident
            const(Y, Z),  # xhi
            const(X, Z),  # ylo
            const(X, Z),  # yhi
            const(Y, X),  # zlo (transposed)
            const(Y, X),  # zhi (transposed)
            const(Y, Z),  # yz_d2
        ],
        out_specs=pl.BlockSpec((1, Y, Z), lambda i: (jnp.maximum(i - 1, 0), 0, 0)),
        out_shape=jax.ShapeDtypeStruct((X, Y, Z), block.dtype),
        scratch_shapes=[pltpu.VMEM((2, Y, Z), block.dtype)],
        interpret=interpret,
        **_tpu_compiler_params(interpret),
    )(
        origin.astype(jnp.int32),
        block,
        xlo, xhi, ylo, yhi, zlo, zhi,
        yz_d2.astype(jnp.int32),
    )


def jacobi_plane_step(
    block: jax.Array,
    origin: jax.Array,  # (3,) int32: global coords of this shard's interior start
    yz_d2: jax.Array,  # (Y-2, Z-2) int32 from yz_dist2_plane
    global_size: Tuple[int, int, int],
    interpret: bool = False,
    f32_accumulate: bool = False,  # bf16-storage variant: f32 mean, one
    # downcast at the interior store (halo ring passes through untouched)
) -> jax.Array:
    """One Jacobi iteration over a radius-1 shell-carrying block (X, Y, Z)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    X, Y, Z = block.shape
    gx = global_size[0]
    hot_x, cold_x, in_r2 = sphere_params(gx)

    def kernel(origin_ref, in_ref, d2_ref, out_ref, ring):
        i = pl.program_id(0)
        cur = in_ref[0]

        @pl.when(i == 0)
        def _():
            out_ref[0] = cur  # -x halo plane passes through

        @pl.when(jnp.logical_and(i >= 2, i <= X - 1))
        def _():
            prev = ring[i % 2]  # plane i-2
            cent = ring[(i + 1) % 2]  # plane i-1
            up = (
                (lambda v: v.astype(jnp.float32))
                if f32_accumulate
                else (lambda v: v)
            )
            mean = (
                up(prev[1:-1, 1:-1])
                + up(cur[1:-1, 1:-1])
                + up(cent[:-2, 1:-1])
                + up(cent[2:, 1:-1])
                + up(cent[1:-1, :-2])
                + up(cent[1:-1, 2:])
            ) / 6.0
            # raw plane i-1 -> interior x = i-2; sphere test per cell is just
            # a compare of the precomputed y/z distances against a scalar
            x_g = (origin_ref[0] + i - 2) % gx
            d2 = d2_ref[...]
            val = jnp.where(d2 < in_r2 - (x_g - hot_x) ** 2, HOT_TEMP, mean)
            val = jnp.where(d2 < in_r2 - (x_g - cold_x) ** 2, COLD_TEMP, val)
            out_ref[0] = cent  # keep the y/z halo ring
            out_ref[0, 1:-1, 1:-1] = val.astype(cur.dtype)

        @pl.when(i == X)
        def _():
            out_ref[0] = ring[(i + 1) % 2]  # +x halo plane (X-1) passes through

        # ring update: store the current input plane (skip the replayed last
        # plane at i == X so the ring stays consistent)
        @pl.when(i <= X - 1)
        def _():
            ring[i % 2] = cur

    return pl.pallas_call(
        kernel,
        name=tm.KERNEL_JACOBI_PLANE,
        grid=(X + 1,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, Y, Z), lambda i: (jnp.minimum(i, X - 1), 0, 0)),
            # constant index map: fetched once, stays resident in VMEM
            pl.BlockSpec((Y - 2, Z - 2), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, Y, Z), lambda i: (jnp.clip(i - 1, 0, X - 1), 0, 0)),
        out_shape=jax.ShapeDtypeStruct((X, Y, Z), block.dtype),
        scratch_shapes=[pltpu.VMEM((2, Y, Z), block.dtype)],
        interpret=interpret,
        **_tpu_compiler_params(interpret),
    )(origin.astype(jnp.int32), block, yz_d2.astype(jnp.int32))
