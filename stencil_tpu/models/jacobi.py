"""7-point Jacobi heat stencil with hot/cold sphere forcing.

Parity target: reference bin/jacobi3d.cu — the flagship app.  Semantics
replicated exactly:

* single float quantity, radius-1 faces-only stencil (jacobi3d.cu:205-214,227)
* init: whole domain at (HOT+COLD)/2 (jacobi3d.cu:15-29)
* forcing (jacobi3d.cu:40-66): a hot sphere (radius = X/10) centered at
  (X/3, Y/2, Z/2) is clamped to HOT each step; a cold sphere at (2X/3, Y/2,
  Z/2) clamped to COLD; elsewhere next = mean of the 6 face neighbors.
  ``dist`` is the reference's float-sqrt truncated to integer
  (jacobi3d.cu:31-33).
* iteration: overlapped interior/exchange/exterior pipeline or single
  whole-region kernel under --no-overlap (jacobi3d.cu:265-337).
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from jax import shard_map

from stencil_tpu.core.dim3 import Dim3
from stencil_tpu.core.radius import Radius
from stencil_tpu.domain import DistributedDomain
from stencil_tpu.ops.stream import macro_loop as _macro_loop
from stencil_tpu.ops.stream_plan import macros_per_trip as _macros_per_trip
from stencil_tpu.utils.config import MethodFlags, PlacementStrategy

COLD_TEMP = 0.0
HOT_TEMP = 1.0


class Jacobi3D:
    def __init__(
        self,
        x: int,
        y: int,
        z: int,
        overlap: bool = True,
        strategy: PlacementStrategy = PlacementStrategy.NodeAware,
        methods: MethodFlags = MethodFlags.All,
        devices=None,
        dtype=jnp.float32,
        kernel_impl: str = "jnp",  # "jnp" (XLA slices) | "pallas" (plane streaming)
        interpret: bool = False,  # pallas interpreter mode (CPU testing)
        temporal_k="auto",  # wrap-path temporal blocking depth (int | "auto")
        pallas_path: str = "auto",  # "auto"|"wrap"|"slab"|"shell"|"wavefront"
        check_divergence_every: int = 0,  # divergence sentinel cadence
        # (resilience/sentinel.py); 0 = off
        wavefront_alias: bool = None,  # input_output_aliases on the wavefront
        # kernels: None = env (STENCIL_WAVEFRONT_ALIAS) > tuned config >
        # un-aliased static default; the autotuner's candidate builds set it
        # explicitly
        z_ring: bool = None,  # z-RING vs padded layout preference: None =
        # env (STENCIL_Z_RING) > tuned config > ring default; structural
        # gates (lane alignment, slab mode) still apply either way
        storage_dtype: str = None,  # field buffers' storage axis ("native"
        # | "bf16" | None/"auto"): bf16 stores f32 fields at 2 B/cell
        # end-to-end (HBM, VMEM pipeline, exchange messages) while the
        # kernels accumulate at f32 and downcast once per pass.  None/
        # "auto" = STENCIL_STORAGE_DTYPE > tuned config > static native;
        # non-f32 fields and the XLA engine degrade to native with a warning
    ):
        self.dd = DistributedDomain(x, y, z)
        # radius 1 on faces only (jacobi3d.cu:205-214)
        radius = Radius.constant(0)
        radius.set_face(1)
        self.dd.set_radius(radius)
        self.dd.set_methods(methods)
        self.dd.set_placement(strategy)
        if devices is not None:
            self.dd.set_devices(devices)
        self.h = self.dd.add_data("temp", dtype=dtype)
        self.overlap = overlap
        self.kernel_impl = kernel_impl
        self.interpret = interpret
        self.temporal_k = temporal_k
        if pallas_path not in ("auto", "wrap", "slab", "shell", "wavefront"):
            raise ValueError(f"unknown pallas_path {pallas_path!r}")
        self.pallas_path_request = pallas_path
        self.wavefront_alias_request = wavefront_alias
        self.z_ring_request = z_ring
        self.storage_dtype_request = storage_dtype
        self._storage_dtype = "native"  # resolved by realize()
        if check_divergence_every:
            self.dd.set_divergence_check(check_divergence_every)
        # tuned config applied by _plan_wavefront (auto mode only)
        self._tuned_wavefront = None
        self._step = None
        self._ladder = None  # degradation ladder, built at realize()
        # fast paths (wrap/slab kernels) advance interiors only; the carried
        # shell goes stale and raw readback must re-exchange (mark_shell_stale)
        self._marks_shell_stale = False
        # which pallas route realize() picked:
        # "wrap" | "wavefront" | "slab" | "shell"
        self._pallas_path = None

    def realize(self) -> None:
        self._wavefront_m = 0
        # storage dtype resolves FIRST: it shapes the allocation and the
        # VMEM-model itemsizes every later plan (wavefront fits, temporal-k)
        # consults
        self._resolve_storage()
        if self.kernel_impl == "pallas" and self.pallas_path_request in ("auto", "wavefront"):
            # must be decided BEFORE dd.realize(): the wavefront path rides
            # the halo-multiplier machinery (m-wide shells, exchange every m
            # steps), which shapes the allocation
            if self.pallas_path_request == "wavefront":
                self._wavefront_m = self._plan_wavefront()  # raises if not viable
            elif self.dd.halo_multiplier() == 1 and self._planned_devices() > 1:
                try:
                    m = self._plan_wavefront()
                except ValueError:
                    m = 0  # uneven sizes etc. — slab/shell routes handle it
                # depth 1 buys nothing over the slab route; require real blocking
                self._wavefront_m = m if m >= 2 else 0
            if self._wavefront_m:
                self.dd.set_halo_multiplier(self._wavefront_m)
        self.dd.realize()
        # set compute region to (HOT+COLD)/2 (jacobi3d.cu:15-29, 253-263)
        mid = (HOT_TEMP + COLD_TEMP) / 2
        self.dd.init_by_coords(self.h, lambda x, y, z: jnp.full((), mid) + 0 * (x + y + z))
        # shipped numerics guardband (docs/observability.md "Numerics
        # observatory"): jacobi's clamped mean-of-6 update obeys the
        # diffusion max principle — the field can never leave [COLD, HOT];
        # a cell outside the band is numerical drift long before anything
        # overflows to inf.  Registration is idempotent (keyed by label);
        # it fires only on the numerics cadence, so an unsnapshotted run
        # pays nothing.
        from stencil_tpu.telemetry.numerics import max_principle

        # band widened by 1e-5 of the span: the f32-accumulated mean can
        # legitimately overshoot the exact bound by a few ulps (six adds at
        # magnitude ~6 before the divide) — the guardband hunts drift, not
        # last-ulp rounding
        pad = 1e-5 * (HOT_TEMP - COLD_TEMP)
        self.dd.numerics().register_guardband(
            max_principle(
                COLD_TEMP - pad, HOT_TEMP + pad, quantities=(self.h.name,)
            )
        )
        if self.kernel_impl == "pallas":
            if self._wavefront_m:
                self._step = self._make_wavefront_step()
            else:
                # the plane-streaming kernel hard-codes a 1-cell shell ring
                if self.dd.halo_multiplier() != 1:
                    raise ValueError(
                        "kernel_impl='pallas' requires halo multiplier 1 "
                        "(the plane kernel assumes a radius-1 shell); use "
                        "kernel_impl='jnp' with set_halo_multiplier, or "
                        "pallas_path='wavefront' which sets its own"
                    )
                self._step = self._make_pallas_step()
        else:
            self._step = self.dd.make_step(self._kernel, overlap=self.overlap)
        self._ladder = self._make_ladder()

    def _planned_devices(self) -> int:
        import jax

        devs = self.dd._devices
        return len(devs) if devs is not None else len(jax.devices())

    def _prospective_tune_route(self):
        """The workload-key route the build WILL consult (pre-realize
        mirror of the route choice) — where the tuned storage-dtype field
        lives; None when no tunable pallas route can be
        reached (jnp engine, forced slab/shell)."""
        if self.kernel_impl != "pallas":
            return None
        req = self.pallas_path_request
        single = self._planned_devices() == 1
        if req == "wrap" or (req == "auto" and single):
            return "jacobi-wrap"
        if req in ("auto", "wavefront") and not single:
            return "jacobi-wavefront"
        return None

    def _resolve_storage(self) -> None:
        """Resolve the storage-dtype axis (explicit ctor knob >
        ``STENCIL_STORAGE_DTYPE`` > tuned config > static ``native`` —
        ops/jacobi_pallas.resolve_storage_dtype) and pin the result on the
        domain BEFORE allocation.  The XLA engine has no f32-accumulate
        kernels, so it structurally degrades bf16 to native."""
        from stencil_tpu.ops.jacobi_pallas import resolve_storage_dtype

        route = self._prospective_tune_route()
        tuned = None
        if self.storage_dtype_request in (None, "auto") and route is not None:
            from stencil_tpu import tune

            cfg = tune.best_config(self.dd.tune_key(route))
            tuned = (cfg or {}).get("storage_dtype")
        sd, _src = resolve_storage_dtype(
            self.storage_dtype_request,
            tuned,
            [self.h.dtype],
            where=f"jacobi:{route or self.kernel_impl}",
            engine_ok=self.kernel_impl == "pallas",
            engine_why="the XLA slice engine has no f32-accumulate kernels",
        )
        self._storage_dtype = sd
        if sd != "native":
            self.dd.set_storage(sd)

    def _plan_wavefront(self) -> int:
        """Choose the wavefront depth m (>= 1) before ``dd.realize()``: mirror
        the domain's deterministic mesh/shard computation and fit
        ``temporal_k`` ("auto") within the shard extents and the modeled VMEM
        limit.  Prefers the z-slab kernel variant (z halos never touch the
        tiled array) and records the choice in ``self._wavefront_z_planned``.

        PADDED (uneven) shards are supported on the PLAIN kernel variant:
        the valid-width exchange places each halo contiguously after the
        valid cells, so the wavefront's shrinking-validity and
        wrapped-coordinate arguments hold unchanged at the dynamic positions
        (see ``ops/stream_plan.plan_stream``); the z-slab form's static interior
        emit slices keep it even-shard-only, and the depth is capped by the
        smallest VALID extent (partition.hpp:83-114 parity: remainders run
        at full speed)."""
        import jax

        from stencil_tpu.ops.jacobi_pallas import (
            _WRAP_MAX_K,
            warn_if_over_vmem_budget,
            wavefront_vmem_fits,
        )
        from stencil_tpu.parallel.mesh import make_mesh

        dd = self.dd
        if dd.halo_multiplier() != 1:
            raise ValueError("pallas_path='wavefront' manages the halo multiplier itself")
        devices = list(dd._devices) if dd._devices is not None else jax.devices()
        _, placement = make_mesh(
            dd._size, dd._radius, devices, dd._strategy, force_dim=dd._force_dim
        )
        dim = placement.dim()
        n = [-(-dd._size[ax] // dim[ax]) for ax in range(3)]
        padded = any(dd._size[ax] != n[ax] * dim[ax] for ax in range(3))
        # last-shard valid extents; min caps the depth (a shard must fill an
        # m-wide halo for its neighbor from valid cells)
        v = [dd._size[ax] - n[ax] * (dim[ax] - 1) for ax in range(3)]
        if min(v) < 1:
            raise ValueError(
                f"pallas_path='wavefront': empty last shard for {tuple(dd._size)} "
                f"over mesh {tuple(dim)}"
            )
        n_min = min(min(n), min(v))
        # pipeline planes stream at the STORAGE itemsize; the level ring
        # carries the f32_accumulate working precision (native itemsize)
        itemsize = self.dd.field_dtype(self.h).itemsize
        ring_itemsize = self.h.dtype.itemsize
        # planning diagnostics for the autotuner's candidate-space builder
        # (tune/runners.autotune_jacobi_wavefront)
        self._wavefront_plan_info = {
            "n": tuple(n), "valid": tuple(v), "padded": padded, "n_min": n_min,
        }

        def fits(m, z):
            return wavefront_vmem_fits(
                m, n[1] + 2 * m, n[2] + 2 * m, itemsize, z_slabs=z,
                ring_itemsize=ring_itemsize,
            )

        if self.temporal_k != "auto":
            m = int(self.temporal_k)
            if not 1 <= m <= n_min:
                raise ValueError(
                    f"wavefront temporal_k={m} needs 1 <= m <= min(shard/valid)={n_min}"
                )
            warn_if_over_vmem_budget(m, n[1] + 2 * m, n[2] + 2 * m, itemsize,
                                     ring_itemsize)
            self._wavefront_z_planned = fits(m, True) and not padded
            return m
        # the autotuner's persisted on-device measurement beats the static
        # model below (docs/tuning.md); only structural bounds are
        # re-checked — a tuned m may exceed the shell-traffic heuristic cap,
        # that is the point of measuring
        from stencil_tpu import tune

        cfg = tune.best_config(dd.tune_key("jacobi-wavefront"))
        if cfg is not None:
            m = cfg.get("m")
            if isinstance(m, int) and 1 <= m <= n_min:
                self._tuned_wavefront = cfg
                self._wavefront_z_planned = fits(m, True) and not padded
                return m
            from stencil_tpu.utils.logging import log_warn

            log_warn(
                f"tuned config {cfg} for jacobi-wavefront is structurally "
                f"invalid here (need 1 <= m <= {n_min}); using the static plan"
            )
        # n_min//4 caps the redundant shell traffic: a depth-m macro step
        # exchanges ~6*m*n^2 extra cells against m*n^3 of compute, so keep
        # the shell a small fraction of the shard
        depth_cap = min(_WRAP_MAX_K, max(1, n_min // 4), n_min)
        for z_mode in ((True, False) if not padded else (False,)):
            m = 1 if not z_mode else 0
            for cand in range(2, depth_cap + 1):
                if fits(cand, z_mode):
                    m = cand
            if m >= 2 or not z_mode:
                self._wavefront_z_planned = z_mode and m >= 2
                return max(m, 1)
        raise AssertionError("unreachable: z_mode=False always returns")

    def _make_wavefront_step(self):
        """Temporally-blocked multi-device step: one m-wide shell exchange
        feeds an m-level wavefront kernel (``jacobi_shell_wavefront_step``) —
        ~8/m HBM bytes per cell per iteration, the multi-device counterpart
        of the wrap path's temporal blocking.  A steps%m remainder runs one
        shallower wavefront over the same shell.

        The z halos never touch the big array (``STENCIL_Z_SLABS=0``
        disables): a z-halo read or write on the tiled layout rewrites whole
        (8,128)-tile columns (~a full-domain pass per exchange, probe12d),
        so the z-shell lives in a separate z-major (Xr, 2m, Yr) packed slab
        array (rows [0,m) = low halo, [m,2m) = high) that the kernel
        consumes (VMEM column patching via one small per-plane transpose,
        inside the lane tiles that hold the halo: ``patch_z_halo``)
        and emits (next macro's outgoing slabs).  Corner data propagates on the slabs themselves:
        after the z ppermute, each slab is extended with rows from the y
        neighbors and then planes from the x neighbors (two hops carry the
        xyz-corner cells from the diagonal blocks), mirroring the sweep
        order of the in-array exchange.

        The macro loop (``_macro_loop``) runs ``_macros_per_trip`` macros a
        ``fori_loop`` trip: TWO while the kernel writes a fresh result (the
        default: in place serialises the deep-m pipeline, see ``alias``
        below), so that the second result lands in the buffer the trip's
        operand died in and the carry is back in its own place -- with one a
        trip XLA copied the whole block every macro (7.2% of
        ``jacobi3d-512x4.bulk``); ONE where ``alias`` resolves true, the loop
        that was always there.  The kernels themselves stay un-aliased: an
        ``input_output_aliases`` would bring the serialisation back, and the
        benchmark's ``pallas_hbm_pct`` reads only calls whose result aliases
        no operand.  ``domain.step`` says ``macros_per_trip``."""
        from functools import partial

        import jax
        from jax import lax
        from jax.sharding import PartitionSpec as P

        from stencil_tpu.ops.exchange import halo_exchange_shard
        from stencil_tpu.ops.jacobi_pallas import (
            _ZRING_OFF,
            jacobi_shell_wavefront_step,
            jacobi_zring_wavefront_step,
            pack_d2,
            yz_dist2_plane,
            z_halo_patch_form,
            zring_dist2_plane,
        )
        from stencil_tpu.ops.stream_pass import lane_pad_width
        from stencil_tpu.ops.stream import (
            make_slab_extenders,
            permute_and_extend_z_slabs,
            prime_z_slabs,
        )
        from stencil_tpu.parallel.mesh import MESH_AXES

        dd = self.dd
        m = self._wavefront_m
        # effective depth <= the allocated shell width m: the VMEM-OOM
        # fallback steps it down WITHOUT reallocating (the kernel supports
        # depth < shell via interior_offset; the exchange keeps the full
        # m-wide shell, just refreshed every `depth` steps)
        depth_run = getattr(self, "_wavefront_depth", m)
        assert 1 <= depth_run <= m, (depth_run, m)
        n = dd.local_spec().sz
        shell = dd._shell_radius
        mesh_shape = tuple(dd.mesh.shape[a] for a in MESH_AXES)
        gsize = tuple(dd.size())
        raw = dd.local_spec().raw_size()
        interpret = self.interpret
        name = self.h.name
        from stencil_tpu.utils.config import env_bool

        tuned = self._tuned_wavefront or {}
        f32_acc = dd.field_dtype(self.h) != self.h.dtype
        z_slab_mode = env_bool("STENCIL_Z_SLABS", True) and getattr(
            self, "_wavefront_z_planned", False
        )
        # In-place aliasing serializes the deep-m pipeline (probe21b, 512^3:
        # m=16 aliased 84k vs un-aliased 102k Mcells/s) — default to a fresh
        # output buffer and trade one raw-sized HBM allocation for ~20%.
        # The un-aliased kernel leaves high-x shell planes UNINITIALIZED;
        # every consumer (next macro's exchange, stale-shell readback)
        # rewrites the shell before reading it, so no garbage escapes.
        # Precedence: constructor request (autotuner candidate builds) >
        # STENCIL_WAVEFRONT_ALIAS (validated read) > the tuned config for
        # this workload > the un-aliased static default above.
        if self.wavefront_alias_request is not None:
            alias = bool(self.wavefront_alias_request)
        else:
            env_alias = env_bool("STENCIL_WAVEFRONT_ALIAS", None)
            if env_alias is not None:
                alias = env_alias
            elif tuned.get("alias") is not None:
                alias = bool(tuned["alias"])
            else:
                alias = False
        per_trip = _macros_per_trip(alias)
        self._marks_shell_stale = True
        self._pallas_path = "wavefront"
        self._wavefront_z_slabs = z_slab_mode
        Xr, Yr, Zr = raw.x, raw.y, raw.z
        # z-RING layout: when the shard's z interior is lane-aligned, drop
        # the z-shell columns from HBM entirely — the kernel stages each
        # plane into a ring-layout working plane whose lane wrap is
        # periodic-consistent (jacobi_zring_wavefront_step) — cutting the
        # streamed bytes by the whole z pad share (~20% at 512^3 m=16,
        # probe24/25).  STENCIL_Z_RING=0 restores the padded layout.
        # ring preference: constructor request > STENCIL_Z_RING (validated
        # read) > the tuned config's measured layout pick > ring by default
        # (probe25d: neutral wall-clock on v5e, smaller footprint)
        if self.z_ring_request is not None:
            ring_pref = bool(self.z_ring_request)
        else:
            env_ring = env_bool("STENCIL_Z_RING", None)
            if env_ring is not None:
                ring_pref = env_ring
            elif tuned.get("z_ring") is not None:
                ring_pref = bool(tuned["z_ring"])
            else:
                ring_pref = True
        z_ring_mode = (
            z_slab_mode
            and n.z % 128 == 0
            and 2 * m <= _ZRING_OFF
            and ring_pref
        )
        self._wavefront_z_ring = z_ring_mode
        # Ragged lane extents cripple the plane DMA (probe22: 512^2x516
        # streams 30% slower than 512^3; 512^2x640 runs at full per-byte
        # rate), so the z-slab route rounds the plane width up to a 128
        # multiple with dead columns the kernel treats as outside the domain
        # (z_valid).  Padding/unpadding happens once per step() dispatch,
        # amortized over the device-side macro loop.
        Zp = lane_pad_width(Zr) if z_slab_mode else Zr
        def per_shard(steps, raw_block):
            # origin (and everything derived from it, like the d2 planes)
            # must be computed INSIDE each loop body: axis_index lowers to
            # partition-id, which XLA's SPMD partitioner rejects as a
            # while-loop operand on some toolchains (see ops/stream.py
            # origin_of; LICM re-hoists it after partitioning)
            def origin_of():
                return jnp.stack(
                    [lax.axis_index(MESH_AXES[ax]) * n[ax] for ax in range(3)]
                )

            def d2_of(origin):
                return pack_d2(
                    yz_dist2_plane(
                        origin[1] - m, origin[2] - m, (raw.y, Zp), gsize
                    ),
                    gsize,
                )

            if not z_slab_mode:
                def macro_plain(depth, b):
                    origin = origin_of()
                    yz_d2 = d2_of(origin)
                    b = halo_exchange_shard(
                        b, shell, mesh_shape, valid_last=dd._valid_last
                    )
                    return jacobi_shell_wavefront_step(
                        b, depth, origin, yz_d2, gsize, interior_offset=m,
                        alias=alias, interpret=interpret,
                        f32_accumulate=f32_acc,
                    )

                macros, rem = divmod(steps, depth_run)
                b = _macro_loop(
                    partial(macro_plain, depth_run), macros, raw_block, per_trip
                )
                if rem:
                    b = macro_plain(rem, b)
                return b

            # slab y/x extension (corner propagation) + z permute + priming
            # are shared with the generic engine (ops/stream.py helpers)
            yext, xext = make_slab_extenders(Xr, Yr, m, mesh_shape)

            if z_ring_mode:
                # z-interior-only HBM layout + ring-layout working planes
                Zi = n.z

                def macro_ring(depth, carry):
                    origin = origin_of()
                    ring_d2 = pack_d2(
                        zring_dist2_plane(
                            origin[1] - m, origin[2], m, Yr, Zi, gsize
                        ),
                        gsize,
                    )
                    b, zout = carry
                    b = halo_exchange_shard(b, shell, mesh_shape, axes=(0, 1))
                    zs = permute_and_extend_z_slabs(zout, m, mesh_shape, yext, xext)
                    return jacobi_zring_wavefront_step(
                        b, depth, origin, ring_d2, gsize, z_slabs=zs,
                        interior_offset=m, alias=alias, interpret=interpret,
                        f32_accumulate=f32_acc,
                    )

                b0 = lax.slice(
                    raw_block, (0, 0, m), (Xr, Yr, m + Zi)
                )  # drop the z-shell columns from the streamed array
                carry = (b0, prime_z_slabs(raw_block, Zr, m))
                macros, rem = divmod(steps, depth_run)
                carry = _macro_loop(
                    partial(macro_ring, depth_run), macros, carry, per_trip
                )
                if rem:
                    carry = macro_ring(rem, carry)
                # re-inflate with zero z-shell columns instead of writing
                # back into raw_block: equivalent (the shell is stale either
                # way) and lets raw_block's buffer die at the b0 slice
                # instead of living across the whole macro loop
                return jnp.pad(carry[0], ((0, 0), (0, 0), (m, m)))

            def macro(depth, carry):
                origin = origin_of()
                yz_d2 = d2_of(origin)
                b, zout = carry
                # x/y shells in the array (cheap: planes / sublane rows)
                b = halo_exchange_shard(b, shell, mesh_shape, axes=(0, 1))
                # zout is z-major (Xr, 2m, Yr): [(-z)-bound | (+z)-bound]
                zs = permute_and_extend_z_slabs(zout, m, mesh_shape, yext, xext)
                return jacobi_shell_wavefront_step(
                    b, depth, origin, yz_d2, gsize, interior_offset=m,
                    z_slabs=zs, z_valid=Zr, alias=alias, interpret=interpret,
                    f32_accumulate=f32_acc,
                )

            # prime the slab carry from the block's interior z boundaries
            # (z-major), then lane-pad the block
            carry = (
                jnp.pad(raw_block, ((0, 0), (0, 0), (0, Zp - Zr))),
                prime_z_slabs(raw_block, Zr, m),
            )
            macros, rem = divmod(steps, depth_run)
            carry = _macro_loop(partial(macro, depth_run), macros, carry, per_trip)
            if rem:
                carry = macro(rem, carry)
            return carry[0][:, :, :Zr]

        spec = P(*MESH_AXES)

        @partial(jax.jit, static_argnums=1, donate_argnums=0)
        def step(curr, steps: int = 1):
            # check_vma off: pallas_call outputs carry no vma annotation
            fn = shard_map(
                partial(per_shard, steps),
                mesh=dd.mesh,
                in_specs=(spec,),
                out_specs=spec,
                check_vma=False,
            )
            return {name: fn(curr[name])}

        # what a macro sends over wires (``run_step``'s counters, and the
        # span's ``wired`` / ``wire_bytes`` a raw step): the m-wide shell of
        # the block as the macro exchanges it -- the raw block whole, or its
        # x and y sweeps (in ring mode over the z interior alone) beside the
        # z-slab buffers' permutes
        from stencil_tpu.ops.exchange import (
            WireAccount,
            exchange_account,
            slab_wrap_axes,
            sum_hops,
            z_slab_hops,
        )

        dtype = dd.field_dtype(self.h)
        if z_slab_mode:
            swept = exchange_account(mesh_shape, shell, (Xr, Yr, n.z if z_ring_mode else Zp),
                                     [dtype], axes=(0, 1))
            hops = sum_hops(swept.hops, z_slab_hops(mesh_shape, Xr, Yr, m, [dtype.itemsize]))
        else:
            swept = exchange_account(
                mesh_shape, shell, (Xr, Yr, Zr), [dtype], valid_last=dd._valid_last)
            hops = swept.hops
        span_args = {"macros_per_trip": per_trip}
        if z_slab_mode:
            # where the kernel patches its z halo, read off the working
            # plane's width as the kernel's own helper reads it
            span_args["z_halo_patch"] = z_halo_patch_form(
                _ZRING_OFF + n.z if z_ring_mode else Zp, m
            )
            # ... and the axes on which a macro's slab extension is the
            # self-wrap kernel, nothing sent to oneself
            span_args["slab_wrap"] = slab_wrap_axes(mesh_shape, Xr, Yr, m, [dtype])
        return self._declare_wires(
            step, WireAccount(1, hops, depth_run, joint=swept.joint), **span_args)

    def _make_pallas_step(self):
        """Fused exchange + plane-streaming pallas kernel (ops/jacobi_pallas):
        one HBM read + one write per plane per iteration, vs ~6 reads for the
        XLA slice formulation.

        Three routes, fastest applicable wins (``self._pallas_path`` records
        the choice):

        * ``wrap``  — 1 subdomain: periodic wrap folds into the kernel, no
          exchange at all.
        * ``slab``  — multi-device, even sizes: 6 bare face-slab ppermutes
          consumed DIRECTLY by the kernel (``jacobi_slab_step``) — no shell
          writes, no halo re-read; the traffic of the wrap kernel plus the 6
          messages.  The TPU expression of the reference's production
          overlapped multi-GPU pipeline (jacobi3d.cu:265-337).
          SUPERSEDED as a default by the temporally-blocked ``wavefront``
          (m levels per exchange vs this route's 1); kept for explicit
          request and as the m=1 structural baseline.  Its Mosaic
          z-column-rotate constraint (128-aligned shard x-extent) makes it
          unreachable for most real mesh shapes — by design we did not lift
          it, since the wavefront route both outperforms it and has no such
          constraint.
        * ``shell`` — fallback (uneven/padded sizes, or shards with < 2
          x-planes): the general shell-carrying exchange + plane kernel.

        The wrap route's macro loop (``_macro_loop``) runs TWO k-level
        ``jacobi_wrap_step`` calls a ``fori_loop`` trip: the kernel writes a
        fresh result (in place, the replay at its wrap-around would re-read
        planes it has already overwritten), and the second result of a trip
        takes the buffer the trip's operand died in, so the carry is back in
        its own place -- with one a trip XLA copied the whole block every
        macro (10.4% of ``jacobi3d-512.bulk``).  The kernel stays un-aliased, onto its input
        or anything else: the benchmark's ``pallas_hbm_pct`` reads only calls
        whose result aliases no operand.  An odd macro runs behind the loop,
        before the ``steps % k`` remainder; ``domain.step`` says
        ``macros_per_trip``.
        """
        from functools import partial

        import jax
        from jax import lax
        from jax.sharding import PartitionSpec as P

        from stencil_tpu.ops.exchange import WireAccount, exchange_account, halo_exchange_shard
        from stencil_tpu.ops.jacobi_pallas import (
            choose_temporal_k,
            jacobi_plane_step,
            jacobi_wrap_step,
            yz_dist2_plane,
        )
        from stencil_tpu.parallel.mesh import MESH_AXES

        dd = self.dd
        want = self.pallas_path_request
        if want == "wrap" and dd.num_subdomains() != 1:
            raise ValueError("pallas_path='wrap' requires a single subdomain")
        # the slab kernel's z-column dynamic rotate (pltpu.roll on a (Y, X)
        # slab) compiles only when the lane extent X is 128-aligned (Mosaic
        # "unsupported unaligned shape" otherwise — scripts/probe11b at 64^3)
        slab_aligned = self.interpret or dd.local_spec().sz.x % 128 == 0
        if want == "slab" and (
            any(v is not None for v in dd._valid_last)
            or dd.local_spec().sz.x < 2
            or not slab_aligned
        ):
            raise ValueError(
                "pallas_path='slab' requires even (unpadded) sizes, >= 2 "
                "x-planes per shard, and a 128-aligned x-extent per shard "
                "when compiled for TPU"
            )
        if want == "wrap" or (want == "auto" and dd.num_subdomains() == 1):
            # single-device fast path: the periodic wrap folds into the
            # kernel's index maps/rotates — no shell reads, no exchange (the
            # reference's same-GPU translate kernels disappear too).  The
            # shell-carrying HBM layout is kept; interior is sliced out once
            # per dispatch and written back (amortized over `steps`).
            spec_ = dd.local_spec()
            n = spec_.sz
            lo = dd._shell_radius.lo()
            name = self.h.name
            interpret = self.interpret
            self._marks_shell_stale = True
            self._pallas_path = "wrap"
            # pipeline planes stream at the STORAGE itemsize; the level
            # ring carries the f32_accumulate working precision, so the
            # VMEM model takes both (a storage-only model under bf16 would
            # admit depths whose f32 ring blows the budget)
            k = choose_temporal_k(
                (n.x, n.y, n.z), dd.field_dtype(self.h).itemsize,
                self.temporal_k,
                tune_key=dd.tune_key("jacobi-wrap"),
                ring_itemsize=self.h.dtype.itemsize,
            )
            self._wrap_k = k
            f32_acc = dd.field_dtype(self.h) != self.h.dtype
            per_trip = _macros_per_trip(False)  # the wrap kernel never writes in place

            @partial(jax.jit, static_argnums=1, donate_argnums=0)
            def step(curr, steps: int = 1):
                arr = curr[name]
                block = lax.slice(
                    arr, (lo.x, lo.y, lo.z), (lo.x + n.x, lo.y + n.y, lo.z + n.z)
                )
                # temporal blocking: steps//k wavefront dispatches touch HBM
                # once per k iterations; the remainder runs unblocked.  Each
                # level's arithmetic is identical to a k=1 pass, so any
                # (blocked, remainder) split is bit-exact vs k=1.
                blocked, rem = divmod(steps, k)
                block = _macro_loop(
                    partial(
                        jacobi_wrap_step, interpret=interpret, k=k,
                        f32_accumulate=f32_acc,
                    ),
                    blocked, block, per_trip,
                )
                if rem:
                    # one k=rem wavefront (rem < k <= X//2 so always valid);
                    # bit-exact and one HBM pass instead of rem
                    block = jacobi_wrap_step(
                        block, interpret=interpret, k=rem, f32_accumulate=f32_acc
                    )
                # stencil-lint: disable=sliver-dus whole-interior write-back into the shell-carrying array after the k-loop — block spans the full interior, not a y/z sliver
                return {name: lax.dynamic_update_slice(arr, block, (lo.x, lo.y, lo.z))}

            # one chip: no exchange, no wire
            return self._declare_wires(step, WireAccount(0, {}), macros_per_trip=per_trip)
        if want in ("auto", "slab") and (
            all(v is None for v in dd._valid_last)
            and dd.local_spec().sz.x >= 2
            and slab_aligned
        ):
            return self._make_slab_step()
        self._pallas_path = "shell"
        n = dd.local_spec().sz
        shell = dd._shell_radius
        mesh_shape = tuple(dd.mesh.shape[a] for a in MESH_AXES)
        gsize = tuple(dd.size())
        valid_last = dd._valid_last
        interpret = self.interpret
        name = self.h.name
        f32_acc = dd.field_dtype(self.h) != self.h.dtype

        def per_shard(steps, block):
            shape_yz = (block.shape[1] - 2, block.shape[2] - 2)

            def body(_, b):
                # inside the loop body: axis_index as a while operand trips
                # the SPMD partitioner on some toolchains (see ops/stream.py)
                origin = jnp.stack(
                    [lax.axis_index(MESH_AXES[ax]) * n[ax] for ax in range(3)]
                )
                yz_d2 = yz_dist2_plane(origin[1], origin[2], shape_yz, gsize)
                b = halo_exchange_shard(b, shell, mesh_shape, valid_last=valid_last)
                return jacobi_plane_step(
                    b, origin, yz_d2, gsize, interpret=interpret,
                    f32_accumulate=f32_acc,
                )

            return lax.fori_loop(0, steps, body, block)

        spec = P(*MESH_AXES)

        @partial(jax.jit, static_argnums=1, donate_argnums=0)
        def step(curr, steps: int = 1):
            # check_vma off: pallas_call out_shape carries no vma annotation
            fn = shard_map(
                partial(per_shard, steps),
                mesh=dd.mesh,
                in_specs=(spec,),
                out_specs=spec,
                check_vma=False,
            )
            return {name: fn(curr[name])}

        raw = dd.local_spec().raw_size()
        return self._declare_wires(step, exchange_account(
            mesh_shape, shell, (raw.x, raw.y, raw.z), [dd.field_dtype(self.h)],
            valid_last=valid_last,
        ))

    @staticmethod
    def _declare_wires(step, account, **span_args):
        """``step`` with its account of the wires (``run_step``'s counters)
        and what its ``domain.step`` span says: ``span_args`` and the
        account's ``wired`` / ``wire_bytes`` / ``joint``."""
        span_args.update(account.span_args())
        step._wire_account = lambda: account
        step._span_args = lambda: dict(span_args)
        return step

    def _make_slab_step(self):
        """Multi-device fast path: ppermute six BARE face slabs and hand them
        to ``jacobi_slab_step``, which patches the boundary rows/columns while
        streaming planes — no shell blend writes, no halo re-read (the double
        traffic of the shell route).  The interior is sliced out of the
        shell-carrying storage once per dispatch and written back once, both
        amortized over the device-side step loop.  Matches the reference's
        production overlapped pipeline (jacobi3d.cu:265-337); exactly 6
        collective-permutes per iteration, the same count test_hlo pins for
        the general exchange."""
        from functools import partial

        import jax
        from jax import lax
        from jax.sharding import PartitionSpec as P

        from stencil_tpu.ops.exchange import WireAccount, _shift_from_high, _shift_from_low
        from stencil_tpu.telemetry import names as tm
        from stencil_tpu.ops.jacobi_pallas import jacobi_slab_step, yz_dist2_plane
        from stencil_tpu.parallel.mesh import MESH_AXES

        dd = self.dd
        n = dd.local_spec().sz
        lo = dd._shell_radius.lo()
        mesh_shape = tuple(dd.mesh.shape[a] for a in MESH_AXES)
        gsize = tuple(dd.size())
        interpret = self.interpret
        name = self.h.name
        self._marks_shell_stale = True
        self._pallas_path = "slab"
        f32_acc = dd.field_dtype(self.h) != self.h.dtype

        def per_shard(steps, raw_block):
            block = lax.slice(
                raw_block, (lo.x, lo.y, lo.z), (lo.x + n.x, lo.y + n.y, lo.z + n.z)
            )

            def body(_, b):
                # inside the loop body: axis_index as a while operand trips
                # the SPMD partitioner on some toolchains (see ops/stream.py)
                origin = jnp.stack(
                    [lax.axis_index(MESH_AXES[ax]) * n[ax] for ax in range(3)]
                )
                yz_d2 = yz_dist2_plane(origin[1], origin[2], (n.y, n.z), gsize)
                # each slab is the sender's outermost interior plane — the
                # -dir convention at radius 1 (packer.cuh:91-93); z-slabs
                # travel transposed so lanes ride the x axis (see
                # jacobi_slab_step's layout note)
                # (cut + wire of each axis under its exchange.<axis> scope,
                # like the shell-carrying sweeps)
                with jax.named_scope(tm.SPAN_EXCHANGE_X):
                    xlo = _shift_from_low(b[n.x - 1], MESH_AXES[0], mesh_shape[0])
                    xhi = _shift_from_high(b[0], MESH_AXES[0], mesh_shape[0])
                with jax.named_scope(tm.SPAN_EXCHANGE_Y):
                    ylo = _shift_from_low(b[:, n.y - 1, :], MESH_AXES[1], mesh_shape[1])
                    yhi = _shift_from_high(b[:, 0, :], MESH_AXES[1], mesh_shape[1])
                with jax.named_scope(tm.SPAN_EXCHANGE_Z):
                    zlo = _shift_from_low(b[:, :, n.z - 1].T, MESH_AXES[2], mesh_shape[2])
                    zhi = _shift_from_high(b[:, :, 0].T, MESH_AXES[2], mesh_shape[2])
                return jacobi_slab_step(
                    b, xlo, xhi, ylo, yhi, zlo, zhi, origin, yz_d2, gsize,
                    interpret=interpret, f32_accumulate=f32_acc,
                )

            block = lax.fori_loop(0, steps, body, block)
            # stencil-lint: disable=sliver-dus whole-interior write-back after the step loop — block spans the full interior, not a y/z sliver
            return lax.dynamic_update_slice(raw_block, block, (lo.x, lo.y, lo.z))

        spec = P(*MESH_AXES)

        @partial(jax.jit, static_argnums=1, donate_argnums=0)
        def step(curr, steps: int = 1):
            # check_vma off: pallas_call outputs carry no vma annotation
            fn = shard_map(
                partial(per_shard, steps),
                mesh=dd.mesh,
                in_specs=(spec,),
                out_specs=spec,
                check_vma=False,
            )
            return {name: fn(curr[name])}

        # six bare faces of the interior a step, one each way an axis
        isz = dd.field_dtype(self.h).itemsize
        faces = {"x": n.y * n.z, "y": n.x * n.z, "z": n.x * n.y}
        return self._declare_wires(step, WireAccount(1, {
            (MESH_AXES[a], side): faces[MESH_AXES[a]] * isz
            for a in range(3) if mesh_shape[a] > 1
            for side in ("low", "high")
        }))

    def _kernel(self, views, info):
        size = info.global_size
        hot_c = Dim3(size.x // 3, size.y // 2, size.z // 2)
        cold_c = Dim3(size.x * 2 // 3, size.y // 2, size.z // 2)
        sphere_r = size.x // 10

        src = views["temp"]
        val = (
            src.sh(1, 0, 0)
            + src.sh(-1, 0, 0)
            + src.sh(0, 1, 0)
            + src.sh(0, -1, 0)
            + src.sh(0, 0, 1)
            + src.sh(0, 0, -1)
        ) / 6.0

        cx, cy, cz = info.coords()

        def dist2(c: Dim3):
            return (cx - c.x) ** 2 + (cy - c.y) ** 2 + (cz - c.z) ** 2

        # the reference's truncated-float-sqrt membership (jacobi3d.cu:31-33):
        # floor(sqrtf(d2)) <= r  is exactly  d2 < (r+1)^2  while
        # (r+1)*ulp(r+1) < 1, i.e. r+1 < ~2896 (gx up to ~29,000 at
        # r = gx/10) — beyond that correctly-rounded sqrtf((r+1)^2 - 1)
        # rounds up to exactly r+1 and the predicates diverge.  Amply
        # satisfied at realistic sizes, so skip the sqrt entirely.
        in_r2 = (sphere_r + 1) ** 2
        val = jnp.where(dist2(hot_c) < in_r2, HOT_TEMP, val)
        val = jnp.where(dist2(cold_c) < in_r2, COLD_TEMP, val)
        return {"temp": val.astype(src.center().dtype)}

    def rebuild_after_reshard(self) -> None:
        """Rebuild the step function + ladder for the domain's CURRENT
        mesh — the supervisor's ``on_mesh_change`` hook: a reshard (or a
        restore onto a different mesh) leaves ``self.dd`` on the new
        geometry, but the built steps close over the old one.  Device
        state is untouched; this only re-traces the step builders."""
        if self.kernel_impl == "pallas":
            if self._wavefront_m:
                self._step = self._make_wavefront_step()
            else:
                self._step = self._make_pallas_step()
        else:
            self._step = self.dd.make_step(self._kernel, overlap=self.overlap)
        self._ladder = self._make_ladder()

    def step(self, steps: int = 1) -> None:
        """Advance ``steps`` RAW iterations — uniform across engines.  The
        XLA route under a halo multiplier is built in macro steps
        (make_step: one exchange per ``mult`` iterations), so ``steps`` must
        divide into whole macros there; the pallas routes count raw
        iterations natively (their wavefront manages its own multiplier)."""
        mult = self.dd.halo_multiplier()
        if self.kernel_impl == "jnp" and mult > 1:
            if steps % mult:
                raise ValueError(
                    f"steps={steps} must be a multiple of the halo "
                    f"multiplier {mult} on the jnp engine (macro steps)"
                )
            steps //= mult
        self._ladder.step(steps)
        if self._marks_shell_stale:
            self.dd.mark_shell_stale()

    def _rung_name(self) -> str:
        if self.kernel_impl != "pallas":
            return "xla"
        suffix = ",bf16" if self.dd.storage_dtype() == "bf16" else ""
        if self._pallas_path == "wrap":
            return f"wrap[k={self._wrap_k}{suffix}]"
        if self._pallas_path == "wavefront":
            depth = getattr(self, "_wavefront_depth", self._wavefront_m)
            return f"wavefront[depth={depth}{suffix}]"
        return (self._pallas_path or "pallas") + suffix

    def _run_current(self, steps: int = 1) -> None:
        # resolves self._step at CALL time: the degradation ladder swaps the
        # built step underneath when a rung steps down
        self.dd.run_step(self._step, steps, label="jacobi")

    def _make_ladder(self):
        """The model's degradation ladder (resilience/ladder.py): wrap
        re-plans at k-1 per descent, the wavefront keeps its allocated
        m-wide shell and advances fewer levels per pass — the same implicit
        order the old hand-rolled try/except walked, now with classified
        failures, donation-guarded re-invocation, and fault-injection hooks
        labeled ``jacobi:<rung>``."""
        from stencil_tpu.resilience.ladder import DegradationLadder, Rung

        def rung():
            return Rung(name=self._rung_name(), build=lambda: self._run_current)

        def lower(rung_, cls, exc):
            return rung() if self._step_down(cls) else None

        return DegradationLadder(
            rung(), lower=lower, label="jacobi", buffers=lambda: self.dd._curr
        )

    def _step_down(self, cls) -> bool:
        """Runtime fallback for the bespoke pallas paths: when Mosaic
        rejects the planned temporal depth (scoped-VMEM OOM or another
        classified compile reject — the calibrated model under-estimated on
        this toolchain), rebuild one level shallower instead of crashing.
        The wavefront keeps its allocated m-wide shell and just advances
        fewer levels per pass (``_wavefront_depth``); the wrap path re-plans
        with ``temporal_k-1``.  Returns True when a shallower rebuild was
        installed."""
        from stencil_tpu.utils.logging import log_warn

        if self.kernel_impl != "pallas":
            return False
        # the storage rung comes BEFORE any depth descent: a bf16 build
        # carries its own extra compiler surface (mixed-dtype pipelines), so
        # the failure may be the axis's fault, not the depth's — step the
        # axis down at the SAME depth first
        if self.dd.storage_dtype() == "bf16":
            log_warn(
                f"storage_dtype=bf16 on the {self._pallas_path} route "
                f"exceeded the compiler's capability ({cls.value}); stepping "
                "down to native storage at the same depth (exact: every "
                "bfloat16 value upcasts losslessly)"
            )
            self._convert_storage_to_native()
            self._rebuild_current_route()
            return True
        if self._pallas_path == "wrap" and self._wrap_k > 1:
            self.temporal_k = self._wrap_k - 1
            log_warn(
                f"wrap temporal depth k={self._wrap_k} exceeded the compiler's "
                f"capability ({cls.value}); retrying k={self.temporal_k} "
                "(for vmem_oom: recalibrate the VMEM model / "
                "STENCIL_VMEM_LIMIT_BYTES for this toolchain)"
            )
            self._step = self._make_pallas_step()
            return True
        if self._pallas_path == "wavefront":
            depth = getattr(self, "_wavefront_depth", self._wavefront_m)
            if depth <= 1:
                return False
            self._wavefront_depth = depth - 1
            log_warn(
                f"wavefront depth {depth} exceeded the compiler's capability "
                f"({cls.value}); retrying depth {depth - 1} over the same "
                f"{self._wavefront_m}-wide shell (for vmem_oom: recalibrate "
                "the VMEM model for this toolchain)"
            )
            self._step = self._make_wavefront_step()
            return True
        return False

    def _rebuild_current_route(self) -> None:
        """Rebuild the installed step for the CURRENT route after an axis
        step-down (bf16->native) — same depth, same allocation.
        The wrap rebuild re-runs ``choose_temporal_k`` (whose auto/tuned
        resolution could shift under the changed storage itemsize), so pin
        the depth explicitly: the axis steps down FIRST, depth only through
        its own later ladder rungs."""
        if self._pallas_path == "wrap":
            self.temporal_k = self._wrap_k
        if self._pallas_path == "wavefront":
            self._step = self._make_wavefront_step()
        else:
            self._step = self._make_pallas_step()

    def _convert_storage_to_native(self) -> None:
        """Runtime bf16->native step-down: upcast the live field buffers
        (exact — every bfloat16 is an f32) and re-mark the domain native so
        rebuilt kernels, the exchange, and the byte accounting all follow.
        Post-realize by necessity (this is a ladder rung, the allocation
        already exists), hence the direct ``_storage`` write rather than
        ``set_storage``'s pre-realize setter."""
        dd = self.dd
        dd._storage = "native"
        self._storage_dtype = "native"
        for h in dd._handles:
            for slot in (dd._curr, dd._next):
                if h.name in slot:
                    slot[h.name] = slot[h.name].astype(h.dtype)
        # the analytic exchange-bytes cache and the compiled exchange were
        # built over the narrow buffers; drop both so they re-derive
        dd._exchange_nbytes = None
        dd._exchange_many_fn = None

    def temperature(self) -> np.ndarray:
        return self.dd.quantity_to_host(self.h)

    def block_until_ready(self) -> None:
        self.dd.block_until_ready()


def weak_scaled_size(base: int, num_subdomains: int) -> int:
    """jacobi3d.cu:167-169: scale each axis by numSubdoms^(1/3), rounded."""
    return int(float(base) * float(num_subdomains) ** 0.33333 + 0.5)
