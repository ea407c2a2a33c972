"""``DistributedDomain`` — the public orchestrator.

Parity target: reference ``DistributedDomain`` (include/stencil/stencil.hpp:61
+ src/stencil.cu).  Same lifecycle: construct with a global size, configure
(``set_radius`` / ``add_data`` / ``set_methods`` / ``set_placement``), then
``realize()`` and iterate ``exchange()`` / compute / ``swap()``.

TPU design (not a translation):

* A quantity is ONE global ``jax.Array`` sharded ``P('x','y','z')`` over the
  3D device mesh.  Each shard is the reference's ``LocalDomain`` allocation —
  interior plus halo shell (``raw_size``) — so the global array has shape
  ``dim * raw_size`` and the *logical* user domain is the union of shard
  interiors.  Double buffering is two array slots whose references swap
  (reference src/local_domain.cu:41-54); buffer donation makes the step
  in-place in HBM.
* ``exchange()`` is a jitted 3-axis-sweep ppermute (ops/exchange.py) — the
  whole transport layer of the reference.
* ``make_step`` builds the fused exchange+compute step with
  interior/exterior overlap (reference src/stencil.cu:567-666 +
  jacobi3d.cu:265-337): interior compute carries no data dependency on the
  ppermutes, so XLA overlaps communication with compute — the job of the
  reference's entire sender/recver state-machine zoo.

Uneven global sizes (the reference's ±1-cell remainders, partition.hpp:83-114)
are handled by pad-and-mask: every shard is padded to ``ceil(size/dim)`` (XLA
shards must be equal), the LAST shard on a padded axis owns the remainder, the
exchange uses per-shard dynamic slab offsets so halos carry VALID cells across
the periodic wrap, and host gather/scatter masks the padding (SURVEY.md §7
"Hard parts").
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
import weakref
from functools import partial
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from stencil_tpu.core.dim3 import Dim3, Rect3
from jax import shard_map
from stencil_tpu.core.geometry import LocalSpec
from stencil_tpu.core.radius import Radius
from stencil_tpu.ops.exchange import (
    halo_exchange_multi,
    halo_exchange_shard,
    make_exchange_fn,
)
from stencil_tpu.parallel.mesh import MESH_AXES, make_mesh
from stencil_tpu.parallel.placement import Placement
from stencil_tpu import telemetry
from stencil_tpu.telemetry import names as tm
from stencil_tpu.utils.config import MethodFlags, PlacementStrategy
from stencil_tpu.utils.logging import log_debug, log_info, log_warn


@dataclasses.dataclass(frozen=True)
class DataHandle:
    """Typed handle to a named quantity (reference local_domain.cuh:17-25).

    ``components`` are leading per-cell dims (N-D data — the reference's
    future-work item, README.md:157-176): a (3,)-component quantity stores a
    vector per cell as a (3, X, Y, Z) array, unsharded on the component dim.
    """

    name: str
    dtype: object
    components: tuple = ()

    def cell_count(self) -> int:
        n = 1
        for c in self.components:
            n *= c
        return n


@dataclasses.dataclass
class DomainStats:
    """Setup/exchange wall-time accounting (reference STENCIL_SETUP_STATS /
    STENCIL_EXCHANGE_STATS, stencil.hpp:106-131).  Setup phases map:
    mpi_topo -> process/device discovery, placement -> partition+QAP solve,
    realize -> array allocation, plan -> exchange-fn construction,
    create -> jit trace+compile of the exchange (the analog of sender/recver
    creation + CUDA-Graph capture, src/stencil.cu:385-529)."""

    time_topo: float = 0.0
    time_placement: float = 0.0
    time_realize: float = 0.0
    time_plan: float = 0.0
    time_create: float = 0.0
    time_exchange: float = 0.0
    time_swap: float = 0.0


class ShardView:
    """Per-shard stencil-term access used inside step kernels.

    ``sh(dx,dy,dz)`` returns the region's cells shifted by the offset —
    the reference's ``src[o + Dim3(dx,dy,dz)]`` Accessor pattern
    (accessor.hpp:27-40) as a fused slice.
    """

    def __init__(self, block: jax.Array, r_lo: Dim3, region: Tuple[slice, slice, slice]):
        self._block = block
        self._lo = r_lo
        self._region = region

    def sh(self, dx: int = 0, dy: int = 0, dz: int = 0) -> jax.Array:
        idx = []
        for ax, d in zip(range(3), (dx, dy, dz)):
            s = self._region[ax]
            idx.append(slice(self._lo[ax] + s.start + d, self._lo[ax] + s.stop + d))
        # leading component dims (N-D data) ride unsliced
        return self._block[(Ellipsis,) + tuple(idx)]

    def center(self) -> jax.Array:
        return self.sh(0, 0, 0)


@dataclasses.dataclass
class BlockInfo:
    """Traced per-shard context handed to step kernels."""

    origin: Tuple[jax.Array, jax.Array, jax.Array]  # global coords of interior start
    interior: Dim3  # interior size per shard
    global_size: Dim3
    radius: Radius
    region: Tuple[slice, slice, slice]  # interior-local region being computed

    def coords(self):
        """Global (x, y, z) coordinate arrays for the region, broadcastable
        to the region's shape.  Wrapped periodically: regions extended into
        the halo shell (halo-multiplier sub-steps) see the coordinates of the
        cells the shell mirrors."""
        s = self.region
        g = self.global_size
        cx = (self.origin[0] + jnp.arange(s[0].start, s[0].stop)) % g.x
        cy = (self.origin[1] + jnp.arange(s[1].start, s[1].stop)) % g.y
        cz = (self.origin[2] + jnp.arange(s[2].start, s[2].stop)) % g.z
        return cx[:, None, None], cy[None, :, None], cz[None, None, :]


#: a step kernel: (views, info) -> {name: new values for info.region}
StepKernel = Callable[[Dict[str, ShardView], BlockInfo], Dict[str, jax.Array]]


def _qspec(h: DataHandle) -> P:
    """PartitionSpec for a quantity: spatial dims sharded over the mesh,
    leading component dims (N-D data) unsharded."""
    return P(*([None] * len(h.components)), *MESH_AXES)


def _row_major_format(sharding: NamedSharding, shape, dtype):
    """A ``Format`` that pins an array's shards row-major -- z in the lanes,
    y in the sublanes, what every kernel of ``ops/`` is written for -- where
    the backend's own default layout for the shard's shape is another one,
    else None (nothing to pin: the programs stay as they are).

    A TPU stores an array in the dimension order that wastes least on its
    (8, 128) tiles: a 602 x 602 x 1197 f32 shard (the reference's weak run at
    750^3 per chip) goes y-minor -- 640 lanes x 1200 sublanes beats 1280 x
    608 -- and every Pallas call, which takes its operands row-major, then
    pays a whole-array relayout on the way in and on the way out (PERF.md §6,
    PR 31).  Cubes, and z extents close under a lane multiple, default to
    row-major, so no other shape the benchmark runs is pinned."""
    from jax.experimental.layout import Format, Layout

    device = next(iter(sharding.device_set))
    try:
        default = Layout.from_pjrt_layout(
            device.client.get_default_layout(
                jnp.dtype(dtype), sharding.shard_shape(tuple(shape)), device
            )
        )
    except Exception as e:  # noqa: BLE001 -- a backend without layouts pins nothing
        log_debug(f"no default layout to read ({type(e).__name__}); pinning nothing")
        return None
    row_major = tuple(range(len(shape)))
    if tuple(default.major_to_minor) == row_major:
        return None
    return Format(Layout(major_to_minor=row_major), sharding)


def _persistent_cache_off() -> None:
    """Turn jax's persistent compilation cache off for this process -- what a
    domain does once it pins a layout.  jax 0.9 serves an executable from the
    persistent cache WITHOUT its non-default entry layouts: the second fill of
    a pinned quantity (same program, a cache hit) then expects the backend's
    default layout and is handed the pinned buffer (`INVALID_ARGUMENT: expected
    parameter 0 of size 1849344000 ... {1,2,0} but got ... {2,1,0}` on the
    chip; silently transposed data on the CPU).  It holds for EVERY program
    that takes or returns a pinned array, the caller's own included, so the
    switch is the process's, not a program's.  Every aligned extent pins
    nothing and keeps the cache."""
    if not jax.config.jax_enable_compilation_cache:
        return
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    log_warn(
        "this domain pins its arrays row-major against the backend's default "
        "layout; jax's persistent compilation cache drops such layouts, so it "
        "is off for this process (every program compiles once per run)"
    )


class DistributedDomain:
    def __init__(self, x: int, y: int, z: int):
        self._size = Dim3(x, y, z)
        self._radius = Radius.constant(0)
        self._handles: List[DataHandle] = []
        self._methods = MethodFlags.All
        self._strategy = PlacementStrategy.NodeAware
        self._devices: Optional[Sequence] = None
        self._realized = False
        # post-realize state
        self.mesh: Optional[Mesh] = None
        self.placement: Optional[Placement] = None
        self._spec: Optional[LocalSpec] = None
        self._valid_last: Tuple[Optional[int], Optional[int], Optional[int]] = (None, None, None)
        self._curr: Dict[str, jax.Array] = {}
        self._next: Dict[str, jax.Array] = {}
        # quantity name -> Format, for the quantities whose arrays are pinned
        # row-major (``_row_major_format``); empty on every aligned extent
        self._pinned: Dict[str, object] = {}
        self._exchange_fn = None
        self._exchange_many_fn = None
        self._exchange_count = 0
        # program -> the static arguments this domain has called it with
        # (``_dispatch_span_args``): the first call of each is where jax
        # compiles.  Weak keys: a step the caller dropped is not kept alive
        self._dispatched = weakref.WeakKeyDictionary()
        self._dispatched_by_id: dict = {}  # programs that cannot be weak keys
        # z-sweep exchange route (ops/exchange.py EXCHANGE_ROUTES): resolved
        # at realize() — explicit request > STENCIL_EXCHANGE_ROUTE > tuned
        # config > static "direct"; packed-route analytic accounting rides it
        self._exchange_route_req: Optional[str] = None
        self._exchange_route = "direct"
        self._wrap_axes = ""  # mesh axes swept by the self-wrap kernel
        self._uneven_axes = ""  # mesh axes swept at per-shard traced offsets
        # storage-dtype axis (ops/jacobi_pallas STORAGE_DTYPES): models
        # resolve the axis (explicit > STENCIL_STORAGE_DTYPE > tuned >
        # static native) and pin the RESOLVED value here before realize();
        # field allocation, exchange byte accounting, and the packed z-shell
        # messages all follow ``field_dtype``
        self._storage = "native"
        self._halo_mult = 1
        self._shell_stale = False
        self._shell_radius: Optional[Radius] = None
        self._force_dim: Optional[Dim3] = None
        self.stats = DomainStats()
        # blocking per-exchange timing costs a device sync per call, exactly
        # like the reference's barrier-per-call EXCHANGE_STATS (default OFF,
        # CMakeLists.txt:20); opt in via env or enable_exchange_stats().
        from stencil_tpu.utils.config import env_bool, env_int

        self._exchange_stats = env_bool("STENCIL_EXCHANGE_STATS", False)
        # resilience: divergence sentinel (off unless STENCIL_DIVERGENCE_EVERY
        # or set_divergence_check sets a cadence) + dispatch retry policy,
        # both lazily built on first run_step

        self._divergence_every = env_int("STENCIL_DIVERGENCE_EVERY", 0, minimum=0)
        self._sentinel = None
        # numerics observatory (telemetry/numerics.py): the fused on-device
        # field-health engine, built lazily on first use; the observe
        # cadence (snapshots + guardbands per STENCIL_NUMERICS_EVERY /
        # --numerics-every) is independent of the sentinel's
        self._numerics_every = env_int("STENCIL_NUMERICS_EVERY", 0, minimum=0)
        self._numerics = None
        self._retry_policy = None
        # dispatch watchdog (resilience/watchdog.py): resolved lazily from
        # STENCIL_WATCHDOG_S at first dispatch, or installed programmatically
        self._watchdog = None
        self._watchdog_resolved = False
        # analytic bytes per exchange (exchange_bytes_total: the span's
        # ``nbytes`` and the gauge), computed once per realize(); the
        # exchange's own account of its wires and packed sweeps (what the
        # counters read) is cached beside it
        self._exchange_nbytes: Optional[int] = None
        self._wires = None

    def set_watchdog(self, wd) -> None:
        """Install (or clear, with ``None``) a dispatch watchdog
        (``resilience/watchdog.DispatchWatchdog``): every ``run_step`` and
        ``exchange`` dispatch is then armed with its deadline — a dispatch
        that wedges past it emits a ``watchdog.stall`` event naming the
        phase, and in abort mode is interrupted and re-raised as a
        classified ``StallError`` for the supervisor to restart on.
        Without this call, ``STENCIL_WATCHDOG_S`` configures one from the
        environment at first dispatch."""
        self._watchdog = wd
        self._watchdog_resolved = True

    def _get_watchdog(self):
        if not self._watchdog_resolved:
            from stencil_tpu.resilience.watchdog import DispatchWatchdog

            self._watchdog = DispatchWatchdog.from_env()
            self._watchdog_resolved = True
        return self._watchdog

    def _watched_call(self, phase: str, fn):
        """Run one dispatch under the watchdog (when configured).

        The jitted call returns at ENQUEUE on asynchronous backends — a
        wedged collective surfaces at the sync — so the watched region
        includes a ``block_until_ready`` on the dispatch's own outputs:
        the deadline covers the execution, not just the enqueue.  (The
        sync is watchdog-mode only; unwatched dispatches keep jax's async
        pipelining.)  An abort-mode interrupt is converted into the
        classified ``StallError``; in observe-only mode a KeyboardInterrupt
        stays a KeyboardInterrupt — a user Ctrl-C must never be re-labeled
        by a stale, uninterrupting deadline trip."""
        wd = self._get_watchdog()
        if wd is None:
            return fn()
        try:
            with wd.watch(phase):
                out = fn()
                jax.block_until_ready(out)
                return out
        except KeyboardInterrupt:
            if wd.abort:
                stall = wd.take_stall()
                if stall is not None:
                    raise stall from None
            raise

    def set_divergence_check(self, every: int) -> None:
        """Enable the divergence sentinel (resilience/sentinel.py): every
        ``every`` raw steps run through ``run_step``, each floating quantity
        is checked for NaN/Inf on-device (ONE fused numerics dispatch —
        telemetry/numerics.py) and a classified ``DIVERGENCE`` error names
        the quantity, the global first-non-finite coordinate, and the
        bracketing step window.  0 disables (the default).  A mid-run
        cadence change preserves the sentinel's accumulated step count, so
        reported divergence steps stay correct."""
        self._divergence_every = int(every)
        if self._sentinel is not None:
            self._sentinel.set_every(self._divergence_every)

    def set_numerics_every(self, every: int) -> None:
        """Enable the numerics observatory's snapshot cadence
        (telemetry/numerics.py): every ``every`` raw steps through
        ``run_step``, one fused on-device health snapshot (per-quantity
        min/max/absmax/mean/L2/non-finite stats) lands in the engine's
        ring and runs the registered guardbands.  0 disables (the
        default; ``STENCIL_NUMERICS_EVERY`` / ``--numerics-every`` set it
        from the run surface).  Like ``set_divergence_check``, a mid-run
        change preserves the accumulated step count."""
        self._numerics_every = int(every)
        if self._numerics is not None:
            self._numerics.set_every(self._numerics_every)

    def numerics(self):
        """This domain's :class:`~stencil_tpu.telemetry.numerics.
        NumericsEngine` — the fused on-device field-statistics program
        (built lazily, memoized per geometry signature, auto-rebuilt after
        a mesh transition).  The divergence sentinel, the observe cadence,
        and direct callers (tests, guardband registration) all share this
        one engine, so they share one compiled program and one snapshot
        ring."""
        if self._numerics is None:
            from stencil_tpu.telemetry.numerics import NumericsEngine

            self._numerics = NumericsEngine(self, every=self._numerics_every)
        return self._numerics

    # --- configuration (stencil.hpp:276-306) ---------------------------------
    def set_radius(self, radius) -> None:
        self._radius = Radius.constant(radius) if isinstance(radius, int) else radius

    def radius(self) -> Radius:
        return self._radius

    def add_data(self, name: str, dtype=jnp.float32, components=()) -> DataHandle:
        h = DataHandle(name, jnp.dtype(dtype), tuple(components))
        self._handles.append(h)
        return h

    def set_methods(self, methods: MethodFlags) -> None:
        self._methods = methods

    def set_placement(self, strategy: PlacementStrategy) -> None:
        self._strategy = strategy

    def set_devices(self, devices: Sequence) -> None:
        """Analog of set_gpus (stencil.hpp:306): restrict/order the devices."""
        self._devices = devices

    def set_partition(self, px: int, py: int, pz: int) -> None:
        """Force the process grid instead of deriving it (manual partition,
        the reference's future-work item, README.md:157-176).  The product
        must equal the device count at realize()."""
        assert not self._realized
        self._force_dim = Dim3(px, py, pz)

    def set_halo_multiplier(self, k: int) -> None:
        """Allocate ``k * radius``-wide shells and run ``k`` compute sub-steps
        per exchange — fewer, larger messages (the reference's future-work
        item, README.md:157-176; BASELINE.md config #5).  A step built by
        ``make_step`` then advances ``k`` iterations per call."""
        assert k >= 1
        assert not self._realized, "set_halo_multiplier must precede realize()"
        self._halo_mult = int(k)

    def halo_multiplier(self) -> int:
        return self._halo_mult

    def set_exchange_route(self, route: Optional[str]) -> None:
        """Pin the y/z-sweep exchange route (ops/exchange.py
        ``EXCHANGE_ROUTES``: ``direct`` | ``zpack_xla`` | ``zpack_pallas``
        | ``yzpack_xla`` | ``yzpack_pallas``).
        ``None``/"auto" restores planner resolution: ``STENCIL_EXCHANGE_ROUTE``,
        then the tuned config (``tune.best_config`` on this domain's
        "exchange" workload key), then the static ``direct`` fallback.  An
        explicit pin — like every explicit request — never consults the
        tuner; it still steps down to ``direct`` if the packed kernels are
        rejected at compile (the resilience ladder) or NO packed sweep can
        structurally engage (uneven packed axes, unsupported dtype) — a
        partially engageable route runs its eligible sweeps packed and the
        rest direct."""
        from stencil_tpu.ops.exchange import EXCHANGE_ROUTES

        if route in (None, "auto"):
            self._exchange_route_req = None
            return
        if route not in EXCHANGE_ROUTES:
            raise ValueError(
                f"unknown exchange route {route!r} (one of {EXCHANGE_ROUTES})"
            )
        assert not self._realized, "set_exchange_route must precede realize()"
        self._exchange_route_req = route

    def exchange_route(self) -> str:
        """The resolved y/z-sweep route (meaningful after ``realize()``)."""
        return self._exchange_route

    def set_storage(self, storage: str) -> None:
        """Pin the field buffers' STORAGE dtype axis (``"native"`` |
        ``"bf16"`` — ops/jacobi_pallas ``STORAGE_DTYPES``).  Callers (the
        models' ctor knobs) resolve the axis through
        ``resolve_storage_dtype`` — precedence explicit >
        ``STENCIL_STORAGE_DTYPE`` > tuned config > static ``native``, with
        the structural f32-only / f32-accumulate-engine gates — and hand
        the RESOLVED value here before ``realize()``.  Under ``bf16`` every
        f32 field allocates as bfloat16 (HBM planes, the VMEM pipeline
        blocks streamed from them, and the fused exchange messages all
        narrow to 2 B/cell); the kernels accumulate at f32 and downcast
        once per pass (the ``f32_accumulate`` contract), and host readback
        (``quantity_to_host`` etc.) upcasts back to the native dtype."""
        from stencil_tpu.ops.jacobi_pallas import STORAGE_DTYPES

        if storage not in STORAGE_DTYPES:
            raise ValueError(
                f"unknown storage dtype {storage!r} (one of {STORAGE_DTYPES})"
            )
        assert not self._realized, "set_storage must precede realize()"
        self._storage = storage

    def storage_dtype(self) -> str:
        """The resolved storage axis: ``"native"`` or ``"bf16"``."""
        return self._storage

    def field_dtype(self, h: DataHandle):
        """The dtype ``h``'s buffers actually store: bfloat16 under the
        bf16 storage axis for f32 fields (the only narrowing the analytic
        error contract covers — see ``bf16_supported``), the native dtype
        otherwise."""
        if self._storage == "bf16" and jnp.dtype(h.dtype) == jnp.float32:
            return jnp.dtype(jnp.bfloat16)
        return h.dtype

    def tune_key(self, route: str):
        """The autotuner ``WorkloadKey`` for this domain under ``route`` —
        THE one place the (chip kind, domain shape, dtype, n_fields, mesh
        shape, radius, engine route) tuple is assembled, so every planner
        consults the same cache entry.  Works pre-realize too: the mesh dim
        is mirrored from the deterministic ``make_mesh`` computation (the
        same mirror ``Jacobi3D._plan_wavefront`` relies on)."""
        from stencil_tpu.tune.key import WorkloadKey, chip_kind

        if self.placement is not None:
            dim = self.placement.dim()
        else:
            devices = (
                list(self._devices) if self._devices is not None else jax.devices()
            )
            _, placement = make_mesh(
                self._size, self._radius, devices, self._strategy,
                force_dim=self._force_dim,
            )
            dim = placement.dim()
        r = self._radius
        rmax = max(
            r.lo().x, r.lo().y, r.lo().z, r.hi().x, r.hi().y, r.hi().z
        )
        if route == "exchange":
            # the exchange operates on the SHELL (user radius × halo
            # multiplier): its z message depth is what a route winner was
            # measured at, so the multiplier must re-key the workload.  The
            # temporally-blocked routes key by the user radius instead —
            # there the multiplier IS the tuned axis, not a key axis.
            rmax *= max(self._halo_mult, 1)
        dtypes = ",".join(sorted({h.dtype.name for h in self._handles}))
        return WorkloadKey(
            chip=chip_kind(),
            domain=(self._size.x, self._size.y, self._size.z),
            dtype=dtypes or "float32",
            n_fields=max(len(self._handles), 1),
            mesh=(dim.x, dim.y, dim.z),
            radius=rmax,
            route=route,
        )

    def size(self) -> Dim3:
        return self._size

    # --- realize (src/stencil.cu:27-539) -------------------------------------
    def enable_exchange_stats(self, on: bool = True) -> None:
        self._exchange_stats = on

    def _derive_geometry(self, devices):
        """Mesh/placement/spec for THIS domain over ``devices`` — the one
        place the padded-equal-split geometry (and its admissibility
        checks) is computed, shared by ``realize()`` and the reshard
        target planning so the two can never drift."""
        mesh, placement = make_mesh(
            self._size, self._radius, devices, self._strategy,
            force_dim=self._force_dim,
        )
        dim = placement.dim()
        # uneven sizes: pad each axis's shard to ceil(size/dim) and mask (the
        # reference's +-1-cell remainders, partition.hpp:83-114; XLA shards
        # must be equal).  The LAST shard on a padded axis owns
        # ``size - (dim-1)*n_pad`` valid cells.
        n = Dim3(*(-(-self._size[ax] // dim[ax]) for ax in range(3)))
        vlast = []
        for ax in range(3):
            v = self._size[ax] - (dim[ax] - 1) * n[ax]
            vlast.append(None if v == n[ax] else v)
        # the SHELL radius is the user radius times the halo multiplier: the
        # allocation, the exchange, and the bytes model all use it; compute
        # sub-steps shrink by the user radius
        r = self._radius.scaled(self._halo_mult)
        max_r = max(r.lo().x, r.lo().y, r.lo().z, r.hi().x, r.hi().y, r.hi().z)
        min_valid = min(v if v is not None else n[ax] for ax, v in enumerate(vlast))
        if min_valid <= 0:
            # pad-and-mask confines the remainder to ONE trailing shard; a
            # split where (dim-1)*ceil(size/dim) >= size (e.g. 10 cells over
            # 8 shards) leaves the last shard empty.  The reference spreads
            # +-1-cell remainders across shards instead (partition.hpp:83-114)
            # — that scheme has no equal-shard analog, so reject explicitly.
            raise ValueError(
                f"axis remainder does not fit in one trailing shard: size "
                f"{self._size} over mesh {dim} gives last-shard valid cells "
                f"{vlast}; choose a mesh dim with (dim-1)*ceil(size/dim) < size"
            )
        if min(n.x, n.y, n.z) < max_r or min_valid < max_r:
            raise ValueError(
                f"subdomain {n} (last-shard valid {vlast}) smaller than radius shell"
            )
        # all shards share one spec (padded equal split); per-shard origin varies
        spec = LocalSpec.make(n, Dim3(0, 0, 0), r)
        return mesh, placement, spec, tuple(vlast), r

    def realize(self, allocate: bool = True) -> None:
        """``allocate=False`` sets up mesh/placement/geometry WITHOUT creating
        arrays or compiling the exchange — for AOT work over device-less
        topologies (``jax.experimental.topologies``), where ``make_step`` can
        then be lowered/compiled against abstract sharded shapes (used by the
        overlap-schedule proof, tests/test_overlap_schedule.py)."""
        self._radius.validate()
        if self._storage == "bf16":
            # the structural gate the model resolvers apply, repeated here
            # for direct set_storage() callers: the f32-accumulate stream
            # passes upcast EVERY quantity uniformly, so a mixed domain with
            # non-f32 fields (f64 would silently lose 29 mantissa bits, int
            # fields have no f32 round trip contract) must degrade the whole
            # axis — only all-f32 domains narrow (``bf16_supported``)
            from stencil_tpu.ops.jacobi_pallas import bf16_supported

            if not bf16_supported([h.dtype for h in self._handles]):
                log_warn(
                    "storage bf16 cannot engage: fields are "
                    f"{[jnp.dtype(h.dtype).name for h in self._handles]}, "
                    "not all f32; degrading to native storage"
                )
                self._storage = "native"
        t0 = time.perf_counter()
        devices = list(self._devices) if self._devices is not None else jax.devices()
        self.stats.time_topo = time.perf_counter() - t0
        t0 = time.perf_counter()
        (
            self.mesh,
            self.placement,
            self._spec,
            self._valid_last,
            self._shell_radius,
        ) = self._derive_geometry(devices)
        self.stats.time_placement = time.perf_counter() - t0
        # the span opens once the geometry is known, so it can say which
        # shards are padded; it holds what takes the time (allocation, the
        # exchange's build and eager compile)
        telemetry.watch_jax()  # jax is up by now: its compile events, by phase
        with telemetry.span(
            tm.SPAN_REALIZE, total=tm.PHASE_REALIZE,
            valid_last=",".join("-" if v is None else str(v) for v in self._valid_last),
        ):
            self._allocate_and_plan(allocate)

    def _allocate_and_plan(self, allocate: bool) -> None:
        dim = self.placement.dim()
        raw = self._spec.raw_size()
        gshape = (dim.x * raw.x, dim.y * raw.y, dim.z * raw.z)
        self._pinned = {}
        for h in self._handles:
            pin = _row_major_format(
                NamedSharding(self.mesh, _qspec(h)), h.components + gshape,
                self.field_dtype(h),
            )
            if pin is not None:
                self._pinned[h.name] = pin
        if self._pinned:
            _persistent_cache_off()
        if not allocate:
            self._realized = True
            log_info(f"realized (abstract) {self._size} over mesh {dim} (raw shard {raw})")
            return
        t0 = time.perf_counter()
        both = self._both_slots_fit()
        for h in self._handles:
            self._curr[h.name] = self._zeros(h, gshape)
            if both:
                self._next[h.name] = self._zeros(h, gshape)
        self.stats.time_realize = time.perf_counter() - t0
        t0 = time.perf_counter()
        if self._methods in (MethodFlags.AllGather, MethodFlags.RollCompare):
            # debug methods: two independent oracles for the ppermute path
            # (stencil.hpp:29-41 method selection); even (unpadded) sizes only
            from stencil_tpu.ops.exchange import (
                make_exchange_fn_allgather,
                make_exchange_fn_rollcompare,
            )

            if any(v is not None for v in self._valid_last):
                raise ValueError("debug exchange methods require even sizes")
            if any(h.components for h in self._handles):
                raise ValueError(
                    "debug exchange methods support scalar quantities only"
                )
            maker = (
                make_exchange_fn_allgather
                if self._methods == MethodFlags.AllGather
                else make_exchange_fn_rollcompare
            )
            self._exchange_fn = maker(self.mesh, self._shell_radius, self._spec, dim)
            self._exchange_route = "direct"  # the debug oracles have no z route
            self._wrap_axes = self._uneven_axes = ""  # ...and no axis sweeps
            self.stats.time_plan = time.perf_counter() - t0
            # eager trace+compile of the exchange — the analog of the
            # reference's sender/recver creation + CUDA-Graph capture
            # (src/stencil.cu:385-529); later exchange() calls hit the
            # executable cache.
            if self._handles:
                t0 = time.perf_counter()
                with telemetry.span(
                    tm.EVENT_COMPILE, total=tm.PHASE_COMPILE, label="exchange:realize"
                ):
                    self._exchange_fn.lower(self._curr).compile()
                self._record_exchange_compile(t0, "realize")
        else:
            self._exchange_route = self._resolve_exchange_route()
            self.stats.time_plan = time.perf_counter() - t0
            # build + eager-compile through the route ladder: a packed route
            # the compiler rejects (VMEM_OOM / COMPILE_REJECT) steps down to
            # `direct`; the compile itself rides the transient-retry policy
            # (a dropped connection retries instead of killing realize)
            t0 = time.perf_counter()
            self._exchange_fn = self._build_exchange_with_ladder()
            if self._handles:
                self._record_exchange_compile(t0, f"realize:{self._exchange_route}")
        self._realized = True
        log_info(f"realized {self._size} over mesh {dim} (raw shard {raw})")

    def _both_slots_fit(self) -> bool:
        """Do ``curr`` AND ``next`` of every quantity fit one device's memory?
        Where they do, ``realize()`` allocates both, as the reference does
        (its double buffer, ``swap()``); where they do not -- elastic's
        thirteen 608^3 quantities are 12.3 GB of a 16.9 GB chip, and a built
        step carries ``curr`` in place and never touches ``next`` -- the
        ``next`` slot is allocated on first use (``_next_slot``).  A backend
        that does not say how much memory it has (the CPU) gets both."""
        stats = self.mesh.devices.flat[0].memory_stats() or {}
        limit = stats.get("bytes_limit")
        if not limit:
            return True
        from stencil_tpu.ops.jacobi_pallas import _padded_plane_bytes

        raw = self._spec.raw_size()
        per_device = sum(
            int(np.prod(h.components, dtype=np.int64)) * raw.x
            * _padded_plane_bytes(raw.y, raw.z, self.field_dtype(h).itemsize)
            for h in self._handles
        )
        return 2 * per_device <= limit

    def _next_slot(self) -> Dict[str, jax.Array]:
        """The ``next`` slot, every quantity allocated (zeroed) by now."""
        missing = [h for h in self._handles if h.name not in self._next]
        if missing:
            dim, raw = self.placement.dim(), self._spec.raw_size()
            gshape = (dim.x * raw.x, dim.y * raw.y, dim.z * raw.z)
            for h in missing:
                self._next[h.name] = self._zeros(h, gshape)
        return self._next

    def _zeros(self, h: DataHandle, gshape) -> jax.Array:
        """A zeroed array of quantity ``h`` on the mesh, in its pinned layout
        where it has one."""
        shape, fdt = h.components + tuple(gshape), self.field_dtype(h)
        pin = self._pinned.get(h.name)
        if pin is None:
            return jnp.zeros(shape, dtype=fdt, device=NamedSharding(self.mesh, _qspec(h)))
        return jax.jit(partial(jnp.zeros, shape, dtype=fdt), out_shardings=pin)()

    def _record_exchange_compile(self, t0: float, label: str) -> None:
        # the reference-parity stat (``bin/weak.py`` prints it) and the JSONL /
        # flight-ring event; the always-live record is the ``domain.compile``
        # span's total (``setup.span_seconds.compile``)
        self.stats.time_create = time.perf_counter() - t0
        telemetry.emit_event(
            tm.EVENT_COMPILE,
            phase="exchange",
            label=label,
            seconds=round(self.stats.time_create, 6),
        )

    def mesh_dim(self) -> Tuple[int, int, int]:
        """The current mesh extent as a plain tuple (heartbeat/telemetry)."""
        d = self.placement.dim()
        return (d.x, d.y, d.z)

    # --- elastic capacity ------------------------------------------------------

    def reshard(self, devices=None, force_dim=None, source: str = "request") -> dict:
        """Live mesh transition: move the realized interior state onto a
        new device mesh IN MEMORY — the on-device generalization of
        checkpoint-elastic-restore (docs/resilience.md "Elastic capacity").

        The interiors travel as a schedule of portable collectives
        (``parallel/redistribute.py``, per arxiv 2112.01075) with peak
        per-chip memory bounded by a constant number of shard-sized staging
        buffers — never a full gather — at the STORED dtype, so the result
        is bitwise-identical to a checkpoint-elastic-restore round trip.
        Afterward the domain is fully re-realized for the new geometry:
        fresh exchange plan/executable (route re-resolved — the tuner is
        consulted under the new mesh's workload key), zeroed ``next`` slot,
        zeroed shells (exactly ``set_quantity``'s scatter), reset analytic
        counters.  Steps built by ``make_step`` close over the OLD mesh and
        must be rebuilt by the caller (the supervisor's ``on_mesh_change``
        hook does this for supervised runs).

        Raises :class:`~stencil_tpu.parallel.redistribute.ReshardImpossibleError`
        when redistribution is structurally impossible (no admissible
        partition on the target devices, source buffers already consumed) —
        the supervisor answers that with the checkpoint-elastic-restore
        fallback.  Returns a stats dict (seconds/bytes/from_mesh/to_mesh).
        """
        from stencil_tpu.parallel.redistribute import (
            ReshardImpossibleError,
            SideGeometry,
            plan_redistribution,
            redistribute_array,
        )
        from stencil_tpu.resilience.retry import buffers_live

        assert self._realized, "reshard() needs a realized domain"
        t0 = time.perf_counter()
        if self._methods in (MethodFlags.AllGather, MethodFlags.RollCompare):
            raise ReshardImpossibleError(
                "debug exchange methods do not support live resharding"
            )
        if self._handles and not self._curr:
            raise ReshardImpossibleError(
                "domain was realized without allocation — nothing to move"
            )
        if self._handles and not buffers_live(self._curr):
            raise ReshardImpossibleError(
                "a donated source buffer was already consumed mid-dispatch; "
                "redistribution has nothing to read — fall back to "
                "checkpoint-elastic-restore"
            )
        devices = list(devices) if devices is not None else jax.devices()
        # the new force_dim is pinned only while deriving the TARGET
        # geometry, then restored until the install point below: a failure
        # anywhere before installation (inadmissible partition, an error
        # mid-collective) must leave the domain — including a
        # set_partition pin — exactly as it was
        old_force = self._force_dim
        new_force = Dim3.of(force_dim) if force_dim is not None else None
        self._force_dim = new_force
        try:
            try:
                mesh, placement, spec, vlast, shell = self._derive_geometry(devices)
            except ValueError as e:
                raise ReshardImpossibleError(
                    f"no admissible partition on the target devices: {e}"
                ) from e
        finally:
            self._force_dim = old_force
        src_geom = SideGeometry.of_domain(self)
        raw = spec.raw_size()
        lo = shell.lo()
        dim = placement.dim()
        dst_geom = SideGeometry(
            dim=(dim.x, dim.y, dim.z),
            n=tuple(spec.sz),
            raw=(raw.x, raw.y, raw.z),
            lo=(lo.x, lo.y, lo.z),
            valid_last=vlast,
            devices=tuple(mesh.devices.flat),
        )
        plan = plan_redistribution(tuple(self._size), src_geom, dst_geom)
        new_curr: Dict[str, jax.Array] = {}
        nbytes = 0
        # one traced+compiled schedule per DISTINCT (components, dtype)
        # signature — fused multi-quantity domains share it (a fresh
        # build_redistribute_fn per quantity would re-trace identical
        # programs: jit caches by function identity)
        from stencil_tpu.parallel.redistribute import build_redistribute_fn

        fn_cache: Dict[tuple, object] = {}
        for h in self._handles:
            fdt = self.field_dtype(h)
            sig = (tuple(h.components), jnp.dtype(fdt).name)
            if sig not in fn_cache:
                fn_cache[sig] = build_redistribute_fn(
                    plan, tuple(h.components), fdt
                )[0]
            new_curr[h.name] = redistribute_array(
                plan, self._curr[h.name], h.components, fdt, mesh, _qspec(h),
                fn=fn_cache[sig],
            )
            nbytes += (
                int(np.prod(tuple(self._size)))
                * h.cell_count()
                * jnp.dtype(fdt).itemsize
            )
        from_mesh = self.mesh_dim()
        # install the new geometry + redistributed buffers; fresh zero
        # ``next`` slot, exactly like realize()
        self._devices = devices
        self._force_dim = new_force
        self.mesh, self.placement = mesh, placement
        self._spec, self._valid_last, self._shell_radius = spec, vlast, shell
        self._curr = new_curr
        # the redistributed arrays come in the backend's default layout
        self._pinned = {}
        gshape = (dim.x * raw.x, dim.y * raw.y, dim.z * raw.z)
        self._next = (
            {h.name: self._zeros(h, gshape) for h in self._handles}
            if self._both_slots_fit() else {}
        )
        # re-realize the exchange plan/executable for the new geometry:
        # the route re-resolves (explicit pin > env > tuned — the tuner is
        # re-keyed automatically, tune_key reads the new placement) and the
        # analytic byte models recompute lazily
        self._exchange_many_fn = None
        self._exchange_nbytes = None
        self._shell_stale = False
        if self._numerics is not None:
            # the stats program closes over the OLD mesh/spec; the engine's
            # signature check would also catch this lazily, but a mesh
            # transition is the one known invalidation point — be explicit
            self._numerics.on_mesh_change()
        t1 = time.perf_counter()
        self._exchange_route = self._resolve_exchange_route()
        self._exchange_fn = self._build_exchange_with_ladder()
        if self._handles:
            self._record_exchange_compile(t1, f"reshard:{self._exchange_route}")
        dt = time.perf_counter() - t0
        telemetry.inc(tm.RESHARDS)
        telemetry.inc(tm.RESHARD_BYTES, nbytes)
        telemetry.observe(tm.RESHARD_SECONDS, dt)
        telemetry.emit_event(
            tm.EVENT_RESHARD,
            from_mesh=list(from_mesh),
            to_mesh=list(self.mesh_dim()),
            seconds=round(dt, 6),
            bytes=nbytes,
            quantities=len(self._handles),
            source=source,
        )
        log_info(
            f"resharded {self._size} from mesh {from_mesh} to "
            f"{self.mesh_dim()} in {dt:.3f}s ({nbytes} B moved in-memory)"
        )
        return {
            "seconds": dt,
            "bytes": nbytes,
            "from_mesh": list(from_mesh),
            "to_mesh": list(self.mesh_dim()),
        }

    def re_realize(self, devices=None, force_dim=None) -> None:
        """Fresh realize onto a new device set, DISCARDING the in-memory
        state (fields re-zero, like a first realize): the first half of
        the checkpoint-elastic-restore fallback — when ``reshard()`` is
        structurally impossible, the supervisor re-realizes here and
        restores the last ring checkpoint onto the new mesh."""
        assert self._realized, "re_realize() follows a realized domain"
        self._devices = list(devices) if devices is not None else None
        self._force_dim = Dim3.of(force_dim) if force_dim is not None else None
        self._curr = {}
        self._next = {}
        self._exchange_fn = None
        self._exchange_many_fn = None
        self._exchange_nbytes = None
        self._shell_stale = False
        if self._numerics is not None:
            self._numerics.on_mesh_change()
        self._realized = False
        self.realize()

    def _resolve_exchange_route(self) -> str:
        """Resolve the z-sweep exchange route for this realize.  Precedence
        (mirrors the stream-alias rule): explicit ``set_exchange_route`` >
        ``STENCIL_EXCHANGE_ROUTE`` (validated read) > the tuned config
        (``tune.best_config`` on the "exchange" workload key) > the static
        ``direct`` fallback (ROADMAP: calibration constants are fallbacks).
        A route the pack pipeline structurally cannot serve (uneven z split,
        unsupported dtype) degrades to ``direct`` with a warning — a stale
        or wrong persisted config must never crash a run the fallback could
        have served.  Every resolution is an ``exchange.route`` telemetry
        decision event."""
        from stencil_tpu.ops.exchange import EXCHANGE_ROUTES, route_supported
        from stencil_tpu.utils.config import env_choice

        route: Optional[str] = None
        source = "static"
        if self._exchange_route_req is not None:
            route, source = self._exchange_route_req, "explicit"
        else:
            env = env_choice(
                "STENCIL_EXCHANGE_ROUTE", "auto", ("auto",) + EXCHANGE_ROUTES
            )
            if env != "auto":
                route, source = env, "env"
        if route is None:
            from stencil_tpu import tune

            cfg = tune.best_config(self.tune_key("exchange"))
            tuned = (cfg or {}).get("exchange_route")
            if tuned is not None:
                if tuned in EXCHANGE_ROUTES:
                    route, source = str(tuned), "tuned"
                else:
                    log_warn(
                        f"tuned exchange_route {tuned!r} is not one of "
                        f"{EXCHANGE_ROUTES}; using the static 'direct' fallback"
                    )
        if route is None:
            route = "direct"
        # degrade only when NO packed sweep of the route can engage (each
        # sweep degrades independently inside the exchange — a yzpack route
        # over an uneven y still packs its z sweep, and vice versa)
        if not route_supported(
            route,
            [self.field_dtype(h) for h in self._handles],
            self._valid_last,
        ):
            log_warn(
                f"exchange route {route!r} ({source}) cannot engage here "
                "(uneven packed axes or unsupported dtype); degrading to "
                "'direct'"
            )
            route, source = "direct", source + "/degraded"
        telemetry.emit_event(
            tm.EVENT_EXCHANGE_ROUTE, route=route, source=source,
            wrap_axes=self._plan_wrap_axes(route),
        )
        return route

    def _plan_wrap_axes(self, route: str) -> str:
        """Record (and return) the mesh axes whose sweep under ``route`` is
        the self-wrap kernel (``ops/exchange.py wrap_axes``): called wherever
        the route is settled, so ``self._wrap_axes`` — the ``wrap_axes`` field
        of every ``domain.exchange`` span — follows ``self._exchange_route``."""
        from stencil_tpu.ops.exchange import uneven_axes, wrap_axes

        raw = self._spec.raw_size()
        mesh_shape = tuple(self.mesh.shape[a] for a in MESH_AXES)
        self._uneven_axes = uneven_axes(
            mesh_shape, self._shell_radius, (raw.x, raw.y, raw.z), self._valid_last
        )
        self._wrap_axes = wrap_axes(
            mesh_shape,
            self._shell_radius,
            (raw.x, raw.y, raw.z),
            [self.field_dtype(h) for h in self._handles],
            all_3d=not any(h.components for h in self._handles),
            valid_last=self._valid_last,
            route=route,
        )
        return self._wrap_axes

    def make_exchange_route_fn(
        self,
        route: str,
        donate: bool = True,
        axes: Tuple[int, ...] = (0, 1, 2),
    ):
        """One jitted exchange over this domain's quantities for ``route``,
        eagerly compiled (compile rides the transient-retry policy, so a
        dropped connection retries instead of dying).  The production
        path uses it at realize; the autotuner's route trials and
        bench-exchange's A/B build non-donating (``donate=False``) variants
        so measuring never consumes the live buffers."""
        from stencil_tpu.resilience import inject
        from stencil_tpu.resilience.retry import execute_with_retry

        fn = make_exchange_fn(
            self.mesh,
            self._shell_radius,
            valid_last=self._valid_last,
            route=route,
            axes=axes,
            donate=donate,
            out_shardings=self._out_formats(),
        )
        if self._handles:
            label = f"compile:exchange:{route}"

            def compile_unit():
                # the fault hook sits INSIDE the retried unit (the run_step
                # dispatch() pattern) so injected connection drops exercise
                # the same retry path real ones take
                inject.maybe_fail("compile", label)
                with telemetry.span(tm.EVENT_COMPILE, total=tm.PHASE_COMPILE, label=label):
                    return fn.lower(self._curr).compile()

            execute_with_retry(compile_unit, label=label)
        return fn

    def _out_formats(self):
        """``out_shardings`` of a program that returns this domain's
        quantities as a dict: None -- leave it to jit, the program's text
        stays as it is -- unless a quantity is pinned row-major
        (``_row_major_format``); then each result keeps its array's own
        layout, so the next program finds what it was built for."""
        if not self._pinned:
            return None
        return {
            h.name: self._pinned.get(h.name, NamedSharding(self.mesh, _qspec(h)))
            for h in self._handles
        }

    def _build_exchange_with_ladder(self):
        """Build (and compile) the production exchange for the resolved
        route.  Packed routes ride a two-rung degradation ladder: a VMEM_OOM
        or COMPILE_REJECT building the packed exchange descends to
        ``direct`` (counted + event-logged by the ladder) instead of failing
        realize."""
        route = self._exchange_route
        if route == "direct":
            return self.make_exchange_route_fn("direct")
        from stencil_tpu.resilience.ladder import DegradationLadder, Rung

        def rung_for(rt: str) -> Rung:
            return Rung(rt, build=lambda rt=rt: self.make_exchange_route_fn(rt))

        def lower(rung, cls, exc):
            return rung_for("direct") if rung.name != "direct" else None

        ladder = DegradationLadder(rung_for(route), lower, label="exchange")
        fn = ladder.built()
        if ladder.rung.name != route:
            self._exchange_route = ladder.rung.name
            telemetry.emit_event(
                tm.EVENT_EXCHANGE_ROUTE, route=ladder.rung.name, source="ladder",
                wrap_axes=self._plan_wrap_axes(ladder.rung.name),
            )
        return fn

    def abstract_arrays(self) -> Dict[str, jax.ShapeDtypeStruct]:
        """Sharded ShapeDtypeStructs matching the quantity arrays — lowering
        inputs for AOT compilation (pairs with ``realize(allocate=False)``)."""
        dim = self.placement.dim()
        raw = self._spec.raw_size()
        gshape = (dim.x * raw.x, dim.y * raw.y, dim.z * raw.z)
        return {
            h.name: jax.ShapeDtypeStruct(
                h.components + gshape,
                self.field_dtype(h),
                sharding=self._pinned.get(h.name, NamedSharding(self.mesh, _qspec(h))),
            )
            for h in self._handles
        }

    # --- geometry accessors ---------------------------------------------------
    def local_spec(self) -> LocalSpec:
        return self._spec

    def subdomain_size(self) -> Dim3:
        return self._spec.sz

    def get_interior(self) -> Rect3:
        """Interior region in interior-local coords (src/stencil.cu:567-610)."""
        return self._spec.interior()

    def get_exterior(self) -> List[Rect3]:
        return self._spec.exterior()

    def num_subdomains(self) -> int:
        return self.placement.dim().flatten()

    def valid_last(self) -> Tuple[Optional[int], Optional[int], Optional[int]]:
        """Per axis, the valid interior cells of the LAST shard where the
        mesh does not divide the extent (every other shard owns
        ``subdomain_size()``; the rest of the last one is padding that no one
        owns), None where it does."""
        return self._valid_last

    def shard_valid(self, idx) -> Dim3:
        """Valid (unpadded) interior extent of the shard at mesh index ``idx``
        (last shard on a padded axis owns the remainder)."""
        idx = Dim3.of(idx)
        dim = self.placement.dim()
        n = self._spec.sz
        return Dim3(
            *(
                (self._valid_last[ax] if (idx[ax] == dim[ax] - 1 and self._valid_last[ax] is not None) else n[ax])
                for ax in range(3)
            )
        )

    # --- data movement --------------------------------------------------------
    def _to_raw_global(self, interior: np.ndarray, dtype) -> np.ndarray:
        """Scatter a (*components, X,Y,Z) user-domain array into the
        shell-carrying global layout (host-side; used for init and small
        domains).  Leading component dims pass through."""
        dim = self.placement.dim()
        n = self._spec.sz
        raw = self._spec.raw_size()
        lo = self._shell_radius.lo()
        comps = interior.shape[:-3]
        out = np.zeros(comps + (dim.x * raw.x, dim.y * raw.y, dim.z * raw.z), dtype=dtype)
        for ix in range(dim.x):
            for iy in range(dim.y):
                for iz in range(dim.z):
                    v = self.shard_valid((ix, iy, iz))
                    src = interior[
                        ...,
                        ix * n.x : ix * n.x + v.x,
                        iy * n.y : iy * n.y + v.y,
                        iz * n.z : iz * n.z + v.z,
                    ]
                    out[
                        ...,
                        ix * raw.x + lo.x : ix * raw.x + lo.x + v.x,
                        iy * raw.y + lo.y : iy * raw.y + lo.y + v.y,
                        iz * raw.z + lo.z : iz * raw.z + lo.z + v.z,
                    ] = src
        return out

    def _from_raw_global(self, raw_arr: np.ndarray) -> np.ndarray:
        dim = self.placement.dim()
        n = self._spec.sz
        raw = self._spec.raw_size()
        lo = self._shell_radius.lo()
        comps = raw_arr.shape[:-3]
        out = np.zeros(comps + (self._size.x, self._size.y, self._size.z), dtype=raw_arr.dtype)
        for ix in range(dim.x):
            for iy in range(dim.y):
                for iz in range(dim.z):
                    v = self.shard_valid((ix, iy, iz))
                    out[
                        ...,
                        ix * n.x : ix * n.x + v.x,
                        iy * n.y : iy * n.y + v.y,
                        iz * n.z : iz * n.z + v.z,
                    ] = raw_arr[
                        ...,
                        ix * raw.x + lo.x : ix * raw.x + lo.x + v.x,
                        iy * raw.y + lo.y : iy * raw.y + lo.y + v.y,
                        iz * raw.z + lo.z : iz * raw.z + lo.z + v.z,
                    ]
        return out

    def set_quantity(self, h: DataHandle, interior: np.ndarray, slot: str = "curr") -> None:
        """Load a full (*components, X,Y,Z) user-domain array into a
        quantity's interior."""
        want = h.components + tuple(self._size)
        assert interior.shape == want, (interior.shape, want)
        raw = self._to_raw_global(np.asarray(interior), self.field_dtype(h))
        where = self._pinned.get(h.name, NamedSharding(self.mesh, _qspec(h)))
        arr = jax.device_put(jnp.asarray(raw), where)
        (self._curr if slot == "curr" else self._next_slot())[h.name] = arr

    def quantity_to_host(self, h: DataHandle, slot: str = "curr") -> np.ndarray:
        """Gather a quantity's interior to a (X,Y,Z) host array (analog of
        reference quantity_to_host, local_domain.cuh:329-346)."""
        arr = (self._curr if slot == "curr" else self._next_slot())[h.name]
        # bf16-storage buffers upcast back to the native dtype at readback
        # (exact: every bfloat16 is an f32)
        return self._from_raw_global(np.asarray(jax.device_get(arr))).astype(
            h.dtype, copy=False
        )

    def region_to_host(self, h: DataHandle, region: Rect3, slot: str = "curr") -> np.ndarray:
        """Arbitrary-region readback in USER-domain (global) coordinates —
        the reference's ``LocalDomain::region_to_host``
        (src/local_domain.cu:97, local_domain.cuh:329-346) lifted to the
        distributed domain.  Gathers only the shards the region touches."""
        assert self._realized
        r = Rect3(Dim3.of(region.lo), Dim3.of(region.hi))
        assert r.lo.all_gt(-1) and (self._size - r.hi).all_gt(-1), (r, self._size)
        dim = self.placement.dim()
        n = self._spec.sz
        raw = self._spec.raw_size()
        lo = self._shell_radius.lo()
        arr = (self._curr if slot == "curr" else self._next_slot())[h.name]
        ext = r.extent()
        out = np.zeros(h.components + (ext.x, ext.y, ext.z), dtype=h.dtype)
        shard_lo = Dim3(*(r.lo[a] // n[a] for a in range(3)))
        shard_hi = Dim3(*((r.hi[a] - 1) // n[a] if r.hi[a] > r.lo[a] else shard_lo[a] for a in range(3)))
        for ix in range(shard_lo.x, min(shard_hi.x, dim.x - 1) + 1):
            for iy in range(shard_lo.y, min(shard_hi.y, dim.y - 1) + 1):
                for iz in range(shard_lo.z, min(shard_hi.z, dim.z - 1) + 1):
                    idx = Dim3(ix, iy, iz)
                    v = self.shard_valid(idx)
                    # overlap of the request with this shard's valid interior
                    olo = Dim3(*(max(r.lo[a], idx[a] * n[a]) for a in range(3)))
                    ohi = Dim3(*(min(r.hi[a], idx[a] * n[a] + v[a]) for a in range(3)))
                    if not (ohi - olo).all_gt(0):
                        continue
                    block = arr[
                        ...,
                        ix * raw.x + lo.x + olo.x - ix * n.x : ix * raw.x + lo.x + ohi.x - ix * n.x,
                        iy * raw.y + lo.y + olo.y - iy * n.y : iy * raw.y + lo.y + ohi.y - iy * n.y,
                        iz * raw.z + lo.z + olo.z - iz * n.z : iz * raw.z + lo.z + ohi.z - iz * n.z,
                    ]
                    out[
                        ...,
                        olo.x - r.lo.x : ohi.x - r.lo.x,
                        olo.y - r.lo.y : ohi.y - r.lo.y,
                        olo.z - r.lo.z : ohi.z - r.lo.z,
                    ] = np.asarray(jax.device_get(block)).astype(
                        h.dtype, copy=False
                    )
        return out

    def interior_to_host(self, h: DataHandle, slot: str = "curr") -> np.ndarray:
        """Whole-interior readback (reference ``interior_to_host``,
        local_domain.cuh:329-346) — alias of ``quantity_to_host``."""
        return self.quantity_to_host(h, slot)

    def mark_shell_stale(self) -> None:
        """Fast-path steps that skip the shell entirely (the single-device
        wrap kernel; any path exchanging bare slabs) leave the carried shell
        holding whatever the last real exchange wrote — arbitrarily old.
        Models using such paths mark the shell stale so raw readback
        re-exchanges first (``quantity_to_host`` reads interiors only and
        never needs this)."""
        self._shell_stale = True

    def raw_to_host(self, h: DataHandle, slot: str = "curr") -> np.ndarray:
        """The raw shell-carrying global array (halos visible) for tests.

        Halos reflect the most recent exchange — for the standard step paths
        that is the exchange at the top of the last iteration (pre-compute
        neighbor values, exactly the reference's shell contents between
        exchanges).  A shell marked stale (``mark_shell_stale``) is first
        refreshed with one production exchange so it is at least that fresh."""
        if self._shell_stale and slot == "curr":
            self._curr = self._exchange_fn(self._curr)
            self._shell_stale = False
        arr = (self._curr if slot == "curr" else self._next_slot())[h.name]
        return np.asarray(jax.device_get(arr)).astype(h.dtype, copy=False)

    def init_by_coords(self, h: DataHandle, fn, include_halo: bool = False,
                       args: tuple = ()) -> None:
        """Device-side init: ``fn(cx, cy, cz)`` maps broadcastable global
        coordinate arrays to values.  Fills the interior (and optionally the
        shell, for analytic whole-domain fields).

        ``args`` are handed to ``fn`` after the coordinates as TRACED
        arguments of the fill program: parameters that change from fill to
        fill (a seed's words) then leave the program's text alone, so the
        compile cache serves every later fill instead of compiling a new
        program per value."""
        with telemetry.span(tm.SPAN_INIT, total=tm.PHASE_INIT, quantity=h.name):
            fill = self._init_program(h, fn, include_halo, len(args))
            self._curr[h.name] = fill(self._curr[h.name], *args)

    def _init_program(self, h: DataHandle, fn, include_halo: bool, n_args: int):
        """The jitted fill of ``init_by_coords``: ``(array, *args) -> array``.
        The old buffer is donated and the values are computed over the raw
        block and selected in under the interior's mask -- one fused pass that
        reads and writes the block in place, so a fill holds one array, not
        the old one, the values and the new one (at 1.87 GB an array and
        14.99 GB of fields on a 16 GB chip the difference is the fill)."""
        n = self._spec.sz
        raw = self._spec.raw_size()
        lo = self._shell_radius.lo()
        comps = h.components

        def per_shard(block, *extra):
            # raw coordinate k of this shard holds global cell o - lo + k
            k = [jnp.arange(raw[a]) for a in range(3)]
            c = [
                lax.axis_index(MESH_AXES[a]) * n[a] - lo[a] + k[a] for a in range(3)
            ]
            vals = fn(c[0][:, None, None], c[1][None, :, None], c[2][None, None, :], *extra)
            vals = jnp.broadcast_to(vals, comps + tuple(raw)).astype(block.dtype)
            if include_halo:
                return vals
            inside = [(k[a] >= lo[a]) & (k[a] < lo[a] + n[a]) for a in range(3)]
            mask = inside[0][:, None, None] & inside[1][None, :, None] & inside[2][None, None, :]
            return jnp.where(mask, vals, block)

        spec = _qspec(h)
        pin = self._pinned.get(h.name)
        return jax.jit(
            shard_map(
                per_shard, mesh=self.mesh,
                in_specs=(spec,) + (P(),) * n_args, out_specs=spec,
            ),
            donate_argnums=0,
            **({} if pin is None else {"out_shardings": pin}),
        )

    # --- the hot path ---------------------------------------------------------
    @contextlib.contextmanager
    def _phase_timer(self, attr: str, histogram: str, span_name: str,
                     sync: bool = False, **span_args):
        """THE timing path for the per-call hot-loop phases.  The phase is
        ALWAYS a ``telemetry.span`` (a profiler annotation: free without a
        profiler session, never a sync).  When exchange-stats (the
        reference's blocking per-call opt-in, stencil.hpp:106-131) or
        telemetry is enabled, one ``perf_counter`` pair additionally feeds
        the reference-parity ``DomainStats`` accumulator (``attr``) and the
        telemetry histogram, and ``sync=True`` adds the honest device sync
        that timing requires (see ``block_until_ready``) inside the span."""
        with telemetry.span(span_name, **span_args):
            if not (self._exchange_stats or telemetry.enabled()):
                yield
                return
            t0 = time.perf_counter()
            yield
            if sync:
                self.block_until_ready()
            dt = time.perf_counter() - t0
        setattr(self.stats, attr, getattr(self.stats, attr) + dt)
        telemetry.observe(histogram, dt)

    def _model_exchange(self) -> int:
        """Analytic bytes of ONE exchange via ``exchange_bytes_total``
        (src/stencil.cu:6-25: every shell cell, the self-filled ones too --
        the ``domain.exchange`` span's ``nbytes`` and the gauge), modeled once
        beside the exchange's own account of its wires and packed sweeps
        (``self._wires``: what the counters read) and cached -- the hot path
        is a None check."""
        if self._exchange_nbytes is None:
            self._exchange_nbytes = (
                self.exchange_bytes_total() if self._handles else 0
            )
            telemetry.set_gauge(
                tm.EXCHANGE_BYTES_PER_EXCHANGE, self._exchange_nbytes
            )
            self._wires = self._exchange_account()
        return self._exchange_nbytes

    def _dispatch_span_args(self, fn, *static) -> dict:
        """The set-up account's part of a dispatch span's arguments: phase
        ``first_dispatch`` (and ``first=1``) the first time this domain calls
        ``fn`` with these static arguments -- the call in which jax traces,
        lowers and compiles or loads -- and ``steady`` ever after, for the
        price of two lookups (``telemetry.dispatch_phase``: the first steady
        dispatch ends set-up)."""
        try:
            seen = self._dispatched.setdefault(fn, set())
        except TypeError:  # unhashable or not weakly referenceable: by identity, held
            seen = self._dispatched_by_id.setdefault(id(fn), (fn, set()))[1]
        first = static not in seen
        args = {"total": telemetry.dispatch_phase(first)}
        if first:  # the span says so; a steady span's arguments stay what they were
            seen.add(static)
            args["first"] = 1
        return args

    def _account_exchanges(self, n: int) -> None:
        """Counter bookkeeping for ``n`` (possibly fused) ``exchange()``s of
        every quantity at the shell radius -- the domain-wide model: what
        ``exchange()`` / ``exchange_many()`` run, and the FALLBACK for a
        caller's own step callable that declares no account of its wires
        (``run_step``)."""
        self._model_exchange()
        self._account_wires(self._wires, n)

    def _account_wires(self, account, raw_steps: int) -> None:
        """Counter bookkeeping for a dispatch of ``raw_steps`` of whatever
        declared what it exchanges (``ops/exchange.py WireAccount``, from the
        message plan that is run: a built step's, or ``exchange()``'s own):
        its count of exchanges, the bytes of its hops summed over subdomains
        -- ``domain.exchange.bytes`` their sum, so one chip counts exchanges
        and no bytes -- and what its packed sweeps move.  Counters are always
        live: an int multiply and one ``inc`` per trafficked hop."""
        units = account.units(raw_steps)
        scale = units * self.num_subdomains()
        telemetry.inc(tm.EXCHANGE_COUNT, units * account.exchanges)
        telemetry.inc(tm.EXCHANGE_BYTES, scale * sum(account.hops.values()))
        for hop, nb in account.hops.items():
            telemetry.inc(tm.EXCHANGE_HOP_BYTES[hop], scale * nb)
        if account.packed[1]:
            telemetry.inc(tm.EXCHANGE_PACKED_BYTES, scale * account.packed[0])
            telemetry.inc(tm.EXCHANGE_PACKED_KERNELS, scale * account.packed[1])
        if account.joint[1]:
            telemetry.inc(tm.EXCHANGE_JOINT_SWEEPS, scale * account.joint[1])

    def exchange(self) -> None:
        """Fill every quantity's halo shell (src/stencil.cu:670-864)."""
        assert self._realized
        nbytes = self._model_exchange()
        with self._phase_timer(
            "time_exchange", tm.EXCHANGE_SECONDS, tm.SPAN_EXCHANGE, sync=True,
            route=self._exchange_route, nbytes=nbytes, count=1,
            wrap_axes=self._wrap_axes, uneven_axes=self._uneven_axes,
            wire_bytes=self._wires.said()[1], joint=self._wires.joint[0],
            **self._dispatch_span_args(self._exchange_fn),
        ):
            self._curr = self._watched_call(
                "exchange", lambda: self._exchange_fn(self._curr)
            )
            self._shell_stale = False
        self._exchange_count += 1
        self._account_exchanges(1)

    def exchange_many(self, steps: int) -> None:
        """Run ``steps`` exchanges in ONE device dispatch (``lax.fori_loop``
        over the exchange).  Timing helper for hosts where a per-call sync
        costs a round trip that would swamp the exchange itself; exchanging is idempotent on a filled
        domain, so looping it measures steady-state exchange cost."""
        assert self._realized
        if self._exchange_many_fn is None:
            inner = self._exchange_fn

            @partial(jax.jit, static_argnums=1, donate_argnums=0)
            def many(arrays, s):
                return lax.fori_loop(0, s, lambda _, a: inner(a), arrays)

            self._exchange_many_fn = many
        nbytes = steps * self._model_exchange()
        with telemetry.span(
            tm.SPAN_EXCHANGE, route=self._exchange_route,
            nbytes=nbytes, count=steps,
            wrap_axes=self._wrap_axes, uneven_axes=self._uneven_axes,
            wire_bytes=self._wires.said()[1], joint=self._wires.joint[0],
            **self._dispatch_span_args(self._exchange_many_fn, steps),
        ):
            self._curr = self._exchange_many_fn(self._curr, steps)
        self._shell_stale = False
        self._exchange_count += steps
        self._account_exchanges(steps)

    def swap(self) -> None:
        """Swap curr/next slots (src/stencil.cu:541-561)."""
        with self._phase_timer("time_swap", tm.SWAP_SECONDS, tm.SPAN_SWAP):
            self._curr, self._next = self._next_slot(), self._curr

    def block_until_ready(self) -> None:
        """Wait for all in-flight device work on the current buffers —
        every shard of every quantity, on every device of the mesh."""
        for a in self._curr.values():
            a.block_until_ready()

    def get_curr(self, h: DataHandle) -> jax.Array:
        return self._curr[h.name]

    def get_next(self, h: DataHandle) -> jax.Array:
        return self._next_slot()[h.name]

    def exchange_bytes_total(self) -> int:
        """Analytic bytes-per-exchange across all subdomains
        (src/stencil.cu:6-25 exchange_bytes_for_method analog)."""
        from stencil_tpu.core.geometry import exchange_bytes

        per_dom = exchange_bytes(
            self._spec,
            [
                self.field_dtype(h).itemsize * h.cell_count()
                for h in self._handles
            ],
        )
        return per_dom * self.num_subdomains()

    def exchange_hop_bytes(self) -> Dict[Tuple[str, str], int]:
        """Bytes ONE ``exchange()`` sends over each mesh hop, keyed
        ``(mesh axis name, side)`` with side in ``low``/``high``, summed
        across subdomains: the message plan of the exchange this domain
        runs (``ops/exchange.py exchange_account`` -- every quantity, the shell
        radius, the realize-resolved route, so a packed sweep counts its
        padded buffer), the function a built step's own account is made of.
        Hops on mesh axes of size 1 report 0: the sweep self-wraps inside
        the chip, no fabric traffic.  Feeds the ``exchange.hop.*.bytes``
        counters of ``exchange()`` / ``exchange_many()`` and the per-hop
        table in the weak-scaling artifacts (docs/observability.md "Fabric
        observatory")."""
        per_dom, n_sub = self._exchange_account().hops, self.num_subdomains()
        return {
            (axis, side): per_dom.get((axis, side), 0) * n_sub
            for axis in MESH_AXES for side in ("low", "high")
        }

    def _exchange_account(self):
        """The account of one ``exchange()`` (``ops/exchange.py
        exchange_account``): the bytes ONE shard receives over each wired hop,
        and what its packed sweeps move."""
        from stencil_tpu.ops.exchange import exchange_account

        raw = self._spec.raw_size()
        return exchange_account(
            tuple(self.mesh.shape[a] for a in MESH_AXES) if self.mesh is not None else (1, 1, 1),
            self._shell_radius, (raw.x, raw.y, raw.z),
            [self.field_dtype(h) for h in self._handles],
            valid_last=self._valid_last, route=self._exchange_route,
            cells=[h.cell_count() for h in self._handles],
        )

    def write_plan(self, prefix: str = "plan", link_model=None) -> str:
        """Dump the communication plan — the analog of the reference's
        per-rank ``plan_<rank>.txt`` (src/stencil.cu:259-353): the placement
        report plus one line per direction with the message extent and bytes
        (all riding the collective exchange), then the projected ICI/DCN
        exchange cost (``parallel/cost.py`` — measured defaults, or a
        ``LinkModel`` built from this framework's pingpong/bench-alltoallv
        output).  Returns the path written."""
        from stencil_tpu.core.direction_map import DIRECTIONS_26
        from stencil_tpu.core.geometry import exchange_bytes
        from stencil_tpu.parallel.cost import (
            LinkModel,
            axis_edge_kinds,
            format_cost_report,
            projected_exchange_cost,
        )

        lines = [self.placement.report(), "", "# messages (method=ppermute for all)"]
        spec = self._spec
        itemsizes = [
            self.field_dtype(h).itemsize * h.cell_count()
            for h in self._handles
        ]
        for d in DIRECTIONS_26:
            if spec.radius.dir(-d) == 0:
                continue
            ext = spec.halo_extent(-d)
            nbytes = sum(spec.halo_bytes(-d, s) for s in itemsizes)
            lines.append(f"dir={d} extent={ext} bytes={nbytes} method=ppermute")
        total = exchange_bytes(spec, itemsizes)
        lines.append(f"# total bytes per exchange per subdomain: {total}")
        link = link_model or LinkModel()
        rows, total_ms = projected_exchange_cost(
            spec, itemsizes, axis_edge_kinds(self.mesh), link
        )
        lines += format_cost_report(rows, total_ms, link, self._halo_mult)
        path = f"{prefix}_{jax.process_index()}.txt"
        from stencil_tpu.utils.artifact import atomic_write_text

        atomic_write_text(path, "\n".join(lines) + "\n")
        return path

    def exchange_bytes_for_method(self, method: MethodFlags) -> int:
        """Per-method byte counter (src/stencil.cu:6-25).  On TPU every
        transport is the collective path, so all bytes are attributed to
        ``Ppermute`` (= reference All) and the debug methods report 0."""
        if method & MethodFlags.Ppermute:
            return self.exchange_bytes_total()
        return 0

    # --- fused step builder ---------------------------------------------------
    def make_step(
        self,
        kernel: StepKernel,
        overlap: bool = True,
        donate: bool = True,
        engine: str = "xla",
        x_radius: int = None,
        stream_path: str = "auto",  # stream engine route:
        # auto|wrap|plane|wavefront (auto: wrap on one device, wavefront
        # when a shell >= 2 allows temporal blocking, plane otherwise)
        separable: bool = False,  # stream engine: kernel is correct on view
        # subsets (each field reads only itself) -> per-field passes may
        # replace the joint pass when many fields blow the VMEM model
        stream_depth: int = None,  # stream engine: cap the temporal depth
        # (auto maximizes it — the right call for bandwidth-bound kernels,
        # wrong for compute-heavy ones, whose VPU work scales with depth)
        stream_overlap: str = "auto",  # stream engine: split-step overlap
        # schedule (ops/stream_plan.py STREAM_OVERLAP): "split" dispatches the
        # interior pass with no data dependency on the shell ppermutes and
        # recomputes the boundary bands from fresh halos afterward —
        # bitwise-identical to "off"; "auto" resolves env > tuned > off
        stream_halo: str = "auto",  # stream engine: halo consumption mode
        # (ops/stream_plan.py STREAM_HALO): "fused" lands the packed yzpack_*
        # exchange messages directly in the pass's level-0 VMEM planes (no
        # big-array halo write at all) — bitwise-identical to "array";
        # "auto" resolves env > tuned > array (docs/tuning.md "Fused halo
        # consumption")
        interpret: bool = False,  # stream engine only: pallas interpret mode
    ):
        """Build ``step(curr) -> next`` fusing exchange + compute.

        ``kernel`` is one ``StepKernel`` or a SEQUENCE of them: the STAGES of
        a time step, run in order inside one device program, each behind its
        own exchange -- a later stage reads what an earlier one wrote, its
        halo included (``models/elastic.py``: velocities, then stresses from
        the new velocities).  ``step(curr, s)`` advances ``s`` whole steps.

        With a halo multiplier ``k`` (``set_halo_multiplier``) each built step
        is a MACRO step: one exchange of ``k*r``-wide shells followed by ``k``
        compute sub-steps over shrinking valid regions — ``step(curr, s)``
        advances ``s*k`` iterations with ``s`` exchanges.

        ``overlap=True`` splits interior/exterior (reference overlap pipeline,
        jacobi3d.cu:265-337): the interior update reads no halo cells and so
        carries no dependency on the ppermutes — XLA schedules them
        concurrently.  ``overlap=False`` computes the whole region after the
        exchange (jacobi3d.cu:312-329 --no-overlap).

        ``engine`` selects the compute lowering for the SAME kernel callable:

        * ``"xla"`` — shifted-slice formulation (this method's body).  Fully
          general (padded shards, N-D data, any shifts) but each shifted
          operand re-reads the block from HBM (~6 reads/cell for a 7-point
          stencil).
        * ``"stream"`` — the plane-streaming engine (``ops/stream.py``):
          x-planes ride a VMEM ring so each HBM plane is read once per pass;
          a uniform shell >= 2 upgrades to the temporal wavefront (m levels
          per pass, padded shards included on the plain variant) and a
          single device to the exchange-free wrap route.  Requires
          elementwise kernels with all shifts within ``x_radius`` (default:
          the max user radius) and no N-D component data.  This is how USER
          stencils reach the flagship paths' speed — the reference's
          user-kernel model (accessor.hpp:13-40) where the cache hierarchy
          is an explicit plane ring.  The ``overlap`` flag is the XLA
          engine's; the stream engine's split-step schedule is selected by
          ``stream_overlap`` instead ("off" | "split" | "auto" — a tuner
          axis, see docs/tuning.md "Stream overlap"); ``stream_depth`` caps
          the temporal depth for compute-heavy kernels.
        """
        assert self._realized
        stages = tuple(kernel) if isinstance(kernel, (list, tuple)) else (kernel,)
        if not stages:
            raise ValueError("make_step needs at least one kernel")
        if engine == "stream":
            from stencil_tpu.ops.stream import make_stream_step

            if x_radius is None:
                x_radius = max(
                    max(self._radius.lo()[ax], self._radius.hi()[ax])
                    for ax in range(3)
                )
            return make_stream_step(
                self, stages, x_radius=x_radius, path=stream_path,
                separable=separable, interpret=interpret, donate=donate,
                max_depth=stream_depth, overlap=stream_overlap,
                halo=stream_halo,
            )
        if engine != "xla":
            raise ValueError(f"unknown engine {engine!r}")
        from stencil_tpu.core.geometry import exterior_of, shrink_by_radius

        n = self._spec.sz
        r_user = self._radius
        shell = self._shell_radius
        mult = self._halo_mult
        if mult > 1 and len(stages) > 1:
            raise ValueError(
                "a step of several stages exchanges before every stage; a halo "
                f"multiplier of {mult} (one exchange per {mult} sub-steps) has "
                "no meaning for it"
            )
        lo = shell.lo()  # allocation offset of the interior
        mesh_shape = tuple(self.mesh.shape[a] for a in MESH_AXES)
        names = [h.name for h in self._handles]

        # pre-exchange interior: cells whose USER-radius stencil support lies
        # entirely inside the valid interior
        interior_rect = shrink_by_radius(self._spec.compute_region(), r_user)
        # padded axes: the last shard's valid cells end before n_pad, so the
        # overlap-safe interior (computable before the exchange) must also
        # stop short of the earliest possible halo: shrink the high side by
        # the padding width.  Non-last shards lose some overlap (their cells
        # there become exterior, computed after the exchange) — correct for
        # every shard, conservative for most.
        pad_shrink = [
            (n[ax] - self._valid_last[ax]) if self._valid_last[ax] is not None else 0
            for ax in range(3)
        ]
        if any(pad_shrink):
            hi = Dim3(
                *(
                    max(interior_rect.hi[ax] - pad_shrink[ax], interior_rect.lo[ax])
                    for ax in range(3)
                )
            )
            interior_rect = Rect3(interior_rect.lo, hi)

        # halo-multiplier sub-step regions (interior-local coords): the region
        # valid after the exchange is the full shell; each sub-step shrinks it
        # by the user radius, landing exactly on the interior after ``mult``
        # sub-steps.  mult == 1 -> a single region == the compute region.
        shell_rect = Rect3(Dim3(0, 0, 0) - shell.lo(), n + shell.hi())
        sub_regions: List[Rect3] = []
        cur_rect = shell_rect
        for _ in range(mult):
            cur_rect = shrink_by_radius(cur_rect, r_user)
            sub_regions.append(cur_rect)

        def rect_to_slices(rect: Rect3):
            return tuple(slice(rect.lo[ax], rect.hi[ax]) for ax in range(3))

        def region_update(kernel, blocks, region, origin):
            views = {k: ShardView(b, lo, region) for k, b in blocks.items()}
            info = BlockInfo(origin, n, self._size, r_user, region)
            return kernel(views, info)

        def write_region(new_block, region, vals):
            idx = tuple(
                slice(lo[ax] + region[ax].start, lo[ax] + region[ax].stop) for ax in range(3)
            )
            # leading component dims (N-D data) ride unsliced
            # stencil-lint: disable=halo-set-in-loop interior compute-region write on the generic correctness-first path, not a halo sliver; the measured fast paths go through ops/stream.py's aliased kernels
            return new_block.at[(Ellipsis,) + idx].set(vals)

        def one_step(blocks):
            for kernel in stages:
                blocks = one_stage(kernel, blocks)
            return blocks

        def one_stage(kernel, blocks):
            """One macro step of one stage: exchange + ``mult`` compute
            sub-steps."""
            origin = tuple(
                lax.axis_index(MESH_AXES[ax]) * n[ax] for ax in range(3)
            )
            if overlap:
                # interior: no shell reads -> no ppermute dependency; XLA
                # schedules it concurrently with the collective
                with jax.named_scope(tm.SPAN_OVERLAP_INTERIOR):
                    int_region = rect_to_slices(interior_rect)
                    int_vals = region_update(kernel, blocks, int_region, origin)
            # joint multi-quantity exchange: all fields fuse into one message
            # per direction (reference packer.cuh:52-69), ≤6 permutes total;
            # the z sweep runs the realize-resolved route, so fused steps
            # escape the 64×-amplified thin-z path exactly like exchange()
            exch = dict(
                zip(
                    names,
                    halo_exchange_multi(
                        [blocks[k] for k in names],
                        shell,
                        mesh_shape,
                        valid_last=self._valid_last,
                        route=self._exchange_route,
                    ),
                )
            )
            cur = exch
            for j, rect in enumerate(sub_regions):
                region = rect_to_slices(rect)
                new_blocks = dict(cur)
                if j == 0 and overlap:
                    for k in names:
                        if k in int_vals:
                            new_blocks[k] = write_region(new_blocks[k], int_region, int_vals[k])
                    # exterior slabs (incl. shell extensions) read fresh halos
                    for ext_rect in exterior_of(rect, interior_rect):
                        ext_region = rect_to_slices(ext_rect)
                        vals = region_update(kernel, cur, ext_region, origin)
                        for k in names:
                            if k in vals:
                                new_blocks[k] = write_region(new_blocks[k], ext_region, vals[k])
                else:
                    vals = region_update(kernel, cur, region, origin)
                    for k in names:
                        if k in vals:
                            new_blocks[k] = write_region(new_blocks[k], region, vals[k])
                cur = new_blocks
            return cur

        def per_shard(steps, *blocks_tuple):
            blocks = dict(zip(names, blocks_tuple))
            # device-side iteration: many steps per dispatch.  The fused,
            # replayed step graph is the TPU analog of the reference's
            # CUDA-Graph pack replay (packer.cuh:168-187) — and in-loop
            # dynamic-update-slices stay in place in HBM.
            blocks = lax.fori_loop(0, steps, lambda _, b: one_step(b), blocks)
            return tuple(blocks[k] for k in names)

        specs = tuple(_qspec(h) for h in self._handles)
        donate_kw = {"donate_argnums": 0} if donate else {}
        # vma validation stays on whenever neither the exchange's blend
        # kernels nor the packed pallas route can engage — user kernels get
        # full varying-manual-axes checking on the plain-DUS path
        from stencil_tpu.ops.exchange import route_vma_check

        check_vma = route_vma_check(
            [self.field_dtype(h) for h in self._handles],
            self._valid_last,
            max((len(h.components) for h in self._handles), default=0),
            self._exchange_route,
        )

        @partial(jax.jit, static_argnums=1, **donate_kw)
        def step(curr: Dict[str, jax.Array], steps: int = 1) -> Dict[str, jax.Array]:
            fn = shard_map(
                partial(per_shard, steps),
                mesh=self.mesh,
                in_specs=specs,
                out_specs=specs,
                check_vma=check_vma,
            )
            outs = fn(*[curr[k] for k in names])
            return dict(zip(names, outs))

        # under a halo multiplier each built step is a MACRO step advancing
        # `mult` raw iterations — consumers that count raw steps (the
        # divergence sentinel) read this factor off the step
        step._raw_steps_per_call = mult
        # what a macro exchanges: every quantity at the shell radius on the
        # domain's route, once a stage (``run_step``'s counters)
        from stencil_tpu.ops.exchange import sum_accounts

        account = sum_accounts([self._exchange_account()] * len(stages), every=mult)
        step._wire_account = lambda: account
        step._span_args = account.span_args
        return step

    def run_step(self, step_fn, steps: int = 1, label: str = None) -> None:
        """Apply a built step to curr and make its output the new curr.

        The built step already fuses the buffer rotation: with donation the
        old curr's HBM is reused for the output (the functional analog of the
        reference's pointer swap, src/local_domain.cu:41-54), so the old
        arrays must not be retained — the ``next`` slot is left untouched.

        ``steps > 1`` runs that many iterations in ONE device dispatch
        (``lax.fori_loop`` inside the shard_map) — essential on TPU, where
        per-dispatch overhead would otherwise dominate small steps.

        This is the resilience layer's DISPATCH boundary (one entry for
        every engine — xla, stream, and the bespoke pallas paths):

        * classified ``TRANSIENT_RUNTIME`` failures (dropped connections,
          RPC unavailability) retry with exponential backoff — guarded by a
          donated-buffer liveness check, so a failure that surfaced AFTER
          donation propagates instead of re-reading freed memory;
        * the ``STENCIL_FAULT_PLAN`` hook fires here with phase
          ``dispatch`` and this call's ``label`` (models pass their name);
        * the dispatch watchdog (``STENCIL_WATCHDOG_S`` /
          ``set_watchdog``) is armed around the dispatch: a wedge past the
          deadline emits a ``watchdog.stall`` event, and in abort mode
          surfaces as a classified ``StallError`` for supervisor recovery;
        * the divergence sentinel (``set_divergence_check``) runs on its
          cadence after a successful dispatch.

        This is also the TELEMETRY boundary: the dispatch counters
        (``domain.step.*``) and analytic exchange bytes are always counted;
        the enqueue is always a ``domain.step`` span (a profiler annotation
        [label, steps]: on the device's clock under a profiler session, free
        without one, never a sync).  The FIRST call of a step with given
        ``steps`` -- where jax traces, lowers and compiles or loads -- is the
        set-up account's phase ``first_dispatch``: its span says ``first=1``
        and its enqueue wall time goes to an always-live total; every later
        call is ``steady``: marked, not timed (``_dispatch_span_args``;
        docs/observability.md "Set-up").  With ``STENCIL_TELEMETRY`` enabled the
        dispatch is additionally honest-synced inside that span and a
        per-raw-iteration histogram sample (``domain.step.seconds``) is taken
        — enabling telemetry therefore adds one device sync per dispatch,
        exactly like exchange-stats, and must stay off in a measured run.
        """
        from stencil_tpu.resilience import inject
        from stencil_tpu.resilience.retry import RetryPolicy, execute_with_retry
        from stencil_tpu.resilience.sentinel import DivergenceSentinel

        if label is None:
            label = getattr(step_fn, "_resilience_label", "step")
        if self._retry_policy is None:
            self._retry_policy = RetryPolicy.from_env()

        def dispatch():
            inject.maybe_fail("dispatch", label)
            return self._watched_call(
                f"dispatch:{label}", lambda: step_fn(self._curr, steps)
            )

        raw = steps * getattr(step_fn, "_raw_steps_per_call", 1)
        timed = telemetry.enabled()
        t0 = time.perf_counter() if timed else 0.0
        # the span is the ENQUEUE (a profiler annotation, never a sync);
        # only STENCIL_TELEMETRY's honest timing waits inside it
        plan_args = getattr(step_fn, "_span_args", dict)()  # a stream step's plan
        if hasattr(step_fn, "_dispatch_args"):  # ... and what THIS dispatch runs of it
            plan_args.update(step_fn._dispatch_args(steps))
        with telemetry.span(
            tm.SPAN_STEP, label=label, steps=raw, **plan_args,
            **self._dispatch_span_args(step_fn, steps),
        ):
            self._curr = execute_with_retry(
                dispatch,
                label=f"dispatch:{label}",
                policy=self._retry_policy,
                buffers=lambda: self._curr,
            )
            if timed:
                self.block_until_ready()
        if timed:
            dt = time.perf_counter() - t0
            telemetry.observe(tm.STEP_SECONDS, dt / max(raw, 1))
        telemetry.inc(tm.STEP_DISPATCHES)
        telemetry.inc(tm.STEP_ITERATIONS, raw)
        # the wires of the fused step: from the account its builder declared
        # of the message plan it runs (``step._wire_account``).  FALLBACK for
        # a caller's own step callable that declares nothing: the domain-wide
        # model, one exchange of every quantity per macro (= raw iterations /
        # halo multiplier) -- modeled bytes, not this step's
        account = getattr(step_fn, "_wire_account", None)
        if account is not None:
            self._account_wires(account(), raw)
        else:
            self._account_exchanges(max(raw // max(self._halo_mult, 1), 1))
        # streaming-engine steps advance interiors only; the carried shell
        # goes stale and raw readback must re-exchange first
        if getattr(step_fn, "_marks_shell_stale", False):
            self.mark_shell_stale()
        if self._sentinel is None:
            self._sentinel = DivergenceSentinel(self._divergence_every)
        elif self._sentinel.every != self._divergence_every:
            # cadence changed mid-run (set_divergence_check on a domain
            # whose sentinel predates the setter): update in place — a
            # rebuild would silently reset steps_done and mislabel every
            # later divergence step
            self._sentinel.set_every(self._divergence_every)
        # sentinel cadence and the reported step index are in RAW iterations:
        # a macro step (halo multiplier on the xla engine) advances `mult`
        # raw iterations per dispatch-step, which the built step declares
        self._sentinel.after_steps(self, raw)
        # the numerics observatory's independent observe cadence (snapshots
        # + guardbands — telemetry/numerics.py).  ALWAYS accounted, even
        # with the cadence off: the engine's step counter must agree with
        # the sentinel's when the observatory is enabled mid-run (a
        # counter that starts at the enable point would mislabel every
        # snapshot and defeat the shared-dispatch dedupe), and off-cadence
        # accounting is two int ops on a jax-free object
        self.numerics().after_steps(raw)
