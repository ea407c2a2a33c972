"""Astaroth MHD proxy: radius-3, sin-wave field, 6-neighbor averaging.

Of the two Astaroth models THIS is the proxy (the reference's stand-in with
Astaroth's communication volume and a cheap kernel); the real right-hand side
-- the MHD equations at sixth order, three Runge-Kutta substeps a step -- is
``models/astaroth_mhd.py AstarothMHD`` (docs/astaroth-mhd.md).

Parity target: reference bin/astaroth_sim.cu — a proxy for the Astaroth
magnetohydrodynamics code used to study compute/communication overlap:

* radius 3 in all 26 directions (astaroth_sim.cu:184)
* init: ``sin(2*pi/period * (x + y + z))`` over the interior
  (astaroth_sim.cu:15-61; period = 10 by default there)
* stencil: mean of the 6 face neighbors at distance 1 via ``Accessor``
  (astaroth_sim.cu:65-83) — the radius-3 halo is exchanged even though the
  proxy kernel reads only distance 1, exactly like the reference (it models
  Astaroth's real communication volume with a cheap kernel)
* loop: interior launch / exchange / exterior launches, 5 fixed iterations
  (astaroth_sim.cu:223-274)

The reference keeps 3 more quantities commented out (astaroth_sim.cu:193-196);
``num_quantities`` makes that scaling axis explicit here (the real Astaroth
exchanges 8 fields).

The pallas path runs ``_kernel`` VERBATIM under the plane-streaming engine
(``ops/stream.py``): the default ``schedule="auto"`` upgrades to the m-level
temporal wavefront — m <= 3 x the halo multiplier, since the radius-3 shell
feeds 3 levels of the distance-1 stencil per multiplier step (a
``set_halo_multiplier(2)`` run wavefronts 6 levels per exchange) — whenever
shards are even, ~2.6x faster at 512^3 than the per-step schedule; on one
device it upgrades further to the exchange-free wrap route.  ``--schedule
per-step`` restores exact exchange-cadence parity with the reference (one
exchange per iteration, modeling Astaroth's real communication volume).
"""

from __future__ import annotations

import math

import jax.numpy as jnp
import numpy as np

from stencil_tpu.core.radius import Radius
from stencil_tpu.domain import DistributedDomain
from stencil_tpu.utils.config import MethodFlags, PlacementStrategy


class AstarothSim:
    def __init__(
        self,
        x: int,
        y: int,
        z: int,
        num_quantities: int = 1,
        period: float = 10.0,
        overlap: bool = True,
        strategy: PlacementStrategy = PlacementStrategy.NodeAware,
        devices=None,
        dtype=jnp.float32,
        kernel_impl: str = "jnp",  # "jnp" | "pallas" (plane streaming)
        interpret: bool = False,
        schedule: str = "auto",  # "auto" (DEFAULT: the radius-3 shell
        # already feeds 3 levels of the distance-1 stencil, so exchange
        # every m steps (m <= 3 x the halo multiplier) and run an m-level
        # wavefront kernel — same field values up to last-ulp fusion
        # effects, ~1/m the traffic; a single device upgrades to the
        # exchange-free wrap route) | "wavefront" (forced: raises when not
        # viable) | "per-step" (reference parity escape hatch: exchange
        # every iteration, modeling Astaroth's real communication volume —
        # astaroth_sim.cu:223-274)
        check_divergence_every: int = 0,  # divergence sentinel cadence
        # (resilience/sentinel.py); 0 = off
        stream_overlap: str = "auto",  # pallas engine only: the stream
        # engine's split-step overlap schedule (ops/stream.py
        # STREAM_OVERLAP; "auto" = env > tuned > static off)
        stream_halo: str = "auto",  # pallas engine only: the stream
        # engine's halo consumption mode (ops/stream_plan.py STREAM_HALO;
        # "fused" lands the packed yzpack_* messages directly in the
        # pass's VMEM planes; "auto" = env > tuned > static array)
        exchange_route: str = None,  # pin the halo exchange's y/z-sweep
        # route (ops/exchange.py EXCHANGE_ROUTES; None/"auto" = env >
        # tuned > static direct)
        storage_dtype: str = None,  # field buffers' storage axis ("native"
        # | "bf16" | None/"auto" = env > tuned > static native): bf16
        # stores f32 fields at 2 B/cell end-to-end while the stream kernels
        # accumulate at f32; the XLA engine degrades to native
    ):
        self.dd = DistributedDomain(x, y, z)
        self.dd.set_radius(Radius.constant(3))  # astaroth_sim.cu:184
        self.dd.set_placement(strategy)
        if devices is not None:
            self.dd.set_devices(devices)
        self.period = period
        self.handles = [
            self.dd.add_data(f"d{i}", dtype=dtype) for i in range(num_quantities)
        ]
        self.overlap = overlap
        self.kernel_impl = kernel_impl
        self.interpret = interpret
        if schedule not in ("auto", "per-step", "wavefront"):
            raise ValueError(f"unknown schedule {schedule!r}")
        self.schedule = schedule
        self.stream_overlap = stream_overlap
        self.stream_halo = stream_halo
        if exchange_route not in (None, "auto"):
            self.dd.set_exchange_route(exchange_route)
        self.storage_dtype_request = storage_dtype
        self._storage_dtype = "native"
        if check_divergence_every:
            self.dd.set_divergence_check(check_divergence_every)
        self._step = None

    def realize(self) -> None:
        # storage dtype resolves BEFORE allocation (explicit >
        # STENCIL_STORAGE_DTYPE > tuned "stream" config > static native);
        # only the pallas (stream) engine has f32-accumulate kernels
        from stencil_tpu.ops.jacobi_pallas import resolve_storage_dtype

        tuned = None
        if self.storage_dtype_request in (None, "auto") and self.kernel_impl == "pallas":
            from stencil_tpu import tune

            cfg = tune.best_config(self.dd.tune_key("stream"))
            tuned = (cfg or {}).get("storage_dtype")
        sd, _src = resolve_storage_dtype(
            self.storage_dtype_request,
            tuned,
            [h.dtype for h in self.handles],
            where="astaroth",
            engine_ok=self.kernel_impl == "pallas",
            engine_why="the XLA slice engine has no f32-accumulate kernels",
        )
        self._storage_dtype = sd
        if sd != "native":
            self.dd.set_storage(sd)
        self.dd.realize()
        w = 2 * math.pi / self.period
        for h in self.handles:
            self.dd.init_by_coords(h, lambda x, y, z: jnp.sin(w * (x + y + z)))
        # shipped numerics guardband (docs/observability.md "Numerics
        # observatory"): the mean-of-6 update is non-expansive, so every
        # quantity's magnitude stays under its unit-amplitude sin init —
        # a growing absmax means the numerics drifted.  Envelope at 1.5x
        # the amplitude: far above any rounding, far below a real blow-up.
        from stencil_tpu.telemetry.numerics import magnitude_envelope

        self.dd.numerics().register_guardband(
            magnitude_envelope(1.5, quantities=tuple(h.name for h in self.handles))
        )
        if self.dd.halo_multiplier() != 1 and self.schedule == "per-step":
            # on EITHER kernel_impl a multiplier means fewer, wider
            # exchanges — the opposite of the cadence 'per-step' promises
            raise ValueError(
                "schedule='per-step' (exchange-cadence parity) "
                "contradicts a halo multiplier; use schedule='auto'"
            )
        if self.kernel_impl == "pallas":
            # the plane-streaming ENGINE (ops/stream.py) runs the model's own
            # _kernel verbatim: per-step exchange = plane route, wavefront
            # schedule = the engine's m-level temporal route (m <= 3 x the
            # halo multiplier — the radius-3 shell feeds 3 levels of the
            # distance-1 stencil per multiplier step); step() counts RAW
            # iterations on every engine (see AstarothSim.step)
            if not self.overlap:
                raise ValueError(
                    "overlap=False has no meaning for the fused pallas step; "
                    "use kernel_impl='jnp' for overlap comparisons"
                )
        elif self.schedule == "wavefront":
            raise ValueError("schedule='wavefront' requires kernel_impl='pallas'")
        self._step = self._build_step()

    def _build_step(self):
        """The ONE step-construction site, shared by ``realize()`` and
        ``rebuild_after_reshard`` — every knob threaded into ``make_step``
        lives here exactly once, so a post-reshard rebuild can never
        silently drop an axis the first build carried."""
        if self.kernel_impl == "pallas":
            path = {"auto": "auto", "wavefront": "wavefront", "per-step": "plane"}[
                self.schedule
            ]
            return self.dd.make_step(
                self._kernel,
                engine="stream",
                x_radius=1,
                stream_path=path,
                # _kernel updates each field from itself only, so many-field
                # runs may stream per-field at full wavefront depth
                separable=True,
                interpret=self.interpret,
                stream_overlap=self.stream_overlap,
                stream_halo=self.stream_halo,
            )
        return self.dd.make_step(self._kernel, overlap=self.overlap)

    def rebuild_after_reshard(self) -> None:
        """Rebuild the step for the domain's CURRENT mesh — the
        supervisor's ``on_mesh_change`` hook (the Jacobi3D twin): a
        reshard or cross-mesh restore leaves ``self.dd`` on the new
        geometry, and the built step closes over the old one."""
        self._step = self._build_step()

    @property
    def _wavefront_m(self) -> int:
        """CURRENT wavefront depth (0 = per-step) — read from the live
        stream plan, which the engine's runtime VMEM fallback may have
        stepped down after realize()."""
        plan = getattr(self._step, "_stream_plan", None)
        if plan is not None and plan["route"] == "wavefront":
            return plan["m"]
        return 0

    def _kernel(self, views, info):
        # iterate the views HANDED IN (not self.handles): each field updates
        # from itself only, so the kernel is correct on any subset — the
        # separability the stream engine exploits for per-field passes
        out = {}
        for name, src in views.items():
            out[name] = (
                src.sh(-1, 0, 0)
                + src.sh(0, -1, 0)
                + src.sh(0, 0, -1)
                + src.sh(1, 0, 0)
                + src.sh(0, 1, 0)
                + src.sh(0, 0, 1)
            ) / 6.0
        return out

    def step(self, steps: int = 1) -> None:
        """Advance ``steps`` RAW iterations — uniform across engines (the
        stream engine counts raw iterations natively; the XLA route under a
        halo multiplier is built in macro steps, so ``steps`` must divide
        into whole macros there)."""
        mult = self.dd.halo_multiplier()
        if self.kernel_impl == "jnp" and mult > 1:
            if steps % mult:
                raise ValueError(
                    f"steps={steps} must be a multiple of the halo "
                    f"multiplier {mult} on the jnp engine (macro steps)"
                )
            steps //= mult
        # label routes dispatch-phase fault injection / retry logs to THIS
        # model (the stream engine's own ladder hooks stay labeled stream:*)
        self.dd.run_step(self._step, steps, label="astaroth")

    def field(self, i: int = 0) -> np.ndarray:
        return self.dd.quantity_to_host(self.handles[i])

    def block_until_ready(self) -> None:
        self.dd.block_until_ready()
