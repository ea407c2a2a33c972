"""Isotropic elastic wave propagation, space order 8 (Devito / Minimod).

The velocity-stress scheme on a staggered grid (Devito
``examples/seismic/elastic``: ``ForwardOperator``, ``elastic_stencil``;
Minimod's "elastic isotropic"; Virieux 1986 / Levander 1988), first order in
time, eighth order in space:

    v_i+    = damp * ( v_i    + dt * b  * sum_j D_j tau_ij )                    (stage V)
    tau_ij+ = damp * ( tau_ij + dt * ( lam * delta_ij * sum_k D_k v_k+
                                       + mu * (D_i v_j+ + D_j v_i+) ) )         (stage T)

(``models/elastic_reference.py`` has the staggered positions, the weights,
the grid's numbers and the plain reference this model is tested against.)

What it asks of the runtime, beyond what ``AcousticWave`` asks:

* thirteen quantities -- nine wavefields, four model fields -- where four
  coupled ones are all that fit ONE plane pass at 608 x 608: the stream
  engine forms each stage's passes from the kernel's own footprint, a pass
  carrying only the quantities its outputs touch and a VMEM ring only for
  those read off-centre along x (``ops/stream_plan.py plan_plane_passes``);
* a time step of TWO stages: stage T reads the velocities stage V has just
  written, off-centre, so the step exchanges between its stages --
  ``make_step`` takes the stages in order and every stage gets the exchange
  its own footprint asks for (V: the six stresses; T: the three velocities);
* asymmetric reads: the staggered difference reads ``-3..+4`` or ``-4..+3``,
  radius 4 on both sides of the shell;
* everything in place: a step holds the thirteen arrays and nothing else.

As in ``AcousticWave`` the ``FRAME`` outer cells of every wavefield are pinned
to zero by the kernels from ``info.coords()``, and there is no source and no
receiver: the seeded velocity packet stands for the shot.
docs/elastic.md says what a step moves.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from stencil_tpu.core.radius import Radius
from stencil_tpu.domain import DistributedDomain
from stencil_tpu.models.elastic_reference import (
    AMPLITUDE_BOUND,
    FRAME,
    QUANTITIES,
    RADIUS,
    STRESSES,
    VELOCITIES,
    AcousticGrid,
    dt_of,
    seeded_fields,
    staggered,
    update_t,
    update_v,
)
from stencil_tpu.utils.config import PlacementStrategy


class ElasticWave:
    def __init__(
        self,
        x: int,
        y: int,
        z: int,
        nbl: int = 40,  # sponge cells per side (Devito's benchmark default)
        spacing: float = 20.0,  # m
        strategy: PlacementStrategy = PlacementStrategy.NodeAware,
        devices=None,
        kernel_impl: str = "pallas",  # "pallas" (stream engine, plane
        # route) | "jnp" (the XLA slice engine, same kernels)
        interpret: bool = False,
        storage_dtype: str = None,  # as AcousticWave has it
        seed_words=(0, 0, 0, 0),  # realize() fills the thirteen quantities
        # from elastic_reference.seeded_fields and these words; None leaves
        # them to the caller's fill()
    ):
        if kernel_impl not in ("pallas", "jnp"):
            raise ValueError(f"unknown kernel_impl {kernel_impl!r}")
        self.grid = AcousticGrid((x, y, z), nbl=nbl, spacing=spacing)
        self.dt = dt_of(self.grid)
        self.dd = DistributedDomain(x, y, z)
        self.dd.set_radius(Radius.constant(RADIUS))
        self.dd.set_placement(strategy)
        if devices is not None:
            self.dd.set_devices(devices)
        self.handles = {q: self.dd.add_data(q, dtype=jnp.float32) for q in QUANTITIES}
        self.kernel_impl = kernel_impl
        self.interpret = interpret
        self.storage_dtype_request = storage_dtype
        self.seed_words = None if seed_words is None else tuple(seed_words)
        self._step = None

    def realize(self) -> None:
        from stencil_tpu.ops.jacobi_pallas import resolve_storage_dtype

        tuned = None
        if self.storage_dtype_request in (None, "auto") and self.kernel_impl == "pallas":
            from stencil_tpu import tune

            tuned = (tune.best_config(self.dd.tune_key("stream")) or {}).get("storage_dtype")
        sd, _src = resolve_storage_dtype(
            self.storage_dtype_request,
            tuned,
            [h.dtype for h in self.handles.values()],
            where="elastic",
            engine_ok=self.kernel_impl == "pallas",
            engine_why="the XLA slice engine has no f32-accumulate kernels",
        )
        if sd != "native":
            self.dd.set_storage(sd)
        self.dd.realize()
        if self.seed_words is not None:
            self.fill(seeded_fields(self.grid), (np.asarray(self.seed_words, dtype=np.uint32),))
        # numerics guardband: the scheme conserves the wave's energy and the
        # sponge only removes it, so a velocity stays near its seeded
        # amplitude -- an interface into a softer layer raises it by the root
        # of the impedance ratio, 1.7 at most on this model -- and a stress
        # near impedance x velocity; four times that is far above focusing,
        # far below a blow-up
        from stencil_tpu.telemetry.numerics import magnitude_envelope

        self.dd.numerics().register_guardband(
            magnitude_envelope(4.0 * AMPLITUDE_BOUND, quantities=VELOCITIES)
        )
        self.dd.numerics().register_guardband(
            magnitude_envelope(4.0 * AMPLITUDE_BOUND * self.impedance_max, quantities=STRESSES)
        )
        self._step = self._build_step()

    @property
    def impedance_max(self) -> float:
        """``rho vp`` of the fastest layer (Gardner's rho)."""
        return 0.31 * (1000.0 * self.grid.vp_max) ** 0.25 * self.grid.vp_max

    def fill(self, fields: dict, args: tuple = ()) -> None:
        """Fill quantities from ``{name: f(x, y, z, *args)}`` on the device;
        ``args`` (a seed's words) are traced, so one compiled fill per
        quantity serves every seed."""
        for name, fn in fields.items():
            self.dd.init_by_coords(self.handles[name], fn, args=args)

    def _build_step(self):
        """The ONE step-construction site, shared by ``realize()`` and
        ``rebuild_after_reshard``."""
        stages = (self._stage_v, self._stage_t)
        if self.kernel_impl == "pallas":
            return self.dd.make_step(
                stages, engine="stream", x_radius=RADIUS, interpret=self.interpret
            )
        return self.dd.make_step(stages)

    def rebuild_after_reshard(self) -> None:
        self._step = self._build_step()

    def _stage(self, views, info, outputs, update):
        f = {q: v.center() for q, v in views.items()}

        def diff(q, axis, direction):
            def read(o):
                off = [0, 0, 0]
                off[axis] = o
                return views[q].sh(*off)

            return staggered(read, direction)

        # the frame: distance to the nearest outer face, negative inside it
        g = info.global_size
        edge = None
        for c, n in zip(info.coords(), (g.x, g.y, g.z)):
            d = jnp.minimum(c - FRAME, (n - FRAME - 1) - c)
            edge = d if edge is None else jnp.minimum(edge, d)
        dt_h = self.dt / self.grid.spacing
        return {q: jnp.where(edge >= 0, update(q, f, diff, dt_h), 0.0) for q in outputs}

    def _stage_v(self, views, info):
        return self._stage(views, info, VELOCITIES, update_v)

    def _stage_t(self, views, info):
        return self._stage(views, info, STRESSES, update_t)

    def step(self, steps: int = 1) -> None:
        """Advance ``steps`` whole time steps (both stages) in one device
        program."""
        self.dd.run_step(self._step, steps, label="elastic")

    def field(self, name: str) -> np.ndarray:
        return self.dd.quantity_to_host(self.handles[name])

    def block_until_ready(self) -> None:
        self.dd.block_until_ready()
