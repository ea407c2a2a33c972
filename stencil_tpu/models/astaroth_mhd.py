"""Astaroth's MHD step: compressible MHD at sixth order, three RK3 substeps a step.

The solver Astaroth ships as ``acc-runtime/samples/mhd_modular/mhdsolver.ac``
(the Pencil Code's continuity, momentum, induction and entropy equations;
Pekkilä et al., Comput. Phys. Commun. 217 (2017), arXiv:2103.01597), the code
the reference's ``bin/astaroth_sim.cu`` imitates and ``models/astaroth.py
AstarothSim`` stands in for with a proxy: THIS is the real step
(``models/astaroth_mhd_reference.py`` has the equations, the departures from
the source and the plain reference this model is tested against;
docs/astaroth-mhd.md says how to run it).

What it asks of the runtime, unlike every other model:

* SIXTEEN quantities, the eight fields and the eight ``*_prev`` of Astaroth's
  two-buffer Runge-Kutta: every substep returns ``{q: new, q_prev:
  views[q].center()}`` for all eight, which the engine reads off the kernel's
  jaxpr as eight RENAMES (``ops/stream_plan.py trace_plane_kernel``): the new
  value of ``q`` lands in ``q_prev``'s block and the two handles swap, eight
  pairs at once in each of three stages -- 16 arrays read and 8 written a
  substep, nothing copied.  Three swaps a step is an odd permutation: the
  step loop runs two steps a trip (``_carry_period``);
* a step of THREE stages that all exchange the same eight fields (``make_step``
  takes the sequence of substeps), the eight ``*_prev`` read at the centre
  only and so in no message;
* radius 3 read at full distance on every axis, and the mixed differences'
  IN-PLANE DIAGONALS ``sh(0, +-k, +-k)``, ``sh(+-k, +-k, 0)``, ``sh(+-k, 0,
  +-k)``: the y-z corner of a loaded plane and the x-y / x-z edge halos, on a
  box that is periodic and nowhere zero, so every fill is seen;
* some 840 arithmetic operations a cell a substep over 296 reads: the first
  kernel the VPU bounds, not the HBM.

One kernel a substep, the same ``astaroth_mhd_reference.substep`` the plain
reference runs, over reads that go through one ``Taps``: each ``(dx, dy, dz)``
of a field is read ONCE and shared by every operator that needs it.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from stencil_tpu.core.radius import Radius
from stencil_tpu.domain import DistributedDomain
from stencil_tpu.models.astaroth_mhd_reference import (
    COEFFS,
    FIELDS,
    QUANTITIES,
    RADIUS,
    SUBSTEPS,
    MhdSetup,
    Taps,
    seeded_fields,
    substep,
)
from stencil_tpu.utils.config import PlacementStrategy


class AstarothMHD:
    def __init__(
        self,
        x: int,
        y: int,
        z: int,
        setup: MhdSetup = None,  # the coefficients, the fixed dt and the
        # seeded state's numbers; None = MhdSetup's own, on this shape
        strategy: PlacementStrategy = PlacementStrategy.NodeAware,
        devices=None,
        kernel_impl: str = "pallas",  # "pallas" (stream engine, plane
        # route) | "jnp" (the XLA slice engine, same kernels)
        interpret: bool = False,
        storage_dtype: str = None,  # as AcousticWave has it
        seed_words=(0, 0, 0, 0),  # realize() fills the sixteen quantities
        # from astaroth_mhd_reference.seeded_fields and these words; None
        # leaves them to the caller's fill()
    ):
        if kernel_impl not in ("pallas", "jnp"):
            raise ValueError(f"unknown kernel_impl {kernel_impl!r}")
        self.setup = MhdSetup((x, y, z)) if setup is None else setup
        if tuple(self.setup.shape) != (x, y, z):
            raise ValueError(f"the set-up is for {self.setup.shape}, the domain {(x, y, z)}")
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
            where="astaroth-mhd",
            engine_ok=self.kernel_impl == "pallas",
            engine_why="the XLA slice engine has no f32-accumulate kernels",
        )
        if sd != "native":
            self.dd.set_storage(sd)
        self.dd.realize()
        if self.seed_words is not None:
            self.fill(seeded_fields(self.setup), (np.asarray(self.seed_words, dtype=np.uint32),))
        # numerics guardband (docs/observability.md "Numerics observatory"):
        # every field starts within ``amplitude`` of its rest value and the
        # flow is subsonic and diffusive -- sound and Alfven waves carry the
        # perturbations about, the transport terms wear them down; four times
        # the seeded bound is far above any focusing, far below a blow-up
        from stencil_tpu.telemetry.numerics import magnitude_envelope

        s = self.setup
        for group, rest in ((FIELDS[1:], 0.0), (("lnrho",), abs(s.lnrho0))):
            names = [q + tail for q in group for tail in ("", "_prev")]
            self.dd.numerics().register_guardband(
                magnitude_envelope(rest + 4.0 * s.amplitude, quantities=names)
            )
        self._step = self._build_step()

    def fill(self, fields: dict, args: tuple = ()) -> None:
        """Fill quantities from ``{name: f(x, y, z, *args)}`` on the device;
        ``args`` (a seed's words) are traced, so one compiled fill per
        quantity serves every seed."""
        for name, fn in fields.items():
            self.dd.init_by_coords(self.handles[name], fn, args=args)

    def _build_step(self):
        """The ONE step-construction site, shared by ``realize()`` and
        ``rebuild_after_reshard``."""
        stages = tuple(self._substep(s) for s in range(SUBSTEPS))
        if self.kernel_impl == "pallas":
            return self.dd.make_step(
                stages, engine="stream", x_radius=RADIUS, interpret=self.interpret
            )
        return self.dd.make_step(stages)

    def rebuild_after_reshard(self) -> None:
        self._step = self._build_step()

    def _substep(self, s: int):
        def kernel(views, info):
            del info  # periodic everywhere: no cell knows where it is
            taps = Taps(lambda f, dx, dy, dz: views[f].sh(dx, dy, dz))
            prev = (lambda f: views[f + "_prev"].center()) if s else None
            new = substep(self.setup, taps, prev, *COEFFS[s])
            # q_prev <- q as the centre plane ITSELF: a rename, not a copy
            new.update({f + "_prev": views[f].center() for f in FIELDS})
            return new

        return kernel

    def step(self, steps: int = 1) -> None:
        """Advance ``steps`` whole time steps (three substeps each) in one
        device program."""
        self.dd.run_step(self._step, steps, label="astaroth-mhd")

    def field(self, name: str) -> np.ndarray:
        return self.dd.quantity_to_host(self.handles[name])

    def block_until_ready(self) -> None:
        self.dd.block_until_ready()
