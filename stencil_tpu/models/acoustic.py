"""Isotropic acoustic wave propagation, space order 8 (Devito / Minimod).

The forward kernel of seismic modelling, RTM and FWI (Devito
``examples/seismic/acoustic``: ``iso_stencil``, kernel ``OT2``; Minimod's
"acoustic isotropic"), the standard 25-point high-order star:

    m u_tt + eta u_t = laplace(u),   m = 1/vp^2,   eta = sponge damping
    u+ = [ 2 m u - (m - s) u- + dt^2 L8(u) ] / (m + s),     s = eta dt / 2

with ``L8`` the eighth-order Laplacian (radius 4 on faces only;
``models/acoustic_reference.py`` has the equations, the grid's numbers and
the plain reference this model is tested against).

What it asks of the runtime, unlike ``Jacobi3D`` and ``AstarothSim``:

* the kernel READS its whole halo: ``Radius.constant(4)`` is exchanged and
  distance 4 is read, so the stream engine runs its plane route (one level
  per pass over a ``2r``-deep ring, an exchange every step);
* two time levels through a contract that knows one: the kernel returns
  ``u <- u+`` and ``u_prev <- u`` in the same pass, the second as ``u``'s
  centre plane and nothing else -- which the engine reads off the kernel's
  jaxpr: it writes ``u+`` into ``u_prev``'s block and hands ``u``'s old
  array on under the name ``u_prev``, a swap of two handles where Devito
  rotates its time buffers by index (the kernel stays as written; a
  ``uc + 0.0`` or a masked copy here would be written as a copy again);
* model fields ``m`` and ``damp`` that are read and never written, and at
  the centre only: like ``u_prev`` they stay out of the step's exchange
  (the engine exchanges what the kernel reads off-centre, which is ``u``
  alone), and they are inputs of the pass and nothing else (it writes what
  the kernel returns with a value of its own, ``u``) -- ``ops/stream_plan.py
  trace_plane_kernel`` learns all three from this kernel, docs/acoustic.md
  says what a step moves;
* a Dirichlet edge on a periodic runtime: the ``FRAME`` outer cells are
  pinned to zero BY THE KERNEL from ``info.coords()`` -- no model field
  could do it (``u+`` has no coefficient that a zero would null), and no
  non-periodic exchange is needed: a read across the periodic seam lands
  in the other side's frame and reads Devito's zero halo.

No source injection and no receivers (the runtime has no sparse point
operation): the seeded initial ``u``, ``u_prev`` stand for the shot.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from stencil_tpu.core.radius import Radius
from stencil_tpu.domain import DistributedDomain
from stencil_tpu.models.acoustic_reference import (
    AMPLITUDE_BOUND,
    COEFFS,
    FRAME,
    RADIUS,
    AcousticGrid,
    seeded_fields,
)
from stencil_tpu.utils.config import PlacementStrategy

#: the quantities, in the order they are added: the wavefield's two time
#: levels, then the source's two model fields
QUANTITIES = ("u", "u_prev", "m", "damp")


class AcousticWave:
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
        # route) | "jnp" (the XLA slice engine, same kernel)
        interpret: bool = False,
        storage_dtype: str = None,  # field buffers' storage axis ("native"
        # | "bf16" | None/"auto" = env > tuned > static native), as
        # AstarothSim has it; the XLA engine degrades to native
        seed_words=(0, 0, 0, 0),  # realize() fills the four quantities
        # from acoustic_reference.seeded_fields and these words; None leaves
        # them to the caller's fill() (all zero until then: m = 0 divides)
    ):
        if kernel_impl not in ("pallas", "jnp"):
            raise ValueError(f"unknown kernel_impl {kernel_impl!r}")
        self.grid = AcousticGrid((x, y, z), nbl=nbl, spacing=spacing)
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
            where="acoustic",
            engine_ok=self.kernel_impl == "pallas",
            engine_why="the XLA slice engine has no f32-accumulate kernels",
        )
        if sd != "native":
            self.dd.set_storage(sd)
        self.dd.realize()
        if self.seed_words is not None:
            self.fill(seeded_fields(self.grid), (np.asarray(self.seed_words, dtype=np.uint32),))
        # numerics guardband (docs/observability.md "Numerics observatory"):
        # at Devito's CFL the update does not grow the wave's energy and the
        # sponge only removes it, so the wavefield stays near its seeded
        # amplitude (< AMPLITUDE_BOUND); twice that is far above rounding and
        # focusing, far below a blow-up, which doubles every few steps
        from stencil_tpu.telemetry.numerics import magnitude_envelope

        self.dd.numerics().register_guardband(
            magnitude_envelope(2.0 * AMPLITUDE_BOUND, quantities=("u", "u_prev"))
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
        ``rebuild_after_reshard`` (the AstarothSim twin)."""
        if self.kernel_impl == "pallas":
            # x_radius=4: plan_stream admits wrap and wavefront at x_radius 1
            # only, so this is the plane route, jointly over the four
            # quantities (the kernel couples them: not separable)
            return self.dd.make_step(
                self._kernel, engine="stream", x_radius=RADIUS, interpret=self.interpret
            )
        return self.dd.make_step(self._kernel)

    def rebuild_after_reshard(self) -> None:
        """Rebuild the step for the domain's CURRENT mesh (the supervisor's
        ``on_mesh_change`` hook)."""
        self._step = self._build_step()

    def _kernel(self, views, info):
        u = views["u"]
        uc = u.center()
        # grouped by distance: c_k times the six neighbours at distance k,
        # in acoustic_reference._update's order
        acc = (3.0 * COEFFS[0]) * uc
        for k in range(1, RADIUS + 1):
            acc = acc + COEFFS[k] * (
                ((u.sh(k, 0, 0) + u.sh(-k, 0, 0)) + (u.sh(0, k, 0) + u.sh(0, -k, 0)))
                + (u.sh(0, 0, k) + u.sh(0, 0, -k))
            )
        m = views["m"].center()
        s = views["damp"].center() * (0.5 * self.grid.dt)
        new = (2.0 * m * uc - (m - s) * views["u_prev"].center() + self.grid.dt2_h2 * acc) / (m + s)
        # the frame: distance to the nearest outer face, negative inside it
        # (one integer plane and one compare, whatever the engine's shapes)
        g = info.global_size
        edge = None
        for c, n in zip(info.coords(), (g.x, g.y, g.z)):
            d = jnp.minimum(c - FRAME, (n - FRAME - 1) - c)
            edge = d if edge is None else jnp.minimum(edge, d)
        # u_prev <- u needs no pin: u is already 0 in the frame -- and must get
        # none: returned as the centre plane itself it is a rename, not a copy
        return {"u": jnp.where(edge >= 0, new, 0.0), "u_prev": uc}

    def step(self, steps: int = 1) -> None:
        """Advance ``steps`` RAW time steps in one device program."""
        self.dd.run_step(self._step, steps, label="acoustic")

    def field(self, name: str = "u") -> np.ndarray:
        return self.dd.quantity_to_host(self.handles[name])

    def block_until_ready(self) -> None:
        self.dd.block_until_ready()
