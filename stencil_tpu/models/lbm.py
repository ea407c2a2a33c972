"""D3Q19 BGK lattice Boltzmann on a periodic box (FluidX3D's benchmark).

The set-up every row of FluidX3D's published device table runs (``src/setup.cpp``
``benchmark``: ``LBM lbm(256u, 256u, 256u, 1.0f/6.0f)``, arXiv:2112.08926):
nineteen populations ``f_0..f_18``, streamed along their directions and relaxed
towards the local equilibrium every step (``models/lbm_reference.py`` has the
equations, the direction table, the departures from the source and the plain
reference this model is tested against; docs/lbm.md says how to run it).

What it asks of the runtime, unlike every other model:

* NINETEEN quantities coupled in every output: ``rho`` and ``u`` are sums over
  all of them, so nothing can be cut into passes or run field by field
  (``separable=False``, one kernel, one pass whatever route the planner takes);
* every population but the rest one is read at exactly ONE offset,
  ``views[f_i].sh(-c_i)`` (pull streaming), twelve of them DIAGONAL (two
  non-zero components): the first kernel to read an edge halo, which the x,
  then y, then z sweeps of the exchange (or the plane pass's in-VMEM fills, or
  the wrap route's own index maps and rotates) must have filled;
* a box periodic on every side and nowhere zero: every wrap and every fill is
  seen by a comparison with the reference.

On one device the planner takes the stream engine's WRAP route (bare interiors,
the periodic boundary folded into the kernel, ``m`` steps a trip through HBM);
on a mesh the wavefront or plane route over real exchanges.  All of that is
``make_step(engine="stream")``'s to decide: every axis is left at ``auto``.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from stencil_tpu.core.radius import Radius
from stencil_tpu.domain import DistributedDomain
from stencil_tpu.models.lbm_reference import (
    C,
    NAMES,
    Q,
    RHO_BAND,
    U_MAX,
    W,
    LbmSetup,
    seeded_fields,
    signed_sum,
)
from stencil_tpu.utils.config import PlacementStrategy

RADIUS = 1


def population_bounds(i: int) -> tuple:
    """``[lo, hi]`` that ``f_i`` keeps while the moments stay inside the
    model's guardband (``rho`` in ``RHO_BAND``, ``|u| < U_MAX``): the
    equilibrium's extremes there (``|c_i.u| <= sqrt(2) U_MAX``), with half of
    the span again on either side for the non-equilibrium part."""
    cu = (2.0 ** 0.5) * U_MAX if i else 0.0
    lo = W[i] * RHO_BAND[0] * (1.0 - 3.0 * cu - 1.5 * U_MAX * U_MAX)
    hi = W[i] * RHO_BAND[1] * (1.0 + 3.0 * cu + 4.5 * cu * cu)
    pad = 0.5 * (hi - lo)
    return lo - pad, hi + pad


class LatticeBoltzmann:
    def __init__(
        self,
        x: int,
        y: int,
        z: int,
        nu: float = 1.0 / 30.0,  # lattice units; omega = 1/(3 nu + 0.5)
        strategy: PlacementStrategy = PlacementStrategy.NodeAware,
        devices=None,
        kernel_impl: str = "pallas",  # "pallas" (stream engine) | "jnp" (the
        # XLA slice engine, same kernel)
        interpret: bool = False,
        storage_dtype: str = None,  # field buffers' storage axis ("native" |
        # "bf16" | None/"auto" = env > tuned > static native), as AcousticWave
        # has it; the XLA engine degrades to native
        seed_words=(0, 0, 0, 0),  # realize() fills the nineteen populations
        # from lbm_reference.seeded_fields and these words; None leaves them
        # to the caller's fill() (all zero until then: rho = 0 divides)
    ):
        if kernel_impl not in ("pallas", "jnp"):
            raise ValueError(f"unknown kernel_impl {kernel_impl!r}")
        self.setup = LbmSetup((x, y, z), nu=nu)
        self.dd = DistributedDomain(x, y, z)
        self.dd.set_radius(Radius.constant(RADIUS))
        self.dd.set_placement(strategy)
        if devices is not None:
            self.dd.set_devices(devices)
        self.handles = {q: self.dd.add_data(q, dtype=jnp.float32) for q in NAMES}
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
            where="lbm",
            engine_ok=self.kernel_impl == "pallas",
            engine_why="the XLA slice engine has no f32-accumulate kernels",
        )
        if sd != "native":
            self.dd.set_storage(sd)
        self.dd.realize()
        if self.seed_words is not None:
            self.fill(seeded_fields(self.setup), (np.asarray(self.seed_words, dtype=np.uint32),))
        # numerics guardband (docs/observability.md "Numerics observatory"):
        # the model is the low-Mach limit, rho within RHO_BAND and |u| < U_MAX;
        # the snapshots see one quantity at a time, so the band is stated per
        # population -- what f_i can be while the moments stay inside it
        from stencil_tpu.telemetry.numerics import max_principle

        for i in (0, 1, 7):  # one band a weight class
            lo, hi = population_bounds(i)
            self.dd.numerics().register_guardband(
                max_principle(lo, hi, quantities=[n for n, w in zip(NAMES, W) if w == W[i]])
            )
        self._step = self._build_step()

    def fill(self, fields: dict, args: tuple = ()) -> None:
        """Fill populations from ``{name: f(x, y, z, *args)}`` on the device;
        ``args`` (a seed's words) are traced, so one compiled fill per
        population serves every seed."""
        for name, fn in fields.items():
            self.dd.init_by_coords(self.handles[name], fn, args=args)

    def _build_step(self):
        """The ONE step-construction site, shared by ``realize()`` and
        ``rebuild_after_reshard`` (the AcousticWave twin)."""
        if self.kernel_impl == "pallas":
            return self.dd.make_step(
                self._kernel, engine="stream", x_radius=RADIUS, interpret=self.interpret
            )
        return self.dd.make_step(self._kernel)

    def rebuild_after_reshard(self) -> None:
        """Rebuild the step for the domain's CURRENT mesh (the supervisor's
        ``on_mesh_change`` hook)."""
        self._step = self._build_step()

    def _kernel(self, views, info):
        del info  # periodic everywhere: no cell knows where it is
        omega = self.setup.omega
        # pull streaming: g_i(x) = f_i(x - c_i), each population read ONCE
        g = [views[NAMES[i]].sh(-C[i][0], -C[i][1], -C[i][2]) for i in range(Q)]
        # moments, summed in index order (lbm_reference.moments)
        rho = g[0]
        for i in range(1, Q):
            rho = rho + g[i]
        inv = 1.0 / rho

        u = [signed_sum((C[i][a], g[i]) for i in range(Q) if C[i][a]) * inv for a in range(3)]
        base = 1.0 - 1.5 * ((u[0] * u[0] + u[1] * u[1]) + u[2] * u[2])
        # equilibria of the eighteen moving populations, then the rest one's
        # from conservation (lbm_reference.equilibrium says why)
        feq, moving = [None], None
        for i in range(1, Q):
            cu = signed_sum((C[i][a], u[a]) for a in range(3) if C[i][a])
            feq.append((W[i] * rho) * (base + cu * (3.0 + 4.5 * cu)))
            moving = feq[i] if moving is None else moving + feq[i]
        feq[0] = rho - moving
        out = {NAMES[i]: g[i] - omega * (g[i] - feq[i]) for i in range(Q)}
        return out

    def step(self, steps: int = 1) -> None:
        """Advance ``steps`` RAW time steps in one device program."""
        self.dd.run_step(self._step, steps, label="lbm")

    def field(self, name: str = "f0") -> np.ndarray:
        return self.dd.quantity_to_host(self.handles[name])

    def block_until_ready(self) -> None:
        self.dd.block_until_ready()
