"""``LatticeBoltzmann`` (FluidX3D's benchmark: D3Q19, BGK, f32, a fully
periodic box) on the stream engine: nineteen populations through one joint
kernel, each read at exactly one offset, twelve of them diagonal."""

from __future__ import annotations

import numpy as np

from benchmark.factories.common import DomainReader, interior_sharding
from benchmark.harness import reference as ref
from benchmark.harness import reference_lbm as lbm


class LbmCell:
    def __init__(self, config: dict, devices, interpret: bool, lower_precision: bool):
        from stencil_tpu.models.lbm import LatticeBoltzmann

        self.setup = lbm.setup_from(config, config["global_extent"])
        self.sim = LatticeBoltzmann(
            *self.setup.shape, nu=self.setup.nu, devices=devices, interpret=interpret,
            storage_dtype="bf16" if lower_precision else None,
            seed_words=None,  # filled from the benchmark's own seeded fields
            **config["model"],
        )
        if abs(self.sim.setup.omega - self.setup.omega) > 1e-12:
            raise SystemExit(f"the program relaxes with omega {self.sim.setup.omega}, "
                             f"the configuration with {self.setup.omega}")
        self.sim.realize()
        self.fields = lbm.seeded_fields(self.setup)
        self.reader = DomainReader(self.sim.dd)
        self.limits = config["limits"]

    def init(self, words) -> None:
        """All nineteen populations from the seed; the words are an argument
        of the fill programs, so a new seed compiles nothing."""
        self.sim.fill(self.fields, (np.asarray(words, dtype=np.uint32),))

    def dispatch(self, n: int) -> None:
        self.sim.step(n)

    def token(self):
        return self.reader.token(self.sim.handles[lbm.NAMES[-1]])  # the pass's last output

    def plan(self) -> dict:
        p = self.sim._step._stream_plan
        said = self.sim._step._span_args()  # what the program's domain.step span says
        return {
            "route": p["route"], "depth": p["m"], "grouping": p.get("grouping"),
            "storage": self.sim.dd.storage_dtype(),
            "descents": len(self.sim._step._resilience.descents),
            "exchange_route": self.sim.dd.exchange_route(),
            "wrap_axes": self.sim.dd._wrap_axes,
            **{k: said.get(k) for k in ("quantities", "offcentre", "diagonal", "read_sides",
                                        "exchanged_sides", "aliased", "macros_per_trip")},
        }

    def _interiors(self):
        return [self.reader.interior(self.sim.handles[q]) for q in lbm.NAMES]

    def state_checks(self) -> list:
        """On the window's own final state: every population finite, ``rho``
        and ``|u|`` inside the guardband in every cell, and the total mass
        where the seeded state put it."""
        bad, mass = lbm.state_counts(self.setup, self._interiors())
        seeded = lbm.seeded_mass(self.setup)
        band, u_max = self.setup.rho_band, self.setup.u_max
        return [
            ref.check("window_state_bad_cells", bad, 0,
                      f"non-finite, rho outside [{band[0]}, {band[1]}] or |u| >= {u_max}"),
            ref.check("mass_drift", abs(mass - seeded) / seeded, self.limits["mass_drift"],
                      "|sum_x rho - seeded total| / seeded total: a periodic box conserves mass"),
        ]

    def verify(self, words, dispatches: int, n: int) -> list:
        """Re-fill from the seed, drive ``dispatches`` window dispatches
        through the same compiled objects, compare every cell of all nineteen
        populations with the benchmark's plain reference."""
        self.init(words)
        for _ in range(dispatches):
            self.dispatch(n)
        steps = dispatches * n
        want = lbm.reference(self.setup, steps, interior_sharding(self.sim.dd), words)
        worst = 0.0
        for q, w in zip(lbm.NAMES, want):
            worst = max(worst, ref.max_abs_err(self.reader.interior(self.sim.handles[q]), w))
        return [ref.check("max_abs_err", worst, self.limits["max_abs_err"],
                          f"{steps} steps vs the plain periodic reference, nineteen populations, all cells")]


def build(config: dict, devices, interpret: bool, lower_precision: bool = False):
    return LbmCell(config, devices, interpret, lower_precision)
