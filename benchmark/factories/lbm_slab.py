"""``LatticeBoltzmann`` (FluidX3D's benchmark: D3Q19, BGK, f32, a fully
periodic box) at the size a 16 GB device holds: nineteen 512^3 populations are
13.0 GB in ONE slot, so nothing here materialises an interior -- the state is
read, and compared with the plain reference, in x-slabs of one chip's raw
arrays (``harness/reference_lbm_slab.py``; ``factories/elastic.py``'s pattern)."""

from __future__ import annotations

import numpy as np

from benchmark.factories.common import DomainReader
from benchmark.factories.lbm import LbmCell
from benchmark.harness import reference as ref
from benchmark.harness import reference_lbm as lbm
from benchmark.harness import reference_lbm_slab as slab


class LbmSlabCell(LbmCell):
    """``LbmCell``'s fills, dispatch, token and plan line; its own set-up (it asks
    first whether the planner holds the box) and its own slab-wise checks."""

    def __init__(self, config: dict, devices, interpret: bool, lower_precision: bool):
        from stencil_tpu.models.lbm import LatticeBoltzmann

        if len(devices) != 1:
            raise SystemExit("the lattice-Boltzmann slab cell compares x-slabs of ONE chip's arrays")
        self.setup = lbm.setup_from(config, config["global_extent"])

        def model():
            return LatticeBoltzmann(
                *self.setup.shape, nu=self.setup.nu, devices=devices, interpret=interpret,
                storage_dtype="bf16" if lower_precision else None,
                seed_words=None,  # filled from the benchmark's own seeded fields
                **config["model"],
            )

        # can the program's planner hold this box at all?  Asked of a model that
        # allocates nothing, so that a program that cannot fails at once and
        # cleanly (it would otherwise allocate 13 GB and compile an exchange
        # first): the planner's own refusal is the message
        probe = model()
        if lower_precision:
            probe.dd.set_storage("bf16")
        probe.dd.realize(allocate=False)
        try:
            probe._build_step()
        except ValueError as e:
            raise SystemExit(f"lbm_slab: this program's planner refuses the box "
                             f"{self.setup.shape}: {e}")
        self.sim = model()
        if abs(self.sim.setup.omega - self.setup.omega) > 1e-12:
            raise SystemExit(f"the program relaxes with omega {self.sim.setup.omega}, "
                             f"the configuration with {self.setup.omega}")
        self.sim.realize()
        self.fields = lbm.seeded_fields(self.setup)
        self.reader = DomainReader(self.sim.dd)
        self.limits = config["limits"]
        self.slab_planes = int(config["reference"]["slab_planes"])
        self.lo = self.sim.dd.local_spec().radius.lo().x  # the shell, equal on every side

    def plan(self) -> dict:
        said = self.sim._step._span_args()  # what the program's domain.step span says
        return {**super().plan(), **{k: said.get(k) for k in (
            "exchanged", "wrapped", "plane_window", "plane_strip", "tile_rows", "y_tiles",
            "steps_per_trip")}}

    def _raws(self):
        return [self.sim.dd.get_curr(self.sim.handles[q]) for q in lbm.NAMES]

    def state_checks(self) -> list:
        """On the window's own final state, slab by slab: every population
        finite, ``rho`` and ``|u|`` inside the guardband in every cell, and the
        total mass where the seeded state put it."""
        bad, mass, planes = slab.state_counts(self.setup, self._raws(), self.lo, self.slab_planes)
        seeded = lbm.seeded_mass(self.setup)
        band, u_max = self.setup.rho_band, self.setup.u_max
        return [
            ref.check("window_state_bad_cells", bad, 0,
                      f"non-finite, rho outside [{band[0]}, {band[1]}] or |u| >= {u_max}"),
            ref.check("mass_drift", abs(mass - seeded) / seeded, self.limits["mass_drift"],
                      "|sum_x rho - seeded total| / seeded total: a periodic box conserves mass"),
            ref.check("unseen_planes", self.setup.shape[0] - planes, 0,
                      "the slabs cover every plane of the state"),
        ]

    def verify(self, words, dispatches: int, n: int) -> list:
        """Re-fill from the seed, drive ``dispatches`` window dispatches through
        the same compiled objects, compare every cell of all nineteen
        populations with the benchmark's plain reference, slab by slab."""
        self.init(words)
        for _ in range(dispatches):
            self.dispatch(n)
        steps = dispatches * n
        X = self.setup.shape[0]
        width = min(self.slab_planes, X)
        raws = self._raws()
        worst, seen = 0.0, np.zeros(X, bool)
        for first in slab.slab_starts(X, width):
            want = slab.reference_slab(self.setup, steps, words, first, width)
            for raw, w in zip(raws, want):
                worst = max(worst, slab.slab_error(raw, self.lo, self.setup.shape, first, w))
            seen[first : first + width] = True
            del want
        per_plane = lbm.Q * self.setup.shape[1] * self.setup.shape[2]
        return [
            ref.check("max_abs_err", worst, self.limits["max_abs_err"],
                      f"{steps} steps vs the plain periodic reference, nineteen populations, all cells, in x-slabs"),
            ref.check("uncompared_cells", int((~seen).sum()) * per_plane, 0,
                      "the slabs cover every cell of every population"),
        ]


def build(config: dict, devices, interpret: bool, lower_precision: bool = False):
    return LbmSlabCell(config, devices, interpret, lower_precision)
