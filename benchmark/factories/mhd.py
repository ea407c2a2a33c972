"""``AstarothMHD`` (Astaroth's compressible MHD step: eight fields and their
eight second buffers, sixth-order differences read at full radius 3 with
in-plane diagonals, three Runge-Kutta substeps a time step) on the stream
engine's plane route: one in-place pass a substep, eight renames each."""

from __future__ import annotations

import numpy as np

from benchmark.factories.common import DomainReader, interior_sharding
from benchmark.harness import reference as ref
from benchmark.harness import reference_mhd as mhd


class MhdCell:
    def __init__(self, config: dict, devices, interpret: bool, lower_precision: bool):
        from stencil_tpu.models.astaroth_mhd import AstarothMHD
        from stencil_tpu.models.astaroth_mhd_reference import MhdSetup, dt_of

        self.setup = s = mhd.setup_from(config, config["global_extent"])
        # the program's set-up from the configuration's numbers, one by one
        theirs = MhdSetup(
            s.shape, nu=s.nu, eta=s.eta, chi=s.chi, zeta=s.zeta, gamma=s.gamma, cp=s.cp,
            cs0=s.cs0, mu0=s.mu0, lnrho0=s.lnrho0, lnT0=s.lnT0, box=s.box, dt=s.dt,
            amplitude=s.amplitude, modes=s.modes, max_waves=s.max_waves,
        )
        if abs(dt_of(theirs) - s.dt) > 1e-12 * s.dt:
            raise SystemExit(f"the program steps by {dt_of(theirs)}, the configuration by {s.dt}")
        self.sim = AstarothMHD(
            *s.shape, setup=theirs, devices=devices, interpret=interpret,
            storage_dtype="bf16" if lower_precision else None,
            seed_words=None,  # filled from the benchmark's own seeded fields
            **config["model"],
        )
        self.sim.realize()
        self.fields = mhd.seeded_fields(s)
        self.reader = DomainReader(self.sim.dd)
        self.limits = config["limits"]

    def init(self, words) -> None:
        """All sixteen quantities from the seed; the words are an argument of
        the fill programs, so a new seed compiles nothing."""
        self.sim.fill(self.fields, (np.asarray(words, dtype=np.uint32),))

    def dispatch(self, n: int) -> None:
        self.sim.step(n)

    def token(self):
        return self.reader.token(self.sim.handles[mhd.FIELDS[-1]])  # the pass's last output

    def plan(self) -> dict:
        p = self.sim._step._stream_plan
        said = self.sim._step._span_args()  # what the program's domain.step span says
        return {
            "route": p["route"], "depth": p["m"], "grouping": p.get("grouping"),
            "storage": self.sim.dd.storage_dtype(),
            "descents": len(self.sim._step._resilience.descents),
            "exchange_route": self.sim.dd.exchange_route(),
            "wrap_axes": self.sim.dd._wrap_axes,
            "renamed": len(p.get("renamed", ())),  # distinct quantities; the span says it a stage
            "renamed_by_stage": said.get("renamed"),
            **{k: said.get(k) for k in ("quantities", "stages", "passes", "steps_per_trip",
                                        "offcentre", "diagonal", "read_sides", "exchanged_sides",
                                        "exchanged", "written", "aliased", "wrapped")},
        }

    def _interiors(self):
        return [self.reader.interior(self.sim.handles[q]) for q in mhd.QUANTITIES]

    def state_checks(self) -> list:
        """On the window's own final state: all sixteen quantities finite and
        within the envelope of their rest value in every cell."""
        return [
            ref.check("window_state_bad_cells", mhd.state_bad_cells(self.setup, self._interiors()), 0,
                      f"non-finite, or further than {self.setup.envelope} from the field's rest value"),
        ]

    def verify(self, words, dispatches: int, n: int) -> list:
        """Re-fill from the seed, drive ``dispatches`` window dispatches
        through the same compiled objects, compare every cell of the eight
        fields and of their eight second buffers with the benchmark's plain
        reference."""
        self.init(words)
        for _ in range(dispatches):
            self.dispatch(n)
        steps = dispatches * n
        want = mhd.reference(self.setup, steps, interior_sharding(self.sim.dd), words)
        worst = 0.0
        for q, w in zip(mhd.QUANTITIES, want):
            worst = max(worst, ref.max_abs_err(self.reader.interior(self.sim.handles[q]), w))
        return [ref.check("max_abs_err", worst, self.limits["max_abs_err"],
                          f"{steps} time steps vs the plain periodic reference, sixteen quantities, all cells")]


def build(config: dict, devices, interpret: bool, lower_precision: bool = False):
    return MhdCell(config, devices, interpret, lower_precision)
