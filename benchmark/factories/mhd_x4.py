"""``AstarothMHD`` decomposed over a mesh (configuration ``astaroth-mhd-256x4``:
Astaroth's MHD step weak-scaled as its scaling study runs it, 512 x 512 x 256
on mesh [2,2,1], 256^3 a chip): ``factories/mhd.py`` with a box that has a
side of its own an axis (the CELL is the one-chip cell's), the mesh and the
wires in the plan, so that the configuration's ``expect`` holds a run to them,
and a reference every chip computes its own block of."""

from __future__ import annotations

from benchmark.factories.common import DomainReader, interior_sharding
from benchmark.factories.mhd import MhdCell
from benchmark.harness import reference as ref
from benchmark.harness import reference_mhd_x4 as mhd


class MhdMeshCell(MhdCell):
    def __init__(self, config: dict, devices, interpret: bool, lower_precision: bool):
        from stencil_tpu.models.astaroth_mhd import AstarothMHD
        from stencil_tpu.models.astaroth_mhd_reference import MhdSetup, dt_of

        self.setup = s = mhd.setup_from(config, config["global_extent"])
        try:
            theirs = MhdSetup(
                s.shape, nu=s.nu, eta=s.eta, chi=s.chi, zeta=s.zeta, gamma=s.gamma, cp=s.cp,
                cs0=s.cs0, mu0=s.mu0, lnrho0=s.lnrho0, lnT0=s.lnT0, box=s.box, dt=s.dt,
                amplitude=s.amplitude, modes=s.modes, max_waves=s.max_waves,
            )
            spacing = theirs.spacing
        except TypeError as e:
            # a program whose box is one side for every axis (before PR 47):
            # said at once, before anything is built on the chips
            raise SystemExit(f"this program's MhdSetup takes no per-axis box {s.box}: {e}")
        if max(abs(a - b) for a, b in zip(spacing, s.spacing)) > 1e-12 * min(s.spacing):
            raise SystemExit(f"the program's cell is {spacing}, the configuration's {s.spacing}")
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

    def plan(self) -> dict:
        said = self.sim._step._span_args()  # the program's own words for the wires
        return {**super().plan(), "mesh": [int(m) for m in self.sim.dd.mesh_dim()],
                "wired": said.get("wired"), "wire_bytes": said.get("wire_bytes")}

    def verify(self, words, dispatches: int, n: int) -> list:
        """As the one-chip cell, on the GLOBAL arrays: every cell of all
        sixteen quantities against the plain reference, the cells beside
        every chip seam and the four x-y shard edges among them -- the box is
        periodic and nowhere zero, so a halo no wire filled, or an edge the
        sweep order did not, shows."""
        self.init(words)
        for _ in range(dispatches):
            self.dispatch(n)
        steps = dispatches * n
        # cut as the domain is, every chip's block computed on that chip from
        # the seed (harness/reference_mhd_x4.py says why no 1-D cut serves)
        want = mhd.reference(self.setup, steps, interior_sharding(self.sim.dd), words)
        worst = 0.0
        for q, w in zip(mhd.QUANTITIES, want):
            worst = max(worst, ref.max_abs_err(self.reader.interior(self.sim.handles[q]), w))
        return [ref.check("max_abs_err", worst, self.limits["max_abs_err"],
                          f"{steps} time steps vs the plain periodic reference, sixteen quantities, "
                          "all cells of the global arrays")]


def build(config: dict, devices, interpret: bool, lower_precision: bool = False):
    return MhdMeshCell(config, devices, interpret, lower_precision)
