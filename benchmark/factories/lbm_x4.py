"""``LatticeBoltzmann`` decomposed over a mesh whose every chip is full
(configuration ``lbm-d3q19-512x4``: FluidX3D's multi-GPU benchmark line, 1024 x
1024 x 512 on mesh [2,2,1], nineteen 512^3 populations = 13.0 GB in ONE slot a
chip): ``factories/lbm.py``'s fills, dispatch, token and plan line, the mesh and
the wires in the plan so that the configuration's ``expect`` holds a run to
them, and checks in which every chip reads its own raw shard piece by piece
against pieces it computes from the seed (``harness/reference_lbm_x4.py``):
nothing here materialises an interior, and nothing crosses a chip."""

from __future__ import annotations

from benchmark.factories.common import DomainReader
from benchmark.factories.lbm import LbmCell
from benchmark.harness import reference as ref
from benchmark.harness import reference_lbm as lbm
from benchmark.harness import reference_lbm_x4 as x4


class LbmMeshCell(LbmCell):
    """``LbmCell``'s fills, dispatch and token; its own set-up (it asks first
    whether the planner holds the box on this mesh) and its own piece-wise
    checks."""

    def __init__(self, config: dict, devices, interpret: bool, lower_precision: bool):
        from stencil_tpu.models.lbm import LatticeBoltzmann

        self.setup = lbm.setup_from(config, config["global_extent"])

        def model():
            return LatticeBoltzmann(
                *self.setup.shape, nu=self.setup.nu, devices=devices, interpret=interpret,
                storage_dtype="bf16" if lower_precision else None,
                seed_words=None,  # filled from the benchmark's own seeded fields
                **config["model"],
            )

        # can the program's planner hold 512^3 of nineteen beside a split y at
        # all?  Asked of a model that allocates nothing, so that a program that
        # cannot fails at once and cleanly (it would otherwise allocate 13 GB a
        # chip and compile an exchange first): the planner's own refusal is the
        # message
        probe = model()
        if lower_precision:
            probe.dd.set_storage("bf16")
        probe.dd.realize(allocate=False)
        mesh = [int(m) for m in probe.dd.mesh_dim()]
        if mesh != list(config["mesh"]):
            raise SystemExit(f"lbm_x4: the program cuts {self.setup.shape} over {len(devices)} "
                             f"device(s) as {mesh}, the configuration as {config['mesh']}")
        try:
            probe._build_step()
        except ValueError as e:
            raise SystemExit(f"lbm_x4: this program's planner refuses the box "
                             f"{self.setup.shape} on mesh {mesh}: {e}")
        self.sim = model()
        if abs(self.sim.setup.omega - self.setup.omega) > 1e-12:
            raise SystemExit(f"the program relaxes with omega {self.sim.setup.omega}, "
                             f"the configuration with {self.setup.omega}")
        self.sim.realize()
        self.fields = lbm.seeded_fields(self.setup)
        self.reader = DomainReader(self.sim.dd)
        self.limits = config["limits"]
        spec = self.sim.dd.local_spec()
        self.lo = spec.radius.lo().x  # the shell, equal on every side
        self.width = x4.piece_width(spec.sz.x, config["reference"]["piece_planes"])

    def plan(self) -> dict:
        said = self.sim._step._span_args()  # what the program's domain.step span says
        return {**super().plan(), "mesh": [int(m) for m in self.sim.dd.mesh_dim()],
                **{k: said.get(k) for k in (
                    "wired", "wire_bytes", "exchanged", "wrapped", "plane_window", "plane_strip",
                    "tile_rows", "y_tiles", "steps_per_trip")}}

    def _raws(self):
        return [self.sim.dd.get_curr(self.sim.handles[q]) for q in lbm.NAMES]

    def _cells(self) -> int:
        n = self.setup.shape
        return lbm.Q * n[0] * n[1] * n[2]

    def state_checks(self) -> list:
        """On the window's own final state, every chip its own shard piece by
        piece: every population finite, ``rho`` and ``|u|`` inside the guardband
        in every cell, and the GLOBAL mass where the seeded state put it."""
        bad, mass, cells = x4.state_counts(
            self.setup, self.sim.dd.mesh, self._raws(), self.lo, self.width)
        seeded = lbm.seeded_mass(self.setup)
        band, u_max = self.setup.rho_band, self.setup.u_max
        return [
            ref.check("window_state_bad_cells", bad, 0,
                      f"non-finite, rho outside [{band[0]}, {band[1]}] or |u| >= {u_max}"),
            ref.check("mass_drift", abs(mass - seeded) / seeded, self.limits["mass_drift"],
                      "|sum_x rho - seeded total| / seeded total over the GLOBAL box: a periodic "
                      "box conserves mass"),
            ref.check("unseen_cells", self._cells() // lbm.Q - cells, 0,
                      "the pieces cover every cell of every chip's block"),
        ]

    def verify(self, words, dispatches: int, n: int) -> list:
        """Re-fill from the seed, drive ``dispatches`` window dispatches through
        the same compiled objects, compare every cell of all nineteen
        populations ON EVERY CHIP with that chip's pieces of the plain
        reference, read from the chip's own raw shard: the cells beside every
        chip seam and the four x-y shard edges are among them -- the box is
        periodic and nowhere zero, so a halo no wire filled, or an edge the
        sweep order did not, shows."""
        self.init(words)
        for _ in range(dispatches):
            self.dispatch(n)
        steps = dispatches * n
        worst, seen = x4.piece_errors(
            self.setup, steps, self.sim.dd.mesh, words, self._raws(), self.lo, self.width)
        return [
            ref.check("max_abs_err", worst, self.limits["max_abs_err"],
                      f"{steps} steps vs the plain periodic reference, nineteen populations, all "
                      f"cells of every chip's block, in pieces of {self.width} planes"),
            ref.check("uncompared_cells", self._cells() - seen, 0,
                      "the pieces cover every cell of every population on every chip"),
        ]


def build(config: dict, devices, interpret: bool, lower_precision: bool = False):
    return LbmMeshCell(config, devices, interpret, lower_precision)
