"""``AstarothMHD`` (Astaroth's compressible MHD step) at the size a 16 GB device
holds: sixteen 512^3 f32 quantities are 11.0 GB in ONE slot, so nothing here
materialises an interior -- the state is read, and compared with the plain
reference, in PIECES of one chip's raw arrays (``harness/reference_mhd_slab.py``;
``factories/lbm_slab.py``'s pattern)."""

from __future__ import annotations

import numpy as np

from benchmark.factories.common import DomainReader
from benchmark.factories.mhd import MhdCell
from benchmark.harness import reference as ref
from benchmark.harness import reference_mhd as mhd
from benchmark.harness import reference_mhd_slab as slab


#: the most x planes the seeded fields are evaluated over at a time (``in_slabs``)
FILL_PLANES = 74


def in_slabs(field, most: int = FILL_PLANES):
    """``field(x, y, z, words)`` evaluated a SLAB of x planes at a time -- the
    largest divisor of the block's x extent not above ``most`` (37 of 518) --
    inside one ``lax.map``: the same function of the same coordinates, value for
    value, but the compiler meets it at a slab's size.  The chip's compiler takes
    31-35 s for ONE fill of three summed cosines over a whole 518^3 block and 2-3 s
    for this form (cross-compiled for a described v5e, PR 57; 2.2 s at 262^3
    whole): sixteen fills were 355 s of a cold set-up."""

    def fn(x, y, z, words):
        import jax.numpy as jnp
        from jax import lax

        planes = max(d for d in range(1, most + 1) if x.shape[0] % d == 0)
        shape = (planes, y.shape[1], z.shape[2])
        vals = lax.map(
            lambda xs: jnp.broadcast_to(field(xs, y, z, words), shape),
            x.reshape(x.shape[0] // planes, planes, 1, 1),
        )
        return vals.reshape((x.shape[0],) + shape[1:])

    return fn


class MhdSlabCell(MhdCell):
    """``MhdCell``'s fills, dispatch, token and plan line; its own set-up (it asks
    first whether the planner holds the box) and its own piece-wise checks."""

    def __init__(self, config: dict, devices, interpret: bool, lower_precision: bool):
        from stencil_tpu.models.astaroth_mhd import AstarothMHD
        from stencil_tpu.models.astaroth_mhd_reference import MhdSetup, dt_of

        if len(devices) != 1:
            raise SystemExit("the MHD slab cell compares pieces of ONE chip's arrays")
        self.setup = s = mhd.setup_from(config, config["global_extent"])
        # the program's set-up from the configuration's numbers, one by one
        theirs = MhdSetup(
            s.shape, nu=s.nu, eta=s.eta, chi=s.chi, zeta=s.zeta, gamma=s.gamma, cp=s.cp,
            cs0=s.cs0, mu0=s.mu0, lnrho0=s.lnrho0, lnT0=s.lnT0, box=s.box, dt=s.dt,
            amplitude=s.amplitude, modes=s.modes, max_waves=s.max_waves,
        )
        if abs(dt_of(theirs) - s.dt) > 1e-12 * s.dt:
            raise SystemExit(f"the program steps by {dt_of(theirs)}, the configuration by {s.dt}")

        def model():
            return AstarothMHD(
                *s.shape, setup=theirs, devices=devices, interpret=interpret,
                storage_dtype="bf16" if lower_precision else None,
                seed_words=None,  # filled from the benchmark's own seeded fields
                **config["model"],
            )

        # can the program's planner hold this box at all?  Asked of a model that
        # allocates nothing, so that a program that cannot fails at once and
        # cleanly (it would otherwise allocate 11 GB and compile an exchange
        # first): the planner's own refusal is the message
        probe = model()
        if lower_precision:
            probe.dd.set_storage("bf16")
        probe.dd.realize(allocate=False)
        try:
            probe._build_step()
        except ValueError as e:
            raise SystemExit(f"mhd_slab: this program's planner refuses the box {s.shape}: {e}")
        self.sim = model()
        self.sim.realize()
        self.fields = {q: in_slabs(f) for q, f in mhd.seeded_fields(s).items()}
        self.reader = DomainReader(self.sim.dd)
        self.limits = config["limits"]
        self.piece_cells = int(config["reference"]["piece_cells"])
        self.lo = self.sim.dd.local_spec().radius.lo().x  # the shell, equal on every side

    def plan(self) -> dict:
        said = self.sim._step._span_args()  # what the program's domain.step span says
        stages = self.sim._step._stream_plan["stages"]
        return {
            **super().plan(),
            **{k: said.get(k) for k in ("plane_window", "plane_strip", "plane_lanes",
                                        "passes_by_stage")},
            # the rows of each pass's y tiles, stage by stage (0 = whole planes)
            "tile_rows": "/".join(
                "+".join(str(p["tile_rows"]) for p in st["passes"]) for st in stages),
        }

    def _raws(self):
        return [self.sim.dd.get_curr(self.sim.handles[q]) for q in mhd.QUANTITIES]

    def state_checks(self) -> list:
        """On the window's own final state, quantity by quantity: all sixteen
        finite and within the envelope of their rest value in every cell."""
        return [
            ref.check("window_state_bad_cells",
                      slab.state_bad_cells(self.setup, self._raws(), self.lo), 0,
                      f"non-finite, or further than {self.setup.envelope} from the field's rest value"),
        ]

    def verify(self, words, dispatches: int, n: int) -> list:
        """Re-fill from the seed, drive ``dispatches`` window dispatches through
        the same compiled objects, compare every cell of the eight fields and of
        their eight second buffers with the benchmark's plain reference, piece by
        piece."""
        self.init(words)
        for _ in range(dispatches):
            self.dispatch(n)
        steps = dispatches * n
        shape = self.setup.shape
        piece, padded = slab.piece_plan(shape, slab.REACH * 3 * steps, self.piece_cells)
        raws = self._raws()
        worst, seen = 0.0, 0
        for at in slab.piece_starts(shape, piece):
            want = slab.reference_piece(self.setup, steps, words, at, piece, padded)
            for raw, w in zip(raws, want):
                worst = max(worst, slab.piece_error(raw, self.lo, at, w))
            seen += int(np.prod(piece))
            del want
        return [
            ref.check("max_abs_err", worst, self.limits["max_abs_err"],
                      f"{steps} time steps vs the plain periodic reference, sixteen quantities, "
                      f"all cells, in pieces of {'x'.join(map(str, piece))}"),
            ref.check("uncompared_cells", (int(np.prod(shape)) - seen) * len(mhd.QUANTITIES), 0,
                      "the pieces cover every cell of every quantity"),
        ]


def build(config: dict, devices, interpret: bool, lower_precision: bool = False):
    return MhdSlabCell(config, devices, interpret, lower_precision)
