"""``AcousticWave`` (Devito's acoustic so-8 propagator: radius-4 25-point
star, two time levels, sponge, zero frame) on the stream engine's plane
route, four quantities jointly, an exchange every step."""

from __future__ import annotations

import numpy as np

from benchmark.factories.common import DomainReader, interior_sharding
from benchmark.harness import reference as ref
from benchmark.harness import reference_acoustic as wave


class AcousticCell:
    def __init__(self, config: dict, devices, interpret: bool, lower_precision: bool):
        from stencil_tpu.models.acoustic import AcousticWave

        # a rehearsal hands a small extent: the sponge shrinks to fit it
        self.setup = wave.setup_from(config, config["global_extent"])
        self.sim = AcousticWave(
            *self.setup.shape, nbl=self.setup.nbl, spacing=self.setup.spacing,
            devices=devices, interpret=interpret,
            storage_dtype="bf16" if lower_precision else None,
            seed_words=None,  # filled from the benchmark's own seeded fields
            **config["model"],
        )
        self.sim.realize()
        self.fields = wave.seeded_fields(self.setup)
        self.reader = DomainReader(self.sim.dd)
        self.limit = config["limits"]["max_abs_err"]

    def init(self, words) -> None:
        """All four quantities from the seed; the words are an argument of
        the fill programs, so a new seed compiles nothing."""
        self.sim.fill(self.fields, (np.asarray(words, dtype=np.uint32),))

    def dispatch(self, n: int) -> None:
        self.sim.step(n)

    def token(self):
        return self.reader.token(self.sim.handles["u"])

    def plan(self) -> dict:
        p = self.sim._step._stream_plan
        return {
            "route": p["route"], "depth": p["m"], "grouping": p.get("grouping"),
            "storage": self.sim.dd.storage_dtype(),
            "descents": len(self.sim._step._resilience.descents),
            "exchange_route": self.sim.dd.exchange_route(),
            "wrap_axes": self.sim.dd._wrap_axes,
        }

    def state_checks(self) -> list:
        """On the window's own final state: both time levels finite and
        inside twice the seeded wave's sup (at Devito's CFL the update does
        not grow the wave's energy and the sponge removes it; a blow-up
        doubles every few steps), and the outer frame exactly 0."""
        bound = 2.0 * self.setup.amplitude_bound
        bad = frame = 0
        for q in ("u", "u_prev"):
            a = self.reader.interior(self.sim.handles[q])
            bad += ref.bad_cells(a, -bound, bound)
            frame += wave.frame_nonzero(self.setup, a)
            del a
        return [
            ref.check("window_state_bad_cells", bad, 0, f"non-finite or |u| > {bound}"),
            ref.check("frame_nonzero_cells", frame, 0, "outer frame of u, u_prev not exactly 0"),
        ]

    def verify(self, words, dispatches: int, n: int) -> list:
        """Re-fill from the seed, drive ``dispatches`` window dispatches
        through the same compiled objects, compare every cell of both time
        levels with the benchmark's plain reference."""
        self.init(words)
        for _ in range(dispatches):
            self.dispatch(n)
        want = wave.reference(self.setup, dispatches * n, interior_sharding(self.sim.dd), words)
        worst = 0.0
        for q, w in zip(("u", "u_prev"), want):
            worst = max(worst, ref.max_abs_err(self.reader.interior(self.sim.handles[q]), w))
        return [ref.check("max_abs_err", worst, self.limit,
                          f"{dispatches * n} steps vs the plain zero-halo reference, u and u_prev, all cells")]


def build(config: dict, devices, interpret: bool, lower_precision: bool = False):
    return AcousticCell(config, devices, interpret, lower_precision)
