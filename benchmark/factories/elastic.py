"""``ElasticWave`` (Devito's elastic so-8 propagator: velocity-stress on a
staggered grid, thirteen quantities, two stages a time step with an exchange
between them) on the stream engine's plane route, every pass in place."""

from __future__ import annotations

import numpy as np

from benchmark.factories.common import DomainReader
from benchmark.harness import reference as ref
from benchmark.harness import reference_elastic as wave


class ElasticCell:
    def __init__(self, config: dict, devices, interpret: bool, lower_precision: bool):
        from stencil_tpu.models.elastic import ElasticWave

        if len(devices) != 1:
            raise SystemExit("the elastic cell compares x-slabs of ONE chip's arrays")
        # a rehearsal hands a small extent: the sponge shrinks to fit it
        self.setup = wave.setup_from(config, config["global_extent"])
        self.sim = ElasticWave(
            *self.setup.shape, nbl=self.setup.nbl, spacing=self.setup.spacing,
            devices=devices, interpret=interpret,
            storage_dtype="bf16" if lower_precision else None,
            seed_words=None,  # filled from the benchmark's own seeded fields
            **config["model"],
        )
        if abs(self.sim.dt - self.setup.dt) > 1e-12 * self.setup.dt:
            raise SystemExit(f"the program steps by {self.sim.dt} ms, the configuration by {self.setup.dt}")
        self.sim.realize()
        self.fields = wave.seeded_fields(self.setup)
        self.reader = DomainReader(self.sim.dd)
        self.limit = config["limits"]["max_abs_err"]
        self.slab_planes = int(config["reference"]["slab_planes"])
        self.lo = self.sim.dd.local_spec().radius.lo().x  # the shell, equal on every side
        # a velocity stays near its seeded sup (x 1.7 at most into a softer
        # layer), a stress near impedance x velocity: four times either is
        # far above focusing and far below a blow-up
        v = 4.0 * self.setup.amplitude_bound
        self.bounds = {q: v for q in wave.VELOCITIES}
        self.bounds.update({q: v * self.setup.impedance_max for q in wave.STRESSES})

    def init(self, words) -> None:
        """All thirteen quantities from the seed; the words are an argument of
        the fill programs, so a new seed compiles nothing."""
        self.sim.fill(self.fields, (np.asarray(words, dtype=np.uint32),))

    def dispatch(self, n: int) -> None:
        self.sim.step(n)

    def token(self):
        return self.reader.token(self.sim.handles["tyz"])  # the last pass's last output

    def plan(self) -> dict:
        p = self.sim._step._stream_plan
        return {
            "route": p["route"], "depth": p["m"], "grouping": p.get("grouping"),
            "storage": self.sim.dd.storage_dtype(),
            "descents": len(self.sim._step._resilience.descents),
            "exchange_route": self.sim.dd.exchange_route(),
            "wrap_axes": self.sim.dd._wrap_axes,
            "stages": [
                {"exchanged": len(st["readers"]),
                 "passes": [{"reads": len(q["reads"]), "writes": len(q["writes"]),
                             "rings": len(q["rings"])} for q in st["passes"]]}
                for st in p.get("stages", ())
            ],
        }

    def _raw(self, q):
        return self.sim.dd.get_curr(self.sim.handles[q])

    def state_checks(self) -> list:
        """On the window's own final state: all nine wavefields finite and
        inside their bound, and the outer frame exactly 0."""
        bad = frame = 0
        for q in wave.WAVEFIELDS:
            b, f = wave.state_counts(self.setup, self._raw(q), self.lo, self.bounds[q])
            bad, frame = bad + b, frame + f
        return [
            ref.check("window_state_bad_cells", bad, 0,
                      "non-finite, |v| > 4 x seeded sup or |tau| > that x max impedance"),
            ref.check("frame_nonzero_cells", frame, 0, "outer frame of a wavefield not exactly 0"),
        ]

    def verify(self, words, dispatches: int, n: int) -> list:
        """Re-fill from the seed, drive ``dispatches`` window dispatches
        through the same compiled objects, compare every cell of all nine
        wavefields with the benchmark's plain reference, slab by slab."""
        self.init(words)
        for _ in range(dispatches):
            self.dispatch(n)
        steps = dispatches * n
        X = self.setup.shape[0]
        width = min(self.slab_planes, X)
        worst, cells, sup = 0.0, 0, 0.0
        for first in wave.slab_starts(X, width):
            want = wave.reference_slab(self.setup, steps, words, first, width)
            for q, w in zip(wave.WAVEFIELDS, want):
                err, _ = wave.slab_errors(
                    self._raw(q), self.lo, self.setup.shape, first, w, self.bounds[q]
                )
                worst = max(worst, err)
                cells += int(np.prod(w.shape))
                if q in wave.STRESSES:
                    sup = max(sup, wave.sup(w))
            del want
        whole = len(wave.WAVEFIELDS) * int(np.prod(self.setup.shape))
        return [
            ref.check("max_abs_err", worst, self.limit,
                      f"{steps} steps vs the plain zero-halo reference, nine wavefields, all cells, in x-slabs"),
            ref.check("uncompared_cells", max(whole - cells, 0), 0,
                      "the slabs cover every cell of every wavefield"),
            {"name": "reference_stress_sup", "value": sup, "limit": ">0", "ok": sup > 0.0,
             "what": "the stresses start at zero: the reference they are compared with has moved"},
        ]


def build(config: dict, devices, interpret: bool, lower_precision: bool = False):
    return ElasticCell(config, devices, interpret, lower_precision)
