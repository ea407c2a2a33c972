"""``AstarothSim`` (the reference's MHD communication proxy: radius-3 shell,
mean-of-6 right-hand side per field) on the stream engine's wavefront
route."""

from __future__ import annotations

from benchmark.factories.common import DomainReader, interior_sharding
from benchmark.harness import reference as ref


class AstarothCell:
    def __init__(self, config: dict, devices, interpret: bool, lower_precision: bool):
        from stencil_tpu.models.astaroth import AstarothSim

        self.shape = tuple(config["global_extent"])
        self.sim = AstarothSim(
            *self.shape, num_quantities=config["fields"], devices=devices,
            interpret=interpret,
            storage_dtype="bf16" if lower_precision else None,
            **config["model"],
        )
        self.sim.realize()
        self.reader = DomainReader(self.sim.dd)
        self.limit = config["limits"]["max_abs_err"]

    def init(self, words) -> None:
        for q, h in enumerate(self.sim.handles):
            self.sim.dd.init_by_coords(h, ref.seeded_field(words, q))

    def dispatch(self, n: int) -> None:
        self.sim.step(n)

    def token(self):
        return self.reader.token(self.sim.handles[0])

    def plan(self) -> dict:
        p = self.sim._step._stream_plan
        return {
            "route": p["route"], "depth": p["m"], "grouping": p.get("grouping"),
            "storage": self.sim.dd.storage_dtype(),
            "descents": len(self.sim._step._resilience.descents),
            "exchange_route": self.sim.dd.exchange_route(),
        }

    def state_checks(self) -> list:
        bad = sum(
            ref.bad_cells(self.reader.interior(h), 0.0, 1.0) for h in self.sim.handles
        )
        return [ref.check("window_state_bad_cells", bad, 0, "non-finite or outside [0,1]")]

    def verify(self, words, dispatches: int, n: int) -> list:
        """One field at a time against ``jnp.roll``: each field updates from
        itself only, so the reference never holds more than one."""
        self.init(words)
        for _ in range(dispatches):
            self.dispatch(n)
        sharding = interior_sharding(self.sim.dd)
        worst = 0.0
        for q, h in enumerate(self.sim.handles):
            want = ref.ref_mean6(self.shape, dispatches * n, sharding, words, q)
            worst = max(worst, ref.max_abs_err(self.reader.interior(h), want))
            del want
        return [ref.check("max_abs_err", worst, self.limit,
                          f"{dispatches * n} steps vs jnp.roll reference, "
                          f"{len(self.sim.handles)} fields, all cells")]


def build(config: dict, devices, interpret: bool, lower_precision: bool = False):
    return AstarothCell(config, devices, interpret, lower_precision)
