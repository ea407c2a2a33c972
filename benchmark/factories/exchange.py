"""A bare ``DistributedDomain`` at radius 3 in all 26 directions: no
compute, ``dd.exchange()`` back to back (the reference's ``weak``)."""

from __future__ import annotations

from benchmark.factories.common import DomainReader
from benchmark.harness import reference as ref


class ExchangeCell:
    def __init__(self, config: dict, devices, interpret: bool, lower_precision: bool):
        import jax.numpy as jnp

        from stencil_tpu import DistributedDomain, Radius

        del interpret  # the exchange picks its blend path from the backend
        self.shape = tuple(config["global_extent"])
        self.radius = config["radius"]
        dd = self.dd = DistributedDomain(*self.shape)
        dd.set_radius(Radius.constant(self.radius))
        dd.set_devices(devices)
        self.handles = [
            dd.add_data(f"q{i}", dtype=jnp.dtype(config["dtype"]))
            for i in range(config["fields"])
        ]
        if lower_precision:
            dd.set_storage("bf16")
        dd.realize()  # compiles the exchange eagerly
        self.reader = DomainReader(dd)

    def _field(self, words, q):
        return ref.ripple(q, words[0])

    def init(self, words) -> None:
        self._filled_from = tuple(words)
        for q, h in enumerate(self.handles):
            self.dd.init_by_coords(h, self._field(words, q))

    def dispatch(self, n: int) -> None:
        """One dispatch is ``n`` calls of ``exchange()``, enqueued back to back."""
        for _ in range(n):
            self.dd.exchange()

    def token(self):
        return self.reader.token(self.handles[0])

    def plan(self) -> dict:
        return {
            "route": self.dd.exchange_route(), "depth": 1,
            "storage": self.dd.storage_dtype(), "descents": 0,
            "exchange_route": self.dd.exchange_route(),
        }

    def state_checks(self) -> list:
        return []  # verify() reads the window's own final state

    def _mismatches(self, words) -> int:
        spec = self.dd.local_spec()
        lo = spec.radius.lo()
        return sum(
            ref.ripple_mismatches(
                self.dd.get_curr(h), self.dd.mesh, tuple(spec.sz),
                (lo.x, lo.y, lo.z), self.shape, self._field(words, q),
            )
            for q, h in enumerate(self.handles)
        )

    def verify(self, words, dispatches: int, n: int) -> list:
        """Every cell of every shard, shell included, against the analytic
        field: first on the state the window left (exchanging is idempotent
        on a filled domain), then after a fresh fill and ``dispatches``
        dispatches."""
        checks = []
        if tuple(words) == self._filled_from:  # the state the window left
            checks.append(ref.check("window_state_mismatches", self._mismatches(words), 0,
                                    "every cell incl. shell, exact"))
        self.init(words)
        for _ in range(dispatches):
            self.dispatch(n)
        checks.append(ref.check("refilled_mismatches", self._mismatches(words), 0,
                                "every cell incl. shell, exact"))
        return checks


def build(config: dict, devices, interpret: bool, lower_precision: bool = False):
    return ExchangeCell(config, devices, interpret, lower_precision)
