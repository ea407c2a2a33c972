"""A bare ``DistributedDomain`` at radius 3 in all 26 directions whose mesh
does NOT divide the global extent: ``dd.exchange()`` back to back over
pad-and-mask shards (the reference's ``weak`` at its own 750^3 per device,
1191^3 on four).  ``factories/exchange.py`` with a geometry-aware check."""

from __future__ import annotations

from benchmark.factories.common import DomainReader
from benchmark.harness import reference as ref
from benchmark.harness import reference_ragged as ragged


def ragged_extent(extent_per_chip, mesh) -> list:
    """The global extent whose SMALLEST shard holds ``extent_per_chip``: on a
    split axis every other shard is one cell wider, ``m*e + (m-1)``; an
    unsplit axis is ``e``.  Derived, not read: a rehearsal overwrites the
    file's ``global_extent`` with an even one."""
    return [m * e + (m - 1) for e, m in zip(extent_per_chip, mesh)]


class RaggedExchangeCell:
    def __init__(self, config: dict, devices, interpret: bool, lower_precision: bool):
        import jax.numpy as jnp

        from stencil_tpu import DistributedDomain, Radius

        if not hasattr(DistributedDomain, "valid_last"):
            # fail at once and cleanly: a program without the accessor predates
            # the deployment (its exchange needs whole-array temporaries that
            # 15 GB of fields leave no room for)
            raise SystemExit("exchange_ragged: this program has no DistributedDomain.valid_last(); "
                             "it cannot run the ragged deployment")
        self.mesh_shape = tuple(config["mesh"])
        self.shape = tuple(ragged_extent(config["extent_per_chip"], self.mesh_shape))
        if not interpret:  # a real run is the file's deployment, to the cell
            assert list(self.shape) == config["global_extent"], (self.shape, config["global_extent"])
        self.radius = config["radius"]
        self.fields = config["fields"]
        dd = self.dd = DistributedDomain(*self.shape)
        dd.set_radius(Radius.constant(self.radius))
        dd.set_devices(devices)  # the partitioner picks the mesh, as for any user
        self.handles = [
            dd.add_data(f"q{i}", dtype=jnp.dtype(config["dtype"]))
            for i in range(self.fields)
        ]
        if lower_precision:
            dd.set_storage("bf16")
        dd.realize()  # compiles the exchange eagerly
        assert tuple(dd.mesh_dim()) == self.mesh_shape, (dd.mesh_dim(), self.mesh_shape)
        self.reader = DomainReader(dd)

    def init(self, words) -> None:
        import numpy as np

        self._filled_from = tuple(words)
        phase = np.int32(words[0] % (1 << 20))
        for q, h in enumerate(self.handles):
            # quantity and phase ride as arguments: one fill program per cell
            self.dd.init_by_coords(
                h, lambda x, y, z, q, phase: ref.ripple(q, phase)(x, y, z),
                args=(np.int32(q), phase))

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
            "valid_last": list(self.dd.valid_last()),
        }

    def state_checks(self) -> list:
        return []  # verify() reads the window's own final state

    def _compare(self, words) -> tuple:
        r = (self.radius,) * 3
        bad = checked = 0
        for q, h in enumerate(self.handles):
            b, c = ragged.owned_mismatches(
                self.dd.get_curr(h), self.dd.mesh, self.shape, r, r, q, words[0])
            bad, checked = bad + b, checked + c
        return bad, checked

    def verify(self, words, dispatches: int, n: int) -> list:
        """Every OWNED cell of every shard, shell included, against the
        analytic field: first on the state the window left (exchanging is
        idempotent on a filled domain), then after a fresh fill and
        ``dispatches`` dispatches.  ``checked_cells`` must be the count the
        configuration implies, so a mask that hides too much fails; the
        program must have padded the axes the arithmetic says it must."""
        r = (self.radius,) * 3
        implied = self.fields * ragged.owned_cells(self.shape, self.mesh_shape, r, r)
        checks = []

        def compare(name):
            bad, checked = self._compare(words)
            checks.append(ref.check(name, bad, 0, "every owned cell incl. shell, exact"))
            checks.append({"name": name.replace("mismatches", "checked_cells"), "value": checked,
                           "limit": implied, "ok": checked == implied,
                           "what": "cells compared == fields x prod(size + mesh x 2r)"})

        if tuple(words) == self._filled_from:  # the state the window left
            compare("window_state_mismatches")
        self.init(words)
        for _ in range(dispatches):
            self.dispatch(n)
        compare("refilled_mismatches")
        want = [None if v == w else v for v, w in zip(
            ragged.valid_last(self.shape, self.mesh_shape),
            ragged.shard_width(self.shape, self.mesh_shape))]
        got = list(self.dd.valid_last())
        checks.append({"name": "valid_last", "value": got, "limit": want, "ok": got == want,
                       "what": "the program padded the axes the geometry says it must"})
        return checks


def build(config: dict, devices, interpret: bool, lower_precision: bool = False):
    return RaggedExchangeCell(config, devices, interpret, lower_precision)
