"""``Jacobi3D(kernel_impl="pallas")`` on its default route: the bespoke
wrap kernel on one chip, the z-ring wavefront with the wide-shell exchange
on several."""

from __future__ import annotations

from benchmark.factories.common import DomainReader, interior_sharding
from benchmark.harness import reference as ref


class JacobiCell:
    def __init__(self, config: dict, devices, interpret: bool, lower_precision: bool):
        from stencil_tpu.models.jacobi import Jacobi3D

        self.shape = tuple(config["global_extent"])
        self.model = Jacobi3D(
            *self.shape, devices=devices, interpret=interpret,
            storage_dtype="bf16" if lower_precision else None,
            **config["model"],
        )
        self.model.realize()
        self.reader = DomainReader(self.model.dd)
        self.limit = config["limits"]["max_abs_err"]

    def init(self, words) -> None:
        self.model.dd.init_by_coords(self.model.h, ref.seeded_field(words))

    def dispatch(self, n: int) -> None:
        """Enqueue one dispatch: ``n`` raw iterations in one device program."""
        self.model.step(n)

    def token(self):
        return self.reader.token(self.model.h)

    def plan(self) -> dict:
        m = self.model
        if m._pallas_path == "wrap":
            depth = m._wrap_k
        elif m._pallas_path == "wavefront":
            depth = getattr(m, "_wavefront_depth", m._wavefront_m)
        else:
            depth = 1
        return {
            "route": m._pallas_path, "depth": depth,
            "storage": m.dd.storage_dtype(),
            "descents": len(m._ladder.descents),
            "exchange_route": m.dd.exchange_route(),
        }

    def state_checks(self) -> list:
        """On the window's own final state: every cell finite and inside the
        clamps (the update is a mean, so nothing leaves [0, 1])."""
        bad = ref.bad_cells(self.reader.interior(self.model.h), 0.0, 1.0)
        return [ref.check("window_state_bad_cells", bad, 0, "non-finite or outside [0,1]")]

    def verify(self, words, dispatches: int, n: int) -> list:
        """Re-fill from the seed, drive ``dispatches`` window dispatches
        through the same compiled objects, compare with the plain reference."""
        self.init(words)
        for _ in range(dispatches):
            self.dispatch(n)
        got = self.reader.interior(self.model.h)
        want = ref.ref_jacobi(
            self.shape, dispatches * n, interior_sharding(self.model.dd), words,
        )
        err = ref.max_abs_err(got, want)
        return [ref.check("max_abs_err", err, self.limit,
                          f"{dispatches * n} steps vs jnp.roll reference, all cells")]


def build(config: dict, devices, interpret: bool, lower_precision: bool = False):
    return JacobiCell(config, devices, interpret, lower_precision)
