"""``AcousticWave`` decomposed over a mesh (configuration
``acoustic-so8-1200x4``: Devito's acoustic so-8 shot on four chips, mesh
[2,2,1], 600^3 a chip): ``factories/acoustic.py`` with the mesh and the wires
in the plan, so that the configuration's ``expect`` holds a run to them -- a
run on another mesh, or one whose x and y halos did not cross a wire, is not
this cell."""

from __future__ import annotations

from benchmark.factories.acoustic import AcousticCell
from benchmark.harness import reference as ref
from benchmark.harness import reference_acoustic as wave


class AcousticMeshCell(AcousticCell):
    def plan(self) -> dict:
        p = self.sim._step._stream_plan
        mesh = [int(m) for m in self.sim.dd.mesh_dim()]
        out = super().plan()
        out["mesh"] = mesh
        # the program's own word (``domain.step``'s ``wired``).  A program
        # that predates it says nothing: every axis its mesh splits has a
        # neighbour, and no correct exchange can fill that halo without a wire
        out["wired"] = p["wired"] if "wired" in p else "".join(
            a for a, m in zip("xyz", mesh) if m > 1)
        out["wire_bytes"] = p.get("wire_bytes")
        out["wrapped"] = p.get("pass_wrap_axes", "")
        return out

    def reference_sharding(self):
        """The whole reference, its x axis cut in as many slabs as there are
        chips.  On the domain's own mesh XLA's partitioner turns every one
        of the 24 shifted slices of the padded array into a halo exchange
        with a result of its own: 17.7 GB of temporaries a chip at 1200 x
        1200 x 600 on [2,2,1] (compiled for a described v5e:2x2), beside 3.8
        GB of fields.  Cut along x alone only the eight x shifts cross a
        chip and the rest fuses as on one chip: 6.7 GB."""
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        mesh = self.sim.dd.mesh
        return NamedSharding(mesh, P(mesh.axis_names))

    def verify(self, words, dispatches: int, n: int) -> list:
        """As the one-chip cell: re-fill from the seed, drive ``dispatches``
        window dispatches through the same compiled objects, compare every
        cell of both time levels of the GLOBAL array with the benchmark's
        plain reference -- the cells beside every chip seam among them: the
        seams run through the middle of the wave packet, so a halo that no
        wire filled shows here (the one-chip cell's seam lies in the zero
        frame and cannot)."""
        import jax

        from benchmark.factories.common import interior_sharding

        self.init(words)
        for _ in range(dispatches):
            self.dispatch(n)
        want = wave.reference(self.setup, dispatches * n, self.reference_sharding(), words)
        like = interior_sharding(self.sim.dd)
        worst = 0.0
        for q, w in zip(("u", "u_prev"), want):
            w = jax.device_put(w, like)  # as the domain is sharded: compared chip by chip
            worst = max(worst, ref.max_abs_err(self.reader.interior(self.sim.handles[q]), w))
        return [ref.check("max_abs_err", worst, self.limit,
                          f"{dispatches * n} steps vs the plain zero-halo reference, u and u_prev, "
                          "all cells of the global array")]


def build(config: dict, devices, interpret: bool, lower_precision: bool = False):
    return AcousticMeshCell(config, devices, interpret, lower_precision)
