"""What the three factories share: reading a ``DistributedDomain``'s state
on the device, the completion token of a dispatch, and the program's own
failure counters.  This is the only place the benchmark touches the
program's layout (shell-carrying shards on ``dd.mesh``)."""

from __future__ import annotations


def mesh_spec(dd):
    from jax.sharding import PartitionSpec as P

    return P(*dd.mesh.axis_names)


def interior_sharding(dd):
    from jax.sharding import NamedSharding

    return NamedSharding(dd.mesh, mesh_spec(dd))


class DomainReader:
    """Jitted per-shard readers of one domain, built once (set-up) so the
    window compiles nothing."""

    def __init__(self, dd):
        import jax

        self.dd = dd
        spec = dd.local_spec()
        n, lo = spec.sz, spec.radius.lo()
        p = mesh_spec(dd)

        def cut(b):
            return b[lo.x : lo.x + n.x, lo.y : lo.y + n.y, lo.z : lo.z + n.z]

        self._interior = jax.jit(
            jax.shard_map(cut, mesh=dd.mesh, in_specs=p, out_specs=p)
        )
        self._token = jax.jit(
            jax.shard_map(lambda b: b[:1, :1, :1], mesh=dd.mesh, in_specs=p, out_specs=p)
        )

    def interior(self, h):
        """The quantity's interior as one global device array, sharded like
        the domain (no shell)."""
        return self._interior(self.dd.get_curr(h))

    def token(self, h):
        """One element per shard of the quantity's CURRENT buffer, enqueued
        behind whatever produced it: ready on every chip exactly when that
        dispatch is.  (The buffer itself is donated to the next dispatch,
        so it cannot be waited on two deep.)"""
        return self._token(self.dd.get_curr(h))


def failure_counters() -> dict:
    """The program's resilience counters: every ``resilience.*`` counter and
    ladder descent.  All must stay where they were across a window."""
    from stencil_tpu import telemetry

    c = telemetry.snapshot()["counters"]
    return {k: int(v) for k, v in c.items() if k.startswith("resilience.")}
