"""Workload keys: what a tuned config is FOR.

A config measured on one workload must never be applied to another: the
k-plateau, alias crossover, and layout picks all shift with the chip
generation, domain shape, dtype, field count, mesh, radius, and engine
route (PERF_NOTES.md "re-qualify when the toolchain or chip generation
changes").  ``WorkloadKey`` pins all seven axes; the jax/jaxlib toolchain
version is checked separately by the cache layer (``cache.py``), so a
toolchain upgrade invalidates every persisted config at load time without
changing the key (and hence the cache filename) itself.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
from typing import Tuple


def chip_kind() -> str:
    """The device kind tuned configs are keyed by — ``device_kind`` when a
    backend is up-able (e.g. "TPU v5 lite", "cpu"), else the platform name.
    Only called from tuning/plan paths that already initialized jax."""
    import jax

    try:
        return str(jax.devices()[0].device_kind)
    except Exception:  # noqa: BLE001 — device-less topologies, odd backends
        return str(jax.default_backend())


@dataclasses.dataclass(frozen=True)
class WorkloadKey:
    """One tunable workload: (chip kind, global domain shape, dtype,
    n_fields, mesh shape, radius, engine route)."""

    chip: str
    domain: Tuple[int, int, int]
    dtype: str
    n_fields: int
    mesh: Tuple[int, int, int]
    radius: int
    route: str  # "jacobi-wrap" | "jacobi-wavefront" | "stream" | "exchange"
    # | ... — "exchange" keys the halo-exchange route search, whose persisted
    # config carries the ``exchange_route`` field (tune/space.py
    # ``exchange_space``; consulted by DistributedDomain.realize)

    def to_dict(self) -> dict:
        return {
            "chip": self.chip,
            "domain": list(self.domain),
            "dtype": self.dtype,
            "n_fields": self.n_fields,
            "mesh": list(self.mesh),
            "radius": self.radius,
            "route": self.route,
        }

    @classmethod
    def from_dict(cls, d: dict) -> "WorkloadKey":
        return cls(
            chip=str(d["chip"]),
            domain=tuple(int(v) for v in d["domain"]),
            dtype=str(d["dtype"]),
            n_fields=int(d["n_fields"]),
            mesh=tuple(int(v) for v in d["mesh"]),
            radius=int(d["radius"]),
            route=str(d["route"]),
        )

    def digest(self) -> str:
        """Stable content hash — the cache filename stem."""
        canon = json.dumps(self.to_dict(), sort_keys=True, separators=(",", ":"))
        return hashlib.sha256(canon.encode()).hexdigest()[:16]

    def label(self) -> str:
        """Human/log/fault-plan label, e.g.
        ``jacobi-wrap:512x512x512:float32x1:mesh1x1x1``."""
        return (
            f"{self.route}:{'x'.join(map(str, self.domain))}:"
            f"{self.dtype}x{self.n_fields}:mesh{'x'.join(map(str, self.mesh))}"
        )
