"""Halo geometry math for one subdomain.

Parity target: the geometry half of ``LocalDomain`` (reference
include/stencil/local_domain.cuh:33-349 + src/local_domain.cu:14-95) plus the
interior/exterior region split (src/stencil.cu:567-666).  The device-memory
half of LocalDomain (cudaMalloc double buffers, device pointer tables) does not
exist on TPU: per-chip storage is a shard of a ``jax.Array`` and lives in
``stencil_tpu.domain``.

``LocalSpec`` is pure host-side metadata: compute size ``sz``, global
``origin``, and ``Radius``.  All the invariants the reference's tests pin are
reproduced here:

* ``halo_pos(dir, halo)`` — offset (from allocation start) of the halo
  (``halo=True``) or interior-edge (``halo=False``) region on side ``dir``
  (src/local_domain.cu:56-95).
* ``halo_extent(dir)`` — region size: ``sz`` on 0-axes, ``radius.dir(dir)`` on
  +-1 axes (local_domain.cuh:285-298).
* the ``-dir`` convention: a message sent in direction ``d`` packs the
  interior region at ``halo_pos(d, False)`` with extent ``halo_extent(-d)``
  and unpacks into ``halo_pos(-d, True)`` with extent ``halo_extent(-d)``
  (packer.cuh:91-93, 271-273) — the *receiver's* halo width rules the size.
"""

from __future__ import annotations

import dataclasses
from typing import List

import numpy as np

from stencil_tpu.core.dim3 import Dim3, Rect3
from stencil_tpu.core.direction_map import DIRECTIONS_26
from stencil_tpu.core.radius import Radius


def halo_extent(direction: Dim3, sz: Dim3, radius: Radius) -> Dim3:
    """Point-size of the halo region on side ``dir`` (local_domain.cuh:285-298).

    Each nonzero axis contributes that axis's *face* radius
    (``radius.x(dir.x)`` etc., NOT the full-direction radius) — so an edge
    region is face-radius-wide on both its axes.  ``dir == (0,0,0)`` returns
    ``sz``.
    """
    d = Dim3.of(direction)
    return Dim3(
        sz.x if d.x == 0 else radius.x(d.x),
        sz.y if d.y == 0 else radius.y(d.y),
        sz.z if d.z == 0 else radius.z(d.z),
    )


@dataclasses.dataclass(frozen=True)
class LocalSpec:
    """Geometry of one per-chip subdomain (shell-carrying layout)."""

    sz: Dim3
    origin: Dim3
    radius: Radius

    @staticmethod
    def make(sz, origin, radius: Radius) -> "LocalSpec":
        return LocalSpec(Dim3.of(sz), Dim3.of(origin), radius)

    # --- allocation shape ----------------------------------------------------
    def raw_size(self) -> Dim3:
        """Allocation extent: sz + negative + positive face radii per axis
        (local_domain.cuh:309-313)."""
        r = self.radius
        return Dim3(
            self.sz.x + r.x(-1) + r.x(1),
            self.sz.y + r.y(-1) + r.y(1),
            self.sz.z + r.z(-1) + r.z(1),
        )

    # --- halo position/extent (src/local_domain.cu:56-95) --------------------
    def halo_pos(self, direction, halo: bool) -> Dim3:
        d = Dim3.of(direction)
        assert d.all_gt(-2) and d.all_lt(2)
        r = self.radius

        def one(axis: int, s: int) -> int:
            if s == 1:
                return self.sz[axis] + (r.axis(axis, -1) if halo else 0)
            if s == -1:
                return 0 if halo else r.axis(axis, -1)
            return r.axis(axis, -1)

        return Dim3(one(0, d.x), one(1, d.y), one(2, d.z))

    def halo_extent(self, direction) -> Dim3:
        return halo_extent(direction, self.sz, self.radius)

    def halo_coords(self, direction, halo: bool) -> Rect3:
        """Global coordinates of the region (src/local_domain.cu:14-32)."""
        pos = self.halo_pos(direction, halo)
        ext = self.halo_extent(direction)
        pos = pos - self.radius.lo() + self.origin
        return Rect3(pos, pos + ext)

    def halo_bytes(self, direction, itemsize: int) -> int:
        """Bytes of one quantity's halo on side ``dir`` (local_domain.cuh:301-303)."""
        return int(itemsize) * self.halo_extent(direction).flatten()

    # --- compute region (global coords) --------------------------------------
    def compute_region(self) -> Rect3:
        return Rect3(self.origin, self.origin + self.sz)

    def full_region(self) -> Rect3:
        """Compute region plus the halo shell, in global coords
        (local_domain.cuh:213-227 get_full_region analog)."""
        return Rect3(self.origin - self.radius.lo(), self.origin + self.sz + self.radius.hi())

    # --- interior/exterior split (src/stencil.cu:567-666) --------------------
    def interior(self) -> Rect3:
        """Compute region shrunk per-direction so no point reads a halo cell."""
        return shrink_by_radius(self.compute_region(), self.radius)

    def exterior(self) -> List[Rect3]:
        """Non-overlapping face slabs covering compute-region minus interior,
        via the reference's slide-in construction (src/stencil.cu:616-666):
        order +x, +y, +z, -x, -y, -z."""
        return exterior_of(self.compute_region(), self.interior())

    # --- local (allocation-relative) views -----------------------------------
    def to_local(self, r: Rect3) -> Rect3:
        """Global-coords region -> allocation-relative indices."""
        shift = self.radius.lo() - self.origin
        return Rect3(r.lo + shift, r.hi + shift)

    def local_slices(self, r: Rect3):
        """numpy-style index tuple (x, y, z order) for a global-coords region."""
        lr = self.to_local(r)
        return (
            slice(lr.lo.x, lr.hi.x),
            slice(lr.lo.y, lr.hi.y),
            slice(lr.lo.z, lr.hi.z),
        )

    def interior_slices(self):
        return self.local_slices(self.compute_region())


def shrink_by_radius(com: Rect3, radius: Radius) -> Rect3:
    """Shrink a region per-direction so no point inside reads outside it
    (the interior construction, src/stencil.cu:567-610; also the per-sub-step
    valid-region shrink under a halo multiplier)."""
    lo = [com.lo.x, com.lo.y, com.lo.z]
    hi = [com.hi.x, com.hi.y, com.hi.z]
    for d in DIRECTIONS_26:
        rad = radius.dir(d)
        for axis in range(3):
            if d[axis] < 0:
                lo[axis] = max(com.lo[axis] + rad, lo[axis])
            elif d[axis] > 0:
                hi[axis] = min(com.hi[axis] - rad, hi[axis])
    return Rect3(Dim3(*lo), Dim3(*hi))


def exterior_of(com: Rect3, int_reg: Rect3) -> List[Rect3]:
    """Non-overlapping face slabs covering ``com`` minus ``int_reg`` via the
    slide-in construction (src/stencil.cu:616-666): +x, +y, +z, -x, -y, -z."""
    clo = [com.lo.x, com.lo.y, com.lo.z]
    chi = [com.hi.x, com.hi.y, com.hi.z]
    ilo = [int_reg.lo.x, int_reg.lo.y, int_reg.lo.z]
    ihi = [int_reg.hi.x, int_reg.hi.y, int_reg.hi.z]
    out: List[Rect3] = []
    for axis in range(3):  # +x, +y, +z
        if ihi[axis] != chi[axis]:
            lo = list(clo)
            hi = list(chi)
            lo[axis] = ihi[axis]
            out.append(Rect3(Dim3(*lo), Dim3(*hi)))
            chi[axis] = ihi[axis]
    for axis in range(3):  # -x, -y, -z
        if ilo[axis] != clo[axis]:
            lo = list(clo)
            hi = list(chi)
            hi[axis] = ilo[axis]
            out.append(Rect3(Dim3(*lo), Dim3(*hi)))
            clo[axis] = ilo[axis]
    return out


def exchange_bytes(spec: LocalSpec, itemsizes) -> int:
    """Total bytes one subdomain receives per exchange, all quantities, all 26
    directions — the analytic model behind the reference's per-method byte
    counters (src/stencil.cu:260-361).  A direction contributes iff the radius
    in the *opposite* direction is nonzero (src/stencil.cu:149: skip dir if
    ``radius.dir(-dir) == 0``)."""
    total = 0
    for d in DIRECTIONS_26:
        if spec.radius.dir(-d) == 0:
            continue
        ext = spec.halo_extent(-d).flatten()
        total += sum(int(s) for s in itemsizes) * ext
    return total


def sweep_bytes(spec: LocalSpec, itemsizes) -> int:
    """Bytes one subdomain actually RECEIVES per 3-axis-sweep exchange
    (ops/exchange.py): each axis's slabs span the FULL raw extent of the
    other axes — including their halos — so edge/corner data rides along
    (and transits once per participating axis).  Whenever more than one axis
    has a radius this exceeds ``exchange_bytes`` (the reference's 26-message
    model, which counts each edge/corner once): the honest denominator for
    sweep-based B/s.
    """
    raw = spec.raw_size()
    r = spec.radius
    total = 0
    itemsize_sum = sum(int(s) for s in itemsizes)
    for axis in range(3):
        others = [raw[b] for b in range(3) if b != axis]
        plane = others[0] * others[1]
        # the +axis message has the receiver's -axis halo width and vice versa
        total += itemsize_sum * plane * (r.axis(axis, -1) + r.axis(axis, +1))
    return total


def ripple_value(p: Dim3) -> float:
    """The analytic test field from the reference's exchange tests
    (test_exchange.cu:14-38): ``x + ripple[x%4] + y + ripple[y%4] + z +
    ripple[z%4]`` with ripple = [0, .25, 0, -.25].  Any wrong halo byte is
    detectable without a reference simulation."""
    ripple = (0.0, 0.25, 0.0, -0.25)
    return p.x + ripple[p.x % 4] + p.y + ripple[p.y % 4] + p.z + ripple[p.z % 4]


def ripple_field(lo: Dim3, ext: Dim3, dtype=np.float32) -> np.ndarray:
    """Vectorized ripple over a box, returned with (x, y, z) index order."""
    ripple = np.array([0.0, 0.25, 0.0, -0.25])

    def axis_vals(start, n):
        idx = np.arange(start, start + n)
        return idx + ripple[idx % 4]

    vx = axis_vals(lo.x, ext.x)[:, None, None]
    vy = axis_vals(lo.y, ext.y)[None, :, None]
    vz = axis_vals(lo.z, ext.z)[None, None, :]
    return (vx + vy + vz).astype(dtype)
