"""Tier-2: N-D data — quantities with leading per-cell component dims.

The reference lists N-D data as future work (README.md:157-176); here a
(3,)-component quantity is a (3, X, Y, Z) array, unsharded on the component
dim, riding the same fused halo exchange (leading dims flatten into the
per-direction messages, ops/exchange._fused_shift).
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from stencil_tpu.core.dim3 import Dim3, Rect3
from stencil_tpu.core.radius import Radius
from stencil_tpu.domain import DistributedDomain


def _ripple(c, x, y, z):
    return c * 1e6 + x * 10000.0 + y * 100.0 + z


def _make(size=(16, 16, 16), radius=2, components=(3,)):
    dd = DistributedDomain(*size)
    dd.set_radius(Radius.face_edge_corner(radius, radius, radius))
    h = dd.add_data("v", components=components)
    dd.realize()
    field = np.zeros(components + size, np.float32)
    for c in np.ndindex(*components):
        xs, ys, zs = np.meshgrid(*[np.arange(s) for s in size], indexing="ij")
        field[c] = _ripple(c[0] if c else 0, xs, ys, zs)
    dd.set_quantity(h, field)
    return dd, h, field


def test_nd_roundtrip():
    dd, h, field = _make()
    np.testing.assert_array_equal(dd.quantity_to_host(h), field)


def test_nd_exchange_fills_shell_per_component():
    """Every component's halo must hold the periodic-wrapped neighbor value
    — the ripple check of test_exchange, lifted to a vector quantity."""
    dd, h, field = _make()
    dd.exchange()
    raw = dd.raw_to_host(h)
    dim = dd.placement.dim()
    rawsz = dd.local_spec().raw_size()
    lo = dd._shell_radius.lo()
    n = dd.subdomain_size()
    size = tuple(dd.size())
    rng = np.random.default_rng(0)
    for _ in range(60):
        c = rng.integers(0, 3)
        sx, sy, sz = (rng.integers(0, dim[a]) for a in range(3))
        rx, ry, rz = (rng.integers(0, rawsz[a]) for a in range(3))
        gx = (sx * n.x + rx - lo.x) % size[0]
        gy = (sy * n.y + ry - lo.y) % size[1]
        gz = (sz * n.z + rz - lo.z) % size[2]
        got = raw[c, sx * rawsz.x + rx, sy * rawsz.y + ry, sz * rawsz.z + rz]
        assert got == _ripple(c, gx, gy, gz), (c, sx, sy, sz, rx, ry, rz)


def test_nd_mixed_with_scalar_fuses_6_permutes():
    """A vector and a scalar quantity still exchange in <= 6 face messages
    (and the joint x-y sweep's two corner relays)."""
    dd = DistributedDomain(16, 16, 16)
    dd.set_radius(1)
    dd.add_data("v", components=(3,))
    dd.add_data("s")
    dd.realize()
    txt = dd._exchange_fn.lower(dd._curr).compile().as_text()
    # count APPLICATION sites only — older toolchains name result variables
    # "%collective-permute.N", so a bare substring count would also match
    # every USE of the result
    from tests.test_hlo import _PERMUTE_RE, MAX_PERMUTES

    assert 1 <= len(re.findall(_PERMUTE_RE, txt)) <= MAX_PERMUTES


def test_nd_make_step_matches_per_component_scalar_run():
    """A 3-component diffusion step == three independent scalar domains."""

    def kernel(views, info):
        src = views["v"]
        val = (
            src.sh(1, 0, 0) + src.sh(-1, 0, 0) + src.sh(0, 1, 0)
            + src.sh(0, -1, 0) + src.sh(0, 0, 1) + src.sh(0, 0, -1)
        ) / 6.0
        return {"v": val.astype(src.center().dtype)}

    size = (16, 16, 16)
    dd = DistributedDomain(*size)
    dd.set_radius(1)
    h = dd.add_data("v", components=(3,))
    dd.realize()
    rng = np.random.default_rng(1)
    field = rng.random((3,) + size).astype(np.float32)
    dd.set_quantity(h, field)
    step = dd.make_step(kernel, overlap=True)
    dd.run_step(step, 3)
    got = dd.quantity_to_host(h)

    for c in range(3):
        sd = DistributedDomain(*size)
        sd.set_radius(1)
        sh = sd.add_data("v")
        sd.realize()
        sd.set_quantity(sh, field[c])
        sstep = sd.make_step(kernel, overlap=True)
        sd.run_step(sstep, 3)
        np.testing.assert_allclose(got[c], sd.quantity_to_host(sh), rtol=1e-6)


def test_nd_region_readback():
    dd, h, field = _make()
    r = Rect3(Dim3(3, 1, 5), Dim3(9, 14, 12))
    got = dd.region_to_host(h, r)
    np.testing.assert_array_equal(got, field[:, 3:9, 1:14, 5:12])


def test_nd_paraview_one_column_per_component(tmp_path):
    from stencil_tpu.io.paraview import write_paraview

    dd, h, field = _make(size=(8, 8, 8), radius=1, components=(2,))
    write_paraview(dd, str(tmp_path / "out"))
    first = (tmp_path / "out_0.txt").read_text().splitlines()
    assert first[0] == "Z,Y,X,v_0,v_1"
    z, y, x, v0, v1 = first[1].split(",")
    gx, gy, gz = int(x), int(y), int(z)
    assert float(v0) == pytest.approx(_ripple(0, gx, gy, gz))
    assert float(v1) == pytest.approx(_ripple(1, gx, gy, gz))


def test_nd_uneven_roundtrip_and_exchange():
    """Padded axes with a component dim: interior survives, exchange runs."""
    dd = DistributedDomain(15, 13, 16)
    dd.set_radius(1)
    h = dd.add_data("v", components=(2,))
    dd.realize()
    rng = np.random.default_rng(2)
    field = rng.random((2, 15, 13, 16)).astype(np.float32)
    dd.set_quantity(h, field)
    dd.exchange()
    np.testing.assert_array_equal(dd.quantity_to_host(h), field)
