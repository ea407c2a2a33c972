"""Tier-2: the slab-consuming Jacobi kernel — the multi-device fast path.

``jacobi_slab_step`` eats the six ppermuted face slabs directly (no shell
writes, no halo re-read).  Pinned three ways:

* unit: feeding a block its OWN faces as slabs is the periodic wrap — must be
  bit-identical to ``jacobi_wrap_step`` (the mesh-[1,1,1] self-permute case).
* model: ``Jacobi3D(kernel_impl="pallas")`` on the fake 8-chip mesh routes
  through the slab path and matches the generic jnp formulation.
* HLO: one slab iteration carries exactly 6 collective-permutes (the same
  count test_hlo pins for the general exchange).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from stencil_tpu.models.jacobi import Jacobi3D
from stencil_tpu.ops.jacobi_pallas import (
    jacobi_slab_step,
    jacobi_wrap_step,
    yz_dist2_plane,
)


def _self_slabs(b):
    """The block's own boundary planes as received slabs = periodic wrap."""
    n = b.shape
    return (
        b[n[0] - 1],
        b[0],
        b[:, n[1] - 1, :],
        b[:, 0, :],
        b[:, :, n[2] - 1].T,
        b[:, :, 0].T,
    )


@pytest.mark.parametrize("shape", [(16, 16, 16), (8, 12, 16)])
def test_slab_self_faces_bitexact_vs_wrap(shape):
    key = jax.random.PRNGKey(0)
    b = jax.random.uniform(key, shape, jnp.float32)
    d2 = yz_dist2_plane(0, 0, shape[1:], shape)
    origin = jnp.zeros((3,), jnp.int32)
    out_slab = jacobi_slab_step(
        b, *_self_slabs(b), origin, d2, shape, interpret=True
    )
    # wrap kernel only handles cubic gx == X; emulate with the same sphere
    # params by using a cubic domain for the cross-check
    if shape[0] == shape[1] == shape[2]:
        out_wrap = jacobi_wrap_step(b, interpret=True)
        np.testing.assert_array_equal(np.asarray(out_slab), np.asarray(out_wrap))
    # always: iterating the slab step preserves the mean away from spheres
    assert np.isfinite(np.asarray(out_slab)).all()


def test_slab_step_requires_two_planes():
    b = jnp.zeros((1, 8, 8), jnp.float32)
    d2 = yz_dist2_plane(0, 0, (8, 8), (1, 8, 8))
    with pytest.raises(AssertionError):
        jacobi_slab_step(
            b, *_self_slabs(b), jnp.zeros((3,), jnp.int32), d2, (1, 8, 8),
            interpret=True,
        )


def test_model_routes_slab_multidevice():
    """Forced slab on even sizes on the 8-device mesh engages (auto now
    prefers the temporally-blocked wavefront route)."""
    m = Jacobi3D(24, 24, 24, kernel_impl="pallas", interpret=True,
                 pallas_path="slab")
    m.realize()
    assert m.dd.num_subdomains() == len(jax.devices())
    assert m._pallas_path == "slab"


def test_model_routes_wavefront_plain_when_uneven():
    # uneven sizes now reach the temporal fast path too (plain kernel
    # variant; the z-slab form needs even shards) — full-speed uneven,
    # partition.hpp:83-114 parity
    m = Jacobi3D(17, 18, 19, kernel_impl="pallas", interpret=True)
    m.realize()
    assert m._pallas_path == "wavefront"
    assert not m._wavefront_z_slabs




@pytest.mark.parametrize("size", [(24, 24, 24), (16, 24, 32)])
def test_slab_model_matches_jnp(size):
    a = Jacobi3D(*size)
    a.realize()
    b = Jacobi3D(*size, kernel_impl="pallas", interpret=True,
                 pallas_path="slab")
    b.realize()
    assert b._pallas_path == "slab"
    a.step(4)
    b.step(4)
    np.testing.assert_allclose(a.temperature(), b.temperature(), rtol=1e-6)


def test_slab_model_raw_readback_refreshes_shell():
    """The slab path never writes the carried shell; raw readback must still
    show halos consistent with the current interiors (mark_shell_stale)."""
    m = Jacobi3D(24, 24, 24, kernel_impl="pallas", interpret=True)
    m.realize()
    m.step(2)
    assert m.dd._shell_stale
    raw = m.dd.raw_to_host(m.h)
    t = m.temperature()
    # one shard's -x halo plane == the wrapped neighbor's top interior plane
    lo = m.dd._shell_radius.lo()
    n = m.dd.subdomain_size()
    dim = m.dd.placement.dim()
    rawsz = m.dd.local_spec().raw_size()
    # shard (0,0,0): its -x halo comes from shard (dim.x-1, 0, 0)'s top plane
    halo = raw[lo.x - 1, lo.y : lo.y + n.y, lo.z : lo.z + n.z]
    expect = t[(dim.x - 1) * n.x + n.x - 1, 0 : n.y, 0 : n.z]
    np.testing.assert_array_equal(halo, expect)


def test_slab_iteration_hlo_has_six_permutes():
    """One forced-slab iteration = exactly 6 collective-permutes (2 per
    axis).  The default wavefront route trades message count for in-VMEM z
    handling: 6 face messages plus 8 small corner-forwarding permutes (its
    z slabs are extended with y- then x-neighbor pieces), all slab-sized."""
    m = Jacobi3D(24, 24, 24, kernel_impl="pallas", interpret=True,
                 pallas_path="slab")
    m.realize()
    text = m._step.lower(m.dd._curr, 1).compile().as_text()
    assert text.count("collective-permute-start") <= 6, text.count(
        "collective-permute-start"
    )
    n_permutes = text.count("collective-permute(") + text.count(
        "collective-permute-start("
    )
    assert n_permutes == 6, n_permutes


def test_wavefront_macro_hlo_permute_count(monkeypatch):
    """The z-slab wavefront macro: 4 array sweeps (x/y, flying jointly: + 2
    corner relays behind the y faces) + 2 z-slab permutes + 8
    corner-forwarding extension permutes = 16, independent of depth."""
    monkeypatch.delenv("STENCIL_Z_SLABS", raising=False)  # pin z-slab mode on
    m = Jacobi3D(24, 24, 24, kernel_impl="pallas", interpret=True)
    m.realize()
    assert m._pallas_path == "wavefront" and m._wavefront_z_slabs
    text = m._step.lower(m.dd._curr, m._wavefront_m).compile().as_text()
    n_permutes = text.count("collective-permute(") + text.count(
        "collective-permute-start("
    )
    assert n_permutes == 16, n_permutes
