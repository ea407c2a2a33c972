"""Tier-2: tile-local pallas halo blend == DUS, and the exchange with blend
forced produces identical halos to the DUS path."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from stencil_tpu.ops.halo_blend import blend_slab


@pytest.mark.parametrize("axis", [0, 1, 2])
@pytest.mark.parametrize("pos_kind", ["lo", "hi"])
@pytest.mark.parametrize("r", [1, 3, 9])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_blend_equals_dus(axis, pos_kind, r, dtype):
    shape = (6, 21, 19)
    if r > shape[axis]:
        pytest.skip("slab wider than the axis")
    rng = np.random.default_rng(0)
    block = jnp.asarray(rng.random(shape), dtype=dtype)
    slab_shape = list(shape)
    slab_shape[axis] = r
    slab = jnp.asarray(rng.random(slab_shape), dtype=dtype)
    pos = 0 if pos_kind == "lo" else shape[axis] - r

    idx = [slice(None)] * 3
    idx[axis] = slice(pos, pos + r)
    want = np.asarray(block).copy()
    want[tuple(idx)] = np.asarray(slab)

    got = blend_slab(block, slab, axis, pos, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), want)


def test_blend_mid_position_spanning_tiles():
    """A slab crossing a tile boundary (pos 6, r 5 spans sublane tiles 0+1)."""
    shape = (4, 24, 16)
    rng = np.random.default_rng(1)
    block = jnp.asarray(rng.random(shape), dtype=jnp.float32)
    slab = jnp.asarray(rng.random((4, 5, 16)), dtype=jnp.float32)
    want = np.asarray(block).copy()
    want[:, 6:11, :] = np.asarray(slab)
    got = blend_slab(block, slab, 1, 6, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("axis", [1, 2])
@pytest.mark.parametrize("r", [1, 3, 5])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_blend_dynamic_equals_dus_all_positions(axis, r, dtype):
    """Traced-offset blend == DUS at every legal offset, notably those whose
    region ends inside the LAST tile (the revisit-clobber hazard the modulo
    index map exists for)."""
    from stencil_tpu.ops.halo_blend import blend_slab_dynamic

    shape = (5, 21, 19)
    rng = np.random.default_rng(2)
    block = jnp.asarray(rng.random(shape), dtype=dtype)
    slab_shape = list(shape)
    slab_shape[axis] = r
    slab = jnp.asarray(rng.random(slab_shape), dtype=dtype)

    blend = jax.jit(
        lambda b, s, p: blend_slab_dynamic(b, s, axis, p, interpret=True)
    )
    for pos in range(shape[axis] - r + 1):
        idx = [slice(None)] * 3
        idx[axis] = slice(pos, pos + r)
        want = np.asarray(block).copy()
        want[tuple(idx)] = np.asarray(slab)
        got = blend(block, slab, jnp.int32(pos))
        np.testing.assert_array_equal(np.asarray(got), want, err_msg=f"pos={pos}")


def test_blend_dynamic_spans_tile_boundary():
    """r=5 slab crossing the f32 sublane-tile boundary at a traced offset."""
    from stencil_tpu.ops.halo_blend import blend_slab_dynamic

    shape = (4, 24, 16)
    rng = np.random.default_rng(3)
    block = jnp.asarray(rng.random(shape), dtype=jnp.float32)
    slab = jnp.asarray(rng.random((4, 5, 16)), dtype=jnp.float32)
    want = np.asarray(block).copy()
    want[:, 6:11, :] = np.asarray(slab)
    got = jax.jit(lambda b, s, p: blend_slab_dynamic(b, s, 1, p, interpret=True))(
        block, slab, jnp.int32(6)
    )
    np.testing.assert_array_equal(np.asarray(got), want)


def test_uneven_exchange_with_blend_forced_matches_dus(monkeypatch):
    """Padded (uneven) domain: exchange with the dynamic blend kernels forced
    equals the DUS path — the reference handles uneven sizes at full speed
    (partition.hpp:83-114) and so must we."""
    from stencil_tpu.core.radius import Radius
    from stencil_tpu.domain import DistributedDomain

    def run():
        dd = DistributedDomain(15, 13, 19)  # padded on every axis over 8 devs
        dd.set_radius(Radius.face_edge_corner(2, 1, 1))
        h = dd.add_data("q")
        dd.realize()
        dd.init_by_coords(h, lambda x, y, z: x * 10000.0 + y * 100.0 + z)
        dd.exchange()
        return dd.raw_to_host(h)

    monkeypatch.setenv("STENCIL_HALO_BLEND", "0")
    ref = run()
    monkeypatch.setenv("STENCIL_HALO_BLEND", "1")
    got = run()
    np.testing.assert_array_equal(ref, got)


def test_exchange_with_blend_forced_matches_dus(monkeypatch):
    """Full exchange with STENCIL_HALO_BLEND=1 equals the DUS path."""
    from stencil_tpu.core.radius import Radius
    from stencil_tpu.domain import DistributedDomain

    def run():
        dd = DistributedDomain(16, 16, 16)
        dd.set_radius(Radius.face_edge_corner(2, 1, 1))
        h = dd.add_data("q")
        dd.realize()
        dd.init_by_coords(h, lambda x, y, z: x * 10000.0 + y * 100.0 + z)
        dd.exchange()
        return dd.raw_to_host(h)

    monkeypatch.setenv("STENCIL_HALO_BLEND", "0")
    ref = run()
    monkeypatch.setenv("STENCIL_HALO_BLEND", "1")
    got = run()
    np.testing.assert_array_equal(ref, got)


# --- the self-wrap sweep (an axis the mesh does not split) --------------------

def _one_axis_exchange(block, axis, r_lo, r_hi, monkeypatch, blend):
    """One axis sweep of the real exchange on a one-device mesh: the
    self-wrap kernel when the blend kernels are forced on, the slab cut +
    self-``ppermute`` + plain DUS when they are off."""
    from jax.sharding import Mesh

    from stencil_tpu.core.radius import Radius
    from stencil_tpu.ops.exchange import make_exchange_fn
    from stencil_tpu.parallel.mesh import MESH_AXES

    d = [0, 0, 0]
    entries = {}
    for sign, r in ((-1, r_lo), (+1, r_hi)):
        d[axis] = sign
        entries[tuple(d)] = r
    monkeypatch.setenv("STENCIL_HALO_BLEND", blend)
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1, 1), MESH_AXES)
    fn = make_exchange_fn(mesh, Radius.from_dict(entries), axes=(axis,), donate=False)
    return np.asarray(fn([block])[0])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.float64])
@pytest.mark.parametrize("n", [8, 126, 250, 512])
@pytest.mark.parametrize("radii", [(1, 1), (3, 3), (2, 5), (16, 16)])
@pytest.mark.parametrize("axis", [0, 1, 2])
def test_self_wrap_sweep_equals_dus_sweep(axis, radii, n, dtype, monkeypatch):
    """Bit for bit, over extents whose four ranges (two halos, two sources)
    share one tile (interior 8), straddle a tile boundary (126: the high
    ranges cross lane 128; 250 with radius 16), and sit in distinct tiles
    (250, 512 — the weak cell's own geometry on a thin block)."""
    r_lo, r_hi = radii
    if n < max(radii):
        pytest.skip("interior narrower than the halo: the sweep stays on the slab path")
    shape = [5, 11, 13]
    shape[axis] = n + r_lo + r_hi
    rng = np.random.default_rng(7)
    block = jnp.asarray(rng.integers(0, 1 << 7, size=shape), dtype=dtype)
    want = _one_axis_exchange(block, axis, r_lo, r_hi, monkeypatch, "0")
    got = _one_axis_exchange(block, axis, r_lo, r_hi, monkeypatch, "1")
    np.testing.assert_array_equal(got, want)
    # the halos really moved (a sweep that wrote nothing would also "agree"
    # with itself, not with the roll truth)
    idx = [slice(None)] * 3
    idx[axis] = slice(r_lo, r_lo + n)
    interior = np.asarray(block)[tuple(idx)]
    truth = np.concatenate(
        [np.take(interior, range(n - r_lo, n), axis), interior,
         np.take(interior, range(r_hi), axis)],
        axis=axis,
    )
    np.testing.assert_array_equal(got, truth)


@pytest.mark.parametrize("radius", [1, 3])
def test_one_chip_exchange_wraps_all_axes_against_roll(radius, monkeypatch):
    """The full three-axis exchange on mesh [1,1,1], every sweep a self-wrap:
    every raw cell — faces, edges and corners — equals the periodic
    ``jnp.roll`` truth of the interior."""
    from stencil_tpu.core.radius import Radius
    from stencil_tpu.domain import DistributedDomain
    from stencil_tpu.ops.exchange import wrap_axes

    monkeypatch.setenv("STENCIL_HALO_BLEND", "1")
    n, r = (12, 10, 14), radius
    dd = DistributedDomain(*n)
    dd.set_radius(Radius.constant(r))
    dd.set_devices(jax.devices()[:1])
    h = dd.add_data("q")
    dd.realize()
    assert dd.exchange_route() == "direct" and dd._wrap_axes == "xyz"
    dd.init_by_coords(h, lambda x, y, z: x * 10000.0 + y * 100.0 + z)
    dd.exchange()
    interior = dd.quantity_to_host(h)
    want = np.pad(interior, r, mode="wrap")
    np.testing.assert_array_equal(dd.raw_to_host(h), want)
    assert wrap_axes((2, 2, 1), Radius.constant(r), (18, 18, 18), [jnp.float32]) == "z"
    assert wrap_axes((2, 2, 1), Radius.constant(r), (18, 18, 18), [jnp.float32],
                     route="zpack_xla") == ""
    monkeypatch.setenv("STENCIL_HALO_BLEND", "0")
    assert wrap_axes((1, 1, 1), Radius.constant(r), (18, 18, 18), [jnp.float32]) == ""


def test_uneven_unsplit_axis_wraps_at_static_offset(monkeypatch):
    """A padded axis the mesh does not split: its one shard is the last
    shard, the valid width is static, and the wrap kernel serves it (no
    ``blend_slab_dynamic``) — equal to the DUS path."""
    from stencil_tpu.ops import exchange
    from stencil_tpu.core.radius import Radius

    assert exchange._sweep_kind(2, 2, 2, 1, 24, 17, "direct", [jnp.float32], True) == "direct"
    monkeypatch.setenv("STENCIL_HALO_BLEND", "1")
    assert exchange._sweep_kind(2, 2, 2, 1, 24, 17, "direct", [jnp.float32], True) == "wrap"
    assert exchange._sweep_kind(2, 2, 2, 2, 24, 17, "direct", [jnp.float32], True) == "direct"
    assert exchange._sweep_kind(2, 2, 2, 1, 24, 17, "direct", [jnp.complex128], True) == "direct"
    assert exchange._sweep_kind(2, 2, 2, 1, 24, 17, "direct", [jnp.float32], False) == "direct"
    assert exchange._sweep_kind(2, 2, 2, 1, 5, None, "direct", [jnp.float32], True) == "direct"

    rng = np.random.default_rng(11)
    block = jnp.asarray(rng.random((6, 9, 24)), jnp.float32)
    blocks = exchange._sweep_group(
        [block], [exchange._Sweep(2, "wrap", 2, 2, 1, 24, 17)], "xyz", "direct"
    )
    want = np.asarray(block).copy()
    want[:, :, 0:2] = want[:, :, 17:19]
    want[:, :, 19:21] = want[:, :, 2:4]
    np.testing.assert_array_equal(np.asarray(blocks[0]), want)
