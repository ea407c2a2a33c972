"""Tier-2 integration: halo exchange correctness over the fake 8-device mesh.

Mirrors reference test/test_exchange.cu: init every interior cell with the
analytic ripple field f(global coord), exchange, then require every halo cell
to equal f(periodically wrapped global coord) — any wrong halo byte is
detected without a reference simulation.  Radius matrix follows
test_exchange.cu:205-238: 0, 1, 2, +x-only, uneven x, faces-only,
face+edge+corner mixes.
"""

import jax
import numpy as np
import pytest

from stencil_tpu.core.dim3 import Dim3
from stencil_tpu.core.geometry import ripple_value
from stencil_tpu.core.radius import Radius
from stencil_tpu.domain import DistributedDomain


def _check_exchanged_halos(dd: DistributedDomain, h) -> None:
    """Walk every shard's full raw block; each cell (interior or halo) must
    hold ripple(wrap(global coord))."""
    raw_global = dd.raw_to_host(h)
    dim = dd.placement.dim()
    spec = dd.local_spec()
    n = spec.sz
    raw = spec.raw_size()
    lo = dd.radius().lo()
    size = dd.size()
    for ix in range(dim.x):
        for iy in range(dim.y):
            for iz in range(dim.z):
                block = raw_global[
                    ix * raw.x : (ix + 1) * raw.x,
                    iy * raw.y : (iy + 1) * raw.y,
                    iz * raw.z : (iz + 1) * raw.z,
                ]
                origin = Dim3(ix * n.x, iy * n.y, iz * n.z)
                for (bx, by, bz), val in np.ndenumerate(block):
                    g = Dim3(
                        origin.x - lo.x + bx, origin.y - lo.y + by, origin.z - lo.z + bz
                    ).wrap(size)
                    expected = ripple_value(g)
                    assert val == pytest.approx(expected), (
                        f"shard ({ix},{iy},{iz}) raw ({bx},{by},{bz}) -> global {g}: "
                        f"got {val}, want {expected}"
                    )


def _run_exchange_check(radius: Radius, size=(16, 16, 16)) -> None:
    dd = DistributedDomain(*size)
    dd.set_radius(radius)
    h = dd.add_data("d0")
    dd.realize()
    dd.init_by_coords(h, lambda x, y, z: _ripple_jnp(x) + _ripple_jnp(y) + _ripple_jnp(z))
    # interior must be intact before and after
    before = dd.quantity_to_host(h)
    dd.exchange()
    after = dd.quantity_to_host(h)
    np.testing.assert_array_equal(before, after)
    _check_exchanged_halos(dd, h)


def _ripple_jnp(v):
    import jax.numpy as jnp

    table = jnp.array([0.0, 0.25, 0.0, -0.25])
    return v + table[v % 4]


def test_exchange_radius_1():
    _run_exchange_check(Radius.constant(1))


def test_exchange_radius_2():
    _run_exchange_check(Radius.constant(2))


def test_exchange_radius_0_noop():
    dd = DistributedDomain(8, 8, 8)
    dd.set_radius(Radius.constant(0))
    h = dd.add_data("d0")
    dd.realize()
    dd.init_by_coords(h, lambda x, y, z: x + y + z)
    before = dd.quantity_to_host(h)
    dd.exchange()
    np.testing.assert_array_equal(before, dd.quantity_to_host(h))


def test_exchange_plus_x_only():
    # test_exchange.cu radius {+x: 2}: only the -x halo (width 2) is exchanged
    r = Radius.constant(0)
    r.set_dir(Dim3(1, 0, 0), 2)
    _run_exchange_check(r)


def test_exchange_uneven_x():
    # +x=2, -x=1 (test_exchange.cu:228-232 mixed radius)
    r = Radius.constant(0)
    r.set_dir(Dim3(1, 0, 0), 2)
    r.set_dir(Dim3(-1, 0, 0), 1)
    _run_exchange_check(r)


def test_exchange_faces_only():
    _run_exchange_check(Radius.face_edge_corner(2, 0, 0))


def test_exchange_face_edge_corner():
    _run_exchange_check(Radius.face_edge_corner(2, 2, 2))


def test_allgather_method_matches_ppermute():
    """MethodFlags.AllGather (debug path) produces identical halos to the
    production ppermute exchange (the role method selection plays in the
    reference, stencil.hpp:29-41)."""
    from stencil_tpu.utils.config import MethodFlags

    results = []
    for method in (MethodFlags.All, MethodFlags.AllGather):
        dd = DistributedDomain(16, 16, 16)
        dd.set_radius(Radius.face_edge_corner(2, 1, 1))
        dd.set_methods(method)
        h = dd.add_data("d0")
        dd.realize()
        dd.init_by_coords(h, lambda x, y, z: x * 37.0 + y * 5.0 + z)
        dd.exchange()
        results.append(dd.raw_to_host(h))
    np.testing.assert_array_equal(results[0], results[1])


def test_exchange_multi_quantity():
    """N fields share one exchange (packer.cuh:52-69 joint exchange analog)."""
    dd = DistributedDomain(16, 16, 16)
    dd.set_radius(Radius.constant(1))
    h1 = dd.add_data("q1")
    h2 = dd.add_data("q2", dtype=np.float64)
    dd.realize()
    dd.init_by_coords(h1, lambda x, y, z: _ripple_jnp(x) + _ripple_jnp(y) + _ripple_jnp(z))
    dd.init_by_coords(h2, lambda x, y, z: (x * 10000 + y * 100 + z).astype(np.float64))
    dd.exchange()
    _check_exchanged_halos(dd, h1)
    # pack_xyz-style check for q2 (test_cuda_mpi_distributed_domain.cu:10-22)
    raw_global = dd.raw_to_host(h2)
    dim = dd.placement.dim()
    spec = dd.local_spec()
    n, raw, lo = spec.sz, spec.raw_size(), dd.radius().lo()
    for ix in range(dim.x):
        for iy in range(dim.y):
            for iz in range(dim.z):
                block = raw_global[
                    ix * raw.x : (ix + 1) * raw.x,
                    iy * raw.y : (iy + 1) * raw.y,
                    iz * raw.z : (iz + 1) * raw.z,
                ]
                for (bx, by, bz), val in np.ndenumerate(block):
                    g = Dim3(
                        ix * n.x - lo.x + bx, iy * n.y - lo.y + by, iz * n.z - lo.z + bz
                    ).wrap(dd.size())
                    assert val == g.x * 10000 + g.y * 100 + g.z


def test_exchange_two_rounds_stable():
    """Exchanging twice must be idempotent on interior+halo."""
    dd = DistributedDomain(8, 8, 8)
    dd.set_radius(Radius.constant(1))
    h = dd.add_data("d0")
    dd.realize()
    dd.init_by_coords(h, lambda x, y, z: x * 100.0 + y * 10.0 + z)
    dd.exchange()
    first = dd.raw_to_host(h)
    dd.exchange()
    np.testing.assert_array_equal(first, dd.raw_to_host(h))


def test_swap():
    dd = DistributedDomain(8, 8, 8)
    dd.set_radius(Radius.constant(1))
    h = dd.add_data("d0")
    dd.realize()
    dd.init_by_coords(h, lambda x, y, z: x + 0 * y + 0 * z)
    a = dd.quantity_to_host(h, "curr").copy()
    dd.swap()
    np.testing.assert_array_equal(dd.quantity_to_host(h, "next"), a)
    assert dd.quantity_to_host(h, "curr").sum() == 0


def test_exchange_int8_and_bool_quantities():
    """1-byte dtypes (int8, bool) must survive the byte-fused message path."""
    import jax.numpy as jnp

    dd = DistributedDomain(16, 16, 16)
    dd.set_radius(Radius.constant(1))
    hf = dd.add_data("f", jnp.float32)
    hi = dd.add_data("i8", jnp.int8)
    hb = dd.add_data("m", jnp.bool_)
    dd.realize()
    dd.init_by_coords(hf, lambda x, y, z: (x + y + z).astype(jnp.float32))
    dd.init_by_coords(hi, lambda x, y, z: ((x + y + z) % 100).astype(jnp.int8))
    dd.init_by_coords(hb, lambda x, y, z: (x + y + z) % 2 == 0)
    dd.exchange()
    spec = dd.local_spec()
    raw = dd.raw_to_host(hi)
    rawb = dd.raw_to_host(hb)
    rawsz, n, lo = spec.raw_size(), spec.sz, dd.radius().lo()
    dim = dd.placement.dim()
    for ix in range(dim.x):
        blk = raw[ix * rawsz.x : (ix + 1) * rawsz.x, : rawsz.y, : rawsz.z]
        blkb = rawb[ix * rawsz.x : (ix + 1) * rawsz.x, : rawsz.y, : rawsz.z]
        gx = (ix * n.x - lo.x) % 16  # -x halo cell's global x
        assert blk[0, 1, 1] == (gx + 0 + 0) % 100
        assert blkb[0, 1, 1] == ((gx + 0 + 0) % 2 == 0)


@pytest.mark.parametrize(
    "mesh_shape,wired,wrapped",
    [((1, 1, 8), "z", "xy"), ((2, 2, 1), "xy", "z"), ((1, 1, 1), "", "xyz")],
    ids=["1x1x8", "2x2x1", "1x1x1"],
)
def test_unsplit_axes_trace_wrap_kernels_not_ppermutes(
    mesh_shape, wired, wrapped, monkeypatch
):
    """What the exchange traces per axis with the blend kernels engaged: a
    split axis its two ``ppermute``s (one per direction scope), an axis the
    mesh does not split NO ``ppermute`` and one in-place kernel per quantity
    under ``exchange.<axis>.wrap`` — carrying a registered kernel name, its
    only operand the block itself."""
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from stencil_tpu.analysis import jaxpr as jx
    from stencil_tpu.ops.exchange import make_exchange_fn
    from stencil_tpu.parallel.mesh import MESH_AXES
    from stencil_tpu.telemetry import names as tm

    monkeypatch.setenv("STENCIL_HALO_BLEND", "1")
    n_dev = int(np.prod(mesh_shape))
    mesh = Mesh(np.array(jax.devices()[:n_dev]).reshape(mesh_shape), MESH_AXES)
    fn = make_exchange_fn(mesh, Radius.constant(2), donate=False)
    shape = tuple(12 * d for d in mesh_shape)
    quantities = [jnp.zeros(shape, jnp.float32) for _ in range(3)]
    eqns = list(jx.iter_eqns(jax.make_jaxpr(fn)(quantities)))
    for axis in "xyz":
        sweep, wrap = tm.exchange_axis_span(axis), tm.exchange_wrap_span(axis)
        under = [e for e in eqns if sweep in jx.name_stack_str(e).split("/")]
        permutes = [e for e in under if e.primitive.name == "ppermute"]
        wraps = [
            e
            for e in under
            if e.primitive.name == "pallas_call"
            and wrap in jx.name_stack_str(e).split("/")
        ]
        if axis in wired:
            assert len(permutes) == 2 and not wraps, (axis, len(permutes), len(wraps))
            assert {jx.name_stack_str(e).split("/")[-1] for e in permutes} == {
                tm.exchange_direction_span(axis, "low"),
                tm.exchange_direction_span(axis, "high"),
            }
        else:
            assert axis in wrapped
            assert not permutes and len(wraps) == len(quantities), (axis, len(wraps))
            kernel = tm.KERNEL_BLEND_PLANES if axis == "x" else tm.KERNEL_BLEND_SLAB
            for e in wraps:
                assert e.params["name"] == kernel
                assert len(e.invars) == 1 and e.invars[0].aval.shape == (12, 12, 12)
            # the sweep is the kernels and nothing else: no slab cut survives
            assert len(under) == len(wraps), [e.primitive.name for e in under]
    assert len([e for e in eqns if e.primitive.name == "ppermute"]) == 2 * len(wired)
