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
    split axis its two face ``ppermute``s (one per direction scope) -- and the
    second axis of a jointly swept pair one corner relay behind each --, an
    axis the mesh does not split NO ``ppermute`` and one in-place kernel per
    quantity under ``exchange.<axis>.wrap`` — carrying a registered kernel
    name, its only operand the block itself."""
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
            relays = 2 if axis == wired[1:2] else 0  # "xy" flies jointly: y relays the corners
            assert len(permutes) == 2 + relays and not wraps, (axis, len(permutes), len(wraps))
            sides = [jx.name_stack_str(e).split("/")[-1] for e in permutes]
            assert sorted(sides) == sorted((1 + relays // 2) * [
                tm.exchange_direction_span(axis, "low"),
                tm.exchange_direction_span(axis, "high"),
            ])
        else:
            assert axis in wrapped
            assert not permutes and len(wraps) == len(quantities), (axis, len(wraps))
            kernel = tm.KERNEL_BLEND_PLANES if axis == "x" else tm.KERNEL_BLEND_SLAB
            for e in wraps:
                assert e.params["name"] == kernel
                assert len(e.invars) == 1 and e.invars[0].aval.shape == (12, 12, 12)
            # the sweep is the kernels and nothing else: no slab cut survives
            assert len(under) == len(wraps), [e.primitive.name for e in under]
    assert len([e for e in eqns if e.primitive.name == "ppermute"]) == 2 * len(wired) + (
        2 if len(wired) == 2 else 0)


# --- the joint sweep (ISSUE 50): two wired axes fly at once, the corners relayed ----------


def _mixed_radius():
    """``r_lo != r_hi`` on x and y, one side of z with no halo at all."""
    r = Radius.constant(0)
    for d, w in (((1, 0, 0), 2), ((-1, 0, 0), 1), ((0, 1, 0), 3), ((0, -1, 0), 2), ((0, 0, -1), 2)):
        r.set_dir(Dim3(*d), w)
    return r


def _exchange_of(arrays, mesh_shape, radius, valid_last=None, route="direct", serial=False):
    """``(the exchanged arrays, the traced exchange)`` on a CPU mesh -- with
    ``serial``, every sweep run strictly in turn (a group of one each: what
    the exchange did before)."""
    from jax.sharding import Mesh

    from stencil_tpu.ops import exchange as ex
    from stencil_tpu.parallel.mesh import MESH_AXES

    n_dev = int(np.prod(mesh_shape))
    mesh = Mesh(np.array(jax.devices()[:n_dev]).reshape(mesh_shape), MESH_AXES)
    with pytest.MonkeyPatch.context() as mp:
        if serial:
            mp.setattr(ex, "_sweep_groups", lambda sweeps: [[s] for s in sweeps])
        fn = ex.make_exchange_fn(mesh, radius, valid_last=valid_last, route=route, donate=False)
        return fn(arrays), jax.make_jaxpr(fn)(arrays)  # traced while the patch holds


def _blocks(mesh_shape, radius, interior, dtypes):
    import jax.numpy as jnp

    raw = [interior[a] + radius.axis(a, -1) + radius.axis(a, +1) for a in range(3)]
    shape = tuple(raw[a] * mesh_shape[a] for a in range(3))
    rng = np.random.default_rng(50)
    return [jnp.asarray(rng.integers(0, 120, shape)).astype(dt) for dt in dtypes]


JOINT_MESHES = {
    # mesh: the axes that fly jointly ("" = every sweep alone)
    (2, 2, 1): "xy", (2, 2, 2): "xy", (1, 2, 2): "yz", (4, 2, 1): "xy", (2, 1, 2): "",
}


@pytest.mark.parametrize("blend", [False, True], ids=["dus", "kernels"])
@pytest.mark.parametrize("uneven", [False, True], ids=["even", "uneven"])
@pytest.mark.parametrize("mesh_shape", sorted(JOINT_MESHES), ids=lambda m: "x".join(map(str, m)))
def test_the_joint_sweep_is_bitwise_the_sweeps_in_turn(mesh_shape, uneven, blend, monkeypatch):
    """Mixed dtypes (one message a direction, byte-fused), ``r_lo != r_hi``, a
    side with no halo, a padded last shard on every split axis: every raw cell
    of every block equals what the sweeps give run strictly in turn -- and the
    account says which axes flew jointly."""
    import jax.numpy as jnp

    from stencil_tpu.ops import exchange as ex

    monkeypatch.setenv("STENCIL_HALO_BLEND", "1" if blend else "0")
    radius = _mixed_radius()
    # the blend kernels know 32-bit tiles: all-f32 there, the byte-fused mix without them
    dtypes = [jnp.float32, jnp.float32] if blend else [jnp.float32, jnp.int8, jnp.bfloat16]
    valid_last = tuple(v if n > 1 else None for v, n in zip((5, 4, 6), mesh_shape)) if uneven else None
    arrays = _blocks(mesh_shape, radius, (6, 6, 7), dtypes)
    joint, traced = _exchange_of(arrays, mesh_shape, radius, valid_last)
    serial, in_turn = _exchange_of(arrays, mesh_shape, radius, valid_last, serial=True)
    assert (str(traced) != str(in_turn)) == bool(JOINT_MESHES[mesh_shape])
    for got, want in zip(joint, serial):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    raw = tuple(a // n for a, n in zip(arrays[0].shape, mesh_shape))
    account = ex.exchange_account(mesh_shape, radius, raw, dtypes, valid_last=valid_last)
    assert account.joint == (JOINT_MESHES[mesh_shape], 1 if JOINT_MESHES[mesh_shape] else 0)


def test_no_face_of_the_second_axis_waits_for_the_first():
    """The independence the scheduler needs: on mesh [2,2,1] the y FACE
    permutes take nothing a permute delivered -- they are cut from the blocks
    as they enter --, only the two corner relays behind them do (the x halo's
    rows, which x received), and they are the smaller message; the sweeps in
    turn make both y faces wait for x."""
    import jax.numpy as jnp

    from stencil_tpu.analysis import jaxpr as jx
    from stencil_tpu.telemetry import names as tm

    def y_permutes(serial):
        _, closed = _exchange_of(
            [jnp.zeros((24, 24, 12), jnp.float32)] * 3, (2, 2, 1), Radius.constant(2), serial=serial)
        (body,) = [
            j for j in jx.walk(closed.jaxpr) if any(e.primitive.name == "ppermute" for e in j.eqns)
        ]
        rows = jx.taint_rows(
            body,
            source=lambda e: e.primitive.name == "ppermute",
            watch=lambda e: e.primitive.name == "ppermute",
        )
        assert [r.tainted for r in rows if tm.SPAN_EXCHANGE_X in r.scopes.split("/")] == [False] * 2
        return [
            (r.scopes.split("/")[-1], r.tainted, r.eqn.invars[0].aval.size)
            for r in rows if tm.SPAN_EXCHANGE_Y in r.scopes.split("/")
        ]

    low, high = tm.SPAN_EXCHANGE_Y_LOW, tm.SPAN_EXCHANGE_Y_HIGH
    face, relay = 3 * 12 * 2 * 12, 3 * 4 * 2 * 12  # three quantities: the raw x extent 12, both x halos 4
    assert y_permutes(serial=False) == [
        (low, False, face), (high, False, face), (low, True, relay), (high, True, relay)]
    assert y_permutes(serial=True) == [(low, True, face), (high, True, face)]


@pytest.mark.parametrize("mesh_shape,route", [
    ((2, 1, 1), "direct"), ((1, 1, 8), "direct"), ((2, 1, 2), "direct"), ((2, 2, 2), "yzpack_xla"),
], ids=["2x1x1", "1x1x8", "2x1x2", "2x2x2-yzpack"])
def test_an_exchange_without_two_wired_sweeps_in_a_row_is_the_program_it_was(mesh_shape, route):
    """One wired axis, two with an unsplit one between them, a packed y behind
    a wired x: every sweep is a group of one, and the traced exchange is
    equation for equation the one the sweeps in turn trace."""
    import jax.numpy as jnp

    radius = Radius.constant(2)
    arrays = _blocks(mesh_shape, radius, (8, 8, 8), [jnp.float32, jnp.float32])
    _, joint = _exchange_of(arrays, mesh_shape, radius, route=route)
    _, serial = _exchange_of(arrays, mesh_shape, radius, route=route, serial=True)
    assert str(joint) == str(serial)
