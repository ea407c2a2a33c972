"""Tier-2: the Pallas plane-streaming Jacobi kernel matches the XLA path.

The pallas kernel (ops/jacobi_pallas.py) is the flagship fast path (~2.6x on
real TPU); interpret mode lets the fake 8-chip CPU mesh pin its math against
the generic make_step formulation, including sphere forcing, periodic wrap,
multi-device halos, and uneven padding.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from stencil_tpu.models.jacobi import Jacobi3D


@pytest.mark.parametrize("size", [(24, 24, 24), (17, 18, 19)])
def test_pallas_matches_jnp_multidevice(size):
    a = Jacobi3D(*size)
    a.realize()
    b = Jacobi3D(*size, kernel_impl="pallas", interpret=True)
    b.realize()
    assert b.dd.num_subdomains() == len(jax.devices())
    a.step(4)
    b.step(4)
    np.testing.assert_allclose(a.temperature(), b.temperature(), rtol=1e-6)


def test_pallas_single_device_spheres_active():
    """The forcing must actually fire (hot=1, cold=0 present)."""
    m = Jacobi3D(30, 30, 30, kernel_impl="pallas", interpret=True, devices=jax.devices()[:1])
    m.realize()
    m.step(2)
    t = m.temperature()
    assert t.max() == pytest.approx(1.0)
    assert t.min() == pytest.approx(0.0)
    # hot sphere center (x=10, y=15, z=15) clamped hot
    assert t[10, 15, 15] == pytest.approx(1.0)
    assert t[20, 15, 15] == pytest.approx(0.0)


def _jacobi_roll_reference(b, levels):
    """``levels`` jacobi updates of the whole periodic domain by ``jnp.roll``
    on the array: the mean of the six face neighbours in the kernels' own
    order, then the hot and cold spheres — no plane ring, no kernel."""
    import jax.numpy as jnp

    X, Y, Z = b.shape
    x, y, z = jnp.meshgrid(jnp.arange(X), jnp.arange(Y), jnp.arange(Z), indexing="ij")
    in_r2 = (X // 10 + 1) ** 2

    def d2(cx):
        return (x - cx) ** 2 + (y - Y // 2) ** 2 + (z - Z // 2) ** 2

    for _ in range(levels):
        b = (
            jnp.roll(b, 1, 0) + jnp.roll(b, -1, 0)
            + jnp.roll(b, 1, 1) + jnp.roll(b, -1, 1)
            + jnp.roll(b, 1, 2) + jnp.roll(b, -1, 2)
        ) / 6.0
        b = jnp.where(d2(X // 3) < in_r2, 1.0, b)
        b = jnp.where(d2(X * 2 // 3) < in_r2, 0.0, b).astype(b.dtype)
    return b


@pytest.mark.parametrize(
    "shape,k,seed",
    [
        ((12, 16, 16), 2, 7),
        ((12, 16, 16), 3, 7),
        # the shapes, depths and seeds the matrix-unit pins ran their roll +
        # add side at (PR 29): never held against ground truth before
        ((12, 16, 16), 1, 7),
        ((12, 16, 16), 1, 9),
        ((12, 16, 16), 3, 9),
        ((12, 13, 13), 2, 5),  # prime plane extents
    ],
)
def test_wrap_temporal_blocking_bit_exact(shape, k, seed):
    """k temporally-blocked levels == k plain applications, bitwise: each
    level's arithmetic (summation order, forcing selects) is identical to a
    k=1 pass, so the wavefront must not change a single ulp.  And both equal
    the ``jnp.roll`` formulation on the whole array, bitwise too: the sums
    run in the same order (the reference is jitted, as the interpreted
    kernel is, so XLA lowers the division by 6 alike on both sides)."""
    import jax.numpy as jnp

    from stencil_tpu.ops.jacobi_pallas import jacobi_wrap_step

    rng = np.random.default_rng(seed)
    b0 = jnp.asarray(rng.random(shape), jnp.float32)
    ref = b0
    for _ in range(k):
        ref = jacobi_wrap_step(ref, interpret=True)
    got = jacobi_wrap_step(b0, interpret=True, k=k)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    want = jax.jit(_jacobi_roll_reference, static_argnums=1)(b0, k)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_wrap_temporal_blocking_model_with_remainder():
    """Model path with temporal_k=3 and steps=5 (1 blocked dispatch + 2
    remainder) equals the plain k=1 wrap path exactly."""
    dev = jax.devices()[:1]
    a = Jacobi3D(26, 24, 22, kernel_impl="pallas", interpret=True, devices=dev,
                 temporal_k=1)
    a.realize()
    b = Jacobi3D(26, 24, 22, kernel_impl="pallas", interpret=True, devices=dev,
                 temporal_k=3)
    b.realize()
    assert b._wrap_k == 3
    a.step(5)
    b.step(5)
    np.testing.assert_array_equal(a.temperature(), b.temperature())


@pytest.mark.parametrize(
    "size",
    [(24, 24, 24), (16, 24, 32), (21, 21, 21)],  # 21: padded last shards
)
def test_wavefront_matches_jnp_multidevice(size):
    """The temporally-blocked multi-device path (m-shell exchange + m-level
    wavefront kernel) equals the generic jnp formulation, including a
    steps % m remainder dispatch."""
    a = Jacobi3D(*size)
    a.realize()
    b = Jacobi3D(*size, kernel_impl="pallas", interpret=True, pallas_path="wavefront")
    b.realize()
    assert b._pallas_path == "wavefront"
    assert b._wavefront_m >= 2
    a.step(5)
    b.step(5)  # 5 = 2 macros of m=2 + rem 1, or 1 macro of m>=3 + rem
    np.testing.assert_allclose(a.temperature(), b.temperature(), rtol=1e-6)


def test_wavefront_bit_exact_vs_wrap_single_device():
    """At mesh [1,1,1] the self-permuted shell is the periodic wrap, and the
    wavefront kernel's summation order matches the wrap kernel's — the two
    paths must agree bitwise."""
    dev = jax.devices()[:1]
    a = Jacobi3D(20, 18, 22, kernel_impl="pallas", interpret=True, devices=dev,
                 temporal_k=3)
    a.realize()
    assert a._pallas_path == "wrap"
    b = Jacobi3D(20, 18, 22, kernel_impl="pallas", interpret=True, devices=dev,
                 pallas_path="wavefront", temporal_k=3)
    b.realize()
    assert b._pallas_path == "wavefront" and b._wavefront_m == 3
    a.step(6)
    b.step(6)
    np.testing.assert_array_equal(a.temperature(), b.temperature())


def test_auto_routes_multidevice_to_wavefront():
    """Even multi-device sizes default to the temporally-blocked wavefront
    (probe11: 1.8x the slab route on hardware); uneven falls back to shell."""
    m = Jacobi3D(24, 24, 24, kernel_impl="pallas", interpret=True)
    m.realize()
    assert m._pallas_path == "wavefront" and m._wavefront_m >= 2
    u = Jacobi3D(15, 16, 16, kernel_impl="pallas", interpret=True)
    u.realize()
    assert u._pallas_path == "shell"


def test_slab_forced_rejects_unaligned_x_on_tpu(monkeypatch):
    """Forced slab with interpret=False must reject a non-128-aligned shard
    x-extent (the z-column dynamic rotate limit, probe11b)."""
    m = Jacobi3D(24, 24, 24, kernel_impl="pallas", interpret=False,
                 pallas_path="slab")
    with pytest.raises(ValueError, match="128-aligned"):
        m.realize()


def test_wavefront_z_ring_matches_jnp(monkeypatch):
    """The z-RING layout (lane-aligned shard z interior: z shell absent from
    HBM, halo segments ring-wrapped in the VMEM working plane) must equal
    the XLA formulation exactly up to fusion ulp."""
    monkeypatch.delenv("STENCIL_Z_RING", raising=False)
    devs = jax.devices()[:2]

    def mk(**kw):
        m = Jacobi3D(16, 16, 128, devices=devs, **kw)
        m.dd.set_partition(2, 1, 1)  # keep the z axis whole (shard z = 128)
        m.realize()
        return m

    a = mk()
    b = mk(kernel_impl="pallas", pallas_path="wavefront", temporal_k=2,
           interpret=True)
    assert b._wavefront_z_slabs and b._wavefront_z_ring
    a.step(5)
    b.step(5)  # 2 macros + depth-1 remainder
    np.testing.assert_allclose(a.temperature(), b.temperature(),
                               rtol=1e-6, atol=1e-6)

    # and the env escape hatch restores the padded layout, same values
    monkeypatch.setenv("STENCIL_Z_RING", "0")
    c = mk(kernel_impl="pallas", pallas_path="wavefront", temporal_k=2,
           interpret=True)
    assert c._wavefront_z_slabs and not c._wavefront_z_ring
    c.step(5)
    np.testing.assert_allclose(b.temperature(), c.temperature(),
                               rtol=1e-6, atol=1e-6)


def test_wavefront_accepts_uneven_on_plain_variant():
    """Padded sizes run the wavefront's PLAIN kernel variant (full-speed
    uneven support, partition.hpp:83-114 parity); see test_uneven.py for the
    gold numerics."""
    m = Jacobi3D(15, 16, 16, kernel_impl="pallas", interpret=True,
                 pallas_path="wavefront")
    m.realize()
    assert m._pallas_path == "wavefront"
    assert not m._wavefront_z_slabs


def test_bf16_wrap_and_wavefront_paths():
    """bf16 quantities run the temporal fast paths.  This pins
    INTERPRET-mode parity only (blocked == plain at the same dtype,
    wavefront == wrap); the compiled branch — Mosaic rotates upcast narrow
    floats to f32 and the level sum accumulates in f32 — is exercised on
    hardware (512^3 bf16 wrap k=6 at 108 Gcells/s), not in CI."""
    import jax.numpy as jnp

    from stencil_tpu.ops.jacobi_pallas import jacobi_wrap_step

    rng = np.random.default_rng(11)
    b0 = jnp.asarray(rng.random((12, 16, 16)), jnp.bfloat16)
    ref = jacobi_wrap_step(jacobi_wrap_step(b0, interpret=True), interpret=True)
    got = jacobi_wrap_step(b0, interpret=True, k=2)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))

    dev = jax.devices()[:1]
    a = Jacobi3D(20, 18, 22, kernel_impl="pallas", interpret=True, devices=dev,
                 temporal_k=3, dtype=jnp.bfloat16)
    a.realize()
    b = Jacobi3D(20, 18, 22, kernel_impl="pallas", interpret=True, devices=dev,
                 pallas_path="wavefront", temporal_k=3, dtype=jnp.bfloat16)
    b.realize()
    a.step(6)
    b.step(6)
    np.testing.assert_array_equal(a.temperature(), b.temperature())


def test_choose_temporal_k():
    from stencil_tpu.ops.jacobi_pallas import choose_temporal_k

    # 100 MB budget fits the plateau cap (_WRAP_MAX_K) at 512^3
    assert choose_temporal_k((512, 512, 512), 4) == 16
    assert choose_temporal_k((4, 64, 64), 4) == 2  # X//2 caps
    assert choose_temporal_k((2, 64, 64), 4) == 1
    # budget caps: huge planes leave no VMEM for the ring
    assert choose_temporal_k((512, 2048, 2048), 4) == 1
    assert choose_temporal_k((512, 128, 128), 4, requested=2) == 2
    with pytest.raises(ValueError):
        choose_temporal_k((4, 64, 64), 4, requested=3)
    # the env override restores the r04 16 MB default-budget calibration
    import os

    prior = os.environ.get("STENCIL_VMEM_LIMIT_BYTES")
    os.environ["STENCIL_VMEM_LIMIT_BYTES"] = "16000000"
    try:
        assert choose_temporal_k((512, 512, 512), 4) == 3
    finally:
        if prior is None:
            del os.environ["STENCIL_VMEM_LIMIT_BYTES"]
        else:
            os.environ["STENCIL_VMEM_LIMIT_BYTES"] = prior


def test_wrap_fast_path_matches_jnp_single_device():
    """Single-device pallas uses the wrap-in-kernel path (no shell reads, no
    exchange); must equal the generic make_step formulation exactly."""
    dev = jax.devices()[:1]
    a = Jacobi3D(26, 24, 22, devices=dev)
    a.realize()
    b = Jacobi3D(26, 24, 22, kernel_impl="pallas", interpret=True, devices=dev)
    b.realize()
    assert b.dd.num_subdomains() == 1
    a.step(5)
    b.step(5)
    np.testing.assert_allclose(a.temperature(), b.temperature(), rtol=1e-6)


# -- ISSUE 40: the z-halo patch works inside the lane tiles that hold the halo --


def parent_patch_z_halo(plane, zst, s, lo_at, hi_at, roll=None):
    """The staging all three z-slab kernels made before ISSUE 40, kept here as
    the oracle: ``2s`` compare + select pairs over the WHOLE plane."""
    col = jax.lax.broadcasted_iota(jnp.int32, plane.shape, 1)
    for j in range(s):
        plane = jnp.where(col == lo_at + j, zst[:, j][:, None], plane)
        plane = jnp.where(col == hi_at + j, zst[:, s + j][:, None], plane)
    return plane


def _plane_and_slab(yr, width, s, seed=0):
    """A plane and a transposed slab block with no value in common, so a
    column that lands in the wrong lane -- or nowhere -- shows."""
    rng = np.random.default_rng(seed)
    plane = jnp.asarray(rng.random((yr, width), dtype=np.float32))
    zst = jnp.asarray(10.0 + rng.random((yr, 2 * s), dtype=np.float32))
    return plane, zst


@pytest.mark.parametrize("zi", [128, 512])
@pytest.mark.parametrize("s", [1, 3, 16, 64])
def test_patch_z_halo_builds_the_ring_tile(s, zi):
    """The z-ring working plane -- a (Yr, 128) ring tile built on its own in
    front of the interior lanes -- is, bitwise and in every lane, the parent's
    whole-plane pad + ``2s`` selects: hi halo in lanes [0, s), lo halo in
    [128 - s, 128), the dead lanes between them zero."""
    from stencil_tpu.ops import jacobi_pallas as jp

    interior, zst = _plane_and_slab(24, zi, s, seed=s + zi)
    want = parent_patch_z_halo(
        jnp.pad(interior, ((0, 0), (jp._ZRING_OFF, 0))), zst, s, jp._ZRING_OFF - s, 0
    )
    assert jp.z_halo_patch_form(jp._ZRING_OFF, s) == "tile"
    tile = jp.patch_z_halo(
        jnp.zeros((24, jp._ZRING_OFF), jnp.float32), zst, s, jp._ZRING_OFF - s, 0,
        jp._make_roll(True),
    )
    got = jnp.concatenate([tile, interior], axis=1)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(got[:, :s]), np.asarray(zst[:, s:]))
    np.testing.assert_array_equal(np.asarray(got[:, 128 - s : 128]), np.asarray(zst[:, :s]))


@pytest.mark.parametrize("zv", [518, 514, 640], ids=["one-tile", "straddles-512", "last-lanes"])
@pytest.mark.parametrize("s", [1, 3, 16, 64])
def test_patch_z_halo_patches_a_lane_padded_plane_in_its_tiles(s, zv):
    """The shell-layout plane (lo halo in lanes [0, s), hi halo in
    [zv - s, zv), dead lanes behind) on a 640-wide plane: the hi halo inside
    ONE lane tile (zv = 518: lanes 515..517 of tile 4 at s = 3), STRADDLING a
    multiple of 128 (zv = 514: lanes 511..513) and at the plane's very end --
    bitwise the parent's whole-plane selects, every untouched lane as it was."""
    from stencil_tpu.ops import jacobi_pallas as jp

    plane, zst = _plane_and_slab(22, 640, s, seed=7 * s + zv)
    assert jp.z_halo_patch_form(640, s) == "tile"
    got = jp.patch_z_halo(plane, zst, s, 0, zv - s, jp._make_roll(True))
    want = parent_patch_z_halo(plane, zst, s, 0, zv - s)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(got[:, zv - s : zv]), np.asarray(zst[:, s:]))


@pytest.mark.parametrize("width,s", [(132, 2), (26, 1), (518, 3), (640, 65)],
                         ids=["132x2", "26x1", "518x3", "slab-wider-than-a-tile"])
def test_patch_z_halo_keeps_the_whole_plane_form_off_the_lane_tiling(width, s, monkeypatch):
    """A plane that is not whole lane tiles (the shell kernel on an unpadded
    block), or a slab block wider than one tile, takes the parent's
    whole-plane form: the rule reads the static shapes alone, and that form
    never slices a tile or rotates."""
    from stencil_tpu.ops import jacobi_pallas as jp

    plane, zst = _plane_and_slab(20, width, s, seed=width)
    assert jp.z_halo_patch_form(width, s) == "plane"

    def no_roll(*a):
        raise AssertionError("the whole-plane form rotates nothing")

    got = jp.patch_z_halo(plane, zst, s, 0, width - s, no_roll)
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(parent_patch_z_halo(plane, zst, s, 0, width - s))
    )


def _zring_case(m, s, seed=3):
    """Operands of ``jacobi_zring_wavefront_step`` on a (2s + 8)^2 x 128
    shard of a 2 x 2 x 1 mesh, the spheres inside the domain."""
    from stencil_tpu.ops import jacobi_pallas as jp

    n, zi = 2 * s + 8, 128
    xr = yr = n + 2 * s
    gsize = (2 * n, 2 * n, zi)
    rng = np.random.default_rng(seed)
    raw = jnp.asarray(rng.random((xr, yr, zi), dtype=np.float32))
    zs = jnp.asarray(rng.random((xr, 2 * s, yr), dtype=np.float32))
    origin = jnp.array([n, 0, 0], jnp.int32)
    d2 = jp.pack_d2(jp.zring_dist2_plane(origin[1] - s, origin[2], s, yr, zi, gsize), gsize)
    return raw, origin, d2, gsize, zs


@pytest.mark.parametrize("m,s", [(1, 1), (3, 3), (16, 16), (2, 5)], ids=["m1", "m3", "m16", "m2-of-shell-5"])
def test_zring_kernel_returns_the_parents_bytes(m, s, monkeypatch):
    """``jacobi_zring_wavefront_step`` with the tile-form patch returns, on
    every plane it writes, the block AND the outgoing slabs it returned with
    the parent's whole-plane staging (the oracle patched in for the helper)."""
    from stencil_tpu.ops import jacobi_pallas as jp

    raw, origin, d2, gsize, zs = _zring_case(m, s)

    def run():
        out, zout = jp.jacobi_zring_wavefront_step(
            raw, m, origin, d2, gsize, zs, interior_offset=s, interpret=True
        )
        written = raw.shape[0] - m  # the un-aliased call leaves the last m planes unwritten
        return np.asarray(out[:written]), np.asarray(zout[:written])

    ours = run()
    monkeypatch.setattr(jp, "patch_z_halo", parent_patch_z_halo)
    parents = run()
    np.testing.assert_array_equal(ours[0], parents[0])
    np.testing.assert_array_equal(ours[1], parents[1])
    assert not np.array_equal(ours[0], np.asarray(raw[: ours[0].shape[0]]))


@pytest.mark.parametrize("zr,form", [(128, "tile"), (28, "plane")], ids=["lane-padded", "unpadded"])
def test_shell_kernel_returns_the_parents_bytes(zr, form, monkeypatch):
    """``jacobi_shell_wavefront_step`` in z-slab mode, on a lane-padded plane
    (the tile form) and on an unpadded one (the whole-plane form), against the
    parent's staging: the block and the outgoing slabs, bitwise."""
    from stencil_tpu.ops import jacobi_pallas as jp

    m = s = 3
    n, zv = 22, 28
    xr = yr = n + 2 * s
    gsize = (2 * n, 2 * n, n)
    rng = np.random.default_rng(11)
    raw = jnp.asarray(rng.random((xr, yr, zr), dtype=np.float32))
    zs = jnp.asarray(rng.random((xr, 2 * s, yr), dtype=np.float32))
    origin = jnp.array([0, n, 0], jnp.int32)
    d2 = jp.pack_d2(jp.yz_dist2_plane(origin[1] - s, origin[2] - s, (yr, zr), gsize), gsize)
    assert jp.z_halo_patch_form(zr, s) == form

    def run():
        out, zout = jp.jacobi_shell_wavefront_step(
            raw, m, origin, d2, gsize, interior_offset=s, interpret=True, alias=False,
            z_slabs=zs, z_valid=zv,
        )
        return np.asarray(out[: xr - m]), np.asarray(zout[: xr - m])

    ours = run()
    monkeypatch.setattr(jp, "patch_z_halo", parent_patch_z_halo)
    parents = run()
    np.testing.assert_array_equal(ours[0], parents[0])
    np.testing.assert_array_equal(ours[1], parents[1])
