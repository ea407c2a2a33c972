"""``dd.exchange()`` against the plain reference
(``stencil_tpu/models/exchange_reference.py``) on seeded RANDOM fields, at
sizes the mesh does not divide: every owned cell of every shard equal,
exactly.  4 and 8 CPU devices; meshes [2,2,1], [2,2,2], [4,1,1]; uneven on
one, two and three axes; radius 1, 3 and asymmetric; one and four
quantities; f32 and bf16 storage; the blend kernels interpreted and off."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from stencil_tpu.core.radius import Radius
from stencil_tpu.domain import DistributedDomain
from stencil_tpu.models import exchange_reference as xref

#: (mesh, global size, axes the mesh splits unevenly)
GEOMETRIES = [
    ((2, 2, 1), (23, 23, 23), "xy"),
    ((2, 2, 2), (23, 23, 23), "xyz"),
    ((4, 1, 1), (23, 23, 23), "x"),
    ((2, 2, 1), (29, 23, 31), "xy"),
    ((2, 2, 2), (29, 23, 31), "xyz"),
    ((4, 1, 1), (29, 23, 31), "x"),
    ((2, 2, 2), (24, 23, 32), "y"),
]


def _asymmetric() -> Radius:
    """Face radii that differ by side and by axis (the shell is allocated
    from them, and the three sweeps fill all of it, edges and corners too)."""
    return Radius.from_dict({(1, 0, 0): 2, (-1, 0, 0): 1, (0, 1, 0): 1, (0, -1, 0): 3,
                             (0, 0, 1): 2, (0, 0, -1): 2})


RADII = {"r1": lambda: Radius.constant(1), "r3": lambda: Radius.constant(3), "asym": _asymmetric}
#: (quantities, storage)
LOADS = [(1, "native"), (4, "native"), (1, "bf16"), (4, "bf16")]


def _domain(mesh, size, radius, quantities, storage):
    n_dev = int(np.prod(mesh))
    dd = DistributedDomain(*size)
    dd.set_radius(radius)
    dd.set_devices(jax.devices()[:n_dev])
    dd.set_partition(*mesh)
    hs = [dd.add_data(f"q{i}", dtype=jnp.float32) for i in range(quantities)]
    if storage == "bf16":
        dd.set_storage("bf16")
    dd.realize()
    return dd, hs


def _cases():
    """Every geometry x radius x load with the blend kernels off (the CPU's
    own path); with them interpreted (the chip's path, seconds a case) every
    geometry at the two radii that differ in kind, on the lightest and the
    heaviest load."""
    for mesh, size, uneven in GEOMETRIES:
        geo = "x".join(map(str, mesh)) + "-" + "x".join(map(str, size))
        for radius in sorted(RADII):
            for q, storage in LOADS:
                yield pytest.param(mesh, size, uneven, radius, q, storage, "blend_off",
                                   id=f"{geo}-{radius}-{q}q-{storage}-blend_off")
                if radius != "r1" and (q, storage) in ((1, "native"), (4, "bf16")):
                    yield pytest.param(mesh, size, uneven, radius, q, storage, "blend_interpreted",
                                       id=f"{geo}-{radius}-{q}q-{storage}-blend_interpreted")


@pytest.mark.parametrize("mesh,size,uneven,radius,quantities,storage,blend", list(_cases()))
def test_every_owned_cell_equals_the_reference(monkeypatch, mesh, size, uneven, radius, quantities, storage, blend):
    monkeypatch.setenv("STENCIL_HALO_BLEND", "1" if blend == "blend_interpreted" else "0")
    rad = RADII[radius]()
    dd, hs = _domain(mesh, size, rad, quantities, storage)
    assert dd._uneven_axes == uneven and tuple(dd.mesh_dim()) == mesh
    lo, hi = tuple(rad.lo()), tuple(rad.hi())
    assert tuple(v is not None for v in dd.valid_last()) == tuple(a in uneven for a in "xyz")
    rng = np.random.default_rng(hash((mesh, size, radius, quantities)) % 2**32)
    mask = xref.owned_mask(size, mesh, lo, hi)
    assert mask.sum() == xref.pad_cells(size, mesh, lo, hi)[1]
    fields = [rng.standard_normal(size).astype(np.float32) for _ in hs]
    for h, f in zip(hs, fields):
        dd.set_quantity(h, f)
    dd.exchange()
    dd.exchange()  # idempotent on a filled domain: pad cells must not leak back
    for h, f in zip(hs, fields):
        stored = np.asarray(jnp.asarray(f).astype(dd.field_dtype(h)).astype(jnp.float32))
        want = xref.exchanged_blocks(stored, mesh, lo, hi)
        got = xref.split_blocks(np.asarray(jax.device_get(dd.get_curr(h))).astype(np.float32), mesh)
        assert got.shape == want.shape
        bad = np.argwhere(mask & (got != want))
        assert bad.size == 0, f"{len(bad)} owned cells differ, first (shard, raw cell): {bad[:3].tolist()}"


def test_a_last_shard_narrower_than_the_radius_is_refused():
    """22 cells over 4 shards: ceil = 6, the last shard would own 4 - under a
    radius of 5 its halo would have to come from two shards back."""
    dd = DistributedDomain(22, 16, 16)
    dd.set_radius(Radius.constant(5))
    dd.set_devices(jax.devices()[:4])
    dd.set_partition(4, 1, 1)
    dd.add_data("q")
    with pytest.raises(ValueError, match="smaller than radius shell"):
        dd.realize()
    dd = DistributedDomain(10, 16, 16)  # (8-1) x ceil(10/8) = 14 >= 10: an empty last shard
    dd.set_radius(Radius.constant(1))
    dd.set_partition(8, 1, 1)
    dd.add_data("q")
    with pytest.raises(ValueError, match="does not fit in one trailing shard"):
        dd.realize()


def test_an_even_geometry_comparison_counts_exactly_the_pad_cells():
    """ISSUE 31: the benchmark's even-shard ``ripple_mismatches`` read 8,236
    on four quantities of 23^3 over mesh [2,2,1] at radius 3, an uneven-aware
    comparison of the same arrays 0.  As arithmetic: per quantity the raw
    cells no shard owns, 2 x 522 + 2 x 522 - 29 = 2,059; and on the device,
    with a random field (no pad cell holds what that comparison expects)."""
    size, mesh, r = (23, 23, 23), (2, 2, 1), (3, 3, 3)
    pad, owned = xref.pad_cells(size, mesh, r, r)
    plane = 18 * 29  # one padded plane of a last shard: raw y (or x) x raw z
    assert pad == 2 * plane + 2 * plane - 29 == 2059 and 4 * pad == 8236
    assert owned == (23 + 12) * (23 + 12) * (23 + 6)
    dd, (h,) = _domain(mesh, size, Radius.constant(3), 1, "native")
    f = np.random.default_rng(31).uniform(1.0, 2.0, size).astype(np.float32)
    dd.set_quantity(h, f)
    dd.exchange()
    got = xref.split_blocks(np.asarray(jax.device_get(dd.get_curr(h))), mesh)
    # the even-geometry expectation (benchmark/harness/reference.py
    # ripple_mismatches): EVERY raw cell k of shard i stands for global cell
    # (i x n - r + k) mod size -- true of the owned cells, not of the padding
    n = [xref.shard_width(size[a], mesh[a]) for a in range(3)]
    even = np.zeros_like(got)
    for idx in np.ndindex(*mesh):
        c = [(idx[a] * n[a] - r[a] + np.arange(got.shape[3 + a])) % size[a] for a in range(3)]
        even[idx] = f[np.ix_(*c)]
    assert int((got != even).sum()) == pad
    mask = xref.owned_mask(size, mesh, r, r)
    assert not (mask & (got != even)).any() and (got != even)[~mask].all()
    assert not (mask & (got != xref.exchanged_blocks(f, mesh, r, r))).any()


def test_a_pinned_layout_survives_the_persistent_cache(monkeypatch, tmp_path):
    """Where the backend's default layout of a shard is not row-major (on a
    TPU: 602 x 602 x 1197 f32 goes y-minor) the domain pins its arrays and
    every program that returns them.  Here the rule is turned round -- the
    CPU's default IS row-major, so pin z-before-y -- and the persistent cache
    is made to keep every program, as the benchmark does: jax 0.9 would serve
    the second fill (the same program: a hit) without its layouts and hand
    back transposed data; a pinning domain turns the cache off instead."""
    from jax.experimental.compilation_cache import compilation_cache
    from jax.experimental.layout import Format, Layout

    import stencil_tpu.domain as domain

    monkeypatch.setattr(
        domain, "_row_major_format", lambda sh, shape, dt: Format(Layout(major_to_minor=(0, 2, 1)), sh))
    before = {k: getattr(jax.config, k) for k in (
        "jax_enable_compilation_cache", "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes", "jax_compilation_cache_dir")}
    try:
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
        size, mesh, r = (23, 23, 23), (2, 2, 1), (3, 3, 3)
        dd, hs = _domain(mesh, size, Radius.constant(3), 2, "native")
        assert sorted(dd._pinned) == ["q0", "q1"] and not jax.config.jax_enable_compilation_cache
        field = lambda x, y, z, q: (x * 1009 + y * 31 + z + q * 7919).astype(jnp.float32)  # noqa: E731
        for _ in range(2):  # the second round re-fills through programs seen before
            for q, h in enumerate(hs):
                dd.init_by_coords(h, field, args=(np.int32(q),))
            dd.exchange()
        x, y, z = np.meshgrid(*(np.arange(n) for n in size), indexing="ij")
        mask = xref.owned_mask(size, mesh, r, r)
        for q, h in enumerate(hs):
            arr = dd.get_curr(h)
            assert tuple(arr.format.layout.major_to_minor) == (0, 2, 1)
            want = xref.exchanged_blocks((x * 1009 + y * 31 + z + q * 7919).astype(np.float32), mesh, r, r)
            assert not (mask & (xref.split_blocks(np.asarray(arr), mesh) != want)).any()
        assert not list(tmp_path.iterdir())  # nothing was written: nothing can be served wrong
    finally:
        for k, v in before.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
