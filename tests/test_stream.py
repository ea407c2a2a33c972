"""Plane-streaming engine (ops/stream.py): the SAME StepKernel runs under
make_step(engine="xla") and make_step(engine="stream") with matching results.

This is the user-kernel model of the reference (apps write kernels through
Accessor, accessor.hpp:13-40; the framework makes them fast) — the engine
proof is that Jacobi3D/AstarothSim's kernels, VERBATIM, and new user-written
stencils all agree with the XLA route in interpret mode (1e-6, the ulp slack
fused-vs-separate XLA graphs carry on CPU), across plane and wavefront
routes, meshes, and field counts.

Ground truth is always a mult=1 XLA-engine domain stepped once per
iteration; the stream domain may carry a wider shell (halo multiplier or a
wide declared radius) that the engine turns into temporal wavefronts.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from stencil_tpu.core.radius import Radius
from stencil_tpu.domain import DistributedDomain
from stencil_tpu.models.astaroth import AstarothSim
from stencil_tpu.models.jacobi import Jacobi3D
from stencil_tpu.ops import stream_plan as sp
from stencil_tpu.ops import stream_pass as spass

TOL = dict(rtol=1e-6, atol=1e-6)


def _mk(x, y, z, radius, names, devices, mult=1, init=None, dtype=jnp.float32):
    dd = DistributedDomain(x, y, z)
    dd.set_radius(radius)
    dd.set_devices(devices)
    if mult != 1:
        dd.set_halo_multiplier(mult)
    hs = [dd.add_data(n, dtype=dtype) for n in names]
    dd.realize()
    for i, h in enumerate(hs):
        f = init or (lambda x_, y_, z_, i=i: jnp.sin(0.13 * (x_ + 2 * y_ + 3 * z_) + i))
        dd.init_by_coords(h, f)
    return dd, hs


def _run_both(mk_ref, mk_stream, kernel, steps, x_radius=None):
    """Run the XLA engine (per-step ground truth) and the stream engine the
    same number of ITERATIONS; return paired host fields + the stream step."""
    dd_a, hs_a = mk_ref()
    dd_b, hs_b = mk_stream()
    step_a = dd_a.make_step(kernel, overlap=False)
    step_b = dd_b.make_step(kernel, engine="stream", x_radius=x_radius, interpret=True)
    assert dd_a.halo_multiplier() == 1  # ground truth advances 1 iter/step
    dd_a.run_step(step_a, steps)
    dd_b.run_step(step_b, steps)
    outs = []
    for ha, hb in zip(hs_a, hs_b):
        outs.append((dd_a.quantity_to_host(ha), dd_b.quantity_to_host(hb)))
    return outs, step_b


def mean6_kernel(views, info):
    out = {}
    for name, src in views.items():
        out[name] = (
            src.sh(-1, 0, 0)
            + src.sh(0, -1, 0)
            + src.sh(0, 0, -1)
            + src.sh(1, 0, 0)
            + src.sh(0, 1, 0)
            + src.sh(0, 0, 1)
        ) / 6.0
    return out


def stencil27_kernel(views, info):
    """27-point weighted stencil — a NEW user stencil written only against
    the public kernel API (the engine's 'users are fast by default' proof)."""
    src = views["u"]
    acc = 0.0
    for dx in (-1, 0, 1):
        for dy in (-1, 0, 1):
            for dz in (-1, 0, 1):
                w = 1.0 / (2.0 ** (abs(dx) + abs(dy) + abs(dz)))
                acc = acc + w * src.sh(dx, dy, dz)
    return {"u": acc / 7.0}


def vc_diffusion_kernel(views, info):
    """Variable-coefficient diffusion: the coefficient is a second FIELD the
    kernel reads but never updates (pass-through under both engines)."""
    u, c = views["u"], views["c"]
    lap = (
        u.sh(-1, 0, 0) + u.sh(1, 0, 0)
        + u.sh(0, -1, 0) + u.sh(0, 1, 0)
        + u.sh(0, 0, -1) + u.sh(0, 0, 1)
        - 6.0 * u.center()
    )
    return {"u": u.center() + c.center() * lap}


def forced_kernel(views, info):
    """Coordinate-dependent forcing — exercises info.coords() broadcasting
    under both engines (scalar x / column y / row z on the stream route)."""
    src = views["u"]
    cx, cy, cz = info.coords()
    g = info.global_size
    val = (src.sh(1, 0, 0) + src.sh(-1, 0, 0) + src.sh(0, 1, 0) + src.sh(0, -1, 0)) / 4.0
    d2 = (cx - g.x // 2) ** 2 + (cy - g.y // 2) ** 2 + (cz - g.z // 2) ** 2
    return {"u": jnp.where(d2 < 9, 1.0, val).astype(src.center().dtype)}


def test_stream_wrap_route_single_device():
    """One device: the engine folds the periodic wrap into the kernel (no
    shell, no exchange, deepest temporal blocking) — jacobi_wrap_step's
    structure for USER kernels."""
    dev = jax.devices()[:1]
    r1 = Radius.constant(1)
    outs, step = _run_both(
        lambda: _mk(12, 10, 11, r1, ["u"], dev),
        lambda: _mk(12, 10, 11, r1, ["u"], dev),
        mean6_kernel, 3,
    )
    assert step._stream_plan["route"] == "wrap"
    assert step._stream_plan["m"] >= 2
    for a, b in outs:
        np.testing.assert_allclose(a, b, **TOL)


def test_stream_plane_route_single_device_forced():
    dev = jax.devices()[:1]
    r1 = Radius.constant(1)
    dd_a, hs_a = _mk(12, 10, 11, r1, ["u"], dev)
    dd_b, hs_b = _mk(12, 10, 11, r1, ["u"], dev)
    step_a = dd_a.make_step(mean6_kernel, overlap=False)
    step_b = dd_b.make_step(mean6_kernel, engine="stream", stream_path="plane",
                            interpret=True)
    assert step_b._stream_plan["route"] == "plane"
    dd_a.run_step(step_a, 3)
    dd_b.run_step(step_b, 3)
    np.testing.assert_allclose(
        dd_a.quantity_to_host(hs_a[0]), dd_b.quantity_to_host(hs_b[0]), **TOL
    )


def test_stream_wrap_route_forcing_and_multifield():
    """Wrap route with coordinate forcing and a pass-through second field;
    steps not a multiple of k exercise the remainder dispatch."""
    dev = jax.devices()[:1]
    r1 = Radius.constant(1)
    outs, step = _run_both(
        lambda: _mk(16, 16, 16, r1, ["u", "c"], dev),
        lambda: _mk(16, 16, 16, r1, ["u", "c"], dev),
        vc_diffusion_kernel, 5,
    )
    assert step._stream_plan["route"] == "wrap"
    (ua, ub), (ca, cb) = outs
    np.testing.assert_allclose(ua, ub, **TOL)
    np.testing.assert_array_equal(ca, cb)

    outs, _ = _run_both(
        lambda: _mk(16, 16, 16, r1, ["u"], dev),
        lambda: _mk(16, 16, 16, r1, ["u"], dev),
        forced_kernel, 5,
    )
    for a, b in outs:
        np.testing.assert_allclose(a, b, **TOL)


def test_stream_plane_route_multi_device_multi_quantity():
    devs = jax.devices()[:8]
    r1 = Radius.constant(1)
    outs, _ = _run_both(
        lambda: _mk(16, 12, 8, r1, ["u", "v"], devs),
        lambda: _mk(16, 12, 8, r1, ["u", "v"], devs),
        mean6_kernel, 3,
    )
    for a, b in outs:
        np.testing.assert_allclose(a, b, **TOL)


def test_stream_wavefront_route():
    devs = jax.devices()[:8]
    r1 = Radius.constant(1)
    outs, step = _run_both(
        lambda: _mk(24, 24, 24, r1, ["u"], devs),
        lambda: _mk(24, 24, 24, r1, ["u"], devs, mult=3),
        mean6_kernel,
        7,  # 2 macros + remainder 1
    )
    assert step._stream_plan["route"] == "wavefront"
    assert step._stream_plan["m"] == 3
    for a, b in outs:
        np.testing.assert_allclose(a, b, **TOL)


def _mean6_roll_reference(a, steps):
    """``steps`` mean-of-6 updates of the whole periodic array by ``jnp.roll``,
    in ``mean6_kernel``'s own order of taps."""
    for _ in range(steps):
        a = (
            jnp.roll(a, 1, 0) + jnp.roll(a, 1, 1) + jnp.roll(a, 1, 2)
            + jnp.roll(a, -1, 0) + jnp.roll(a, -1, 1) + jnp.roll(a, -1, 2)
        ) / 6.0
    return a


#: (id, extent, devices, halo multiplier, stream_path, route, depth): every
#: geometry x route the removed matrix-unit pins ran their roll + add side at
#: (16^3 and 24^3 over 8 devices at multipliers 2 and 3, one device), plus
#: the plane route and a padded last shard
_MEAN6_ROUTES = [
    ("plane-8dev", (16, 16, 16), 8, 1, "auto", "plane", 1),
    ("plane-forced-1dev", (16, 16, 16), 1, 1, "plane", "plane", 1),
    ("wavefront-m2", (16, 16, 16), 8, 2, "auto", "wavefront", 2),
    ("wavefront-m3", (16, 16, 16), 8, 3, "auto", "wavefront", 3),
    ("wavefront-m3-24", (24, 24, 24), 8, 3, "auto", "wavefront", 3),
    ("wavefront-m2-uneven", (17, 17, 17), 8, 2, "auto", "wavefront", 2),
    ("wrap-1dev", (16, 16, 16), 1, 1, "auto", "wrap", 8),
]


@pytest.mark.parametrize("storage", ["native", "bf16"])
@pytest.mark.parametrize(
    "extent,n_dev,mult,path,route,depth",
    [c[1:] for c in _MEAN6_ROUTES], ids=[c[0] for c in _MEAN6_ROUTES],
)
def test_stream_mean6_routes_match_roll_reference(
    extent, n_dev, mult, path, route, depth, storage
):
    """The mean-of-6 kernel on every stream route, native and bf16 storage,
    against ``jnp.roll`` on the global array: native to a few reordered
    roundings (a fused m-level graph may differ from m dispatches in the last
    ulp per level under interpret), bf16 storage to its analytic bound of one
    rounding per stored pass."""
    from ulp import assert_bf16_storage_close, assert_reassociation_close

    steps = 4
    dd = DistributedDomain(*extent)
    dd.set_radius(Radius.constant(1))
    dd.set_devices(jax.devices()[:n_dev])
    if mult != 1:
        dd.set_halo_multiplier(mult)
    h = dd.add_data("u")
    if storage == "bf16":
        dd.set_storage("bf16")
    dd.realize()
    dd.init_by_coords(h, lambda x, y, z: jnp.sin(0.13 * (x + 2 * y + 3 * z)))
    x, y, z = np.meshgrid(*(np.arange(n) for n in extent), indexing="ij")
    start = jnp.sin(0.13 * jnp.asarray(x + 2 * y + 3 * z)).astype(jnp.float32)
    step = dd.make_step(mean6_kernel, engine="stream", stream_path=path, interpret=True)
    plan = step._stream_plan
    assert (plan["route"], plan["m"]) == (route, depth), plan
    assert dd.storage_dtype() == storage
    dd.run_step(step, steps)
    assert step._resilience.descents == []
    got = dd.quantity_to_host(h)
    want = np.asarray(_mean6_roll_reference(start, steps))
    if storage == "bf16":
        # the fill quantizes once, then one downcast per stored pass at most
        assert_bf16_storage_close(got, want, passes=steps + 1, context=route)
    else:
        assert_reassociation_close(
            got, want, rounds=2 * steps, scale=6.0, context=route
        )


def test_stream_wavefront_wide_radius_narrow_reads():
    """Astaroth's pattern: radius-3 shell, distance-1 reads — the engine
    wavefronts m=3 against ONE exchange without a halo multiplier."""
    devs = jax.devices()[:8]
    outs, step = _run_both(
        lambda: _mk(24, 24, 24, Radius.constant(1), ["u"], devs),
        lambda: _mk(24, 24, 24, Radius.constant(3), ["u"], devs),
        mean6_kernel,
        5,
        x_radius=1,
    )
    assert step._stream_plan["route"] == "wavefront"
    assert step._stream_plan["m"] == 3
    for a, b in outs:
        np.testing.assert_allclose(a, b, **TOL)


def test_stream_27point_new_user_stencil():
    devs = jax.devices()[:8]
    r1 = Radius.constant(1)
    outs, _ = _run_both(
        lambda: _mk(16, 16, 16, r1, ["u"], devs),
        lambda: _mk(16, 16, 16, r1, ["u"], devs),
        stencil27_kernel, 4,
    )
    for a, b in outs:
        np.testing.assert_allclose(a, b, **TOL)


def test_stream_27point_wavefront():
    devs = jax.devices()[:8]
    r1 = Radius.constant(1)
    outs, step = _run_both(
        lambda: _mk(24, 24, 24, r1, ["u"], devs),
        lambda: _mk(24, 24, 24, r1, ["u"], devs, mult=2),
        stencil27_kernel,
        4,
    )
    assert step._stream_plan["route"] == "wavefront"
    for a, b in outs:
        np.testing.assert_allclose(a, b, **TOL)


def test_stream_vc_diffusion_passthrough_field():
    devs = jax.devices()[:8]

    def mk():
        dd = DistributedDomain(16, 12, 12)
        dd.set_radius(Radius.constant(1))
        dd.set_devices(devs)
        hu = dd.add_data("u")
        hc = dd.add_data("c")
        dd.realize()
        dd.init_by_coords(hu, lambda x, y, z: jnp.sin(0.3 * x + 0.2 * y + 0.1 * z))
        dd.init_by_coords(hc, lambda x, y, z: 0.05 + 0.01 * jnp.cos(0.2 * (x + y - z)))
        return dd, [hu, hc]

    outs, _ = _run_both(mk, mk, vc_diffusion_kernel, 3)
    (ua, ub), (ca, cb) = outs
    np.testing.assert_allclose(ua, ub, **TOL)
    np.testing.assert_array_equal(ca, cb)  # coefficient untouched by both


def test_stream_coords_forcing():
    devs = jax.devices()[:8]
    r1 = Radius.constant(1)
    outs, _ = _run_both(
        lambda: _mk(16, 16, 16, r1, ["u"], devs),
        lambda: _mk(16, 16, 16, r1, ["u"], devs),
        forced_kernel, 4,
    )
    for a, b in outs:
        np.testing.assert_allclose(a, b, **TOL)


def test_stream_coords_forcing_wavefront():
    """Forcing through shell levels: coords() must be periodic-wrapped so
    intermediate-level shell cells force correctly (they feed valid cells)."""
    devs = jax.devices()[:8]
    r1 = Radius.constant(1)
    outs, _ = _run_both(
        lambda: _mk(24, 24, 24, r1, ["u"], devs),
        lambda: _mk(24, 24, 24, r1, ["u"], devs, mult=3),
        forced_kernel,
        6,
    )
    for a, b in outs:
        np.testing.assert_allclose(a, b, **TOL)


def _jacobi_radius():
    r = Radius.constant(0)
    r.set_face(1)
    return r


def test_stream_jacobi_model_kernel_verbatim():
    """Jacobi3D's OWN kernel under the stream engine equals the XLA route and
    the model's bespoke pallas wavefront path — nothing is lost."""
    devs = jax.devices()[:8]
    n = 24

    model = Jacobi3D(n, n, n, devices=devs)
    model.realize()

    mid = lambda x, y, z: jnp.full((), 0.5) + 0 * (x + y + z)
    dd, hs = _mk(n, n, n, _jacobi_radius(), ["temp"], devs, mult=3, init=mid)
    step = dd.make_step(model._kernel, engine="stream", interpret=True)
    assert step._stream_plan["route"] == "wavefront"
    model.step(5)
    dd.run_step(step, 5)
    np.testing.assert_allclose(
        model.temperature(), dd.quantity_to_host(hs[0]), **TOL
    )

    wf = Jacobi3D(n, n, n, devices=devs, kernel_impl="pallas",
                  pallas_path="wavefront", temporal_k=3, interpret=True)
    wf.realize()
    wf.step(5)
    np.testing.assert_allclose(model.temperature(), wf.temperature(), **TOL)


def test_stream_astaroth_model_kernel_verbatim():
    devs = jax.devices()[:8]
    n = 24
    a = AstarothSim(n, n, n, num_quantities=2, devices=devs)
    a.realize()
    b = AstarothSim(n, n, n, num_quantities=2, devices=devs)
    b.realize()
    step = b.dd.make_step(b._kernel, engine="stream", x_radius=1, interpret=True)
    assert step._stream_plan["route"] == "wavefront"
    a.step(5)
    b.dd.run_step(step, 5)
    for i in range(2):
        np.testing.assert_allclose(
            a.field(i), b.dd.quantity_to_host(b.handles[i]), **TOL
        )


def test_stream_padded_plane_route():
    """Padded (uneven) shards run on the plane route: the exchange blends
    halos at the dynamic valid-width offsets, so the streamed kernel reads
    correct neighbors and pad cells compute garbage nothing consumes."""
    devs = jax.devices()[:8]
    r1 = Radius.constant(1)
    outs, step = _run_both(
        lambda: _mk(15, 13, 15, r1, ["u"], devs),
        lambda: _mk(15, 13, 15, r1, ["u"], devs),
        mean6_kernel, 3,
    )
    assert step._stream_plan["route"] == "plane"
    for a, b in outs:
        np.testing.assert_allclose(a, b, **TOL)


def test_stream_separable_per_field_grouping(monkeypatch):
    """When many fields jointly blow the VMEM model, a separable kernel
    streams per-field at FULL wavefront depth instead of a shallower m."""

    devs = jax.devices()[:8]
    r3 = Radius.constant(3)
    names = ["a", "b", "c", "d"]
    # 5 MB budget: four 24x128-padded-plane rings don't fit jointly at m>=2
    # (12.5 MB modeled) but a single field does (3.1 MB)
    monkeypatch.setenv("STENCIL_VMEM_LIMIT_BYTES", "5000000")
    dd, hs = _mk(24, 24, 24, r3, names, devs)
    step = dd.make_step(
        mean6_kernel, engine="stream", x_radius=1, separable=True, interpret=True
    )
    assert step._stream_plan == {
        "route": "wavefront", "m": 3, "z_slabs": True, "grouping": "per-field",
        "alias": True,  # four fields: the wavefront's static rule, written back
        "overlap": "off", "halo": "array",
        "halo_readers": ("a", "b", "c", "d"),  # the wavefront exchanges every quantity
        "writers": ("a", "b", "c", "d"),  # and writes every one
        "pass_wrap_axes": "",  # the plane route's alone (ISSUE 34)
        "renamed": (),  # as is the rename of a time level (ISSUE 36)
        # what the kernel reads, one trace a group, for the span alone (ISSUE 39)
        "footprint": {"offcentre": 4, "diagonal": 0, "read_sides": 24},
        # the z-slab pass patches its z halo in the lane tiles that hold it,
        # on the lane-padded plane (ISSUE 40), which it makes in VMEM from the
        # 30-lane raw block (ISSUE 41)
        "z_halo_patch": "tile",
        "lane_pad": "vmem",
        # no slab extension is a self-wrap on mesh [2,2,2], nor anywhere with
        # the blend kernels off (ISSUE 56)
        "slab_wrap": "",
        # the account of the wires a macro crosses (ISSUE 49: tests/test_wire_account.py)
        "wire_account": step._stream_plan["wire_account"],
        "wired": step._stream_plan["wire_account"].said()[0],
        "wire_bytes": step._stream_plan["wire_account"].said()[1],
        # ... and the pair of wired axes whose sweeps fly jointly (ISSUE 50)
        "joint": step._stream_plan["wire_account"].joint[0],
    }
    assert step._span_args()["z_halo_patch"] == "tile"
    assert step._span_args()["lane_pad"] == "vmem"
    assert step._span_args()["slab_wrap"] == ""
    monkeypatch.delenv("STENCIL_VMEM_LIMIT_BYTES")
    ref_dd, ref_hs = _mk(24, 24, 24, Radius.constant(1), names, devs)
    ref = ref_dd.make_step(mean6_kernel, overlap=False)
    dd.run_step(step, 5)
    ref_dd.run_step(ref, 5)
    for ha, hb in zip(ref_hs, hs):
        np.testing.assert_allclose(
            ref_dd.quantity_to_host(ha), dd.quantity_to_host(hb), **TOL
        )


def test_stream_runtime_vmem_fallback(monkeypatch):
    """A Mosaic scoped-VMEM OOM at the planned depth steps the wavefront
    down one level and retries instead of crashing (the VMEM model is
    toolchain-calibrated; a compiler upgrade may shift it)."""
    import stencil_tpu.ops.stream as sm

    real_build = sm._build_stream_step
    calls = {"n": 0}

    def fake_build(dd, kernel, r, plan, interp, donate=True, **kw):
        calls["n"] += 1
        if calls["n"] == 1:
            assert plan["m"] == 3

            def boom(curr, steps=1):
                raise RuntimeError(
                    "Ran out of memory in memory space vmem ... "
                    "exceeded scoped vmem limit by 8.59M"
                )

            return boom
        return real_build(dd, kernel, r, plan, interp, donate, **kw)

    monkeypatch.setattr(sm, "_build_stream_step", fake_build)
    devs = jax.devices()[:8]
    dd, hs = _mk(24, 24, 24, Radius.constant(1), ["u"], devs, mult=3)
    step = dd.make_step(mean6_kernel, engine="stream", interpret=True)
    assert step._stream_plan["m"] == 3
    dd.run_step(step, 4)  # first call: fake OOM -> rebuild at m=2 -> runs
    assert step._stream_plan["m"] == 2
    assert calls["n"] == 2

    ref_dd, ref_hs = _mk(24, 24, 24, Radius.constant(1), ["u"], devs)
    ref = ref_dd.make_step(mean6_kernel, overlap=False)
    ref_dd.run_step(ref, 4)
    np.testing.assert_allclose(
        ref_dd.quantity_to_host(ref_hs[0]), dd.quantity_to_host(hs[0]), **TOL
    )


def test_stream_depth_cap():
    """stream_depth caps the temporal depth (compute-heavy kernels multiply
    their VPU work by the depth; the auto planner maximizes it for the
    bandwidth-bound case)."""
    dev = jax.devices()[:1]
    r1 = Radius.constant(1)
    outs, step = _run_both(
        lambda: _mk(16, 16, 16, r1, ["u"], dev),
        lambda: _mk(16, 16, 16, r1, ["u"], dev),
        stencil27_kernel, 5,
    )
    assert step._stream_plan == {
        "route": "wrap", "m": 8, "z_slabs": False, "grouping": "joint",
        "alias": False,  # the wrap pass has no in-place form
        "overlap": "off", "halo": "array",
        "halo_readers": (),  # and no exchange
        "writers": ("u",),
        "pass_wrap_axes": "",
        "renamed": (),
        # the 27-point kernel reads every edge and corner (ISSUE 39)
        "footprint": {"offcentre": 1, "diagonal": 1, "read_sides": 6},
        "macros_per_trip": 2,  # the wrap pass writes fresh results (ISSUE 39)
        "edges": "xla",  # a 16 x 16 interior is no whole vector tile (ISSUE 52)
        # no exchange, no wire (ISSUE 49)
        "wire_account": (0, {}, 1, (0, 0), ("", 0)), "wired": "", "wire_bytes": 0, "joint": "",
    }
    for a, b in outs:  # uncapped wrap vs the XLA ground truth
        np.testing.assert_allclose(a, b, **TOL)
    dd, hs = _mk(16, 16, 16, r1, ["u"], dev)
    capped = dd.make_step(stencil27_kernel, engine="stream", stream_depth=2,
                          interpret=True)
    assert capped._stream_plan["m"] == 2
    dd.run_step(capped, 5)
    # capped wrap vs the XLA ground truth (not just vs its uncapped sibling)
    np.testing.assert_allclose(outs[0][0], dd.quantity_to_host(hs[0]), **TOL)
    with pytest.raises(ValueError, match="stream_depth"):
        dd.make_step(stencil27_kernel, engine="stream", stream_depth=0,
                     interpret=True)


def test_stream_bf16_wavefront():
    """bf16 fields through the engine: rolls upcast to f32 in compiled mode
    (interpret uses jnp.roll directly); parity vs the XLA engine at bf16
    resolution."""
    devs = jax.devices()[:8]
    r1 = Radius.constant(1)
    outs, step = _run_both(
        lambda: _mk(24, 24, 24, r1, ["u"], devs, dtype=jnp.bfloat16),
        lambda: _mk(24, 24, 24, r1, ["u"], devs, mult=2, dtype=jnp.bfloat16),
        mean6_kernel,
        4,
    )
    assert step._stream_plan["route"] == "wavefront"
    for a, b in outs:
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            rtol=2e-2, atol=2e-2,  # bf16 resolution over 4 steps
        )


def test_jacobi_bespoke_vmem_fallback():
    """The bespoke jacobi paths step down on a runtime scoped-VMEM OOM too:
    wrap re-plans at k-1; the wavefront keeps its allocated m-wide shell and
    advances fewer levels per pass."""
    dev = jax.devices()[:1]

    boom = RuntimeError("Ran out of memory in memory space vmem ... exceeded")

    def raise_once(model):
        real = model._step
        state = {"fired": False}

        def wrapped(curr, steps=1):
            if not state["fired"]:
                state["fired"] = True
                raise boom
            return real(curr, steps)

        model._step = wrapped

    m = Jacobi3D(24, 24, 24, devices=dev, kernel_impl="pallas", temporal_k=4,
                 interpret=True)
    m.realize()
    raise_once(m)
    m.step(8)
    assert m._wrap_k == 3
    ref = Jacobi3D(24, 24, 24, devices=dev, kernel_impl="pallas", temporal_k=1,
                   interpret=True)
    ref.realize()
    ref.step(8)
    np.testing.assert_array_equal(ref.temperature(), m.temperature())

    w = Jacobi3D(24, 24, 24, devices=dev, kernel_impl="pallas",
                 pallas_path="wavefront", temporal_k=4, interpret=True)
    w.realize()
    raise_once(w)
    w.step(8)
    assert w._wavefront_depth == 3 and w._wavefront_m == 4
    np.testing.assert_allclose(ref.temperature(), w.temperature(), **TOL)


def test_stream_tiny_budget_degrades_to_plane(monkeypatch):
    """An over-tight env budget degrades the plan to the plane route (and a
    joint 4-field plane pass to per-field) — never a crash."""
    monkeypatch.setenv("STENCIL_VMEM_LIMIT_BYTES", "100000")
    devs = jax.devices()[:8]
    dd, hs = _mk(24, 24, 24, Radius.constant(1), ["a", "b"], devs, mult=3)
    step = dd.make_step(
        mean6_kernel, engine="stream", separable=True, interpret=True
    )
    assert step._stream_plan["route"] == "plane"
    assert step._stream_plan["grouping"] == "per-field"


def test_stream_forced_paths_and_rejects():
    devs = jax.devices()[:8]
    dd = DistributedDomain(15, 15, 15)  # pads over a [2,2,2] mesh
    dd.set_radius(Radius.constant(1))
    dd.set_devices(devs)
    dd.add_data("u")
    dd.set_halo_multiplier(2)
    dd.realize()
    if any(v is not None for v in dd._valid_last):
        # padded: wavefront runs on the PLAIN kernel variant (the z-slab
        # form's static emit slices need even shards)
        step = dd.make_step(
            mean6_kernel, engine="stream", stream_path="wavefront",
            interpret=True,
        )
        assert step._stream_plan["route"] == "wavefront"
        assert not step._stream_plan["z_slabs"]

    # stream_path="plane" forces per-step exchange despite a wide shell
    dd1 = DistributedDomain(16, 16, 16)
    dd1.set_radius(Radius.constant(1))
    dd1.set_devices(devs)
    dd1.add_data("u")
    dd1.set_halo_multiplier(2)
    dd1.realize()
    dd1.init_by_coords(dd1._handles[0], lambda x, y, z: jnp.sin(0.2 * (x + y + z)))
    step = dd1.make_step(mean6_kernel, engine="stream", stream_path="plane",
                         interpret=True)
    assert step._stream_plan["route"] == "plane"

    # N-D component data stays on the XLA engine
    dd2 = DistributedDomain(16, 16, 16)
    dd2.set_radius(Radius.constant(1))
    dd2.set_devices(devs)
    dd2.add_data("v", components=(3,))
    dd2.realize()
    with pytest.raises(ValueError):
        dd2.make_step(mean6_kernel, engine="stream", interpret=True)


# --- the plane route beyond radius 1 (x_radius 2..4, ISSUE 27) ----------------


def star_kernel(r):
    """A (6r+1)-point star reading every distance 1..r on every axis, with
    distinct weights so a wrong offset or ring slot cannot cancel."""

    def kernel(views, info):
        src = views["u"]
        acc = 0.5 * src.center()
        for k in range(1, r + 1):
            w = 1.0 / (12.0 * k)
            acc = acc + w * (
                (src.sh(k, 0, 0) + 0.9 * src.sh(-k, 0, 0))
                + (src.sh(0, k, 0) + 0.8 * src.sh(0, -k, 0))
                + (src.sh(0, 0, k) + 0.7 * src.sh(0, 0, -k))
            )
        return {"u": acc}

    return kernel


@pytest.mark.parametrize("n_dev", [1, 8])
@pytest.mark.parametrize("r", [2, 3, 4])
def test_plane_route_reads_its_whole_halo(r, n_dev):
    """A kernel that reads as far as it exchanges (``x_radius == radius``):
    the plane route's 2r-deep ring, against the XLA engine, on one device
    (every sweep a self-wrap) and on eight (every sweep over the mesh)."""
    devices = jax.devices()[:n_dev]
    mk = lambda: _mk(16, 16, 16, Radius.constant(r), ["u"], devices)  # noqa: E731
    outs, step = _run_both(mk, mk, star_kernel(r), 3, x_radius=r)
    assert step._stream_plan["route"] == "plane", step._stream_plan
    assert step._span_args()["x_radius"] == r
    for a, b in outs:
        np.testing.assert_allclose(a, b, **TOL)


# --- the plane pass in place (ISSUE 28) ---------------------------------------


def coupled_kernel(r):
    """Four coupled quantities in acoustic's shape: ``u`` reads its whole
    halo and every other field's centre, ``v`` takes the old ``u`` (a second
    time level), ``d`` is rewritten from itself, and ``c`` is a coefficient
    the kernel does not return (a pass-through, as ``m`` and ``damp``)."""
    star = star_kernel(r)

    def kernel(views, info):
        u = views["u"]
        new = star(views, info)["u"] * (1.0 + 0.1 * views["c"].center())
        new = new - 0.05 * views["v"].center() + 0.01 * views["d"].center()
        return {"u": new, "v": u.center(), "d": 0.5 * views["d"].center()}

    return kernel


#: (id, radius, quantities, devices, extent, plan overrides, domain set-up)
_INPLACE_CASES = [
    pytest.param(r, nq, n_dev, (16, 16, 16), {}, {}, id=f"r{r}-q{nq}-dev{n_dev}")
    for r in (1, 2, 4)
    for nq in (1, 4)
    for n_dev in (1, 8)
] + [
    pytest.param(1, 1, 8, (15, 13, 15), {}, {}, id="padded"),
    pytest.param(2, 4, 8, (16, 16, 16), {"overlap": "split", "overlap_forced": True}, {},
                 id="split"),
    pytest.param(1, 4, 8, (16, 16, 16), {"halo": "fused", "halo_forced": True},
                 {"exchange_route": "yzpack_xla"}, id="fused"),
    pytest.param(2, 4, 1, (16, 16, 16), {}, {"storage": "bf16"}, id="bf16-storage"),
]


@pytest.mark.parametrize("r,nq,n_dev,extent,plan_kw,dom_kw", _INPLACE_CASES)
def test_plane_pass_in_place_is_bitwise_the_fresh_pass(r, nq, n_dev, extent, plan_kw, dom_kw):
    """The plane route with every pass output aliased onto its input
    (``plan["alias"]``, forced through the plan as the autotuner's candidate
    builds force it) against the same plan un-aliased: bitwise equal on every
    quantity after three steps, over radius, quantity count (one of four a
    pass-through), devices, padded extents, the split schedule, the fused
    halo and bf16 storage.

    What this cannot show: CPU interpret mode runs an aliased ``pallas_call``
    FUNCTIONALLY (the input is copied, never overwritten under the kernel),
    so a read-after-write hazard of the in-place order cannot appear here.
    The ``inplace-order`` contract (``analysis/kernels.py``; fixtures
    ``inplace_order_*``) proves the order from the traced block maps, and the
    benchmark cell ``acoustic-so8-600.bulk``'s ``correct`` holds the compiled
    kernel to the plain reference on the chip."""
    from stencil_tpu.ops import stream as sm

    names = ["u", "v", "c", "d"][:nq]
    kernel = coupled_kernel(r) if nq == 4 else star_kernel(r)
    fields, plans = [], []
    for alias in (True, False):
        dd = DistributedDomain(*extent)
        dd.set_radius(Radius.constant(r))
        dd.set_devices(jax.devices()[:n_dev])
        if "exchange_route" in dom_kw:
            dd.set_exchange_route(dom_kw["exchange_route"])
        if "storage" in dom_kw:
            dd.set_storage(dom_kw["storage"])
        hs = [dd.add_data(n) for n in names]
        dd.realize()
        for i, h in enumerate(hs):
            dd.init_by_coords(
                h, lambda x, y, z, i=i: jnp.sin(0.13 * (x + 2 * y + 3 * z) + i)
            )
        plan = sp.resolve_stream_plan(dd, kernel, r, dict(
            sp.plan_stream(dd, r, "plane", False),
            alias=alias, alias_forced=True, **plan_kw,
        ), True)
        step = sm._build_stream_step(dd, kernel, r, plan, interpret=True)
        dd.run_step(step, 3)
        plans.append(plan)
        fields.append([dd.quantity_to_host(h) for h in hs])
    assert [p["route"] for p in plans] == ["plane", "plane"]
    assert [p["alias"] for p in plans] == [True, False]  # as resolved
    for key in ("overlap", "halo"):
        if key in plan_kw:  # the variant engaged, it did not degrade
            assert plans[0][key] == plans[1][key] == plan_kw[key]
    for name, a, b in zip(names, *fields):
        assert np.isfinite(a).all(), name
        assert np.array_equal(a, b), name


@pytest.mark.parametrize("nq,want", [(8, 8), (2, 0)])
def test_wavefront_span_counts_its_in_place_passes(nq, want):
    """``domain.step``'s ``aliased`` on the wavefront route: real Astaroth's 8
    fields run in place (the static rule from 4 fields up, as before ISSUE
    28), two fields run fresh; the resolved value is in the plan either way."""
    sim = AstarothSim(24, 24, 24, num_quantities=nq, kernel_impl="pallas",
                      schedule="wavefront", devices=jax.devices()[:1], interpret=True)
    sim.realize()
    plan = sim._step._stream_plan
    assert plan["route"] == "wavefront", plan
    assert plan["alias"] is (want > 0), plan
    args = sim._step._span_args()
    assert (args["streamed"], args["aliased"]) == (nq, want), args


# --- the plane pass wraps the planes it loads (ISSUE 34) ----------------------
#
# On the plane route's default schedule the y and z sweeps of an axis the mesh
# does not split leave the exchange and ride in the pass (``plan["pass_wrap_
# axes"]``, ``ops/stream_plan.py pass_wrap_fills``).  The step as built against the
# same step with the rule off (the parent's program: every axis swept by the
# exchange, an unsplit one by ``halo_blend.wrap_halo``), blend kernels on as on
# the chip: every raw cell of every writer bitwise equal, halo included.


def staged_kernels():
    """A two-stage step in elastic's shape: stage V differences ``s`` along y
    alone (no ring: fetched lagged) and ``t`` along z and x; stage S reads the
    ``v`` stage V wrote, halo included, and carries a coefficient."""

    def stage_v(views, info):
        s, t = views["s"], views["t"]
        return {
            "v": views["v"].center()
            + 0.25 * (s.sh(0, 1, 0) - s.sh(0, -1, 0))
            + 0.125 * (t.sh(0, 0, 1) - t.sh(-1, 0, 0))
        }

    def stage_s(views, info):
        v, c = views["v"], views["c"].center()
        return {
            "s": views["s"].center() + 0.25 * (v.sh(1, 0, 0) - v.sh(0, -1, 0)) * c,
            "t": views["t"].center() + 0.5 * (v.sh(0, 0, 1) - v.sh(0, 0, -1)),
        }

    return (stage_v, stage_s)


def _uneven_shell():
    """Face widths lo (2, 1, 2), hi (1, 2, 1): every direction as wide as its
    narrowest face."""
    from stencil_tpu.core.direction_map import DIRECTIONS_26

    faces = {-1: (2, 1, 2), 1: (1, 2, 1)}
    return Radius.from_dict(
        {d: min(faces[s][ax] for ax, s in enumerate(d) if s) for d in DIRECTIONS_26}
    )


_FOUR = (["u", "v", "c", "d"], 2)
_PASS_WRAP_STEP_CASES = [
    # id: kernel, names, x_radius, partition, shell, extent, axes that ride in the pass
    pytest.param(coupled_kernel(2), *_FOUR, (1, 1, 1), None, (16, 16, 16), "yz", id="one-chip"),
    pytest.param(coupled_kernel(2), *_FOUR, (2, 1, 1), None, (16, 16, 16), "yz", id="mesh-2x1x1"),
    pytest.param(coupled_kernel(2), *_FOUR, (1, 2, 1), None, (16, 16, 16), "z", id="mesh-1x2x1"),
    pytest.param(coupled_kernel(2), *_FOUR, (1, 1, 2), None, (16, 16, 16), "y", id="mesh-1x1x2"),
    pytest.param(coupled_kernel(2), *_FOUR, (2, 2, 2), None, (16, 16, 16), "", id="mesh-2x2x2"),
    pytest.param(staged_kernels(), ["v", "s", "t", "c"], 1, (1, 1, 1), None, (16, 16, 16), "yz",
                 id="two-stage"),
    pytest.param(staged_kernels(), ["v", "s", "t", "c"], 1, (2, 1, 1), None, (16, 16, 16), "yz",
                 id="two-stage-mesh-2x1x1"),
    pytest.param(star_kernel(1), ["u"], 1, (1, 1, 1), "uneven", (16, 16, 16), "yz",
                 id="asymmetric-shells"),
    pytest.param(star_kernel(1), ["u"], 1, (2, 1, 1), None, (15, 16, 16), "yz",
                 id="padded-x-over-the-wires"),
]


def _pass_wrap_domain(names, r, partition, shell, extent):
    dd = DistributedDomain(*extent)
    dd.set_radius(_uneven_shell() if shell == "uneven" else Radius.constant(r))
    dd.set_devices(jax.devices()[: int(np.prod(partition))])
    dd.set_partition(*partition)
    hs = [dd.add_data(n) for n in names]
    dd.realize()
    for i, h in enumerate(hs):
        dd.init_by_coords(h, lambda x, y, z, i=i: jnp.sin(0.13 * (x + 2 * y + 3 * z) + i))
    return dd, hs


@pytest.mark.parametrize("kernel,names,r,partition,shell,extent,axes", _PASS_WRAP_STEP_CASES)
def test_plane_step_wraps_its_unsplit_axes_in_the_pass(
    kernel, names, r, partition, shell, extent, axes, monkeypatch
):
    from stencil_tpu.analysis import jaxpr as jx
    from stencil_tpu.ops import stream as sm

    monkeypatch.setenv("STENCIL_HALO_BLEND", "1")

    def run():
        dd, hs = _pass_wrap_domain(names, r, partition, shell, extent)
        plan = sp.resolve_stream_plan(dd, kernel, r, sp.plan_stream(dd, r, "plane", False), True)
        step = sm._build_stream_step(dd, kernel, r, plan, interpret=True)
        closed = jax.make_jaxpr(step, static_argnums=1)(dd._curr, 1)
        swept = {
            ax
            for e in jx.iter_eqns(closed)
            if e.primitive.name in ("pallas_call", "ppermute")
            for ax in "xyz"
            if f"exchange.{ax}" in jx.name_stack_str(e)
        }
        dd.run_step(step, 3)
        raws = {h.name: np.asarray(dd._curr[h.name]) for h in hs}
        return raws, [dd.quantity_to_host(h) for h in hs], plan, swept

    raws, fields, plan, swept = run()
    assert plan["route"] == "plane" and plan["pass_wrap_axes"] == axes, plan
    assert swept == set("xyz") - set(axes)  # only the remaining axes are swept
    monkeypatch.setattr(sp, "pass_wrap_fills", lambda dd, route: ("", ()))
    raws_off, fields_off, plan_off, swept_off = run()
    assert plan_off["pass_wrap_axes"] == "" and swept_off == set("xyz")
    assert plan_off["halo_readers"] == plan["halo_readers"] and plan["halo_readers"]
    # a quantity some stage both reads off-centre and writes comes back with
    # its halo as the exchange left it (the pass-through writes the patched
    # centre plane); one that is read in one stage and written in another
    # keeps a stale y / z shell in HBM, which nothing reads before the next
    # exchange refills it: its interior is held instead
    whole = {
        nm for st in plan["stages"] for nm in st["readers"]
        if any(nm in p["writes"] for p in st["passes"])
    }
    assert whole or len(plan["stages"]) > 1
    for i, name in enumerate(names):
        assert np.isfinite(raws_off[name]).all(), name
        assert np.array_equal(fields[i], fields_off[i]), name
        if name in whole:
            assert np.array_equal(raws[name], raws_off[name]), name
    dd, hs = _pass_wrap_domain(names, r, partition, shell, extent)
    dd.run_step(dd.make_step(kernel, overlap=False), 3)
    for name, a, h in zip(names, fields, hs):
        np.testing.assert_allclose(a, dd.quantity_to_host(h), err_msg=name, **TOL)


#: id: partition, exchange route, stream path, plan overrides, blend kernels,
#: and what the DEFAULT plane schedule wraps on that domain
_NO_PASS_WRAP_CASES = [
    # the packed routes need a wire on y and z, and their own sweeps of an
    # unsplit axis are not the self-wrap
    pytest.param((1, 2, 2), "yzpack_xla", "plane", {"halo": "fused", "halo_forced": True},
                 "1", "", id="fused"),
    pytest.param((1, 2, 1), None, "plane", {"overlap": "split", "overlap_forced": True},
                 "1", "z", id="split"),
    pytest.param((1, 1, 1), None, "plane", {}, "0", "", id="blend-kernels-off"),
    pytest.param((1, 1, 1), None, "wavefront", {}, "1", "yz", id="wavefront-route"),
    pytest.param((1, 1, 1), None, "wrap", {}, "1", "yz", id="wrap-route"),
]


@pytest.mark.parametrize("partition,route,path,plan_kw,blend,default", _NO_PASS_WRAP_CASES)
def test_the_pass_wraps_nothing_where_the_rule_does_not_hold(
    partition, route, path, plan_kw, blend, default, monkeypatch
):
    """``pass_wrap_axes`` is ``""`` -- the program the step had before ISSUE 34
    -- under ``halo="fused"``, under ``overlap="split"``, with the blend
    kernels off (a CPU run) and off the plane route; the default plane
    schedule on the same domain says what the mesh leaves unsplit."""

    monkeypatch.setenv("STENCIL_HALO_BLEND", blend)

    def built(path, kw):
        dd = DistributedDomain(16, 16, 16)
        dd.set_radius(Radius.constant(2 if path == "wavefront" else 1))
        dd.set_devices(jax.devices()[: int(np.prod(partition))])
        dd.set_partition(*partition)
        if route is not None:
            dd.set_exchange_route(route)
        dd.add_data("u")
        dd.realize()
        request = dict(sp.plan_stream(dd, 1, path, False), **kw)
        return sp.resolve_stream_plan(dd, star_kernel(1), 1, request, True)

    plan = built(path, plan_kw)
    for key, want in plan_kw.items():  # the variant engaged, it did not degrade
        assert plan[key] == want, plan
    assert plan["route"] == path and plan["pass_wrap_axes"] == "", plan
    assert built("plane", {})["pass_wrap_axes"] == default


def test_an_nd_quantity_keeps_every_sweep_in_the_exchange(monkeypatch):
    """The rule reads the whole domain, as ``_sweep_kind`` reads the blocks of
    an exchange: one N-D quantity and no axis rides in a pass (the stream
    engine refuses such a domain anyway: ``plan_stream``)."""

    monkeypatch.setenv("STENCIL_HALO_BLEND", "1")
    for components, want in (((), "yz"), ((3,), "")):
        dd = DistributedDomain(16, 16, 16)
        dd.set_radius(Radius.constant(1))
        dd.set_devices(jax.devices()[:1])
        dd.add_data("u")
        dd.add_data("w", components=components)
        dd.realize()
        axes, fills = sp.pass_wrap_fills(dd, "direct")
        assert axes == want, (components, axes)
        assert fills == (
            ((1, 0, 16, 1), (1, 17, 1, 1), (2, 0, 16, 1), (2, 17, 1, 1)) if want else ()
        )


@pytest.mark.parametrize("zr", [30, 130, 128], ids=["lane-padded", "hi-halo-straddles-128", "whole-tiles"])
def test_wavefront_pass_z_slab_patch_returns_the_parents_bytes(zr, monkeypatch):
    """``stream_wavefront_pass`` in z-slab form, m = 3, two fields: with the
    z halo patched inside its lane tiles (ISSUE 40) the pass returns, on every
    plane it writes, the blocks and the outgoing slabs it returned with the
    parent's whole-plane selects (the oracle of tests/test_jacobi_pallas.py
    patched in for the helper) -- on a raw block the pass pads to one lane
    tile in VMEM (ISSUE 41), on one whose hi halo straddles a multiple of 128
    (lanes 127..129 of a 256-lane plane), and on one that is whole lane tiles
    as it stands."""
    from stencil_tpu.core.dim3 import Dim3
    from stencil_tpu.ops.jacobi_pallas import z_halo_patch_form
    from test_jacobi_pallas import parent_patch_z_halo

    m = s = 3
    xr = yr = 24
    names = ["a", "b"]
    rng = np.random.default_rng(zr)
    raws = [jnp.asarray(rng.random((xr, yr, zr), dtype=np.float32)) for _ in names]
    slabs = [jnp.asarray(rng.random((xr, 2 * s, yr), dtype=np.float32)) for _ in names]
    origin = jnp.array([18, 0, 0], jnp.int32)
    assert z_halo_patch_form(spass.lane_pad_width(zr), s) == "tile"

    def run():
        outs, zouts = spass.stream_wavefront_pass(
            mean6_kernel, names, raws, m, s, origin, Dim3(36, 36, zr - 2 * s),
            z_slabs=slabs, interpret=True,
        )
        assert all(o.shape == (xr, yr, zr) for o in outs)
        return [np.asarray(o[: xr - m]) for o in list(outs) + list(zouts)]

    ours = run()
    monkeypatch.setattr(spass, "patch_z_halo", parent_patch_z_halo)
    parents = run()
    assert len(ours) == 4
    for a, b in zip(ours, parents):
        np.testing.assert_array_equal(a, b)
    assert not np.array_equal(ours[0], np.asarray(raws[0][: xr - m]))
