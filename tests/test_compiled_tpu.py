"""Compiled-kernel safety tier — the cuda-memcheck analog.

The reference runs every CUDA test binary under cuda-memcheck
(test/CMakeLists.txt:31,44); the TPU analog is running the SAME kernel
parameter matrix through the REAL Mosaic compiler (interpret=False) whenever
a chip is visible, pinning compiled-vs-ground-truth numerics.  Interpret
mode exercises different code (jnp.roll vs pltpu.roll, no Mosaic lowering,
no index-map hardware bounds), so without this tier the compiled index maps
and DMA bounds would be validated by bench.py alone.

On CPU-only runs (CI, the fake 8-chip mesh) the whole module SKIPS — the
suite stays green everywhere, and gains the compiled coverage exactly where
it means something.  Sizes are kept small (<= 128^3) so the tier adds ~1
minute of compile+run on one chip.

Run it against real hardware with (conftest.py otherwise pins the fake
CPU fleet):

    STENCIL_TEST_PLATFORM=tpu JAX_ENABLE_X64=0 pytest tests/test_compiled_tpu.py

(use the platform name your environment registers, e.g. ``tpu``.)
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

pytestmark = pytest.mark.skipif(
    jax.default_backend() == "cpu",
    reason="compiled-kernel tier needs a real TPU (interpret mode is tier 2)",
)


def test_compiled_wrap_depths_match_k1():
    from stencil_tpu.models.jacobi import Jacobi3D

    dev = jax.devices()[:1]
    ref = Jacobi3D(128, 128, 128, devices=dev, kernel_impl="pallas", temporal_k=1)
    ref.realize()
    ref.step(12)
    want = ref.temperature()
    for k in (3, 6):
        m = Jacobi3D(128, 128, 128, devices=dev, kernel_impl="pallas", temporal_k=k)
        m.realize()
        m.step(12)
        np.testing.assert_array_equal(want, m.temperature())


def test_compiled_wavefront_and_slab_match_wrap():
    from stencil_tpu.models.jacobi import Jacobi3D

    dev = jax.devices()[:1]
    ref = Jacobi3D(128, 128, 128, devices=dev, kernel_impl="pallas", temporal_k=1)
    ref.realize()
    ref.step(8)
    want = ref.temperature()

    wf = Jacobi3D(128, 128, 128, devices=dev, kernel_impl="pallas",
                  pallas_path="wavefront", temporal_k=4)
    wf.realize()
    assert wf._wavefront_z_slabs  # z-slab + lane-pad form on hardware
    wf.step(8)
    np.testing.assert_array_equal(want, wf.temperature())

    slab = Jacobi3D(128, 128, 128, devices=dev, kernel_impl="pallas",
                    pallas_path="slab")  # x-extent 128: Mosaic rotate aligned
    slab.realize()
    slab.step(8)
    np.testing.assert_array_equal(want, slab.temperature())


def test_compiled_stream_engine_matches_xla():
    from stencil_tpu.core.radius import Radius
    from stencil_tpu.domain import DistributedDomain

    def kern(views, info):
        src = views["u"]
        cx, cy, cz = info.coords()
        val = (
            src.sh(1, 0, 0) + src.sh(-1, 0, 0) + src.sh(0, 1, 0)
            + src.sh(0, -1, 0) + src.sh(0, 0, 1) + src.sh(0, 0, -1)
        ) / 6.0
        d2 = (cx - 32) ** 2 + (cy - 32) ** 2 + (cz - 32) ** 2
        return {"u": jnp.where(d2 < 25, 1.0, val).astype(src.center().dtype)}

    def mk(mult):
        dd = DistributedDomain(64, 64, 64)
        dd.set_radius(Radius.constant(1))
        dd.set_devices(jax.devices()[:1])
        if mult != 1:
            dd.set_halo_multiplier(mult)
        h = dd.add_data("u")
        dd.realize()
        dd.init_by_coords(h, lambda x, y, z: jnp.sin(0.1 * (x + y + z)))
        return dd, h

    dd_ref, h_ref = mk(1)
    ref = dd_ref.make_step(kern, overlap=False)  # XLA engine
    dd_ref.run_step(ref, 6)
    want = dd_ref.quantity_to_host(h_ref)

    # single device auto-routes WRAP; forced plane and the wavefront (via a
    # halo multiplier) cover the other two routes — all compiled by Mosaic
    # on one device auto always prefers WRAP (even with a halo multiplier:
    # the self-permuted wavefront cannot beat the no-shell wrap), so the
    # wavefront is forced explicitly to get compiled coverage here
    for mult, path, route in (
        (1, "auto", "wrap"),
        (1, "plane", "plane"),
        (3, "wavefront", "wavefront"),
    ):
        dd, h = mk(mult)
        step = dd.make_step(kern, engine="stream", stream_path=path)
        assert step._stream_plan["route"] == route
        dd.run_step(step, 6)
        np.testing.assert_array_equal(want, dd.quantity_to_host(h))


def test_compiled_astaroth_schedules_match():
    from stencil_tpu.models.astaroth import AstarothSim

    dev = jax.devices()[:1]
    a = AstarothSim(64, 64, 64, num_quantities=2, devices=dev,
                    kernel_impl="pallas", schedule="per-step")
    a.realize()
    b = AstarothSim(64, 64, 64, num_quantities=2, devices=dev,
                    kernel_impl="pallas", schedule="wavefront")
    b.realize()
    assert b._wavefront_m == 3
    a.step(6)
    b.step(6)
    for i in range(2):
        np.testing.assert_allclose(a.field(i), b.field(i), rtol=0, atol=1e-6)


@pytest.mark.parametrize("storage", ["native", "bf16"])
def test_compiled_zslab_step_carries_the_raw_blocks(storage, monkeypatch):
    """The z-slab wavefront step compiled by Mosaic (ISSUE 41): the raw
    ``(70, 70, 70)`` blocks stream through ``(1, 70, 128)`` boundary blocks, in
    place, their dead lanes zeroed in VMEM -- bitwise, on every plane the
    passes own (all rows, all lanes: the z-shell lanes beside the dead ones
    included), the step that pads in HBM and runs the plain pass
    (``tests/test_lane_pad_vmem.py hbm_padded_pass``), after two dispatches
    of two macros and a remainder, and within the interior the XLA engine's
    per-step result.  A dead lane left as the VMEM buffer held it shows in
    lane 69 after one level."""
    import test_lane_pad_vmem as lp
    from stencil_tpu.core.radius import Radius
    from stencil_tpu.domain import DistributedDomain
    from stencil_tpu.ops import stream as sm, stream_pass as spass

    names = ("a", "b", "c", "d")  # four: the wavefront's static rule runs in place

    def two_dispatches(engine):
        dd = DistributedDomain(64, 64, 64)
        dd.set_radius(Radius.constant(3 if engine == "stream" else 1))
        dd.set_devices(jax.devices()[:1])
        if engine == "stream":
            dd.set_storage(storage)
        hs = [dd.add_data(nm) for nm in names]
        dd.realize()
        for i, h in enumerate(hs):
            dd.init_by_coords(h, lambda x, y, z, i=i: jnp.sin(0.13 * (x + 2 * y + 3 * z) + i))
        if engine == "stream":
            step = dd.make_step(_mean6, engine="stream", x_radius=1, stream_path="wavefront")
            plan = step._stream_plan
            assert (plan["route"], plan["m"], plan["z_slabs"], plan["alias"], plan["lane_pad"]) == (
                "wavefront", 3, True, True, "vmem"), plan
        else:
            step = dd.make_step(_mean6, overlap=False)
        for _ in range(2):
            dd.run_step(step, 7)
        if engine == "stream":
            assert step._resilience.descents == [], step._resilience.descents
        raws = [np.asarray(dd._curr[nm].astype(jnp.float32)) for nm in names]
        return raws, [dd.quantity_to_host(h) for h in hs]

    ours, fields = two_dispatches("stream")
    monkeypatch.setattr(sm, "stream_wavefront_pass", lp.hbm_padded_pass(spass.stream_wavefront_pass))
    padded, _ = two_dispatches("stream")
    for a, b in zip(ours, padded):
        assert a.shape == (70, 70, 70)
        np.testing.assert_array_equal(a[3:-3], b[3:-3])
        assert np.isfinite(a[3:-3]).all()
    if storage == "native":
        _, want = two_dispatches("xla")
        for a, b in zip(fields, want):
            np.testing.assert_allclose(a, b, rtol=0, atol=1e-6)


# --- census: one compiled case per non-default axis value ---------------------
#
# Twins of the interpret-mode suites (test_exchange_routes, test_overlap_split,
# test_stream_fused, test_kernel_axes), each forcing ONE non-default axis value
# through Mosaic at <= 128^3.  ROADMAP S4-S6/D1 need to know which axes compile
# at all before chip time goes into A/B-ing them; a case the compiler rejects
# is marked xfail(strict=True) with the compiler's own message and listed under
# ROADMAP D1 — repairing it is a separate change.  A ladder descent counts as a
# failure here: a rejected axis must not pass by quietly running its default.


def _mean6(views, info):
    out = {}
    for name, src in views.items():
        out[name] = (
            src.sh(-1, 0, 0) + src.sh(1, 0, 0)
            + src.sh(0, -1, 0) + src.sh(0, 1, 0)
            + src.sh(0, 0, -1) + src.sh(0, 0, 1)
        ) / 6.0
    return out


def _stream_run(n=128, mult=1, route=None, storage=None, steps=4, **step_kw):
    """One compiled stream-engine run on one device; returns (plan, field).
    Fails on any ladder descent."""
    from stencil_tpu.core.radius import Radius
    from stencil_tpu.domain import DistributedDomain

    dd = DistributedDomain(n, n, n)
    dd.set_radius(Radius.constant(1))
    dd.set_devices(jax.devices()[:1])
    if mult > 1:
        dd.set_halo_multiplier(mult)
    if route is not None:
        dd.set_exchange_route(route)
    if storage is not None:
        dd.set_storage(storage)
    h = dd.add_data("q0")
    dd.realize()
    dd.init_by_coords(h, lambda x, y, z: jnp.sin(0.13 * (x + 2 * y + 3 * z)))
    step = dd.make_step(_mean6, engine="stream", **step_kw)
    dd.run_step(step, steps)
    assert step._resilience.descents == [], step._resilience.descents
    return step._stream_plan, dd.quantity_to_host(h)


#: chip run of PR 21 (TPU v5 lite, jax 0.9.0 / libtpu 0.0.34) — ROADMAP D1
_PACK_REJECT = (
    "ValueError at realize(): The Pallas TPU lowering currently requires that "
    "the last two dimensions of your block shape are divisible by 8 and 128 "
    "respectively, or be equal to the respective dimensions of the overall "
    "array. Block spec for outputs in pallas_call kernel at ops/pack.py:345 "
    "has block shape (2, 132, 1), array shape (2, 132, 256) [z pack; the y "
    "pack at ops/pack.py:434 likewise: block (2, 1, 132) of (2, 132, 132)]"
)
@pytest.mark.parametrize(
    "route",
    [
        "zpack_xla",
        pytest.param("zpack_pallas",
                     marks=pytest.mark.xfail(strict=True, reason=_PACK_REJECT)),
        "yzpack_xla",
        pytest.param("yzpack_pallas",
                     marks=pytest.mark.xfail(strict=True, reason=_PACK_REJECT)),
    ],
)
def test_compiled_exchange_route(route):
    """A radius-2 shell through each packed route equals the analytic
    field in every raw cell (interior and shell)."""
    from stencil_tpu.core.radius import Radius
    from stencil_tpu.domain import DistributedDomain

    n, r = 128, 2
    dd = DistributedDomain(n, n, n)
    dd.set_radius(Radius.constant(r))
    dd.set_devices(jax.devices()[:1])
    dd.set_exchange_route(route)
    h = dd.add_data("q0")
    dd.realize()
    assert dd.exchange_route() == route  # no realize-time step-down to direct
    dd.init_by_coords(h, lambda x, y, z: (x * 37 + y * 5 + z).astype(jnp.float32))
    dd.exchange()
    c = (np.arange(n + 2 * r) - r) % n
    want = (
        c[:, None, None] * 37 + c[None, :, None] * 5 + c[None, None, :]
    ).astype(np.float32)
    np.testing.assert_array_equal(dd.raw_to_host(h), want)


def test_compiled_overlap_split():
    plan_off, want = _stream_run(mult=3, stream_path="wavefront",
                                 stream_overlap="off", steps=7)
    plan, got = _stream_run(mult=3, stream_path="wavefront",
                            stream_overlap="split", steps=7)
    assert plan["overlap"] == "split" and plan["route"] == "wavefront", plan
    np.testing.assert_array_equal(want, got)


def test_compiled_halo_fused():
    _, want = _stream_run(mult=3, route="yzpack_xla", stream_path="wavefront",
                          stream_halo="array", steps=7)
    plan, got = _stream_run(mult=3, route="yzpack_xla", stream_path="wavefront",
                            stream_halo="fused", steps=7)
    assert plan["halo"] == "fused" and plan["route"] == "wavefront", plan
    np.testing.assert_array_equal(want, got)


def test_compiled_storage_bf16():
    from ulp import assert_bf16_storage_close

    _, want = _stream_run(stream_depth=4)
    plan, got = _stream_run(storage="bf16", stream_depth=4)
    # init quantizes the input (one extra rounding) + <= one downcast per step
    assert_bf16_storage_close(got, want, passes=5, context="compiled bf16")


@pytest.mark.parametrize("axis", [1, 2], ids=["y", "z"])
def test_compiled_self_wrap_518(axis):
    """The self-wrap kernel at the weak cell's own block — 518^3 f32, radius
    3: sublane tiles 0 and 64 (y), lane tiles 0 and 4 with the last one
    ragged (z) — equals the plain-DUS fill in every cell."""
    from stencil_tpu.ops.halo_blend import wrap_halo

    n, r = 512, 3
    size = n + 2 * r
    cells = jax.lax.iota(jnp.int32, size**3).reshape(size, size, size)
    block = (cells % 1000003).astype(jnp.float32)

    def cut(lo, hi):
        idx = [slice(None)] * 3
        idx[axis] = slice(lo, hi)
        return tuple(idx)

    want = block.at[cut(0, r)].set(block[cut(n, n + r)])
    want = want.at[cut(r + n, size)].set(block[cut(r, 2 * r)])
    got = jax.jit(lambda b: wrap_halo(b, axis, r, r, n))(block)
    assert bool(jnp.array_equal(got, want))


@pytest.mark.parametrize("shape,s", [((518, 6, 518), 3), ((544, 32, 544), 16)],
                         ids=["astaroth-518x6x518", "jacobi-x4-544x32x544"])
def test_compiled_self_wrap_of_a_z_slab_buffer(shape, s):
    """The self-wrap kernel on a z-major ``(Xr, 2s, Yr)`` slab buffer, as
    ``permute_and_extend_z_slabs`` calls it on an axis the mesh does not split
    (ISSUE 56): the y extension (axis 2, lane tiles 0 and 4) and then the x
    extension (axis 0, ``2s`` planes) at the astaroth cell's own buffer -- a
    SIX-row sublane dim, under the 8-row tile, which interpret mode cannot
    vouch for -- and at the jacobi x4 cell's, against the numpy twin of
    ``wrap_halo`` (``tests/test_plane_stencil.py _self_wrap``) on every cell."""
    from test_plane_stencil import _self_wrap

    from stencil_tpu.ops.halo_blend import wrap_halo

    rng = np.random.default_rng(56)
    host = rng.standard_normal(shape).astype(np.float32)
    want = _self_wrap(_self_wrap(host, 2, s, s), 0, s, s)

    @jax.jit
    def both(b):
        b = wrap_halo(b, 2, s, s, shape[2] - 2 * s)
        return wrap_halo(b, 0, s, s, shape[0] - 2 * s)

    np.testing.assert_array_equal(np.asarray(both(jnp.asarray(host))), want)


def test_compiled_plane_pass_wraps_the_planes_it_loads(monkeypatch):
    """The plane pass's own y / z halo fills (ISSUE 34) as Mosaic compiles
    them, on acoustic's plane -- 608 x 608 f32, radius 4: sublane tiles 0 and
    75 (y), lane tiles 0 and 4 with the last one ragged (z), written into the
    pipeline's own input buffer -- for a ringed reader and one fetched lagged,
    both written back: every RAW cell equals the step whose exchange sweeps
    all three axes with ``wrap_halo``.  (The benchmark's wave cells cannot see
    this: their outer frame is zero, so a halo that was never filled is right
    by accident.)"""
    from stencil_tpu.core.radius import Radius
    from stencil_tpu.domain import DistributedDomain
    from stencil_tpu.ops import stream_plan as sp

    r = 4

    def kern(views, info):
        u, b = views["u"], views["b"]
        acc = 0.5 * u.center()
        for k in range(1, r + 1):
            w = 1.0 / (12.0 * k)
            acc = acc + w * (
                (u.sh(k, 0, 0) + 0.9 * u.sh(-k, 0, 0))
                + (u.sh(0, k, 0) + 0.8 * u.sh(0, -k, 0))
                + (u.sh(0, 0, k) + 0.7 * u.sh(0, 0, -k))
            )
        return {
            "u": acc + 0.125 * (b.sh(0, 3, 0) - b.sh(0, 0, -r)),
            "b": 0.5 * b.center() + 0.25 * b.sh(0, -1, 0),
        }

    def run():
        dd = DistributedDomain(8, 600, 600)
        dd.set_radius(Radius.constant(r))
        dd.set_devices(jax.devices()[:1])
        hs = [dd.add_data(n) for n in ("u", "b")]
        dd.realize()
        for i, h in enumerate(hs):
            dd.init_by_coords(h, lambda x, y, z, i=i: jnp.sin(0.013 * (x + 2 * y + 3 * z) + i))
        step = dd.make_step(kern, engine="stream", x_radius=r)
        dd.run_step(step, 3)
        return step._stream_plan, [dd._curr[h.name] for h in hs]

    plan, got = run()
    assert plan["route"] == "plane" and plan["pass_wrap_axes"] == "yz", plan
    assert plan["stages"][0]["passes"][0]["rings"] == ("u",), plan
    monkeypatch.setattr(sp, "pass_wrap_fills", lambda dd, route: ("", ()))
    plan_off, want = run()
    assert plan_off["pass_wrap_axes"] == "", plan_off
    for name, a, b in zip(("u", "b"), got, want):
        assert bool(jnp.all(jnp.isfinite(b))) and bool(jnp.array_equal(a, b)), name


@pytest.mark.parametrize("steps", [8, 9])
def test_compiled_acoustic_step_renames_u_prev(steps, monkeypatch):
    """The acoustic cell's step as Mosaic and XLA compile it, 600^3 (raw
    608^3): ``u_prev <- u`` is a rename (ISSUE 36) -- the pass writes ``u``
    alone, into ``u_prev``'s block, and the loop runs two steps a trip -- and
    every interior cell of ``u`` AND ``u_prev`` is bitwise what the same model
    gives with the rule off (both written, the parent's program), after an
    even dispatch (8: four trips, every block back in place) and an odd one
    (9: the last step behind the loop, the outputs permuted against the
    donated inputs).  In place is what CPU interpret mode cannot show: there
    an aliased call runs functionally."""
    import dataclasses
    import gc

    from stencil_tpu.models.acoustic import AcousticWave
    from stencil_tpu.ops import stream_plan as sp

    def run():
        sim = AcousticWave(600, 600, 600, devices=jax.devices()[:1],
                           seed_words=(0x1234ABCD, 77, 0xDEADBEEF, 2024))
        sim.realize()
        sim.step(steps)
        plan = sim._step._stream_plan
        fields = [sim.field(q) for q in ("u", "u_prev")]  # on the host: 0.86 GB each
        # a domain holds 7.6 GB here (two slots of four blocks) and is kept
        # alive by the cycles of its own closures: free it before the next one
        for slot in (sim.dd._curr, sim.dd._next or {}):
            for block in slot.values():
                block.delete()
        del sim
        gc.collect()
        return plan, fields

    plan, got = run()
    assert plan["renamed"] == ("u_prev",) and plan["writers"] == ("u",), plan
    real = sp.trace_plane_kernel
    monkeypatch.setattr(
        sp, "trace_plane_kernel", lambda *a: dataclasses.replace(real(*a), renames=())
    )
    plan_off, want = run()
    assert plan_off["renamed"] == () and plan_off["writers"] == ("u", "u_prev"), plan_off
    for name, a, b in zip(("u", "u_prev"), got, want):
        assert np.isfinite(b).all() and float(np.max(np.abs(b))) > 0.01, name
        assert np.array_equal(a, b), name


def test_compiled_acoustic_step_across_four_chips():
    """The acoustic shot decomposed over four chips (1200 x 1200 x 600 on mesh
    [2,2,1], 600^3 a chip: ISSUE 37), run by hand on a four-chip host: the
    compiled plane step -- ``u``'s radius-4 x and y halos over ICI every step,
    the z wrap inside the pass, ``u_prev <- u`` a rename inside a loop body
    that holds collectives -- against the XLA slice engine on the same mesh,
    bitwise on EVERY RAW CELL of ``u`` and ``u_prev``, halos included (after
    ``dd.exchange()``: the plane step leaves the shells of what it does not
    read stale), after an even dispatch of 8 steps and again after an odd one
    of 9 behind it; ``m`` and ``damp`` raw at the end.  The wave cells'
    ``correct`` compares interiors; this holds the wires to every shell cell."""
    if len(jax.devices()) < 4:
        pytest.skip("needs the four chips of one host")
    from stencil_tpu.models.acoustic import QUANTITIES, AcousticWave

    def run(impl):
        sim = AcousticWave(1200, 1200, 600, devices=jax.devices()[:4], kernel_impl=impl,
                           seed_words=(0x1234ABCD, 77, 0xDEADBEEF, 2024))
        sim.realize()
        assert tuple(sim.dd.mesh_dim()) == (2, 2, 1), sim.dd.mesh_dim()
        seen = []
        for steps, names in ((8, ("u", "u_prev")), (9, QUANTITIES)):
            sim.step(steps)
            sim.dd.exchange()
            seen.append({q: np.asarray(sim.dd._curr[q]) for q in names})  # 3.6 GB each, on the host
        plan = getattr(sim._step, "_stream_plan", None)
        args = getattr(sim._step, "_span_args", dict)()
        for slot in (sim.dd._curr, sim.dd._next or {}):
            for block in slot.values():
                block.delete()
        return plan, args, seen

    plan, args, got = run("pallas")
    assert plan["route"] == "plane" and plan["pass_wrap_axes"] == "z", plan
    assert plan["renamed"] == ("u_prev",) and plan["halo_readers"] == ("u",), plan
    assert (args["wired"], args["wire_bytes"]) == ("xy", 2 * 2 * 4 * 608 * 608 * 4), args
    _, _, want = run("jnp")
    for k, (a, b) in enumerate(zip(got, want)):
        for q in b:
            assert np.isfinite(b[q]).all() and float(np.max(np.abs(b[q]))) > 1e-4, (q, k)
            assert np.array_equal(a[q], b[q]), (q, k, float(np.max(np.abs(a[q] - b[q]))))


@pytest.mark.parametrize("chips", [1, 4])
def test_compiled_jacobi_macro_loop_is_bitwise_one_a_trip(chips, monkeypatch):
    """Both jacobi cells of the benchmark as Mosaic and XLA compile them, run
    by hand (one chip: 512^3, wrap route, k=16; a four-chip host: 1024 x 1024
    x 512 on mesh [2,2,1], z-ring wavefront, depth 16): the macro loop runs
    TWO macros a trip so that the fresh result lands in the carry's own buffer
    (ISSUE 38), and every cell is bitwise what the same model gives with ONE
    macro a trip (the parent's program, a whole-block copy a trip) -- after the
    cell's own dispatch (256 / 160 steps: 16 / 10 macros, whole trips) and
    again after an odd one behind it (17 / 11 macros and a remainder of 5: the
    last macro and the remainder run behind the loop).  Whose buffer a result
    takes is what CPU interpret mode cannot show."""
    if len(jax.devices()) < chips:
        pytest.skip("needs the four chips of one host")
    from stencil_tpu.models import jacobi as jm
    from stencil_tpu.models.jacobi import Jacobi3D

    size, macros = ((512, 512, 512), 16) if chips == 1 else ((1024, 1024, 512), 10)

    def run():
        sim = Jacobi3D(*size, devices=jax.devices()[:chips], kernel_impl="pallas")
        sim.realize()
        depth = sim._wrap_k if chips == 1 else sim._wavefront_m
        assert (sim._pallas_path, depth) == ("wrap" if chips == 1 else "wavefront", 16)
        assert chips == 1 or (sim._wavefront_z_ring and tuple(sim.dd.mesh_dim()) == (2, 2, 1))
        seen = []
        for steps in (macros * 16, (macros + 1) * 16 + 5):
            sim.step(steps)
            seen.append(sim.temperature())
        return sim._step._span_args(), seen

    # the z-ring step also says where its kernel patches the z halo (ISSUE 40)
    # and that over z its slab buffers send nothing to themselves (ISSUE 56)
    patch = {} if chips == 1 else {"z_halo_patch": "tile", "slab_wrap": "z"}

    def said(args):  # but the wires' (PR 49, ISSUE 50: tests/test_wire_account.py)
        return {k: v for k, v in args.items() if k not in ("wired", "wire_bytes", "joint")}

    args, got = run()
    assert said(args) == {"macros_per_trip": 2, **patch}
    monkeypatch.setattr(jm, "_macros_per_trip", lambda in_place: 1)
    args_one, want = run()
    assert said(args_one) == {"macros_per_trip": 1, **patch}
    for a, b in zip(got, want):
        assert np.isfinite(b).all() and 0.0 <= b.min() < 0.4 and 0.6 < b.max() <= 1.0
        assert np.array_equal(a, b), float(np.max(np.abs(a - b)))


def test_compiled_lbm_step_matches_the_xla_engine():
    """The lattice-Boltzmann cell's step as Mosaic compiles it, 256^3 x 19
    (ISSUE 39): the route ``auto`` takes on one chip (wrap, two macros a trip)
    against the XLA slice engine running the same kernel, on every cell of all
    nineteen populations after a dispatch of whole trips, a macro behind the
    loop and a remainder -- the wrap folded into the index maps and rotates
    must serve every diagonal read.  Within ``max_abs_err`` of the benchmark's
    configuration (Mosaic and XLA round apart here, as on the CPU)."""
    import gc
    import json
    import os

    from stencil_tpu.models.lbm import LatticeBoltzmann
    from stencil_tpu.models.lbm_reference import NAMES

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs", "lbm-d3q19-256.json")) as f:
        config = json.load(f)
    steps = 5 * config["expect"]["depth"] + 1  # two trips, a macro behind them, a step more

    def run(impl):
        sim = LatticeBoltzmann(256, 256, 256, devices=jax.devices()[:1], kernel_impl=impl,
                               seed_words=(0x1234ABCD, 77, 0xDEADBEEF, 2024))
        sim.realize()
        sim.step(steps)
        said = getattr(sim._step, "_span_args", dict)()
        fields = [sim.field(q) for q in NAMES]  # on the host: 67 MB each
        for slot in (sim.dd._curr, sim.dd._next or {}):
            for block in slot.values():
                block.delete()
        del sim
        gc.collect()
        return said, fields

    said, got = run("pallas")
    assert (said["route"], said["macros_per_trip"], said["diagonal"]) == (config["expect"]["route"], 2, 12)
    _, want = run("jnp")
    worst = max(float(np.abs(a - b).max()) for a, b in zip(got, want))
    assert np.isfinite(worst) and worst <= config["limits"]["max_abs_err"], worst
    assert min(float(np.abs(a - b).max()) for a, b in zip(got[1:], got[:-1])) > 1e-3  # no two alike


def test_compiled_wrap_step_carries_the_raw_blocks_at_its_edges(monkeypatch):
    """The wrap route's edge forms as Mosaic compiles them (ISSUE 52), 256^3 x
    19, the benchmark's cell: after 8 steps (raw in, one trip of two bare
    macros, raw out -- aliased onto the step's donated blocks) every interior
    cell of all nineteen populations BITWISE the parent's formulation
    (``wrap_edge_form`` patched to ``"xla"``: ``lax.slice``, bare passes,
    ``dynamic_update_slice``); the y and z shell of every interior x plane the
    periodic image of that plane, and the x halo planes what the blocks held
    before the step."""
    import gc

    from stencil_tpu.models.lbm import LatticeBoltzmann
    from stencil_tpu.models.lbm_reference import NAMES
    from stencil_tpu.ops import stream_plan as sp

    def run(edges):
        with monkeypatch.context() as mp:
            if edges == "xla":
                mp.setattr(sp, "wrap_edge_form", lambda dd, plan: "xla")
            sim = LatticeBoltzmann(256, 256, 256, devices=jax.devices()[:1],
                                   seed_words=(0x1234ABCD, 77, 0xDEADBEEF, 2024))
            sim.realize()
            before = [(np.asarray(sim.dd._curr[q][0]), np.asarray(sim.dd._curr[q][-1])) for q in NAMES]
            sim.step(8)
            said = sim._step._span_args()
            assert (said["route"], said["edges"], sim._step._stream_plan["m"]) == ("wrap", edges, 2), said
            assert not sim._step._resilience.descents
            raws = [np.asarray(sim.dd._curr[q]) for q in NAMES]  # on the host: 69 MB each
        for slot in (sim.dd._curr, sim.dd._next or {}):
            for block in slot.values():
                block.delete()
        del sim
        gc.collect()
        return before, raws

    before, got = run("raw")
    _, want = run("xla")
    for q, (lo_plane, hi_plane), a, b in zip(NAMES, before, got, want):
        inner = a[1:-1, 1:-1, 1:-1]
        assert np.array_equal(inner, b[1:-1, 1:-1, 1:-1]), (q, float(np.abs(inner - b[1:-1, 1:-1, 1:-1]).max()))
        assert np.array_equal(a[1:-1], np.pad(inner, ((0, 0), (1, 1), (1, 1)), mode="wrap")), q
        assert np.array_equal(a[0], lo_plane) and np.array_equal(a[-1], hi_plane), q
    assert min(float(np.abs(a - b).max()) for a, b in zip(got[1:], got[:-1])) > 1e-3  # no two alike


@pytest.mark.parametrize("budget,y_tiles", [(50e6, 2), (36e6, 4)])
def test_compiled_lbm_step_in_y_tiles_is_bitwise_the_wrap_route(budget, y_tiles, monkeypatch):
    """The plane pass over Y TILES of a plane as Mosaic compiles it (ISSUE 51),
    at 256^3 x 19: the PLANNER's budget tightened (the compiler keeps its own)
    and the wrap route taken away, so that ``LatticeBoltzmann``'s normal path
    answers with the tiled pass, the plane in two and in four -- every cell of
    all nineteen populations bitwise the wrap route's after 8 steps (my chip
    runs, PR 51: passed, 78 s; 6.1-6.3 ms a step by call 2's probe).  The 512^3 box itself is the
    benchmark's cell ``lbm-d3q19-512.bulk``: it fills the chip."""
    import gc

    from stencil_tpu.models.lbm import LatticeBoltzmann
    from stencil_tpu.models.lbm_reference import NAMES
    from stencil_tpu.ops import stream_plan as sp

    def run():
        sim = LatticeBoltzmann(256, 256, 256, devices=jax.devices()[:1],
                               seed_words=(0x1234ABCD, 77, 0xDEADBEEF, 2024))
        sim.realize()
        sim.step(8)
        plan = dict(sim._step._stream_plan)
        fields = [sim.field(q) for q in NAMES]
        for slot in (sim.dd._curr, sim.dd._next or {}):
            for block in slot.values():
                block.delete()
        del sim
        gc.collect()
        return plan, fields

    plan, want = run()
    assert plan["route"] == "wrap"
    monkeypatch.setattr(sp, "_deepest_fit", lambda *a, **k: None)  # no wrap depth: the plane route
    monkeypatch.setattr(sp, "_vmem_budget", lambda: int(budget))
    plan, got = run()
    assert (plan["route"], plan["plane_window"], plan["y_tiles"]) == ("plane", "interior", y_tiles)
    assert plan["tile_rows"] == 256 // y_tiles and plan["alias"]
    for q, a, b in zip(NAMES, got, want):
        assert np.array_equal(a, b), (q, float(np.abs(a - b).max()))


def test_compiled_lbm_dispatch_with_the_z_shell_at_its_edges_is_bitwise_whole_calls(monkeypatch):
    """The tiled pass's three lane forms as Mosaic compiles them (ISSUES 54, 58), at
    256^3 x 19 with the plane in four y tiles (the planner's budget tightened as
    above): a dispatch whose first call reads whole raw planes and writes ``(1, 64,
    256)`` blocks of the 258-lane rows, whose calls between (four of six steps, one
    of three) read and write such blocks alone, and whose last call reads them and
    writes the z shell back, against the same program with every call whole
    (``plane_lanes_form`` patched to "raw": the parent's) -- EVERY raw cell of all
    nineteen populations bitwise equal after a dispatch of 6 steps and one of 3
    behind it, the z shell, the tail rows and the x-halo planes included."""
    import gc

    from stencil_tpu.models.lbm import LatticeBoltzmann
    from stencil_tpu.models.lbm_reference import NAMES
    from stencil_tpu.ops import stream_plan as sp

    monkeypatch.setattr(sp, "_deepest_fit", lambda *a, **k: None)  # no wrap depth: the plane route
    monkeypatch.setattr(sp, "_vmem_budget", lambda: int(36e6))

    def run():
        sim = LatticeBoltzmann(256, 256, 256, devices=jax.devices()[:1],
                               seed_words=(0x1234ABCD, 77, 0xDEADBEEF, 2024))
        sim.realize()
        sim.step(6)
        sim.step(3)
        plan = dict(sim._step._stream_plan)
        raws = [np.asarray(sim.dd._curr[q]) for q in NAMES]
        for slot in (sim.dd._curr, sim.dd._next or {}):
            for block in slot.values():
                block.delete()
        del sim
        gc.collect()
        return plan, raws

    plan, got = run()
    assert (plan["route"], plan["y_tiles"], plan["plane_lanes"]) == ("plane", 4, "window")
    monkeypatch.setattr(sp, "plane_lanes_form", lambda plan: "raw")
    plan, want = run()
    assert (plan["route"], plan["y_tiles"], plan["plane_lanes"]) == ("plane", 4, "raw")
    for q, a, b in zip(NAMES, got, want):
        assert a.shape == (258, 258, 258) and np.array_equal(a, b), (q, np.argwhere(a != b)[:4])


@pytest.mark.parametrize("shape,window", [
    pytest.param((32, 58, 122), "raw", id="raw-planes-of-whole-tiles"),
    pytest.param((32, 64, 128), "interior", id="interior-of-whole-tiles"),
])
def test_compiled_mhd_step_matches_the_xla_engine(shape, window):
    """Astaroth's MHD step as Mosaic compiles it (ISSUE 44), at two small
    sizes: one whose RAW plane is whole tiles (64 x 128: the raw window, every
    in-plane shift a rotate of the shell-carrying plane) and one whose
    INTERIOR is (64 x 128 of a 70 x 134 plane: the interior window of ISSUE
    45 -- the block's aligned corner, the interior rotated by the shell, the
    rotates' wraparound the halo, the stored planes' shell rebuilt behind
    them).  The plane route -- three
    stages, eight renames each, two steps a trip, the y / z fills made in the
    pass -- against the XLA slice engine running the same kernels over swept
    exchanges, on every cell of all sixteen quantities after an even and an
    odd count of steps (the odd one runs a step behind the loop and hands the
    handles on permuted).  The box is periodic and nowhere zero: an unfilled
    edge halo or plane corner, or a wrong wrap, would show."""
    from stencil_tpu.models.astaroth_mhd import AstarothMHD
    from stencil_tpu.models.astaroth_mhd_reference import QUANTITIES, MhdSetup

    setup = MhdSetup(shape)  # dt from the finest spacing, 2 pi / 122 or / 128

    def run(impl):
        sim = AstarothMHD(*shape, setup=setup, devices=jax.devices()[:1], kernel_impl=impl,
                          seed_words=(0x1234ABCD, 77, 0xDEADBEEF, 2024))
        sim.realize()
        got = []
        for n in (4, 3):
            sim.step(n)
            got.append([sim.field(q) for q in QUANTITIES])
        return getattr(sim._step, "_span_args", dict)(), got

    said, got = run("pallas")
    assert (said["route"], said["stages"], said["renamed"], said["steps_per_trip"], said["wrapped"]) == (
        "plane", 3, "8/8/8", 2, "yz"), said
    assert said["plane_window"] == window, said
    # the interior window evaluates the kernel a row strip at a time (ISSUE
    # 46: two strips of four of the 64 x 128 plane's eight tiles, every y shift
    # a read of another tile, the margin tiles' wrap included), the raw one
    # over the plane whole
    assert said["plane_strip"] == (32 if window == "interior" else 0), said
    _, want = run("jnp")
    for a, b in zip(got, want):
        worst = max(float(np.abs(x - y).max()) for x, y in zip(a, b))
        assert np.isfinite(worst) and worst <= 3e-6, worst  # the cell's own limit
    moved = min(float(np.abs(x - y).max()) for x, y in zip(got[0], got[1]))
    assert moved > 1e-4, moved  # every quantity advanced between the two readings


def test_compiled_mhd_step_across_four_chips():
    """Astaroth's MHD step decomposed over four chips (ISSUE 47), run by hand on
    a four-chip host at a small size whose raw planes are whole tiles (32 x 58 x
    122 a chip, 64 x 116 x 122 on mesh [2,2,1], a uniform cell): the compiled
    plane step -- three staged exchanges a time step over ICI, the eight
    fields' radius-3 x and y halos and the x-y edge halo that crosses two wires
    in turn, the z wrap inside the pass, eight renames a stage inside a loop
    body that holds collectives -- against the XLA slice engine on the same
    mesh, on EVERY RAW CELL of all sixteen quantities, shells included (after
    ``dd.exchange()``: the plane step leaves the shells of what it does not
    read stale), after an even dispatch of 4 steps and an odd one of 3 behind
    it.  The cell's ``correct`` compares interiors; this holds the wires to
    every shell cell, the four shard edges among them."""
    if len(jax.devices()) < 4:
        pytest.skip("needs the four chips of one host")
    from stencil_tpu.models.astaroth_mhd import AstarothMHD
    from stencil_tpu.models.astaroth_mhd_reference import QUANTITIES, MhdSetup

    shape = (64, 116, 122)
    cell = 2.0 * np.pi / 122
    setup = MhdSetup(shape, box=tuple(cell * n for n in shape))

    def run(impl):
        sim = AstarothMHD(*shape, setup=setup, devices=jax.devices()[:4], kernel_impl=impl,
                          seed_words=(0x1234ABCD, 77, 0xDEADBEEF, 2024))
        sim.dd.set_partition(2, 2, 1)
        sim.realize()
        seen = []
        for n in (4, 3):
            sim.step(n)
            sim.dd.exchange()
            seen.append({q: np.asarray(sim.dd._curr[q]) for q in QUANTITIES})
        return getattr(sim._step, "_span_args", dict)(), seen

    said, got = run("pallas")
    assert (said["route"], said["stages"], said["renamed"], said["steps_per_trip"]) == (
        "plane", 3, "8/8/8", 2), said
    assert (said["wired"], said["wrapped"], said["wired_edges"]) == ("xy", "z", "xy"), said
    assert (said["plane_window"], said["plane_strip"]) == ("raw", 0), said
    stage = 8 * 6 * (64 + 38) * 128 * 4  # eight fields, six x planes and six y rows of the raw 38 x 64 x 128 block
    assert said["wire_bytes_by_stage"] == "/".join([str(stage)] * 3), said
    _, want = run("jnp")
    for a, b in zip(got, want):
        assert a["ux"].shape == (2 * 38, 2 * 64, 128)  # every shard's raw block, shells included
        worst = max(float(np.abs(a[q] - b[q]).max()) for q in QUANTITIES)
        assert np.isfinite(worst) and worst <= 3e-6, worst  # the cell's own limit
    moved = min(float(np.abs(got[0][q] - got[1][q]).max()) for q in QUANTITIES)
    assert moved > 1e-4, moved  # every quantity advanced between the two readings


def _diagonal_r3_kernel(views, info):
    """Radius 3 at full distance on every axis and on the y-z, x-y and x-z
    diagonals, products of fields, the cells' own coordinates; ``c`` read along
    y and z alone (fetched lagged), ``p`` at the centre, ``p <- u`` a rename."""
    u, c = views["u"], views["c"]
    _, y, z = info.coords()
    acc = 0.3 * u.center() + 1e-3 * jnp.sin(0.1 * (y + 2 * z).astype(jnp.float32))
    for k in (1, 2, 3):
        acc = acc + (0.1 / k) * (
            (u.sh(k, 0, 0) - 0.9 * u.sh(-k, 0, 0))
            + (u.sh(0, k, k) - 0.8 * u.sh(0, -k, k)) * c.sh(0, k, 0)
            + (u.sh(k, -k, 0) - 0.7 * u.sh(-k, k, 0))
            + (u.sh(-k, 0, k) - 0.6 * u.sh(k, 0, -k)) * c.sh(0, 0, -k)
        )
    return {"u": acc + 0.5 * views["p"].center(), "p": u.center()}


@pytest.mark.parametrize("storage", ["native", "bf16"])
def test_compiled_interior_window_is_bitwise_the_raw_plane_pass(storage, monkeypatch):
    """The plane pass on its interior window (ISSUE 45) as Mosaic compiles it,
    against the SAME step built on the raw window (``plane_window_form``
    patched to say "raw": the parent's program): a radius-3 kernel with y-z,
    x-y and x-z diagonal reads, products of fields, a lagged quantity and a
    rename, on a 16 x 64 x 256 box (70 x 262 raw planes: two lane tiles and a
    ragged third, as the MHD cell's), f32 and bf16 storage.  Mosaic contracts
    nothing, so every cell of every quantity is BITWISE equal after an even
    and an odd count of steps -- the kernel evaluates the same operations in
    the same order on the cells the raw window keeps.  The interior window
    runs its STRIP form (ISSUE 46: strips of two tiles -- 16 rows of f32, 32 of
    bf16 --, the rings' planes as tiles between their margin tiles, ``c`` a
    lagged halo reader); the same step with the kernel over the plane WHOLE
    (``plane_strip_rows`` patched to 0: the parent's interior form) is held to
    the same bits."""
    from stencil_tpu.core.radius import Radius
    from stencil_tpu.domain import DistributedDomain
    from stencil_tpu.ops import stream_plan as sp

    r = 3
    kern = _diagonal_r3_kernel

    def run():
        dd = DistributedDomain(16, 64, 256)
        dd.set_radius(Radius.constant(r))
        dd.set_devices(jax.devices()[:1])
        if storage != "native":
            dd.set_storage(storage)
        hs = [dd.add_data(n) for n in ("u", "c", "p")]
        dd.realize()
        for i, h in enumerate(hs):
            dd.init_by_coords(h, lambda x, y, z, i=i: jnp.sin(0.13 * (x + 2 * y + 3 * z) + i))
        step = dd.make_step(kern, engine="stream", x_radius=r)
        got = []
        for n in (4, 3):
            dd.run_step(step, n)
            got.append([np.asarray(dd.quantity_to_host(h), np.float32) for h in hs])
        return step._stream_plan, got

    monkeypatch.setattr(sp, "_STRIP_MIN_OPS", 0)  # (a light kernel: whole planes by the planner)
    plan, got = run()
    assert (plan["route"], plan["pass_wrap_axes"], plan["plane_window"]) == ("plane", "yz", "interior")
    assert plan["renamed"] == ("p",) and plan["stages"][0]["passes"][0]["rings"] == ("u",), plan
    assert plan["plane_strip"] == (16 if storage == "native" else 32), plan
    with monkeypatch.context() as mp:
        mp.setattr(sp, "plane_strip_rows", lambda *a: 0)
        plan_whole, whole = run()
    assert (plan_whole["plane_window"], plan_whole["plane_strip"]) == ("interior", 0)
    monkeypatch.setattr(sp, "plane_window_form", lambda *a: "raw")
    plan_raw, want = run()
    assert (plan_raw["plane_window"], plan_raw["plane_strip"]) == ("raw", 0)
    for a, b, c in zip(got, want, whole):
        for name, x, y, w in zip(("u", "c", "p"), a, b, c):
            assert np.isfinite(y).all() and np.array_equal(x, y), name
            assert np.array_equal(x, w), name
    assert float(np.abs(got[0][0] - got[1][0]).max()) > 1e-3  # the state moved


@pytest.mark.parametrize("storage", ["native", "bf16"])
def test_compiled_window_beside_a_split_y_is_bitwise_the_raw_plane_pass(storage):
    """The plane pass on the aligned window beside a SPLIT y (ISSUE 48:
    ``window="interior-z"``, the strip form) as Mosaic compiles it, ONE pass on
    one chip against the same pass on the raw window: the blocks' y halo rows
    hold data of their own (a neighbour's rows: random numbers, not the plane's
    wrap), the pass is handed the z fills alone.  16 x 128 x 256 blocks (134 x
    262 raw planes: sixteen tiles of f32 rows, eight of bf16; two lane tiles and
    a ragged third, as the MHD cell's), strips of two tiles, the planes two
    ``dy`` share rotated once a grid step.  Mosaic contracts nothing: every
    interior cell BITWISE equal, the y halo rows of every stored plane and the
    x-shell planes too (they pass through), and the stored z shell the
    self-wrap of the stored plane."""
    from stencil_tpu.core.dim3 import Dim3
    from stencil_tpu.ops import stream_pass as spass

    r, n, names = 3, (16, 128, 256), ["u", "c", "p"]
    dtype = jnp.float32 if storage == "native" else jnp.bfloat16
    shape = tuple(m + 2 * r for m in n)
    rng = np.random.default_rng(48)
    raws = [jnp.asarray(rng.standard_normal(shape), dtype) for _ in names]
    fills = ((2, 0, n[2], r), (2, r + n[2], r, r))
    lo = hi = Dim3(r, r, r)
    assert spass.plane_window_form(fills, lo, hi, shape[1:], [dtype]) == "interior-z"
    strip = spass.plane_strip_rows("interior-z", n[1:], [dtype], r)
    assert strip == 2 * spass.sublane_tile([dtype])

    def run(window, strip):
        def fn(origin, *blocks):
            return spass.stream_plane_pass(
                _diagonal_r3_kernel, names, list(blocks), lo, hi, r, origin, Dim3(64, 4 * n[1], n[2]),
                f32_accumulate=storage != "native", halo_readers=("u", "c"), rings=("u",),
                writers=("u",), wrap_fills=fills, renames=(("p", "u"),), window=window, strip=strip,
                prerotated=(("u", 0, 1), ("u", 0, 2), ("u", 0, 3), ("c", 0, -1)) if strip else (),
            )

        out = jax.jit(fn)(jnp.asarray([5, 7, 0], jnp.int32), *raws)
        return [np.asarray(o.astype(jnp.float32)) for o in out]

    got, want = run("interior-z", strip), run("raw", 0)
    inner = tuple(slice(r, r + m) for m in n)
    for name, a, b in zip(names, got, want):
        assert np.isfinite(b).all() and np.array_equal(a[inner], b[inner]), name
    u, u_raw = got[0], want[0]
    assert np.array_equal(got[2], np.asarray(raws[0].astype(jnp.float32)))  # ``p`` is the old ``u``
    for y_halo in (slice(0, r), slice(r + n[1], None)):
        assert np.array_equal(u[:, y_halo], u_raw[:, y_halo])
    for x_shell in (slice(0, r), slice(r + n[0], None)):
        assert np.array_equal(u[x_shell], u_raw[x_shell])
    assert np.array_equal(u[..., :r], u[..., n[2] : n[2] + r])  # the stored plane's own z wrap
    assert np.array_equal(u[..., r + n[2] :], u[..., r : 2 * r])
    assert not np.array_equal(u[inner], np.asarray(raws[0].astype(jnp.float32))[inner])


def test_compiled_mhd_step_beside_a_split_y_across_four_chips(monkeypatch):
    """Astaroth's MHD step on mesh [2,2,1] at a shard the aligned window beside
    a split y takes (ISSUE 48; 16 x 64 x 128 a chip, 32 x 128 x 128 on a uniform
    cell: interiors of whole tiles, eight tiles of rows for a six-row y shell),
    run by hand on a four-chip host: the compiled plane step -- the z halo the
    rotates' wraparound, the neighbours' y halo rows riding in the margin tiles,
    the strips of four tiles -- against the XLA slice engine on every interior
    cell of all sixteen quantities after an even dispatch and an odd one, and
    BITWISE the same step built on the raw window (``plane_window_form``
    patched to "raw": the parent's program)."""
    if len(jax.devices()) < 4:
        pytest.skip("needs the four chips of one host")
    from stencil_tpu.models.astaroth_mhd import AstarothMHD
    from stencil_tpu.models.astaroth_mhd_reference import QUANTITIES, MhdSetup
    from stencil_tpu.ops import stream_plan as sp

    shape = (32, 128, 128)
    cell = 2.0 * np.pi / 128
    setup = MhdSetup(shape, box=tuple(cell * n for n in shape))

    def run(impl):
        sim = AstarothMHD(*shape, setup=setup, devices=jax.devices()[:4], kernel_impl=impl,
                          seed_words=(0x1234ABCD, 77, 0xDEADBEEF, 2024))
        sim.dd.set_partition(2, 2, 1)
        sim.realize()
        seen = []
        for n in (4, 3):
            sim.step(n)
            seen.append({q: sim.field(q) for q in QUANTITIES})
        return getattr(sim._step, "_span_args", dict)(), seen

    said, got = run("pallas")
    assert (said["route"], said["stages"], said["renamed"], said["steps_per_trip"]) == (
        "plane", 3, "8/8/8", 2), said
    assert (said["wired"], said["wrapped"], said["wired_edges"]) == ("xy", "z", "xy"), said
    assert (said["plane_window"], said["plane_strip"]) == ("interior-z", 32), said
    _, want = run("jnp")
    for a, b in zip(got, want):
        worst = max(float(np.abs(a[q] - b[q]).max()) for q in QUANTITIES)
        assert np.isfinite(worst) and worst <= 3e-6, worst  # the cell's own limit
    moved = min(float(np.abs(got[0][q] - got[1][q]).max()) for q in QUANTITIES)
    assert moved > 1e-4, moved  # every quantity advanced between the two readings
    monkeypatch.setattr(sp, "plane_window_form", lambda *a: "raw")
    said_raw, raw = run("pallas")
    assert (said_raw["plane_window"], said_raw["plane_strip"]) == ("raw", 0), said_raw
    for a, b in zip(got, raw):
        for q in QUANTITIES:
            assert np.array_equal(a[q], b[q]), q

