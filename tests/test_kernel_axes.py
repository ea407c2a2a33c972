"""Tier-1: the storage-dtype axis (native/bf16) of the level kernels.

In-process on the fake 8-chip CPU mesh (interpret-mode pallas): bf16 storage
with f32 accumulation tracks the f32 ground truth within the analytic
one-rounding-per-downcast bound (``tests/ulp.bf16_storage_atol``); the
default ``native`` path stays BITWISE identical to an explicit one;
resolution follows explicit > env > tuned > static with structural
degradation (non-f32 fields, engines without an f32-accumulate form); the
ladder steps ``bf16 -> native`` at the SAME depth before any depth descent;
and the axis persists and consults through ``tune.best_config`` with
pre-axis cache entries still warm.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from ulp import assert_bf16_storage_close

from stencil_tpu import telemetry, tune
from stencil_tpu.core.radius import Radius
from stencil_tpu.domain import DistributedDomain
from stencil_tpu.models.jacobi import Jacobi3D
from stencil_tpu.ops.jacobi_pallas import (
    bf16_supported,
    jacobi_wrap_step,
    resolve_storage_dtype,
)
from stencil_tpu.resilience import inject
from stencil_tpu.telemetry import names as tm

@pytest.fixture
def tune_dir(tmp_path, monkeypatch):
    monkeypatch.setenv("STENCIL_TUNE_CACHE", str(tmp_path))
    monkeypatch.delenv("STENCIL_TUNE", raising=False)
    tune.reset_memo()
    yield tmp_path
    tune.reset_memo()


def _mk(size=(16, 16, 16), radius=1, mult=1, dtypes=(jnp.float32,)):
    dd = DistributedDomain(*size)
    dd.set_radius(Radius.constant(radius))
    dd.set_devices(jax.devices()[:8])
    if mult > 1:
        dd.set_halo_multiplier(mult)
    hs = [dd.add_data(f"q{i}", dtype=t) for i, t in enumerate(dtypes)]
    dd.realize()
    for i, h in enumerate(hs):
        dd.init_by_coords(
            h, lambda x, y, z, i=i: jnp.sin(0.13 * (x + 2 * y + 3 * z) + i)
        )
    return dd, hs


def mean6_kernel(views, info):
    out = {}
    for name, src in views.items():
        out[name] = (
            src.sh(-1, 0, 0) + src.sh(1, 0, 0)
            + src.sh(0, -1, 0) + src.sh(0, 1, 0)
            + src.sh(0, 0, -1) + src.sh(0, 0, 1)
        ) / 6.0
    return out


# --- kernel-level equivalence ------------------------------------------------


@pytest.mark.parametrize("k", [1, 3])
def test_wrap_bf16_storage_analytic_bound(k):
    """One wrap dispatch = ONE downcast regardless of k (the f32-accumulate
    contract: the level ring carries f32, the store rounds once)."""
    rng = np.random.default_rng(7)
    b0 = jnp.asarray(rng.random((12, 16, 16)), jnp.float32)
    ground = jacobi_wrap_step(b0, interpret=True, k=k)
    got = jacobi_wrap_step(
        b0.astype(jnp.bfloat16), interpret=True, k=k, f32_accumulate=True
    )
    assert got.dtype == jnp.bfloat16
    assert_bf16_storage_close(
        got, ground, passes=1, scale=1.0, context=f"wrap bf16 k={k}"
    )


# --- model-level equivalence -------------------------------------------------


def test_jacobi_bf16_storage_matches_f32_ground_truth():
    a = Jacobi3D(24, 24, 24, kernel_impl="pallas", interpret=True)
    a.realize()
    b = Jacobi3D(24, 24, 24, kernel_impl="pallas", interpret=True,
                 storage_dtype="bf16")
    b.realize()
    assert b.dd.storage_dtype() == "bf16"
    # the field buffers really narrowed (HBM side of the halved bytes/cell)
    assert b.dd._curr["temp"].dtype == jnp.bfloat16
    a.step(4)
    b.step(4)
    # readback upcasts to the declared dtype; ≤ one downcast per raw step
    t = b.temperature()
    assert t.dtype == np.float32
    assert_bf16_storage_close(t, a.temperature(), passes=4, scale=1.0,
                              context="jacobi bf16 storage")


def test_jacobi_bf16_halves_exchange_bytes():
    a = Jacobi3D(24, 24, 24, kernel_impl="pallas", interpret=True)
    a.realize()
    b = Jacobi3D(24, 24, 24, kernel_impl="pallas", interpret=True,
                 storage_dtype="bf16")
    b.realize()
    assert b.dd.exchange_bytes_total() * 2 == a.dd.exchange_bytes_total()


def test_default_path_bitwise_vs_explicit_vpu_native():
    """The axis' static fallback IS today's kernel: an explicit native
    build is bit-identical to an axis-free one."""
    a = Jacobi3D(24, 24, 24, kernel_impl="pallas", interpret=True)
    a.realize()
    b = Jacobi3D(24, 24, 24, kernel_impl="pallas", interpret=True,
                 storage_dtype="native")
    b.realize()
    a.step(3)
    b.step(3)
    np.testing.assert_array_equal(a.temperature(), b.temperature())


# --- structural degradation --------------------------------------------------


def test_bf16_degrades_on_f64_fields_and_xla_engine():
    assert not bf16_supported([jnp.float64])
    m = Jacobi3D(24, 24, 24, kernel_impl="pallas", interpret=True,
                 storage_dtype="bf16", dtype=jnp.float64)
    m.realize()
    assert m.dd.storage_dtype() == "native"
    x = Jacobi3D(24, 24, 24, kernel_impl="jnp", storage_dtype="bf16")
    x.realize()  # the XLA engine has no f32-accumulate kernels
    assert x.dd.storage_dtype() == "native"


def test_unknown_axis_values_rejected():
    with pytest.raises(ValueError, match="unknown storage dtype"):
        DistributedDomain(8, 8, 8).set_storage("fp8")
    with pytest.raises(ValueError, match="unknown value"):
        resolve_storage_dtype("fp4", None, [jnp.float32])
    with pytest.raises(ValueError, match="unknown value"):
        Jacobi3D(16, 16, 16, kernel_impl="pallas", interpret=True,
                 storage_dtype="fp8").realize()


def test_wrap_temporal_k_models_f32_ring_under_bf16(monkeypatch):
    """The wrap depth gate must model the level ring at the f32 accumulator
    itemsize under bf16 storage — a storage-itemsize-only model admits
    depths whose f32 ring blows the budget (review finding, PR 7)."""
    from stencil_tpu.ops import jacobi_pallas as jp
    from stencil_tpu.ops.jacobi_pallas import (
        choose_temporal_k,
        wavefront_vmem_bytes,
    )

    Y = Z = 512
    lo = wavefront_vmem_bytes(8, Y, Z, 2)  # bf16-ring (wrong) model at k=8
    hi = wavefront_vmem_bytes(8, Y, Z, 2, ring_itemsize=4)  # f32 ring
    assert hi > lo
    budget = (lo + hi) // 2 + jp._VMEM_STACK_MARGIN
    monkeypatch.setenv("STENCIL_VMEM_LIMIT_BYTES", str(budget))
    k_storage_only = choose_temporal_k((64, Y, Z), 2)
    k_ring_aware = choose_temporal_k((64, Y, Z), 2, ring_itemsize=4)
    assert k_storage_only >= 8  # the wrong model admits the blown depth
    assert k_ring_aware < 8  # the ring-aware model refuses it


def test_set_storage_bf16_degrades_on_mixed_dtype_domain():
    """Direct domain-API bf16 on a mixed f32/f64 domain degrades whole at
    realize(): the f32-accumulate passes upcast EVERY quantity uniformly,
    so an engaged bf16 would silently truncate the f64 field in-kernel."""
    dd = DistributedDomain(16, 16, 16)
    dd.set_radius(Radius.constant(1))
    dd.set_devices(jax.devices()[:8])
    dd.add_data("f", dtype=jnp.float32)
    dd.add_data("d", dtype=jnp.float64)
    dd.set_storage("bf16")
    dd.realize()
    assert dd.storage_dtype() == "native"
    assert dd._curr["f"].dtype == jnp.float32
    assert dd._curr["d"].dtype == jnp.float64


# --- stream engine -----------------------------------------------------------


def test_stream_bf16_storage_via_domain():
    dd_a, hs_a = _mk(mult=2)
    dd_b = DistributedDomain(16, 16, 16)
    dd_b.set_radius(Radius.constant(1))
    dd_b.set_devices(jax.devices()[:8])
    dd_b.set_halo_multiplier(2)
    h_b = dd_b.add_data("q0")
    dd_b.set_storage("bf16")
    dd_b.realize()
    assert dd_b._curr["q0"].dtype == jnp.bfloat16
    dd_b.init_by_coords(
        h_b, lambda x, y, z: jnp.sin(0.13 * (x + 2 * y + 3 * z))
    )
    sa = dd_a.make_step(mean6_kernel, engine="stream", interpret=True)
    sb = dd_b.make_step(mean6_kernel, engine="stream", interpret=True)
    dd_a.run_step(sa, 4)
    dd_b.run_step(sb, 4)
    # init quantizes the input (one extra rounding) + ≤ one downcast/pass
    assert_bf16_storage_close(
        dd_b.quantity_to_host(h_b), dd_a.quantity_to_host(hs_a[0]),
        passes=5, context="stream bf16",
    )


def test_bf16_packed_exchange_matches_direct():
    """The fused z-shell message narrows to 2 B/cell under bf16 storage and
    the packed routes stay BITWISE equal to direct over the narrow buffers
    (the blend kernels know the (16, 128) bf16 tile geometry)."""
    outs = {}
    for route in ("direct", "zpack_xla"):
        dd = DistributedDomain(16, 16, 16)
        dd.set_radius(Radius.constant(2))
        dd.set_devices(jax.devices()[:8])
        dd.set_exchange_route(route)
        h = dd.add_data("q0")
        dd.set_storage("bf16")
        dd.realize()
        assert dd.exchange_route() == route
        dd.init_by_coords(
            h, lambda x, y, z: jnp.sin(0.13 * (x + 2 * y + 3 * z))
        )
        step = dd.make_step(mean6_kernel, engine="stream", interpret=True)
        dd.run_step(step, 3)
        outs[route] = dd.quantity_to_host(h)
    np.testing.assert_array_equal(outs["direct"], outs["zpack_xla"])


# --- precedence: explicit > env > tuned > static -----------------------------


def test_storage_dtype_resolution_precedence(tune_dir, monkeypatch):
    mk = lambda **kw: Jacobi3D(16, 16, 16, kernel_impl="pallas",
                               interpret=True, **kw)
    m = mk()
    m.realize()
    assert m.dd.storage_dtype() == "native"  # static
    monkeypatch.setenv("STENCIL_STORAGE_DTYPE", "bf16")
    m = mk()
    m.realize()
    assert m.dd.storage_dtype() == "bf16"  # env beats static
    m = mk(storage_dtype="native")
    m.realize()
    assert m.dd.storage_dtype() == "native"  # explicit beats env


def test_axis_env_invalid_rejected(monkeypatch):
    monkeypatch.setenv("STENCIL_STORAGE_DTYPE", "fp8")
    with pytest.raises(ValueError, match="STENCIL_STORAGE_DTYPE"):
        resolve_storage_dtype(None, None, [jnp.float32])
    with pytest.raises(ValueError, match="STENCIL_STORAGE_DTYPE"):
        Jacobi3D(16, 16, 16, kernel_impl="pallas", interpret=True).realize()


# --- tuner: search, persist, consult -----------------------------------------


def test_pre_axis_cache_entry_without_fields_still_hits(tune_dir):
    """Pre-axis entries (no storage_dtype) stay consultable — no schema
    bump; absent = the static native."""
    dd, _ = _mk(mult=2)
    key = dd.tune_key("stream")
    tune.record_config(
        key,
        {"route": "wavefront", "m": 2, "z_slabs": False, "grouping": "joint",
         "alias": False, "halo_multiplier": 2},
    )
    tune.reset_memo()
    dd2, _ = _mk(mult=2)
    step = dd2.make_step(mean6_kernel, engine="stream", interpret=True)
    assert step._stream_plan["m"] == 2 and not step._stream_plan["z_slabs"]
    assert dd2.storage_dtype() == "native"


def test_tuned_storage_dtype_consulted_by_jacobi(tune_dir):
    """The jacobi model consults the tuned storage_dtype pre-allocation
    (route-keyed 'jacobi-wavefront' on the multi-device path)."""
    probe = Jacobi3D(24, 24, 24, kernel_impl="pallas", interpret=True)
    key = probe.dd.tune_key("jacobi-wavefront")
    tune.record_config(
        key, {"m": 3, "halo_multiplier": 3, "alias": False, "z_ring": False,
              "storage_dtype": "bf16"},
    )
    tune.reset_memo()
    m = Jacobi3D(24, 24, 24, kernel_impl="pallas", interpret=True)
    m.realize()
    assert m.dd.storage_dtype() == "bf16"
    assert m.dd._curr["temp"].dtype == jnp.bfloat16


# --- resilience ladder -------------------------------------------------------


def test_jacobi_ladder_steps_bf16_down_to_native(tune_dir):
    """A classified failure on a bf16 jacobi build steps storage down to
    native at the same depth: live buffers upcast (exact), the domain
    re-marks native, and the rebuilt route runs."""
    m = Jacobi3D(24, 24, 24, kernel_impl="pallas", interpret=True,
                 storage_dtype="bf16", temporal_k=3,
                 devices=jax.devices()[:1])
    m.realize()
    assert m.dd.storage_dtype() == "bf16"
    k0 = m._wrap_k
    inject.set_plan("execute:vmem_oom:jacobi*1")
    try:
        m.step(3)
    finally:
        inject.set_plan(None)
    assert m.dd.storage_dtype() == "native"
    assert m.dd._curr["temp"].dtype == jnp.float32
    assert m._wrap_k == k0  # SAME depth — the axis dropped first
    ref = Jacobi3D(24, 24, 24, kernel_impl="pallas", interpret=True,
                   temporal_k=3, devices=jax.devices()[:1])
    ref.realize()
    ref.step(3)
    # the first dispatch ran bf16 (one downcast), the retry native
    assert_bf16_storage_close(m.temperature(), ref.temperature(), passes=3,
                              context="post-step-down")


# --- telemetry ---------------------------------------------------------------


def test_storage_event_emitted(tmp_path):
    telemetry.enable(dir=str(tmp_path))
    telemetry.reset()
    try:
        m = Jacobi3D(16, 16, 16, kernel_impl="pallas", interpret=True,
                     storage_dtype="bf16")
        m.realize()
        import json

        events = [
            json.loads(line) for line in open(telemetry.event_log_path())
        ]
        sd = [e for e in events if e["event"] == tm.EVENT_KERNEL_STORAGE_DTYPE]
        assert sd and sd[-1]["storage"] == "bf16"
        assert sd[-1]["source"] == "explicit"
    finally:
        telemetry.disable()
