"""Tier-1: packed z-shell exchange routes (ops/exchange.py EXCHANGE_ROUTES).

The tentpole claims, in-process on the fake 8-chip CPU mesh (interpret-mode
pallas): packed and direct exchanges are BITWISE identical across radii,
uneven shards, halo multipliers, and multi-dtype fused messages; route
resolution follows explicit > env > tuned > static-direct with structural
degradation; the compile-reject ladder steps a packed route down to direct;
realize's eager compile retries classified transients; ``autotune_exchange`` measures the route space and
persists a winner the next realize picks up.
"""

import numpy as np
import jax.numpy as jnp
import pytest

from stencil_tpu import telemetry, tune
from stencil_tpu.core.radius import Radius
from stencil_tpu.domain import DistributedDomain
from stencil_tpu.ops.exchange import (
    EXCHANGE_ROUTES,
    Y_PACK_ROUTES,
    route_supported,
    ypack_supported,
    zpack_supported,
)
from stencil_tpu.resilience import inject
from stencil_tpu.telemetry import names as tm
from stencil_tpu.tune import space as tune_space
from stencil_tpu.tune.runners import autotune_exchange

PACKED_ROUTES = [r for r in EXCHANGE_ROUTES if r != "direct"]


@pytest.fixture(autouse=True)
def _clear_fault_plan():
    yield
    inject.set_plan(None)


@pytest.fixture
def tune_dir(tmp_path, monkeypatch):
    """Hermetic tuned-config cache: route-consult tests must not persist
    entries other tests' realizes (same tiny workloads) would pick up."""
    monkeypatch.setenv("STENCIL_TUNE_CACHE", str(tmp_path))
    monkeypatch.delenv("STENCIL_TUNE", raising=False)
    tune.reset_memo()
    yield tmp_path
    tune.reset_memo()


def _build(route=None, size=(16, 16, 16), radius=2, dtypes=(jnp.float32,), mult=1,
           storage=None):
    dd = DistributedDomain(*size)
    dd.set_radius(radius if isinstance(radius, Radius) else Radius.constant(radius))
    if route is not None:
        dd.set_exchange_route(route)
    if mult > 1:
        dd.set_halo_multiplier(mult)
    if storage is not None:
        dd.set_storage(storage)
    hs = [dd.add_data(f"q{i}", dtype=t) for i, t in enumerate(dtypes)]
    dd.realize()
    for i, h in enumerate(hs):
        if h.dtype == jnp.bool_:
            dd.init_by_coords(h, lambda x, y, z: (x + 2 * y + 3 * z) % 2 == 0)
        else:
            dd.init_by_coords(
                h,
                lambda x, y, z, i=i: (x * 37 + y * 5 + z + i * 1000).astype(h.dtype),
            )
    return dd, hs


def _exchanged_raws(route, **kw):
    dd, hs = _build(route, **kw)
    dd.exchange()
    return dd, [dd.raw_to_host(h) for h in hs]


def _assert_routes_bitwise(**kw):
    _, want = _exchanged_raws("direct", **kw)
    for route in PACKED_ROUTES:
        _, got = _exchanged_raws(route, **kw)
        for w, g in zip(want, got):
            np.testing.assert_array_equal(w, g)


# --- bitwise equivalence -----------------------------------------------------


@pytest.mark.parametrize("radius", [1, 2])
def test_packed_bitwise_uniform_radius(radius):
    _assert_routes_bitwise(radius=radius)


def test_packed_bitwise_multi_quantity_fused():
    """All quantities (mixed itemsizes, incl. the byte-fused message path)
    ride ONE packed message per direction and come back bit-exact."""
    _assert_routes_bitwise(
        radius=1, dtypes=(jnp.float32, jnp.float64, jnp.int8, jnp.bool_)
    )


def test_packed_bitwise_uneven_xy_shards():
    """Packed z engages while x/y run the dynamic-offset direct path (the
    yzpack routes degrade their y sweep here — each sweep independently)."""
    _assert_routes_bitwise(size=(17, 15, 16), radius=1)


def test_packed_bitwise_uneven_z_shard():
    """The mirror case: the yzpack routes pack their y sweep while z runs
    the dynamic-offset direct path — partial engagement stays bitwise."""
    _assert_routes_bitwise(size=(16, 16, 17), radius=1)


def test_bf16_storage_ypack_bitwise():
    """bf16 STORAGE rides the y pack's (16,128) tile geometry: the
    sublane-major y message at 2 B/cell comes back bit-exact."""
    kw = dict(radius=1, storage="bf16")
    _, want = _exchanged_raws("direct", **kw)
    for route in ("yzpack_xla", "yzpack_pallas"):
        _, got = _exchanged_raws(route, **kw)
        for w, g in zip(want, got):
            np.testing.assert_array_equal(w, g)


def test_packed_bitwise_halo_multiplier_shell():
    """The 2m-deep shell (halo multiplier 2, radius 1) packs as one buffer."""
    _assert_routes_bitwise(radius=1, mult=2)


def test_make_step_packed_bitwise():
    """The fused exchange+compute step produces identical state under the
    packed route — plain jacobi no longer pays the thin-z path."""

    def mean6(views, info):
        out = {}
        for name, src in views.items():
            out[name] = (
                src.sh(-1, 0, 0) + src.sh(1, 0, 0)
                + src.sh(0, -1, 0) + src.sh(0, 1, 0)
                + src.sh(0, 0, -1) + src.sh(0, 0, 1)
            ) / 6.0
        return out

    results = {}
    for route in ("direct", "zpack_pallas"):
        dd, hs = _build(route, radius=1)
        step = dd.make_step(mean6)
        dd.run_step(step, 3)
        results[route] = dd.quantity_to_host(hs[0])
    np.testing.assert_array_equal(results["direct"], results["zpack_pallas"])


# --- route resolution --------------------------------------------------------


def test_route_resolution_precedence(tune_dir, monkeypatch):
    # static fallback: no request, no env, cold cache -> direct
    dd, _ = _build()
    assert dd.exchange_route() == "direct"
    # env beats static
    monkeypatch.setenv("STENCIL_EXCHANGE_ROUTE", "zpack_xla")
    dd, _ = _build()
    assert dd.exchange_route() == "zpack_xla"
    # explicit beats env
    dd, _ = _build("zpack_pallas")
    assert dd.exchange_route() == "zpack_pallas"


def test_route_env_invalid_rejected(monkeypatch):
    monkeypatch.setenv("STENCIL_EXCHANGE_ROUTE", "zpack_bogus")
    dd = DistributedDomain(16, 16, 16)
    dd.set_radius(Radius.constant(1))
    dd.add_data("q")
    with pytest.raises(ValueError, match="STENCIL_EXCHANGE_ROUTE"):
        dd.realize()


def test_set_exchange_route_rejects_unknown():
    dd = DistributedDomain(16, 16, 16)
    with pytest.raises(ValueError, match="unknown exchange route"):
        dd.set_exchange_route("bogus")


def test_tuned_route_consulted_and_validated(tune_dir):
    probe = DistributedDomain(16, 16, 16)
    probe.set_radius(Radius.constant(2))
    probe.add_data("q0")
    key = probe.tune_key("exchange")
    tune.record_config(key, {"exchange_route": "zpack_pallas"})
    dd, _ = _build()
    assert dd.exchange_route() == "zpack_pallas"
    # a stale/garbage persisted route degrades to the static fallback
    tune.record_config(key, {"exchange_route": "not-a-route"})
    dd, _ = _build()
    assert dd.exchange_route() == "direct"
    # tuning disabled: static picks, no consult
    with tune.disabled():
        tune.record_config(key, {"exchange_route": "zpack_xla"})
        dd, _ = _build()
        assert dd.exchange_route() == "direct"


def test_uneven_z_degrades_to_direct():
    """The pack kernels cut the shell at static z offsets, so a padded z
    axis structurally cannot engage — the pinned route degrades instead of
    crashing, and the exchange stays correct."""
    dd, hs = _build("zpack_pallas", size=(16, 16, 17), radius=1)
    assert dd.exchange_route() == "direct"
    dd.exchange()
    ref, _ = _build("direct", size=(16, 16, 17), radius=1)
    ref.exchange()
    np.testing.assert_array_equal(
        dd.raw_to_host(hs[0]), ref.raw_to_host(ref._handles[0])
    )


def test_zpack_supported_gates():
    assert zpack_supported([jnp.float32, jnp.int8], (None, None, None))
    assert not zpack_supported([jnp.float32], (None, None, 7))  # padded z
    assert not zpack_supported([jnp.complex128], (None, None, None))


def test_ypack_supported_gates():
    assert ypack_supported([jnp.float32, jnp.int8], (None, None, None))
    assert not ypack_supported([jnp.float32], (None, 7, None))  # padded y
    assert ypack_supported([jnp.float32], (None, None, 7))  # padded z is fine
    assert not ypack_supported([jnp.complex128], (None, None, None))


def test_route_supported_composes_sweeps():
    """A yzpack route is supported when EITHER packed sweep can engage; the
    z-only routes need the z sweep; direct always."""
    f32 = [jnp.float32]
    assert route_supported("direct", f32, (None, 7, 7))
    assert route_supported("zpack_xla", f32, (None, None, None))
    assert not route_supported("zpack_xla", f32, (None, None, 7))
    assert route_supported("yzpack_xla", f32, (None, None, 7))  # y carries it
    assert route_supported("yzpack_pallas", f32, (None, 7, None))  # z carries it
    assert not route_supported("yzpack_xla", f32, (None, 7, 7))


# --- resilience --------------------------------------------------------------


@pytest.mark.parametrize("route", ["zpack_pallas", "yzpack_pallas"])
def test_compile_reject_steps_down_to_direct(tune_dir, route):
    """A packed route the compiler rejects descends the ladder to direct at
    realize — counted, event-logged, and the run proceeds."""
    before = telemetry.snapshot()["counters"][tm.LADDER_DESCENTS]
    inject.set_plan(f"compile:compile_reject:exchange:{route}")
    dd, hs = _build(route, radius=1)
    assert dd.exchange_route() == "direct"
    assert telemetry.snapshot()["counters"][tm.LADDER_DESCENTS] == before + 1
    dd.exchange()  # the stepped-down exchange is live
    ref, _ = _build("direct", radius=1)
    ref.exchange()
    np.testing.assert_array_equal(
        dd.raw_to_host(hs[0]), ref.raw_to_host(ref._handles[0])
    )


def test_realize_compile_retries_transient(monkeypatch):
    """A connection drop during compile is TRANSIENT: the eager exchange
    compile retries under the policy instead of dying."""
    monkeypatch.setenv("STENCIL_RETRY_BACKOFF_S", "0")
    before = telemetry.snapshot()["counters"][tm.RETRY_ATTEMPTS]
    inject.set_plan("compile:transient:compile:exchange:direct")
    dd, _ = _build(radius=1)  # realize survives the injected drop
    assert telemetry.snapshot()["counters"][tm.RETRY_ATTEMPTS] == before + 1
    dd.exchange()


# --- tuner + telemetry -------------------------------------------------------


def test_exchange_space_prefilters_ineligible():
    dd, _ = _build(radius=1)
    cands, pre = tune_space.exchange_space(dd)
    assert cands[0] == {"exchange_route": "direct"}
    assert {c["exchange_route"] for c in cands} == set(EXCHANGE_ROUTES)
    assert pre == 0
    # uneven z: the z-only packed routes prefilter, but the yzpack routes
    # stay candidates (their y sweep engages — a distinct program)
    dd_uneven, _ = _build(size=(16, 16, 17), radius=1)
    cands, pre = tune_space.exchange_space(dd_uneven)
    assert {c["exchange_route"] for c in cands} == {"direct", *Y_PACK_ROUTES}
    assert pre == 2
    # uneven y with even z: the yzpack candidates would measure
    # byte-identical duplicates of their zpack siblings — prefiltered
    dd_uy, _ = _build(size=(16, 15, 16), radius=1)
    cands, pre = tune_space.exchange_space(dd_uy)
    assert {c["exchange_route"] for c in cands} == {
        "direct", "zpack_xla", "zpack_pallas",
    }
    assert pre == 2
    # both packed axes uneven: nothing can engage
    dd_both, _ = _build(size=(16, 15, 17), radius=1)
    cands, pre = tune_space.exchange_space(dd_both)
    assert cands == [{"exchange_route": "direct"}]
    assert pre == len(PACKED_ROUTES)


def test_exchange_tune_key_includes_shell_depth():
    """The exchange route's z message depth is the SHELL (user radius ×
    halo multiplier), so the multiplier must re-key the workload — a winner
    measured at an 8-deep shell must not be consulted by a 2-deep realize."""

    def probe(mult):
        dd = DistributedDomain(16, 16, 16)
        dd.set_radius(Radius.constant(1))
        dd.add_data("q")
        if mult > 1:
            dd.set_halo_multiplier(mult)
        return dd

    assert (
        probe(1).tune_key("exchange").digest()
        != probe(4).tune_key("exchange").digest()
    )
    # the temporally-blocked routes keep keying by the USER radius — there
    # the multiplier is the tuned axis, not a key axis
    assert (
        probe(1).tune_key("stream").digest()
        == probe(4).tune_key("stream").digest()
    )


def test_autotune_exchange_searches_and_persists(tune_dir):
    dd, _ = _build(radius=1)
    report = autotune_exchange(dd, reps=1, rt=0.0)
    assert report.source == "search"
    assert report.trials == len(EXCHANGE_ROUTES)
    assert report.config["exchange_route"] in EXCHANGE_ROUTES
    # warm cache: zero trials
    again = autotune_exchange(dd, reps=1, rt=0.0)
    assert again.cache_hit and again.trials == 0
    assert again.config == report.config
    # the very next realize of this workload picks the winner up
    dd2, _ = _build(radius=1)
    assert dd2.exchange_route() == report.config["exchange_route"]


def test_packed_counters_and_route_event(tmp_path):
    telemetry.enable(dir=str(tmp_path))
    telemetry.reset()
    try:
        dd, _ = _build("zpack_pallas", radius=2)
        dd.exchange()
        snap = telemetry.snapshot()["counters"]
        assert snap[tm.EXCHANGE_PACKED_BYTES] > 0
        assert snap[tm.EXCHANGE_PACKED_KERNELS] > 0
        import json

        events = [
            json.loads(line)
            for line in open(telemetry.event_log_path())
        ]
        route_events = [e for e in events if e["event"] == tm.EVENT_EXCHANGE_ROUTE]
        assert route_events and route_events[-1]["route"] == "zpack_pallas"
        assert route_events[-1]["source"] == "explicit"
    finally:
        telemetry.disable()
    # direct route moves nothing through the packed counters (always-live
    # counters: compare deltas), and snapshots still seed them
    c0 = telemetry.snapshot()["counters"][tm.EXCHANGE_PACKED_BYTES]
    dd, _ = _build("direct", radius=2)
    dd.exchange()
    assert telemetry.snapshot()["counters"][tm.EXCHANGE_PACKED_BYTES] == c0


def test_ypack_counters_add_y_messages():
    """The yzpack routes' analytic packed traffic = the zpack model PLUS
    the sublane-major y messages (depth * X * Z per quantity slice per
    direction, no explicit pad) — per engaged sweep."""
    from stencil_tpu.ops.exchange import ypack_message_stats

    def delta(route):
        before = telemetry.snapshot()["counters"]
        dd, _ = _build(route, radius=2)
        dd.exchange()
        after = telemetry.snapshot()["counters"]
        raw = dd.local_spec().raw_size()
        return (
            after[tm.EXCHANGE_PACKED_BYTES] - before[tm.EXCHANGE_PACKED_BYTES],
            after[tm.EXCHANGE_PACKED_KERNELS]
            - before[tm.EXCHANGE_PACKED_KERNELS],
            raw,
            dd.num_subdomains(),
        )

    zb, zk, raw, n_doms = delta("zpack_pallas")
    yb, yk, _, _ = delta("yzpack_pallas")
    nb, nk = ypack_message_stats((raw.x, raw.y, raw.z), 2, 2, [4])
    assert yb - zb == nb * n_doms
    assert yk - zk == nk * n_doms


def test_pre_ypack_cache_entry_stays_warm(tune_dir):
    """The route vocabulary grew with NO schema bump: an entry persisted
    before the y routes existed (a zpack winner) is still consulted, and a
    persisted yzpack winner resolves on the next realize."""
    probe = DistributedDomain(16, 16, 16)
    probe.set_radius(Radius.constant(2))
    probe.add_data("q0")
    key = probe.tune_key("exchange")
    tune.record_config(key, {"exchange_route": "zpack_pallas"})  # pre-ypack era
    dd, _ = _build()
    assert dd.exchange_route() == "zpack_pallas"
    tune.record_config(key, {"exchange_route": "yzpack_pallas"})
    tune.reset_memo()
    dd, _ = _build()
    assert dd.exchange_route() == "yzpack_pallas"
