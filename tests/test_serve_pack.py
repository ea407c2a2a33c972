"""Tier-1: serving throughput packing (serve/pack.py + the scheduler in
serve/server.py) — the batch planner and sub-slice bin-packer units, and
the bitwise contract of packed dispatch against a serial twin across the
hard mixes: uneven shards, bf16 fields, fused multi-quantity domains, a
mixed queue where only a subset batches, and a fault injected against one
member of a batch.  All in-process; the subprocess packed legs are
``scripts/run_soak.py --serve`` (tier-2 ``slow``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from stencil_tpu import telemetry
from stencil_tpu.core.radius import Radius
from stencil_tpu.domain import DistributedDomain
from stencil_tpu.models.jacobi import Jacobi3D
from stencil_tpu.resilience import inject
from stencil_tpu.serve import (
    ACTIVE,
    AOTCache,
    AdmissionRefused,
    QUARANTINED,
    Request,
    StencilServer,
    TenantSpec,
    pack,
)
from stencil_tpu.resilience.taxonomy import OverloadError
from stencil_tpu.telemetry import names as tm


@pytest.fixture(autouse=True)
def _clear_fault_plan():
    yield
    inject.set_plan(None)


class FakeClock:
    def __init__(self, t: float = 1000.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


def make_server(**kw) -> StencilServer:
    kw.setdefault("clock", FakeClock())
    kw.setdefault("sleep", lambda s: None)
    kw.setdefault("aot", AOTCache(stamp_dir="", clock=kw["clock"]))
    return StencilServer(**kw)


def _counter(name: str) -> int:
    return telemetry.snapshot()["counters"][name]


# --- planner units (no dispatches: fake models) ------------------------------


class _FakeDev:
    def __init__(self, id):
        self.id = id


class _FakeMesh:
    def __init__(self, ids):
        self.devices = np.array([_FakeDev(i) for i in ids], dtype=object)


class _FakeKey:
    def __init__(self, digest):
        self._d = digest

    def digest(self):
        return self._d


class _Dim3:
    def __init__(self, x, y, z):
        self.x, self.y, self.z = x, y, z


class _FakeDD:
    def __init__(self, digest="g", ids=(0, 1), nbytes=1024, size=(8, 8, 8)):
        self._realized = True
        self._curr = {"q": np.zeros(nbytes // 4, np.float32)}
        self.mesh = _FakeMesh(ids)
        self._digest = digest
        self._size = _Dim3(*size)
        self._handles = ["q"]

    def tune_key(self, route):
        return _FakeKey(self._digest)

    def exchange_route(self):
        return "direct"

    def size(self):
        return self._size

    def field_dtype(self, h):
        return "float32"


class _FakeModel:
    def __init__(self, **kw):
        self.dd = _FakeDD(**kw)
        self._step = object()

    def rebuild_after_reshard(self):
        pass


class _FakeTenant:
    def __init__(self, model):
        self.model = model

    def active(self):
        return True


def _pending(*tenant_ids, steps=1):
    return [Request(tenant=t, steps=steps) for t in tenant_ids]


class TestBatchPlanner:
    def test_groups_matching_geometry_oldest_per_tenant(self):
        tenants = {t: _FakeTenant(_FakeModel()) for t in ("a", "b", "c")}
        pending = _pending("a", "a", "b", "c")
        group = pack.plan_batches(pending, tenants, ["a", "b", "c"], 8)
        # one request per tenant (the oldest), all three geometry-matched
        assert [r.tenant for r in group] == ["a", "b", "c"]
        assert group[0] is pending[0]  # a's OLDEST, not its second request

    def test_rotation_orders_the_group(self):
        tenants = {t: _FakeTenant(_FakeModel()) for t in ("a", "b", "c")}
        group = pack.plan_batches(
            _pending("a", "b", "c"), tenants, ["c", "a", "b"], 8
        )
        assert [r.tenant for r in group] == ["c", "a", "b"]

    def test_batch_max_caps_the_group(self):
        tenants = {t: _FakeTenant(_FakeModel()) for t in "abcd"}
        group = pack.plan_batches(_pending(*"abcd"), tenants, list("abcd"), 2)
        assert [r.tenant for r in group] == ["a", "b"]

    def test_only_the_matching_subset_groups(self):
        """Mixed queue: two tenants share a geometry, one differs, one has
        no realized domain — only the matching pair batches."""
        tenants = {
            "a": _FakeTenant(_FakeModel(digest="g1")),
            "b": _FakeTenant(_FakeModel(digest="OTHER")),
            "c": _FakeTenant(_FakeModel(digest="g1")),
        }
        tenants["d"] = _FakeTenant(_FakeModel(digest="g1"))
        tenants["d"].model.dd._realized = False
        group = pack.plan_batches(
            _pending(*"abcd"), tenants, list("abcd"), 8
        )
        assert [r.tenant for r in group] == ["a", "c"]

    def test_mismatched_steps_do_not_group(self):
        tenants = {t: _FakeTenant(_FakeModel()) for t in ("a", "b")}
        pending = [Request(tenant="a", steps=1), Request(tenant="b", steps=2)]
        assert pack.plan_batches(pending, tenants, ["a", "b"], 8) is None

    def test_disabled_or_singleton_returns_none(self):
        tenants = {"a": _FakeTenant(_FakeModel())}
        assert pack.plan_batches(_pending("a"), tenants, ["a"], 8) is None
        tenants["b"] = _FakeTenant(_FakeModel())
        assert pack.plan_batches(_pending("a", "b"), tenants, ["a", "b"], 1) is None


class TestSubslicePlanner:
    def test_greedy_big_tenant_takes_the_fast_slice(self):
        """The measured-QAP analog: with per-slice link docs, the biggest
        tenant (greedy first) takes the slice whose slowest x-link is
        fastest; the small tenant gets the remainder."""
        big = _FakeModel(digest="A", nbytes=1 << 20)
        small = _FakeModel(digest="B", nbytes=1 << 10)
        fleet = [_FakeDev(i) for i in range(4)]

        def link(devices):
            fast = devices[0].id == 0  # slice 0 holds the fast links
            g = 100.0 if fast else 1.0
            return {"axes": {"x": {"low": {"gbps_min": g}}}}

        got = pack.plan_subslices(
            [(Request(tenant="small"), small), (Request(tenant="big"), big)],
            fleet,
            link,
        )
        by = {r.tenant: [d.id for d in devs] for r, _m, devs in got}
        assert by["big"] == [0, 1] and by["small"] == [2, 3]

    def test_slices_are_disjoint_and_cover_distinct_devices(self):
        models = [
            _FakeModel(digest=str(i), nbytes=(i + 1) * 4096) for i in range(3)
        ]
        fleet = [_FakeDev(i) for i in range(8)]
        got = pack.plan_subslices(
            [(Request(tenant=str(i)), m) for i, m in enumerate(models)],
            fleet,
        )
        sets = [frozenset(d.id for d in devs) for _r, _m, devs in got]
        assert all(len(s) == 2 for s in sets)  # 8 // 3 tenants = width 2
        assert len(frozenset.union(*sets)) == 6  # pairwise disjoint

    def test_single_tenant_or_empty_fleet_returns_none(self):
        m = _FakeModel()
        assert pack.plan_subslices([(Request(tenant="a"), m)], [_FakeDev(0)]) is None
        assert (
            pack.plan_subslices(
                [(Request(tenant="a"), m), (Request(tenant="b"), m)],
                [_FakeDev(0)],
            )
            is None
        )


# --- the bitwise contract: packed vs a serial twin ---------------------------


def _mean6_kernel(views, info):
    src = views["q"]
    val = (
        src.sh(1, 0, 0)
        + src.sh(-1, 0, 0)
        + src.sh(0, 1, 0)
        + src.sh(0, -1, 0)
        + src.sh(0, 0, 1)
        + src.sh(0, 0, -1)
    ) / 6.0
    return {"q": val}


def _coupled_kernel(views, info):
    """Fused multi-quantity update: each field's next value reads BOTH."""
    q, r = views["q"], views["r"]
    return {
        "q": (q.sh(1, 0, 0) + q.sh(-1, 0, 0) + r.center()) / 3.0,
        "r": (r.sh(0, 0, 1) + r.sh(0, 0, -1) + q.center()) / 3.0,
    }


class _DomainModel:
    """Minimal serving model around a raw DistributedDomain + make_step:
    the hard-mix rigs (uneven shards, bf16 fields, fused multi-quantity)
    without Jacobi3D's forcing baked in."""

    def __init__(self, shape, kernel, quantities=("q",), dtype=jnp.float32,
                 devices=None, seed=7):
        self.dd = DistributedDomain(*shape)
        self.dd.set_radius(Radius.constant(1))
        handles = [self.dd.add_data(n, dtype=dtype) for n in quantities]
        if devices is not None:
            self.dd.set_devices(devices)
        self.dd.realize()
        rng = np.random.default_rng(seed)
        for h in handles:
            self.dd.set_quantity(
                h, rng.random(shape).astype(np.dtype(dtype) if dtype != jnp.bfloat16 else np.float32)
            )
        self.handles = handles
        self._kernel = kernel
        self._step = self.dd.make_step(kernel, donate=False)

    def step(self, n):
        self.dd.run_step(self._step, n)

    def rebuild_after_reshard(self):
        self._step = self.dd.make_step(self._kernel, donate=False)

    def fields(self):
        return {h.name: self.dd.quantity_to_host(h) for h in self.handles}


def _twin(factory, tenant_ids):
    """Two identical tenant fleets from one factory (same seeds)."""
    return (
        {t: factory(i) for i, t in enumerate(tenant_ids)},
        {t: factory(i) for i, t in enumerate(tenant_ids)},
    )


def _rounds(srv, order, rounds, steps=1):
    for _ in range(rounds):
        for tid in order:
            try:
                srv.submit(Request(tenant=tid, steps=steps))
            except (OverloadError, AdmissionRefused):
                pass
        srv.drain()


def _serve_pair(packed_models, serial_models, rounds=3, steps=1, **packed_kw):
    """Serve the same load through a packed server and a serial twin."""
    order = sorted(packed_models)
    for models, kw in ((packed_models, packed_kw), (serial_models, {})):
        srv = make_server(queue_max=32, **kw)
        try:
            for tid in order:
                srv.add_tenant(TenantSpec(tenant_id=tid), models[tid])
            _rounds(srv, order, rounds, steps)
        finally:
            srv.close()
        if models is packed_models:
            packed_srv = srv
    return packed_srv


def _assert_fields_equal(a: "_DomainModel", b: "_DomainModel"):
    fa, fb = a.fields(), b.fields()
    assert fa.keys() == fb.keys()
    for name in fa:
        np.testing.assert_array_equal(fa[name], fb[name])


class TestBatchedBitwise:
    def test_uneven_shards_batched_equals_serial(self):
        """17^3 over an 8-device mesh: every shard boundary lands uneven,
        and the batched (vmap) dispatch must still be bitwise."""
        packed, serial = _twin(
            lambda i: _DomainModel(
                (17, 17, 17), _mean6_kernel, seed=7 + i,
                devices=jax.devices()[:8],
            ),
            ("tenant-a", "tenant-b", "tenant-c"),
        )
        before = _counter(tm.SERVE_BATCH_DISPATCHES)
        _serve_pair(packed, serial, rounds=2, batch_max=8)
        assert _counter(tm.SERVE_BATCH_DISPATCHES) > before  # really batched
        for tid in packed:
            _assert_fields_equal(packed[tid], serial[tid])

    def test_bf16_fields_batched_equals_serial(self):
        packed, serial = _twin(
            lambda i: _DomainModel(
                (8, 8, 8), _mean6_kernel, dtype=jnp.bfloat16, seed=3 + i,
                devices=jax.devices()[:8],
            ),
            ("tenant-a", "tenant-b"),
        )
        before = _counter(tm.SERVE_BATCH_DISPATCHES)
        _serve_pair(packed, serial, rounds=2, batch_max=8)
        assert _counter(tm.SERVE_BATCH_DISPATCHES) > before
        for tid in packed:
            _assert_fields_equal(packed[tid], serial[tid])

    def test_fused_multi_quantity_batched_equals_serial(self):
        """Two coupled quantities per tenant: the stacked dispatch carries
        the whole fused state dict, and both fields stay bitwise."""
        packed, serial = _twin(
            lambda i: _DomainModel(
                (8, 8, 8), _coupled_kernel, quantities=("q", "r"),
                seed=11 + i, devices=jax.devices()[:8],
            ),
            ("tenant-a", "tenant-b", "tenant-c"),
        )
        before = _counter(tm.SERVE_BATCH_DISPATCHES)
        _serve_pair(packed, serial, rounds=2, steps=2, batch_max=8)
        assert _counter(tm.SERVE_BATCH_DISPATCHES) > before
        for tid in packed:
            _assert_fields_equal(packed[tid], serial[tid])

    def test_mixed_queue_batches_only_the_matching_subset(self):
        """Mixed-priority queue where only a subset is batchable: the two
        geometry twins batch, the odd-shaped high-priority tenant rides
        serial — everyone bitwise vs the all-serial twin."""

        def factory(i):
            shape = (8, 8, 8) if i < 2 else (10, 10, 10)
            return _DomainModel(
                shape, _mean6_kernel, seed=5 + i, devices=jax.devices()[:8]
            )

        packed, serial = _twin(factory, ("tenant-a", "tenant-b", "tenant-c"))
        order = sorted(packed)
        before = _counter(tm.SERVE_BATCH_DISPATCHES)
        for models, kw in ((packed, {"batch_max": 8}), (serial, {})):
            srv = make_server(queue_max=32, **kw)
            try:
                for tid in order:
                    srv.add_tenant(
                        TenantSpec(
                            tenant_id=tid,
                            priority=1 if tid == "tenant-c" else 0,
                        ),
                        models[tid],
                    )
                _rounds(srv, order, rounds=2)
            finally:
                srv.close()
        assert _counter(tm.SERVE_BATCH_DISPATCHES) > before
        for tid in packed:
            _assert_fields_equal(packed[tid], serial[tid])


class TestFaultInBatch:
    def test_poison_against_one_member_falls_back_serial_bitwise(self):
        """A poison_request seeded against one tenant of a batch: the group
        falls back to serial re-execution, the poisoned tenant is evicted
        through its unchanged envelope, and every healthy member's fields
        stay bitwise identical to the fault-free serial twin."""
        ids = ("tenant-a", "tenant-b", "tenant-c")
        packed, serial = _twin(
            lambda i: _DomainModel(
                (8, 8, 8), _mean6_kernel, seed=7 + i,
                devices=jax.devices()[:8],
            ),
            ids,
        )
        fb_before = _counter(tm.SERVE_BATCH_FALLBACKS)
        srv = make_server(queue_max=32, batch_max=8)
        try:
            for tid in ids:
                srv.add_tenant(TenantSpec(tenant_id=tid), packed[tid])
            inject.set_plan("execute:poison_request:serve:tenant-b@1")
            _rounds(srv, ids, rounds=3)
        finally:
            srv.close()
            inject.set_plan(None)
        tw = make_server(queue_max=32)
        try:
            for tid in ids:
                tw.add_tenant(TenantSpec(tenant_id=tid), serial[tid])
            _rounds(tw, ids, rounds=3)
        finally:
            tw.close()
        assert _counter(tm.SERVE_BATCH_FALLBACKS) > fb_before
        assert srv.tenants["tenant-b"].state == QUARANTINED
        assert srv.tenants["tenant-a"].state == ACTIVE
        assert srv.tenants["tenant-c"].state == ACTIVE
        _assert_fields_equal(packed["tenant-a"], serial["tenant-a"])
        _assert_fields_equal(packed["tenant-c"], serial["tenant-c"])


class TestSubsliceBitwise:
    def test_subslice_pack_is_disjoint_and_bitwise(self):
        """Two non-matching tenants bin-packed onto disjoint halves of the
        fleet: final meshes are disjoint, fields bitwise vs serial twins
        that never left the full fleet (mesh-shape independence)."""

        def factory(i):
            shape = (8, 8, 8) if i == 0 else (10, 10, 10)
            return _DomainModel(
                shape, _mean6_kernel, seed=21 + i, devices=jax.devices()[:8]
            )

        packed, serial = _twin(factory, ("tenant-a", "tenant-b"))
        before = _counter(tm.SERVE_SUBSLICE_DISPATCHES)
        _serve_pair(
            packed, serial, rounds=2, subslice=True, fleet=jax.devices()[:8]
        )
        assert _counter(tm.SERVE_SUBSLICE_DISPATCHES) > before
        placed = [
            {d.id for d in packed[t].dd.mesh.devices.flat} for t in sorted(packed)
        ]
        assert placed[0] & placed[1] == set()  # disjoint sub-meshes
        assert all(len(s) == 4 for s in placed)  # 8 devices, 2 tenants
        for tid in packed:
            _assert_fields_equal(packed[tid], serial[tid])


# --- Jacobi end-to-end (the soak's in-process twin) --------------------------


class TestJacobiPacked:
    def test_jacobi_batched_equals_serial(self):
        def factory(i):
            m = Jacobi3D(8, 8, 8, devices=jax.devices()[:8])
            m.realize()
            return m

        packed, serial = _twin(factory, ("tenant-a", "tenant-b", "tenant-c"))
        before = _counter(tm.SERVE_BATCH_DISPATCHES)
        _serve_pair(packed, serial, rounds=3, batch_max=8)
        assert _counter(tm.SERVE_BATCH_DISPATCHES) > before
        for tid in packed:
            np.testing.assert_array_equal(
                packed[tid].temperature(), serial[tid].temperature()
            )


# --- drain truncation --------------------------------------------------------


class _HungModel:
    """A model whose tenant never drains: step() requeues nothing, but we
    keep the queue full by submitting faster than max_cycles allows."""

    def step(self, n):
        pass


class TestDrainTruncation:
    def test_drain_truncation_warns_and_counts(self, capsys):
        srv = make_server(queue_max=32)
        before = _counter(tm.SERVE_DRAIN_TRUNCATED)
        try:
            srv.add_tenant(TenantSpec(tenant_id="a"), _HungModel())
            for _ in range(5):
                srv.submit(Request(tenant="a"))
            srv.drain(max_cycles=2)
        finally:
            srv.close()
        assert _counter(tm.SERVE_DRAIN_TRUNCATED) == before + 1
        err = capsys.readouterr().err
        assert "max_cycles=2" in err and "3 request(s) still queued" in err

    def test_full_drain_stays_quiet(self, capsys):
        srv = make_server(queue_max=8)
        before = _counter(tm.SERVE_DRAIN_TRUNCATED)
        try:
            srv.add_tenant(TenantSpec(tenant_id="a"), _HungModel())
            srv.submit(Request(tenant="a"))
            srv.drain()
        finally:
            srv.close()
        assert _counter(tm.SERVE_DRAIN_TRUNCATED) == before
        assert "drain truncated" not in capsys.readouterr().err


# --- ledger + contract wiring ------------------------------------------------


class TestThroughputLedger:
    def test_ledger_ingests_serve_throughput_higher_is_better(self, tmp_path):
        import json

        from stencil_tpu.telemetry.ledger import entries_from_artifact

        doc = {
            "bench": "serve_soak",
            "isolation_ok": True,
            "p99_ms": 12.5,
            "shed_rate": 0.0,
            "requests": 40,
            "tenants": [{"tenant": "a"}],
            "throughput": {
                "requests_per_s": 9.5,
                "mcells_per_s": 1.25,
                "batch_max": 8,
                "subslice": False,
            },
        }
        path = str(tmp_path / "serve_summary.json")
        with open(path, "w") as f:
            json.dump(doc, f)
        entries = {e["key"]: e for e in entries_from_artifact(path)}
        tp = entries["serve:throughput"]
        assert tp["value"] == 9.5 and tp["unit"] == "1/s"
        assert "better" not in tp  # higher-is-better default: drops flag
        assert tp["mcells_per_s"] == 1.25 and tp["batch_max"] == 8
        # the SLO series keep their lower-is-better pin
        assert entries["serve:p99_ms"]["better"] == "lower"


class TestBatchIsolationContract:
    def test_batched_mode_gathering_collective_fires(self):
        """A synthetic batched artifact whose program mixes batch members
        through a collective over the BATCH axis: batch-isolation must
        fire (the canonical clean programs are tests/analysis_fixtures +
        analysis/programs.py)."""
        from stencil_tpu import analysis
        from stencil_tpu.analysis.contracts import BatchIsolation

        def leaky(stacked):
            def member(c):
                return c * 2.0 - jax.lax.pmean(c, axis_name="batch")

            return jax.vmap(member, axis_name="batch")(stacked)

        art = analysis.trace_artifact(
            leaky,
            jnp.ones((4, 8, 8), jnp.float32),
            label="test:batched-leak",
            kind="serve",
            meta={"mode": "batched", "batch": 4, "mesh_axes": ("x", "y", "z")},
        )
        findings = BatchIsolation().check(art)
        assert findings, "cross-batch collective must trip batch-isolation"
        assert any("batch" in f.message for f in findings)
