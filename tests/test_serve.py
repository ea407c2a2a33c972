"""Tier-1: the multi-tenant serving layer — the OVERLOAD taxonomy class,
admission control (VMEM verdict, AOT budget, warmth stamps), bounded-queue
shedding, per-tenant fault isolation (bitwise, >= 3 tenants), jittered
retry budgets, elasticity hysteresis, and the status/ledger wiring.  All
in-process with a fake clock and zero real sleeps; the subprocess serving
chaos soak (``scripts/run_soak.py --serve``) is tier-2 ``slow``."""

import json
import os
import random
import subprocess
import sys

import jax
import numpy as np
import pytest

from stencil_tpu.models.jacobi import Jacobi3D
from stencil_tpu.resilience import inject
from stencil_tpu.resilience.retry import (
    RetryBudget,
    RetryPolicy,
    execute_with_retry,
)
from stencil_tpu.resilience.taxonomy import (
    FailureClass,
    OverloadError,
    classify,
)
from stencil_tpu.serve import (
    ACTIVE,
    AOTCache,
    AdmissionRefused,
    BoundedQueue,
    ElasticityPolicy,
    QUARANTINED,
    Request,
    Response,
    StencilServer,
    Tenant,
    TenantSpec,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clear_fault_plan():
    yield
    inject.set_plan(None)


class FakeClock:
    """Injectable monotonic clock: tests advance time, nothing sleeps."""

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


# --- the OVERLOAD taxonomy class --------------------------------------------


class TestOverloadTaxonomy:
    def test_pinned_wordings_classify_overload(self):
        """Every OverloadError refusal path's message classifies OVERLOAD
        from the TEXT alone (the marker path, not just the typed path) —
        a shed surviving a str() round trip still refuses blind retry."""
        for why in ("queue_full", "deadline", "compile_budget"):
            e = OverloadError(why=why)
            assert classify(e) is FailureClass.OVERLOAD
            assert classify(RuntimeError(str(e))) is FailureClass.OVERLOAD

    def test_deadline_shed_outranks_transient(self):
        """The deadline shed's wording mentions the exceeded deadline —
        a transient marker — but must classify OVERLOAD: retrying in
        place against a saturated queue is the herd the shed breaks."""
        msg = str(OverloadError(why="deadline"))
        assert "deadline exceeded" in msg  # brushes the transient marker
        assert classify(RuntimeError(msg)) is FailureClass.OVERLOAD
        # the bare gRPC wording is still transient
        assert (
            classify(RuntimeError("deadline exceeded"))
            is FailureClass.TRANSIENT_RUNTIME
        )

    def test_overload_is_never_blindly_retried(self):
        """execute_with_retry only re-runs TRANSIENT_RUNTIME: an overload
        propagates on the first attempt with zero sleeps."""
        sleeps = []
        calls = [0]

        def saturated():
            calls[0] += 1
            raise OverloadError(why="queue_full", queue_depth=64)

        with pytest.raises(OverloadError):
            execute_with_retry(saturated, sleep=sleeps.append)
        assert calls == [1] and sleeps == []

    def test_overload_carries_backoff_hint(self):
        e = OverloadError(why="queue_full", queue_depth=7, retry_after_s=1.5)
        assert e.retry_after_s == 1.5 and e.queue_depth == 7
        assert "retry after 1.50s" in str(e)

    def test_fault_plan_parses_serving_classes(self):
        plan = inject.FaultPlan.parse(
            "dispatch:overload:serve:*@1,execute:poison_request:serve:tenant-b,"
            "execute:slow_tenant:serve:tenant-a*2"
        )
        kinds = []
        for ent in plan._entries:
            kinds.append((ent.cls.value if ent.cls else None, ent.slow))
        assert kinds == [
            ("overload", None),
            ("divergence", None),  # poison_request IS the divergence class
            (None, "slow_tenant"),
        ]


# --- jittered backoff + shared retry budgets --------------------------------


class TestRetryJitterAndBudget:
    def test_zero_jitter_recovers_the_deterministic_schedule(self):
        p = RetryPolicy(backoff_base_s=0.25, multiplier=2.0, jitter=0.0)
        assert [p.delay_s(a) for a in range(3)] == [0.25, 0.5, 1.0]

    def test_seeded_jitter_is_deterministic_and_banded(self):
        p = RetryPolicy(backoff_base_s=1.0, multiplier=2.0, jitter=0.1)
        a = [p.delay_s(n, rng=random.Random(7)) for n in range(4)]
        b = [p.delay_s(n, rng=random.Random(7)) for n in range(4)]
        assert a == b  # pinned by the rng seed
        for n, d in enumerate(a):
            base = 2.0**n
            assert base * 0.9 <= d <= base * 1.1

    def test_jitter_env_knob(self, monkeypatch):
        monkeypatch.setenv("STENCIL_RETRY_JITTER", "0.5")
        assert RetryPolicy.from_env().jitter == 0.5
        monkeypatch.setenv("STENCIL_RETRY_JITTER", "7")  # clamped: spread
        assert RetryPolicy.from_env().jitter == 1.0  # past 1 goes negative

    def test_budget_charges_and_replenishes(self):
        b = RetryBudget(2, label="t")
        assert b.try_charge() and b.try_charge() and not b.try_charge()
        b.replenish()
        assert b.remaining == 2

    def test_shared_budget_caps_retries_across_calls(self):
        """Policy allows 3 retries per call, but a shared budget of 2
        spans calls: the second flaky call gets ONE retry, not three."""
        budget = RetryBudget(2)
        policy = RetryPolicy(max_retries=3, backoff_base_s=0.0, jitter=0.0)

        def flaky_once(state=[0]):
            state[0] += 1
            if state[0] == 1:
                raise RuntimeError("unavailable: connection dropped")

        execute_with_retry(flaky_once, policy=policy, budget=budget, sleep=lambda s: None)
        assert budget.remaining == 1

        def always_flaky():
            raise RuntimeError("unavailable: connection dropped")

        calls = []
        with pytest.raises(RuntimeError):
            execute_with_retry(
                always_flaky,
                policy=policy,
                budget=budget,
                sleep=calls.append,
            )
        assert len(calls) == 1  # one budgeted retry, then exhaustion
        assert budget.remaining == 0


# --- the bounded queue -------------------------------------------------------


class TestBoundedQueue:
    def test_full_queue_refuses_with_classified_overload(self):
        q = BoundedQueue(2)
        q.push(Request(tenant="a"), now=0.0)
        q.push(Request(tenant="a"), now=0.0)
        with pytest.raises(OverloadError) as ei:
            q.push(Request(tenant="b"), now=0.0)
        assert classify(ei.value) is FailureClass.OVERLOAD
        assert ei.value.queue_depth == 2
        assert ei.value.retry_after_s is not None  # backpressure hint

    def test_shed_expired_oldest_first(self):
        q = BoundedQueue(8)
        keep = Request(tenant="a", deadline_s=100.0)
        old = Request(tenant="b", deadline_s=1.0)
        older = Request(tenant="c", deadline_s=2.0)
        q.push(older, now=0.0)
        q.push(old, now=0.5)
        q.push(keep, now=1.0)
        shed = q.shed_expired(now=50.0)
        assert [r.tenant for r in shed] == ["c", "b"]  # oldest first
        assert q.peek_all() == [keep]

    def test_priority_make_room_takes_the_lowest(self):
        q = BoundedQueue(8)
        q.push(Request(tenant="lo", priority=0), now=0.0)
        q.push(Request(tenant="mid", priority=1), now=0.0)
        victim = q.shed_lowest_priority(below=2)
        assert victim.tenant == "lo"
        assert q.shed_lowest_priority(below=0) is None  # nobody below

    def test_take_is_round_robin_by_rotation(self):
        q = BoundedQueue(8)
        for t in ("a", "a", "b", "c"):
            q.push(Request(tenant=t), now=0.0)
        assert q.take(["b", "c", "a"]).tenant == "b"
        assert q.take(["c", "a", "b"]).tenant == "c"
        assert q.take(["a", "b", "c"]).tenant == "a"
        assert q.take(["b", "c", "a"]).tenant == "a"  # FIFO fallback
        assert q.take(["a"]) is None


# --- admission ---------------------------------------------------------------


class TestAdmission:
    def test_unknown_tenant_is_fatal(self):
        srv = make_server()
        try:
            with pytest.raises(AdmissionRefused) as ei:
                srv.submit(Request(tenant="ghost"))
            assert ei.value.failure_class is FailureClass.FATAL
        finally:
            srv.close()

    def test_evicted_tenant_refusal_is_fatal(self):
        srv = make_server()
        try:
            t = srv.add_tenant(TenantSpec(tenant_id="a"))
            t.quarantine("poisoned")
            with pytest.raises(AdmissionRefused) as ei:
                srv.submit(Request(tenant="a"))
            assert ei.value.failure_class is FailureClass.FATAL
            assert "quarantined" in str(ei.value)
        finally:
            srv.close()

    def test_vmem_verdict_refuses_an_oversized_plan(self):
        """The static VMEM verdict (analysis.check_vmem) runs at admission:
        a plan the compiler would refuse is rejected as a degradable
        VMEM_OOM before it can waste a dispatch slot."""
        m = Jacobi3D(16, 16, 16, devices=jax.devices()[:8])
        m.realize()
        srv = make_server()
        try:
            srv.add_tenant(
                TenantSpec(
                    tenant_id="big", plan={"route": "plane", "m": 10**6}
                ),
                m,
            )
            with pytest.raises(AdmissionRefused) as ei:
                srv.submit(Request(tenant="big"))
            assert ei.value.failure_class is FailureClass.VMEM_OOM
        finally:
            srv.close()

    def test_cold_compile_over_budget_refuses_then_warms(self):
        """A cold key whose compile blows the admission budget is refused
        (classified OVERLOAD, retryable) but the executable is KEPT: the
        re-submission admits instantly and the build never re-runs."""
        clk = FakeClock()
        srv = make_server(clock=clk, compile_budget_s=0.5)
        builds = [0]

        def build():
            builds[0] += 1
            clk.advance(2.0)  # well past the 0.5s budget
            return object()

        try:
            srv.add_tenant(TenantSpec(tenant_id="a"))
            srv.register_workload("k1", build)
            with pytest.raises(OverloadError) as ei:
                srv.submit(Request(tenant="a", key_digest="k1"))
            assert ei.value.why == "compile_budget"
            assert classify(ei.value) is FailureClass.OVERLOAD
            srv.submit(Request(tenant="a", key_digest="k1"))  # now warm
            assert builds == [1]
            assert srv.queue.depth() == 1
        finally:
            srv.close()

    def test_warm_key_admits_without_building(self):
        clk = FakeClock()
        srv = make_server(clock=clk, compile_budget_s=0.5)
        try:
            srv.add_tenant(TenantSpec(tenant_id="a"))
            srv.aot.compile("k1", lambda: object(), label="a")
            srv.register_workload("k1", lambda: pytest.fail("rebuilt a warm key"))
            srv.submit(Request(tenant="a", key_digest="k1"))
        finally:
            srv.close()


class TestAOTStamps:
    def test_stamp_survives_a_process_restart(self, tmp_path):
        """A key compiled by one cache instance is ``stamped`` for the
        next (new process): the re-compile runs WITHOUT the budget refusal
        — a persistent-XLA-cache read."""
        d = str(tmp_path / "aot")
        clk = FakeClock()
        first = AOTCache(stamp_dir=d, clock=clk)

        def slow_build():
            clk.advance(3.0)
            return object()

        first.compile("k", slow_build)
        fresh = AOTCache(stamp_dir=d, clock=clk)
        assert fresh.stamped("k") and not fresh.warm("k")
        # over budget but stamped: no refusal
        exe, seconds = fresh.compile("k", slow_build, budget_s=0.1)
        assert exe is not None and seconds > 0.1

    def test_corrupt_or_stale_stamp_is_a_miss(self, tmp_path):
        d = str(tmp_path / "aot")
        clk = FakeClock()
        cache = AOTCache(stamp_dir=d, clock=clk)
        cache.compile("k", lambda: object())
        path = os.path.join(d, "k.json")
        with open(path, "w") as f:
            f.write("{not json")
        assert not AOTCache(stamp_dir=d, clock=clk).stamped("k")
        with open(path, "w") as f:
            json.dump({"schema": 999, "jax": "x", "jaxlib": "y"}, f)
        assert not AOTCache(stamp_dir=d, clock=clk).stamped("k")


# --- shedding + deadlines ----------------------------------------------------


class TestShedding:
    def test_expired_requests_are_shed_at_dispatch(self):
        clk = FakeClock()
        srv = make_server(clock=clk, default_deadline_s=5.0)
        try:
            srv.add_tenant(TenantSpec(tenant_id="a"))
            srv.submit(Request(tenant="a"))
            clk.advance(6.0)  # past the propagated deadline
            out = srv.cycle()
            assert len(out) == 1 and not out[0].ok
            assert out[0].failure_class == FailureClass.OVERLOAD.value
            assert "deadline" in out[0].error
            assert srv.tenants["a"].shed == 1
            assert srv.tenants["a"].state == ACTIVE  # load shed, not evicted
        finally:
            srv.close()

    def test_full_queue_sheds_expired_before_refusing(self):
        clk = FakeClock()
        srv = make_server(clock=clk, queue_max=2, default_deadline_s=5.0)
        try:
            srv.add_tenant(TenantSpec(tenant_id="a"))
            srv.submit(Request(tenant="a"))
            srv.submit(Request(tenant="a"))
            clk.advance(6.0)  # both queued requests are now expired
            srv.submit(Request(tenant="a"))  # sheds them, admits
            assert srv.queue.depth() == 1
            assert srv.tenants["a"].shed == 2
        finally:
            srv.close()

    def test_higher_priority_arrival_shes_the_lowest(self):
        srv = make_server(queue_max=2)
        try:
            srv.add_tenant(TenantSpec(tenant_id="lo", priority=0))
            srv.add_tenant(TenantSpec(tenant_id="hi", priority=1))
            srv.submit(Request(tenant="lo"))
            srv.submit(Request(tenant="lo"))
            srv.submit(Request(tenant="hi", priority=1))  # makes room
            assert {r.tenant for r in srv.queue.peek_all()} == {"lo", "hi"}
            assert srv.tenants["lo"].shed == 1
        finally:
            srv.close()

    def test_equal_priority_arrival_is_backpressured(self):
        srv = make_server(queue_max=2)
        try:
            srv.add_tenant(TenantSpec(tenant_id="a"))
            srv.submit(Request(tenant="a"))
            srv.submit(Request(tenant="a"))
            with pytest.raises(OverloadError) as ei:
                srv.submit(Request(tenant="a"))
            assert ei.value.why == "queue_full"
            assert srv.queue.depth() == 2  # nobody was evicted for an equal
        finally:
            srv.close()


# --- the per-tenant envelope (unit) -----------------------------------------


class _LadderModel:
    """Fake model: a two-rung descent ladder, then exhaustion."""

    def __init__(self, rungs: int = 2):
        self.rungs = rungs
        self.descents = 0

    def step_down(self, cls) -> bool:
        if self.descents >= self.rungs:
            return False
        self.descents += 1
        return True

    def step(self, n):
        pass


class TestTenantEnvelope:
    def test_vmem_oom_descends_then_quarantines_on_exhaustion(self):
        t = Tenant(TenantSpec(tenant_id="a", max_rungs=5), _LadderModel(2))
        assert t.handle_failure(FailureClass.VMEM_OOM) == "degrade"
        assert t.handle_failure(FailureClass.VMEM_OOM) == "degrade"
        assert t.handle_failure(FailureClass.VMEM_OOM) == "evict"
        assert t.state == QUARANTINED and "ladder exhausted" in t.why

    def test_max_rungs_bounds_the_descents(self):
        t = Tenant(TenantSpec(tenant_id="a", max_rungs=1), _LadderModel(99))
        assert t.handle_failure(FailureClass.COMPILE_REJECT) == "degrade"
        assert t.handle_failure(FailureClass.VMEM_OOM) == "evict"
        assert t.state == QUARANTINED

    def test_divergence_evicts_only_this_tenant(self):
        t = Tenant(TenantSpec(tenant_id="a"))
        other = Tenant(TenantSpec(tenant_id="b"))
        assert t.handle_failure(FailureClass.DIVERGENCE, "poisoned") == "evict"
        assert t.state == QUARANTINED and not t.active()
        assert other.state == ACTIVE  # untouched

    def test_transient_and_preempted_routing(self):
        t = Tenant(TenantSpec(tenant_id="a"))
        assert t.handle_failure(FailureClass.TRANSIENT_RUNTIME) == "retry_exhausted"
        assert t.handle_failure(FailureClass.PREEMPTED) == "propagate"
        assert t.state == ACTIVE


# --- fault isolation, bitwise (>= 3 tenants, real fields) -------------------


def _serve_rounds(srv, order, rounds):
    """Submit one request per tenant per round (skipping refused tenants),
    draining between rounds; returns every response."""
    out = []
    for _ in range(rounds):
        for tid in order:
            try:
                srv.submit(Request(tenant=tid))
            except (OverloadError, AdmissionRefused):
                pass
        out.extend(srv.drain())
    return out


class TestTenantIsolation:
    """The isolation contract on REAL fields: an injected fault against one
    tenant leaves every other tenant's temperature bitwise identical to an
    unfaulted reference.  The subprocess chaos proof (separate reference
    process, sha256 digests in the soak artifact) is run_soak.py --serve."""

    def _models(self, n=3, size=8):
        out = {}
        for i in range(n):
            m = Jacobi3D(size, size, size, devices=jax.devices()[:8])
            m.realize()
            out[f"tenant-{chr(ord('a') + i)}"] = m
        return out

    def _reference(self, steps, size=8):
        m = Jacobi3D(size, size, size, devices=jax.devices()[:8])
        m.realize()
        if steps:
            m.step(steps)
        return m.temperature()

    def test_poison_request_evicts_only_its_tenant_bitwise(self):
        models = self._models()
        srv = make_server(queue_max=16)
        try:
            for tid, m in sorted(models.items()):
                srv.add_tenant(TenantSpec(tenant_id=tid), m)
            inject.set_plan("execute:poison_request:serve:tenant-b@1")
            _serve_rounds(srv, sorted(models), rounds=4)
        finally:
            srv.close()
        assert srv.tenants["tenant-b"].state == QUARANTINED
        assert srv.tenants["tenant-a"].state == ACTIVE
        assert srv.tenants["tenant-c"].state == ACTIVE
        # healthy tenants: all 4 rounds served, bitwise = reference
        want4 = self._reference(4)
        np.testing.assert_array_equal(models["tenant-a"].temperature(), want4)
        np.testing.assert_array_equal(models["tenant-c"].temperature(), want4)
        # the poisoned tenant stopped cleanly at its one completed step —
        # the fault never half-applied anything to its field either
        np.testing.assert_array_equal(
            models["tenant-b"].temperature(), self._reference(1)
        )
        # and re-submission is refused FATAL, not queued
        with pytest.raises(AdmissionRefused):
            srv.submit(Request(tenant="tenant-b"))

    def test_vmem_oom_stays_inside_its_envelope_bitwise(self):
        models = self._models()
        srv = make_server(queue_max=16)
        try:
            for tid, m in sorted(models.items()):
                srv.add_tenant(TenantSpec(tenant_id=tid), m)
            inject.set_plan("execute:vmem_oom:serve:tenant-c@1")
            _serve_rounds(srv, sorted(models), rounds=4)
        finally:
            srv.close()
        tc = srv.tenants["tenant-c"]
        assert tc.rung > 0 or tc.state != ACTIVE  # answered in-envelope
        assert srv.tenants["tenant-a"].state == ACTIVE
        assert srv.tenants["tenant-b"].state == ACTIVE
        want4 = self._reference(4)
        np.testing.assert_array_equal(models["tenant-a"].temperature(), want4)
        np.testing.assert_array_equal(models["tenant-b"].temperature(), want4)

    def test_injected_overload_sheds_without_evicting(self):
        models = self._models(n=2)
        srv = make_server(queue_max=16)
        try:
            for tid, m in sorted(models.items()):
                srv.add_tenant(TenantSpec(tenant_id=tid), m)
            inject.set_plan("dispatch:overload:serve:tenant-a@0*1")
            out = _serve_rounds(srv, sorted(models), rounds=2)
        finally:
            srv.close()
        shed = [r for r in out if not r.ok]
        assert len(shed) == 1 and shed[0].request.tenant == "tenant-a"
        assert shed[0].failure_class == FailureClass.OVERLOAD.value
        assert all(t.state == ACTIVE for t in srv.tenants.values())
        # the shed round is the ONLY delta: a completed one step less
        np.testing.assert_array_equal(
            models["tenant-a"].temperature(), self._reference(1)
        )
        np.testing.assert_array_equal(
            models["tenant-b"].temperature(), self._reference(2)
        )

    def test_slow_tenant_penalty_served_through_the_injectable_sleep(self):
        """A seeded slow_tenant notice inflates the slow tenant's service
        time through the server's injectable sleep — one penalty, charged
        at dispatch, with every envelope left active."""
        sleeps = []
        clk = FakeClock()
        srv = make_server(
            clock=clk, sleep=lambda s: (sleeps.append(s), clk.advance(s)),
            slow_penalty_s=0.25,
        )
        try:
            srv.add_tenant(TenantSpec(tenant_id="ok"))
            srv.add_tenant(TenantSpec(tenant_id="slow"))
            inject.set_plan("execute:slow_tenant:serve:slow*1")
            # the fast tenant is served FIRST (rotation order), so its
            # latency never includes the penalty queued behind it
            srv.submit(Request(tenant="ok"))
            srv.submit(Request(tenant="slow"))
            out = srv.drain()
        finally:
            srv.close()
        assert sleeps == [0.25]
        by = {r.request.tenant: r for r in out}
        assert by["slow"].ok and by["ok"].ok
        assert by["slow"].latency_s >= 0.25 > by["ok"].latency_s
        assert all(t.state == ACTIVE for t in srv.tenants.values())

    def test_transient_retries_charge_the_tenant_budget(self):
        clk = FakeClock()
        sleeps = []
        srv = make_server(
            clock=clk,
            sleep=sleeps.append,
            retry_policy=RetryPolicy(max_retries=3, backoff_base_s=0.01, jitter=0.0),
        )
        try:
            srv.add_tenant(TenantSpec(tenant_id="a", retry_allowance=8))
            inject.set_plan("execute:transient:serve:a*2")
            srv.submit(Request(tenant="a"))
            out = srv.drain()
        finally:
            srv.close()
        assert out[0].ok
        t = srv.tenants["a"]
        assert t.retries == 2 and t.budget.remaining == 6
        assert sleeps == [0.01, 0.02]  # the jitter-free backoff schedule

    def test_exhausted_budget_stops_the_retry_train(self):
        srv = make_server(
            retry_policy=RetryPolicy(max_retries=5, backoff_base_s=0.0, jitter=0.0),
        )
        try:
            srv.add_tenant(TenantSpec(tenant_id="a", retry_allowance=1))
            inject.set_plan("execute:transient:serve:a*10")
            srv.submit(Request(tenant="a"))
            out = srv.drain()
        finally:
            srv.close()
        assert not out[0].ok
        assert out[0].failure_class == FailureClass.TRANSIENT_RUNTIME.value
        assert srv.tenants["a"].budget.remaining == 0
        assert srv.tenants["a"].state == ACTIVE  # exhaustion is not eviction


# --- elasticity hysteresis ---------------------------------------------------


class TestElasticityPolicy:
    def test_dead_band_requires_low_below_high(self):
        with pytest.raises(ValueError):
            ElasticityPolicy(high=4, low=4)

    def test_hysteresis_pinned(self):
        """The exact decision sequence for a load ramp: grow only after
        ``consecutive`` samples above high, shrink only after the same run
        at/below low, repeats suppressed until the direction reverses."""
        p = ElasticityPolicy(high=4, low=0, consecutive=3, cooldown_s=0.0)
        got = [p.observe(d, now=float(i)) for i, d in enumerate(
            [0, 0, 0,          # idle at start: shrink is NOT armed
             5, 5,             # two above-high samples: not yet
             5,                # third: grow
             5, 5, 5, 5,       # sustained load: no repeated grow
             2, 2,             # dead band: resets both runs
             0, 0,             # armed now, but only two at/below low
             0,                # third: shrink
             0, 0, 0])         # idle: no repeated shrink
        ]
        assert [g for g in got if g] == ["grow", "shrink"]
        assert got[5] == "grow" and got[14] == "shrink"

    def test_spike_does_not_move_the_mesh(self):
        p = ElasticityPolicy(high=4, low=0, consecutive=3, cooldown_s=0.0)
        assert [p.observe(d, float(i)) for i, d in enumerate([5, 5, 2, 5, 5])] == [
            None
        ] * 5  # the dead-band visit reset the above-high run

    def test_cooldown_holds_after_an_action(self):
        p = ElasticityPolicy(high=4, low=0, consecutive=2, cooldown_s=10.0)
        assert p.observe(5, now=0.0) is None
        assert p.observe(5, now=1.0) == "grow"
        assert p.observe(0, now=2.0) is None
        assert p.observe(0, now=3.0) is None  # run complete, cooling down
        assert p.observe(0, now=12.0) == "shrink"  # cooldown elapsed

    def test_server_loop_grows_once_and_shrinks_once(self):
        """The closed loop over a burst: queue depth drives exactly one
        grow and, once drained, exactly one shrink through capacity()."""
        asked = []
        policy = ElasticityPolicy(high=2, low=0, consecutive=2, cooldown_s=0.0)
        srv = make_server(queue_max=16, policy=policy, capacity=asked.append)
        try:
            srv.add_tenant(TenantSpec(tenant_id="a"))
            for _ in range(6):
                srv.submit(Request(tenant="a"))
            for _ in range(8):
                srv.cycle()
        finally:
            srv.close()
        assert asked == ["grow", "shrink"]
        assert [k for _, k in policy.decisions] == ["grow", "shrink"]


# --- status + ledger wiring --------------------------------------------------


class TestStatusAndLedger:
    def test_heartbeat_tenant_table_renders(self, tmp_path, capsys):
        """The server's heartbeat carries the tenant table; ``python -m
        stencil_tpu.status`` renders one line per tenant."""
        from stencil_tpu.telemetry.flight import FlightRecorder

        clk = FakeClock()
        srv = make_server(
            clock=clk, flight=FlightRecorder(str(tmp_path), label="serve")
        )
        try:
            srv.add_tenant(TenantSpec(tenant_id="tenant-a"))
            t = srv.add_tenant(TenantSpec(tenant_id="tenant-b"))
            t.quarantine("poisoned request")
            srv.submit(Request(tenant="tenant-a"))
            srv.drain()
        finally:
            srv.close()
        from stencil_tpu.status import main as status_main

        assert status_main([str(tmp_path)]) == 0
        rendered = capsys.readouterr().out
        assert "tenants:" in rendered
        assert "tenant-a" in rendered and "active" in rendered
        assert "tenant-b" in rendered and "quarantined" in rendered
        assert "queue depth" in rendered

    def test_ledger_ingests_only_isolation_verified_serve_soaks(self, tmp_path):
        from stencil_tpu.telemetry.ledger import entries_from_artifact

        doc = {
            "bench": "serve_soak",
            "isolation_ok": True,
            "p99_ms": 12.5,
            "shed_rate": 0.25,
            "requests": 40,
            "tenants": [{"tenant": "a"}, {"tenant": "b"}],
        }
        path = str(tmp_path / "serve_summary.json")
        with open(path, "w") as f:
            json.dump(doc, f)
        entries = entries_from_artifact(path)
        assert {e["key"] for e in entries} == {"serve:p99_ms", "serve:shed_rate"}
        assert all(e["better"] == "lower" for e in entries)
        # an UNVERIFIED artifact (isolation_ok absent/false) never lands
        doc["isolation_ok"] = False
        with open(path, "w") as f:
            json.dump(doc, f)
        assert entries_from_artifact(path) == []


# --- subprocess drivers (tier-2) --------------------------------------------


def _cpu_env():
    env = dict(os.environ)
    env.pop("STENCIL_FAULT_PLAN", None)
    env["JAX_PLATFORMS"] = "cpu"
    return env


@pytest.mark.slow
class TestServeSubprocess:
    def test_serve_driver_writes_the_soak_artifact(self, tmp_path):
        out = str(tmp_path / "serve")
        proc = subprocess.run(
            [
                sys.executable, "-m", "stencil_tpu.bin.stencil_serve",
                "--tenants", "3", "--size", "8", "--cycles", "8",
                "--peak", "2", "--out", out,
            ],
            env=_cpu_env(), cwd=REPO, capture_output=True, text=True,
        )
        assert proc.returncode == 0, proc.stderr
        doc = json.load(open(os.path.join(out, "serve_summary.json")))
        assert doc["bench"] == "serve_soak"
        assert doc["isolation_ok"] is True  # fault-free: trivially isolated
        assert len(doc["tenants"]) == 3 and len(doc["digests"]) == 3

    def test_run_soak_serve_proves_isolation(self, tmp_path):
        """The full serving chaos story: poison/vmem isolation bitwise,
        overload sheds without evictions, elasticity one grow + one
        shrink bitwise — the PR's acceptance harness."""
        out = str(tmp_path / "soak")
        proc = subprocess.run(
            [
                sys.executable, os.path.join(REPO, "scripts", "run_soak.py"),
                "--dryrun", "--serve", "--serve-cycles", "12",
                "--out-dir", out,
            ],
            env=_cpu_env(), cwd=REPO, capture_output=True, text=True,
        )
        assert proc.returncode == 0, proc.stderr
        doc = json.load(open(os.path.join(out, "serve_summary.json")))
        assert doc["isolation_ok"] is True
        assert all(doc["checks"].values()), doc["checks"]
        assert doc["elasticity"]["decisions"] == ["grow", "shrink"]
