"""Resilience layer (stencil_tpu/resilience/): taxonomy pinning, degradation
ladder, retry/backoff with the donated-buffer guard, fault injection, and the
divergence sentinel — all on CPU (``STENCIL_FAULT_PLAN`` makes every failure
class reproducible without a TPU toolchain)."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from stencil_tpu.core.radius import Radius
from stencil_tpu.domain import DistributedDomain
from stencil_tpu.models.jacobi import Jacobi3D
from stencil_tpu.resilience import inject
from stencil_tpu.resilience.ladder import DegradationLadder, Rung
from stencil_tpu.resilience.retry import (
    RetryPolicy,
    buffers_live,
    execute_with_retry,
)
from stencil_tpu.resilience.taxonomy import (
    DivergenceError,
    FailureClass,
    classify,
)

TOL = dict(rtol=1e-6, atol=1e-6)


@pytest.fixture(autouse=True)
def _clear_fault_plan():
    yield
    inject.set_plan(None)


def mean6_kernel(views, info):
    out = {}
    for name, src in views.items():
        out[name] = (
            src.sh(-1, 0, 0) + src.sh(1, 0, 0)
            + src.sh(0, -1, 0) + src.sh(0, 1, 0)
            + src.sh(0, 0, -1) + src.sh(0, 0, 1)
        ) / 6.0
    return out


def _mk(x, y, z, radius, names, devices, mult=1):
    dd = DistributedDomain(x, y, z)
    dd.set_radius(radius)
    dd.set_devices(devices)
    hs = [dd.add_data(n) for n in names]
    if mult > 1:
        dd.set_halo_multiplier(mult)
    dd.realize()
    for h in hs:
        dd.init_by_coords(h, lambda cx, cy, cz: jnp.sin(0.3 * cx + 0.2 * cy) + 0.1 * cz)
    return dd, hs


# --- taxonomy: pinned toolchain wordings ------------------------------------


class TestClassify:
    def test_mosaic_vmem_oom_wordings_pinned(self):
        """The CURRENT Mosaic scoped-VMEM failure texts.  If a toolchain
        upgrade re-words these, this test fails instead of the runtime
        silently reclassifying to FATAL (and losing the depth fallback)."""
        for msg in (
            # the wording the repo's probes hit on v5e (probe10/14/17)
            "Ran out of memory in memory space vmem. Used 107.90M of 100.00M",
            "Mosaic failed: exceeded scoped vmem limit by 8.59M",
            "RESOURCE_EXHAUSTED: Ran out of memory in memory space vmem",
        ):
            assert classify(RuntimeError(msg)) is FailureClass.VMEM_OOM, msg

    def test_vmem_alone_is_not_oom(self):
        # "vmem" appears in benign messages (our own log lines, plan dumps)
        assert classify(RuntimeError("vmem budget is 100MB")) is FailureClass.FATAL

    def test_mosaic_compile_rejects_pinned(self):
        for msg in (
            # wordings this repo has hit on real Mosaic (see ops/ comments)
            "Mosaic failed to compile TPU kernel",
            "unsupported unaligned shape",  # probe11b, slab z-rotate
            "Target does not support this comparison",  # 16-bit vector cmp
            "Rotate with non-32-bit data",  # narrow-dtype pltpu.roll
            "failed to legalize operation 'tpu.iota'",
        ):
            assert classify(RuntimeError(msg)) is FailureClass.COMPILE_REJECT, msg

    def test_transient_runtime_pinned(self):
        for msg in (
            "UNAVAILABLE: Socket closed",
            "DEADLINE_EXCEEDED: deadline exceeded after 59.9s",
            "connection reset by peer",
            "handshake failed, try again later",
        ):
            assert classify(RuntimeError(msg)) is FailureClass.TRANSIENT_RUNTIME, msg

    def test_typed_and_fatal(self):
        assert classify(DivergenceError("temp", 40)) is FailureClass.DIVERGENCE
        assert classify(ValueError("shape mismatch")) is FailureClass.FATAL
        assert classify(KeyError("temp")) is FailureClass.FATAL

    def test_capacity_loss_wordings_pinned(self):
        """The CURRENT device-unavailable / slice-health texts.  These
        route to the supervisor's reshard/restore path — a toolchain
        upgrade that re-words one must fail here, not silently fall back
        to FATAL (losing the elastic-capacity recovery)."""
        for msg in (
            "UNAVAILABLE: TPU is unhealthy: lost device at coordinates [0,1,0]",
            "FAILED_PRECONDITION: The TPU slice health check failed: "
            "worker 3 unreachable",
            "INTERNAL: Device coordinator reported missing chips after "
            "preemption notice",
            "a device has been removed from the fleet",
        ):
            assert classify(RuntimeError(msg)) is FailureClass.CAPACITY_LOSS, msg

    def test_capacity_loss_beats_the_transient_markers(self):
        """THE ordering pin: real device-loss wordings carry the gRPC
        'UNAVAILABLE:' prefix — they must classify CAPACITY_LOSS, never
        TRANSIENT (a blind retry against a missing chip re-fails forever),
        while a plain UNAVAILABLE stays retryable."""
        loss = "UNAVAILABLE: TPU is unhealthy: lost device at coordinates"
        assert classify(RuntimeError(loss)) is FailureClass.CAPACITY_LOSS
        assert (
            classify(RuntimeError("UNAVAILABLE: Socket closed"))
            is FailureClass.TRANSIENT_RUNTIME
        )

    def test_capacity_loss_never_degrades(self):
        from stencil_tpu.resilience.taxonomy import is_degradable

        assert not is_degradable(FailureClass.CAPACITY_LOSS)

    def test_preemption_never_transient(self):
        """THE preemption pin: KeyboardInterrupt / SIGTERM-driven
        termination classifies PREEMPTED, so the retry loop can never
        swallow a preemption notice by re-running the work — even when the
        notice's wording brushes the transient marker list."""
        from stencil_tpu.resilience.taxonomy import PreemptionError, StallError

        assert classify(KeyboardInterrupt()) is FailureClass.PREEMPTED
        assert classify(PreemptionError("SIGTERM")) is FailureClass.PREEMPTED
        # typed class wins over substring matching: this wording contains
        # TWO transient markers and must still classify PREEMPTED
        notice = PreemptionError("deadline exceeded — node reclaimed, try again later")
        assert classify(notice) is FailureClass.PREEMPTED
        assert classify(StallError("dispatch:jacobi", 30.0)) is FailureClass.STALL

    def test_preempted_and_stall_never_degrade(self):
        from stencil_tpu.resilience.taxonomy import is_degradable

        assert not is_degradable(FailureClass.PREEMPTED)
        assert not is_degradable(FailureClass.STALL)

    def test_user_kernel_bugs_stay_fatal(self):
        """Ordinary Python errors whose wording brushes the marker lists must
        NOT be misread as degradable/retryable — a programming bug should
        propagate immediately, not walk the ladder or retry with backoff."""
        for msg in (
            "unsupported operand type(s) for +: 'PlaneView' and 'int'",
            "slicing is not implemented for this view",
            "no backend is unavailable right now",  # no gRPC 'UNAVAILABLE:'
        ):
            assert classify(TypeError(msg)) is FailureClass.FATAL, msg


# --- env validation ---------------------------------------------------------


class TestEnvValidation:
    def test_vmem_limit_malformed_names_the_var(self, monkeypatch):
        from stencil_tpu.ops.jacobi_pallas import _vmem_budget

        monkeypatch.setenv("STENCIL_VMEM_LIMIT_BYTES", "100mb")
        with pytest.raises(ValueError, match="STENCIL_VMEM_LIMIT_BYTES"):
            _vmem_budget()

    def test_vmem_limit_nonpositive_rejected(self, monkeypatch):
        from stencil_tpu.ops.jacobi_pallas import _vmem_budget

        for bad in ("0", "-5"):
            monkeypatch.setenv("STENCIL_VMEM_LIMIT_BYTES", bad)
            with pytest.raises(ValueError, match="STENCIL_VMEM_LIMIT_BYTES"):
                _vmem_budget()

    def test_vmem_limit_valid_and_default(self, monkeypatch):
        from stencil_tpu.ops.jacobi_pallas import (
            _VMEM_BUDGET_DEFAULT,
            _vmem_budget,
        )

        monkeypatch.setenv("STENCIL_VMEM_LIMIT_BYTES", "16000000")
        assert _vmem_budget() == 16000000
        monkeypatch.delenv("STENCIL_VMEM_LIMIT_BYTES")
        assert _vmem_budget() == _VMEM_BUDGET_DEFAULT

    def test_env_int_and_float_helpers(self, monkeypatch):
        from stencil_tpu.utils.config import env_float, env_int

        monkeypatch.setenv("STENCIL_RETRY_MAX", "7")
        assert env_int("STENCIL_RETRY_MAX", 3) == 7
        monkeypatch.setenv("STENCIL_RETRY_MAX", "nope")
        with pytest.raises(ValueError, match="STENCIL_RETRY_MAX"):
            env_int("STENCIL_RETRY_MAX", 3)
        monkeypatch.setenv("STENCIL_RETRY_BACKOFF_S", "0.5")
        assert env_float("STENCIL_RETRY_BACKOFF_S", 0.25) == 0.5
        monkeypatch.setenv("STENCIL_RETRY_BACKOFF_S", "-1")
        with pytest.raises(ValueError, match="STENCIL_RETRY_BACKOFF_S"):
            env_float("STENCIL_RETRY_BACKOFF_S", 0.25, minimum=0.0)


# --- fault plan parsing -----------------------------------------------------


class TestFaultPlan:
    def test_parse_and_counts(self):
        p = inject.FaultPlan.parse("execute:vmem_oom:stream*2,dispatch:transient")
        assert p.pending() == 3

    def test_label_prefix_glob(self):
        p = inject.FaultPlan.parse("execute:vmem_oom:stream*1")
        p.fire("execute", "jacobi:wrap[k=4]")  # no match, no raise
        with pytest.raises(RuntimeError, match="vmem"):
            p.fire("execute", "stream:wavefront[m=3]")
        p.fire("execute", "stream:wavefront[m=2]")  # spent

    def test_exact_rung_label_with_colons_and_brackets(self):
        """A full ladder-rung label ('engine:rung[param]') is a valid target:
        colons must survive the entry split and brackets must match
        literally (prefix match), not as an fnmatch character class."""
        p = inject.FaultPlan.parse("execute:vmem_oom:stream:wavefront[m=3]*1")
        p.fire("execute", "stream:wavefront[m=2]")  # different rung: no fire
        with pytest.raises(RuntimeError, match="vmem"):
            p.fire("execute", "stream:wavefront[m=3]")

    def test_label_glob_may_contain_wildcards(self):
        # '*' inside the glob is NOT the count suffix (only a trailing
        # '*<digits>' is) — wildcarded label patterns must parse
        p = inject.FaultPlan.parse("execute:vmem_oom:*wavefront*2")
        assert p.pending() == 2
        with pytest.raises(RuntimeError, match="vmem"):
            p.fire("execute", "stream:wavefront[m=3]")
        p.fire("execute", "stream:plane[m=1]")  # no match: different rung

    def test_bad_entries_rejected(self):
        for bad in ("boot:vmem_oom", "execute:nope", "execute", "execute:fatal*0"):
            with pytest.raises(ValueError, match="STENCIL_FAULT_PLAN"):
                inject.FaultPlan.parse(bad)

    def test_skip_suffix_delays_firing(self):
        """'@K' lets K matching hook calls pass before the entry arms — the
        chaos harness's 'die at the K-th dispatch' primitive."""
        p = inject.FaultPlan.parse("dispatch:fatal:jacobi@2*1")
        p.fire("dispatch", "jacobi")  # pass 1
        p.fire("dispatch", "jacobi")  # pass 2
        with pytest.raises(RuntimeError, match="injected fatal"):
            p.fire("dispatch", "jacobi")
        p.fire("dispatch", "jacobi")  # spent

    def test_process_kill_classes_parse(self):
        """sigkill/sigterm entries parse (firing them would signal THIS
        process — the subprocess soak covers delivery, scripts/run_soak.py)."""
        p = inject.FaultPlan.parse("dispatch:sigkill:jacobi@7,dispatch:sigterm:x*2")
        assert p.pending() == 3
        p.fire("dispatch", "other")  # label mismatch: nothing fires

    def test_injected_capacity_loss_classifies(self):
        """The capacity_loss class raises the real device-unhealthy
        wording: classify routes it to CAPACITY_LOSS, exercising the
        supervisor's reshard/restore path like the real thing."""
        from stencil_tpu.resilience.taxonomy import FailureClass, classify

        p = inject.FaultPlan.parse("dispatch:capacity_loss:jacobi*1")
        with pytest.raises(RuntimeError, match="unhealthy") as ei:
            p.fire("dispatch", "jacobi")
        assert classify(ei.value) is FailureClass.CAPACITY_LOSS

    def test_capacity_notices_call_the_registered_handler(self):
        """shrink/grow are NOTICES, not failures: the registered handler
        (the supervisor) records them and the dispatch proceeds; with no
        handler they are logged and dropped, never raised."""
        seen = []
        prev = inject.set_capacity_handler(
            lambda kind, phase, label: seen.append((kind, phase, label))
        )
        try:
            p = inject.FaultPlan.parse(
                "dispatch:shrink:jacobi@1,dispatch:grow:jacobi@1"
            )
            p.fire("dispatch", "jacobi")  # both entries pass through
            p.fire("dispatch", "jacobi")  # shrink fires (no raise)
            p.fire("dispatch", "jacobi")  # grow fires
            assert seen == [
                ("shrink", "dispatch", "jacobi"),
                ("grow", "dispatch", "jacobi"),
            ]
        finally:
            inject.set_capacity_handler(prev)
        # no handler: the notice is dropped without raising
        p = inject.FaultPlan.parse("dispatch:shrink:x*1")
        p.fire("dispatch", "x")

    def test_env_plan_reparsed_on_change(self, monkeypatch):
        monkeypatch.setenv("STENCIL_FAULT_PLAN", "dispatch:fatal*1")
        with pytest.raises(RuntimeError, match="injected fatal"):
            inject.maybe_fail("dispatch", "x")
        inject.maybe_fail("dispatch", "x")  # spent (same env value: no re-arm)
        monkeypatch.setenv("STENCIL_FAULT_PLAN", "dispatch:fatal*2")
        with pytest.raises(RuntimeError, match="injected fatal"):
            inject.maybe_fail("dispatch", "x")  # CHANGED value re-parses
        monkeypatch.delenv("STENCIL_FAULT_PLAN")
        inject.maybe_fail("dispatch", "x")  # cleared env deactivates


# --- retry with backoff -----------------------------------------------------


class TestRetry:
    def test_transient_retries_with_backoff(self):
        calls = {"n": 0}
        delays = []

        def flaky():
            calls["n"] += 1
            if calls["n"] <= 2:
                raise RuntimeError("UNAVAILABLE: connection reset by peer")
            return "ok"

        policy = RetryPolicy(max_retries=3, backoff_base_s=0.1, multiplier=2.0, jitter=0.0)
        out = execute_with_retry(flaky, policy=policy, sleep=delays.append)
        assert out == "ok" and calls["n"] == 3
        assert delays == pytest.approx([0.1, 0.2])

    def test_exhaustion_reraises(self):
        def always():
            raise RuntimeError("UNAVAILABLE: Socket closed")

        policy = RetryPolicy(max_retries=2, backoff_base_s=0.0)
        with pytest.raises(RuntimeError, match="Socket closed"):
            execute_with_retry(always, policy=policy, sleep=lambda _: None)

    def test_non_transient_never_retries(self):
        calls = {"n": 0}

        def boom():
            calls["n"] += 1
            raise ValueError("real bug")

        with pytest.raises(ValueError):
            execute_with_retry(boom, policy=RetryPolicy(), sleep=lambda _: None)
        assert calls["n"] == 1

    def test_donated_buffer_refuses_retry(self):
        class Deleted:
            def is_deleted(self):
                return True

        calls = {"n": 0}

        def flaky():
            calls["n"] += 1
            raise RuntimeError("UNAVAILABLE: connection dropped")

        with pytest.raises(RuntimeError, match="connection dropped"):
            execute_with_retry(
                flaky,
                policy=RetryPolicy(max_retries=3, backoff_base_s=0.0),
                buffers=lambda: [Deleted()],
                sleep=lambda _: None,
            )
        assert calls["n"] == 1  # the retry was REFUSED, not exhausted

    def test_preemption_is_never_retried(self):
        """The retry loop re-raises a preemption on the FIRST attempt: a
        burning preemption deadline must not be spent on backoff sleeps
        (exact satellite behavior, paired with the classify pin above)."""
        from stencil_tpu.resilience.taxonomy import PreemptionError

        calls = {"n": 0}

        def preempted():
            calls["n"] += 1
            raise PreemptionError("SIGTERM")

        with pytest.raises(PreemptionError):
            execute_with_retry(
                preempted,
                policy=RetryPolicy(max_retries=5, backoff_base_s=0.0),
                sleep=lambda _: None,
            )
        assert calls["n"] == 1

    def test_buffers_live_on_real_arrays(self):
        a = jnp.zeros((4,))
        assert buffers_live({"u": a, "steps": 3})
        a.delete()  # the state a donated-and-consumed input ends up in
        assert a.is_deleted()
        assert not buffers_live({"u": a})


# --- degradation ladder (unit) ----------------------------------------------


class TestLadder:
    def _ladder(self, fail_classes, rung_names=("a", "b", "c")):
        """A toy ladder whose first len(fail_classes) rungs raise."""
        log = {"built": [], "ran": []}
        names = list(rung_names)

        def mk(i):
            def build():
                log["built"].append(names[i])

                def impl(x):
                    if i < len(fail_classes):
                        raise RuntimeError(fail_classes[i])
                    log["ran"].append(names[i])
                    return x * 2

                return impl

            return Rung(name=names[i], build=build)

        def lower(rung, cls, exc):
            i = names.index(rung.name)
            return mk(i + 1) if i + 1 < len(names) else None

        return DegradationLadder(mk(0), lower=lower, label="toy"), log

    def test_descends_on_vmem_oom_and_compile_reject(self):
        ladder, log = self._ladder([
            "Ran out of memory in memory space vmem (exceeded)",
            "Mosaic failed to compile TPU kernel",
        ])
        assert ladder.step(21) == 42
        assert log["built"] == ["a", "b", "c"] and log["ran"] == ["c"]
        assert [d[0] for d in ladder.descents] == ["a", "b"]
        assert [d[1] for d in ladder.descents] == [
            FailureClass.VMEM_OOM, FailureClass.COMPILE_REJECT,
        ]

    def test_exhausted_ladder_reraises(self):
        ladder, _ = self._ladder(
            ["vmem exceeded", "vmem exceeded", "vmem exceeded"])
        with pytest.raises(RuntimeError, match="vmem"):
            ladder.step(1)

    def test_fatal_and_transient_do_not_descend(self):
        for msg in ("a real bug", "UNAVAILABLE: socket closed"):
            ladder, log = self._ladder([msg])
            with pytest.raises(RuntimeError):
                ladder.step(1)
            assert log["built"] == ["a"]  # never descended

    def test_descent_refused_when_args_donated(self):
        class Deleted:
            def is_deleted(self):
                return True

        ladder, log = self._ladder(["vmem exceeded"])
        with pytest.raises(RuntimeError, match="vmem"):
            ladder.step(Deleted())
        # the descent installed rung b but REFUSED to re-invoke it
        assert log["ran"] == []


# --- ladder through the real engines (fault-injected) -----------------------


class TestLadderEngines:
    def test_stream_every_rung_via_injection(self):
        """Drive the stream engine down its whole ladder on CPU: injected
        VMEM OOMs walk wavefront[m=3] -> wavefront[m=2] -> plane[m=1], which
        then runs and matches the XLA reference."""
        devs = jax.devices()[:8]
        dd, hs = _mk(24, 24, 24, Radius.constant(1), ["u"], devs, mult=3)
        step = dd.make_step(mean6_kernel, engine="stream", interpret=True)
        assert step._stream_plan == {
            "route": "wavefront", "m": 3, "z_slabs": True, "grouping": "joint",
            "alias": False,  # one field on the wavefront route: fresh outputs
            "overlap": "off", "halo": "array", "halo_readers": ("u",),
            "writers": ("u",), "pass_wrap_axes": "", "renamed": (),
            # what the kernel reads, traced for the span alone here (ISSUE 39)
            "footprint": {"offcentre": 1, "diagonal": 0, "read_sides": 6},
            # the z-slab pass patches its z halo in the lane tiles that hold
            # it, on the lane-padded plane (ISSUE 40), made in VMEM from the
            # raw block (ISSUE 41)
            "z_halo_patch": "tile",
            "lane_pad": "vmem",
            "slab_wrap": "",  # every axis of mesh [2,2,2] is wired (ISSUE 56)
            # what a macro sends over the wires of mesh [2,2,2] (ISSUE 49)
            "wire_account": step._stream_plan["wire_account"],
            "wired": "xyz", "wire_bytes": step._stream_plan["wire_account"].said()[1],
            "joint": "xy",  # x and y fly jointly, z behind the pair (ISSUE 50)
        }
        assert step._stream_plan["wire_account"].every == 3
        inject.set_plan("execute:vmem_oom:stream*2")
        dd.run_step(step, 4)
        assert step._stream_plan["route"] == "plane"
        # the z-slab wavefront's alone
        assert not {"z_halo_patch", "lane_pad", "slab_wrap"} & set(step._stream_plan)
        # a depth descent re-plans: the plane rung resolves its OWN alias
        # (in place) instead of inheriting the wavefront rung's
        assert step._stream_plan["alias"] is True
        assert step._span_args()["aliased"] == 1
        assert [d[0] for d in step._resilience.descents] == [
            "wavefront[m=3]", "wavefront[m=2]",
        ]
        ref_dd, ref_hs = _mk(24, 24, 24, Radius.constant(1), ["u"], devs)
        ref = ref_dd.make_step(mean6_kernel, overlap=False)
        ref_dd.run_step(ref, 4)
        np.testing.assert_allclose(
            ref_dd.quantity_to_host(ref_hs[0]), dd.quantity_to_host(hs[0]), **TOL
        )

    def test_stream_compile_phase_injection(self):
        """A compile-time rejection (the rung's BUILD, phase ``compile``)
        descends the ladder during make_step's eager build: the returned
        step already holds the lower rung's plan."""
        devs = jax.devices()[:8]
        dd, hs = _mk(24, 24, 24, Radius.constant(1), ["u"], devs, mult=2)
        inject.set_plan("compile:compile_reject:stream*1")
        step = dd.make_step(mean6_kernel, engine="stream", interpret=True)
        assert step._stream_plan["route"] == "plane"
        assert [d[1] for d in step._resilience.descents] == [
            FailureClass.COMPILE_REJECT,
        ]
        dd.run_step(step, 2)
        ref_dd, ref_hs = _mk(24, 24, 24, Radius.constant(1), ["u"], devs)
        ref = ref_dd.make_step(mean6_kernel, overlap=False)
        ref_dd.run_step(ref, 2)
        np.testing.assert_allclose(
            ref_dd.quantity_to_host(ref_hs[0]), dd.quantity_to_host(hs[0]), **TOL
        )

    def test_jacobi_wrap_rung_via_injection(self):
        m = Jacobi3D(24, 24, 24, devices=jax.devices()[:1],
                     kernel_impl="pallas", temporal_k=4, interpret=True)
        m.realize()
        assert m._wrap_k == 4
        inject.set_plan("execute:vmem_oom:jacobi*1")
        m.step(8)
        assert m._wrap_k == 3
        assert [d[1] for d in m._ladder.descents] == [FailureClass.VMEM_OOM]
        ref = Jacobi3D(24, 24, 24, devices=jax.devices()[:1],
                       kernel_impl="pallas", temporal_k=1, interpret=True)
        ref.realize()
        ref.step(8)
        np.testing.assert_array_equal(ref.temperature(), m.temperature())

    def test_jacobi_wavefront_rung_via_injection(self):
        w = Jacobi3D(24, 24, 24, devices=jax.devices()[:1],
                     kernel_impl="pallas", pallas_path="wavefront",
                     temporal_k=4, interpret=True)
        w.realize()
        inject.set_plan("execute:compile_reject:jacobi*1")
        w.step(8)
        assert w._wavefront_depth == 3 and w._wavefront_m == 4
        ref = Jacobi3D(24, 24, 24, devices=jax.devices()[:1],
                       kernel_impl="pallas", temporal_k=1, interpret=True)
        ref.realize()
        ref.step(8)
        np.testing.assert_allclose(ref.temperature(), w.temperature(), **TOL)

    def test_dispatch_transient_retry_end_to_end(self, monkeypatch):
        """A transient dispatch failure (the dropped-connection class)
        retries with backoff and completes — same final field as a clean
        run."""
        monkeypatch.setenv("STENCIL_RETRY_BACKOFF_S", "0.0")
        m = Jacobi3D(16, 16, 16, devices=jax.devices()[:1])
        m.realize()
        inject.set_plan("dispatch:transient:jacobi*2")
        m.step(3)
        assert inject.active_plan().pending() == 0
        ref = Jacobi3D(16, 16, 16, devices=jax.devices()[:1])
        ref.realize()
        ref.step(3)
        np.testing.assert_array_equal(ref.temperature(), m.temperature())

    def test_dispatch_transient_exhaustion(self, monkeypatch):
        monkeypatch.setenv("STENCIL_RETRY_BACKOFF_S", "0.0")
        monkeypatch.setenv("STENCIL_RETRY_MAX", "1")
        m = Jacobi3D(16, 16, 16, devices=jax.devices()[:1])
        m.realize()
        inject.set_plan("dispatch:transient:jacobi*5")
        with pytest.raises(RuntimeError, match="connection reset"):
            m.step(2)


# --- divergence sentinel ----------------------------------------------------


class TestDivergenceSentinel:
    def test_nan_raises_named_divergence(self):
        m = Jacobi3D(16, 16, 16, devices=jax.devices()[:1],
                     check_divergence_every=1)
        m.realize()
        m.step(1)  # finite: passes
        arr = m.dd._curr["temp"]
        c = tuple(s // 2 for s in arr.shape)  # an INTERIOR cell (not shell)
        m.dd._curr["temp"] = arr.at[c].set(jnp.nan)
        with pytest.raises(DivergenceError) as ei:
            m.step(1)
        assert ei.value.quantity == "temp"
        assert ei.value.step == 2
        # the on-device path adds the uncertainty window (the step-1 check
        # ran clean) and a global first-non-finite coordinate
        assert ei.value.window == (1, 2)
        assert ei.value.coord is not None
        assert all(0 <= c < 16 for c in ei.value.coord)
        assert classify(ei.value) is FailureClass.DIVERGENCE

    def test_cadence_skips_intermediate_checks(self):
        from stencil_tpu.resilience.sentinel import DivergenceSentinel
        from stencil_tpu.telemetry.numerics import FieldStats, NumericsSnapshot

        poisoned = [True]
        calls = []

        class FakeEngine:
            def snapshot(self, step=None, window=None):
                calls.append((step, window))
                bad = poisoned[0]
                st = FieldStats(
                    name="u", dtype="float32", min=0.0, max=1.0, absmax=1.0,
                    mean=0.5, l2=1.0, finite=7,
                    nonfinite=1 if bad else 0,
                    first_nonfinite=(1, 2, 3) if bad else None,
                )
                return NumericsSnapshot(
                    step=step, window=window, ts=0.0, seconds=0.0, stats=(st,)
                )

        class FakeDD:
            def numerics(self):
                return FakeEngine()

        s = DivergenceSentinel(10)
        s.after_steps(FakeDD(), 4)  # 4: no crossing, no check, no raise
        s.after_steps(FakeDD(), 5)  # 9: still below the cadence
        assert s.steps_done == 9
        assert calls == []  # no crossing -> no fused dispatch at all
        with pytest.raises(DivergenceError) as ei:
            s.after_steps(FakeDD(), 5)  # 14 crosses 10: checked
        assert ei.value.quantity == "u" and ei.value.step == 14
        # the error carries the bracketing step window (no check had run
        # clean yet, so the low edge is 0) and the on-device coordinate
        assert ei.value.window == (0, 14)
        assert ei.value.coord == (1, 2, 3)
        assert calls == [(14, (0, 14))]

    def test_window_low_edge_is_last_clean_check(self):
        """A clean crossing advances the window's low edge: the next trip
        brackets the first bad step to (last clean check, detection]."""
        from stencil_tpu.resilience.sentinel import DivergenceSentinel
        from stencil_tpu.telemetry.numerics import FieldStats, NumericsSnapshot

        poisoned = [False]

        class FakeEngine:
            def snapshot(self, step=None, window=None):
                bad = poisoned[0]
                st = FieldStats(
                    name="u", dtype="float32", min=0.0, max=1.0, absmax=1.0,
                    mean=0.5, l2=1.0, finite=7,
                    nonfinite=1 if bad else 0,
                    first_nonfinite=(0, 0, 0) if bad else None,
                )
                return NumericsSnapshot(
                    step=step, window=window, ts=0.0, seconds=0.0, stats=(st,)
                )

        class FakeDD:
            def numerics(self):
                return FakeEngine()

        s = DivergenceSentinel(5)
        s.after_steps(FakeDD(), 6)  # 6 crosses 5: clean check
        assert s.last_checked == 6
        poisoned[0] = True
        with pytest.raises(DivergenceError) as ei:
            s.after_steps(FakeDD(), 6)  # 12 crosses 10: trips
        assert ei.value.window == (6, 12)

    def test_set_every_preserves_steps_done(self):
        """ISSUE-15 satellite: changing the cadence mid-run (the domain's
        set_divergence_check) must not reset the accumulated step count —
        reported divergence steps would otherwise restart from zero."""
        m = Jacobi3D(16, 16, 16, devices=jax.devices()[:1])
        m.realize()
        m.dd.set_divergence_check(7)
        m.step(2)
        assert m.dd._sentinel.steps_done == 2
        m.dd.set_divergence_check(3)  # mid-run cadence change
        assert m.dd._sentinel.steps_done == 2  # preserved, not rebuilt
        assert m.dd._sentinel.every == 3
        m.step(2)
        assert m.dd._sentinel.steps_done == 4

    def test_macro_steps_count_as_raw_iterations(self):
        """Under a halo multiplier the xla engine's built step is a MACRO
        step; the sentinel cadence must count raw iterations, not
        dispatches."""
        m = Jacobi3D(16, 16, 16, devices=jax.devices()[:8])
        m.dd.set_halo_multiplier(2)
        m.dd.set_divergence_check(3)
        m.realize()
        assert m._step._raw_steps_per_call == 2
        m.step(4)  # 2 dispatches x 2 raw iterations
        assert m.dd._sentinel.steps_done == 4

    def test_injected_divergence_class(self):
        m = Jacobi3D(16, 16, 16, devices=jax.devices()[:1])
        m.realize()
        inject.set_plan("dispatch:divergence:jacobi*1")
        with pytest.raises(DivergenceError):
            m.step(1)


# --- cost model: non-axis-aligned process boundaries ------------------------


def test_axis_edge_kinds_scans_all_lines():
    """A snaking device order whose process boundary is NOT an axis-aligned
    plane must classify dcn (the old lead-line-only scan said ici)."""
    import types

    from stencil_tpu.parallel.cost import axis_edge_kinds

    def dev(p):
        return types.SimpleNamespace(process_index=p)

    # axis 0 line at [:,0] stays in process 0, but line [:,1] crosses
    mesh = types.SimpleNamespace(
        devices=np.array([[dev(0), dev(0)], [dev(0), dev(1)]])
    )
    assert axis_edge_kinds(mesh) == ["dcn", "dcn"]
    # a clean axis-aligned split: axis 0 crosses, axis 1 never does
    mesh2 = types.SimpleNamespace(
        devices=np.array([[dev(0), dev(0)], [dev(1), dev(1)]])
    )
    assert axis_edge_kinds(mesh2) == ["dcn", "ici"]


# --- bench driver: artifact survives an astaroth-section failure ------------


# stencil-lint: disable=slow-marker runs bench.py at size 16 in interpret mode on CPU — 7s measured; artifact-survival is PR-1's headline acceptance and must stay in the tier-1 gate
def test_bench_artifact_survives_injected_transient():
    """The artifact-first acceptance scenario: a transient failure that
    outlasts its retries during the astaroth section of ``python bench.py``
    must still produce a JSON artifact with the headline jacobi numbers —
    and still exit nonzero so the regression is visible."""
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        STENCIL_BENCH_SIZE="16",
        STENCIL_BENCH_INTERPRET="1",
        STENCIL_RETRY_BACKOFF_S="0.01",
        STENCIL_FAULT_PLAN="dispatch:transient:astaroth*9",
    )
    env.pop("XLA_FLAGS", None)  # 1 CPU device is enough and much faster
    proc = subprocess.run(
        [sys.executable, "bench.py"],
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        env=env,
        capture_output=True,
        text=True,
        timeout=600,
    )
    assert proc.returncode != 0, (proc.stdout, proc.stderr)
    lines = [l for l in proc.stdout.splitlines() if l.strip().startswith("{")]
    assert lines, (proc.stdout, proc.stderr)
    artifact = json.loads(lines[-1])
    # headline jacobi numbers survived the astaroth failure
    assert artifact["metric"] == "jacobi3d_mcells_per_s_per_chip"
    assert isinstance(artifact["value"], (int, float)) and artifact["value"] > 0
    assert artifact["chip_copy_gbps"] > 0
    # the failed section is recorded as null, not dropped
    assert artifact["astaroth_8q_ms_per_iter"] is None
    assert artifact["astaroth_8q_mupdates_per_s"] is None
    assert "astaroth bench section failed" in proc.stderr
