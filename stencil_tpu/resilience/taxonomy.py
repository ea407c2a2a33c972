"""Failure taxonomy: one ``classify(exc)`` for every error-handling site.

Nine classes cover everything the framework reacts to differently:

* ``VMEM_OOM``          — Mosaic rejected a kernel because its scoped-VMEM
  request does not fit (the calibrated model under-estimated on this
  toolchain).  Recoverable by DESCENDING the degradation ladder (shallower
  temporal depth, eventually the plane/reference route).
* ``COMPILE_REJECT``    — the compiler refused the kernel for a capability
  reason other than VMEM (unsupported op/shape/dtype).  Also recoverable by
  descending: a shallower or structurally simpler rung may avoid the
  offending construct.
* ``TRANSIENT_RUNTIME`` — infrastructure flakes: RPC unavailability,
  deadline expiries, connection resets.  Recoverable by RETRYING
  the same rung with backoff (see ``retry.py``) — provided no donated
  buffer was consumed.
* ``DIVERGENCE``        — the simulation itself went non-finite
  (``sentinel.py``).  Never retried: re-running the same numerics diverges
  again; the caller must change the model or step size.
* ``PREEMPTED``         — the RUN was told to stop: ``KeyboardInterrupt``,
  or the supervisor's SIGTERM/preemption notice (``PreemptionError``).
  Never retried and never degraded — a preemption deadline is burning; the
  supervisor (``supervisor.py``) takes a final checkpoint and exits with a
  resumable status.  Distinct from TRANSIENT_RUNTIME so the retry loop can
  never swallow a preemption notice by re-running the work.
* ``STALL``             — a dispatch exceeded the watchdog deadline
  (``watchdog.py``): the device or its runtime is wedged, not failing fast.
  Handled like FATAL by in-process machinery (no retry — the same dispatch
  would wedge again); the supervisor's restart-from-checkpoint budget is
  the recovery rung.
* ``CAPACITY_LOSS``     — the FLEET changed under the run: a device became
  unhealthy, a slice-health monitor reported missing chips, a worker was
  removed.  Never blindly retried (the devices are gone — re-running the
  same dispatch re-fails) and never degraded (no shallower kernel brings a
  chip back): the supervisor routes it to the elastic-capacity path —
  drain, then ``DistributedDomain.reshard`` onto the surviving mesh, with
  checkpoint-elastic-restore as the fallback (docs/resilience.md "Elastic
  capacity").  The markers are checked BEFORE the transient list because
  real device-loss wordings carry the gRPC ``UNAVAILABLE:`` prefix that
  would otherwise classify them retryable.
* ``OVERLOAD``          — the SERVING layer refused or shed the request
  because the fleet is saturated: the admission queue is full, the request's
  deadline passed while queued, or a cold compile would not fit the
  admission budget (``serve/``).  Never retried blindly — N tenants
  re-dispatching into a saturated queue is the thundering herd that caused
  the shed; the caller backs off (the refusal carries ``retry_after_s``)
  or lowers its request rate.  Distinct from TRANSIENT_RUNTIME even though
  both are "try later": transient retries re-run the SAME work in place,
  an overload refusal pushes the decision back to the submitting tenant.
  The markers are checked BEFORE the transient list because shed wordings
  mention the deadline ("deadline exceeded" is a transient marker).
* ``FATAL``             — everything else.  Propagates unchanged.

Classification is by exception type first (``ResilienceError`` subclasses
carry their class), then by PINNED message substrings.  The pinned texts are
what the current jax/Mosaic/XLA toolchain emits — ``tests/test_resilience.py``
asserts them verbatim so a toolchain upgrade that re-words an error fails a
test instead of silently reclassifying to FATAL.
"""

from __future__ import annotations

import enum


class FailureClass(enum.Enum):
    VMEM_OOM = "vmem_oom"
    COMPILE_REJECT = "compile_reject"
    TRANSIENT_RUNTIME = "transient"
    DIVERGENCE = "divergence"
    PREEMPTED = "preempted"
    STALL = "stall"
    CAPACITY_LOSS = "capacity_loss"
    OVERLOAD = "overload"
    FATAL = "fatal"


class ResilienceError(RuntimeError):
    """Base for errors that carry their own taxonomy class."""

    failure_class: FailureClass = FailureClass.FATAL


class DivergenceError(ResilienceError):
    """Raised by the divergence sentinel (or an aborting numerics
    guardband): a quantity went NaN/Inf or drifted past a registered
    invariant.  Carries the quantity, the detection ``step``, the
    bracketing step ``window`` — ``(last clean check, detection step]``,
    the first-bad-step uncertainty interval — and, for non-finite trips,
    the global 3D ``coord`` of the first non-finite cell (the on-device
    numerics engine computes it inside the fused stats dispatch)."""

    failure_class = FailureClass.DIVERGENCE

    def __init__(
        self,
        quantity: str,
        step: int,
        window: tuple = None,
        coord: tuple = None,
        why: str = None,
    ):
        self.quantity = quantity
        self.step = step
        self.window = tuple(window) if window is not None else None
        self.coord = tuple(coord) if coord is not None else None
        self.why = why
        what = why or "contains non-finite values"
        msg = f"quantity {quantity!r} {what} at step {step}"
        if self.coord is not None:
            msg += f", first non-finite cell at global {self.coord}"
        if self.window is not None:
            msg += (
                f"; diverged within step window ({self.window[0]}, "
                f"{self.window[1]}]"
            )
        super().__init__(msg + " (divergence sentinel)")


class OverloadError(ResilienceError):
    """The serving layer refused or shed a request under load (``serve/``).
    Carries WHY (``queue_full`` / ``deadline`` / ``compile_budget``), the
    queue depth observed at refusal time, and a backoff hint the caller
    should honor before re-submitting — blind immediate re-dispatch is the
    herd behavior the shed exists to break."""

    failure_class = FailureClass.OVERLOAD

    def __init__(
        self,
        why: str = "queue_full",
        queue_depth: int = None,
        retry_after_s: float = None,
        tenant: str = None,
    ):
        self.why = why
        self.queue_depth = queue_depth
        self.retry_after_s = retry_after_s
        self.tenant = tenant
        # pinned wordings (matched by _OVERLOAD_MARKERS below and pinned by
        # tests): every refusal path names its cause in the message
        if why == "queue_full":
            msg = "request queue is full; load shed"
        elif why == "deadline":
            msg = "request deadline exceeded while queued; load shed"
        elif why == "compile_budget":
            msg = "cold compile exceeded the admission budget; load shed"
        else:
            msg = f"serving overload ({why}); load shed"
        if tenant is not None:
            msg += f" [tenant {tenant}]"
        if queue_depth is not None:
            msg += f" (queue depth {queue_depth})"
        if retry_after_s is not None:
            msg += f"; retry after {retry_after_s:.2f}s"
        super().__init__(msg)


class PreemptionError(ResilienceError):
    """The run was asked to terminate (SIGTERM / preemption notice /
    watchdog-abort conversion site).  Raised by the supervisor's signal
    handler path, never by infrastructure — so it can never be confused
    with a retryable TRANSIENT_RUNTIME flake."""

    failure_class = FailureClass.PREEMPTED

    def __init__(self, why: str = "SIGTERM"):
        self.why = why
        super().__init__(f"run preempted ({why}); checkpoint and exit resumable")


class StallError(ResilienceError):
    """A dispatch exceeded the watchdog deadline (``watchdog.py``).  Carries
    the last-known phase so the supervisor's restart event can say WHERE the
    run wedged."""

    failure_class = FailureClass.STALL

    def __init__(self, phase: str, deadline_s: float):
        self.phase = phase
        self.deadline_s = deadline_s
        super().__init__(
            f"dispatch stalled: {phase!r} exceeded the {deadline_s:g}s "
            "watchdog deadline (STENCIL_WATCHDOG_S)"
        )


class CheckpointCorruptError(ResilienceError):
    """A checkpoint failed validation (missing/partial manifest, digest
    mismatch, unreadable state).  FATAL by class — there is nothing to retry
    or degrade; ``io/checkpoint.latest_valid`` responds by falling back to
    the previous checkpoint in the retention ring, and only raises this when
    no valid checkpoint remains."""

    def __init__(self, path: str, why: str):
        self.path = path
        self.why = why
        super().__init__(f"checkpoint {path} is not usable: {why}")


class InjectedFault(RuntimeError):
    """Raised by the fault-injection harness (``inject.py``).  Deliberately
    NOT a ``ResilienceError``: injected VMEM_OOM / COMPILE_REJECT /
    TRANSIENT faults carry only the real toolchain's message wording, so
    they exercise ``classify``'s substring matching the same way the real
    errors do (DIVERGENCE injections raise the typed ``DivergenceError``
    instead)."""


#: Mosaic scoped-VMEM exhaustion.  Current toolchain wording (pinned by
#: tests):  "Ran out of memory in memory space vmem. Used 107.90M of 100.00M"
#: and "exceeded scoped vmem limit by 8.59M".  Matching requires "vmem" PLUS
#: one of the exhaustion phrases — "vmem" alone appears in many benign
#: messages (e.g. our own log lines).
_VMEM_OOM_MARKERS = ("ran out of memory", "exceeded")

#: Transient infrastructure failures: the gRPC/socket texts a dropped
#: runtime or coordination-service connection surfaces as.  Markers are
#: deliberately SPECIFIC ("unavailable:" is
#: the gRPC status prefix, not the bare word) so unrelated errors that
#: merely mention availability are not silently re-run.  All lowercase;
#: matched case-insensitively.
_TRANSIENT_MARKERS = (
    "unavailable:",
    "deadline exceeded",
    "deadline_exceeded",
    "connection reset",
    "connection refused",
    "socket closed",
    "broken pipe",
    "transport closed",
    "temporarily unavailable",
    "try again later",
)

#: Device-unavailable / slice-health wordings: the fleet changed under the
#: run.  Checked BEFORE the transient list — the PJRT/megascale device-loss
#: texts carry the gRPC "UNAVAILABLE:" prefix, and a blind retry against a
#: missing chip re-fails forever; the supervisor's reshard/restore path is
#: the only recovery.  Current toolchain wordings (pinned by tests):
#:   "TPU is unhealthy: lost device at coordinates ..."   (PJRT health)
#:   "The TPU slice health check failed: worker N ..."    (megascale)
#:   "Device coordinator reported missing chips ..."      (coordinator)
#:   "device has been removed"                            (hot-unplug)
_CAPACITY_MARKERS = (
    "is unhealthy",
    "slice health",
    "missing chips",
    "device has been removed",
)

#: Serving-layer overload refusals (``serve/`` — bounded-queue rejection,
#: queued-past-deadline shed, cold-compile-over-budget refusal).  Checked
#: BEFORE the transient list: the deadline-shed wording contains "deadline
#: exceeded", which would otherwise classify a shed as a retry-in-place
#: transient — exactly the blind re-dispatch the OVERLOAD class forbids.
#: Wordings are OURS (OverloadError pins them), not a toolchain's, so they
#: are chosen to be unmistakable: "load shed" appears in every refusal.
_OVERLOAD_MARKERS = (
    "load shed",
    "request queue is full",
)

#: Non-VMEM Mosaic/XLA capability rejections observed by this repo's probes
#: (each wording is pinned by tests):
#:   "Target does not support this comparison"    (16-bit vector compare)
#:   "unsupported unaligned shape"                (z-column rotate, probe11b)
#:   "Rotate with non-32-bit data"                (narrow-dtype pltpu.roll)
#:   "Mosaic failed to compile TPU kernel"        (generic lowering failure)
#:   "failed to legalize operation"               (MLIR legalization)
#: Markers stay COMPILER-SPECIFIC: a bare "unsupported"/"not implemented"
#: would also match ordinary Python errors from user kernels (TypeError:
#: "unsupported operand type(s)"), sending a programming bug down the whole
#: ladder before it finally propagates.
_COMPILE_REJECT_MARKERS = (
    "target does not support",
    "does not support this comparison",
    "unsupported unaligned shape",
    "mosaic failed to compile",
    "failed to legalize",
    "rotate with non-32-bit data",
)


def classify(exc: BaseException) -> FailureClass:
    """Map an exception onto the failure taxonomy.

    Typed ``ResilienceError``s carry their class; everything else is
    classified by pinned message substrings, most-specific first: VMEM_OOM
    (a specific compile reject) before TRANSIENT (a connection drop mentions
    neither memory nor support) before the generic COMPILE_REJECT markers.
    Unrecognized errors are FATAL — the safe default: no retry, no
    degradation, propagate to the caller.
    """
    if isinstance(exc, ResilienceError):
        return exc.failure_class
    if isinstance(exc, KeyboardInterrupt):
        # typed check BEFORE any substring matching: Ctrl-C / SIGINT-driven
        # termination is a preemption notice, and no marker list may ever
        # reclassify it to a retryable class (tests pin this).  The retry
        # and ladder loops additionally catch only ``Exception``, so a
        # KeyboardInterrupt propagates even uninspected — this makes the
        # contract explicit for call sites that do classify BaseExceptions
        # (the supervisor).
        return FailureClass.PREEMPTED
    explicit = getattr(exc, "failure_class", None)
    if isinstance(explicit, FailureClass):
        return explicit
    msg = str(exc).lower()
    if "vmem" in msg and any(m in msg for m in _VMEM_OOM_MARKERS):
        return FailureClass.VMEM_OOM
    # capacity loss BEFORE transient: device-loss wordings brush the
    # "unavailable:" gRPC prefix, and re-running against a missing chip is
    # not a retry, it is a hang with extra steps (pinned by tests)
    if any(m in msg for m in _CAPACITY_MARKERS):
        return FailureClass.CAPACITY_LOSS
    # overload BEFORE transient: a deadline shed's wording mentions the
    # exceeded deadline, and a retry-in-place against a saturated queue is
    # the thundering herd the shed exists to break (pinned by tests)
    if any(m in msg for m in _OVERLOAD_MARKERS):
        return FailureClass.OVERLOAD
    if any(m in msg for m in _TRANSIENT_MARKERS):
        return FailureClass.TRANSIENT_RUNTIME
    if any(m in msg for m in _COMPILE_REJECT_MARKERS):
        return FailureClass.COMPILE_REJECT
    return FailureClass.FATAL


def is_degradable(cls: FailureClass) -> bool:
    """True for classes the degradation ladder may respond to by descending
    a rung (compile-time capability failures — see the module docstring for
    why TRANSIENT retries in place instead)."""
    return cls in (FailureClass.VMEM_OOM, FailureClass.COMPILE_REJECT)
