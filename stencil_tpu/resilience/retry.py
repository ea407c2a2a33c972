"""Retry-with-backoff for TRANSIENT_RUNTIME failures, donation-guarded.

The transient class (dropped connections, RPC unavailability) is the one
failure mode where re-running the SAME work is the right response — without
it a single dropped connection discards a whole run.

The guard: every fast-path step is jitted with ``donate_argnums=0``, so a
failure that surfaces MID-EXECUTION may have already consumed its input
buffers — re-invoking would read deleted arrays.  In practice Mosaic
scoped-VMEM OOM and the connection class both surface at COMPILE time, before
donation (the compile-time-only-OOM assumption, docs/resilience.md), but the
assumption is now ENFORCED rather than hoped: ``buffers_live`` checks
``x.is_deleted()`` on every candidate input and a retry is refused (the
original error propagates, with a logged explanation) when any buffer is
gone.

Two serving-era hardenings (docs/serving.md):

* **Jittered backoff** — when N tenants hit the same transient (one
  dropped connection fails every in-flight dispatch), unjittered exponential backoff
  re-synchronizes their re-dispatches into lockstep waves.  ``delay_s``
  spreads each sleep uniformly over ``[1-jitter, 1+jitter]`` times the
  exponential base (full determinism for tests via an injectable ``rng``).
* **Shared retry budgets** — ``RetryBudget`` caps the TOTAL retries a
  tenant may charge across all its requests, so one flaky tenant cannot
  monopolize dispatch slots with endless per-call retry allowances.
"""

from __future__ import annotations

import dataclasses
import random
import time
from typing import Callable, Iterable, Optional

from stencil_tpu import telemetry
from stencil_tpu.resilience.taxonomy import FailureClass, classify
from stencil_tpu.telemetry import names as tm


@dataclasses.dataclass(frozen=True)
class RetryPolicy:
    """Exponential backoff: attempt n (0-based) sleeps
    ``backoff_base_s * multiplier**n`` (jittered) before re-invoking.
    ``max_retries=0`` disables retrying entirely; ``jitter=0`` recovers the
    deterministic unjittered schedule."""

    max_retries: int = 3
    backoff_base_s: float = 0.25
    multiplier: float = 2.0
    #: uniform spread: each delay is scaled by a factor drawn from
    #: ``[1-jitter, 1+jitter]`` so synchronized failures desynchronize
    #: their re-dispatches (clamped to [0, 1] by from_env)
    jitter: float = 0.1

    @classmethod
    def from_env(cls) -> "RetryPolicy":
        """``STENCIL_RETRY_MAX`` / ``STENCIL_RETRY_BACKOFF_S`` /
        ``STENCIL_RETRY_JITTER`` override the defaults (validated reads —
        see utils/config.py)."""
        from stencil_tpu.utils.config import env_float, env_int

        return cls(
            max_retries=env_int("STENCIL_RETRY_MAX", cls.max_retries, minimum=0),
            backoff_base_s=env_float(
                "STENCIL_RETRY_BACKOFF_S", cls.backoff_base_s, minimum=0.0
            ),
            # clamp to <=1: a spread factor past 1 could go negative
            jitter=min(1.0, env_float("STENCIL_RETRY_JITTER", cls.jitter, minimum=0.0)),
        )

    def delay_s(self, attempt: int, rng: Optional[random.Random] = None) -> float:
        base = self.backoff_base_s * self.multiplier**attempt
        if self.jitter <= 0.0:
            return base
        u = (rng or random).random()  # [0, 1)
        return base * (1.0 + self.jitter * (2.0 * u - 1.0))


class RetryBudget:
    """A shared, mutable retry allowance — one per tenant in the serving
    layer.  Every retry across every call charged to the same budget
    decrements it; at zero, the transient propagates (``RETRY_EXHAUSTED``)
    even when the per-call policy would have kept going.  Deliberately NOT
    thread-safe-fancy: the serving loop charges it from one dispatch thread.
    """

    def __init__(self, allowance: int = 8, label: str = "budget"):
        self.allowance = int(allowance)
        self.remaining = int(allowance)
        self.label = label

    def try_charge(self) -> bool:
        """Consume one retry credit; False when the budget is spent."""
        if self.remaining <= 0:
            return False
        self.remaining -= 1
        return True

    def replenish(self) -> None:
        """Restore the full allowance (e.g. after a sustained healthy
        window, mirroring the supervisor's restart-credit replenish)."""
        self.remaining = self.allowance


def buffers_live(buffers) -> bool:
    """True when no candidate input buffer has been deleted (donated and
    consumed).  ``buffers`` is any pytree (dict/tuple/list of arrays);
    non-array leaves (ints, numpy) are trivially live."""
    import jax

    for leaf in jax.tree_util.tree_leaves(buffers):
        is_deleted = getattr(leaf, "is_deleted", None)
        if is_deleted is not None and is_deleted():
            return False
    return True


def execute_with_retry(
    fn: Callable,
    *args,
    label: str = "step",
    policy: Optional[RetryPolicy] = None,
    buffers: Optional[Callable[[], Iterable]] = None,
    sleep: Callable[[float], None] = time.sleep,
    budget: Optional[RetryBudget] = None,
    rng: Optional[random.Random] = None,
    **kwargs,
):
    """Invoke ``fn(*args, **kwargs)``, retrying classified TRANSIENT_RUNTIME
    failures with jittered exponential backoff.

    ``buffers`` (a zero-arg callable returning the arrays whose liveness
    gates a retry) defaults to scanning ``args``/``kwargs`` for jax arrays.
    ``budget`` (optional, shared across calls — the serving layer passes the
    tenant's) must yield a credit for every retry on top of the per-call
    policy.  ``rng`` pins the jitter draw for tests.  Any other failure
    class propagates immediately — degradation (VMEM_OOM / COMPILE_REJECT)
    belongs to the ladder, not the retrier.
    """
    from stencil_tpu.utils.logging import log_warn

    policy = policy or RetryPolicy.from_env()
    attempt = 0
    while True:
        try:
            return fn(*args, **kwargs)
        except Exception as e:
            if classify(e) is not FailureClass.TRANSIENT_RUNTIME:
                raise
            if attempt >= policy.max_retries or (
                budget is not None and not budget.try_charge()
            ):
                telemetry.inc(tm.RETRY_EXHAUSTED)
                telemetry.emit_event(
                    tm.EVENT_RETRY_EXHAUSTED,
                    label=label,
                    max_retries=policy.max_retries,
                    budget_remaining=(budget.remaining if budget else None),
                    error=str(e)[:300],
                )
                log_warn(
                    f"{label}: transient failure persisted through the retry "
                    f"allowance (policy {policy.max_retries}"
                    + (f", shared budget {budget.label!r}" if budget else "")
                    + f"); giving up: {e}"
                )
                raise
            candidates = buffers() if buffers is not None else (args, kwargs)
            if not buffers_live(candidates):
                telemetry.inc(tm.RETRY_REFUSED)
                telemetry.emit_event(
                    tm.EVENT_RETRY_REFUSED, label=label, error=str(e)[:300]
                )
                log_warn(
                    f"{label}: transient failure but an input buffer was "
                    "already donated (deleted) — retry would reuse freed "
                    f"memory, propagating instead: {e}"
                )
                raise
            delay = policy.delay_s(attempt, rng=rng)
            attempt += 1
            telemetry.inc(tm.RETRY_ATTEMPTS)
            telemetry.emit_event(
                tm.EVENT_RETRY,
                label=label,
                attempt=attempt,
                max_retries=policy.max_retries,
                delay_s=delay,
                error=str(e)[:300],
            )
            log_warn(
                f"{label}: transient failure "
                f"(attempt {attempt}/{policy.max_retries}), retrying in "
                f"{delay:.2f}s: {e}"
            )
            # the back-off is host work that stalls the dispatch pipeline:
            # a span, so a profiler trace shows what the idle device waited on
            with telemetry.span(tm.EVENT_RETRY, label=label, attempt=attempt):
                sleep(delay)
