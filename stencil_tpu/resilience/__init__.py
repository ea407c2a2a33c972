"""Unified resilience layer: failure taxonomy, degradation ladder,
retry/backoff, fault injection, and the divergence sentinel.

The reference library's core value is picking the fastest transport per
neighbor and degrading gracefully when a capability is absent (PAPER.md:
per-pair transport selection with staged-MPI fallback).  This package is the
TPU port's equivalent, centralized: every failure-handling decision that was
previously scattered across ``ops/stream.py``, ``models/jacobi.py``, and the
bench driver flows through one place.

* ``taxonomy``  — ``classify(exc) -> FailureClass`` replaces ad-hoc
  substring matching; the current Mosaic/XLA error texts are pinned by
  tests so a toolchain upgrade that re-words them is caught loudly.
* ``ladder``    — ``DegradationLadder`` formalizes the implicit route order
  (wavefront m=16 -> lower m -> plane/slab -> reference) as declarative
  rungs with per-rung state; ``make_stream_step`` and the bespoke jacobi
  paths consume it instead of hand-rolled try/except loops.
* ``retry``     — retry-with-backoff for ``TRANSIENT_RUNTIME`` failures (the
  dropped-connection class), guarded by a donated-buffer liveness check
  so a retry can never re-execute with deleted inputs.
* ``inject``    — ``STENCIL_FAULT_PLAN`` deterministic fault injection, so
  every rung and retry path is testable on CPU.
* ``sentinel``  — optional NaN/Inf divergence check at a configurable step
  cadence, raising a classified ``DIVERGENCE`` error naming the quantity.

See ``docs/resilience.md`` for the knob reference and the
compile-time-only-OOM assumption behind donated-buffer retries.
"""

from stencil_tpu.resilience.inject import FaultPlan, maybe_fail, set_plan
from stencil_tpu.resilience.ladder import DegradationLadder, Rung
from stencil_tpu.resilience.retry import (
    RetryPolicy,
    buffers_live,
    execute_with_retry,
)
from stencil_tpu.resilience.sentinel import DivergenceSentinel
from stencil_tpu.resilience.taxonomy import (
    DivergenceError,
    FailureClass,
    InjectedFault,
    classify,
)

__all__ = [
    "DegradationLadder",
    "DivergenceError",
    "DivergenceSentinel",
    "FailureClass",
    "FaultPlan",
    "InjectedFault",
    "RetryPolicy",
    "Rung",
    "buffers_live",
    "classify",
    "execute_with_retry",
    "maybe_fail",
    "set_plan",
]
