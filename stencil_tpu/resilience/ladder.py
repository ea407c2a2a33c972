"""The degradation ladder: declarative rungs replacing hand-rolled fallback.

The framework's implicit route order — wavefront m=16 -> lower m -> plane
streaming -> XLA reference — previously lived in three separate try/except
loops (``make_stream_step``, ``Jacobi3D.step``'s wrap and wavefront cases).
``DegradationLadder`` centralizes the control flow; each call site supplies
only its rungs:

* a ``Rung`` names one configuration (e.g. ``wavefront[m=3]``) and knows how
  to ``build()`` its step impl; arbitrary per-rung state (the stream plan,
  the bespoke depth) rides ``rung.state``.
* ``lower(rung, failure_class, exc)`` produces the next rung down (or
  ``None`` = ladder exhausted, propagate).  Degradable classes are VMEM_OOM
  and COMPILE_REJECT (``taxonomy.is_degradable``); everything else
  propagates immediately — transient retry happens at the dispatch layer
  (``retry.execute_with_retry`` in ``DistributedDomain.run_step``), never
  here, so the two mechanisms cannot compound.

Re-invoking after a descent re-uses the ORIGINAL call arguments, which is
only safe while they are alive: compile-rejects surface before donation
consumes the inputs (the compile-time-only-OOM assumption), and the ladder
now ENFORCES that with a ``buffers_live`` check — if an input was already
donated, the original error propagates instead of a use-after-free.

Fault-injection hooks (``inject.maybe_fail``) fire at rung build
(``compile`` phase) and before each impl invocation (``execute`` phase),
labeled ``<ladder-label>:<rung-name>`` — so tests drive every rung and every
descent deterministically on CPU.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, Optional, Tuple, Union

from stencil_tpu import telemetry
from stencil_tpu.resilience import inject
from stencil_tpu.resilience.retry import buffers_live
from stencil_tpu.resilience.taxonomy import FailureClass, classify, is_degradable
from stencil_tpu.telemetry import names as tm


@dataclasses.dataclass
class Rung:
    """One ladder configuration: a name (for logs and fault-plan labels), a
    zero-arg ``build`` returning the step impl, and free-form state the call
    site's ``lower`` callback reads to decide the next rung down."""

    name: str
    build: Callable[[], Callable]
    state: Dict[str, Any] = dataclasses.field(default_factory=dict)


class DegradationLadder:
    """Owns the current rung, its built impl, and classified descent.

    ``step(*args, **kwargs)`` invokes the current rung's impl; on a
    degradable failure it asks ``lower`` for the next rung, rebuilds, and
    re-invokes — repeating until an attempt succeeds or the ladder is
    exhausted.  The descent path is recorded in ``self.descents`` (a list of
    ``(from_rung, failure_class)`` names) for observability.
    """

    def __init__(
        self,
        first: Rung,
        lower: Optional[
            Callable[[Rung, FailureClass, BaseException], Optional[Rung]]
        ] = None,
        label: str = "step",
        eager_build: bool = True,
        buffers: Optional[Callable[[], Any]] = None,
        prefilter: Optional[
            Callable[[Rung], Union[None, str, Tuple[str, FailureClass]]]
        ] = None,
    ):
        self.label = label
        self.rung = first
        self._lower = lower
        # a STATIC reject — ``prefilter(rung)`` returning a reason string
        # descends without ever compiling (the analysis VMEM model's
        # verdict, stencil_tpu/analysis/vmem.py): the compile-and-catch
        # VMEM_OOM becomes a zero-cost descent.  A ``(reason, FailureClass)``
        # tuple names the class explicitly — the kernel legality model
        # (stencil_tpu/analysis/kernels.py) records COMPILE_REJECT descents
        # the same way.  None = rung may build.
        self._prefilter = prefilter
        # the arrays whose liveness gates a re-invocation; defaults to the
        # step call's own args (call sites whose donated buffers live
        # elsewhere — e.g. the models' domain-held curr dict — pass a getter)
        self._buffers = buffers
        self._impl: Optional[Callable] = None
        self.descents = []  # [(rung_name, FailureClass), ...]
        if eager_build:
            # a rung whose BUILD is rejected (compile-phase failure) descends
            # immediately — by construction nothing has executed yet, so no
            # donation guard is needed here
            while True:
                try:
                    self._ensure_built()
                    break
                except Exception as e:
                    cls = classify(e)
                    failed = self.rung.name
                    if not is_degradable(cls) or not self._descend(cls, e):
                        raise
                    from stencil_tpu.utils.logging import log_warn

                    log_warn(
                        f"{self.label}: {cls.value} building rung {failed!r}; "
                        f"descending to {self.rung.name!r}: {e}"
                    )

    def _apply_prefilter(self) -> None:
        """Descend past every rung the static prefilter rejects — recorded
        as the verdict's failure class (a bare reason string is the VMEM
        model's verdict, VMEM_OOM; a ``(reason, FailureClass)`` tuple names
        its class — COMPILE_REJECT for the kernel legality model), with no
        compile attempted.  An exhausted ladder raises the reject."""
        if self._prefilter is None:
            return
        while True:
            verdict = self._prefilter(self.rung)
            if verdict is None:
                return
            if isinstance(verdict, tuple):
                reason, cls = verdict
            else:
                reason, cls = verdict, FailureClass.VMEM_OOM
            exc = RuntimeError(f"statically prefiltered: {reason}")
            failed = self.rung.name
            if not self._descend(cls, exc):
                raise exc
            from stencil_tpu.utils.logging import log_warn

            log_warn(
                f"{self.label}: rung {failed!r} statically prefiltered "
                f"({reason}); descending to {self.rung.name!r} without "
                "compiling"
            )

    def _ensure_built(self) -> Callable:
        if self._impl is None:
            self._apply_prefilter()
            inject.maybe_fail("compile", f"{self.label}:{self.rung.name}")
            t0 = time.perf_counter()
            with telemetry.span(
                tm.EVENT_COMPILE, total=tm.PHASE_COMPILE,
                label=f"{self.label}:{self.rung.name}",
            ):
                self._impl = self.rung.build()
            dt = time.perf_counter() - t0
            telemetry.observe(tm.LADDER_BUILD_SECONDS, dt)
            telemetry.emit_event(
                tm.EVENT_COMPILE,
                phase="ladder",
                label=f"{self.label}:{self.rung.name}",
                seconds=round(dt, 6),
            )
        return self._impl

    def built(self) -> Callable:
        """The current rung's built impl (building it if needed) — for call
        sites that use the ladder for classified BUILD-time descent only and
        then drive the impl directly (e.g. ``DistributedDomain.realize``'s
        exchange-route step-down, where the per-call path must stay a bare
        function call)."""
        return self._ensure_built()

    def _descend(self, cls: FailureClass, exc: BaseException) -> bool:
        """Install the next rung down; False when the ladder is exhausted."""
        if self._lower is None:
            return False
        nxt = self._lower(self.rung, cls, exc)
        if nxt is None:
            return False
        self.descents.append((self.rung.name, cls))
        telemetry.inc(tm.LADDER_DESCENTS)
        telemetry.emit_event(
            tm.EVENT_DESCENT,
            label=self.label,
            from_rung=self.rung.name,
            to_rung=nxt.name,
            failure_class=cls.value,
        )
        self.rung = nxt
        self._impl = None
        return True

    def step(self, *args, **kwargs):
        from stencil_tpu.utils.logging import log_warn

        while True:
            try:
                impl = self._ensure_built()
                inject.maybe_fail("execute", f"{self.label}:{self.rung.name}")
                return impl(*args, **kwargs)
            except Exception as e:
                cls = classify(e)
                if not is_degradable(cls):
                    raise
                failed = self.rung.name
                # a descent re-invokes with the SAME args: refuse BEFORE
                # descending if any was already donated (deleted) — the
                # lower() callback has side effects (model mutation, a full
                # rebuild) that would otherwise be wasted on a re-invocation
                # the guard then vetoes (see module docstring)
                candidates = (
                    self._buffers() if self._buffers is not None else (args, kwargs)
                )
                if not buffers_live(candidates):
                    log_warn(
                        f"{self.label}: {cls.value} on rung {failed!r} but an "
                        "input buffer was already donated (deleted) — cannot "
                        "re-invoke a lower rung, propagating"
                    )
                    raise
                if not self._descend(cls, e):
                    raise
                log_warn(
                    f"{self.label}: {cls.value} on rung {failed!r}; descending "
                    f"to {self.rung.name!r}: {e}"
                )
