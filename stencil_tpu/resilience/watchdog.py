"""Dispatch watchdog: detect dispatches that WEDGE instead of failing.

Every failure mode the resilience layer handled so far announces itself —
an exception to classify, a NaN to detect.  The one that doesn't is the
hang: a runtime whose peer went away mid-collective, a
device-side deadlock, a preempted neighbor stalling a ppermute.  The run
burns its preemption deadline doing nothing, and no checkpoint gets taken.

``DispatchWatchdog`` is a monitor THREAD armed around each
``run_step``/``exchange`` dispatch (``DistributedDomain`` arms it when
``STENCIL_WATCHDOG_S`` is set).  A dispatch that runs past the deadline:

* always counts a ``watchdog.stalls`` and emits a ``watchdog.stall`` event
  carrying the last-known phase — the post-mortem breadcrumb a hung-then-
  SIGKILLed run leaves behind;
* with ``STENCIL_WATCHDOG_ABORT=1``, additionally interrupts the main
  thread.  The interrupt surfaces as ``KeyboardInterrupt`` inside the
  blocked dispatch; the arming site converts it to a classified
  :class:`StallError` (``take_stall``) so the supervisor's
  restart-from-checkpoint budget — not the PREEMPTED final-checkpoint path
  and not the transient retry loop — handles it.

The deadline should comfortably exceed the slowest legitimate dispatch
(compiles included): a false trip in abort mode costs a supervisor restart.
Non-abort mode (the default) is observation-only and always safe.
"""

from __future__ import annotations

import contextlib
import threading
import time
from typing import Optional

from stencil_tpu import telemetry
from stencil_tpu.resilience.taxonomy import StallError
from stencil_tpu.telemetry import names as tm


def _interrupt_main() -> None:
    import _thread

    _thread.interrupt_main()


class DispatchWatchdog:
    """One monitor thread, armed/disarmed around dispatches via ``watch``.

    The thread is started lazily at first arm and is a daemon — an idle
    watchdog never blocks interpreter exit.  ``interrupt`` and ``clock``
    are injectable for tests."""

    def __init__(
        self,
        deadline_s: float,
        abort: bool = False,
        clock=time.monotonic,
        interrupt=None,
    ):
        assert deadline_s > 0, deadline_s
        self.deadline_s = float(deadline_s)
        self.abort = bool(abort)
        self._clock = clock
        self._interrupt = interrupt or _interrupt_main
        self._cv = threading.Condition()
        self._thread: Optional[threading.Thread] = None
        self._stop = False
        # armed state: a generation counter distinguishes "this arm" from
        # "a later arm" so a disarm+rearm can never be fired by a stale wait
        self._gen = 0
        self._phase: Optional[str] = None
        self._due: Optional[float] = None
        self._stalled: Optional[str] = None  # trip of the CURRENT arm
        # trip of the most recently EXITED watch — what take_stall claims.
        # Every watch exit overwrites it (None when that dispatch did not
        # trip), so a stale trip can never outlive one dispatch and relabel
        # a later unrelated interrupt.
        self._last_stall: Optional[str] = None

    @classmethod
    def from_env(cls) -> Optional["DispatchWatchdog"]:
        """``STENCIL_WATCHDOG_S`` (seconds; unset/0 = no watchdog) and
        ``STENCIL_WATCHDOG_ABORT`` (default off: observe-only), validated
        reads."""
        from stencil_tpu.utils.config import env_bool, env_float

        deadline = env_float("STENCIL_WATCHDOG_S", 0.0, minimum=0.0)
        if deadline <= 0:
            return None
        return cls(deadline, abort=env_bool("STENCIL_WATCHDOG_ABORT", False))

    # --- arming ---------------------------------------------------------------

    @contextlib.contextmanager
    def watch(self, phase: str):
        """Arm the deadline around one dispatch; disarm on exit (success OR
        exception — an exception means the dispatch did not hang)."""
        self._ensure_thread()
        with self._cv:
            self._gen += 1
            self._phase = phase
            self._due = self._clock() + self.deadline_s
            self._stalled = None
            self._cv.notify_all()
        try:
            yield
        finally:
            with self._cv:
                self._gen += 1
                self._phase = None
                self._due = None
                self._last_stall = self._stalled  # this dispatch's trip (or None)
                self._stalled = None
                self._cv.notify_all()

    def take_stall(self) -> Optional[StallError]:
        """The classified error for the MOST RECENT dispatch's deadline trip
        (and clear it) — call sites convert the abort-mode
        ``KeyboardInterrupt`` into this so ``classify`` sees STALL, not
        PREEMPTED.  Only the just-exited watch's trip is claimable: an
        earlier dispatch's unclaimed trip (its wedge surfaced as some other
        exception) is cleared at the next watch exit and can never relabel
        a later genuine Ctrl-C."""
        with self._cv:
            phase = self._last_stall or self._stalled
            self._last_stall = self._stalled = None
        if phase is None:
            return None
        return StallError(phase, self.deadline_s)

    def close(self) -> None:
        with self._cv:
            self._stop = True
            self._cv.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=1.0)
            self._thread = None

    # --- monitor thread -------------------------------------------------------

    def _ensure_thread(self) -> None:
        if self._thread is None or not self._thread.is_alive():
            self._stop = False
            self._thread = threading.Thread(
                target=self._run, name="stencil-watchdog", daemon=True
            )
            self._thread.start()

    def _run(self) -> None:
        with self._cv:
            while not self._stop:
                if self._due is None:
                    self._cv.wait()
                    continue
                gen = self._gen
                remaining = self._due - self._clock()
                if remaining > 0:
                    self._cv.wait(timeout=remaining)
                    continue
                # deadline passed and the SAME arm is still active: fire.
                # The lock is HELD through the interrupt: a disarm cannot
                # slip between this gen check and interrupt_main, so an
                # abort-mode interrupt always lands while the arming site's
                # converter is still on the stack (interrupt_main only sets
                # a pending flag — nothing here blocks on the main thread)
                if self._gen == gen and self._due is not None:
                    phase = self._phase or "?"
                    self._stalled = phase
                    self._due = None  # one trip per arm
                    self._fire(phase)

    def _fire(self, phase: str) -> None:
        from stencil_tpu.utils.logging import log_warn

        telemetry.inc(tm.WATCHDOG_STALLS)
        telemetry.emit_event(
            tm.EVENT_WATCHDOG_STALL,
            phase=phase,
            deadline_s=self.deadline_s,
            abort=self.abort,
        )
        log_warn(
            f"watchdog: {phase!r} exceeded the {self.deadline_s:g}s deadline"
            + (" — interrupting the dispatch" if self.abort else " (observe-only)")
        )
        if self.abort:
            self._interrupt()
