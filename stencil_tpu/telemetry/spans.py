"""Nestable wall-clock span tracer + Chrome trace-event dump.

``annotate`` (the NVTX-range analog — ``jax.named_scope`` labels the region
in compiled HLO and XProf timelines) and ``trace`` (a ``jax.profiler``
capture) live here, alongside the host-side span recorder.

Every span is also a ``jax.profiler.TraceAnnotation`` (``_profiler_annotation``):
under a profiler session it sits in the profiler's own trace beside the device
ops, whether or not the recorder below is on.

Spans record (name, start, duration, thread, parent, args) tuples that
``chrome_trace_events`` renders as Chrome trace-event JSON — complete
("ph":"X") events with microsecond timestamps — viewable in
``chrome://tracing`` or https://ui.perfetto.dev.  Timestamps are
``time.perf_counter`` offsets from the recorder's epoch: monotonic and
mutually consistent, which is all the trace viewers need.

jax is touched ONLY if it is already imported (``sys.modules`` probe, the
same fail-closed rule as ``logging._rank``): recording a span must never
pull in — let alone initialize — a jax backend.
"""

from __future__ import annotations

import contextlib
import os
import sys
import threading
import time
from typing import List, Optional

#: warn once per process when the profiler backend is absent — a CPU dryrun
#: container must run a profiled command line unchanged, just without traces
_trace_unavailable_warned = False


def annotate(name: str):
    """Label a region in traces and HLO (the NVTX range analog)."""
    import jax

    return jax.named_scope(name)


@contextlib.contextmanager
def trace(log_dir: Optional[str]):
    """Capture a ``jax.profiler`` trace into ``log_dir`` (no-op when None).
    View with TensorBoard's profile plugin / xprof.

    Degrades gracefully: the directory is created up front (a capture that
    dies mid-run must still leave the dir its tooling expects), and a
    backend with no profiler support (CPU dryrun containers) WARNS once and runs the body unprofiled — a profiling knob
    must never crash the run it was meant to observe."""
    if not log_dir:
        yield
        return
    os.makedirs(log_dir, exist_ok=True)
    import jax

    global _trace_unavailable_warned
    ctx = None
    try:
        ctx = jax.profiler.trace(log_dir)
        ctx.__enter__()
    except Exception as e:  # noqa: BLE001 — degrade, never crash the run
        ctx = None
        if not _trace_unavailable_warned:
            _trace_unavailable_warned = True
            from stencil_tpu.utils.logging import log_warn

            log_warn(
                f"jax.profiler unavailable on this backend ({e!r}); "
                f"running unprofiled — {log_dir} will hold no trace"
            )
    try:
        yield
    finally:
        if ctx is not None:
            try:
                ctx.__exit__(None, None, None)
            except Exception as e:  # noqa: BLE001 — a failed trace FINALIZE
                # (profiler died mid-capture) must not eat the run's result
                from stencil_tpu.utils.logging import log_warn

                log_warn(f"jax.profiler trace finalize failed: {e!r}")


def _maybe_named_scope(name: str):
    """``jax.named_scope`` when jax is ALREADY imported, else a null context
    — a span must never import jax on behalf of the caller."""
    jax = sys.modules.get("jax")
    if jax is None:
        return contextlib.nullcontext()
    return jax.named_scope(name)


def _profiler_annotation(name: str, args: dict):
    """The span on the PROFILER's timeline: a ``jax.profiler.TraceAnnotation``
    (name + args) when jax is already imported, else a null context — the
    same fail-closed rule as ``_maybe_named_scope``.  The profiler session
    is the switch: with none running this is a ~0.5 us no-op; with one
    (``--profile-dir`` / ``STENCIL_PROFILE_DIR``, the benchmark's
    ``--trace 1``) the span lands on ``/host:CPU`` of the same xplane as
    the device ops, on the same clock.  It never syncs the device."""
    jax = sys.modules.get("jax")
    if jax is None:
        return contextlib.nullcontext()
    return jax.profiler.TraceAnnotation(name, **args)


class SpanRecorder:
    """Thread-safe recorder of completed spans with a per-thread name stack
    (so a span knows its parent at record time) and a per-thread PHASE stack
    (so an event knows which phase of the program it fell in).  The name
    stack moves only while the recorder is on; the phase stack is always
    live: two list operations per ``span(total=)``."""

    def __init__(self):
        self.epoch = time.perf_counter()
        self._lock = threading.Lock()
        self._events: List[dict] = []
        #: (ts_us, series, value) counter samples — rendered as Chrome
        #: counter-track ("ph":"C") events so Perfetto shows cumulative
        #: exchange bytes as a throughput track under the spans
        self._counter_samples: List[tuple] = []
        self._counter_last: dict = {}
        self._tls = threading.local()

    # --- the per-thread nesting stack ----------------------------------------
    def _stack(self) -> list:
        s = getattr(self._tls, "stack", None)
        if s is None:
            s = self._tls.stack = []
        return s

    def current(self) -> Optional[str]:
        s = self._stack()
        return s[-1] if s else None

    def push(self, name: str) -> None:
        self._stack().append(name)

    def pop(self) -> None:
        s = self._stack()
        if s:
            s.pop()

    # --- the per-thread phase stack (always live) ------------------------------
    def _phases(self) -> list:
        s = getattr(self._tls, "phases", None)
        if s is None:
            s = self._tls.phases = []
        return s

    def phase(self) -> Optional[str]:
        """The innermost open phase on this thread (None: no program span)."""
        s = self._phases()
        return s[-1] if s else None

    def push_phase(self, phase: str) -> None:
        self._phases().append(phase)

    def pop_phase(self) -> None:
        s = self._phases()
        if s:
            s.pop()

    # --- recording ------------------------------------------------------------
    def record(self, name: str, t0: float, dur: float, parent=None, **args) -> None:
        """Record a completed span.  ``t0`` is a ``time.perf_counter`` value;
        ``dur`` is seconds."""
        if parent is None:
            parent = self.current()
        ev = {
            "name": name,
            "ts": (t0 - self.epoch) * 1e6,  # µs, trace-event convention
            "dur": dur * 1e6,
            "tid": threading.get_ident() & 0xFFFF,
            "args": dict(args, parent=parent) if parent else dict(args),
        }
        with self._lock:
            self._events.append(ev)

    def sample_counter(self, name: str, value: float, t: float = None) -> None:
        """Record one counter-track sample at ``t`` (a ``perf_counter``
        value; now when None).  Consecutive identical values are dropped —
        a flat counter contributes one point, not one per span."""
        if t is None:
            t = time.perf_counter()
        ts = (t - self.epoch) * 1e6
        with self._lock:
            if self._counter_last.get(name) == value:
                return
            self._counter_last[name] = value
            self._counter_samples.append((ts, name, value))

    def events(self) -> List[dict]:
        with self._lock:
            return list(self._events)

    def counter_samples(self) -> List[tuple]:
        with self._lock:
            return list(self._counter_samples)

    def clear(self) -> None:
        with self._lock:
            self._events.clear()
            self._counter_samples.clear()
            self._counter_last.clear()

    def chrome_trace_events(self, pid: int = 0) -> List[dict]:
        """The recorded spans as Chrome trace-event dicts (complete events),
        followed by the counter-track samples ("ph":"C" — Perfetto renders
        each series as a value track alongside the spans)."""
        out = [
            {
                "name": e["name"],
                "ph": "X",
                "ts": e["ts"],
                "dur": e["dur"],
                "pid": pid,
                "tid": e["tid"],
                "args": e["args"],
            }
            for e in self.events()
        ]
        out.extend(
            {
                "name": name,
                "ph": "C",
                "ts": ts,
                "pid": pid,
                "args": {"value": value},
            }
            for ts, name, value in self.counter_samples()
        )
        return out
