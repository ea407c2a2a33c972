"""Unified telemetry: metrics registry, span tracer, structured event log.

The measurement substrate under every perf claim this repo makes: the
reference instruments every phase with NVTX ranges and reports Statistics
CSVs per benchmark (src/stencil.cu:672-861, bin/statistics.hpp); here the
same visibility is one process-local facade:

* **metrics** (``metrics.py``) — counters / gauges / histograms, histograms
  backed by ``utils/statistics.Statistics`` (trimean and friends for free).
  ``snapshot()`` returns the JSON-safe dict ``bench.py`` embeds in the
  BENCH artifact and every ``bin/`` driver writes via ``--metrics-out``.
* **spans** (``spans.py``) — nestable spans: always a
  ``jax.profiler.TraceAnnotation`` (so a profiler trace holds them beside the
  device ops), and with telemetry on wall-clock spans dumped as Chrome
  trace-event JSON (``chrome://tracing`` / Perfetto); also home of the
  ``annotate``/``trace`` jax wrappers.
* **events** (``events.py``) — rank-tagged JSONL event log for the signals
  a program must consume (retries, ladder descents, divergence trips).

Knobs (validated reads — ``utils/config.py`` pattern):

* ``STENCIL_TELEMETRY=1|0``     — master switch (default: on iff a dir is set)
* ``STENCIL_TELEMETRY_DIR=D``   — output dir for events + traces; implies on
* ``STENCIL_TELEMETRY_EVENTS``  — JSONL sink on/off (default: on iff dir set)

Design rules (enforced here, asserted by tests):

* **near-zero cost when disabled** — ``span()`` opens one profiler
  annotation (a no-op without a profiler session) and yields, ``observe``/
  ``emit_event`` return after one attribute check, no formatting happens.
  Counters/gauges stay live always (an int add; a post-mortem ``snapshot()``
  after a failed run still counts its retries).
* **set-up is accounted always** — the few set-up-class spans open with
  ``total=`` (a ``names.PHASE_*``): their wall time goes to always-live
  totals and jax's own trace / lower / compile / cache events
  (``watch_jax``) are counted under the innermost open phase, ``setup.*``
  until the first steady dispatch of any program and ``run.*`` after it.
  The steady dispatch path is marked, never timed: no clock read, no sync.
* **never initialize a jax backend** — rank tags use the fail-closed
  ``logging._rank`` probe; spans enter ``jax.named_scope`` only when jax is
  already imported.
* **no free-string names** — call sites name series through
  ``telemetry.names`` constants; the ``telemetry-name`` rule of
  ``stencil_tpu.lint`` enforces it (and ``jax-import`` enforces the
  backend-free contract above).
"""

from __future__ import annotations

import collections
import contextlib
import json
import os
import sys
import threading
import time
from typing import List, Optional

from stencil_tpu.telemetry import names  # noqa: F401  (re-export)
from stencil_tpu.telemetry.events import EventSink
from stencil_tpu.telemetry.metrics import MetricsRegistry
from stencil_tpu.telemetry.spans import (  # noqa: F401  (annotate/trace re-export)
    SpanRecorder,
    _maybe_named_scope,
    _profiler_annotation,
    annotate,
    trace,
)
from stencil_tpu.utils.logging import _rank


#: events kept in the in-memory flight ring (the crash-report tail)
RING_SIZE = 256

#: counters sampled onto Chrome counter tracks at every span record — the
#: cumulative series whose slope IS the throughput Perfetto shows next to
#: the spans (exchange/packed bytes)
_TRACK_COUNTERS = (
    names.EXCHANGE_BYTES,
    names.EXCHANGE_PACKED_BYTES,
)


class _Telemetry:
    """Process-local singleton state (module functions below delegate)."""

    def __init__(self):
        self.registry = MetricsRegistry()
        self.spans = SpanRecorder()
        self.sink: Optional[EventSink] = None
        self.enabled = False
        self.out_dir: Optional[str] = None
        self._configured = False
        #: bounded flight ring of the last events — ALWAYS live (one deque
        #: append; the caller already built the fields dict), because the
        #: runs whose last events matter most are the ones that die with
        #: telemetry off.  Dumped by the flight recorder's crash report.
        self.ring = collections.deque(maxlen=RING_SIZE)
        #: ``setup`` until the first steady dispatch of any program, then
        #: ``run`` (``dispatch_phase``): which half of the phase totals grows
        self.epoch = names.EPOCH_SETUP
        #: jax's monitoring listeners are registered (``watch_jax``)
        self.jax_watched = False

    def configure_from_env(self) -> None:
        from stencil_tpu.utils.config import env_bool, env_str

        out_dir = env_str("STENCIL_TELEMETRY_DIR", None)
        enabled = env_bool("STENCIL_TELEMETRY", out_dir is not None)
        events = env_bool("STENCIL_TELEMETRY_EVENTS", out_dir is not None)
        if events and out_dir is None and "STENCIL_TELEMETRY_EVENTS" in os.environ:
            # an explicit EVENTS=1 with nowhere to write is a config error
            # even when the master switch is off — the user asked for a JSONL
            # log they would silently never get
            raise ValueError(
                "STENCIL_TELEMETRY_EVENTS=1 needs STENCIL_TELEMETRY_DIR to "
                "point at a writable directory (events are a JSONL file; "
                "set the dir or unset STENCIL_TELEMETRY_EVENTS)"
            )
        self.enabled = enabled
        self.out_dir = out_dir
        self.sink = EventSink(out_dir) if (enabled and events and out_dir) else None
        self._configured = True


_t = _Telemetry()


def _cfg() -> _Telemetry:
    if not _t._configured:
        _t.configure_from_env()
    return _t


# --- lifecycle ---------------------------------------------------------------


def enabled() -> bool:
    return _cfg().enabled


def enable(dir: Optional[str] = None, events: Optional[bool] = None) -> None:
    """Programmatic enable (tests, driver ``--metrics-out``).  ``dir`` adds
    the JSONL event sink and gives Chrome-trace dumps a default home;
    without it, spans/histograms record in memory only."""
    t = _t
    t._configured = True
    t.enabled = True
    if dir is not None:
        t.out_dir = str(dir)
        os.makedirs(t.out_dir, exist_ok=True)
    if events is None:
        events = t.out_dir is not None
    if events and t.out_dir is None:
        raise ValueError("telemetry events need a directory (enable(dir=...))")
    if t.sink is not None:
        t.sink.close()
    t.sink = EventSink(t.out_dir) if events else None


def disable() -> None:
    t = _t
    t._configured = True
    t.enabled = False
    if t.sink is not None:
        t.sink.close()
        t.sink = None
    t.out_dir = None


def reset() -> None:
    """Clear all recorded metrics, spans, and the event ring (counters
    restart at 0, the phase totals with them: the account of a set-up starts
    over).  jax's listeners stay registered."""
    t = _cfg()
    t.registry.reset()
    t.spans.clear()
    t.ring.clear()
    t.epoch = names.EPOCH_SETUP


# --- metrics -----------------------------------------------------------------


def inc(name: str, value: int = 1) -> None:
    """Increment a counter.  Always live (a dict hit + int add)."""
    _cfg().registry.counter(name).inc(value)


def set_gauge(name: str, value: float) -> None:
    _cfg().registry.gauge(name).set(value)


def observe(name: str, value: float) -> None:
    """Record one histogram sample — only while telemetry is enabled, so a
    disabled hot loop never touches the Statistics list."""
    t = _cfg()
    if t.enabled:
        t.registry.histogram(name).observe(value)


def snapshot() -> dict:
    """JSON-safe dict of all metrics.  Every canonical counter name appears
    (0 when untouched) and every canonical histogram appears (empty
    distribution when never observed) so snapshots diff cleanly across
    rounds."""
    return _cfg().registry.snapshot(
        seed_counters=names.ALL_COUNTERS,
        seed_histograms=names.ALL_HISTOGRAMS,
    )


# --- spans -------------------------------------------------------------------


class _Phase:
    """One open phase (``span(total=)``): on the per-thread phase stack, so
    jax's events are charged to it, and -- a timed phase -- its wall time and
    a count into the always-live totals of the epoch it opened in.  The
    steady phase has no total: entering it reads no clock."""

    __slots__ = ("t", "phase", "seconds", "count", "t0")

    def __init__(self, t: _Telemetry, phase: str):
        self.t, self.phase = t, phase
        self.seconds = names.PHASE_SERIES.get((t.epoch, names.TOTAL_SPAN_SECONDS, phase))
        self.count = names.PHASE_SERIES.get((t.epoch, names.TOTAL_SPAN_COUNT, phase))

    def __enter__(self):
        self.t.spans.push_phase(self.phase)
        if self.seconds is not None:
            self.t0 = time.perf_counter()

    def __exit__(self, *exc):
        if self.seconds is not None:
            dur = time.perf_counter() - self.t0
            self.t.registry.counter(self.seconds).inc(dur)
            self.t.registry.counter(self.count).inc()
        self.t.spans.pop_phase()


_NO_PHASE = contextlib.nullcontext()


@contextlib.contextmanager
def span(name: str, histogram: Optional[str] = None, total: Optional[str] = None, **args):
    """Nestable span.  ALWAYS: a ``jax.profiler.TraceAnnotation`` of the
    same name and args (if jax is already up) — a no-op without a profiler
    session, and with one the span sits on the host plane of the profiler's
    trace, on the device ops' clock.  ``total`` (a ``names.PHASE_*``; the
    set-up-class spans and the dispatches only) makes the span a phase of the
    set-up account, always live: see ``_Phase``.  When telemetry is enabled,
    additionally: records a Chrome-trace event (nested under the enclosing
    span), optionally observes the duration into ``histogram``, and labels
    the region in HLO/XProf."""
    t = _cfg()
    phase = _NO_PHASE if total is None else _Phase(t, total)
    with _profiler_annotation(name, args), phase:
        if not t.enabled:
            yield
            return
        parent = t.spans.current()
        t.spans.push(name)
        t0 = time.perf_counter()
        try:
            with _maybe_named_scope(name):
                yield
        finally:
            dur = time.perf_counter() - t0
            t.spans.pop()
            t.spans.record(name, t0, dur, parent=parent, **args)
            _sample_track_counters(t, t0 + dur)
            if histogram is not None:
                t.registry.histogram(histogram).observe(dur)


def dispatch_phase(first: bool) -> str:
    """The phase of one program dispatch, for ``span(total=)``.  ``first``:
    the program has not been called with these static arguments before, so
    jax traces, lowers and compiles or loads inside the call.  Any later
    call is steady, and the first steady dispatch of any program ENDS
    set-up: from it on totals and events accumulate under ``run.*`` (a
    second fill, a tenant admitted late, a re-realize after a reshard)."""
    if first:
        return names.PHASE_FIRST_DISPATCH
    _t.epoch = names.EPOCH_RUN
    return names.PHASE_STEADY


# --- jax's own events, under the program's phases -----------------------------

#: how many jaxpr traces are open on this thread: a jit traced inside a jit
#: reports its own duration inside the outer one's
_tracing = threading.local()


def _charge(total: str, value) -> None:
    """Add ``value`` to ``<epoch>.<total>.<innermost open phase>``."""
    phase = _t.spans.phase() or names.PHASE_OUTSIDE
    _t.registry.counter(names.PHASE_SERIES[_t.epoch, total, phase]).inc(value)


def _on_jax_start(event: str, value, **kwargs) -> None:
    # jax announces each timed region's start as a scalar of the same name
    if event == names.JAX_TRACE_EVENT:
        _tracing.depth = getattr(_tracing, "depth", 0) + 1


def _on_jax_duration(event: str, duration: float, **kwargs) -> None:
    total = names.JAX_DURATION_TOTALS.get(event)
    if total is None:
        return
    if event == names.JAX_TRACE_EVENT:
        _tracing.depth = depth = max(getattr(_tracing, "depth", 0) - 1, 0)
        if depth:
            return  # the enclosing trace's duration holds this one
    _charge(total, duration)
    if event == names.JAX_BACKEND_COMPILE_EVENT:
        _charge(names.TOTAL_BACKEND_COMPILES, 1)


def _on_jax_event(event: str, **kwargs) -> None:
    total = names.JAX_EVENT_TOTALS.get(event)
    if total is not None:
        _charge(total, 1)


def watch_jax() -> bool:
    """Register, once per process, the ``jax.monitoring`` listeners that fold
    jax's own trace / lower / backend-compile / persistent-cache events into
    the always-live phase counters (``names.PHASE_SERIES``).  Only if jax is
    ALREADY imported -- the facade never imports it; callers that know it is
    (``realize()``) make sure.  Returns whether the listeners are on."""
    if _t.jax_watched:
        return True
    jax = sys.modules.get("jax")
    if jax is None:
        return False
    _t.jax_watched = True
    jax.monitoring.register_scalar_listener(_on_jax_start)
    jax.monitoring.register_event_duration_secs_listener(_on_jax_duration)
    jax.monitoring.register_event_listener(_on_jax_event)
    return True


def _sample_track_counters(t: _Telemetry, at: float) -> None:
    """Sample the cumulative track counters onto the Chrome counter tracks
    at span-record time (``at`` is a ``perf_counter`` value).  Three dict
    hits per recorded span; identical consecutive values are dropped by the
    recorder, so quiet series cost one event total."""
    for name in _TRACK_COUNTERS:
        t.spans.sample_counter(name, t.registry.counter(name).value, at)


def dump_chrome_trace(path: Optional[str] = None) -> Optional[str]:
    """Write the recorded spans as Chrome trace-event JSON; returns the path
    (None when there is nothing to write or nowhere to put it).  Open in
    chrome://tracing or https://ui.perfetto.dev."""
    t = _cfg()
    events = t.spans.chrome_trace_events(pid=_rank())
    if not events:
        return None
    if path is None:
        if t.out_dir is None:
            return None
        path = os.path.join(t.out_dir, f"trace_{_rank()}.json")
    from stencil_tpu.utils.artifact import atomic_write

    with atomic_write(path) as f:
        json.dump({"traceEvents": events, "displayTimeUnit": "ms"}, f)
    return path


# --- events ------------------------------------------------------------------


def emit_event(name: str, **fields) -> None:
    """Append one structured JSONL event.  The JSONL sink runs only while
    enabled AND a sink directory is configured — guarded before any
    formatting happens.  The in-memory flight ring records ALWAYS (one
    deque append of the dict the caller already built): like the counters,
    the last events before a crash must survive telemetry being off —
    the flight recorder dumps them as the crash report
    (docs/observability.md "Flight recorder")."""
    t = _cfg()
    t.ring.append({"ts": time.time(), "event": name, **fields})
    if t.enabled and t.sink is not None:
        t.sink.emit(name, fields)


def recent_events(n: Optional[int] = None) -> List[dict]:
    """The last ``n`` (default: all retained) events from the bounded
    in-memory flight ring, oldest first — the post-mortem tail a crash
    report captures even when no JSONL sink was configured."""
    ring = _cfg().ring
    out = list(ring)
    if n is not None:
        out = out[-n:]
    return out


def event_log_path() -> Optional[str]:
    t = _cfg()
    return t.sink.path() if t.sink is not None else None


def dump_metrics(path: Optional[str] = None) -> Optional[str]:
    """Write the metrics snapshot as JSON; returns the path (None when
    nowhere to put it).  Default home: ``metrics_<rank>.json`` next to the
    trace/events, which makes a telemetry dir self-contained for
    ``scripts/perf_report.py`` (the roofline join needs the analytic
    counters AND the trace from the same run)."""
    t = _cfg()
    if path is None:
        if t.out_dir is None:
            return None
        path = os.path.join(t.out_dir, f"metrics_{_rank()}.json")
    from stencil_tpu.utils.artifact import atomic_write_json

    return atomic_write_json(path, snapshot())


def write_artifacts() -> dict:
    """Flush end-of-run artifacts (the Chrome trace and metrics snapshot;
    events stream live).  Returns ``{"trace": ..., "events": ...,
    "metrics": ...}`` (path or None each)."""
    return {
        "trace": dump_chrome_trace(),
        "events": event_log_path(),
        "metrics": dump_metrics(),
    }
