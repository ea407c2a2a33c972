"""Device-time attribution: parse ``jax.profiler`` trace dumps offline and
attribute DEVICE time to the named scopes this tree already emits into HLO
metadata.

The host span tracer (``spans.py``) sees wall-clock only — a dispatch that
returns at enqueue looks free, and the split-step overlap win/loss, the
exchange's real cost and the pack kernels' share of a step are only
knowable from the device timeline (T3, arxiv 2401.16677: overlap efficiency
comes from fine-grained attribution of compute vs collectives).  This module
closes that gap without any online dependency on the profiler:

* **Capture** (``ProfileCapture``): wrap dispatches with a cadence-gated
  ``jax.profiler`` trace (``STENCIL_PROFILE_EVERY`` / ``--profile-dir``).
  Degrades gracefully — a backend with no profiler (CPU dryrun containers)
  warns once and runs unprofiled; the capture path never crashes a run.
* **Parse** (``find_trace_files`` / ``load_trace_events``): the profiler
  dumps Chrome trace-event JSON (``*.trace.json[.gz]`` under
  ``plugins/profile/<run>/``); we read it back offline — plain stdlib, no
  jax, no TensorBoard.
* **Attribute** (``attribute_device_time``): sum device-row durations per
  named scope (``step.overlap.interior``/``.exterior`` — names.py, entered
  via ``telemetry.annotate`` — plus the exchange/pack kernel families),
  matching scopes as substrings of the event name and its args (XLA carries
  the ``jax.named_scope`` path in op metadata, so scope names survive into
  the trace rows).
* **Merge** (``merge_device_rows`` / ``merge_into_chrome_trace``): append
  the device rows to the host Chrome trace so Perfetto shows host spans and
  device kernels on ONE timeline.  Device clocks are not host clocks;
  alignment shifts the device rows so the capture window starts at the
  host-trace timestamp that opened it (best-effort, recorded in the row
  args as ``device_ts_us``).

Everything here except ``ProfileCapture.__enter__`` is jax-free (the
``jax-import`` lint rule covers this package): parsing a trace from a dead
run must not need a live backend.
"""

from __future__ import annotations

import gzip
import json
import os
import re
import time
from typing import Dict, Iterable, List, Optional, Tuple

from stencil_tpu.telemetry import names

#: the named-scope/kernel families device time is attributed to.  The two
#: ``step.overlap.*`` entries are the annotate() scopes the split schedule
#: enters (names.py); ``exchange``/``pack`` match the collective and pack
#: kernel families by their stable substrings.  Matching is case-insensitive
#: substring over the event name and its args values.
PHASE_PATTERNS: Dict[str, Tuple[str, ...]] = {
    names.SPAN_OVERLAP_INTERIOR: (names.SPAN_OVERLAP_INTERIOR,),
    names.SPAN_OVERLAP_EXTERIOR: (names.SPAN_OVERLAP_EXTERIOR,),
    # device rows match the collective/pack kernel families; the
    # ``domain.*`` entries additionally catch our HOST span names so the
    # host-span fallback (scripts/perf_report.py on a CPU container)
    # attributes the same phases
    "exchange": (
        "halo_ppermute",
        "ppermute",
        "collective-permute",
        "collective_permute",
        "all-to-all",
        names.SPAN_EXCHANGE,
    ),
    "pack": ("zpack", "halo_pack", "shell_pack", "unpack"),
    "step": (names.SPAN_STEP,),
}

#: one phase per registered exchange direction scope (``exchange.x.low``
#: ...) — the per-hop VIEW of the exchange family for the comms roofline;
#: the kernel sweeps enter these scopes around every ppermute
#: (ops/exchange.py ``_shift_from_low``/``_shift_from_high``)
EXCHANGE_DIRECTION_PHASES: Dict[str, Tuple[str, ...]] = {
    span: (span,) for span in sorted(names.EXCHANGE_DIRECTION_SPANS.values())
}

#: process-name patterns that mark a trace pid as a DEVICE row source
_DEVICE_PROCESS_RE = re.compile(
    r"/device:|TPU|GPU|XLA|Device|Chip", re.IGNORECASE
)

#: pid offset applied to device processes when merging into the host trace
#: (host spans use pid = rank, a small integer — device rows must not
#: collide)
DEVICE_PID_BASE = 1000

#: the analytic counters a capture snapshots at its window boundaries, so
#: the roofline join divides CAPTURE-WINDOW work by capture-window device
#: time — joining whole-run cumulative counters with one window's device
#: seconds would overstate achieved rates by (total / captured) dispatches
CAPTURE_COUNTERS = (
    names.EXCHANGE_BYTES,
    names.EXCHANGE_PACKED_BYTES,
) + tuple(sorted(names.EXCHANGE_HOP_BYTES.values()))


# --- locating and loading trace dumps ----------------------------------------


def find_trace_files(profile_dir: str) -> List[str]:
    """Every ``*.trace.json``/``*.trace.json.gz`` under ``profile_dir``
    (the profiler nests them in ``plugins/profile/<run>/``), newest first
    by mtime — callers usually want the latest capture."""
    out = []
    for dirpath, _dirnames, files in os.walk(profile_dir):
        for f in files:
            if f.endswith((".trace.json", ".trace.json.gz")):
                out.append(os.path.join(dirpath, f))
    return sorted(out, key=lambda p: (os.path.getmtime(p), p), reverse=True)


def load_trace_events(path: str) -> List[dict]:
    """The trace-event list from one dump — accepts both the wrapped
    ``{"traceEvents": [...]}`` object and a bare event array, gzipped or
    plain.  A truncated/corrupt dump (the process died mid-write) returns
    [] rather than raising: post-mortem tooling runs on exactly those."""
    opener = gzip.open if path.endswith(".gz") else open
    try:
        with opener(path, "rt", encoding="utf-8") as f:
            doc = json.load(f)
    except (OSError, ValueError):
        return []
    events = doc.get("traceEvents", []) if isinstance(doc, dict) else doc
    return [e for e in events if isinstance(e, dict)]


def device_pids(events: Iterable[dict]) -> Dict[int, str]:
    """pid -> process name for every process whose metadata marks it as a
    device timeline (``process_name`` metadata rows matching
    /device:|TPU|GPU|XLA/)."""
    out: Dict[int, str] = {}
    for e in events:
        if e.get("ph") == "M" and e.get("name") == "process_name":
            pname = str((e.get("args") or {}).get("name", ""))
            if _DEVICE_PROCESS_RE.search(pname):
                out[e.get("pid", 0)] = pname
    return out


def _event_text(e: dict) -> str:
    """The searchable text of one event: its name plus every string arg
    value (XLA puts the named-scope path in op-metadata args like ``name``
    / ``long_name`` / ``tf_op``)."""
    parts = [str(e.get("name", ""))]
    args = e.get("args")
    if isinstance(args, dict):
        parts.extend(str(v) for v in args.values() if isinstance(v, str))
    return " ".join(parts).lower()


# --- attribution -------------------------------------------------------------


def attribute_device_time(
    events: List[dict],
    phases: Optional[Dict[str, Tuple[str, ...]]] = None,
) -> Dict[str, dict]:
    """Sum device-row durations per phase.

    Returns ``{phase: {"device_us": float, "events": int}}`` plus two
    synthetic rows: ``_total`` (all device complete-events) and
    ``_unattributed`` (device time matching no phase).  An event matching
    several phases counts toward each (an interior-scope pack kernel is both
    ``step.overlap.interior`` and ``pack`` time), so rows are VIEWS of the
    device timeline, not a partition — only ``_total`` is additive.

    Row selection: when the dump carries process metadata, only events on
    DEVICE processes count — a dump whose processes are all host (the CPU
    backend: ``/host:CPU`` full of Python-frame rows) attributes ZERO
    device time rather than wall-clock garbage (callers then degrade to
    the host-span fallback).  Traces with no process metadata at all (our
    own host Chrome dumps, bare event arrays) count every complete event —
    that IS the host-span fallback's input.
    """
    phases = PHASE_PATTERNS if phases is None else phases
    dev = device_pids(events)
    has_process_meta = any(
        e.get("ph") == "M" and e.get("name") == "process_name" for e in events
    )
    out = {p: {"device_us": 0.0, "events": 0} for p in phases}
    out["_total"] = {"device_us": 0.0, "events": 0}
    out["_unattributed"] = {"device_us": 0.0, "events": 0}
    pats = {p: tuple(s.lower() for s in subs) for p, subs in phases.items()}
    for e in events:
        if e.get("ph") != "X":
            continue
        if has_process_meta and e.get("pid") not in dev:
            continue
        dur = float(e.get("dur", 0.0) or 0.0)
        out["_total"]["device_us"] += dur
        out["_total"]["events"] += 1
        text = _event_text(e)
        hit = False
        for p, subs in pats.items():
            if any(s in text for s in subs):
                out[p]["device_us"] += dur
                out[p]["events"] += 1
                hit = True
        if not hit:
            out["_unattributed"]["device_us"] += dur
            out["_unattributed"]["events"] += 1
    return out


def attribute_exchange_directions(events: List[dict]) -> dict:
    """Collective-permute device time per exchange DIRECTION — the per-hop
    half of the comms roofline join.

    Runs ``attribute_device_time`` with one phase per registered
    ``exchange.<axis>.<side>`` scope plus the whole exchange family, and
    returns::

        {"directions": {span: {"device_us", "events"}},   # all six, zeros kept
         "exchange_device_us": float,   # the exchange-family total
         "attributed_us": float,        # summed direction time
         "coverage": float | None,      # attributed / exchange; None when no
                                        # exchange device time was seen
         "total_device_us": float}

    Direction rows are disjoint views (one scope path per trace row), so
    ``attributed_us`` is additive and ``coverage`` is the honest "how much
    of the exchange landed on a named hop" figure the fixture test pins at
    >=90%.  Host-only dumps inherit ``attribute_device_time``'s zero
    behavior: everything 0, coverage None — never wall-clock garbage."""
    phases = dict(EXCHANGE_DIRECTION_PHASES)
    phases["exchange"] = PHASE_PATTERNS["exchange"]
    att = attribute_device_time(events, phases)
    directions = {span: att[span] for span in EXCHANGE_DIRECTION_PHASES}
    exchange_us = att["exchange"]["device_us"]
    attributed_us = sum(d["device_us"] for d in directions.values())
    return {
        "directions": directions,
        "exchange_device_us": exchange_us,
        "attributed_us": attributed_us,
        "coverage": (attributed_us / exchange_us) if exchange_us > 0 else None,
        "total_device_us": att["_total"]["device_us"],
    }


# --- merging device rows into the host Chrome trace --------------------------


def merge_device_rows(
    host_events: List[dict],
    trace_events: List[dict],
    align_ts_us: Optional[float] = None,
) -> List[dict]:
    """Host Chrome-trace events + the device rows of a profiler dump, on
    one timeline.

    Device rows keep their relative timing but are SHIFTED so the earliest
    device event lands at ``align_ts_us`` (default: the earliest host span
    — device clocks and the host ``perf_counter`` epoch share no zero).
    Each device row records its original timestamp under
    ``args.device_ts_us``; device pids are remapped past
    ``DEVICE_PID_BASE`` and re-announced with ``process_name`` metadata so
    Perfetto labels the rows.

    Idempotent: rows from a PREVIOUS merge (pid >= ``DEVICE_PID_BASE`` —
    host spans use pid = rank, a small integer) are dropped first, so
    re-merging (perf_report --merge after a driver already merged at
    exit) replaces the device rows instead of stacking a second copy."""
    host_events = [
        e for e in host_events if int(e.get("pid", 0) or 0) < DEVICE_PID_BASE
    ]
    dev = device_pids(trace_events)
    if not dev:
        return list(host_events)
    rows = [
        e
        for e in trace_events
        if e.get("ph") == "X" and e.get("pid") in dev
    ]
    if not rows:
        return list(host_events)
    t0_dev = min(float(e.get("ts", 0.0)) for e in rows)
    if align_ts_us is None:
        host_ts = [
            float(e["ts"]) for e in host_events if e.get("ph") == "X"
        ]
        align_ts_us = min(host_ts) if host_ts else 0.0
    shift = align_ts_us - t0_dev
    pid_map = {
        pid: DEVICE_PID_BASE + i for i, pid in enumerate(sorted(dev))
    }
    out = list(host_events)
    for pid, name in sorted(dev.items()):
        out.append(
            {
                "name": "process_name",
                "ph": "M",
                "pid": pid_map[pid],
                "args": {"name": f"device: {name}"},
            }
        )
    for e in rows:
        ts = float(e.get("ts", 0.0))
        args = dict(e.get("args") or {})
        args["device_ts_us"] = ts
        out.append(
            {
                "name": e.get("name", ""),
                "ph": "X",
                "ts": ts + shift,
                "dur": float(e.get("dur", 0.0) or 0.0),
                "pid": pid_map[e["pid"]],
                "tid": e.get("tid", 0),
                "args": args,
            }
        )
    return out


def merge_into_chrome_trace(
    chrome_path: str, profile_dir: str
) -> Optional[dict]:
    """Merge the newest profiler dump under ``profile_dir`` into the host
    Chrome trace at ``chrome_path`` (atomic rewrite) and return the
    attribution table (None when either side is missing/empty) — the
    one-call form drivers use at exit."""
    traces = find_trace_files(profile_dir)
    if not traces or not os.path.exists(chrome_path):
        return None
    trace_events = load_trace_events(traces[0])
    if not trace_events:
        return None
    try:
        with open(chrome_path, encoding="utf-8") as f:
            doc = json.load(f)
    except (OSError, ValueError):
        return None
    merged = merge_device_rows(doc.get("traceEvents", []), trace_events)
    doc["traceEvents"] = merged
    from stencil_tpu.utils.artifact import atomic_write

    with atomic_write(chrome_path) as f:
        json.dump(doc, f)
    return attribute_device_time(trace_events)


# --- cadence capture ---------------------------------------------------------


class ProfileCapture:
    """Cadence-gated ``jax.profiler`` capture around numbered dispatches.

    ``maybe(i)`` is a context manager: it traces into
    ``<dir>/capture_<i>`` when ``i`` is on the cadence (``every=N`` -> a
    capture at i = 0, N, 2N, ...; ``every=0`` -> exactly one capture, at
    i = 0) and is a no-op otherwise.  Each capture increments
    ``profile.captures`` and emits a ``profile.capture`` event; the
    underlying ``telemetry.trace`` wrapper owns the no-profiler-backend
    degrade (warn once, run unprofiled).
    """

    def __init__(self, dir: str, every: int = 0):
        self.dir = str(dir)
        self.every = max(int(every), 0)
        self.captures = 0
        #: analytic-counter DELTAS over the newest capture's window
        #: (``CAPTURE_COUNTERS``) — the honest numerator for the roofline
        #: join against that capture's device time; None before any capture
        self.last_counter_deltas: Optional[Dict[str, int]] = None

    @classmethod
    def from_env(cls, dir: Optional[str] = None) -> Optional["ProfileCapture"]:
        """``--profile-dir`` flag value (or ``STENCIL_PROFILE_DIR``) +
        ``STENCIL_PROFILE_EVERY`` cadence; None when no dir is configured
        anywhere — profiling is strictly opt-in."""
        from stencil_tpu.utils.config import env_int, env_str

        dir = dir or env_str("STENCIL_PROFILE_DIR", None)
        if not dir:
            return None
        return cls(dir, every=env_int("STENCIL_PROFILE_EVERY", 0, minimum=0))

    def want(self, index: int) -> bool:
        if self.every == 0:
            return index == 0
        return index % self.every == 0

    def capture_dir(self, index: int) -> str:
        return os.path.join(self.dir, f"capture_{index:06d}")

    def maybe(self, index: int):
        if not self.want(index):
            import contextlib

            return contextlib.nullcontext()
        return _OneCapture(self, index)

    # --- offline views over everything this capture object wrote ------------

    def attribution(self) -> Optional[dict]:
        """Attribution over the newest capture (None when nothing was
        dumped — e.g. the backend had no profiler)."""
        traces = find_trace_files(self.dir)
        if not traces:
            return None
        events = load_trace_events(traces[0])
        return attribute_device_time(events) if events else None

    def counters_snapshot(self) -> Optional[dict]:
        """The newest capture's counter DELTAS as a snapshot-shaped dict
        (``{"counters": {...}}``) for ``roofline_report`` — pair it with
        ``attribution()``, which also reads the newest capture."""
        if self.last_counter_deltas is None:
            return None
        return {"counters": dict(self.last_counter_deltas)}


class _OneCapture:
    """One cadence hit: enter the profiler trace, account the capture."""

    def __init__(self, owner: ProfileCapture, index: int):
        self.owner = owner
        self.index = index
        self._t0 = 0.0
        self._ctx = None

    def __enter__(self):
        from stencil_tpu import telemetry
        from stencil_tpu.telemetry.spans import trace

        self._c0 = {
            name: telemetry._cfg().registry.counter(name).value
            for name in CAPTURE_COUNTERS
        }
        self._t0 = time.perf_counter()
        self._ctx = trace(self.owner.capture_dir(self.index))
        self._ctx.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb):
        out = self._ctx.__exit__(exc_type, exc, tb)
        from stencil_tpu import telemetry

        reg = telemetry._cfg().registry
        self.owner.last_counter_deltas = {
            name: reg.counter(name).value - self._c0[name]
            for name in CAPTURE_COUNTERS
        }
        self.owner.captures += 1
        telemetry.inc(names.PROFILE_CAPTURES)
        telemetry.emit_event(
            names.EVENT_PROFILE_CAPTURE,
            dir=self.owner.capture_dir(self.index),
            index=self.index,
            seconds=round(time.perf_counter() - self._t0, 6),
        )
        return out
