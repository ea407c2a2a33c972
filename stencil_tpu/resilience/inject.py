"""Deterministic fault injection: make every resilience path testable on CPU.

``STENCIL_FAULT_PLAN`` holds a comma-separated list of fault entries:

    entry := phase ':' class [':' label-glob] ['@' skip] ['*' count]
    phase := compile | execute | dispatch | any
    class := vmem_oom | compile_reject | transient | divergence | fatal
           | capacity_loss | sigkill | sigterm | shrink | grow
           | overload | poison_request | slow_tenant

Each entry first lets ``skip`` matching hook calls pass untouched (default
0 — the chaos harness's "die at the K-th dispatch" primitive), then fires
``count`` times (default 1), then is spent.  Phases map to the three hook
sites:

* ``compile``  — inside ``DegradationLadder`` when a rung's step impl is
  (re)built: models a compiler rejection before any execution.
* ``execute``  — inside ``DegradationLadder`` immediately before the rung's
  impl runs: models a runtime failure of the compiled step.
* ``dispatch`` — inside ``DistributedDomain.run_step`` before the step
  function is invoked: models infrastructure failures (the dropped-
  connection class) that strike any engine, including the plain XLA route.

The optional label targets a specific site.  It matches when the hook label
starts with the pattern LITERALLY (so an exact rung label like
``stream:wavefront[m=3]`` works even though it contains characters fnmatch
treats specially), or when the pattern matches as an ``fnmatch`` glob with
an implicit trailing ``*`` (only a TRAILING ``*<digits>`` is the count
suffix; a ``*`` elsewhere belongs to the glob).  Ladder hooks are labeled
``<engine>:<rung>`` (e.g. ``stream:wavefront[m=3]``, ``jacobi:wrap[k=8]``),
dispatch hooks carry the label passed to ``run_step`` (models pass their
name: ``jacobi``, ``astaroth``).  Examples:

    STENCIL_FAULT_PLAN='execute:vmem_oom:stream*2'
        -> the stream engine's next two step executions raise a
           Mosaic-worded scoped-VMEM OOM (driving the ladder down 2 rungs)
    STENCIL_FAULT_PLAN='dispatch:transient:astaroth*9'
        -> every astaroth dispatch fails with a connection-drop transient error
           until the 9 charges are spent (outlasting the retry budget)
    STENCIL_FAULT_PLAN='dispatch:sigkill:jacobi@7'
        -> the 8th jacobi dispatch kills the PROCESS with SIGKILL — the
           chaos/soak harness's preemption-without-warning primitive
           (scripts/run_soak.py); 'sigterm' delivers the polite variant the
           supervisor's handler turns into a final checkpoint + resumable
           exit

    STENCIL_FAULT_PLAN='dispatch:shrink:jacobi@5'
        -> the 6th jacobi dispatch delivers a seeded CAPACITY-CHANGE
           notice: the registered capacity handler (the run supervisor
           installs one) records a pending shrink, drains at the next
           chunk boundary, and reshards onto half the current mesh's
           devices ('grow' targets the full fleet).  'capacity_loss'
           instead RAISES a device-unavailable-worded error — the
           taxonomy's CAPACITY_LOSS class, exercising the supervisor's
           reshard-or-restore routing rather than the polite drain

    STENCIL_FAULT_PLAN='execute:poison_request:serve:tenant-b'
        -> tenant-b's next served request raises a typed DivergenceError
           (a request whose execution diverges) — the serving layer's
           per-tenant envelope quarantines/evicts ONLY that tenant, the
           isolation property the serving chaos soak proves bitwise.
           'overload' raises the pinned queue-full shed wording (the
           taxonomy's OVERLOAD class; never blindly retried), and
           'slow_tenant' delivers a slowdown notice to the registered
           slow handler (``set_slow_handler``; the serving layer installs
           one that inflates that request's service time) — like the
           capacity notices, no handler = log and drop, never a crash

Injected VMEM_OOM / COMPILE_REJECT / TRANSIENT faults are raised as
``InjectedFault`` with the SAME message wording the real toolchain emits, so
they flow through ``classify()``'s substring matching exactly like the real
thing; DIVERGENCE raises a typed ``DivergenceError``.  The process-level
kill classes do not raise at all: they deliver a real signal to this
process (``os.kill``), exercising the supervisor exactly like a cloud
preemption would.

The plan is parsed lazily from the environment on first use and re-parsed
whenever the env var's value changes (so tests can monkeypatch it without an
explicit reset); ``set_plan`` installs a plan programmatically, bypassing the
environment.
"""

from __future__ import annotations

import dataclasses
import fnmatch
import os
import re
from typing import List, Optional

from stencil_tpu.resilience.taxonomy import (
    DivergenceError,
    FailureClass,
    InjectedFault,
)

ENV_VAR = "STENCIL_FAULT_PLAN"

_PHASES = ("compile", "execute", "dispatch", "any")
_CLASSES = {
    "vmem_oom": FailureClass.VMEM_OOM,
    "compile_reject": FailureClass.COMPILE_REJECT,
    "transient": FailureClass.TRANSIENT_RUNTIME,
    "divergence": FailureClass.DIVERGENCE,
    "capacity_loss": FailureClass.CAPACITY_LOSS,
    "overload": FailureClass.OVERLOAD,
    # a request whose EXECUTION diverges: same typed DivergenceError as
    # 'divergence' (the serving layer's eviction path keys on the class,
    # not the plan-entry spelling), but the chaos grammar keeps the
    # serving-native name so soak plans read as what they model
    "poison_request": FailureClass.DIVERGENCE,
    "fatal": FailureClass.FATAL,
}
#: process-level kill classes: a REAL signal to this process, not an
#: exception — sigkill models preemption-without-warning (no cleanup runs),
#: sigterm the polite notice the supervisor checkpoints on
_KILLS = ("sigkill", "sigterm")
#: seeded capacity-change notices: no exception, no signal — the hook
#: calls the REGISTERED capacity handler (``set_capacity_handler``; the
#: run supervisor installs one for the duration of ``run()``), which
#: records a pending grow/shrink the supervisor drains and reshards on at
#: the next chunk boundary.  With no handler installed the notice is
#: logged and dropped — a fault plan must never crash an unsupervised run
#: with a primitive only the supervisor can answer.
_CAPACITY = ("shrink", "grow")
#: seeded tenant slowdowns: no exception — the hook calls the REGISTERED
#: slow handler (``set_slow_handler``; the serving layer installs one that
#: inflates the matched request's service time), modeling a tenant whose
#: requests hog dispatch slots without failing.  No handler = log + drop.
_SLOW = ("slow_tenant",)

#: The message each injected class carries — the REAL toolchain wording (the
#: same texts ``taxonomy`` pins), tagged with the injection site.
_MESSAGES = {
    FailureClass.VMEM_OOM: (
        "Ran out of memory in memory space vmem: exceeded scoped vmem "
        "limit by 8.59M"
    ),
    FailureClass.COMPILE_REJECT: (
        "Mosaic failed to compile TPU kernel: unsupported unaligned shape"
    ),
    FailureClass.TRANSIENT_RUNTIME: (
        "UNAVAILABLE: connection reset by peer (injected)"
    ),
    FailureClass.CAPACITY_LOSS: (
        "UNAVAILABLE: TPU is unhealthy: lost device at coordinates [0,1,0]"
    ),
    # the serving layer's own pinned refusal wording (OverloadError's
    # queue-full text — taxonomy._OVERLOAD_MARKERS match it)
    FailureClass.OVERLOAD: "request queue is full; load shed",
    FailureClass.FATAL: "injected fatal failure",
}


@dataclasses.dataclass
class _Entry:
    phase: str
    cls: Optional[FailureClass]  # None for the process-kill classes
    kill: Optional[str]  # "sigkill" | "sigterm" | None
    capacity: Optional[str]  # "shrink" | "grow" | None
    slow: Optional[str]  # "slow_tenant" | None
    label_glob: str
    skip: int
    remaining: int


def _parse_entry(text: str) -> _Entry:
    text = text.strip()
    count = 1
    skip = 0
    # the count suffix is ONLY a trailing '*<digits>' — a '*' elsewhere is
    # part of the label glob (e.g. 'execute:vmem_oom:*wavefront*3')
    m = re.match(r"^(.*)\*(\d+)$", text)
    if m:
        text, count = m.group(1), int(m.group(2))
        if count < 1:
            raise ValueError(f"{ENV_VAR}: count must be >= 1, got {count}")
    # ...and the skip suffix a trailing '@<digits>' before it ('die at the
    # K-th dispatch' = '@K-1', or '@K' counting the fired one as K+1st)
    m = re.match(r"^(.*)@(\d+)$", text)
    if m:
        text, skip = m.group(1), int(m.group(2))
    # split at most twice: ladder labels themselves contain colons
    # ("stream:wavefront[m=3]"), so everything after the class is the glob
    parts = text.split(":", 2)
    if len(parts) == 2:
        phase, cls_name = parts
        label_glob = "*"
    elif len(parts) == 3:
        phase, cls_name, label_glob = parts
    else:
        raise ValueError(
            f"{ENV_VAR}: entry {text!r} is not phase:class[:label][@skip][*count]"
        )
    phase = phase.strip().lower()
    cls_name = cls_name.strip().lower()
    if phase not in _PHASES:
        raise ValueError(
            f"{ENV_VAR}: unknown phase {phase!r} (one of {', '.join(_PHASES)})"
        )
    if (
        cls_name not in _CLASSES
        and cls_name not in _KILLS
        and cls_name not in _CAPACITY
        and cls_name not in _SLOW
    ):
        raise ValueError(
            f"{ENV_VAR}: unknown failure class {cls_name!r} "
            f"(one of {', '.join(_CLASSES)}, {', '.join(_KILLS)}, "
            f"{', '.join(_CAPACITY)}, {', '.join(_SLOW)})"
        )
    return _Entry(
        phase,
        _CLASSES.get(cls_name),
        cls_name if cls_name in _KILLS else None,
        cls_name if cls_name in _CAPACITY else None,
        cls_name if cls_name in _SLOW else None,
        label_glob.strip() or "*",
        skip,
        count,
    )


class FaultPlan:
    """A parsed, stateful fault plan: entries are consumed as they fire."""

    def __init__(self, entries: List[_Entry]):
        self._entries = entries

    @classmethod
    def parse(cls, text: str) -> "FaultPlan":
        entries = [_parse_entry(e) for e in text.split(",") if e.strip()]
        return cls(entries)

    def pending(self) -> int:
        return sum(e.remaining for e in self._entries)

    def fire(self, phase: str, label: str) -> None:
        """Raise the first matching entry's fault (consuming one charge)."""
        for e in self._entries:
            if e.remaining <= 0:
                continue
            if e.phase != "any" and e.phase != phase:
                continue
            # PREFIX match first — rung labels contain '[m=3]', which
            # fnmatch would misread as a one-character class, so an exact
            # or plain-prefix pattern must match literally; fnmatch globs
            # (with an implicit trailing '*') cover the wildcard cases
            if not (
                label.startswith(e.label_glob)
                or fnmatch.fnmatchcase(label, e.label_glob)
                or fnmatch.fnmatchcase(label, e.label_glob + "*")
            ):
                continue
            if e.skip > 0:
                # an un-fired pass-through: this entry lets the match
                # through but stays armed (independent entries may still
                # fire below)
                e.skip -= 1
                continue
            e.remaining -= 1
            if e.kill is not None:
                _kill(e.kill, phase, label)
                return  # sigterm: the handler ran; the dispatch proceeds
            if e.capacity is not None:
                _capacity_notice(e.capacity, phase, label)
                return  # a notice, not a failure; the dispatch proceeds
            if e.slow is not None:
                _slow_notice(phase, label)
                return  # a slowdown, not a failure; the dispatch proceeds
            _raise(e.cls, phase, label)


def _kill(kind: str, phase: str, label: str) -> None:
    """Deliver a REAL signal to this process.  SIGKILL never returns (the
    kernel reaps us mid-bytecode — exactly a preemption without notice);
    SIGTERM runs the installed handler synchronously at the next bytecode
    boundary and returns, letting the supervisor observe its flag at the
    step boundary."""
    import signal as _signal

    from stencil_tpu import telemetry
    from stencil_tpu.telemetry import names as tm

    telemetry.inc(tm.FAULTS_INJECTED)
    telemetry.emit_event(
        tm.EVENT_FAULT, phase=phase, label=label, failure_class=kind
    )
    os.kill(os.getpid(), _signal.SIGKILL if kind == "sigkill" else _signal.SIGTERM)


#: the registered capacity-change handler (``fn(kind, phase, label)`` with
#: kind in ``shrink``/``grow``), installed by the run supervisor for the
#: duration of ``run()`` — jax-free module state, like the plan itself
_capacity_handler = {"fn": None}


def set_capacity_handler(fn) -> object:
    """Install (or clear, with ``None``) the capacity-notice handler;
    returns the previous handler so supervisors can nest/restore."""
    prev = _capacity_handler["fn"]
    _capacity_handler["fn"] = fn
    return prev


def _capacity_notice(kind: str, phase: str, label: str) -> None:
    """Deliver a seeded grow/shrink notice to the registered handler (the
    supervisor's drain-and-reshard entry).  No handler = log and drop —
    this primitive only means something to a supervised run."""
    from stencil_tpu import telemetry
    from stencil_tpu.telemetry import names as tm
    from stencil_tpu.utils.logging import log_warn

    telemetry.inc(tm.FAULTS_INJECTED)
    telemetry.emit_event(
        tm.EVENT_FAULT, phase=phase, label=label, failure_class=kind
    )
    fn = _capacity_handler["fn"]
    if fn is None:
        log_warn(
            f"capacity notice {kind!r} injected at {phase}:{label} but no "
            "handler is registered (no supervisor running); dropped"
        )
        return
    fn(kind, phase, label)


#: the registered tenant-slowdown handler (``fn(phase, label)``), installed
#: by the serving layer for the duration of a serve run — jax-free module
#: state, exactly like the capacity handler above
_slow_handler = {"fn": None}


def set_slow_handler(fn) -> object:
    """Install (or clear, with ``None``) the slow-tenant handler; returns
    the previous handler so nested serve runs can restore."""
    prev = _slow_handler["fn"]
    _slow_handler["fn"] = fn
    return prev


def _slow_notice(phase: str, label: str) -> None:
    """Deliver a seeded slow-tenant notice to the registered handler (the
    serving layer inflates the matched request's service time).  No handler
    = log and drop — the primitive only means something to a serve run."""
    from stencil_tpu import telemetry
    from stencil_tpu.telemetry import names as tm
    from stencil_tpu.utils.logging import log_warn

    telemetry.inc(tm.FAULTS_INJECTED)
    telemetry.emit_event(
        tm.EVENT_FAULT, phase=phase, label=label, failure_class="slow_tenant"
    )
    fn = _slow_handler["fn"]
    if fn is None:
        log_warn(
            f"slow_tenant notice injected at {phase}:{label} but no handler "
            "is registered (no serving layer running); dropped"
        )
        return
    fn(phase, label)


def _raise(cls: FailureClass, phase: str, label: str) -> None:
    from stencil_tpu import telemetry
    from stencil_tpu.telemetry import names as tm

    telemetry.inc(tm.FAULTS_INJECTED)
    telemetry.emit_event(
        tm.EVENT_FAULT, phase=phase, label=label, failure_class=cls.value
    )
    site = f" [fault-injected at {phase}:{label}]"
    if cls is FailureClass.DIVERGENCE:
        raise DivergenceError(quantity=f"<injected:{label}>", step=-1)
    # plain message text: VMEM_OOM / COMPILE_REJECT / TRANSIENT rely on
    # classify()'s substring matching, exercising the real code path (the
    # FATAL message matches no marker and classifies FATAL by default)
    raise InjectedFault(_MESSAGES[cls] + site)


# --- module-level plan state ------------------------------------------------
_state = {"raw": None, "plan": None, "explicit": False}


def set_plan(plan: Optional["FaultPlan | str"]) -> None:
    """Install a plan programmatically (tests), bypassing the environment.
    ``None`` clears it and resumes reading ``STENCIL_FAULT_PLAN``."""
    if isinstance(plan, str):
        plan = FaultPlan.parse(plan)
    _state["plan"] = plan
    _state["explicit"] = plan is not None
    _state["raw"] = None


def active_plan() -> Optional[FaultPlan]:
    if _state["explicit"]:
        return _state["plan"]
    raw = os.environ.get(ENV_VAR)
    if raw != _state["raw"]:  # env changed (or first read): re-parse
        _state["raw"] = raw
        _state["plan"] = FaultPlan.parse(raw) if raw else None
    return _state["plan"]


def maybe_fail(phase: str, label: str = "") -> None:
    """Hook call: raise the next matching injected fault, if any.  Inert
    (one dict lookup + string compare) when no plan is configured."""
    plan = active_plan()
    if plan is not None:
        plan.fire(phase, label)
