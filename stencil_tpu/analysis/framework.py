"""Program-contract framework: artifacts, contracts, registry, engine.

The analog of ``lint/framework.py`` one level down the stack: where a lint
``Rule`` checks SOURCE, a :class:`Contract` checks a TRACED PROGRAM — a
:class:`ProgramArtifact` wrapping the closed jaxpr of a really-built step
(or exchange, or any jitted callable) plus the build-time facts a contract
needs (the stream plan, the domain handle, the axis values the program
claims to exercise).

Contracts are data, like lint rules: id, rationale, an ``applies_to``
predicate over the artifact, a ``check`` returning findings.  The registry
is populated by ``@register`` at ``analysis/contracts.py`` import time; the
CLI (``python -m stencil_tpu.analysis``) and the tier-1 gate
(``tests/test_analysis.py``) both run every registered contract over the
canonical program matrix (``analysis/programs.py``).

Kept import-light: jax is only touched when an artifact is actually traced
(``trace_artifact``), so ``--list-contracts`` and the lint rules' registry
reads stay milliseconds.
"""

from __future__ import annotations

import dataclasses
import json
import time
from typing import Callable, Dict, Iterable, List, Optional, Sequence


@dataclasses.dataclass(frozen=True)
class Finding:
    """One contract violation: contract id, program label, message."""

    contract: str
    program: str
    message: str

    def render(self) -> str:
        return f"{self.program}: [{self.contract}] {self.message}"

    def as_json(self) -> dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass
class ProgramArtifact:
    """One traced program under verification.

    ``label``  — stable display id (``step:wavefront/split/direct/...``).
    ``kind``   — ``"step"`` (a built stream/domain step), ``"exchange"``
                 (a bare exchange fn), or ``"fn"`` (anything else — the
                 fixture corpus's synthetic programs).
    ``closed`` — the ClosedJaxpr of the program.
    ``axes``   — the axis values this program claims to exercise
                 (``route``/``overlap``/``halo``/``exchange_route``/
                 ``storage_dtype``); contracts scope their pins on these.
    ``plan``   — the stream plan dict (steps only; None otherwise).
    ``dd``     — the realized domain (when available: vmem re-derivation).
    ``n_devices`` — mesh size the program was built for (1 = no exchange).
    ``vmem_budget`` — budget override in bytes for the vmem contract
                 (fixtures pin tiny budgets without touching the env).
    ``meta``   — free-form build facts for kind-specific contracts (the
                 redistribution programs carry their staging bound here).
    """

    label: str
    kind: str
    closed: object
    axes: dict = dataclasses.field(default_factory=dict)
    plan: Optional[dict] = None
    dd: object = None
    n_devices: int = 1
    vmem_budget: Optional[int] = None
    meta: dict = dataclasses.field(default_factory=dict)

    def finding(self, contract: str, message: str) -> Finding:
        return Finding(contract=contract, program=self.label, message=message)


def trace_artifact(
    fn: Callable,
    *args,
    label: str,
    kind: str = "fn",
    static_argnums=None,
    **meta,
) -> ProgramArtifact:
    """Trace ``fn(*args)`` to a closed jaxpr and wrap it as an artifact.
    ``meta`` passes through to the artifact fields (``axes=``, ``plan=``,
    ``dd=``, ``n_devices=``, ``vmem_budget=``)."""
    import jax

    kw = {}
    if static_argnums is not None:
        kw["static_argnums"] = static_argnums
    closed = jax.make_jaxpr(fn, **kw)(*args)
    return ProgramArtifact(label=label, kind=kind, closed=closed, **meta)


def step_artifact(dd, step, label: str, axes: dict,
                  vmem_budget: Optional[int] = None) -> ProgramArtifact:
    """Artifact for a ladder-wrapped domain step (``make_step``'s return):
    traces the CURRENT rung's built impl over the domain's live buffers —
    the same program the dispatcher runs."""
    ladder = getattr(step, "_resilience", None)
    fn = ladder.built() if ladder is not None else step
    plan = getattr(step, "_stream_plan", None)
    art = trace_artifact(
        fn,
        dd._curr,
        1,
        static_argnums=1,
        label=label,
        kind="step",
        axes=dict(axes),
        plan=dict(plan) if plan else None,
        dd=dd,
        n_devices=dd.num_subdomains(),
        vmem_budget=vmem_budget,
    )
    return art


class Contract:
    """Base class: subclass, set ``name``/``why``, implement ``check``.

    ``name`` is the id used in output and ``--select``; ``why`` the
    one-line rationale (``--list-contracts``, the docs catalog).
    ``applies_to(art)`` scopes the contract to the artifacts whose claims
    it can actually pin — the engine only calls ``check`` on those."""

    name: str = ""
    why: str = ""

    def applies_to(self, art: ProgramArtifact) -> bool:
        return True

    def check(self, art: ProgramArtifact) -> List[Finding]:
        raise NotImplementedError


#: the global registry, populated by ``@register`` at
#: ``analysis/contracts.py`` import time
_REGISTRY: List[type] = []


def register(cls: type) -> type:
    assert cls.name, f"{cls.__name__} must set a contract name"
    assert all(cls.name != c.name for c in _REGISTRY), f"duplicate {cls.name}"
    _REGISTRY.append(cls)
    return cls


def all_contracts() -> List[type]:
    """Registered contract classes (importing the contracts module on
    demand, the lint ``all_rules`` pattern)."""
    from stencil_tpu.analysis import contracts as _contracts  # noqa: F401

    return list(_REGISTRY)


def _select(select: Optional[Iterable[str]]) -> List[Contract]:
    classes = all_contracts()
    if select is not None:
        wanted = set(select)
        known = {c.name for c in classes}
        unknown = wanted - known
        if unknown:
            raise ValueError(
                f"unknown contract(s) {sorted(unknown)}; known: {sorted(known)}"
            )
        classes = [c for c in classes if c.name in wanted]
    return [c() for c in classes]


def check(
    artifact: ProgramArtifact,
    contract: Optional[str] = None,
    select: Optional[Iterable[str]] = None,
    timings: Optional[Dict[str, float]] = None,
) -> List[Finding]:
    """Run contracts over ONE artifact.  ``contract=`` selects a single id
    (the ported structural tests' entry point); ``select=`` a list; both
    None runs every registered contract that applies.  ``timings=`` is an
    out-param dict accumulating per-contract wall seconds."""
    if contract is not None:
        select = [contract]
    out: List[Finding] = []
    for c in _select(select):
        if not c.applies_to(artifact):
            continue
        t0 = time.perf_counter()
        out.extend(c.check(artifact))
        if timings is not None:
            timings[c.name] = (
                timings.get(c.name, 0.0) + time.perf_counter() - t0
            )
    return sorted(out, key=lambda f: (f.program, f.contract, f.message))


def check_artifacts(
    artifacts: Sequence[ProgramArtifact],
    select: Optional[Iterable[str]] = None,
    timings: Optional[Dict[str, float]] = None,
) -> List[Finding]:
    """Run contracts over a whole artifact set (the canonical matrix).
    ``timings=`` accumulates wall seconds per contract id across the set —
    the CLI's ``--timings`` summary and ``--json`` ``contract_seconds``."""
    out: List[Finding] = []
    for art in artifacts:
        out.extend(check(art, select=select, timings=timings))
    return out


def applied_contracts(artifacts: Sequence[ProgramArtifact]) -> List[str]:
    """The contract ids whose ``applies_to`` held for at least one of these
    artifacts — what a clean ``check_artifacts`` run actually verified
    (callers recording a 'verified' claim must not list contracts that
    never ran; weak.py's ``--verify`` artifact field)."""
    out = set()
    for c in _select(None):
        if any(c.applies_to(a) for a in artifacts):
            out.add(c.name)
    return sorted(out)


def render_json(
    findings: List[Finding],
    programs: int,
    timings: Optional[Dict[str, float]] = None,
) -> str:
    return json.dumps(
        {
            "findings": [f.as_json() for f in findings],
            "count": len(findings),
            "programs_checked": programs,
            "contracts": sorted(c.name for c in all_contracts()),
            "contract_seconds": {
                k: round(v, 4) for k, v in sorted((timings or {}).items())
            },
        },
        indent=2,
        sort_keys=True,
    )


def render_human(findings: List[Finding], stream=None) -> None:
    import sys

    stream = stream or sys.stderr
    for f in findings:
        print(f.render(), file=stream)
    if findings:
        print(f"{len(findings)} program-contract finding(s)", file=stream)


def render_timings(timings: Dict[str, float], stream=None) -> None:
    """Per-contract wall-time summary, slowest first (``--timings``; the
    one-shot gate surfaces this on failure so a matrix-growth slowdown is
    attributable to a contract, not a mystery)."""
    import sys

    stream = stream or sys.stderr
    for name, secs in sorted(timings.items(), key=lambda kv: -kv[1]):
        print(f"{secs:8.3f}s  {name}", file=stream)
