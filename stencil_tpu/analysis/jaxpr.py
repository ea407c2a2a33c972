"""Dataflow analysis over closed jaxprs — the verifier's core machinery.

The repo's hardest invariants live in the TRACED program, not the source:
split-step overlap is a property of the dependency graph the compiler sees
(no dataflow edge from any ppermute into the interior pass), the fused
exchange is a property of the permute count per direction, the thin-z
relayout trap a property of the lowered dynamic-update-slices.  Source
lint (``stencil_tpu/lint``) cannot see through helpers, f-strings, or
tracing — this module walks the jaxpr itself.

Three tools, shared by every contract (``analysis/contracts.py``):

* :func:`walk` / :func:`iter_eqns` — generic descent into the subjaxprs an
  eqn's params carry (pjit, scan, while, cond, shard_map, custom calls),
  with an ``opaque`` set of primitives NOT descended into.  ``pallas_call``
  is opaque by default: a pallas kernel's inner jaxpr describes VMEM-ref
  mutation, not array dataflow, and a contract scanning for e.g. big-array
  dynamic-update-slices must not mistake a tile-local ref update for one.
  The opacity is a TAINT-analysis stance, not ignorance: the kernel
  verifier (``analysis/kernels.py``) descends into pallas bodies
  deliberately, through the call's own metadata (grid, BlockSpec index
  maps, aliases) where the questions ARE kernel-level.
* :func:`taint_rows` — var-level forward taint/reachability inside one
  jaxpr: which eqns transitively consume a source primitive's outputs.
  Opaque eqns (pallas calls, custom calls) are treated CONSERVATIVELY:
  taint flows through them (tainted in => tainted out) and never gets
  lost inside — pinned by ``tests/test_analysis.py``'s opacity fixture.
* :func:`scope_labels` — the named-scope labels (``jax.named_scope`` /
  ``telemetry.annotate``) stamped on eqn source info, the strings XProf
  device-time attribution and the overlap proofs key on.

``Literal`` is re-exported here (from ``jax.extend.core``, its public
home) so the contracts and the overlap tests share one import.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Iterable, Iterator, List, Optional, Set, Tuple

from jax.extend.core import Literal

#: primitives whose inner jaxpr is NOT array dataflow and is never
#: descended into by default — the analyzer treats them as opaque nodes
#: (conservative flow-through).  ``custom_call``-style primitives carry no
#: subjaxpr at all and are opaque by construction.
OPAQUE_PRIMITIVES = frozenset({"pallas_call"})


def subjaxprs(value) -> Iterator:
    """Yield every (raw) Jaxpr found in one eqn-param value — the value may
    be a ClosedJaxpr, a Jaxpr, or a list/tuple of either (``cond`` branches,
    ``custom_jvp`` pairs)."""
    objs = value if isinstance(value, (list, tuple)) else [value]
    for o in objs:
        if hasattr(o, "jaxpr") and hasattr(o, "consts"):  # ClosedJaxpr
            yield o.jaxpr
        elif hasattr(o, "eqns") and hasattr(o, "invars"):  # Jaxpr
            yield o


def eqn_subjaxprs(eqn) -> Iterator:
    """Every subjaxpr carried by one eqn's params."""
    for v in eqn.params.values():
        yield from subjaxprs(v)


def walk(jaxpr, opaque: Iterable[str] = OPAQUE_PRIMITIVES) -> Iterator:
    """Yield ``jaxpr`` and every nested subjaxpr, depth-first, skipping the
    bodies of ``opaque`` primitives.  Pass ``opaque=()`` to descend into
    everything (the accum-dtype contract reads INSIDE pallas kernels)."""
    opaque = frozenset(opaque)
    yield jaxpr
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in opaque:
            continue
        for j in eqn_subjaxprs(eqn):
            yield from walk(j, opaque)


def iter_eqns(closed, opaque: Iterable[str] = OPAQUE_PRIMITIVES) -> Iterator:
    """Every eqn of a ClosedJaxpr (or Jaxpr) across all non-opaque nesting
    levels."""
    root = getattr(closed, "jaxpr", closed)
    for j in walk(root, opaque):
        yield from j.eqns


def primitive_counts(closed, opaque: Iterable[str] = OPAQUE_PRIMITIVES) -> dict:
    """{primitive name: eqn count} over the whole (non-opaque) program."""
    out: dict = {}
    for e in iter_eqns(closed, opaque):
        out[e.primitive.name] = out.get(e.primitive.name, 0) + 1
    return out


def name_stack_str(eqn) -> str:
    """The eqn's named-scope stack as a ``/``-joined string (empty when the
    eqn was traced outside any scope)."""
    return str(eqn.source_info.name_stack)


def scope_labels(closed, opaque: Iterable[str] = ()) -> Set[str]:
    """Every named-scope label appearing on any eqn's source info, split
    out of the ``a/b/c`` stack strings.  Transform frames (``jit(f)``,
    ``vmap(...)``) carry parentheses and are dropped — what remains is the
    labels user code pushed via ``jax.named_scope``/``telemetry.annotate``.
    Descends into opaque bodies by default: a scope entered around a pallas
    call is stamped on the call eqn itself, not its body."""
    out: Set[str] = set()
    root = getattr(closed, "jaxpr", closed)
    for j in walk(root, opaque):
        for e in j.eqns:
            ns = name_stack_str(e)
            if not ns:
                continue
            for part in ns.split("/"):
                if part and "(" not in part and "<" not in part:
                    out.add(part)
    return out


@dataclasses.dataclass(frozen=True)
class TaintRow:
    """One watched eqn inside a tainted-dataflow pass: its primitive name,
    its scope stack, and whether any of its (non-literal) inputs
    transitively depend on a source eqn's outputs."""

    primitive: str
    scopes: str
    tainted: bool
    eqn: object = dataclasses.field(repr=False, compare=False, default=None)


def taint_rows(
    jaxpr,
    source: Callable[[object], bool],
    watch: Callable[[object], bool],
    opaque: Iterable[str] = OPAQUE_PRIMITIVES,
) -> List[TaintRow]:
    """Forward var-level taint inside ONE jaxpr: an eqn for which
    ``source(eqn)`` holds taints its outputs; any eqn consuming a tainted
    var taints its own outputs (conservative flow-through — opaque eqns and
    eqns with subjaxprs included: a source anywhere INSIDE an eqn's nested
    bodies also marks the eqn as a source, so taint cannot be laundered
    through a scan/while/pjit wrapper).  Returns one row per eqn for which
    ``watch(eqn)`` holds, in program order.

    This is the generalized form of the overlap test's hand-rolled walker:
    ``source = ppermute eqns``, ``watch = pallas calls`` reproduces its
    ``(name_stack, tainted)`` rows exactly.
    """
    opaque = frozenset(opaque)
    tainted_vars: Set[int] = set()
    rows: List[TaintRow] = []

    def contains_source(eqn) -> bool:
        if source(eqn):
            return True
        if eqn.primitive.name in opaque:
            return False
        return any(
            source(e2)
            for j in eqn_subjaxprs(eqn)
            for jj in walk(j, opaque)
            for e2 in jj.eqns
        )

    for eqn in jaxpr.eqns:
        invars = [v for v in eqn.invars if not isinstance(v, Literal)]
        src_tainted = any(id(v) in tainted_vars for v in invars)
        if contains_source(eqn) or src_tainted:
            tainted_vars.update(id(v) for v in eqn.outvars)
        if watch(eqn):
            rows.append(
                TaintRow(
                    primitive=eqn.primitive.name,
                    scopes=name_stack_str(eqn),
                    tainted=src_tainted,
                    eqn=eqn,
                )
            )
    return rows


def pallas_taint_rows(closed) -> List[Tuple[str, bool]]:
    """For every jaxpr holding both ppermutes and pallas calls — the loop
    bodies where exchange and passes live — one ``(name_stack, tainted)``
    row per pallas_call, where ``tainted`` means the call's inputs
    transitively depend on some ppermute output.  The overlap-independence
    contract (and the ported ``tests/test_overlap_structural.py``) keys on
    these rows."""
    out: List[Tuple[str, bool]] = []
    root = getattr(closed, "jaxpr", closed)
    for j in walk(root):
        prims = {e.primitive.name for e in j.eqns}
        if "ppermute" not in prims or "pallas_call" not in prims:
            continue
        rows = taint_rows(
            j,
            source=lambda e: e.primitive.name == "ppermute",
            watch=lambda e: e.primitive.name == "pallas_call",
        )
        out.extend((r.scopes, r.tainted) for r in rows)
    return out


def donated_operands(eqn) -> List[Tuple[object, str]]:
    """``(var, kind)`` for the invars this eqn consumes in place: a nested
    jit's ``donated_invars`` (kind ``"donated"``) and a pallas call's
    ``input_output_aliases`` (kind ``"aliased"``) — the jaxpr-level twins
    of ``donate_argnums`` and buffer aliasing.  Literals excluded."""
    out: List[Tuple[object, str]] = []
    if eqn.primitive.name == "jit":
        donated = eqn.params.get("donated_invars") or ()
        for v, d in zip(eqn.invars, donated):
            if d and not isinstance(v, Literal):
                out.append((v, "donated"))
        return out
    aliases = eqn.params.get("input_output_aliases") or ()
    for pair in aliases:
        idx = pair[0] if isinstance(pair, (tuple, list)) else pair
        if isinstance(idx, int) and 0 <= idx < len(eqn.invars):
            v = eqn.invars[idx]
            if not isinstance(v, Literal):
                out.append((v, "aliased"))
    return out


def donation_hazards(jaxpr) -> List[Tuple[object, object, str]]:
    """``(consuming_eqn, other_use, why)`` hazards inside ONE jaxpr.

    SSA + XLA anti-dependency scheduling make a plain later READ of an
    in-place-aliased operand legal (the reader is ordered before the
    write — the split schedule's blend chain relies on exactly this), so
    that is NOT flagged.  What cannot be scheduled away:

    * a jit-DONATED operand with any later use (or escaping as a jaxpr
      output): the donation silently cannot engage — the plan claims
      in-place, the compiler double-buffers (``other_use`` is the later
      eqn or the string ``"outvars"``);
    * TWO in-place consumers (donating or aliasing) of the same SSA value:
      double writers of one buffer;
    * an ALIASED operand escaping as a jaxpr output: the caller receives
      the pre-write value, so the alias is voided by a copy.
    """
    out: List[Tuple[object, object, str]] = []
    outvar_ids = {id(v) for v in jaxpr.outvars if not isinstance(v, Literal)}
    for i, eqn in enumerate(jaxpr.eqns):
        donated = donated_operands(eqn)
        if not donated:
            continue
        for var, kind in donated:
            for later in jaxpr.eqns[i + 1 :]:
                later_inplace = {
                    id(v) for v, _ in donated_operands(later)
                }
                if id(var) in later_inplace:
                    out.append(
                        (eqn, later, "a second in-place consumer writes the "
                         "same buffer")
                    )
                elif kind == "donated" and any(
                    id(v) == id(var)
                    for v in later.invars
                    if not isinstance(v, Literal)
                ):
                    out.append(
                        (eqn, later, "a donated buffer is read after the "
                         "donating call — the donation cannot engage")
                    )
            if id(var) in outvar_ids:
                why = (
                    "a donated buffer escapes as a jaxpr output"
                    if kind == "donated"
                    else "an aliased operand escapes as a jaxpr output — "
                    "the alias is voided by a copy"
                )
                out.append((eqn, "outvars", why))
    return out


def lowered_text(fn, *args, static_argnums=None, **kwargs) -> str:
    """The lowered StableHLO text of ``fn(*args)`` — the HLO-level probe for
    contracts that need to see past the jaxpr (collective-permute counts
    after SPMD partitioning, fusion shapes).  CPU/interpret-safe: lowering
    stops before backend compilation."""
    import jax

    jit_kw = {}
    if static_argnums is not None:
        jit_kw["static_argnums"] = static_argnums
    return jax.jit(fn, **jit_kw).lower(*args, **kwargs).as_text()
