"""Rule ``contract-coverage``: an ops/ module that grows a tuner-axis
vocabulary (``EXCHANGE_ROUTES``, ``STREAM_OVERLAP``, ``STREAM_HALO``,
``STORAGE_DTYPES``) must grow the program-contract verifier's canonical
matrix with it.

Why: the analysis package (``python -m stencil_tpu.analysis``,
docs/static-analysis.md "Program contracts") machine-checks the traced-
program invariants — fused ≤6-permute exchanges, split-step independence,
thin-z relayout traps — against REAL built programs swept over the axis
vocabularies.  A new exchange route or overlap schedule that no canonical
program exercises is an unverified fast path: this rule fails the defining
module until the jax-free coverage ledger
(``stencil_tpu/analysis/registry.py``) — which
``tests/test_analysis.py::test_registry_matches_matrix`` pins against the
real matrix — names every declared value.
"""

from __future__ import annotations

import ast
from typing import List, Optional

from stencil_tpu.lint.framework import FileContext, Rule, Violation, register


def _ledger():
    """The jax-free coverage ledger — imported lazily (the registry module
    never touches jax, so this stays milliseconds; the analysis package
    __init__ is import-light by contract)."""
    from stencil_tpu.analysis.registry import CANONICAL_AXES

    return CANONICAL_AXES


def _tuple_of_strs(node: ast.expr) -> Optional[List[str]]:
    if not isinstance(node, (ast.Tuple, ast.List)):
        return None
    vals = []
    for el in node.elts:
        if not (isinstance(el, ast.Constant) and isinstance(el.value, str)):
            return None
        vals.append(el.value)
    return vals


@register
class ContractCoverageRule(Rule):
    name = "contract-coverage"
    why = (
        "an ops/ or serve/ module growing an axis vocabulary "
        "(EXCHANGE_ROUTES, STREAM_OVERLAP, SERVE_MODES, ...) must be named "
        "in the analysis canonical-matrix ledger — new routes cannot ship "
        "unverified by the program contracts"
    )

    def applies_to(self, rel: str) -> bool:
        rel = rel.replace("\\", "/")
        # serve/ carries one axis vocabulary too: pack.SERVE_MODES (the
        # packed-dispatch modes the batch-isolation contract sweeps)
        return rel.startswith(("stencil_tpu/ops/", "stencil_tpu/serve/"))

    def check(self, ctx: FileContext) -> List[Violation]:
        ledger = _ledger()
        out: List[Violation] = []
        rel = ctx.rel.replace("\\", "/")
        for node in ctx.tree.body:  # module level only: the axis tuples
            # are module constants by convention (tuner-axis vocabularies)
            if not isinstance(node, (ast.Assign, ast.AnnAssign)):
                continue
            targets = node.targets if isinstance(node, ast.Assign) else [node.target]
            names = [t.id for t in targets if isinstance(t, ast.Name)]
            axis = next((n for n in names if n in ledger), None)
            if axis is None:
                continue
            values = _tuple_of_strs(node.value)
            if values is None:
                out.append(
                    ctx.violation(
                        self.name,
                        node,
                        f"{axis} must be a literal tuple of strings so the "
                        "canonical-matrix coverage is statically checkable",
                    )
                )
                continue
            entry = ledger[axis]
            if entry["module"] != rel:
                out.append(
                    ctx.violation(
                        self.name,
                        node,
                        f"{axis} is defined in {rel} but the analysis "
                        f"coverage ledger names {entry['module']} — update "
                        "stencil_tpu/analysis/registry.py (and the "
                        "canonical matrix) for the move",
                    )
                )
            missing = [v for v in values if v not in entry["covered"]]
            if missing:
                out.append(
                    ctx.violation(
                        self.name,
                        node,
                        f"{axis} declares {missing} but no canonical "
                        "program covers them — add a program to "
                        "analysis/programs.py and record it in "
                        "analysis/registry.py before shipping the route",
                    )
                )
        return out
