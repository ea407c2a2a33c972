"""Rule ``kernel-ledger``: a pallas kernel added under ``ops/`` must be
named in the kernel-coverage ledger (``PALLAS_KERNELS`` in
``stencil_tpu/analysis/registry.py``) — the ``contract-coverage`` pattern
one level down.

Why: the kernel verifier (``analysis/kernels.py``; contracts
``kernel-race``/``kernel-coverage``/``tiling-legal``,
docs/static-analysis.md "Kernel verifier") descends into every pallas call
the canonical matrix traces, but a NEW kernel the matrix never reaches is
an unverified write surface: its grid could race, its block maps could
leave output gaps, its shapes could be Mosaic-illegal — exactly the
failure classes the verifier exists to make static.  This rule fails the
defining module until the jax-free ledger — which
``tests/test_analysis.py::test_kernel_ledger_matches_tree`` pins against
the real tree in both directions — names every top-level function that
issues a ``pallas_call``.
"""

from __future__ import annotations

import ast
from typing import List

from stencil_tpu.lint.framework import FileContext, Rule, Violation, register


def _ledger():
    """The jax-free kernel ledger — imported lazily (the registry module
    never touches jax; the lint run stays milliseconds)."""
    from stencil_tpu.analysis.registry import PALLAS_KERNELS

    return PALLAS_KERNELS


def _pallas_calls(tree: ast.AST):
    """Every ``pallas_call(...)`` / ``pl.pallas_call(...)`` call under ``tree``."""
    for sub in ast.walk(tree):
        if not isinstance(sub, ast.Call):
            continue
        fn = sub.func
        if (isinstance(fn, ast.Attribute) and fn.attr == "pallas_call") or (
            isinstance(fn, ast.Name) and fn.id == "pallas_call"
        ):
            yield sub


def _issues_pallas_call(node: ast.FunctionDef) -> bool:
    return next(_pallas_calls(node), None) is not None


@register
class KernelLedgerRule(Rule):
    name = "kernel-ledger"
    why = (
        "an ops/ function issuing a pallas_call must be named in the "
        "kernel-coverage ledger (analysis/registry.py PALLAS_KERNELS) — "
        "new kernels cannot ship outside the kernel verifier's sweep"
    )

    def applies_to(self, rel: str) -> bool:
        return rel.replace("\\", "/").startswith("stencil_tpu/ops/")

    def check(self, ctx: FileContext) -> List[Violation]:
        ledger = _ledger()
        rel = ctx.rel.replace("\\", "/")
        named = ledger.get(rel, ())
        out: List[Violation] = []
        for node in ctx.tree.body:  # top level only: helpers that build a
            # pallas_call for an enclosing kernel fn are that kernel's body
            if not isinstance(node, ast.FunctionDef):
                continue
            if not _issues_pallas_call(node):
                continue
            if node.name in named:
                continue
            out.append(
                ctx.violation(
                    self.name,
                    node,
                    f"{node.name} issues a pallas_call but is not in the "
                    f"kernel-coverage ledger for {rel} — add it to "
                    "PALLAS_KERNELS in stencil_tpu/analysis/registry.py "
                    "(and reach it from the canonical matrix or the "
                    "fixture corpus) before shipping the kernel",
                )
            )
        return out


def _registered_kernel_name(node: ast.AST) -> bool:
    """``tm.KERNEL_X`` / ``names.KERNEL_X`` naming an ``ALL_KERNELS`` entry,
    or a string literal that is one."""
    from stencil_tpu.telemetry import names

    if isinstance(node, ast.Attribute) and isinstance(node.value, ast.Name):
        if node.value.id in {"tm", "names"} and node.attr.startswith("KERNEL_"):
            return getattr(names, node.attr, None) in names.ALL_KERNELS
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value in names.ALL_KERNELS
    return False


@register
class KernelNameRule(Rule):
    name = "kernel-name"
    why = (
        "every ops/ pallas_call passes name= from the kernel registry "
        "(telemetry/names.py ALL_KERNELS): the name is the kernel's identity "
        "in a device trace — without it the op is a custom-call told apart "
        "only by its result shape, and falls out of every per-kernel metric"
    )

    def applies_to(self, rel: str) -> bool:
        return rel.replace("\\", "/").startswith("stencil_tpu/ops/")

    def check(self, ctx: FileContext) -> List[Violation]:
        out: List[Violation] = []
        for call in _pallas_calls(ctx.tree):
            name = next((kw.value for kw in call.keywords if kw.arg == "name"), None)
            if name is not None and _registered_kernel_name(name):
                continue
            out.append(
                ctx.violation(
                    self.name,
                    call,
                    "pallas_call without a registered name= — pass "
                    "name=tm.KERNEL_* (add the constant to stencil_tpu/"
                    "telemetry/names.py ALL_KERNELS: one name per kernel "
                    "family, stable across depth, radius, shape and dtype)",
                )
            )
        return out
