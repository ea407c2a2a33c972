"""Rule ``span-name``: span labels — ``annotate()`` named scopes and the
first argument of ``span()`` — must be SPAN constants
from ``stencil_tpu/telemetry/names.py`` (``names.ALL_SPANS``).

The general ``telemetry-name`` rule already rejects names absent from the
registry; this rule closes the two gaps that matter for DEVICE-time
attribution (telemetry/device.py):

1. ``telemetry.annotate(...)`` was previously unchecked entirely — yet its
   labels are what land in compiled HLO metadata and XProf device rows, so
   a free-string scope silently falls out of the roofline attribution
   (``attribute_device_time`` matches registered scope names).
2. A span call naming a COUNTER or EVENT constant parses as "registered"
   under ``telemetry-name`` but forks the timeline kind: span literals
   must be spans specifically.
3. ``jax.named_scope(<string literal>)`` — the raw form the in-kernel
   exchange sweeps once used (``halo_ppermute_*`` f-strings).  Kernel
   scopes are device-timeline spans exactly like ``annotate`` labels, so
   a literal there must be a registered span too; non-literal arguments
   (the ``names.exchange_direction_span`` helper, SPAN_* constants) are
   the sanctioned form and pass through — the ``span-registry`` contract
   covers those at trace level.

Scope: the product tree (``stencil_tpu/``) and ``bench.py`` — telemetry
internals are exempt (they pass names through as parameters), and tests
may build synthetic spans.
"""

from __future__ import annotations

import ast
from typing import List

from stencil_tpu.lint.framework import FileContext, Rule, Violation, register

#: telemetry facade calls whose first positional arg is a SPAN label
SPAN_TAKING_CALLS = {"annotate", "span"}

#: module aliases the tree uses for the telemetry facade
FACADE_ALIASES = {"telemetry"}


def _span_registry():
    """names.ALL_SPANS — imported lazily so the lint package stays
    importable mid-refactor of the telemetry package."""
    from stencil_tpu.telemetry import names

    return names.ALL_SPANS


def _is_span_call(node: ast.Call) -> bool:
    """``telemetry.annotate/span(...)``, a bare ``annotate(...)``
    (the one verb distinctive enough to match by name — plain ``span``
    collides with too many locals), or ``jax.named_scope(...)`` (in-kernel
    device-timeline scopes)."""
    f = node.func
    if isinstance(f, ast.Attribute):
        if not isinstance(f.value, ast.Name):
            return False
        if f.value.id in FACADE_ALIASES and f.attr in SPAN_TAKING_CALLS:
            return True
        return f.value.id == "jax" and f.attr == "named_scope"
    if isinstance(f, ast.Name):
        return f.id == "annotate"
    return False


@register
class SpanNameRule(Rule):
    name = "span-name"
    why = (
        "annotate()/span labels land in HLO metadata and the device-time "
        "attribution keys on them; use the SPAN constants from "
        "stencil_tpu/telemetry/names.py"
    )

    def applies_to(self, rel: str) -> bool:
        rel = rel.replace("\\", "/")
        if rel.startswith("stencil_tpu/telemetry/"):
            return False  # internals pass names through as parameters
        return rel.startswith("stencil_tpu/") or rel == "bench.py"

    def check(self, ctx: FileContext) -> List[Violation]:
        spans = _span_registry()
        out = []
        for node in ast.walk(ctx.tree):
            if not (isinstance(node, ast.Call) and _is_span_call(node)):
                continue
            if (
                node.args
                and isinstance(node.args[0], ast.Constant)
                and isinstance(node.args[0].value, str)
            ):
                lit = node.args[0].value
                if lit not in spans:
                    out.append(
                        ctx.violation(
                            self.name,
                            node,
                            f"span label {lit!r} is not a registered span "
                            "— add a SPAN_* constant to stencil_tpu/"
                            "telemetry/names.py (ALL_SPANS) and reference "
                            "it, so device-time attribution can key on it",
                        )
                    )
        return out
