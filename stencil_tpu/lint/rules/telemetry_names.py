"""Rule ``telemetry-name``: every telemetry metric/event name used in the
tree is registered in the canonical names module
(``stencil_tpu/telemetry/names.py``).

Two checks, over ``stencil_tpu/`` (telemetry internals exempt — they pass
names through as parameters), ``tests/``, and ``bench.py``:

1. A telemetry API call (``telemetry.inc`` / ``observe`` / ``set_gauge`` /
   ``emit_event`` / ``span`` / ``counter`` / ``gauge`` / ``histogram``) whose first argument is a STRING LITERAL must use a
   literal registered in ``names.ALL_NAMES`` — a free string silently
   forks the time series across bench rounds.
2. An attribute reference ``names.X`` / ``tm.X`` (the aliases this tree
   imports the module under) must name an existing constant — a typo'd
   constant would otherwise surface only at runtime on the telemetry path.

``finalize`` re-checks the registry itself: names are lowercase dotted
paths and no two constants share a value.
"""

from __future__ import annotations

import ast
from typing import List

from stencil_tpu.lint.framework import FileContext, Rule, Violation, register

#: telemetry facade entry points whose first positional arg is a series name
NAME_TAKING_CALLS = {
    "inc",
    "observe",
    "set_gauge",
    "emit_event",
    "span",
    "counter",
    "gauge",
    "histogram",
}

#: module aliases the tree uses for the telemetry facade and the names module
FACADE_ALIASES = {"telemetry"}
NAMES_ALIASES = {"names", "tm"}


def _registry():
    """names.ALL_NAMES plus the constant map — imported lazily so the lint
    package stays importable even mid-refactor of the telemetry package.

    ``constants`` holds every uppercase module attribute: plain string names
    AND the keyed registries over them (``EXCHANGE_HOP_BYTES``,
    ``EXCHANGE_DIRECTION_SPANS`` — dicts mapping (axis, side) to a
    registered name).  The existence check accepts both; the hygiene checks
    in ``finalize`` apply only to the string-valued ones."""
    from stencil_tpu.telemetry import names

    constants = {k: v for k, v in vars(names).items() if k.isupper()}
    return names.ALL_NAMES, constants


def _is_telemetry_call(node: ast.Call) -> bool:
    """``telemetry.<api>(...)`` or a bare ``<api>(...)`` name imported from
    the facade — bare names are matched by name alone, which is safe because
    the API verbs are distinctive (``emit_event``, ``set_gauge``) and
    a false positive only ever asks the author to register a name."""
    f = node.func
    if isinstance(f, ast.Attribute):
        return (
            isinstance(f.value, ast.Name)
            and f.value.id in FACADE_ALIASES
            and f.attr in NAME_TAKING_CALLS
        )
    if isinstance(f, ast.Name):
        # bare imports: only the unambiguous verbs (plain `span`/`counter`
        # etc. collide with too many local names to match blindly)
        return f.id in {"emit_event", "set_gauge"}
    return False


@register
class TelemetryNameRule(Rule):
    name = "telemetry-name"
    why = (
        "free-string telemetry names fork the cross-round time series; "
        "register every series in stencil_tpu/telemetry/names.py and "
        "reference the constant"
    )

    def applies_to(self, rel: str) -> bool:
        rel = rel.replace("\\", "/")
        if rel.startswith("stencil_tpu/telemetry/"):
            return False  # internals pass names through as parameters
        return (
            rel.startswith("stencil_tpu/")
            or rel.startswith("tests/")
            or rel == "bench.py"
        )

    def check(self, ctx: FileContext) -> List[Violation]:
        all_names, constants = _registry()
        out = []
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Call) and _is_telemetry_call(node):
                if (
                    node.args
                    and isinstance(node.args[0], ast.Constant)
                    and isinstance(node.args[0].value, str)
                ):
                    lit = node.args[0].value
                    if lit not in all_names:
                        out.append(
                            ctx.violation(
                                self.name,
                                node,
                                f"free-string telemetry name {lit!r} — "
                                "register it in stencil_tpu/telemetry/"
                                "names.py and reference the constant",
                            )
                        )
            if (
                isinstance(node, ast.Attribute)
                and isinstance(node.value, ast.Name)
                and node.value.id in NAMES_ALIASES
                and node.attr.isupper()
                and node.attr not in constants
                and not node.attr.startswith("ALL_")
            ):
                out.append(
                    ctx.violation(
                        self.name,
                        node,
                        f"names.{node.attr} is not defined in "
                        "stencil_tpu/telemetry/names.py",
                    )
                )
        return out

    def finalize(self) -> List[Violation]:
        _, constants = _registry()
        out = []
        seen = {}
        rel = "stencil_tpu/telemetry/names.py"
        for const, value in sorted(constants.items()):
            if not isinstance(value, str):
                continue  # keyed registries: their values are the constants
            if not all(part for part in value.split(".")) or value != value.lower():
                out.append(
                    Violation(
                        self.name,
                        rel,
                        1,
                        f"names.{const} = {value!r}: names are lowercase "
                        "dotted paths",
                    )
                )
            if value in seen:
                out.append(
                    Violation(
                        self.name,
                        rel,
                        1,
                        f"names.{const} duplicates names.{seen[value]} "
                        f"({value!r})",
                    )
                )
            seen[value] = const
        return out
