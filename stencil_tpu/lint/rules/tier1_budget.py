"""Rule ``slow-marker``: one class of expensive tier-1 test, found by its shape.

What the rule sees: a test that spawns a fresh interpreter (``sys.executable``
/ ``subprocess``) pays import and backend cold start every run, and a test
that invokes ``bench.py`` runs a whole measurement protocol.  Such tests must
carry ``@pytest.mark.slow`` (tier-2) -- or a suppression stating why the spawn
is cheap (e.g. logging's jax-free ``python -c`` children).  Detection is
transitive over same-file helpers: a test calling a module helper that spawns
is as expensive as spawning inline.  Docstrings are ignored (mentioning
bench.py is not running it).

What it does NOT see is where tier-1's time went once the spawns were gone: an
in-process test that traces and lowers interpreted ``pallas_call``s costs
seconds a call, and no syntax says how many programs a test traces (a
``parametrize`` over a static ``steps`` is a program a case).  That cost is
measured, not linted: ``scripts/tier1_times.py <junit.xml>`` prints the
test-seconds a file and the longest cases of the driver's run and exits 1 over
the budget ROADMAP D13 states (the run's limit is 1470 s over six workers under
``--dist loadfile``; the 870 s in this rule's message is the serial limit of
the rounds that wrote it).
"""

from __future__ import annotations

import ast
from typing import List, Set

from stencil_tpu.lint import astutil
from stencil_tpu.lint.framework import FileContext, Rule, register

_SPAWN_ATTRS = {"executable"}  # sys.executable
_SUBPROCESS_CALLS = {"run", "Popen", "call", "check_call", "check_output"}


def _is_docstring(node: ast.AST, parents: Set[int]) -> bool:
    return id(node) in parents


def _docstring_constants(tree: ast.Module) -> Set[int]:
    """ids of every Constant that is a docstring expression."""
    out: Set[int] = set()
    for node in ast.walk(tree):
        body = getattr(node, "body", None)
        if (
            isinstance(node, (ast.Module, ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef))
            and body
            and isinstance(body[0], ast.Expr)
            and isinstance(body[0].value, ast.Constant)
            and isinstance(body[0].value.value, str)
        ):
            out.add(id(body[0].value))
    return out


def _spawns_directly(fn: ast.AST, docstrings: Set[int]) -> bool:
    for node in ast.walk(fn):
        if isinstance(node, ast.Attribute):
            if node.attr in _SPAWN_ATTRS and astutil.dotted(node) == "sys.executable":
                return True
            if (
                node.attr in _SUBPROCESS_CALLS
                and isinstance(node.value, ast.Name)
                and node.value.id == "subprocess"
            ):
                return True
        if (
            isinstance(node, ast.Constant)
            and isinstance(node.value, str)
            and "bench.py" in node.value
            and not _is_docstring(node, docstrings)
        ):
            return True
    return False


def _slow_marked(fn, klass, module_marks: bool) -> bool:
    def mark_in(dec_list) -> bool:
        for d in dec_list:
            target = d.func if isinstance(d, ast.Call) else d
            name = astutil.dotted(target) or ""
            if name.endswith("mark.slow") or name == "slow":
                return True
        return False

    if module_marks:
        return True
    if mark_in(fn.decorator_list):
        return True
    return klass is not None and mark_in(klass.decorator_list)


def _module_pytestmark_slow(tree: ast.Module) -> bool:
    for node in tree.body:
        if isinstance(node, ast.Assign) and any(
            isinstance(t, ast.Name) and t.id == "pytestmark" for t in node.targets
        ):
            for n in ast.walk(node.value):
                if isinstance(n, ast.Attribute) and n.attr == "slow":
                    return True
    return False


@register
class SlowMarkerRule(Rule):
    name = "slow-marker"
    why = (
        "tests that spawn interpreters or run bench.py pay cold starts the "
        "870s tier-1 budget cannot absorb; mark them @pytest.mark.slow or "
        "suppress stating why the spawn is cheap"
    )

    def applies_to(self, rel: str) -> bool:
        rel = rel.replace("\\", "/")
        return rel.startswith("tests/") and rel.split("/")[-1].startswith("test_")

    def check(self, ctx: FileContext) -> List:
        tree = ctx.tree
        docstrings = _docstring_constants(tree)
        defs = astutil.module_defs(tree)
        # transitive spawn set over same-file helpers (fixpoint)
        spawny: Set[str] = {
            name
            for name, nodes in defs.items()
            if any(_spawns_directly(n, docstrings) for n in nodes)
        }
        changed = True
        while changed:
            changed = False
            for name, nodes in defs.items():
                if name in spawny:
                    continue
                for n in nodes:
                    if astutil.called_names(n) & spawny:
                        spawny.add(name)
                        changed = True
                        break
        module_marks = _module_pytestmark_slow(tree)
        out = []
        for klass, fn in _test_functions(tree):
            if fn.name not in spawny:
                continue
            if _slow_marked(fn, klass, module_marks):
                continue
            # anchor at the first decorator so a suppression directly above
            # the decorated test covers the finding
            anchor = min([d.lineno for d in fn.decorator_list] + [fn.lineno])
            out.append(
                ctx.violation(
                    self.name,
                    anchor,
                    f"{fn.name} spawns a subprocess / runs bench.py but is "
                    "not @pytest.mark.slow — heavyweight tests go to "
                    "tier-2 (ROADMAP: tier-1 870s budget), or suppress "
                    "with the reason the child is cheap",
                )
            )
        return out


def _test_functions(tree: ast.Module):
    for node in tree.body:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            if node.name.startswith("test"):
                yield None, node
        elif isinstance(node, ast.ClassDef):
            for sub in node.body:
                if isinstance(
                    sub, (ast.FunctionDef, ast.AsyncFunctionDef)
                ) and sub.name.startswith("test"):
                    yield node, sub
