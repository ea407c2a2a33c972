"""Rule ``accum-dtype``: every matrix contraction in ``stencil_tpu/ops/``
(``dot_general`` / ``jnp.dot`` / ``jnp.matmul`` / ``jnp.einsum``) passes an
explicit ``preferred_element_type``.

Why: bf16 storage runs reduced-precision fields through full-precision
accumulation — a ``dot_general`` over bf16 operands WITHOUT
``preferred_element_type`` silently accumulates at bf16 (bf16 x bf16 -> bf16),
which is exactly the bug class the bf16-storage/f32-accumulate contract
forbids (docs/tuning.md "Storage dtype").  Making the accumulator explicit at
every contraction site keeps the contract checkable instead of hoping each
kernel author remembers the XLA default.
"""

from __future__ import annotations

import ast
from typing import List, Optional

from stencil_tpu.lint.framework import FileContext, Rule, Violation, register

#: callee attribute names that lower to an XLA dot (einsum included: it
#: takes the same keyword and has the same silent-bf16-accumulate default)
_DOT_FUNCS = {"dot_general", "dot", "matmul", "einsum"}

#: module aliases a contraction is expected to hang off — ``jnp.dot``,
#: ``lax.dot_general``, ``jax.lax.dot_general``, ``jax.numpy.matmul``...
_MODULE_NAMES = {"jnp", "lax", "jax", "numpy", "pl", "pltpu"}


def _dot_callee(node: ast.Call) -> Optional[str]:
    """The contraction function name when this call is one, else None.

    Matches ``<mod>.<fn>(...)`` for fn in ``_DOT_FUNCS`` with ``<mod>``
    rooted at a known module alias (``np.dot`` on host arrays is out of
    scope only by module name — ops/ kernels use jnp/lax), and the bare
    ``dot_general(...)`` form from ``from jax.lax import dot_general``."""
    f = node.func
    if isinstance(f, ast.Attribute) and f.attr in _DOT_FUNCS:
        root = f.value
        while isinstance(root, ast.Attribute):
            root = root.value
        if isinstance(root, ast.Name) and root.id in _MODULE_NAMES:
            return f.attr
        return None
    if isinstance(f, ast.Name) and f.id in _DOT_FUNCS:
        return f.id
    return None


@register
class AccumDtypeRule(Rule):
    name = "accum-dtype"
    why = (
        "a dot_general/jnp.dot in ops/ without preferred_element_type "
        "silently accumulates bf16 x bf16 at bf16 — the accumulator must be "
        "explicit so the f32-accumulate contract is checkable"
    )

    def applies_to(self, rel: str) -> bool:
        rel = rel.replace("\\", "/")
        return rel.startswith("stencil_tpu/ops/")

    def check(self, ctx: FileContext) -> List[Violation]:
        out = []
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            fn = _dot_callee(node)
            if fn is None:
                continue
            kw_names = {k.arg for k in node.keywords}
            if "preferred_element_type" in kw_names:
                continue
            if None in kw_names:
                continue  # a **kwargs splat may carry it; not statically decidable
            out.append(
                ctx.violation(
                    self.name,
                    node,
                    f"{fn}() without preferred_element_type — bf16 operands "
                    "would silently accumulate at bf16; pin the accumulator "
                    "(preferred_element_type=jnp.float32) per the "
                    "f32-accumulate contract (docs/tuning.md)",
                )
            )
        return out
