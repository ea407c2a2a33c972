"""Quadratic assignment for topology-aware placement.

Parity target: ``qap::solve`` / ``qap::solve_catch`` (reference
include/stencil/qap.hpp:50-172).  Given a weight (communication) matrix ``w``
and a distance matrix ``d``, find the bijection ``f`` minimizing
``sum_ab w[a][b] * d[f[a]][f[b]]`` — with the reference's ``0 * inf = 0``
guard (qap.hpp:15-20).

* ``qap_solve`` — exact, O(n!) over all permutations (qap.hpp:50-75); the
  reference calls this per-node for <= ~6 GPUs.
* ``qap_solve_catch`` — "CRAFT" 2-opt pairwise-swap hill climbing with
  incremental cost updates (qap.hpp:77-172); the scalable one, used here for
  pod-sized meshes.

A C++ implementation (``native/qap.cpp``) is used when the shared library has
been built (it is ~100x faster for the exact solver at n>=8); these Python
versions are the always-available fallback and the semantic spec.
"""

from __future__ import annotations

import functools
import itertools
from typing import List, Optional, Sequence, Tuple

import numpy as np


def _cost_product(we: float, de: float) -> float:
    # qap.hpp:15-20: avoid 0 * inf = nan
    if we == 0 or de == 0:
        return 0.0
    return we * de


def qap_cost(w: np.ndarray, d: np.ndarray, f: Sequence[int]) -> float:
    """qap.hpp:23-47."""
    w = np.asarray(w, dtype=float)
    d = np.asarray(d, dtype=float)
    n = w.shape[0]
    assert w.shape == (n, n) and d.shape == (n, n) and len(f) == n
    # vectorized with the 0*inf guard: mask where either factor is zero
    df = d[np.ix_(f, f)]
    prod = np.where((w == 0) | (df == 0), 0.0, w * df)
    return float(prod.sum())


def qap_solve(w: np.ndarray, d: np.ndarray) -> Tuple[List[int], float]:
    """Exact exhaustive search (qap.hpp:50-75).  O(n!)."""
    w = np.asarray(w, dtype=float)
    d = np.asarray(d, dtype=float)
    n = w.shape[0]
    best_f = list(range(n))
    best_cost = qap_cost(w, d, best_f)
    for perm in itertools.permutations(range(n)):
        c = qap_cost(w, d, perm)
        if c < best_cost:
            best_cost = c
            best_f = list(perm)
    return best_f, best_cost


def _masked_prod(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    # elementwise cost_product (qap.hpp:15-20): 0 * inf = 0
    return np.where((a == 0) | (b == 0), 0.0, a * b)


def _swap_delta(w: np.ndarray, d: np.ndarray, f: List[int], i: int, j: int) -> float:
    """Cost change from swapping f[i], f[j] (incremental update,
    qap.hpp:108-147), including the diagonal overlap handling.  Vectorized
    over k; semantically identical to the reference's loop."""
    fa = np.asarray(f)

    def affected(fi_sub: int, fj_sub: int) -> float:
        s = _masked_prod(w[i, :], d[fi_sub, fa]).sum()
        s += _masked_prod(w[j, :], d[fj_sub, fa]).sum()
        col = _masked_prod(w[:, i], d[fa, fi_sub]) + _masked_prod(w[:, j], d[fa, fj_sub])
        s += col.sum() - col[i] - col[j]
        # the two row terms above used d[fi_sub, fa] with fa holding the
        # UNswapped values at i and j; patch those four entries
        s -= _masked_prod(w[i, i], d[fi_sub, fa[i]]) + _masked_prod(w[i, j], d[fi_sub, fa[j]])
        s -= _masked_prod(w[j, i], d[fj_sub, fa[i]]) + _masked_prod(w[j, j], d[fj_sub, fa[j]])
        fi_cur, fj_cur = fi_sub, fj_sub
        s += _masked_prod(w[i, i], d[fi_cur, fi_cur]) + _masked_prod(w[i, j], d[fi_cur, fj_cur])
        s += _masked_prod(w[j, i], d[fj_cur, fi_cur]) + _masked_prod(w[j, j], d[fj_cur, fj_cur])
        return float(s)

    before = affected(f[i], f[j])
    after = affected(f[j], f[i])
    return after - before


def qap_solve_catch(w: np.ndarray, d: np.ndarray) -> Tuple[List[int], float]:
    """2-opt hill climbing (qap.hpp:77-172): repeatedly take the best
    single-pair swap until no swap improves."""
    w = np.asarray(w, dtype=float)
    d = np.asarray(d, dtype=float)
    n = w.shape[0]
    best_f = list(range(n))
    best_cost = qap_cost(w, d, best_f)

    improved = True
    while improved:
        improved = False
        impr_swap: Optional[Tuple[int, int]] = None
        impr_cost = best_cost
        for i in range(n):
            for j in range(i + 1, n):
                c = best_cost + _swap_delta(w, d, best_f, i, j)
                if c < impr_cost:
                    impr_cost = c
                    impr_swap = (i, j)
                    improved = True
        if improved:
            i, j = impr_swap
            best_f[i], best_f[j] = best_f[j], best_f[i]
            best_cost = impr_cost
    return best_f, best_cost


@functools.cache
def _native():
    """The native solver module, or None — resolved (built, loaded) once per
    process, saying once which solver placement will use."""
    from stencil_tpu.utils.logging import log_info

    try:
        from stencil_tpu.parallel import native_qap
    except (ImportError, OSError) as e:
        log_info(f"QAP solver: pure Python (native unavailable: {e})")
        return None
    log_info("QAP solver: native (native/libstencil_native.so)")
    return native_qap


def solve_auto(w: np.ndarray, d: np.ndarray, exact_limit: int = 8) -> Tuple[List[int], float]:
    """Exact for small n (like the reference's per-node exact solve for <=6
    GPUs, partition.hpp:802-803), 2-opt beyond.  Prefers the native C++
    implementation when it builds."""
    native = _native()
    if native is not None:
        return native.solve_auto(w, d, exact_limit)
    n = np.asarray(w).shape[0]
    if n <= exact_limit:
        return qap_solve(w, d)
    return qap_solve_catch(w, d)
