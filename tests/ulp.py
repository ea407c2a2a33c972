"""Shared tolerance-aware equivalence helpers: ULP distances instead of
ad-hoc ``atol`` constants.

Two formulations of the same stencil arithmetic (a fused m-level graph vs m
separate dispatches, a kernel vs its XLA twin) differ only in summation
order / excess precision, so the principled equivalence statement is a bound
in UNITS IN THE LAST PLACE of the result's own dtype — one rounding's worth
of divergence per reassociated operation — not an absolute epsilon picked to
make the test pass.  These helpers back:

* the wavefront excess-precision caveat (PERF_NOTES "Equivalence": a fused
  m-level graph vs m separate dispatches may differ in the LAST ulp per
  level through the division — interpret mode only, bitwise on hardware).
* the bf16-storage analytic bound (docs/tuning.md "Storage dtype"): f32
  accumulate with ONE round-to-nearest-bf16 per pass — see
  :func:`bf16_storage_atol`.
"""

import numpy as np

try:  # jnp.bfloat16 arrays reach these helpers via device_get
    import ml_dtypes

    _BFLOAT16 = np.dtype(ml_dtypes.bfloat16)
except ImportError:  # pragma: no cover - ml_dtypes ships with jax
    _BFLOAT16 = None

#: default per-dtype ulp bounds for a SINGLE reassociated operation — one
#: rounding each for the two formulations being compared
ULP_DEFAULT = {
    np.dtype(np.float32): 1,
    np.dtype(np.float64): 1,
}
if _BFLOAT16 is not None:
    ULP_DEFAULT[_BFLOAT16] = 1

_INT_VIEW = {2: np.int16, 4: np.int32, 8: np.int64}


def ulp_diff(actual, desired) -> np.ndarray:
    """Elementwise distance in units-in-the-last-place of the common dtype.

    Floats are viewed as their same-width signed ints and mapped to a
    monotonically ordered integer line (the standard two's-complement
    trick: negative floats fold below the positives, ``-0.0`` lands on
    ``+0.0``), where adjacent representable values differ by exactly 1 —
    so the absolute integer difference IS the ulp distance, correct across
    exponent boundaries where ``np.spacing``-based bounds miscount."""
    a = np.asarray(actual)
    b = np.asarray(desired)
    assert a.dtype == b.dtype, (a.dtype, b.dtype)
    assert np.isfinite(a.astype(np.float64)).all(), "non-finite actual"
    assert np.isfinite(b.astype(np.float64)).all(), "non-finite desired"
    itype = _INT_VIEW[a.dtype.itemsize]
    ai = a.view(itype).astype(np.int64)
    bi = b.view(itype).astype(np.int64)
    fold = np.int64(np.iinfo(itype).min)
    ai = np.where(ai < 0, fold - ai, ai)
    bi = np.where(bi < 0, fold - bi, bi)
    return np.abs(ai - bi)


def assert_ulp_close(actual, desired, ulps=None, context: str = "") -> None:
    """Assert every element of ``actual`` is within ``ulps`` representable
    values of ``desired`` (same dtype).  ``ulps=None`` uses the per-dtype
    single-reassociation default (``ULP_DEFAULT``); multi-level fused
    passes scale it by the level count at the call site, where the depth
    is known."""
    a = np.asarray(actual)
    if ulps is None:
        ulps = ULP_DEFAULT[a.dtype]
    d = ulp_diff(a, desired)
    worst = int(d.max()) if d.size else 0
    assert worst <= ulps, (
        f"{context or 'arrays'} differ by {worst} ulp(s) "
        f"(bound {ulps}, dtype {a.dtype}, "
        f"{int((d > ulps).sum())}/{d.size} elements over)"
    )


def reassociation_atol(rounds: int, scale: float, dtype=np.float32) -> float:
    """Analytic absolute bound for two REASSOCIATED evaluations of the same
    expression: each differing rounding contributes at most a half-ulp AT
    THE MAGNITUDE OF ITS INTERMEDIATE (``scale``), so ``rounds`` reordered
    operations diverge by ≤ ``rounds * scale * eps/2``.  This is the right
    yardstick where the RESULT can approach zero (a mean of cancelling
    terms): result-relative ulps blow up on denormal-scale outputs even
    though the absolute divergence stays at operand scale — the PERF_NOTES
    "last ulp" wavefront caveat measured in its own units."""
    eps = np.finfo(dtype).eps
    return rounds * scale * eps / 2.0


def assert_reassociation_close(actual, desired, rounds: int,
                               scale: float = None, context: str = "") -> None:
    """Pin two formulations differing only in operation ORDER to the
    analytic reassociation bound above.  ``scale`` defaults to the
    desired side's max magnitude (the intermediates of a mean-of-N are
    at most N× that; fold such factors into ``rounds`` or ``scale`` at
    the call site where the expression shape is known)."""
    a = np.asarray(actual)
    d = np.asarray(desired)
    assert a.dtype == d.dtype, (a.dtype, d.dtype)
    if scale is None:
        scale = float(np.abs(d).max()) or 1.0
    atol = reassociation_atol(rounds, scale, d.dtype)
    err = float(np.abs(a - d).max()) if a.size else 0.0
    assert err <= atol, (
        f"{context or 'reassociated forms'} diverged {err:.3e} "
        f"(analytic bound {atol:.3e} = {rounds} roundings * half-ulp at "
        f"scale {scale:.3g}, dtype {d.dtype})"
    )


def bf16_storage_atol(passes: int, scale: float = 1.0) -> float:
    """Analytic absolute bound for ``storage_dtype=bf16`` against the f32
    ground truth after ``passes`` kernel passes (= downcasts).

    The f32-accumulate contract makes each pass exact EXCEPT for one
    round-to-nearest-bfloat16 at the final store: relative error ≤ 2^-9
    per downcast (bfloat16 keeps 8 significand bits, so a half-ulp is
    2^-9).  The carried error passes through the next level's mean — a
    convex average never amplifies it — and picks up one more rounding,
    so after ``passes`` stores plus the initial bf16 representation of the
    input the divergence is ≤ ``(passes + 1) * 2^-9 * scale``, with
    ``scale`` the field's magnitude bound (jacobi/mean6 fields live in
    [0, 1] -> scale 1.0)."""
    return (passes + 1) * 2.0 ** -9 * scale


def assert_bf16_storage_close(actual, desired_f32, passes: int,
                              scale: float = None, context: str = "") -> None:
    """Pin a bf16-storage run against its f32 ground truth to the analytic
    bound above.  ``scale`` defaults to the ground truth's max magnitude."""
    a = np.asarray(actual, np.float32)
    d = np.asarray(desired_f32, np.float32)
    if scale is None:
        scale = float(np.abs(d).max()) or 1.0
    atol = bf16_storage_atol(passes, scale)
    err = float(np.abs(a - d).max()) if a.size else 0.0
    assert err <= atol, (
        f"{context or 'bf16 storage'} diverged {err:.3e} from the f32 "
        f"ground truth (analytic bound {atol:.3e} = ({passes}+1) * 2^-9 "
        f"* {scale:.3g})"
    )
