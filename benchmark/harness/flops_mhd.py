"""The floating-point operations of one substep of Astaroth's MHD step, a cell,
counted from the equations alone (``harness/reference_mhd.py``'s docstring has
them): the yardstick of ``mhd_pass_flops_pct``.  Kept here so that no later
change to the program can move it.

The count is of the UPDATE, not of any program: every distinct difference the
equations need is made once, each add, subtract, multiply, divide and
exponential counts one, the grid spacing is folded into the difference
coefficients, and nothing is shared beyond that or recomputed.  A program
that shares more (or fuses multiply-adds) does the same work in fewer
instructions and reads a higher share, one that recomputes reads a lower one.
"""

from __future__ import annotations

#: one sixth-order difference: first = 3 subtractions, 3 multiplies, 2 adds;
#: second = 3 pair adds, 4 multiplies, 3 adds; mixed (diagonal form) = 9 adds
#: and subtractions over the twelve corners, 3 multiplies, 2 adds
FIRST, SECOND, MIXED = 8, 10, 14

#: the distinct differences a substep needs
DIFFERENCES = {
    # grad lnrho, grad ss, the nine d_j u_i, the six off-diagonal d_j A_i of curl A
    "first": (3 + 3 + 9 + 6, FIRST),
    # the three unmixed second differences of every field (the Laplacians, and
    # the diagonal of grad div u and grad div A)
    "second": (3 * 8, SECOND),
    # d_i d_j v_j, i != j, of u and of A
    "mixed": (6 + 6, MIXED),
}

#: what the equations do with them, term by term
COMBINATIONS = {
    "div u": 2,
    "the eight Laplacians": 8 * 2,
    "grad div u, grad div A (sums of three)": 2 * 3 * 2,
    "B = curl A": 3,
    "mu0 j = grad div A - lap A": 3,
    "S (three diagonal, three off-diagonal, div u / 3)": 3 + 1 + 3 * 2,
    "gamma ss/cp + (gamma - 1)(lnrho - lnrho0)": 4,
    "cs2 = cs0^2 exp(.)": 2,
    "1/rho = exp(-lnrho)": 2,
    "1/T = exp(-(lnT0 + .))": 3,
    "continuity: -u.grad lnrho - div u": 5 + 2,
    # a component: (u.grad)u 5; pressure 3; Lorentz 3 + 1; viscous 10; zeta 1;
    # the five terms summed 4 -- and 1/(mu0 rho) once
    "momentum": 3 * (5 + 3 + 4 + 10 + 1 + 4) + 1,
    "induction: u x B - eta mu0 j": 3 * (3 + 1 + 1),
    # -u.grad ss 6; heating: j.j 5, its factor 2, S:S 12, 2 nu 1, zeta (div u)^2
    # 2, the sum 2, 1/T 1; conduction: the two gradient sums 18, their dot 5, the
    # Laplacians' part 4, cp chi 1; the two added 1
    "entropy": 6 + (5 + 2 + 12 + 1 + 2 + 2 + 1) + (18 + 5 + 4 + 1) + 1,
}

#: the Runge-Kutta update of a field: dt F, beta w, the add -- and in the second
#: and third substeps alpha/beta (cur - prev) and its add: (3 + 6 + 6) / 3
RUNGE_KUTTA = 8 * 5


def flops_per_cell() -> int:
    """Operations a cell a substep (the mean of the three substeps): 837."""
    return (
        sum(n * cost for n, cost in DIFFERENCES.values())
        + sum(COMBINATIONS.values())
        + RUNGE_KUTTA
    )


def pass_flops(config: dict) -> int:
    """Per CALL of the pass: ``flops_per_cell`` x the cells it updates (the
    interior: no shell cell is computed)."""
    x, y, z = config["extent_per_chip"]
    return flops_per_cell() * x * y * z
