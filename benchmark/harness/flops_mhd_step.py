"""The floating-point operations of one call of the pass kernel in a substep of
Astaroth's MHD step that takes SEVERAL calls (configuration
``astaroth-mhd-512``): the yardstick of ``mhd_pass_flops_pct.mhd512``.  Kept here
so that no later change to the program can move it.

``harness/flops_mhd.py`` counts a substep's operations a cell from the equations
alone (837); a TIME STEP's are that times the substeps times the cells, and a
call's the time step's over the calls it is configured to take
(``harness/bytes_mhd_step.py`` has the reason: a substep cut into four passes
does the update's work once, not four times -- what its passes compute twice
counts against them)."""

from __future__ import annotations

from benchmark.harness import flops_mhd
from benchmark.harness.bytes_mhd_step import calls_per_step


def step_flops(config: dict) -> int:
    """``flops_per_cell`` x the cells a substep updates x the substeps."""
    return int(config["substeps"]) * flops_mhd.pass_flops(config)


def pass_flops(config: dict) -> float:
    """Per CALL of the kernel: the time step's operations over its calls."""
    return step_flops(config) / calls_per_step(config)
