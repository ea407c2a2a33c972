"""The least HBM traffic of one call of the pass kernel in a substep of
Astaroth's MHD step that takes SEVERAL calls (configuration
``astaroth-mhd-512``), from a configuration's numbers alone: the yardstick of
``mhd_pass_hbm_pct.mhd512``.  Kept here so that no later change to the program
can move it.

What is counted is a TIME STEP's own work -- ``substeps`` times what one substep
of the two-buffer Runge-Kutta needs whatever makes it (``bytes_mhd.pass_bytes``:
the eight fields and their eight second buffers read, the eight new values
written) -- spread over the calls a time step is configured to take
(``harness/bytes_staged.py plane_pass_bytes``' precedent).  ``calls x`` the
one-pass count of ``bytes_mhd.pass_bytes`` would count a substep once for every
pass it is cut into and could read over 100%; this reads the same work whatever
number of passes later implements a substep, so the share falls when a substep
moves more arrays than it needs and cannot pass 100."""

from __future__ import annotations

from benchmark.harness import bytes_mhd


def calls_per_step(config: dict) -> int:
    """Calls of the pass kernel a time step: the configured passes of a substep
    (``passes``) times the substeps."""
    return int(config["substeps"]) * len(config["passes"])


def step_bytes(config: dict) -> int:
    """(quantities read + quantities written) x cells x itemsize of a substep,
    times the substeps: no shell, no lane padding, no array read twice."""
    return int(config["substeps"]) * bytes_mhd.pass_bytes(config)


def pass_bytes(config: dict) -> float:
    """Per CALL of the kernel: every configured pass runs once a substep, so the
    calls of a traced stretch hold them in equal numbers and the mean call moves
    the time step's bytes over its number of calls."""
    return step_bytes(config) / calls_per_step(config)
