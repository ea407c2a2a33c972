"""The least HBM traffic of one call of the pass that runs a substep of
Astaroth's MHD step, from a configuration's numbers alone: the yardstick of
``mhd_pass_hbm_pct``.  Kept here so that no later change to the program can
move it."""

from __future__ import annotations


def pass_bytes(config: dict) -> int:
    """(quantities read + quantities written) x cells x itemsize per CALL: a
    substep of the two-buffer Runge-Kutta reads the eight fields and their
    eight second buffers and writes the eight new values (``pass.reads`` /
    ``pass.writes`` in the configuration).  No shell, no lane padding:
    traffic the program adds on top counts against it."""
    p = config["pass"]
    x, y, z = config["extent_per_chip"]
    return (p["reads"] + p["writes"]) * x * y * z * config["itemsize"]
