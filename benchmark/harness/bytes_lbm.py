"""The least HBM traffic of one call of the joint lattice-Boltzmann kernel
(whichever stream-engine pass runs it), from a configuration's numbers alone:
the yardstick of ``lbm_pass_hbm_pct``.  Kept here so that no later change to
the program can move it."""

from __future__ import annotations


def pass_bytes(config: dict) -> int:
    """(populations read + populations written) x lattice cells x itemsize
    per CALL: every output of the joint kernel needs all nineteen
    populations, so a call reads each cell of each at least once and writes
    each at least once, however many levels it advances.  No shell, no lane
    padding: traffic the program adds on top counts against it, and a call
    that advances several levels does not move less than this."""
    p = config["pass"]
    x, y, z = config["extent_per_chip"]
    return (p["reads"] + p["writes"]) * x * y * z * config["itemsize"]
