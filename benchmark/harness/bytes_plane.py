"""The least HBM traffic of one call of the stream engine's plane pass
(kernel ``stream_plane_pass``), from a configuration's numbers alone: the
yardstick of ``plane_pass_hbm_pct``.  Kept here so that no later change to
the program can move it."""

from __future__ import annotations


def raw_cells_per_chip(extent_per_chip, radius: int) -> int:
    """Cells of one chip's shell-carrying block: the pass streams every
    x-plane of it, shell included, exactly once."""
    x, y, z = extent_per_chip
    return (x + 2 * radius) * (y + 2 * radius) * (z + 2 * radius)


def plane_pass_bytes(config: dict) -> int:
    """(quantities read + quantities written) x raw cells x itemsize: what
    the update needs -- every quantity read once, every quantity the kernel
    advances written once (``pass.reads`` / ``pass.writes`` in the
    configuration).  No lane padding and no write-back of quantities the
    kernel only reads: traffic the program adds on top counts against it."""
    p = config["pass"]
    cells = raw_cells_per_chip(config["extent_per_chip"], config["radius"])
    return (p["reads"] + p["writes"]) * cells * config["itemsize"]
