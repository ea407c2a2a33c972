"""The least HBM traffic of one call of the plane pass in a step of several
passes (kernel ``stream_plane_pass``, every pass of every stage), from a
configuration's numbers alone: the yardstick of ``plane_pass_hbm_pct.staged``.
Kept here so that no later change to the program can move it."""

from __future__ import annotations

from benchmark.harness.bytes_plane import raw_cells_per_chip


def step_bytes(config: dict) -> int:
    """Sum over the configured passes of (quantities read + quantities
    written) x raw cells x itemsize: what ONE time step's passes need --
    every quantity a pass touches read once, every one it advances written
    once (``passes[].reads`` / ``.writes`` in the configuration).  No lane
    padding: traffic the program adds on top counts against it."""
    cells = raw_cells_per_chip(config["extent_per_chip"], config["radius"])
    return sum(p["reads"] + p["writes"] for p in config["passes"]) * cells * config["itemsize"]


def plane_pass_bytes(config: dict) -> float:
    """Per CALL of the kernel: every configured pass runs once a time step,
    so the calls of a traced stretch hold them in equal numbers and the mean
    call moves the step's bytes over the number of passes."""
    return step_bytes(config) / len(config["passes"])
