"""The benchmark's own arithmetic of work: cell-updates and analytic halo
bytes, from a configuration's numbers alone.  Kept here so that no later
change to the program can move the yardstick."""

from __future__ import annotations


def cell_updates_per_chip(extent_per_chip, fields: int, steps: int) -> int:
    """Cell-updates one chip completes in ``steps`` raw iterations:
    cells x fields x steps."""
    x, y, z = extent_per_chip
    return x * y * z * fields * steps


def halo_cells_per_chip(extent_per_chip, radius: int) -> int:
    """Shell cells of one chip's block at ``radius`` in all 26 directions:
    (n+2r)^3 - n^3 generalised to a box."""
    x, y, z = extent_per_chip
    r2 = 2 * radius
    return (x + r2) * (y + r2) * (z + r2) - x * y * z


def halo_bytes_per_chip(extent_per_chip, radius: int, itemsize: int, quantities: int) -> int:
    """Analytic halo bytes one chip RECEIVES per ``exchange()``: every shell
    cell filled, whether it came over ICI or from the chip itself (on mesh
    [2,2,1] the z neighbours are the chip itself) -- so never read it
    against the ICI peak."""
    return halo_cells_per_chip(extent_per_chip, radius) * itemsize * quantities
