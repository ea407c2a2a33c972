"""The least HBM traffic of one call of the traced-offset blend kernel
(``blend_slab_dynamic``), from a configuration's numbers alone: the yardstick
of ``blend_dynamic_hbm_pct``.  Kept here so that no later change to the
program can move it."""

from __future__ import annotations


def received_slab_bytes(config: dict) -> int:
    """One received halo slab of one quantity on an unevenly split axis:
    ``radius`` cells thick over the raw extent of the other two axes of the
    LARGEST shard (the padded one: every chip's block has its shape).  With x
    and y split alike the x slab (r x Y x Z) and the y slab (X x r x Z) are
    the same size, so one number serves both."""
    mesh, radius = config["mesh"], config["radius"]
    split = [a for a in range(3) if mesh[a] > 1]
    assert split, "no split axis: the traced-offset blend never runs"
    raw = [
        -(-g // m) + 2 * radius  # ceil(global / mesh) + both halos
        for g, m in zip(config["global_extent"], mesh)
    ]
    faces = {raw[(a + 1) % 3] * raw[(a + 2) % 3] for a in split}
    assert len(faces) == 1, f"split axes with different slabs: {sorted(faces)}"
    return radius * faces.pop() * config["itemsize"]


def blend_dynamic_bytes(config: dict) -> int:
    """2 x one received slab: the slab read once, its cells written once.
    The (8, 128) tiles the kernel re-reads and re-writes around them (a
    3-cell slab in 8-row tiles, visited at two tile positions) are traffic
    the layout adds on top, and count against the share."""
    return 2 * received_slab_bytes(config)
