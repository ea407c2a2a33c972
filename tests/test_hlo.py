"""Tier-2: compiled-HLO structure checks.

The 3-axis-sweep design promises <= 6 face collectives per step for
26-neighbor halos (SURVEY.md §7 "26-neighbor exchange": naive = 26 ppermutes),
plus the two corner relays of the pair of wired axes that sweeps jointly
(ops/exchange.py ``_sweep_groups``: x and y on the tests' mesh [2,2,2]).  Pin
that on the compiled step so a regression back to per-direction messages is
caught at compile level.  (True async overlap — permute-start/done straddling
interior compute — only materializes on the TPU backend; the CPU backend
lowers collective-permute synchronously, so it is asserted on hardware runs,
not here.)
"""

import re

from stencil_tpu.models.astaroth import AstarothSim
from stencil_tpu.models.jacobi import Jacobi3D


#: count APPLICATION sites only ("collective-permute(" / the async start
#: form) — older toolchains name result variables "%collective-permute.N",
#: so a bare substring count would also match every USE of the result
_PERMUTE_RE = r"collective-permute(?:-start)?\("
#: six faces and, behind the y faces of the joint x-y sweep, two corner relays
MAX_PERMUTES = 6 + 2


def _permute_count(model) -> int:
    step = model._step
    txt = step.lower(model.dd._curr, 1).compile().as_text()
    return len(re.findall(_PERMUTE_RE, txt))


def test_jacobi_step_has_at_most_6_permutes():
    m = Jacobi3D(24, 24, 24)
    m.realize()
    n = _permute_count(m)
    assert 1 <= n <= MAX_PERMUTES, n


def test_astaroth_26dir_step_still_6_permutes():
    """Radius-3 face+edge+corner halos must NOT explode into 26 messages."""
    m = AstarothSim(28, 28, 28)
    m.realize()
    n = _permute_count(m)
    assert 1 <= n <= MAX_PERMUTES, n


def test_astaroth_4_quantities_still_6_permutes():
    """Message count must be independent of field count: all quantities fuse
    into ONE buffer per direction (reference packer.cuh:52-69).  Before the
    fused multi-quantity exchange this compiled to 6*N permutes."""
    m = AstarothSim(28, 28, 28, num_quantities=4)
    m.realize()
    n = _permute_count(m)
    assert 1 <= n <= MAX_PERMUTES, n


def test_mixed_dtype_quantities_still_6_permutes():
    """Mixed-dtype fields byte-fuse into the same per-direction buffer, like
    the reference's elemSize-aligned packed layout (packer.cuh:146-160)."""
    import jax.numpy as jnp

    from stencil_tpu.domain import DistributedDomain

    dd = DistributedDomain(24, 24, 24)
    dd.set_radius(1)
    hs = [
        dd.add_data("f32", jnp.float32),
        dd.add_data("bf16", jnp.bfloat16),
        dd.add_data("i32", jnp.int32),
    ]
    dd.realize()

    def kernel(views, info):
        return {h.name: views[h.name].center() for h in hs}

    step = dd.make_step(kernel)
    txt = step.lower(dd._curr, 1).compile().as_text()
    n = len(re.findall(_PERMUTE_RE, txt))
    assert 1 <= n <= MAX_PERMUTES, n


def test_exchange_fn_4_quantities_6_permutes():
    """The standalone exchange (make_exchange_fn) fuses too."""
    import jax.numpy as jnp

    from stencil_tpu.domain import DistributedDomain

    dd = DistributedDomain(24, 24, 24)
    dd.set_radius(2)
    for i in range(4):
        dd.add_data(f"q{i}", jnp.float32)
    dd.realize()
    txt = dd._exchange_fn.lower(dd._curr).compile().as_text()
    n = len(re.findall(_PERMUTE_RE, txt))
    assert 1 <= n <= MAX_PERMUTES, n


def test_exchange_permutes_carry_fused_multi_quantity_sizes():
    """Pin not just the message COUNT but the fused payload SHAPES: each of
    the 6 face permutes must carry all 4 quantities stacked into one buffer of
    exactly the sweep-slab size (the reference's packed per-direction buffer,
    packer.cuh:52-69).  28^3 over mesh [2,2,2], radius 3: shard 14^3, raw
    20^3, so z-slabs are [4,20,20,3]; x-slabs (3,20,20) ride flattened as
    [4,1,60,20] (layout-friendly 2D-spatial form), and so do the y-slabs
    (20,3,20), which fly beside them; behind each y face one corner relay,
    both received x slabs on its three rows: [4,6,3,20]."""
    import jax.numpy as jnp

    from stencil_tpu.domain import DistributedDomain

    dd = DistributedDomain(28, 28, 28)
    dd.set_radius(3)
    for i in range(4):
        dd.add_data(f"q{i}", jnp.float32)
    dd.realize()
    assert tuple(dd.placement.dim()) == (2, 2, 2)
    txt = dd._exchange_fn.lower(dd._curr).compile().as_text()
    # CPU lowering prints each permute as `%... = f32[SHAPE]... collective-permute(...`
    shapes = sorted(
        re.findall(r"= f32\[([\d,]+)\]\S* collective-permute\(", txt)
    )
    assert shapes == sorted(
        ["4,1,60,20"] * 4 + ["4,6,3,20"] * 2 + ["4,20,20,3"] * 2
    ), shapes
