"""Share of the chip's published arithmetic peak that one NAMED Pallas kernel
reaches on the operations its update needs, counted by a function of the
configuration (``harness/flops_mhd.py``), not read off the program: the kernel
is found by name (``harness/timeline.py``).  ``named_roofline_hbm``'s twin, and
its arithmetic: work per call over a peak rate, over the calls' device time."""

from benchmark.reducers import named_roofline_hbm


def reduce(ctx, kernel, flops_fn, peak="bf16_flops_per_s"):
    """100 x (calls x operations per call / peak operations/s) / (the calls'
    device time, union of their intervals), over all chips.  ``kernel`` is a
    regex on the Pallas kernel name, ``flops_fn`` a ``module:function`` under
    ``benchmark/`` that takes the cell's configuration, ``peak`` a key of
    ``harness/peaks.json``.  The count is the update's own, so the share
    reads the same work whatever program does it, and cannot pass 100 unless
    the function counts too much.  Nothing to read -- no trace, a program that
    names nothing, no such kernel in it (the parent of the PR that added the
    cell) -- is None, as in the twin, which does the reading: the operations
    stand where it has bytes, the arithmetic peak where it has the HBM's."""
    if ctx.get("peaks") is None:
        return None
    rate = {"hbm_bytes_per_s": ctx["peaks"][peak]}
    return named_roofline_hbm.reduce({**ctx, "peaks": rate}, kernel, flops_fn)
