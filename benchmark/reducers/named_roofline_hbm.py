"""HBM roofline share of one NAMED Pallas kernel whose least traffic per call
comes from a function of the configuration (``harness/bytes_plane.py``), not
from the op's shape: the kernel is found by name (``harness/timeline.py``)."""

import importlib

from benchmark.harness import timeline, trace


def reduce(ctx, kernel, bytes_fn):
    """100 x (calls x least bytes per call / peak HBM bytes/s) / (the calls'
    device time, union of their intervals), over all chips.  ``kernel`` is a
    regex on the Pallas kernel name, ``bytes_fn`` a ``module:function`` under
    ``benchmark/`` that takes the cell's configuration.  A kernel cannot
    move its least traffic faster than the peak, so the share cannot pass 100
    unless the function counts too much.  The configuration is the traced
    cell's (``ctx["config"]`` where a test hands one).  Nothing to read -- no trace, a
    program that names nothing, no such kernel in it (the parent of the PR
    that added the cell) -- is None."""
    tl = timeline.for_ctx(ctx)
    if tl is None or not tl["named"] or ctx.get("peaks") is None:
        return None
    config = ctx.get("config")
    if config is None:
        from benchmark.harness import window

        config = window.find_cell(tl["workload"])[1]
    module, _, function = bytes_fn.partition(":")
    per_call = getattr(importlib.import_module(module), function)(config)
    least_s = took_s = 0.0
    for ops in tl["devices"].values():
        hit = timeline.select(ops, label=[timeline.PALLAS_LABEL.pattern], kernel=[kernel])
        least_s += len(hit) * per_call / ctx["peaks"]["hbm_bytes_per_s"]
        took_s += trace.busy_ns(hit) / 1e9
    if not took_s:
        return None
    return 100.0 * least_s / took_s
