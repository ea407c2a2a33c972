"""Share of device time spent in ops the PROGRAM named: by Pallas kernel
name and by named scope (``harness/timeline.py``), mean over chips."""

from benchmark.harness import timeline, trace


def reduce(ctx, label=(), not_label=(), kernel=(), registered_kernels=False, scope=(),
           not_scope=(), over=(), inherit=True, absent="none"):
    """100 x (union of the selected leaf ops' intervals) / (union of the
    ``over`` ops' intervals; all ops when empty), per chip, averaged -- the
    arithmetic of ``trace_share.py``: both are unions on one chip's clock and
    the selection is a subset of the base, so it cannot pass 100.

    Selection (``timeline.select``): ``label`` / ``not_label`` regexes on the
    opcode+shape label, ``kernel`` regexes on the Pallas kernel name
    (``registered_kernels``: the name is in the program's registry),
    ``scope`` / ``not_scope`` regexes on the scope path.  Nothing to read --
    no trace, no device op, or a program that names nothing (before PR 25)
    -- is None; no selected op on any chip is None unless ``absent="zero"``."""
    tl = timeline.for_ctx(ctx)
    if tl is None or not tl["named"]:
        return None
    if registered_kernels:
        names = "|".join(timeline.program_names()["kernels"])
        kernel = list(kernel) + [f"^({names})$"]
    shares, found = [], False
    for ops in tl["devices"].values():
        base = trace.busy_ns(timeline.select(ops, label=over) if over else ops)
        if not base:
            continue
        hit = timeline.select(ops, label, not_label, kernel, scope, not_scope, inherit)
        if over:  # the selection has to lie inside the base
            hit = timeline.select(hit, label=over)
        found = found or bool(hit)
        shares.append(100.0 * trace.busy_ns(hit) / base)
    if not shares or (not found and absent != "zero"):
        return None
    return sum(shares) / len(shares)
