"""Share of device busy time spent in a class of ops, mean over chips."""

import re

from benchmark.harness import trace


def matching(ops, include, exclude=()):
    inc = [re.compile(p) for p in include]
    exc = [re.compile(p) for p in exclude]
    return [
        o for o in trace.leaf_ops(ops)
        if any(p.search(o[0]) for p in inc) and not any(p.search(o[0]) for p in exc)
    ]


def reduce(ctx, include, exclude=(), absent="none"):
    """100 x (union of the matching leaf ops' intervals) / (union of all
    ops' intervals), per chip, averaged.  Both are unions on one chip's
    clock and the first is a subset of the second, so it cannot pass 100.
    No matching op on any chip: nothing to read (``absent="zero"`` reads 0)."""
    table = ctx["table"]
    if table is None:
        return None
    shares, found = [], False
    for ops in table["devices"].values():
        busy = trace.busy_ns(ops)
        if not busy:
            continue
        hit = matching(ops, include, exclude)
        found = found or bool(hit)
        shares.append(100.0 * trace.busy_ns(hit) / busy)
    if not shares or (not found and absent != "zero"):
        return None
    return sum(shares) / len(shares)
