"""How many host events of the given names the traced window holds."""

from benchmark.harness import timeline


def reduce(ctx, names, requires="domain.step"):
    """The count (0 is a reading: nothing compiled in the window).  Without
    a single ``requires`` span the program is from before PR 25, its compile
    spans cannot be there either, and the count reads as nothing."""
    tl = timeline.for_ctx(ctx)
    if tl is None or not timeline.host_spans(tl, requires):
        return None
    return sum(1 for h in tl["host"] if h[0] in names)
