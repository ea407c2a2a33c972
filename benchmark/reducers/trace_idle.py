"""Share of the traced window in which no op ran on the device."""

from benchmark.harness import trace


def reduce(ctx):
    """100 x (1 - busy / window), busy the union of op intervals per chip
    averaged over chips, window first op start to last op end over all
    chips.  The same two numbers go out as ``device.busy_s`` / ``window_s``."""
    if ctx["table"] is None:
        return None
    busy_s, window_s = trace.busy_and_window_s(ctx["table"])
    return 100.0 * (1.0 - busy_s / window_s)
