"""A percentile of the durations of one of the program's own host spans
inside the traced window (``harness/timeline.py``)."""

from benchmark.harness import timeline


def reduce(ctx, span, q, scale=1.0, min_samples=10):
    """Nearest-rank ``q``-th percentile of the span's durations in seconds
    times ``scale`` (the rule of ``host_percentile.py``); fewer than
    ``min_samples`` spans -- none on a program from before PR 25 -- read as
    nothing.  Also prints the run's ``timeline`` info line."""
    tl = timeline.for_ctx(ctx)
    if tl is None:
        return None
    timeline.say_summary(tl)
    values = sorted(h[2] / 1e9 for h in timeline.host_spans(tl, span))
    if len(values) < min_samples:
        return None
    rank = max(0, min(len(values) - 1, -(-len(values) * q // 100) - 1))
    return values[int(rank)] * scale
