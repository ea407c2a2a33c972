"""A percentile of a series the window took on the host's clock."""


def reduce(ctx, series, q, scale=1.0, min_samples=10):
    """Nearest-rank ``q``-th percentile of ``ctx["samples"][series]`` times
    ``scale``; fewer than ``min_samples`` samples read as nothing."""
    values = sorted(ctx["samples"].get(series, ()))
    if len(values) < min_samples:
        return None
    rank = max(0, min(len(values) - 1, -(-len(values) * q // 100) - 1))
    return values[int(rank)] * scale
