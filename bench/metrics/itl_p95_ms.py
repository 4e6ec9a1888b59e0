"""p95 of every inter-token gap clients received in the window."""
from bench.stats import gaps, percentile


def read(ctx):
    v = gaps(ctx)
    return percentile(v, 95) * 1e3 if v else None
