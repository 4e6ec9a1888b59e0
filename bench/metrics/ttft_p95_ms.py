"""p95 of client-side time to first token, from each request's due time,
over all requests due in the window (a failed one counts as missing)."""
from bench.stats import percentile, ttfts


def read(ctx):
    v = ttfts(ctx)
    return percentile(v, 95) * 1e3 if v else None
