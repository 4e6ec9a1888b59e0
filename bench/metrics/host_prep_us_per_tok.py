"""Host seconds the stages' TSEM prepare took during the window, summed
over stages, per output token received (microseconds)."""
from bench.stats import tokens_in_window


def read(ctx):
    n = tokens_in_window(ctx)
    if not n:
        return None
    return (ctx["c1"]["prep_s"] - ctx["c0"]["prep_s"]) / n * 1e6
