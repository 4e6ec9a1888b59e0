"""Host seconds the engine spent sampling during the window, per output
token received (microseconds)."""
from bench.stats import tokens_in_window


def read(ctx):
    n = tokens_in_window(ctx)
    if not n:
        return None
    return (ctx["c1"]["sample_s"] - ctx["c0"]["sample_s"]) / n * 1e6
