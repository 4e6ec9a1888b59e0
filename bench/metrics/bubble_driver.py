"""Device idle time while the next stage to run had no input because
the driver was building an iteration (``sipipe/schedule``), as a share
of the traced window, in percent (bench.bubbles)."""
from bench.bubbles import idle_share


def read(ctx):
    return idle_share(ctx, "driver")
