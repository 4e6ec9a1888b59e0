"""Device idle time spent on the hidden state's host round trip (ROADMAP
S3): the next stage to run receiving it (``sipipe/stage<i>/recv``), or a
stage before the last copying its own out and handing it on (``d2h``,
``send``), as a share of the traced window, in percent
(bench.bubbles)."""
from bench.bubbles import idle_share


def read(ctx):
    return idle_share(ctx, "inter_stage")
