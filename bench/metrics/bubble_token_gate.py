"""Device idle time while the next stage to run had no input because
the driver waited for a slot's sampled tokens (``sipipe/gate``) and the
sampler was neither sampling nor booking them: the tokens were still on
their way through the pipeline.  A share of the traced window, in
percent (bench.bubbles)."""
from bench.bubbles import idle_share


def read(ctx):
    return idle_share(ctx, "token_gate")
