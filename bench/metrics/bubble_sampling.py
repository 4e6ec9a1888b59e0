"""Device idle time spent turning logits into tokens (ROADMAP S6): the
last stage copying its logits to the host and handing them on
(``sipipe/stage<i>/d2h``, ``emit``), or the next stage waiting for input
while the driver waits at the gate and the sampler samples
(``sipipe/sample``) or books the tokens (``sipipe/complete``).  A share
of the traced window, in percent (bench.bubbles)."""
from bench.bubbles import idle_share


def read(ctx):
    return idle_share(ctx, "sampling")
