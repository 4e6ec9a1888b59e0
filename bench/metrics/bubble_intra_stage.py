"""Device idle time spent on the next stage's own step around its
program: inputs and copy-on-write copies to the device (``h2d``),
dispatch (``launch``), the wait for a result whose program has ended
(``device``), or a TSEM prepare it waits for, as a share of the traced
window, in percent (bench.bubbles).  Moves with dispatch and TSEM work;
the logits copy and the hidden state's round trip are not in it."""
from bench.bubbles import idle_share


def read(ctx):
    return idle_share(ctx, "intra_stage")
