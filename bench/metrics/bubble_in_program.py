"""Device idle time inside a stage's running step program, between its
own ops (the program named ``*_stage<i>`` that runs across the gap), as
a share of the traced window, in percent (bench.bubbles)."""
from bench.bubbles import idle_share


def read(ctx):
    return idle_share(ctx, "in_program")
