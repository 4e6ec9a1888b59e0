"""Output tokens clients received inside the window, per second."""
from bench.stats import tokens_in_window


def read(ctx):
    return tokens_in_window(ctx) / ctx["seconds"]
