"""Whole-step model FLOP utilisation (bench.stats.step_mfu)."""
from bench.stats import step_mfu as read  # noqa: F401
