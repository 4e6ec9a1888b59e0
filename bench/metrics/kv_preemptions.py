"""Sequences the scheduler preempted for KV blocks during the window."""


def read(ctx):
    return ctx["c1"]["preemptions"] - ctx["c0"]["preemptions"]
