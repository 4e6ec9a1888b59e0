"""p95 of the engine's own queue wait (arrival at the engine to first
schedule, ``queue_s``) over the requests due in the window."""
from bench.stats import due_in_window, percentile


def read(ctx):
    er = ctx["engine_requests"]
    v = []
    for r in due_in_window(ctx):
        m = er.get(int(r["rid"][len("cmpl-"):])) if r["rid"] else None
        if m is not None and m["queue_s"] is not None:
            v.append(m["queue_s"])
    return percentile(v, 95) * 1e3 if v else None
