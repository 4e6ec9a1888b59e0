"""Decode steps' least HBM traffic (the block's ``decode_bytes``: the
stage's weights plus the K/V the batch's contexts need, each read once)
over their device time, as a share of the chip's HBM bandwidth, in
percent."""
from bench import trace
from bench.stats import steps_in_window


def read(ctx):
    if "events" not in ctx:
        return None
    m, block, nbytes = ctx["dims"], ctx["block"], 0
    for s in steps_in_window(ctx, "decode"):
        nbytes += block.decode_bytes(m, s)
    dev = trace.matching_seconds(ctx["events"], ctx["trace_lo"],
                                 ctx["trace_hi"], "decode_fn",
                                 trace.MODULES_LINE)
    if not nbytes or dev <= 0:
        return None
    return 100.0 * nbytes / dev / ctx["peaks"]["hbm_bytes_per_s"]
