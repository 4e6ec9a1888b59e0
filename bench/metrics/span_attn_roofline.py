"""The paged span-attention kernel's roofline share: the least time of
the spans' attention (the block's ``span_attn_work``: FLOPs and bytes
from shapes, whatever implements them) over the kernel's device time,
in percent."""
from bench import flops, trace
from bench.stats import steps_in_window

# the serving path's only Pallas kernel, run by the chunk steps (decode
# attends a gathered view in jnp): a custom call to "tpu_custom_call"
KERNEL = "custom-call:tpu_custom_call"


def read(ctx):
    if "events" not in ctx:
        return None
    m, block, p = ctx["dims"], ctx["block"], ctx["peaks"]
    least = 0.0
    for s in steps_in_window(ctx, "chunk"):
        f, b = block.span_attn_work(m, s)
        least += flops.roofline_s(f, b, p["bf16_flops"],
                                  p["hbm_bytes_per_s"])[0]
    dev = trace.matching_seconds(ctx["events"], ctx["trace_lo"],
                                 ctx["trace_hi"], KERNEL)
    if not least or dev <= 0:
        return None
    return 100.0 * least / dev
