"""Operations and bytes that a step's work requires, counted from shapes.

The counts are of the mathematics, not of any implementation: padding
tokens, recomputation and repeated reads are not counted, so an
implementation's time over these counts is its distance from the chip's
roofline.  ``m`` is :func:`bench.weights.dims` of a configuration.
A multiply-add is two operations.  Weights and K/V are bfloat16.
"""
from __future__ import annotations

BF16 = 2


def layer_matmul_flops(m: dict) -> int:
    """Per token, one decoder layer's projections and MLP."""
    d, h, kv, hd, ff = m["d"], m["heads"], m["kv_heads"], m["hd"], m["ff"]
    qkv = 2 * d * (h + 2 * kv) * hd
    out = 2 * h * hd * d
    mlp = 2 * 3 * d * ff
    return qkv + out + mlp


def attn_flops(m: dict, ctx_sum: int) -> int:
    """One layer's attention for tokens whose contexts (keys attended,
    the token's own included) sum to ``ctx_sum``: QK^T and PV."""
    return 4 * m["heads"] * m["hd"] * ctx_sum


def head_flops(m: dict, rows: int) -> int:
    return 2 * m["d"] * m["vocab"] * rows


def step_flops(m: dict, layers: int, tokens: int, ctx_sum: int,
               head_rows: int) -> int:
    """A stage step over ``layers`` layers: ``tokens`` valid tokens whose
    contexts sum to ``ctx_sum``, and the LM head on the ``head_rows``
    rows that are sampled (0 on a stage without the head)."""
    return (layers * (tokens * layer_matmul_flops(m) + attn_flops(m, ctx_sum))
            + head_flops(m, head_rows))


def layer_weight_bytes(m: dict) -> int:
    d, h, kv, hd, ff = m["d"], m["heads"], m["kv_heads"], m["hd"], m["ff"]
    return BF16 * (d * (h + 2 * kv) * hd + h * hd * d + 3 * d * ff + 2 * d)


def stage_weight_bytes(m: dict, layers: int, has_head: bool) -> int:
    """Weights a stage step reads: its layers, plus the final norm and
    the LM head on the last stage (the embedding is gathered by row)."""
    head = BF16 * (m["d"] * m["vocab"] + m["d"]) if has_head else 0
    return layers * layer_weight_bytes(m) + head


def kv_bytes(m: dict, layers: int, rows_ctx_sum: int) -> int:
    """K and V of ``layers`` layers for rows whose contexts sum to
    ``rows_ctx_sum`` tokens, each read once."""
    return layers * 2 * BF16 * m["kv_heads"] * m["hd"] * rows_ctx_sum


def span_attn_bytes(m: dict, layers: int, tokens: int,
                    rows_ctx_sum: int) -> int:
    """The span kernel's least traffic: each row's K/V once, and the
    queries read and outputs written once per token."""
    qo = 2 * BF16 * tokens * m["heads"] * m["hd"]
    return layers * qo + kv_bytes(m, layers, rows_ctx_sum)


def roofline_s(flops: float, nbytes: float, peak_flops: float,
               peak_bw: float):
    """(least seconds, which bound binds) for the given work."""
    tc, tm = flops / peak_flops, nbytes / peak_bw
    return (tc, "compute") if tc >= tm else (tm, "memory")
