"""Client-side arithmetic over the load generator's records (copied in
spirit from the program's ``benchmarks/run.py`` HTTP client: times are
taken by the client, from when a request was due).

A record's "events" are [time, tokens] per streamed chunk, on the same
clock as the window's ``t0``/``t1``.
"""
from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """The nearest-rank q-th percentile (the smallest value with at least
    q% of the sample at or below it)."""
    v = sorted(values)
    if not v:
        raise ValueError("no values")
    return v[max(0, math.ceil(q / 100.0 * len(v)) - 1)]


def due_in_window(ctx) -> list:
    return [r for r in ctx["records"] if ctx["t0"] <= r["due"] < ctx["t1"]]


def ttfts(ctx) -> list:
    """Seconds from each due request's due time to its first token.  A
    request that failed or got no token counts as missing: it takes the
    time it had waited when the run stopped reading, a lower bound."""
    end = ctx["t1"] + ctx["cell"]["tail_s"]
    out = []
    for r in due_in_window(ctx):
        if r["events"] and r["status"] == 200:
            out.append(r["events"][0][0] - r["due"])
        else:
            out.append(end - r["due"])
    return out


def gaps(ctx) -> list:
    """Every inter-token gap received in the window: an event carrying k
    tokens counts as k gaps of its interval over k."""
    out = []
    for r in ctx["records"]:
        ev = r["events"]
        for (ta, _), (tb, k) in zip(ev, ev[1:]):
            if ctx["t0"] <= tb <= ctx["t1"]:
                out.extend([(tb - ta) / k] * k)
    return out


def tokens_in_window(ctx) -> int:
    return sum(k for r in ctx["records"] for t, k in r["events"]
               if ctx["t0"] <= t <= ctx["t1"])


def steps_in_window(ctx, kind=None) -> list:
    return [s for s in ctx["steps"]
            if ctx["t0"] <= s["t0"] < ctx["t1"]
            and (kind is None or s["kind"] == kind)]


def step_mfu(ctx):
    """Model FLOPs the window's stage steps required (the block's
    ``step_flops``: valid tokens only, the LM head on sampled rows
    only), over the traced window times the chip's peak, in percent.
    None without a trace."""
    if "events" not in ctx:
        return None
    m, block, total = ctx["dims"], ctx["block"], 0
    for s in steps_in_window(ctx):
        total += block.step_flops(m, s)
    window = (ctx["trace_hi"] - ctx["trace_lo"]) / 1e9
    if not total or window <= 0:
        return None
    return 100.0 * total / (window * ctx["peaks"]["bf16_flops"])
