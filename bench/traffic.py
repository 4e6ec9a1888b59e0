"""The one traffic generator: reads a mix's parameters from
bench/traffic/<mix>.json and makes a run's requests from ``--seed``.

Lengths follow ShareGPT-shaped lognormal distributions (the shape of
``repro.runtime.data.ShareGPTLike``, copied here so that a change to the
program cannot change the benchmark's traffic).  Every seed gets the
same lengths, and in an open loop the same arrival times: they are
drawn once from the mix's fixed ``sizes_seed``.  The run's seed draws
the token ids and the order of the lengths (in a closed loop, within
each wave of clients; in an open loop, over the arrival times), so two
seeds do the same work in another order.

A mix file holds:

  prompt_len, output_len   {"median", "sigma", "min", "max"}: lognormal
                           token counts, clipped to [min, max]
  sizes_seed               the fixed seed of the lengths and gaps
"""
from __future__ import annotations

import numpy as np


def _lognormal(rng, p: dict, n: int) -> np.ndarray:
    x = rng.lognormal(np.log(p["median"]), p["sigma"], size=n)
    return np.clip(np.round(x), p["min"], p["max"]).astype(np.int64)


def sizes(mix: dict, n: int):
    """The fixed (prompt lengths, output lengths) of the first n
    requests of the mix, the same for every seed."""
    rng = np.random.default_rng(mix["sizes_seed"])
    return _lognormal(rng, mix["prompt_len"], n), \
        _lognormal(rng, mix["output_len"], n)


def requests(mix: dict, n: int, seed: int, vocab: int,
             block: int = 1) -> list:
    """n requests [{"prompt": ids, "max_tokens": k}].  Token ids are
    drawn from [2, vocab) by the seed; with ``block`` > 1 the seed also
    orders each run of ``block`` requests, so a closed loop of ``block``
    clients starts every wave on the same lengths."""
    plen, olen = sizes(mix, n)
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 0x7A11])
    order = np.concatenate([lo + rng.permutation(min(block, n - lo))
                            for lo in range(0, n, block)])
    out = []
    for i in order:
        prompt = rng.integers(2, vocab, size=int(plen[i]))
        out.append({"prompt": [int(t) for t in prompt],
                    "max_tokens": int(olen[i])})
    return out


def open_loop(mix: dict, rate: float, seconds: float, seed: int,
              vocab: int) -> list:
    """Poisson arrivals at ``rate`` requests/s over a window of
    ``seconds``: round(rate x seconds) requests whose exponential gaps
    are drawn once and scaled to fill the window exactly; the seed
    orders the lengths over them.  Each request gains "due", seconds
    after the window opens."""
    n = max(1, int(round(rate * seconds)))
    gaps = np.random.default_rng([mix["sizes_seed"], 0xA881]).exponential(
        1.0, size=n + 1)
    due = np.cumsum(gaps * (seconds / gaps.sum()))[:n]
    reqs = requests(mix, n, seed, vocab, block=n)
    for r, t in zip(reqs, due):
        r["due"] = float(t)
    return reqs
