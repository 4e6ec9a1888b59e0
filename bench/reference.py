"""Plain float32 reference of the dense decoder the configurations state.

Written from the layer equations, not from the program: pre-RMSNorm
attention with rotary positions on every head dimension (rotate-half
pairing) and grouped key/value heads, then a pre-RMSNorm SwiGLU MLP,
both added to the residual stream; a final RMSNorm and the LM head.
Every matmul runs in float32 under ``default_matmul_precision("highest")``
on the bfloat16 weights of :mod:`bench.weights` cast up.  It imports
nothing of the program.

It runs one sequence at a time, one layer per call, so that only one
layer's weights are cast up at once, and it reads the logits only at
the positions asked for, in blocks of rows.  Sequences are padded to a
multiple of ``PAD`` tokens so that few shapes compile; causal masking
keeps the padding out of every real position.

``quant="fp8"`` is the control: the same pass with every matmul weight
rounded to float8 (e4m3) with one scale per output column, the
precision step below the bfloat16 the configurations state.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.weights import dims

PAD = 512
HEAD_ROWS = 512


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    """x [S, H, hd]; rotate-half pairing of dims i and i + hd/2."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos.astype(jnp.float32)[:, None, None] * freqs
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _w(a, quant):
    """A weight in float32, rounded through float8 for the control."""
    a = a.astype(jnp.float32)
    if quant != "fp8":
        return a
    amax = jnp.max(jnp.abs(a), axis=-2, keepdims=True)
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (a / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


@functools.partial(jax.jit, static_argnames=("m", "quant"))
def _layer(x, w, i, *, m, quant):
    """One decoder layer on x [S, d] (float32)."""
    s = x.shape[0]
    h, kv, hd = m["heads"], m["kv_heads"], m["hd"]
    take = lambda name: jax.lax.dynamic_index_in_dim(  # noqa: E731
        w[name], i, keepdims=False)
    pos = jnp.arange(s)
    a = _rms(x, take("ln_attn").astype(jnp.float32), m["eps"])
    q = (a @ _w(take("wq"), quant)).reshape(s, h, hd)
    k = (a @ _w(take("wk"), quant)).reshape(s, kv, hd)
    v = (a @ _w(take("wv"), quant)).reshape(s, kv, hd)
    q, k = _rope(q, pos, m["rope_theta"]), _rope(k, pos, m["rope_theta"])
    g = h // kv
    q = q.reshape(s, kv, g, hd)
    scores = jnp.einsum("qkgd,skd->kgqs", q, k) / np.sqrt(hd)
    causal = pos[:, None] >= pos[None, :]
    scores = jnp.where(causal, scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("kgqs,skd->qkgd", p, v).reshape(s, h * hd)
    x = x + o @ _w(take("wo"), quant)
    b = _rms(x, take("ln_mlp").astype(jnp.float32), m["eps"])
    f = jax.nn.silu(b @ _w(take("w1"), quant)) * (b @ _w(take("w3"), quant))
    return x + f @ _w(take("w2"), quant)


@functools.partial(jax.jit, static_argnames=("m", "quant"))
def _head(x, rows, lnf, head, *, m, quant):
    """Logits at ``rows`` of x; returns the float32 logits [R, V]."""
    y = _rms(x[rows], lnf.astype(jnp.float32), m["eps"])
    return y @ _w(head, quant)


@jax.jit
def _gap(logits, tokens):
    """Per row: best logit minus the logit of ``tokens``, and the argmax."""
    best = jnp.max(logits, -1)
    mine = jnp.take_along_axis(logits, tokens[:, None], -1)[:, 0]
    return best - mine, jnp.argmax(logits, -1).astype(jnp.int32)


def hidden(w: dict, m: dict, ids, quant: str = "") -> jax.Array:
    """Final hidden states [S_pad, d] of the token ids (padded)."""
    n = len(ids)
    s = -(-n // PAD) * PAD
    toks = np.zeros(s, np.int32)
    toks[:n] = ids
    with jax.default_matmul_precision("highest"):
        x = jnp.take(w["embed"], jnp.asarray(toks), axis=0).astype(
            jnp.float32)
        for i in range(m["layers"]):
            x = _layer(x, w, jnp.int32(i), m=_frozen(m), quant=quant)
    return x


def logits_at(w: dict, m: dict, x, rows, quant: str = ""):
    """Yields (row block, logits [R, V]) at the given positions of x."""
    rows = np.asarray(rows, np.int32)
    with jax.default_matmul_precision("highest"):
        for lo in range(0, len(rows), HEAD_ROWS):
            blk = rows[lo:lo + HEAD_ROWS]
            padded = np.full(HEAD_ROWS, blk[-1], np.int32)
            padded[:len(blk)] = blk
            out = _head(x, jnp.asarray(padded), w["lnf"], w["head"],
                        m=_frozen(m), quant=quant)
            yield lo, len(blk), out


def served_gaps(w: dict, cfg: dict, prompt, served) -> np.ndarray:
    """For each served token: how far its reference logit lies below the
    reference's best logit at that position (0 where it is the best)."""
    m = dims(cfg)
    ids = list(prompt) + list(served)
    rows = np.arange(len(prompt) - 1, len(ids) - 1)
    return _gaps_at(w, m, ids, rows, np.asarray(served, np.int32))


def control_gaps(w: dict, cfg: dict, prompt, served) -> np.ndarray:
    """The reference-side control: at each position of the same prompt
    and served tokens, the token the float8 pass puts first, read as a
    gap against the float32 reference (as :func:`served_gaps` reads the
    program's tokens)."""
    m = dims(cfg)
    ids = list(prompt) + list(served)
    rows = np.arange(len(prompt) - 1, len(ids) - 1)
    xq = hidden(w, m, ids, quant="fp8")
    picks = np.zeros(len(rows), np.int32)
    for lo, n, logits in logits_at(w, m, xq, rows, quant="fp8"):
        picks[lo:lo + n] = np.asarray(jnp.argmax(logits, -1))[:n]
    del xq
    return _gaps_at(w, m, ids, rows, picks)


def control_decode(w: dict, cfg: dict, prompt, n: int) -> list:
    """The control put in the program's place: ``n`` tokens decoded
    greedily by the float8 pass from ``prompt``, one whole pass per
    token (for test sizes)."""
    m = dims(cfg)
    ids = list(prompt)
    for _ in range(n):
        x = hidden(w, m, ids, quant="fp8")
        _, _, logits = next(logits_at(w, m, x, [len(ids) - 1], quant="fp8"))
        ids.append(int(jnp.argmax(logits[0])))
    return ids[len(prompt):]


def _gaps_at(w, m, ids, rows, picks) -> np.ndarray:
    x = hidden(w, m, ids)
    gaps = np.zeros(len(rows), np.float64)
    for lo, n, logits in logits_at(w, m, x, rows):
        tok = np.full(HEAD_ROWS, picks[lo + n - 1], np.int32)
        tok[:n] = picks[lo:lo + n]
        g, _ = _gap(logits, jnp.asarray(tok))
        gaps[lo:lo + n] = np.asarray(g)[:n]
    return gaps


class _frozen(dict):
    """A hashable dims dict, for static jit arguments."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))
