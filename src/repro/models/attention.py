"""Attention variants as memory-safe pure-jnp (lax.scan) implementations.

These are the *reference/distribution* paths: the compiled HLO never
materializes an [S, S] score matrix, so 32k prefill fits device memory and
the dry-run ``memory_analysis`` is realistic.  The Pallas kernels in
:mod:`repro.kernels` are the TPU-optimized equivalents of the same math
(validated against these in interpret mode).

Layout conventions:
  q            [B, Sq, Hq, hd]     (Hq may be tp-padded)
  k, v         [B, Skv, Hkv, hd]   (GQA: Hq % Hkv == 0)
  decode q     [B, Hq, hd]         (single new token)
  caches       [B, S_max, Hkv, hd] (full) or [B, W, Hkv, hd] (rolling)
Outputs are [B, Sq, Hq*hd] / [B, Hq*hd].
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import span_attention as ksa

NEG_INF = -1e30


def _group(q: jax.Array, n_kv: int) -> jax.Array:
    """[B,S,Hq,hd] -> [B,S,Kv,G,hd] grouping query heads over kv heads."""
    b, s, h, d = q.shape
    return q.reshape(b, s, n_kv, h // n_kv, d)


def chunked_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    kv_block: int = 512,
    q_positions: Optional[jax.Array] = None,
    triangular: bool = False,
) -> jax.Array:
    """Flash-style attention: lax.scan over kv blocks with running softmax.

    ``triangular=True`` skips fully-masked kv blocks for causal attention via
    a dynamic-bound fori_loop per q block (~2x compute saving at long S);
    kept off for the paper-faithful baseline and enabled during the perf
    hillclimb (see EXPERIMENTS.md §Perf).
    """
    b, sq, hq, hd = q.shape
    skv, n_kv = k.shape[1], k.shape[2]
    g = hq // n_kv
    kv_block = min(kv_block, skv)
    while skv % kv_block:
        kv_block //= 2
    nb = skv // kv_block
    qg = _group(q, n_kv)  # [B,Sq,Kv,G,hd]
    scale = hd ** -0.5
    qpos = q_positions if q_positions is not None else jnp.arange(sq)

    if triangular and causal and nb > 1:
        return _triangular_attention(qg, k, v, window=window, kv_block=kv_block,
                                     q_positions=qpos, scale=scale)

    kb = k.reshape(b, nb, kv_block, n_kv, hd).swapaxes(0, 1)
    vb = v.reshape(b, nb, kv_block, n_kv, hd).swapaxes(0, 1)

    def body(carry, inp):
        m, l, acc = carry
        kblk, vblk, i = inp
        kpos = i * kv_block + jnp.arange(kv_block)
        # scores [B, Kv, G, Sq, blk]
        s = jnp.einsum("bsgqd,btgd->bgqst", qg, kblk).astype(jnp.float32) * scale
        mask = jnp.ones((sq, kv_block), bool)
        if causal:
            mask &= qpos[:, None] >= kpos[None, :]
        if window:
            mask &= kpos[None, :] > qpos[:, None] - window
        s = jnp.where(mask[None, None, None], s, NEG_INF)
        mn = jnp.maximum(m, s.max(-1))
        p = jnp.exp(s - mn[..., None])
        corr = jnp.exp(m - mn)
        l = l * corr + p.sum(-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bgqst,btgd->bgqsd", p.astype(q.dtype), vblk
        ).astype(jnp.float32)
        return (mn, l, acc), None

    m0 = jnp.full((b, n_kv, g, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, n_kv, g, sq), jnp.float32)
    a0 = jnp.zeros((b, n_kv, g, sq, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), (kb, vb, jnp.arange(nb)))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.astype(q.dtype).transpose(0, 3, 1, 2, 4).reshape(b, sq, hq * hd)


def _triangular_attention(qg, k, v, *, window, kv_block, q_positions, scale):
    """Causal attention skipping future kv blocks (dynamic-bound inner loop)."""
    b, sq, n_kv, g, hd = qg.shape
    skv = k.shape[1]
    q_block = kv_block
    while sq % q_block:
        q_block //= 2
    nq = sq // q_block
    dtype = qg.dtype

    def q_block_fn(qi, qblk, qpos_blk):
        # attend kv blocks [lo, hi): lo from the sliding window, hi from causality
        hi = jnp.minimum((qpos_blk.max() // kv_block) + 1, skv // kv_block)
        lo = jnp.maximum((qpos_blk.min() - (window - 1)) // kv_block, 0) if window else 0

        def body(j, carry):
            m, l, acc = carry
            kblk = jax.lax.dynamic_slice_in_dim(k, j * kv_block, kv_block, 1)
            vblk = jax.lax.dynamic_slice_in_dim(v, j * kv_block, kv_block, 1)
            kpos = j * kv_block + jnp.arange(kv_block)
            s = jnp.einsum("bsgqd,btgd->bgqst", qblk, kblk).astype(jnp.float32) * scale
            mask = qpos_blk[:, None] >= kpos[None, :]
            if window:
                mask &= kpos[None, :] > qpos_blk[:, None] - window
            s = jnp.where(mask[None, None, None], s, NEG_INF)
            mn = jnp.maximum(m, s.max(-1))
            p = jnp.exp(s - mn[..., None])
            corr = jnp.exp(m - mn)
            l = l * corr + p.sum(-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bgqst,btgd->bgqsd", p.astype(dtype), vblk
            ).astype(jnp.float32)
            return (mn, l, acc)

        m0 = jnp.full((b, n_kv, g, q_block), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, n_kv, g, q_block), jnp.float32)
        a0 = jnp.zeros((b, n_kv, g, q_block, hd), jnp.float32)
        m, l, acc = jax.lax.fori_loop(lo, hi, body, (m0, l0, a0))
        return (acc / jnp.maximum(l[..., None], 1e-30)).astype(dtype)

    qb = qg.reshape(b, nq, q_block, n_kv, g, hd).swapaxes(0, 1)
    qpos_b = q_positions.reshape(nq, q_block)

    def scan_body(_, inp):
        qi, qblk, qpos_blk = inp
        return None, q_block_fn(qi, qblk, qpos_blk)

    _, outs = jax.lax.scan(scan_body, None, (jnp.arange(nq), qb, qpos_b))
    # outs [nq, B, Kv, G, q_block, hd] -> [B, Sq, H*hd]
    out = outs.transpose(1, 0, 4, 2, 3, 5).reshape(b, sq, n_kv * g * hd)
    return out


def local_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    window: int,
    q_block: int = 512,
) -> jax.Array:
    """Banded causal attention: each q block attends a [window + q_block]
    kv slice -> compute O(S * window) instead of O(S^2)."""
    b, sq, hq, hd = q.shape
    n_kv = k.shape[2]
    g = hq // n_kv
    q_block = min(q_block, sq)
    while sq % q_block:
        q_block //= 2
    nq = sq // q_block
    span = window + q_block
    scale = hd ** -0.5
    qg = _group(q, n_kv).reshape(b, nq, q_block, n_kv, g, hd).swapaxes(0, 1)
    # pad kv on the left so every slice is in-bounds
    kp = jnp.pad(k, ((0, 0), (window, 0), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (window, 0), (0, 0), (0, 0)))

    def body(_, inp):
        i, qblk = inp
        start = i * q_block  # in padded coords: real kv [start-window, start+q_block)
        kblk = jax.lax.dynamic_slice_in_dim(kp, start, span, 1)
        vblk = jax.lax.dynamic_slice_in_dim(vp, start, span, 1)
        qpos = start + jnp.arange(q_block)
        kpos = start - window + jnp.arange(span)
        # §Perf A2: keep the [*, qb, span] score array in bf16 — it is the
        # dominant HBM temporary of windowed prefill; softmax stats in f32
        s = jnp.einsum("bsgqd,btgd->bgqst", qblk, kblk) * jnp.asarray(
            scale, q.dtype)
        mask = (qpos[:, None] >= kpos[None, :]) & (kpos[None, :] > qpos[:, None] - window) & (
            kpos[None, :] >= 0
        )
        s = jnp.where(mask[None, None, None], s, jnp.asarray(-3e38, q.dtype)
                      if q.dtype == jnp.bfloat16 else NEG_INF)
        # §Perf A1: unnormalized probabilities, stored bf16; the softmax
        # division moves to the [*, qb, hd] output (span/hd x less traffic)
        m = s.max(axis=-1, keepdims=True).astype(jnp.float32)
        p = jnp.exp(s.astype(jnp.float32) - m).astype(q.dtype)
        l = p.astype(jnp.float32).sum(axis=-1)            # [*, qb]
        o = jnp.einsum("bgqst,btgd->bgqsd", p, vblk)
        o = (o.astype(jnp.float32) / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)
        return None, o

    _, outs = jax.lax.scan(body, None, (jnp.arange(nq), qg))
    return outs.transpose(1, 0, 4, 2, 3, 5).reshape(b, sq, hq * hd)


def decode_attention(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    positions: jax.Array,
    *,
    rolling_window: int = 0,
) -> jax.Array:
    """Single-token attention over a (possibly sequence-sharded) KV cache.

    q [B, Hq, hd]; caches [B, S, Kv, hd]; positions [B] = index of the new
    token (cache already contains it).  For rolling caches (S == window)
    validity is age-based.
    """
    b, hq, hd = q.shape
    s, n_kv = k_cache.shape[1], k_cache.shape[2]
    g = hq // n_kv
    qg = q.reshape(b, n_kv, g, hd)
    scale = hd ** -0.5
    scores = jnp.einsum("bgqd,bsgd->bgqs", qg, k_cache).astype(jnp.float32) * scale
    if positions is not None:
        idx = jnp.arange(s)
        if rolling_window:
            valid = idx[None, :] < jnp.minimum(positions + 1, rolling_window)[:, None]
        else:
            valid = idx[None, :] <= positions[:, None]
        scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bgqs,bsgd->bgqd", p.astype(q.dtype), v_cache)
    return out.reshape(b, hq * hd)


def span_attention(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    positions: jax.Array,
) -> jax.Array:
    """Multi-token attention over a KV cache for chunked prefill.

    Generalizes :func:`decode_attention` to a span of C new tokens per
    sequence with per-sequence positions: q [B, C, Hq, hd]; caches
    [B, S, Kv, hd] (already containing the span's K/V); positions [B, C]
    absolute position of each span token.  Causal validity is positional:
    cache entry s is visible to span token (b, c) iff s <= positions[b, c]
    — entries beyond the filled region are masked out, so chunk i attends
    chunks 0..i plus itself and nothing else.  Output [B, C, Hq*hd].
    """
    b, c, hq, hd = q.shape
    s, n_kv = k_cache.shape[1], k_cache.shape[2]
    g = hq // n_kv
    qg = q.reshape(b, c, n_kv, g, hd)
    scale = hd ** -0.5
    scores = jnp.einsum("bcgqd,bsgd->bgqcs", qg, k_cache).astype(jnp.float32) * scale
    valid = jnp.arange(s)[None, None, :] <= positions[:, :, None]   # [B, C, S]
    scores = jnp.where(valid[:, None, None], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bgqcs,bsgd->bcgqd", p.astype(q.dtype), v_cache)
    return out.reshape(b, c, hq * hd)


def packed_span_attention(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    positions: jax.Array,
    seq_idx: jax.Array,
    *,
    window: int = 0,
    kv_block: int = 512,
) -> jax.Array:
    """Ragged multi-token attention over a KV cache (packed chunk layout).

    The packed layout replaces the padded [B, C] span matrices: the batch's
    valid span tokens are concatenated into flat [T] vectors, so a mixed
    iteration does ``sum(len_i x T_i)`` attention work instead of
    ``B x C x S``.  q [T, Hq, hd]; caches [B, S, Kv, hd] (already containing
    the span's K/V); positions [T] absolute position of each packed token;
    seq_idx [T] batch row of each token.  Cache entry s of row seq_idx[t]
    is visible to token t iff ``s <= positions[t]`` (and, with ``window``,
    ``s > positions[t] - window`` — full-length cache semantics).  The scan
    streams the cache in kv blocks with a running softmax, so no [T, S]
    score tensor is ever materialized.  Output [T, Hq*hd].
    """
    t, hq, hd = q.shape
    s, n_kv = k_cache.shape[1], k_cache.shape[2]
    g = hq // n_kv
    kv_block = min(kv_block, s)
    while s % kv_block:
        kv_block //= 2
    nb = s // kv_block
    qg = q.reshape(t, n_kv, g, hd)
    scale = hd ** -0.5
    kb = k_cache.reshape(-1, nb, kv_block, n_kv, hd).swapaxes(0, 1)
    vb = v_cache.reshape(-1, nb, kv_block, n_kv, hd).swapaxes(0, 1)

    def body(carry, inp):
        m, l, acc = carry
        kblk, vblk, i = inp
        kt = kblk[seq_idx]                       # [T, kb, Kv, hd]
        vt = vblk[seq_idx]
        kpos = i * kv_block + jnp.arange(kv_block)
        sc = jnp.einsum("tngd,tknd->tngk", qg, kt).astype(jnp.float32) * scale
        mask = kpos[None, :] <= positions[:, None]
        if window:
            mask &= kpos[None, :] > positions[:, None] - window
        sc = jnp.where(mask[:, None, None, :], sc, NEG_INF)
        mn = jnp.maximum(m, sc.max(-1))
        p = jnp.exp(sc - mn[..., None])
        corr = jnp.exp(m - mn)
        l = l * corr + p.sum(-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "tngk,tknd->tngd", p.astype(q.dtype), vt).astype(jnp.float32)
        return (mn, l, acc), None

    m0 = jnp.full((t, n_kv, g), NEG_INF, jnp.float32)
    l0 = jnp.zeros((t, n_kv, g), jnp.float32)
    a0 = jnp.zeros((t, n_kv, g, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), (kb, vb, jnp.arange(nb)))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.astype(q.dtype).reshape(t, hq * hd)


def packed_span_attention_rolling(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    k_span: jax.Array,
    v_span: jax.Array,
    positions: jax.Array,
    seq_idx: jax.Array,
    offsets: jax.Array,
    n_valid: jax.Array,
    *,
    window: int,
    kv_block: int = 512,
) -> jax.Array:
    """Packed span attention for sliding-window models with *rolling* caches.

    A rolling cache (slot = pos %% W) cannot use scatter-then-attend: a
    chunk's writes would overwrite window entries its earlier tokens still
    need.  So the span attends two sources under one running softmax:

      1. the old cache, holding each row's tokens [off-W, off) at slots
         pos %% W — slot s stores position ``off-1-((off-1-s) mod W)``,
         which is reconstructed per query to mask age and window;
      2. the span's own fresh K/V [T, Kv, hd] with an intra-span causal +
         window + same-row mask (``u < n_valid`` drops bucket padding,
         whose duplicated entries would otherwise be double-counted).

    offsets [T] = each token's row span start (tokens already in cache).
    The caller scatters the span K/V into the cache *after* this returns.
    """
    t, hq, hd = q.shape
    w_slots, n_kv = k_cache.shape[1], k_cache.shape[2]
    g = hq // n_kv
    kv_block = min(kv_block, w_slots)
    while w_slots % kv_block:
        kv_block //= 2
    nb = w_slots // kv_block
    qg = q.reshape(t, n_kv, g, hd)
    scale = hd ** -0.5
    kb = k_cache.reshape(-1, nb, kv_block, n_kv, hd).swapaxes(0, 1)
    vb = v_cache.reshape(-1, nb, kv_block, n_kv, hd).swapaxes(0, 1)

    def cache_body(carry, inp):
        m, l, acc = carry
        kblk, vblk, i = inp
        kt = kblk[seq_idx]
        vt = vblk[seq_idx]
        slot = i * kv_block + jnp.arange(kv_block)
        # position stored in slot s of a row whose cache holds [0, off)
        stored = offsets[:, None] - 1 - (
            (offsets[:, None] - 1 - slot[None, :]) % w_slots)
        mask = (offsets[:, None] >= 1) & (stored >= 0) & (
            stored > positions[:, None] - window)
        sc = jnp.einsum("tngd,tknd->tngk", qg, kt).astype(jnp.float32) * scale
        sc = jnp.where(mask[:, None, None, :], sc, NEG_INF)
        mn = jnp.maximum(m, sc.max(-1))
        p = jnp.exp(sc - mn[..., None])
        corr = jnp.exp(m - mn)
        l = l * corr + p.sum(-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "tngk,tknd->tngd", p.astype(q.dtype), vt).astype(jnp.float32)
        return (mn, l, acc), None

    m0 = jnp.full((t, n_kv, g), NEG_INF, jnp.float32)
    l0 = jnp.zeros((t, n_kv, g), jnp.float32)
    a0 = jnp.zeros((t, n_kv, g, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(cache_body, (m0, l0, a0),
                                  (kb, vb, jnp.arange(nb)))

    # intra-span source: fresh K/V of the packed chunk itself
    sc = jnp.einsum("tngd,und->tngu", qg, k_span).astype(jnp.float32) * scale
    upos, useq = positions, seq_idx
    mask = (useq[None, :] == seq_idx[:, None]) \
        & (upos[None, :] <= positions[:, None]) \
        & (upos[None, :] > positions[:, None] - window) \
        & (jnp.arange(t)[None, :] < n_valid)
    sc = jnp.where(mask[:, None, None, :], sc, NEG_INF)
    mn = jnp.maximum(m, sc.max(-1))
    p = jnp.exp(sc - mn[..., None])
    corr = jnp.exp(m - mn)
    l = l * corr + p.sum(-1)
    acc = acc * corr[..., None] + jnp.einsum(
        "tngu,und->tngd", p.astype(q.dtype), v_span).astype(jnp.float32)
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.astype(q.dtype).reshape(t, hq * hd)


def cross_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    kv_block: int = 512,
) -> jax.Array:
    """Non-causal attention to a fixed memory (vision patches / encoder out)."""
    return chunked_attention(q, k, v, causal=False, kv_block=kv_block)


# ---------------------------------------------------------------------------
# Paged KV cache (block tables) — oracles for the paged Pallas kernels
# ---------------------------------------------------------------------------

def gather_paged_cache(cache: jax.Array, block_tables: jax.Array,
                       layer: Optional[jax.Array] = None) -> jax.Array:
    """[n_blocks, bs, ...] physical cache + [B, nb] block table ->
    [B, nb * bs, ...] per-sequence contiguous view: logical slot p of row
    i is ``cache[block_tables[i, p // bs], p %% bs]``.  With ``layer``,
    ``cache`` is a stacked [n, n_blocks, bs, ...] pool and the layer index
    rides in the same gather, so no layer slice is made.  Padded table
    entries gather arbitrary blocks — always position-masked downstream."""
    b, nb = block_tables.shape
    g = cache[block_tables] if layer is None else cache[layer, block_tables]
    return g.reshape(b, nb * g.shape[2], *g.shape[3:])   # g [B, nb, bs, ...]


def paged_span_attention(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    block_tables: jax.Array,
    positions: jax.Array,
    seq_idx: jax.Array,
    *,
    window: int = 0,
    kv_block: int = 512,
) -> jax.Array:
    """:func:`packed_span_attention` over a block-paged physical cache.

    q [T, Hq, hd]; k_cache/v_cache [n_blocks, bs, Kv, hd];
    block_tables [B, nb]; positions/seq_idx [T].  Reference semantics for
    the paged Pallas kernel (``repro.kernels.span_attention.
    paged_span_attention``): gather each row's table into the contiguous
    view, then attend — on TPU the kernel performs the same gather
    per-block in VMEM via scalar-prefetched BlockSpecs."""
    k = gather_paged_cache(k_cache, block_tables)
    v = gather_paged_cache(v_cache, block_tables)
    return packed_span_attention(q, k, v, positions, seq_idx,
                                 window=window, kv_block=kv_block)


def paged_span_attention_quant(
    q: jax.Array,
    k8: jax.Array, ks: jax.Array,
    v8: jax.Array, vs: jax.Array,
    block_tables: jax.Array,
    positions: jax.Array,
    seq_idx: jax.Array,
    *,
    kv_block: int = 512,
) -> jax.Array:
    """:func:`packed_span_attention_quant` over a block-paged int8 cache.
    k8/v8 [n_blocks, bs, Kv, hd] int8; ks/vs [n_blocks, bs, Kv]."""
    return packed_span_attention_quant(
        q,
        gather_paged_cache(k8, block_tables),
        gather_paged_cache(ks, block_tables),
        gather_paged_cache(v8, block_tables),
        gather_paged_cache(vs, block_tables),
        positions, seq_idx, kv_block=kv_block)


def paged_span_attention_rolling(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    k_span: jax.Array,
    v_span: jax.Array,
    block_tables: jax.Array,
    positions: jax.Array,
    seq_idx: jax.Array,
    offsets: jax.Array,
    n_valid: jax.Array,
    *,
    window: int,
    kv_block: int = 512,
) -> jax.Array:
    """:func:`packed_span_attention_rolling` over a block-paged rolling
    cache.  The gathered view has ``nb * bs`` slots; the rolling stored-
    position reconstruction runs against that view width, which matches
    the physical layout whenever either no row has wrapped (every offset
    fits the view) or the tables cover the full window (view == W)."""
    k = gather_paged_cache(k_cache, block_tables)
    v = gather_paged_cache(v_cache, block_tables)
    return packed_span_attention_rolling(
        q, k, v, k_span, v_span, positions, seq_idx, offsets, n_valid,
        window=window, kv_block=kv_block)


def paged_span_attention_rolling_quant(
    q: jax.Array,
    k8: jax.Array, ks: jax.Array,
    v8: jax.Array, vs: jax.Array,
    k_span: jax.Array,
    v_span: jax.Array,
    block_tables: jax.Array,
    positions: jax.Array,
    seq_idx: jax.Array,
    offsets: jax.Array,
    n_valid: jax.Array,
    *,
    window: int,
    kv_block: int = 512,
) -> jax.Array:
    """:func:`packed_span_attention_rolling_quant` over a block-paged int8
    rolling cache."""
    return packed_span_attention_rolling_quant(
        q,
        gather_paged_cache(k8, block_tables),
        gather_paged_cache(ks, block_tables),
        gather_paged_cache(v8, block_tables),
        gather_paged_cache(vs, block_tables),
        k_span, v_span, positions, seq_idx, offsets, n_valid,
        window=window, kv_block=kv_block)


# ---------------------------------------------------------------------------
# int8 KV cache (§Perf C1 — beyond-paper)
# ---------------------------------------------------------------------------

def quantize_kv(x: jax.Array, axis: int = -1):
    """Symmetric per-vector int8 quantization.  Returns (int8, bf16 scale
    with ``axis`` reduced)."""
    xf = x.astype(jnp.float32)
    scale = jnp.max(jnp.abs(xf), axis=axis) / 127.0 + 1e-8
    q = jnp.clip(jnp.round(xf / jnp.expand_dims(scale, axis)), -127, 127)
    return q.astype(jnp.int8), scale.astype(jnp.bfloat16)


def decode_attention_quant(
    q: jax.Array,
    k8: jax.Array, ks: jax.Array,
    v8: jax.Array, vs: jax.Array,
    positions: Optional[jax.Array],
    *,
    rolling_window: int = 0,
) -> jax.Array:
    """Single-token attention over an int8 KV cache.

    Both contractions run as native s8 x s8 -> s32 MXU dots: the cache is
    never dequantized to a materialized bf16 array (halving decode HBM
    traffic).  q and the probability rows are quantized on the fly; the
    per-position V scales fold into the probabilities before the AV dot.

    q [B,H,hd] bf16; k8/v8 [B,S,Kv,hd] int8; ks/vs [B,S,Kv] bf16.
    """
    b, hq, hd = q.shape
    s, n_kv = k8.shape[1], k8.shape[2]
    g = hq // n_kv
    qg = q.reshape(b, n_kv, g, hd)
    q8, qs = quantize_kv(qg)                          # [B,Kv,G,hd], [B,Kv,G]
    s32 = jnp.einsum("bgqd,bsgd->bgqs", q8, k8,
                     preferred_element_type=jnp.int32)
    ks_t = ks.transpose(0, 2, 1)[:, :, None, :].astype(jnp.float32)
    scores = s32.astype(jnp.float32) * qs[..., None].astype(jnp.float32) \
        * ks_t * (hd ** -0.5)
    if positions is not None:
        idx = jnp.arange(s)
        if rolling_window:
            valid = idx[None, :] < jnp.minimum(positions + 1, rolling_window)[:, None]
        else:
            valid = idx[None, :] <= positions[:, None]
        scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)               # [B,Kv,G,S] fp32
    pv = p * vs.transpose(0, 2, 1)[:, :, None, :].astype(jnp.float32)
    p8, ps = quantize_kv(pv)                          # scale per [B,Kv,G]
    o32 = jnp.einsum("bgqs,bsgd->bgqd", p8, v8,
                     preferred_element_type=jnp.int32)
    out = o32.astype(jnp.float32) * ps[..., None].astype(jnp.float32)
    return out.astype(q.dtype).reshape(b, hq * hd)


def packed_span_attention_quant(
    q: jax.Array,
    k8: jax.Array, ks: jax.Array,
    v8: jax.Array, vs: jax.Array,
    positions: jax.Array,
    seq_idx: jax.Array,
    *,
    kv_block: int = 512,
) -> jax.Array:
    """Packed ragged span attention over an int8 KV cache.

    Generalizes :func:`decode_attention_quant` to the packed chunk layout:
    both contractions are s8 x s8 -> s32 dots with the per-position K/V
    scales folded outside them (q and the probability rows are quantized
    on the fly, per kv block).  q [T,Hq,hd]; k8/v8 [B,S,Kv,hd] int8;
    ks/vs [B,S,Kv] bf16; positions/seq_idx [T].  Output [T, Hq*hd].
    """
    t, hq, hd = q.shape
    s, n_kv = k8.shape[1], k8.shape[2]
    g = hq // n_kv
    kv_block = min(kv_block, s)
    while s % kv_block:
        kv_block //= 2
    nb = s // kv_block
    qg = q.reshape(t, n_kv, g, hd)
    q8, qs = quantize_kv(qg)                     # [T,Kv,G,hd], [T,Kv,G]
    scale = hd ** -0.5
    kb = k8.reshape(-1, nb, kv_block, n_kv, hd).swapaxes(0, 1)
    vb = v8.reshape(-1, nb, kv_block, n_kv, hd).swapaxes(0, 1)
    ksb = ks.reshape(-1, nb, kv_block, n_kv).swapaxes(0, 1)
    vsb = vs.reshape(-1, nb, kv_block, n_kv).swapaxes(0, 1)

    def body(carry, inp):
        m, l, acc = carry
        kblk, vblk, ksblk, vsblk, i = inp
        kt, vt = kblk[seq_idx], vblk[seq_idx]    # [T, kb, Kv, hd] int8
        kst = ksblk[seq_idx].transpose(0, 2, 1)[:, :, None, :]  # [T,Kv,1,kb]
        vst = vsblk[seq_idx].transpose(0, 2, 1)[:, :, None, :]
        s32 = jnp.einsum("tngd,tknd->tngk", q8, kt,
                         preferred_element_type=jnp.int32)
        sc = s32.astype(jnp.float32) * qs[..., None].astype(jnp.float32) \
            * kst.astype(jnp.float32) * scale
        kpos = i * kv_block + jnp.arange(kv_block)
        mask = kpos[None, :] <= positions[:, None]
        sc = jnp.where(mask[:, None, None, :], sc, NEG_INF)
        mn = jnp.maximum(m, sc.max(-1))
        p = jnp.exp(sc - mn[..., None])
        corr = jnp.exp(m - mn)
        l = l * corr + p.sum(-1)
        pv = p * vst.astype(jnp.float32)         # fold V scales, then requant
        p8, ps = quantize_kv(pv)
        o32 = jnp.einsum("tngk,tknd->tngd", p8, vt,
                         preferred_element_type=jnp.int32)
        acc = acc * corr[..., None] + \
            o32.astype(jnp.float32) * ps[..., None].astype(jnp.float32)
        return (mn, l, acc), None

    m0 = jnp.full((t, n_kv, g), NEG_INF, jnp.float32)
    l0 = jnp.zeros((t, n_kv, g), jnp.float32)
    a0 = jnp.zeros((t, n_kv, g, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0),
                                  (kb, vb, ksb, vsb, jnp.arange(nb)))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.astype(q.dtype).reshape(t, hq * hd)


def packed_span_attention_rolling_quant(
    q: jax.Array,
    k8: jax.Array, ks: jax.Array,
    v8: jax.Array, vs: jax.Array,
    k_span: jax.Array,
    v_span: jax.Array,
    positions: jax.Array,
    seq_idx: jax.Array,
    offsets: jax.Array,
    n_valid: jax.Array,
    *,
    window: int,
    kv_block: int = 512,
) -> jax.Array:
    """Rolling-cache windowed span attention with an int8 cache.

    The old-cache source runs as s8 x s8 -> s32 dots with folded scales
    (as :func:`packed_span_attention_quant`); the span's own fresh K/V is
    still bf16, so the intra-span source uses full-precision dots — both
    feed one running softmax, mirroring the fp rolling variant.
    """
    t, hq, hd = q.shape
    w_slots, n_kv = k8.shape[1], k8.shape[2]
    g = hq // n_kv
    kv_block = min(kv_block, w_slots)
    while w_slots % kv_block:
        kv_block //= 2
    nb = w_slots // kv_block
    qg = q.reshape(t, n_kv, g, hd)
    q8, qs = quantize_kv(qg)
    scale = hd ** -0.5
    kb = k8.reshape(-1, nb, kv_block, n_kv, hd).swapaxes(0, 1)
    vb = v8.reshape(-1, nb, kv_block, n_kv, hd).swapaxes(0, 1)
    ksb = ks.reshape(-1, nb, kv_block, n_kv).swapaxes(0, 1)
    vsb = vs.reshape(-1, nb, kv_block, n_kv).swapaxes(0, 1)

    def cache_body(carry, inp):
        m, l, acc = carry
        kblk, vblk, ksblk, vsblk, i = inp
        kt, vt = kblk[seq_idx], vblk[seq_idx]
        kst = ksblk[seq_idx].transpose(0, 2, 1)[:, :, None, :]
        vst = vsblk[seq_idx].transpose(0, 2, 1)[:, :, None, :]
        slot = i * kv_block + jnp.arange(kv_block)
        stored = offsets[:, None] - 1 - (
            (offsets[:, None] - 1 - slot[None, :]) % w_slots)
        mask = (offsets[:, None] >= 1) & (stored >= 0) & (
            stored > positions[:, None] - window)
        s32 = jnp.einsum("tngd,tknd->tngk", q8, kt,
                         preferred_element_type=jnp.int32)
        sc = s32.astype(jnp.float32) * qs[..., None].astype(jnp.float32) \
            * kst.astype(jnp.float32) * scale
        sc = jnp.where(mask[:, None, None, :], sc, NEG_INF)
        mn = jnp.maximum(m, sc.max(-1))
        p = jnp.exp(sc - mn[..., None])
        corr = jnp.exp(m - mn)
        l = l * corr + p.sum(-1)
        pv = p * vst.astype(jnp.float32)
        p8, ps = quantize_kv(pv)
        o32 = jnp.einsum("tngk,tknd->tngd", p8, vt,
                         preferred_element_type=jnp.int32)
        acc = acc * corr[..., None] + \
            o32.astype(jnp.float32) * ps[..., None].astype(jnp.float32)
        return (mn, l, acc), None

    m0 = jnp.full((t, n_kv, g), NEG_INF, jnp.float32)
    l0 = jnp.zeros((t, n_kv, g), jnp.float32)
    a0 = jnp.zeros((t, n_kv, g, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(cache_body, (m0, l0, a0),
                                  (kb, vb, ksb, vsb, jnp.arange(nb)))

    sc = jnp.einsum("tngd,und->tngu", qg, k_span).astype(jnp.float32) * scale
    mask = (seq_idx[None, :] == seq_idx[:, None]) \
        & (positions[None, :] <= positions[:, None]) \
        & (positions[None, :] > positions[:, None] - window) \
        & (jnp.arange(t)[None, :] < n_valid)
    sc = jnp.where(mask[:, None, None, :], sc, NEG_INF)
    mn = jnp.maximum(m, sc.max(-1))
    p = jnp.exp(sc - mn[..., None])
    corr = jnp.exp(m - mn)
    l = l * corr + p.sum(-1)
    acc = acc * corr[..., None] + jnp.einsum(
        "tngu,und->tngd", p.astype(q.dtype), v_span).astype(jnp.float32)
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.astype(q.dtype).reshape(t, hq * hd)


# ---------------------------------------------------------------------------
# Paged-native execution path: per-tile block-table gather, no [B, nb*bs]
# materialized view (docs/memory.md §Paged-native execution)
# ---------------------------------------------------------------------------
# The oracles above gather each row's whole table into a contiguous view
# before attending; these natives fetch exactly one kv tile per scan step
# straight through the table, mirroring what the paged Pallas kernels do
# per grid cell in VMEM.  The tile VALUES (and every downstream shape,
# mask, and reduction) are identical to the gather-then-attend path, so
# the natives are bit-exact to the oracles — and hence to the contiguous
# layout, since masked slots contribute exp(NEG_INF - m) == 0.0 exactly.


def _paged_tile(flat: jax.Array, tab_rows: jax.Array, offs: jax.Array,
                bs: int) -> jax.Array:
    """One kv tile through the block table: logical slot p of packed token
    t is ``flat[tab_rows[t, p // bs] * bs + p %% bs]``.  flat is the
    physical cache with its block axes flattened ([n_blocks*bs, ...]);
    offs [kb] are the tile's logical slots.  Returns [T, kb, ...]."""
    idx = tab_rows[:, offs // bs] * bs + (offs % bs)[None, :]
    return flat[idx]


def paged_span_attention_native(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    block_tables: jax.Array,
    positions: jax.Array,
    seq_idx: jax.Array,
    *,
    window: int = 0,
    kv_block: int = 512,
) -> jax.Array:
    """:func:`packed_span_attention` reading straight through the block
    table — no [B, nb*bs] gathered view is ever materialized; each scan
    step gathers one [T, kv_block] tile of K and V from the physical
    cache.  Bit-exact to :func:`paged_span_attention` (the gather-then-
    attend oracle).  q [T,Hq,hd]; caches [n_blocks,bs,Kv,hd];
    block_tables [B,nb]; positions/seq_idx [T]."""
    t, hq, hd = q.shape
    bs, n_kv = k_cache.shape[1], k_cache.shape[2]
    s = block_tables.shape[1] * bs
    g = hq // n_kv
    kv_block = min(kv_block, s)
    while s % kv_block:
        kv_block //= 2
    nb = s // kv_block
    qg = q.reshape(t, n_kv, g, hd)
    scale = hd ** -0.5
    kf = k_cache.reshape(-1, n_kv, hd)
    vf = v_cache.reshape(-1, n_kv, hd)
    tab = block_tables[seq_idx].astype(jnp.int32)       # [T, nb_t]
    span = jnp.arange(kv_block)

    def body(carry, i):
        m, l, acc = carry
        kpos = i * kv_block + span
        kt = _paged_tile(kf, tab, kpos, bs)             # [T, kb, Kv, hd]
        vt = _paged_tile(vf, tab, kpos, bs)
        sc = jnp.einsum("tngd,tknd->tngk", qg, kt).astype(jnp.float32) * scale
        mask = kpos[None, :] <= positions[:, None]
        if window:
            mask &= kpos[None, :] > positions[:, None] - window
        sc = jnp.where(mask[:, None, None, :], sc, NEG_INF)
        mn = jnp.maximum(m, sc.max(-1))
        p = jnp.exp(sc - mn[..., None])
        corr = jnp.exp(m - mn)
        l = l * corr + p.sum(-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "tngk,tknd->tngd", p.astype(q.dtype), vt).astype(jnp.float32)
        return (mn, l, acc), None

    m0 = jnp.full((t, n_kv, g), NEG_INF, jnp.float32)
    l0 = jnp.zeros((t, n_kv, g), jnp.float32)
    a0 = jnp.zeros((t, n_kv, g, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), jnp.arange(nb))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.astype(q.dtype).reshape(t, hq * hd)


def paged_span_attention_quant_native(
    q: jax.Array,
    k8: jax.Array, ks: jax.Array,
    v8: jax.Array, vs: jax.Array,
    block_tables: jax.Array,
    positions: jax.Array,
    seq_idx: jax.Array,
    *,
    kv_block: int = 512,
) -> jax.Array:
    """:func:`packed_span_attention_quant` through the block table (int8
    cache, per-tile gather, no materialized view).  Bit-exact to
    :func:`paged_span_attention_quant`."""
    t, hq, hd = q.shape
    bs, n_kv = k8.shape[1], k8.shape[2]
    s = block_tables.shape[1] * bs
    g = hq // n_kv
    kv_block = min(kv_block, s)
    while s % kv_block:
        kv_block //= 2
    nb = s // kv_block
    qg = q.reshape(t, n_kv, g, hd)
    q8, qs = quantize_kv(qg)
    scale = hd ** -0.5
    kf = k8.reshape(-1, n_kv, hd)
    vf = v8.reshape(-1, n_kv, hd)
    ksf = ks.reshape(-1, n_kv)
    vsf = vs.reshape(-1, n_kv)
    tab = block_tables[seq_idx].astype(jnp.int32)
    span = jnp.arange(kv_block)

    def body(carry, i):
        m, l, acc = carry
        kpos = i * kv_block + span
        kt = _paged_tile(kf, tab, kpos, bs)
        vt = _paged_tile(vf, tab, kpos, bs)
        kst = _paged_tile(ksf, tab, kpos, bs).transpose(0, 2, 1)[:, :, None, :]
        vst = _paged_tile(vsf, tab, kpos, bs).transpose(0, 2, 1)[:, :, None, :]
        s32 = jnp.einsum("tngd,tknd->tngk", q8, kt,
                         preferred_element_type=jnp.int32)
        sc = s32.astype(jnp.float32) * qs[..., None].astype(jnp.float32) \
            * kst.astype(jnp.float32) * scale
        mask = kpos[None, :] <= positions[:, None]
        sc = jnp.where(mask[:, None, None, :], sc, NEG_INF)
        mn = jnp.maximum(m, sc.max(-1))
        p = jnp.exp(sc - mn[..., None])
        corr = jnp.exp(m - mn)
        l = l * corr + p.sum(-1)
        pv = p * vst.astype(jnp.float32)
        p8, ps = quantize_kv(pv)
        o32 = jnp.einsum("tngk,tknd->tngd", p8, vt,
                         preferred_element_type=jnp.int32)
        acc = acc * corr[..., None] + \
            o32.astype(jnp.float32) * ps[..., None].astype(jnp.float32)
        return (mn, l, acc), None

    m0 = jnp.full((t, n_kv, g), NEG_INF, jnp.float32)
    l0 = jnp.zeros((t, n_kv, g), jnp.float32)
    a0 = jnp.zeros((t, n_kv, g, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), jnp.arange(nb))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.astype(q.dtype).reshape(t, hq * hd)


def paged_span_attention_rolling_native(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    k_span: jax.Array,
    v_span: jax.Array,
    block_tables: jax.Array,
    positions: jax.Array,
    seq_idx: jax.Array,
    offsets: jax.Array,
    n_valid: jax.Array,
    *,
    window: int,
    kv_block: int = 512,
) -> jax.Array:
    """:func:`packed_span_attention_rolling` through the block table.
    The stored-position modulus is the table's logical width ``nb * bs``
    (== W once a row's table covers the full window); bit-exact to
    :func:`paged_span_attention_rolling`."""
    t, hq, hd = q.shape
    bs, n_kv = k_cache.shape[1], k_cache.shape[2]
    w_slots = block_tables.shape[1] * bs
    g = hq // n_kv
    kv_block = min(kv_block, w_slots)
    while w_slots % kv_block:
        kv_block //= 2
    nb = w_slots // kv_block
    qg = q.reshape(t, n_kv, g, hd)
    scale = hd ** -0.5
    kf = k_cache.reshape(-1, n_kv, hd)
    vf = v_cache.reshape(-1, n_kv, hd)
    tab = block_tables[seq_idx].astype(jnp.int32)
    span = jnp.arange(kv_block)

    def cache_body(carry, i):
        m, l, acc = carry
        slot = i * kv_block + span
        kt = _paged_tile(kf, tab, slot, bs)
        vt = _paged_tile(vf, tab, slot, bs)
        stored = offsets[:, None] - 1 - (
            (offsets[:, None] - 1 - slot[None, :]) % w_slots)
        mask = (offsets[:, None] >= 1) & (stored >= 0) & (
            stored > positions[:, None] - window)
        sc = jnp.einsum("tngd,tknd->tngk", qg, kt).astype(jnp.float32) * scale
        sc = jnp.where(mask[:, None, None, :], sc, NEG_INF)
        mn = jnp.maximum(m, sc.max(-1))
        p = jnp.exp(sc - mn[..., None])
        corr = jnp.exp(m - mn)
        l = l * corr + p.sum(-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "tngk,tknd->tngd", p.astype(q.dtype), vt).astype(jnp.float32)
        return (mn, l, acc), None

    m0 = jnp.full((t, n_kv, g), NEG_INF, jnp.float32)
    l0 = jnp.zeros((t, n_kv, g), jnp.float32)
    a0 = jnp.zeros((t, n_kv, g, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(cache_body, (m0, l0, a0), jnp.arange(nb))

    sc = jnp.einsum("tngd,und->tngu", qg, k_span).astype(jnp.float32) * scale
    upos, useq = positions, seq_idx
    mask = (useq[None, :] == seq_idx[:, None]) \
        & (upos[None, :] <= positions[:, None]) \
        & (upos[None, :] > positions[:, None] - window) \
        & (jnp.arange(t)[None, :] < n_valid)
    sc = jnp.where(mask[:, None, None, :], sc, NEG_INF)
    mn = jnp.maximum(m, sc.max(-1))
    p = jnp.exp(sc - mn[..., None])
    corr = jnp.exp(m - mn)
    l = l * corr + p.sum(-1)
    acc = acc * corr[..., None] + jnp.einsum(
        "tngu,und->tngd", p.astype(q.dtype), v_span).astype(jnp.float32)
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.astype(q.dtype).reshape(t, hq * hd)


def paged_span_attention_rolling_quant_native(
    q: jax.Array,
    k8: jax.Array, ks: jax.Array,
    v8: jax.Array, vs: jax.Array,
    k_span: jax.Array,
    v_span: jax.Array,
    block_tables: jax.Array,
    positions: jax.Array,
    seq_idx: jax.Array,
    offsets: jax.Array,
    n_valid: jax.Array,
    *,
    window: int,
    kv_block: int = 512,
) -> jax.Array:
    """:func:`packed_span_attention_rolling_quant` through the block table
    (int8 old-cache source + bf16 intra-span source, per-tile gather).
    Bit-exact to :func:`paged_span_attention_rolling_quant`."""
    t, hq, hd = q.shape
    bs, n_kv = k8.shape[1], k8.shape[2]
    w_slots = block_tables.shape[1] * bs
    g = hq // n_kv
    kv_block = min(kv_block, w_slots)
    while w_slots % kv_block:
        kv_block //= 2
    nb = w_slots // kv_block
    qg = q.reshape(t, n_kv, g, hd)
    q8, qs = quantize_kv(qg)
    scale = hd ** -0.5
    kf = k8.reshape(-1, n_kv, hd)
    vf = v8.reshape(-1, n_kv, hd)
    ksf = ks.reshape(-1, n_kv)
    vsf = vs.reshape(-1, n_kv)
    tab = block_tables[seq_idx].astype(jnp.int32)
    span = jnp.arange(kv_block)

    def cache_body(carry, i):
        m, l, acc = carry
        slot = i * kv_block + span
        kt = _paged_tile(kf, tab, slot, bs)
        vt = _paged_tile(vf, tab, slot, bs)
        kst = _paged_tile(ksf, tab, slot, bs).transpose(0, 2, 1)[:, :, None, :]
        vst = _paged_tile(vsf, tab, slot, bs).transpose(0, 2, 1)[:, :, None, :]
        stored = offsets[:, None] - 1 - (
            (offsets[:, None] - 1 - slot[None, :]) % w_slots)
        mask = (offsets[:, None] >= 1) & (stored >= 0) & (
            stored > positions[:, None] - window)
        s32 = jnp.einsum("tngd,tknd->tngk", q8, kt,
                         preferred_element_type=jnp.int32)
        sc = s32.astype(jnp.float32) * qs[..., None].astype(jnp.float32) \
            * kst.astype(jnp.float32) * scale
        sc = jnp.where(mask[:, None, None, :], sc, NEG_INF)
        mn = jnp.maximum(m, sc.max(-1))
        p = jnp.exp(sc - mn[..., None])
        corr = jnp.exp(m - mn)
        l = l * corr + p.sum(-1)
        pv = p * vst.astype(jnp.float32)
        p8, ps = quantize_kv(pv)
        o32 = jnp.einsum("tngk,tknd->tngd", p8, vt,
                         preferred_element_type=jnp.int32)
        acc = acc * corr[..., None] + \
            o32.astype(jnp.float32) * ps[..., None].astype(jnp.float32)
        return (mn, l, acc), None

    m0 = jnp.full((t, n_kv, g), NEG_INF, jnp.float32)
    l0 = jnp.zeros((t, n_kv, g), jnp.float32)
    a0 = jnp.zeros((t, n_kv, g, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(cache_body, (m0, l0, a0), jnp.arange(nb))

    sc = jnp.einsum("tngd,und->tngu", qg, k_span).astype(jnp.float32) * scale
    mask = (seq_idx[None, :] == seq_idx[:, None]) \
        & (positions[None, :] <= positions[:, None]) \
        & (positions[None, :] > positions[:, None] - window) \
        & (jnp.arange(t)[None, :] < n_valid)
    sc = jnp.where(mask[:, None, None, :], sc, NEG_INF)
    mn = jnp.maximum(m, sc.max(-1))
    p = jnp.exp(sc - mn[..., None])
    corr = jnp.exp(m - mn)
    l = l * corr + p.sum(-1)
    acc = acc * corr[..., None] + jnp.einsum(
        "tngu,und->tngd", p.astype(q.dtype), v_span).astype(jnp.float32)
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.astype(q.dtype).reshape(t, hq * hd)


def use_pallas_paged() -> bool:
    """Backend choice for the paged execution path, decided by the
    platform alone: the Pallas kernels (:mod:`repro.kernels.span_attention`
    paged twins) compile natively on TPU; everywhere else interpret-mode
    Pallas is orders of magnitude too slow for a hot path, so the
    bit-exact jnp natives above run instead."""
    return jax.default_backend() == "tpu"


def paged_span_attention_exec(q, k_cache, v_cache, block_tables, positions,
                              seq_idx, *, window=0, kv_block=512):
    """Dispatch :func:`paged_span_attention` semantics to the execution
    backend (Pallas kernel on TPU, jnp native elsewhere)."""
    if use_pallas_paged():
        return ksa.paged_span_attention(
            q, k_cache, v_cache, positions, seq_idx, block_tables,
            window=window, interpret=False)
    return paged_span_attention_native(
        q, k_cache, v_cache, block_tables, positions, seq_idx,
        window=window, kv_block=kv_block)


def paged_span_attention_quant_exec(q, k8, ks, v8, vs, block_tables,
                                    positions, seq_idx, *, kv_block=512):
    if use_pallas_paged():
        return ksa.paged_span_attention_quant(
            q, k8, ks, v8, vs, positions, seq_idx, block_tables,
            interpret=False)
    return paged_span_attention_quant_native(
        q, k8, ks, v8, vs, block_tables, positions, seq_idx,
        kv_block=kv_block)


# The rolling twins read ``n_valid`` through scalar prefetch as a [1]
# vector (``nv_ref[0]``); the engine hands the stage a scalar, which the
# TPU compiler rejects as an index into a rank-0 ref.
def paged_span_attention_rolling_exec(q, k_cache, v_cache, k_span, v_span,
                                      block_tables, positions, seq_idx,
                                      offsets, n_valid, *, window,
                                      kv_block=512):
    if use_pallas_paged():
        return ksa.paged_span_attention_rolling(
            q, k_cache, v_cache, k_span, v_span, positions, seq_idx,
            offsets, jnp.reshape(n_valid, (1,)), block_tables,
            window=window, interpret=False)
    return paged_span_attention_rolling_native(
        q, k_cache, v_cache, k_span, v_span, block_tables, positions,
        seq_idx, offsets, n_valid, window=window, kv_block=kv_block)


def paged_span_attention_rolling_quant_exec(q, k8, ks, v8, vs, k_span,
                                            v_span, block_tables, positions,
                                            seq_idx, offsets, n_valid, *,
                                            window, kv_block=512):
    if use_pallas_paged():
        return ksa.paged_span_attention_rolling_quant(
            q, k8, ks, v8, vs, k_span, v_span, positions, seq_idx,
            offsets, jnp.reshape(n_valid, (1,)), block_tables,
            window=window, interpret=False)
    return paged_span_attention_rolling_quant_native(
        q, k8, ks, v8, vs, k_span, v_span, block_tables, positions,
        seq_idx, offsets, n_valid, window=window, kv_block=kv_block)


def fill_rolling_cache(k: jax.Array, window: int) -> jax.Array:
    """Convert prefill K/V [B, S, kv, hd] into a rolling cache [B, W, kv, hd]
    under the slot = position %% W convention.

    Assumes an UNPADDED batch: every row's sequence fills all S positions.
    Ragged (right-padded) batches must use
    :func:`fill_rolling_cache_ragged`, else pad-tail K/V lands in slots
    that later decode steps treat as real window entries.
    """
    s = k.shape[1]
    if s < window:
        return jnp.pad(k, ((0, 0), (0, window - s), (0, 0), (0, 0)))
    tail = k[:, s - window:]
    shift = s % window
    return jnp.roll(tail, shift, axis=1) if shift else tail


def fill_rolling_cache_ragged(k: jax.Array, window: int,
                              lengths: jax.Array) -> jax.Array:
    """Ragged-batch variant of :func:`fill_rolling_cache`.

    ``k`` [B, S, kv, hd] is right-padded; ``lengths`` [B] gives each row's
    real token count.  Slot s of row i must hold the row's LAST position
    congruent to s mod W — ``L-1 - ((L-1 - s) mod W)`` (the same
    reconstruction the rolling span-attention kernels use) — and slots
    whose reconstructed position is negative (sequence shorter than the
    window) are zeroed, exactly matching what per-token decode/chunk
    scatters would have produced.  Gathering by position instead of
    rolling the tail keeps pad-tail K/V out of the cache.
    """
    b, s = k.shape[0], k.shape[1]
    slots = jnp.arange(window)
    last = lengths.astype(jnp.int32)[:, None] - 1            # [B, 1]
    stored = last - ((last - slots[None, :]) % window)       # [B, W]
    valid = stored >= 0
    idx = jnp.clip(stored, 0, s - 1)
    out = k[jnp.arange(b)[:, None], idx]                     # [B, W, kv, hd]
    return jnp.where(valid[..., None, None], out, 0).astype(k.dtype)
