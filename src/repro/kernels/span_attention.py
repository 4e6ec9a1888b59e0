"""Packed ragged span attention over a KV cache as Pallas TPU kernels.

The serving engine's chunked-prefill iterations carry a *packed* token
layout: all valid span tokens of a mixed batch concatenated into flat
[T] vectors (``docs/scheduling.md``).  These kernels generalize
:mod:`repro.kernels.decode_attention` — one grid row per packed token
instead of per sequence — streaming the KV cache in [kv_block] tiles
through VMEM with a flash-style running softmax in scratch.  The cache
row each token reads is data-dependent (``seq_idx``), so the row index
is scalar-prefetched (``PrefetchScalarGridSpec``) and consumed by the
BlockSpec index maps before the body runs.

Three variants, matching the pure-jnp oracles in
:mod:`repro.models.attention` (validated in interpret mode):

  span_attention          full-length cache; per-token position masking
                          with early termination past the filled prefix,
                          plus an optional sliding window whose lower
                          bound also skips whole kv blocks (the
                          ``_triangular_attention`` trick).
  span_attention_quant    int8 cache: both contractions are s8 x s8 ->
                          s32 MXU dots with the K/V scales folded
                          outside them (q and the probability rows are
                          quantized on the fly, per block).
  span_attention_rolling  sliding-window models with rolling caches
                          (slot = pos %% W): the old cache and the
                          span's fresh K/V feed one running softmax
                          (attend-then-scatter — see the jnp oracle's
                          docstring for why scatter-first is wrong).
  span_attention_rolling_quant
                          the int8 + sliding-window combination: the
                          old-cache source runs s8 x s8 -> s32 dots with
                          folded scales; the span's own fresh K/V is
                          still bf16, so the intra-span source keeps
                          full-precision dots — both into one running
                          softmax.

Every variant also has a *paged* twin (``paged_span_attention`` etc.) for
the engine's block-paged KV substrate (docs/memory.md): the physical
cache is [n_blocks, bs, Kv, hd] and each sequence's slots live at
``(block_table[p // bs], p %% bs)``.  The twins reuse the same kernel
bodies — the only change is the BlockSpec index maps, which look the
*physical* block id up in a scalar-prefetched flattened block table
(``tbl[seq[t] * nb + i]``) instead of indexing a per-sequence row, with
the kv tile pinned to the page size.  Padded table entries point at the
trash block; its garbage is never read live thanks to the same position
masks (and early-termination guards) the contiguous kernels use.

Layouts: q [T, H, hd]; caches [B, S, Kv, hd] (contiguous) or
[n_blocks, bs, Kv, hd] + block_tables [B, nb] (paged);
positions/seq_idx [T].
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _pick_block(s: int, kv_block: int) -> int:
    kv_block = min(kv_block, s)
    while s % kv_block:
        kv_block //= 2
    return kv_block


# ---------------------------------------------------------------------------
# Full-length cache (optionally windowed)
# ---------------------------------------------------------------------------

def _kernel(seq_ref, pos_ref, q_ref, k_ref, v_ref, o_ref,
            m_scr, l_scr, acc_scr, *, kv_block: int, g: int, scale: float,
            ns: int, window: int):
    i_t = pl.program_id(0)
    i_s = pl.program_id(1)

    @pl.when(i_s == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    pos = pos_ref[i_t]
    start = i_s * kv_block
    # early termination past the filled prefix; with a window, also skip
    # blocks that lie entirely below the window's lower bound
    live = start <= pos
    if window:
        live &= start + kv_block > pos - window + 1

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32)               # [H, hd]
        k = k_ref[0].astype(jnp.float32)               # [kb, Kv, hd]
        v = v_ref[0].astype(jnp.float32)
        h, hd = q.shape
        kv = k.shape[1]
        qg = q.reshape(kv, g, hd)
        s = jax.lax.dot_general(
            qg, k.transpose(1, 2, 0),                  # [Kv, hd, kb]
            (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale
        kpos = start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        valid = kpos <= pos
        if window:
            valid &= kpos > pos - window
        s = jnp.where(valid, s, NEG_INF)

        m_prev = m_scr[...]                            # [Kv, G]
        m_new = jnp.maximum(m_prev, s.max(axis=2))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=2)
        acc_scr[...] = acc_scr[...] * corr[..., None] + jax.lax.dot_general(
            p, v.transpose(1, 0, 2),                   # [Kv, kb, hd]
            (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(i_s == ns - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-30)[..., None]
        out = acc_scr[...] / denom
        o_ref[0] = out.reshape(o_ref.shape[1:]).astype(o_ref.dtype)


def span_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                   positions: jax.Array, seq_idx: jax.Array, *,
                   window: int = 0, kv_block: int = 512,
                   scale: float = 0.0, interpret: bool = True) -> jax.Array:
    """q [T,H,hd]; caches [B,S,Kv,hd]; positions/seq_idx [T] -> [T, H*hd]."""
    t, h, hd = q.shape
    s, kv = k_cache.shape[1], k_cache.shape[2]
    g = h // kv
    kv_block = _pick_block(s, kv_block)
    ns = s // kv_block
    scale = scale or hd ** -0.5

    kernel = functools.partial(_kernel, kv_block=kv_block, g=g, scale=scale,
                               ns=ns, window=window)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(t, ns),
        in_specs=[
            pl.BlockSpec((1, h, hd), lambda t_, i, seq, pos: (t_, 0, 0)),
            pl.BlockSpec((1, kv_block, kv, hd),
                         lambda t_, i, seq, pos: (seq[t_], i, 0, 0)),
            pl.BlockSpec((1, kv_block, kv, hd),
                         lambda t_, i, seq, pos: (seq[t_], i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, h, hd), lambda t_, i, seq, pos: (t_, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((kv, g), jnp.float32),
            pltpu.VMEM((kv, g), jnp.float32),
            pltpu.VMEM((kv, g, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t, h, hd), q.dtype),
        interpret=interpret,
    )(seq_idx, positions, q, k_cache, v_cache)
    return out.reshape(t, h * hd)


# ---------------------------------------------------------------------------
# int8 cache
# ---------------------------------------------------------------------------

def _quantize(x: jax.Array):
    """Per-row symmetric int8 quantization along the last axis (fp32 in)."""
    s = jnp.max(jnp.abs(x), axis=-1) / 127.0 + 1e-8
    q = jnp.clip(jnp.round(x / s[..., None]), -127, 127)
    return q.astype(jnp.int8), s


def _quant_kernel(seq_ref, pos_ref, q_ref, k_ref, ks_ref, v_ref, vs_ref,
                  o_ref, m_scr, l_scr, acc_scr, *, kv_block: int, g: int,
                  scale: float, ns: int):
    i_t = pl.program_id(0)
    i_s = pl.program_id(1)

    @pl.when(i_s == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    pos = pos_ref[i_t]
    start = i_s * kv_block

    @pl.when(start <= pos)
    def _compute():
        q = q_ref[0].astype(jnp.float32)               # [H, hd]
        k8 = k_ref[0]                                  # [kb, Kv, hd] int8
        v8 = v_ref[0]
        ks = ks_ref[0].astype(jnp.float32)             # [kb, Kv]
        vs = vs_ref[0].astype(jnp.float32)
        h, hd = q.shape
        kv = k8.shape[1]
        q8, qs = _quantize(q.reshape(kv, g, hd))       # s8, [Kv, G]
        s32 = jax.lax.dot_general(
            q8, k8.transpose(1, 2, 0),                 # [Kv, hd, kb] s8
            (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.int32)          # [Kv, G, kb]
        s = s32.astype(jnp.float32) * qs[..., None] \
            * ks.T[:, None, :] * scale
        kpos = start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        s = jnp.where(kpos <= pos, s, NEG_INF)

        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=2))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=2)
        pv = p * vs.T[:, None, :]                      # fold V scales
        p8, ps = _quantize(pv)
        o32 = jax.lax.dot_general(
            p8, v8.transpose(1, 0, 2),                 # [Kv, kb, hd] s8
            (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.int32)
        acc_scr[...] = acc_scr[...] * corr[..., None] + \
            o32.astype(jnp.float32) * ps[..., None]
        m_scr[...] = m_new

    @pl.when(i_s == ns - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-30)[..., None]
        out = acc_scr[...] / denom
        o_ref[0] = out.reshape(o_ref.shape[1:]).astype(o_ref.dtype)


def span_attention_quant(q: jax.Array, k8: jax.Array, ks: jax.Array,
                         v8: jax.Array, vs: jax.Array, positions: jax.Array,
                         seq_idx: jax.Array, *, kv_block: int = 512,
                         scale: float = 0.0, interpret: bool = True) -> jax.Array:
    """q [T,H,hd] bf16; k8/v8 [B,S,Kv,hd] int8; ks/vs [B,S,Kv] -> [T, H*hd]."""
    t, h, hd = q.shape
    s, kv = k8.shape[1], k8.shape[2]
    g = h // kv
    kv_block = _pick_block(s, kv_block)
    ns = s // kv_block
    scale = scale or hd ** -0.5

    kernel = functools.partial(_quant_kernel, kv_block=kv_block, g=g,
                               scale=scale, ns=ns)
    cache_spec = pl.BlockSpec((1, kv_block, kv, hd),
                              lambda t_, i, seq, pos: (seq[t_], i, 0, 0))
    scale_spec = pl.BlockSpec((1, kv_block, kv),
                              lambda t_, i, seq, pos: (seq[t_], i, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(t, ns),
        in_specs=[
            pl.BlockSpec((1, h, hd), lambda t_, i, seq, pos: (t_, 0, 0)),
            cache_spec, scale_spec, cache_spec, scale_spec,
        ],
        out_specs=pl.BlockSpec((1, h, hd), lambda t_, i, seq, pos: (t_, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((kv, g), jnp.float32),
            pltpu.VMEM((kv, g), jnp.float32),
            pltpu.VMEM((kv, g, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t, h, hd), q.dtype),
        interpret=interpret,
    )(seq_idx, positions, q, k8, ks, v8, vs)
    return out.reshape(t, h * hd)


# ---------------------------------------------------------------------------
# Rolling cache (sliding-window models)
# ---------------------------------------------------------------------------

def _rolling_kernel(seq_ref, pos_ref, off_ref, nv_ref, q_ref, k_ref, v_ref,
                    ksp_ref, vsp_ref, posv_ref, seqv_ref, o_ref,
                    m_scr, l_scr, acc_scr, *, kv_block: int, g: int,
                    scale: float, ns: int, window: int, w_slots: int):
    i_t = pl.program_id(0)
    i_s = pl.program_id(1)

    @pl.when(i_s == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    pos = pos_ref[i_t]
    off = off_ref[i_t]

    def _accumulate(s, v_t):
        """One running-softmax step; s [Kv, G, n], v_t [Kv, n, hd] fp32."""
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=2))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=2)
        acc_scr[...] = acc_scr[...] * corr[..., None] + jax.lax.dot_general(
            p, v_t, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    # old-cache source: reconstruct the position stored in each slot
    # (largest m < off with m % W == slot) to mask age and window
    @pl.when((i_s < ns) & (off >= 1))
    def _cache_block():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)               # [kb, Kv, hd]
        v = v_ref[0].astype(jnp.float32)
        h, hd = q.shape
        kv = k.shape[1]
        qg = q.reshape(kv, g, hd)
        s = jax.lax.dot_general(
            qg, k.transpose(1, 2, 0),
            (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale
        slot = i_s * kv_block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        stored = off - 1 - ((off - 1 - slot) % w_slots)
        valid = (stored >= 0) & (stored > pos - window)
        _accumulate(jnp.where(valid, s, NEG_INF), v.transpose(1, 0, 2))

    # intra-span source: the packed chunk's own fresh K/V
    @pl.when(i_s == ns)
    def _span_block():
        q = q_ref[0].astype(jnp.float32)
        k = ksp_ref[...].astype(jnp.float32)           # [T, Kv, hd]
        v = vsp_ref[...].astype(jnp.float32)
        h, hd = q.shape
        kv = k.shape[1]
        qg = q.reshape(kv, g, hd)
        s = jax.lax.dot_general(
            qg, k.transpose(1, 2, 0),
            (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale
        u = jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        upos = posv_ref[...][None, None, :]            # [1, 1, T]
        useq = seqv_ref[...][None, None, :]
        valid = (useq == seq_ref[i_t]) & (upos <= pos) \
            & (upos > pos - window) & (u < nv_ref[0])
        _accumulate(jnp.where(valid, s, NEG_INF), v.transpose(1, 0, 2))

    @pl.when(i_s == ns)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-30)[..., None]
        out = acc_scr[...] / denom
        o_ref[0] = out.reshape(o_ref.shape[1:]).astype(o_ref.dtype)


def _rolling_quant_kernel(seq_ref, pos_ref, off_ref, nv_ref, q_ref, k_ref,
                          ks_ref, v_ref, vs_ref, ksp_ref, vsp_ref, posv_ref,
                          seqv_ref, o_ref, m_scr, l_scr, acc_scr, *,
                          kv_block: int, g: int, scale: float, ns: int,
                          window: int, w_slots: int):
    i_t = pl.program_id(0)
    i_s = pl.program_id(1)

    @pl.when(i_s == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    pos = pos_ref[i_t]
    off = off_ref[i_t]

    def _accumulate(s, update_acc):
        """One running-softmax step; ``update_acc(p, corr)`` folds the AV
        contraction (int8 cache blocks requantize p, the fp span doesn't)."""
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=2))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=2)
        update_acc(p, corr)
        m_scr[...] = m_new

    # old-cache source: s8 x s8 -> s32 dots with folded scales, masked by
    # the reconstructed stored position (age + window)
    @pl.when((i_s < ns) & (off >= 1))
    def _cache_block():
        q = q_ref[0].astype(jnp.float32)               # [H, hd]
        k8 = k_ref[0]                                  # [kb, Kv, hd] int8
        v8 = v_ref[0]
        ks = ks_ref[0].astype(jnp.float32)             # [kb, Kv]
        vs = vs_ref[0].astype(jnp.float32)
        h, hd = q.shape
        kv = k8.shape[1]
        q8, qs = _quantize(q.reshape(kv, g, hd))       # s8, [Kv, G]
        s32 = jax.lax.dot_general(
            q8, k8.transpose(1, 2, 0),                 # [Kv, hd, kb] s8
            (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.int32)
        s = s32.astype(jnp.float32) * qs[..., None] \
            * ks.T[:, None, :] * scale
        slot = i_s * kv_block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        stored = off - 1 - ((off - 1 - slot) % w_slots)
        valid = (stored >= 0) & (stored > pos - window)
        s = jnp.where(valid, s, NEG_INF)

        def update_acc(p, corr):
            pv = p * vs.T[:, None, :]                  # fold V scales
            p8, ps = _quantize(pv)
            o32 = jax.lax.dot_general(
                p8, v8.transpose(1, 0, 2),             # [Kv, kb, hd] s8
                (((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.int32)
            acc_scr[...] = acc_scr[...] * corr[..., None] + \
                o32.astype(jnp.float32) * ps[..., None]

        _accumulate(s, update_acc)

    # intra-span source: the packed chunk's own fresh bf16 K/V
    @pl.when(i_s == ns)
    def _span_block():
        q = q_ref[0].astype(jnp.float32)
        k = ksp_ref[...].astype(jnp.float32)           # [T, Kv, hd]
        v = vsp_ref[...].astype(jnp.float32)
        h, hd = q.shape
        kv = k.shape[1]
        qg = q.reshape(kv, g, hd)
        s = jax.lax.dot_general(
            qg, k.transpose(1, 2, 0),
            (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale
        u = jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        upos = posv_ref[...][None, None, :]            # [1, 1, T]
        useq = seqv_ref[...][None, None, :]
        valid = (useq == seq_ref[i_t]) & (upos <= pos) \
            & (upos > pos - window) & (u < nv_ref[0])
        s = jnp.where(valid, s, NEG_INF)

        def update_acc(p, corr):
            acc_scr[...] = acc_scr[...] * corr[..., None] + jax.lax.dot_general(
                p, v.transpose(1, 0, 2),
                (((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)

        _accumulate(s, update_acc)

    @pl.when(i_s == ns)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-30)[..., None]
        out = acc_scr[...] / denom
        o_ref[0] = out.reshape(o_ref.shape[1:]).astype(o_ref.dtype)


def span_attention_rolling_quant(q: jax.Array, k8: jax.Array, ks: jax.Array,
                                 v8: jax.Array, vs: jax.Array,
                                 k_span: jax.Array, v_span: jax.Array,
                                 positions: jax.Array, seq_idx: jax.Array,
                                 offsets: jax.Array, n_valid: jax.Array, *,
                                 window: int, kv_block: int = 512,
                                 scale: float = 0.0,
                                 interpret: bool = True) -> jax.Array:
    """Two-source windowed span attention over an int8 rolling cache.

    q [T,H,hd]; k8/v8 [B,W,Kv,hd] int8 (pre-scatter); ks/vs [B,W,Kv];
    k_span/v_span [T,Kv,hd] bf16; positions/seq_idx/offsets [T];
    n_valid [1] -> [T, H*hd].  Matches
    :func:`repro.models.attention.packed_span_attention_rolling_quant`.
    """
    t, h, hd = q.shape
    w_slots, kv = k8.shape[1], k8.shape[2]
    g = h // kv
    kv_block = _pick_block(w_slots, kv_block)
    ns = w_slots // kv_block
    scale = scale or hd ** -0.5

    kernel = functools.partial(_rolling_quant_kernel, kv_block=kv_block, g=g,
                               scale=scale, ns=ns, window=window,
                               w_slots=w_slots)

    def cache_idx(t_, i, seq, pos, off, nv):
        return (seq[t_], jnp.minimum(i, ns - 1), 0, 0)

    def scale_idx(t_, i, seq, pos, off, nv):
        return (seq[t_], jnp.minimum(i, ns - 1), 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,        # seq_idx, positions, offsets, n_valid
        grid=(t, ns + 1),             # ns cache blocks + 1 span block
        in_specs=[
            pl.BlockSpec((1, h, hd), lambda t_, i, *_: (t_, 0, 0)),
            pl.BlockSpec((1, kv_block, kv, hd), cache_idx),
            pl.BlockSpec((1, kv_block, kv), scale_idx),
            pl.BlockSpec((1, kv_block, kv, hd), cache_idx),
            pl.BlockSpec((1, kv_block, kv), scale_idx),
            pl.BlockSpec((t, kv, hd), lambda t_, i, *_: (0, 0, 0)),
            pl.BlockSpec((t, kv, hd), lambda t_, i, *_: (0, 0, 0)),
            pl.BlockSpec((t,), lambda t_, i, *_: (0,)),
            pl.BlockSpec((t,), lambda t_, i, *_: (0,)),
        ],
        out_specs=pl.BlockSpec((1, h, hd), lambda t_, i, *_: (t_, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((kv, g), jnp.float32),
            pltpu.VMEM((kv, g), jnp.float32),
            pltpu.VMEM((kv, g, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t, h, hd), q.dtype),
        interpret=interpret,
    )(seq_idx, positions, offsets, n_valid, q, k8, ks, v8, vs,
      k_span, v_span, positions, seq_idx)
    return out.reshape(t, h * hd)


def span_attention_rolling(q: jax.Array, k_cache: jax.Array,
                           v_cache: jax.Array, k_span: jax.Array,
                           v_span: jax.Array, positions: jax.Array,
                           seq_idx: jax.Array, offsets: jax.Array,
                           n_valid: jax.Array, *, window: int,
                           kv_block: int = 512, scale: float = 0.0,
                           interpret: bool = True) -> jax.Array:
    """Two-source windowed span attention over a rolling cache.

    q [T,H,hd]; caches [B,W,Kv,hd] (pre-scatter); k_span/v_span [T,Kv,hd];
    positions/seq_idx/offsets [T]; n_valid [1] -> [T, H*hd].
    """
    t, h, hd = q.shape
    w_slots, kv = k_cache.shape[1], k_cache.shape[2]
    g = h // kv
    kv_block = _pick_block(w_slots, kv_block)
    ns = w_slots // kv_block
    scale = scale or hd ** -0.5

    kernel = functools.partial(_rolling_kernel, kv_block=kv_block, g=g,
                               scale=scale, ns=ns, window=window,
                               w_slots=w_slots)

    def cache_idx(t_, i, seq, pos, off, nv):
        return (seq[t_], jnp.minimum(i, ns - 1), 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,        # seq_idx, positions, offsets, n_valid
        grid=(t, ns + 1),             # ns cache blocks + 1 span block
        in_specs=[
            pl.BlockSpec((1, h, hd), lambda t_, i, *_: (t_, 0, 0)),
            pl.BlockSpec((1, kv_block, kv, hd), cache_idx),
            pl.BlockSpec((1, kv_block, kv, hd), cache_idx),
            pl.BlockSpec((t, kv, hd), lambda t_, i, *_: (0, 0, 0)),
            pl.BlockSpec((t, kv, hd), lambda t_, i, *_: (0, 0, 0)),
            pl.BlockSpec((t,), lambda t_, i, *_: (0,)),
            pl.BlockSpec((t,), lambda t_, i, *_: (0,)),
        ],
        out_specs=pl.BlockSpec((1, h, hd), lambda t_, i, *_: (t_, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((kv, g), jnp.float32),
            pltpu.VMEM((kv, g), jnp.float32),
            pltpu.VMEM((kv, g, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t, h, hd), q.dtype),
        interpret=interpret,
    )(seq_idx, positions, offsets, n_valid, q, k_cache, v_cache,
      k_span, v_span, positions, seq_idx)
    return out.reshape(t, h * hd)


# ---------------------------------------------------------------------------
# Paged twins: block-table scalar prefetch over [n_blocks, bs, Kv, hd]
# ---------------------------------------------------------------------------
# The kernel bodies are the contiguous ones verbatim — a thin wrapper
# drops the extra block-table scalar ref (only the index maps consume it)
# and the kv tile is the page block size, so logical block i of token t's
# sequence is fetched from physical block ``tbl[seq[t] * nb + i]``.

def _resolve_interpret(interpret: bool | None) -> bool:
    """``None`` = auto: compiled on TPU, interpret-mode elsewhere.

    The paged twins are the engine's execution path (attention.py routes
    through them on TPU backends), so their default must not silently pin
    interpret mode the way the contiguous validation wrappers do."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def _paged_kernel(seq_ref, pos_ref, tbl_ref, *rest, **kw):
    _kernel(seq_ref, pos_ref, *rest, **kw)


def _paged_quant_kernel(seq_ref, pos_ref, tbl_ref, *rest, **kw):
    _quant_kernel(seq_ref, pos_ref, *rest, **kw)


def _paged_rolling_kernel(seq_ref, pos_ref, off_ref, nv_ref, tbl_ref,
                          *rest, **kw):
    _rolling_kernel(seq_ref, pos_ref, off_ref, nv_ref, *rest, **kw)


def _paged_rolling_quant_kernel(seq_ref, pos_ref, off_ref, nv_ref, tbl_ref,
                                *rest, **kw):
    _rolling_quant_kernel(seq_ref, pos_ref, off_ref, nv_ref, *rest, **kw)


def paged_span_attention(q: jax.Array, k_cache: jax.Array,
                         v_cache: jax.Array, positions: jax.Array,
                         seq_idx: jax.Array, block_tables: jax.Array, *,
                         window: int = 0, scale: float = 0.0,
                         interpret: bool | None = None) -> jax.Array:
    """q [T,H,hd]; caches [n_blocks,bs,Kv,hd]; block_tables [B,nb];
    positions/seq_idx [T] -> [T, H*hd].  Matches
    :func:`repro.models.attention.paged_span_attention`."""
    interpret = _resolve_interpret(interpret)
    t, h, hd = q.shape
    bs, kv = k_cache.shape[1], k_cache.shape[2]
    nb = block_tables.shape[1]
    g = h // kv
    scale = scale or hd ** -0.5

    kernel = functools.partial(_paged_kernel, kv_block=bs, g=g, scale=scale,
                               ns=nb, window=window)
    tbl = block_tables.reshape(-1).astype(jnp.int32)
    cache_spec = pl.BlockSpec(
        (1, bs, kv, hd),
        lambda t_, i, seq, pos, tb: (tb[seq[t_] * nb + i], 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,        # seq_idx, positions, block table
        grid=(t, nb),
        in_specs=[
            pl.BlockSpec((1, h, hd), lambda t_, i, *_: (t_, 0, 0)),
            cache_spec,
            cache_spec,
        ],
        out_specs=pl.BlockSpec((1, h, hd), lambda t_, i, *_: (t_, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((kv, g), jnp.float32),
            pltpu.VMEM((kv, g), jnp.float32),
            pltpu.VMEM((kv, g, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t, h, hd), q.dtype),
        interpret=interpret,
        name="paged_span_attention",
    )(seq_idx, positions, tbl, q, k_cache, v_cache)
    return out.reshape(t, h * hd)


def paged_span_attention_quant(q: jax.Array, k8: jax.Array, ks: jax.Array,
                               v8: jax.Array, vs: jax.Array,
                               positions: jax.Array, seq_idx: jax.Array,
                               block_tables: jax.Array, *,
                               scale: float = 0.0,
                               interpret: bool | None = None) -> jax.Array:
    """q [T,H,hd] bf16; k8/v8 [n_blocks,bs,Kv,hd] int8; ks/vs
    [n_blocks,bs,Kv]; block_tables [B,nb] -> [T, H*hd]."""
    interpret = _resolve_interpret(interpret)
    t, h, hd = q.shape
    bs, kv = k8.shape[1], k8.shape[2]
    nb = block_tables.shape[1]
    g = h // kv
    scale = scale or hd ** -0.5

    kernel = functools.partial(_paged_quant_kernel, kv_block=bs, g=g,
                               scale=scale, ns=nb)
    tbl = block_tables.reshape(-1).astype(jnp.int32)
    cache_spec = pl.BlockSpec(
        (1, bs, kv, hd),
        lambda t_, i, seq, pos, tb: (tb[seq[t_] * nb + i], 0, 0, 0))
    scale_spec = pl.BlockSpec(
        (1, bs, kv),
        lambda t_, i, seq, pos, tb: (tb[seq[t_] * nb + i], 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(t, nb),
        in_specs=[
            pl.BlockSpec((1, h, hd), lambda t_, i, *_: (t_, 0, 0)),
            cache_spec, scale_spec, cache_spec, scale_spec,
        ],
        out_specs=pl.BlockSpec((1, h, hd), lambda t_, i, *_: (t_, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((kv, g), jnp.float32),
            pltpu.VMEM((kv, g), jnp.float32),
            pltpu.VMEM((kv, g, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t, h, hd), q.dtype),
        interpret=interpret,
        name="paged_span_attention_quant",
    )(seq_idx, positions, tbl, q, k8, ks, v8, vs)
    return out.reshape(t, h * hd)


def paged_span_attention_rolling(q: jax.Array, k_cache: jax.Array,
                                 v_cache: jax.Array, k_span: jax.Array,
                                 v_span: jax.Array, positions: jax.Array,
                                 seq_idx: jax.Array, offsets: jax.Array,
                                 n_valid: jax.Array,
                                 block_tables: jax.Array, *, window: int,
                                 scale: float = 0.0,
                                 interpret: bool | None = None) -> jax.Array:
    """Two-source windowed span attention over a block-paged rolling cache.

    caches [n_blocks,bs,Kv,hd] (pre-scatter); block_tables [B,nb] with the
    gathered view width ``nb * bs`` playing the stored-position modulus
    (== W once a row's table covers the full window).  Matches
    :func:`repro.models.attention.paged_span_attention_rolling`."""
    interpret = _resolve_interpret(interpret)
    t, h, hd = q.shape
    bs, kv = k_cache.shape[1], k_cache.shape[2]
    nb = block_tables.shape[1]
    g = h // kv
    scale = scale or hd ** -0.5

    kernel = functools.partial(_paged_rolling_kernel, kv_block=bs, g=g,
                               scale=scale, ns=nb, window=window,
                               w_slots=nb * bs)
    tbl = block_tables.reshape(-1).astype(jnp.int32)

    def cache_idx(t_, i, seq, pos, off, nv, tb):
        return (tb[seq[t_] * nb + jnp.minimum(i, nb - 1)], 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,    # seq_idx, positions, offsets, n_valid, tbl
        grid=(t, nb + 1),         # nb cache blocks + 1 span block
        in_specs=[
            pl.BlockSpec((1, h, hd), lambda t_, i, *_: (t_, 0, 0)),
            pl.BlockSpec((1, bs, kv, hd), cache_idx),
            pl.BlockSpec((1, bs, kv, hd), cache_idx),
            pl.BlockSpec((t, kv, hd), lambda t_, i, *_: (0, 0, 0)),
            pl.BlockSpec((t, kv, hd), lambda t_, i, *_: (0, 0, 0)),
            pl.BlockSpec((t,), lambda t_, i, *_: (0,)),
            pl.BlockSpec((t,), lambda t_, i, *_: (0,)),
        ],
        out_specs=pl.BlockSpec((1, h, hd), lambda t_, i, *_: (t_, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((kv, g), jnp.float32),
            pltpu.VMEM((kv, g), jnp.float32),
            pltpu.VMEM((kv, g, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t, h, hd), q.dtype),
        interpret=interpret,
        name="paged_span_attention_rolling",
    )(seq_idx, positions, offsets, n_valid, tbl, q, k_cache, v_cache,
      k_span, v_span, positions, seq_idx)
    return out.reshape(t, h * hd)


def paged_span_attention_rolling_quant(q: jax.Array, k8: jax.Array,
                                       ks: jax.Array, v8: jax.Array,
                                       vs: jax.Array, k_span: jax.Array,
                                       v_span: jax.Array,
                                       positions: jax.Array,
                                       seq_idx: jax.Array,
                                       offsets: jax.Array,
                                       n_valid: jax.Array,
                                       block_tables: jax.Array, *,
                                       window: int, scale: float = 0.0,
                                       interpret: bool | None = None,
                                       ) -> jax.Array:
    """The int8 + sliding-window + paged combination: s8 x s8 -> s32
    old-cache dots with folded scales, bf16 intra-span source, block-table
    scalar prefetch — one running softmax."""
    interpret = _resolve_interpret(interpret)
    t, h, hd = q.shape
    bs, kv = k8.shape[1], k8.shape[2]
    nb = block_tables.shape[1]
    g = h // kv
    scale = scale or hd ** -0.5

    kernel = functools.partial(_paged_rolling_quant_kernel, kv_block=bs,
                               g=g, scale=scale, ns=nb, window=window,
                               w_slots=nb * bs)
    tbl = block_tables.reshape(-1).astype(jnp.int32)

    def cache_idx(t_, i, seq, pos, off, nv, tb):
        return (tb[seq[t_] * nb + jnp.minimum(i, nb - 1)], 0, 0, 0)

    def scale_idx(t_, i, seq, pos, off, nv, tb):
        return (tb[seq[t_] * nb + jnp.minimum(i, nb - 1)], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(t, nb + 1),
        in_specs=[
            pl.BlockSpec((1, h, hd), lambda t_, i, *_: (t_, 0, 0)),
            pl.BlockSpec((1, bs, kv, hd), cache_idx),
            pl.BlockSpec((1, bs, kv), scale_idx),
            pl.BlockSpec((1, bs, kv, hd), cache_idx),
            pl.BlockSpec((1, bs, kv), scale_idx),
            pl.BlockSpec((t, kv, hd), lambda t_, i, *_: (0, 0, 0)),
            pl.BlockSpec((t, kv, hd), lambda t_, i, *_: (0, 0, 0)),
            pl.BlockSpec((t,), lambda t_, i, *_: (0,)),
            pl.BlockSpec((t,), lambda t_, i, *_: (0,)),
        ],
        out_specs=pl.BlockSpec((1, h, hd), lambda t_, i, *_: (t_, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((kv, g), jnp.float32),
            pltpu.VMEM((kv, g), jnp.float32),
            pltpu.VMEM((kv, g, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t, h, hd), q.dtype),
        interpret=interpret,
        name="paged_span_attention_rolling_quant",
    )(seq_idx, positions, offsets, n_valid, tbl, q, k8, ks, v8, vs,
      k_span, v_span, positions, seq_idx)
    return out.reshape(t, h * hd)
