"""Decoder-only transformer families: dense, moe, vlm.

One shared attention block; FFN varies (SwiGLU dense / sparse MoE); the
vlm family interleaves gated cross-attention layers attending to stubbed
patch embeddings (one per ``cross_attn_every`` self-attn layers).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import attention as attn
from repro.models.common import (
    ParamSpec,
    ShardCtx,
    apply_rope,
    pad_heads,
    rmsnorm,
    rope_tables,
)
from repro.models.moe import moe_ffn, moe_specs
from repro.models.stacked import Ctx, Stack

PyTree = Any


# ---------------------------------------------------------------------------
# Specs
# ---------------------------------------------------------------------------

def eff_kv_heads(cfg: ArchConfig, tp: int) -> int:
    """MHA (kv == q heads) pads kv together with q so GQA grouping holds;
    true GQA keeps kv unpadded (replicated when not tp-divisible)."""
    if cfg.num_kv_heads == cfg.num_heads:
        return pad_heads(cfg.num_heads, tp)
    return cfg.num_kv_heads


def attn_specs(cfg: ArchConfig, tp: int) -> Dict[str, ParamSpec]:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    hp = pad_heads(cfg.num_heads, tp)
    kvh = eff_kv_heads(cfg, tp)
    kv_ax = "kv_heads" if kvh % tp == 0 else None
    return {
        "ln": ParamSpec((d,), ("embed",), "ones"),
        "wq": ParamSpec((d, hp * hd), ("embed", "heads")),
        "wk": ParamSpec((d, kvh * hd), ("embed", kv_ax)),
        "wv": ParamSpec((d, kvh * hd), ("embed", kv_ax)),
        "wo": ParamSpec((hp * hd, d), ("heads", "embed"), fan_in=cfg.num_heads * hd),
    }


def mlp_specs(cfg: ArchConfig, tp: int) -> Dict[str, ParamSpec]:
    d, ff = cfg.d_model, cfg.d_ff
    return {
        "ln": ParamSpec((d,), ("embed",), "ones"),
        "w1": ParamSpec((d, ff), ("embed", "ff")),
        "w3": ParamSpec((d, ff), ("embed", "ff")),
        "w2": ParamSpec((ff, d), ("ff", "embed"), fan_in=ff),
    }


def cross_attn_specs(cfg: ArchConfig, tp: int) -> Dict[str, ParamSpec]:
    s = attn_specs(cfg, tp)
    d = cfg.d_model
    s["gate"] = ParamSpec((1,), (None,), "zeros", jnp.float32)
    s["ln_kv"] = ParamSpec((d,), ("embed",), "ones")
    return s


# ---------------------------------------------------------------------------
# Block applications
# ---------------------------------------------------------------------------

def _qkv(p, h, cfg: ArchConfig, tp: int):
    hd = cfg.resolved_head_dim
    hp = pad_heads(cfg.num_heads, tp)
    kvh = eff_kv_heads(cfg, tp)
    lead = h.shape[:-1]
    q = (h @ p["wq"]).reshape(*lead, hp, hd)
    k = (h @ p["wk"]).reshape(*lead, kvh, hd)
    v = (h @ p["wv"]).reshape(*lead, kvh, hd)
    return q, k, v


def _repeat_kv_for_pad(k: jax.Array, cfg: ArchConfig, tp: int) -> int:
    """Padded GQA group count (query heads per kv head, incl. padding)."""
    return pad_heads(cfg.num_heads, tp) // cfg.num_kv_heads


def self_attn_block(p, x, ctx: Ctx, cache, cfg: ArchConfig, *, causal=True,
                    use_rope=True):
    """Returns (x, new_cache).  cache = {"k","v"} or None (train/encoder)."""
    shard = ctx.shard
    tp = shard.tp
    w = cfg.window

    if ctx.mode == "decode":
        h = rmsnorm(x, p["ln"], cfg.norm_eps)            # x [B, d]
        q, k, v = _qkv(p, h, cfg, tp)                    # [B, H, hd]
        if use_rope:
            cos, sin = ctx.rope_cos[:, None, :], ctx.rope_sin[:, None, :]
            q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
        b = x.shape[0]
        slot = ctx.positions % w if w else ctx.positions
        if ctx.block_tables is not None:
            # paged KV: cache leaves are block-major [n_blocks, bs, ...]
            # (or, under ``ctx.layer``, the stack's carried pool with the
            # layer axis in front).  Scatter ONLY the new token's physical
            # (block, offset) slot — the dirty-slot write-back, in place —
            # then attend the per-row gathered view (decode's full softmax
            # reads every slot anyway; XLA fuses the gather, and masked
            # trash contributes exactly 0.0).
            tables = ctx.block_tables                    # [B, nb]
            bs = cache["k"].shape[-3]
            blk = jnp.minimum(slot // bs, tables.shape[1] - 1)
            at = (tables[jnp.arange(b), blk], slot % bs)
            if ctx.layer is not None:
                at = (ctx.layer,) + at
            new_cache = _paged_write(cache, at, k, v)
            view = {n: attn.gather_paged_cache(c, tables, ctx.layer)
                    for n, c in new_cache.items()}
            if "ks" in view:
                o = attn.decode_attention_quant(
                    q, view["k"], view["ks"], view["v"], view["vs"],
                    ctx.positions, rolling_window=w)
            else:
                o = attn.decode_attention(q, view["k"], view["v"],
                                          ctx.positions, rolling_window=w)
            return x + o @ p["wo"], new_cache
        rows = jnp.arange(b)
        if "ks" in cache:  # §Perf C1: int8 cache, s8xs8 attention dots
            k8, ks1 = attn.quantize_kv(k)
            v8, vs1 = attn.quantize_kv(v)
            new_cache = {
                "k": cache["k"].at[rows, slot].set(k8),
                "v": cache["v"].at[rows, slot].set(v8),
                "ks": cache["ks"].at[rows, slot].set(ks1),
                "vs": cache["vs"].at[rows, slot].set(vs1),
            }
            ca = _cache_axes(cfg, tp)
            new_cache = {kk: shard.constrain(vv, ca if vv.ndim == 4 else ca[:3])
                         for kk, vv in new_cache.items()}
            o = attn.decode_attention_quant(
                q, new_cache["k"], new_cache["ks"], new_cache["v"],
                new_cache["vs"], ctx.positions, rolling_window=w)
            return x + o @ p["wo"], new_cache
        kc = cache["k"].at[rows, slot].set(k)
        vc = cache["v"].at[rows, slot].set(v)
        kc = shard.constrain(kc, _cache_axes(cfg, tp))
        vc = shard.constrain(vc, _cache_axes(cfg, tp))
        o = attn.decode_attention(q, kc, vc, ctx.positions, rolling_window=w)
        x = x + o @ p["wo"]
        return x, {"k": kc, "v": vc}

    if ctx.mode == "chunk":
        # chunked prefill, packed ragged layout: x [T, d] is the batch's
        # valid span tokens concatenated (T = bucket width), with per-token
        # absolute positions [T] and batch rows ctx.seq_idx [T]; the cache
        # already holds all earlier chunks.  Bucket padding duplicates the
        # last valid token (same token, position AND row), so duplicate
        # cache scatters write identical values and stay deterministic.
        h = rmsnorm(x, p["ln"], cfg.norm_eps)            # x [T, d]
        q, k, v = _qkv(p, h, cfg, tp)                    # [T, H, hd]
        if use_rope:
            cos = ctx.rope_cos[:, None, :]               # [T, 1, hd/2]
            sin = ctx.rope_sin[:, None, :]
            q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
        si = ctx.seq_idx
        ca = _cache_axes(cfg, tp)
        if w:
            # rolling cache: attend (old cache + the span's own fresh K/V),
            # THEN scatter — scatter-first would overwrite window entries
            # earlier span tokens still need (see attention.py docstrings).
            offs = ctx.span_starts[si]                   # [T] row span start
            n_valid = ctx.n_valid if ctx.n_valid is not None else x.shape[0]
            if ctx.block_tables is not None:
                # paged rolling: attend the old physical cache through the
                # block table (plus the span's fresh K/V), THEN scatter
                # only the touched (block, offset) slots — scatter-first
                # would overwrite window entries earlier span tokens need.
                tables = ctx.block_tables
                bs = cache["k"].shape[1]
                slot = ctx.positions % w
                blk = jnp.minimum(slot // bs, tables.shape[1] - 1)
                at = (tables[si, blk], slot % bs)
                if "ks" in cache:
                    o = attn.paged_span_attention_rolling_quant_exec(
                        q, cache["k"], cache["ks"], cache["v"], cache["vs"],
                        k, v, tables, ctx.positions, si, offs, n_valid,
                        window=w)
                else:
                    o = attn.paged_span_attention_rolling_exec(
                        q, cache["k"], cache["v"], k, v, tables,
                        ctx.positions, si, offs, n_valid, window=w)
                return x + o @ p["wo"], _paged_write(cache, at, k, v)
            if "ks" in (cache or {}):
                o = attn.packed_span_attention_rolling_quant(
                    q, cache["k"], cache["ks"], cache["v"], cache["vs"],
                    k, v, ctx.positions, si, offs, n_valid, window=w)
                k8, ks1 = attn.quantize_kv(k)
                v8, vs1 = attn.quantize_kv(v)
                slot = ctx.positions % w
                new_cache = {
                    "k": cache["k"].at[si, slot].set(k8),
                    "v": cache["v"].at[si, slot].set(v8),
                    "ks": cache["ks"].at[si, slot].set(ks1),
                    "vs": cache["vs"].at[si, slot].set(vs1),
                }
                new_cache = {kk: shard.constrain(vv, ca if vv.ndim == 4
                                                 else ca[:3])
                             for kk, vv in new_cache.items()}
                return x + o @ p["wo"], new_cache
            o = attn.packed_span_attention_rolling(
                q, cache["k"], cache["v"], k, v, ctx.positions, si, offs,
                n_valid, window=w)
            slot = ctx.positions % w
            kc = shard.constrain(cache["k"].at[si, slot].set(k), ca)
            vc = shard.constrain(cache["v"].at[si, slot].set(v), ca)
            return x + o @ p["wo"], {"k": kc, "v": vc}
        if ctx.block_tables is not None:
            # paged full-cache chunk: dirty-slot scatter into the physical
            # blocks the span touches, then attend straight through the
            # table (per-tile gather, no [B, nb*bs] view).  Bucket-padding
            # duplicates write identical (block, offset, value) triples.
            tables = ctx.block_tables
            bs = cache["k"].shape[1]
            blk = jnp.minimum(ctx.positions // bs, tables.shape[1] - 1)
            kv = _paged_write(cache, (tables[si, blk], ctx.positions % bs),
                              k, v)
            if "ks" in kv:
                o = attn.paged_span_attention_quant_exec(
                    q, kv["k"], kv["ks"], kv["v"], kv["vs"], tables,
                    ctx.positions, si)
            else:
                o = attn.paged_span_attention_exec(q, kv["k"], kv["v"], tables,
                                                   ctx.positions, si)
            return x + o @ p["wo"], kv
        if "ks" in (cache or {}):
            k8, ks1 = attn.quantize_kv(k)
            v8, vs1 = attn.quantize_kv(v)
            new_cache = {
                "k": cache["k"].at[si, ctx.positions].set(k8),
                "v": cache["v"].at[si, ctx.positions].set(v8),
                "ks": cache["ks"].at[si, ctx.positions].set(ks1),
                "vs": cache["vs"].at[si, ctx.positions].set(vs1),
            }
            new_cache = {kk: shard.constrain(vv, ca if vv.ndim == 4 else ca[:3])
                         for kk, vv in new_cache.items()}
            o = attn.packed_span_attention_quant(
                q, new_cache["k"], new_cache["ks"], new_cache["v"],
                new_cache["vs"], ctx.positions, si)
            return x + o @ p["wo"], new_cache
        kc = shard.constrain(cache["k"].at[si, ctx.positions].set(k), ca)
        vc = shard.constrain(cache["v"].at[si, ctx.positions].set(v), ca)
        o = attn.packed_span_attention(q, kc, vc, ctx.positions, si)
        return x + o @ p["wo"], {"k": kc, "v": vc}

    h = rmsnorm(x, p["ln"], cfg.norm_eps)                # x [B, S, d]
    q, k, v = _qkv(p, h, cfg, tp)                        # [B, S, H, hd]
    if use_rope:
        cos, sin = ctx.rope_cos[None, :, None, :], ctx.rope_sin[None, :, None, :]
        q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
    q = shard.constrain(q, ("batch", None, "heads", None))
    if not causal:
        o = attn.chunked_attention(q, k, v, causal=False, kv_block=ctx.kv_block)
    elif w:
        o = attn.local_attention(q, k, v, window=w, q_block=min(ctx.kv_block, w))
    else:
        o = attn.chunked_attention(
            q, k, v, causal=True, kv_block=ctx.kv_block,
            q_positions=ctx.positions, triangular=ctx.triangular,
        )
    x = x + o @ p["wo"]
    new_cache = None
    if ctx.mode == "prefill" and cache is not False:
        if w:
            if ctx.seq_lens is not None:
                # ragged (right-padded) batch: gather by per-row position
                # so pad-tail K/V never reaches a rolling slot
                kc = attn.fill_rolling_cache_ragged(k, w, ctx.seq_lens)
                vc = attn.fill_rolling_cache_ragged(v, w, ctx.seq_lens)
            else:
                kc = attn.fill_rolling_cache(k, w)
                vc = attn.fill_rolling_cache(v, w)
        else:
            kc, vc = k, v
        ca = _cache_axes(cfg, tp)
        if ctx.kv_quant:
            k8, ks = attn.quantize_kv(kc)
            v8, vs = attn.quantize_kv(vc)
            new_cache = {
                "k": shard.constrain(k8, ca), "v": shard.constrain(v8, ca),
                "ks": shard.constrain(ks, ca[:3]),
                "vs": shard.constrain(vs, ca[:3]),
            }
        else:
            new_cache = {
                "k": shard.constrain(kc, ca),
                "v": shard.constrain(vc, ca),
            }
    return x, new_cache


def _paged_write(cache, at, k: jax.Array, v: jax.Array) -> Dict:
    """Scatter the new K/V into the paged slots ``at`` (int8 with its
    scales where the pool is quantized)."""
    if "ks" not in cache:
        return {"k": cache["k"].at[at].set(k), "v": cache["v"].at[at].set(v)}
    (k8, ks), (v8, vs) = attn.quantize_kv(k), attn.quantize_kv(v)
    return {"k": cache["k"].at[at].set(k8), "v": cache["v"].at[at].set(v8),
            "ks": cache["ks"].at[at].set(ks), "vs": cache["vs"].at[at].set(vs)}


def _cache_axes(cfg: ArchConfig, tp: int) -> Tuple:
    kvh = eff_kv_heads(cfg, tp)
    if kvh % tp == 0 and kvh >= tp:
        return ("batch", None, "kv_heads", None)
    return ("batch", "kv_seq", None, None)


def mlp_block(p, x, cfg: ArchConfig, shard: ShardCtx):
    h = rmsnorm(x, p["ln"], cfg.norm_eps)
    a = jax.nn.silu(h @ p["w1"]) * (h @ p["w3"])
    a = shard.constrain(a, ("batch", None, "ff") if a.ndim == 3 else ("batch", "ff"))
    return x + a @ p["w2"]


def moe_block(p, x, cfg: ArchConfig, shard: ShardCtx, *, fuse_shared=False):
    h = rmsnorm(x, p["ln"], cfg.norm_eps)
    squeeze = h.ndim == 2
    h3 = h[:, None, :] if squeeze else h
    has_shared = "shared_w1" in p
    if has_shared and fuse_shared:
        # §Perf B1: shared-expert partials join the routed-expert psum
        shared = {"w1": p["shared_w1"], "w3": p["shared_w3"],
                  "w2": p["shared_w2"]}
        y = moe_ffn(h3, p["moe"], cfg.moe, shard, shared=shared)
    else:
        y = moe_ffn(h3, p["moe"], cfg.moe, shard)
        if has_shared:  # baseline: separate dense shared-expert branch
            a = jax.nn.silu(h3 @ p["shared_w1"]) * (h3 @ p["shared_w3"])
            y = y + a @ p["shared_w2"]
    y = y[:, 0, :] if squeeze else y
    return x + y


def cross_attn_block(p, x, ctx: Ctx, cache, cfg: ArchConfig):
    """Gated cross-attention to ctx.patches / ctx.enc_out.

    prefill: computes the memory's K/V and returns them as cache.
    decode:  reuses cached K/V.
    """
    shard = ctx.shard
    tp = shard.tp
    hd = cfg.resolved_head_dim
    kvh = eff_kv_heads(cfg, tp)
    gate = jnp.tanh(p["gate"].astype(jnp.float32))[0]

    h = rmsnorm(x, p["ln"], cfg.norm_eps)
    lead = h.shape[:-1]
    q = (h @ p["wq"]).reshape(*lead, pad_heads(cfg.num_heads, tp), hd)

    if ctx.mode == "decode":
        kc, vc = cache["k"], cache["v"]
        o = attn.decode_attention(q, kc, vc, positions=None)
        return x + (gate * (o @ p["wo"]).astype(jnp.float32)).astype(x.dtype), cache

    mem = ctx.patches if ctx.patches is not None else ctx.enc_out
    m = rmsnorm(mem, p["ln_kv"], cfg.norm_eps)
    k = (m @ p["wk"]).reshape(*mem.shape[:-1], kvh, hd)
    v = (m @ p["wv"]).reshape(*mem.shape[:-1], kvh, hd)
    o = attn.cross_attention(q, k, v, kv_block=ctx.kv_block)
    x = x + (gate * (o @ p["wo"]).astype(jnp.float32)).astype(x.dtype)
    new_cache = None
    if ctx.mode == "prefill":
        new_cache = {
            "k": shard.constrain(k, ("batch", None, None, None)),
            "v": shard.constrain(v, ("batch", None, None, None)),
        }
    return x, new_cache


# ---------------------------------------------------------------------------
# Stacks per family
# ---------------------------------------------------------------------------

def _self_cache_spec(cfg: ArchConfig, tp: int = 1, dtype=jnp.bfloat16,
                     quant: bool = False):
    hd = cfg.resolved_head_dim

    def spec(batch: int, cache_len: int):
        # rolling caches are always exactly window-sized: the decode path
        # indexes slots by position %% window, so the buffer cannot shrink
        # even when the requested cache_len is shorter
        s = cfg.window if cfg.window else cache_len
        kvh = eff_kv_heads(cfg, tp)
        if quant:
            sd = jax.ShapeDtypeStruct((batch, s, kvh, hd), jnp.int8)
            sc = jax.ShapeDtypeStruct((batch, s, kvh), jnp.bfloat16)
            return {"k": sd, "v": sd, "ks": sc, "vs": sc}
        sd = jax.ShapeDtypeStruct((batch, s, kvh, hd), dtype)
        return {"k": sd, "v": sd}

    return spec


def _self_cache_axes(cfg: ArchConfig, tp: int, quant: bool = False):
    def axes():
        a = _cache_axes(cfg, tp)
        if quant:
            return {"k": a, "v": a, "ks": a[:3], "vs": a[:3]}
        return {"k": a, "v": a}

    return axes


def dense_layer_stack(cfg: ArchConfig, tp: int, n: int, *, moe_every: int = 0,
                      shared_expert: bool = False,
                      kv_quant: bool = False) -> Stack:
    """n groups; each group = ``moe_every`` layers with the last one MoE
    (moe_every=0 -> single dense layer per group)."""
    per = max(moe_every, 1)
    layer_specs = []
    for i in range(per):
        is_moe = cfg.moe is not None and (moe_every == 0 or i == per - 1) and (
            moe_every > 0 or cfg.moe is not None
        )
        if cfg.moe is None:
            is_moe = False
        spec = {"attn": attn_specs(cfg, tp)}
        if is_moe:
            ffn = {"ln": ParamSpec((cfg.d_model,), ("embed",), "ones"),
                   "moe": moe_specs(cfg.d_model, cfg.moe, tp)}
            if shared_expert:
                ff = cfg.moe.expert_d_ff or cfg.d_ff
                ffn.update(
                    shared_w1=ParamSpec((cfg.d_model, ff), ("embed", "ff")),
                    shared_w3=ParamSpec((cfg.d_model, ff), ("embed", "ff")),
                    shared_w2=ParamSpec((ff, cfg.d_model), ("ff", "embed"), fan_in=ff),
                )
            spec["ffn"] = ffn
            spec["ffn_kind"] = "moe"
        else:
            spec["ffn"] = mlp_specs(cfg, tp)
            spec["ffn_kind"] = "mlp"
        layer_specs.append(spec)

    kinds = tuple(s.pop("ffn_kind") for s in layer_specs)
    group_specs = {f"l{i}": s for i, s in enumerate(layer_specs)}

    def apply(gp, x, ctx: Ctx, cache_g):
        new_caches = {}
        for i in range(per):
            p = gp[f"l{i}"]
            c = cache_g[f"l{i}"] if cache_g is not None else None
            if ctx.seq_shard and x.ndim == 3:
                # §Perf B2: residual stream sequence-sharded between blocks
                x = ctx.shard.constrain(x, ("batch", "seq_sp", None))
            x, nc = self_attn_block(p["attn"], x, ctx, c, cfg)
            if nc is not None:
                new_caches[f"l{i}"] = nc
            if ctx.seq_shard and x.ndim == 3:
                x = ctx.shard.constrain(x, ("batch", "seq_sp", None))
            if kinds[i] == "moe":
                x = moe_block(p["ffn"], x, cfg, ctx.shard,
                              fuse_shared=ctx.fuse_shared_expert)
            else:
                x = mlp_block(p["ffn"], x, cfg, ctx.shard)
        return x, (new_caches or None)

    cspec = _self_cache_spec(cfg, tp, quant=kv_quant)

    def cache_spec(batch, cache_len):
        return {f"l{i}": cspec(batch, cache_len) for i in range(per)}

    caxes = _self_cache_axes(cfg, tp, quant=kv_quant)

    def cache_axes():
        return {f"l{i}": caxes() for i in range(per)}

    return Stack("blocks", n, group_specs, apply, cache_spec, cache_axes,
                 addresses_stacked_pool=True)


def vlm_stack(cfg: ArchConfig, tp: int) -> Stack:
    """Groups of (cross_attn_every self layers + 1 cross layer)."""
    per = cfg.cross_attn_every
    n = cfg.num_layers // (per + 1)
    assert n * (per + 1) == cfg.num_layers, "vlm layer count must factor"
    group_specs = {f"self{i}": {"attn": attn_specs(cfg, tp), "ffn": mlp_specs(cfg, tp)}
                   for i in range(per)}
    group_specs["cross"] = {"attn": cross_attn_specs(cfg, tp),
                            "ffn": mlp_specs(cfg, tp)}

    def apply(gp, x, ctx: Ctx, cache_g):
        new_caches = {}
        for i in range(per):
            p = gp[f"self{i}"]
            c = cache_g[f"self{i}"] if cache_g is not None else None
            x, nc = self_attn_block(p["attn"], x, ctx, c, cfg)
            if nc is not None:
                new_caches[f"self{i}"] = nc
            x = mlp_block(p["ffn"], x, cfg, ctx.shard)
        c = cache_g["cross"] if cache_g is not None else None
        x, nc = cross_attn_block(gp["cross"]["attn"], x, ctx, c, cfg)
        if nc is not None:
            new_caches["cross"] = nc
        x = mlp_block(gp["cross"]["ffn"], x, cfg, ctx.shard)
        return x, (new_caches or None)

    cspec = _self_cache_spec(cfg, tp)
    hd = cfg.resolved_head_dim

    def cache_spec(batch, cache_len):
        d = {f"self{i}": cspec(batch, cache_len) for i in range(per)}
        sd = jax.ShapeDtypeStruct((batch, cfg_n_patches(cfg), eff_kv_heads(cfg, tp), hd),
                                  jnp.bfloat16)
        d["cross"] = {"k": sd, "v": sd}
        return d

    caxes = _self_cache_axes(cfg, tp)

    def cache_axes():
        d = {f"self{i}": caxes() for i in range(per)}
        a = ("batch", None, None, None)
        d["cross"] = {"k": a, "v": a}
        return d

    return Stack("blocks", n, group_specs, apply, cache_spec, cache_axes)


def cfg_n_patches(cfg: ArchConfig) -> int:
    """Stubbed vision frontend: 4 tiles x 40x40 patches = 6400."""
    return 6400
