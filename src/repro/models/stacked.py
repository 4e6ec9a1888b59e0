"""Generic scan-over-layer-groups machinery shared by all families.

A model is: embed -> [Stack...] -> final norm -> lm head.  Each Stack is a
group of layers scanned ``n`` times (weights stacked on a leading "layers"
axis) so the compiled HLO stays small regardless of depth.  Heterogeneous
patterns (e.g. 4 self-attn + 1 cross-attn) live *inside* one group and are
unrolled; the homogeneous repetition is the scan.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from repro.models.common import ParamSpec, ShardCtx, is_spec

PyTree = Any


@dataclasses.dataclass
class Ctx:
    """Per-call context threaded through block apply functions."""

    mode: str                      # train | prefill | decode | chunk
    shard: ShardCtx
    positions: jax.Array           # prefill: [S]; decode: [B]; chunk: [T]
    rope_cos: Optional[jax.Array] = None
    rope_sin: Optional[jax.Array] = None
    # chunk mode (packed ragged layout): batch row of each packed token [T]
    # and each row's span-start offset [B] (rolling-cache window attention)
    seq_idx: Optional[jax.Array] = None
    span_starts: Optional[jax.Array] = None
    n_valid: Optional[jax.Array] = None    # scalar: valid packed tokens
    # prefill mode: per-row real token counts [B] for RAGGED (right-padded)
    # batches — windowed models need them to keep pad-tail K/V out of the
    # rolling cache (None = batch is unpadded)
    seq_lens: Optional[jax.Array] = None
    patches: Optional[jax.Array] = None    # vlm cross-attn memory [B, P, d]
    enc_out: Optional[jax.Array] = None    # whisper encoder output [B, Se, d]
    # paged KV layout (decode/chunk): per-row physical block ids [B, nb];
    # when set, cache leaves are block-major [n_blocks, block_size, ...] and
    # attention reads/writes through the table (docs/memory.md)
    block_tables: Optional[jax.Array] = None
    kv_block: int = 512
    triangular: bool = False
    fuse_shared_expert: bool = False
    seq_shard: bool = False
    kv_quant: bool = False
    # set by run_stack alone (decode over a block-paged pool, for a stack
    # that addresses it): cache leaves are then the stack's whole carried
    # pool [n, n_blocks, bs, ...] and ``layer`` is the group's index into
    # its leading axis
    layer: Optional[jax.Array] = None


@dataclasses.dataclass
class Stack:
    """``apply(group_params, x, ctx, cache_group) -> (x, new_cache_group)``.

    In train mode ``apply`` must return cache ``None``; in prefill it
    returns the filled per-group cache; in decode it consumes and returns
    the updated per-group cache.
    """

    name: str
    n: int
    specs: PyTree
    apply: Callable
    cache_spec: Optional[Callable] = None  # (B, cache_len) -> per-group SDS tree
    cache_axes: Optional[Callable] = None  # () -> matching logical-axes tree
    # apply's blocks read and write a block-paged pool at ``ctx.layer``,
    # so decode can hand them the whole stacked pool, not a group slice
    addresses_stacked_pool: bool = False


def stack_specs(stack: Stack, axis_name: str = "layers") -> PyTree:
    return jax.tree.map(
        lambda s: ParamSpec((stack.n,) + s.shape, (axis_name,) + s.axes,
                            s.init, s.dtype, s.fan_in),
        stack.specs,
        is_leaf=is_spec,
    )


def run_stack(
    stack: Stack,
    params_stacked: PyTree,
    x: jax.Array,
    ctx: Ctx,
    cache_stacked: Optional[PyTree] = None,
    *,
    remat: bool = True,
) -> tuple:
    """Scan a stack; returns (x, stacked caches or None)."""
    if ctx.mode in ("decode", "chunk"):
        return _run_stack_carried(stack, params_stacked, x, ctx, cache_stacked)
    if stack.n == 1:
        gp = jax.tree.map(lambda p: p[0], params_stacked)
        fn = lambda g, xc: stack.apply(g, xc, ctx, None)
        if remat and ctx.mode == "train":
            fn = jax.checkpoint(fn)
        x, new_c = fn(gp, x)
        pack = (lambda t: jax.tree.map(lambda l: l[None], t)) if new_c is not None else (lambda t: None)
        return x, pack(new_c)

    def body(xc, gp):
        xo, cg = stack.apply(gp, xc, ctx, None)
        return xo, cg

    if remat and ctx.mode == "train":
        body = jax.checkpoint(body)
    x, caches = jax.lax.scan(body, x, params_stacked)
    return x, caches


# The chip's default layout for a pool leaf whose minor axis does not
# fill whole 128-lane tiles (a 64-wide head, the int8 pool's [.., kv]
# scales) puts another axis minor.  A scatter into the whole pool then
# makes the compiler convert all of it on the way into and out of the
# step, where a layer's slice converts only that layer.
_LANES = 128


def carries_whole_pool(stack: Stack, ctx: Ctx, cache: Optional[PyTree]) -> bool:
    """Whether the stack gets the whole carried pool and ``ctx.layer``:
    a decode step over a paged pool that the stack's blocks address and
    whose leaves' rows fill whole lane tiles.  A chunk step's span kernel
    reads one layer's [n_blocks, bs, ...] anyway; slicing it before the
    scatter lets the compiler keep that slice in on-chip memory, which a
    slice taken from the carried pool after the scatter is not given on
    the last stage."""
    return (ctx.mode == "decode" and stack.addresses_stacked_pool
            and ctx.block_tables is not None
            and all(c.shape[-1] % _LANES == 0 for c in jax.tree.leaves(cache)))


def _run_stack_carried(stack: Stack, params_stacked: PyTree, x: jax.Array,
                       ctx: Ctx, cache_stacked: Optional[PyTree]) -> tuple:
    """Decode/chunk: scan the parameters with ``(x, cache, layer)`` as the
    carry, so the stacked cache is updated in place (docs/memory.md).

    Where :func:`carries_whole_pool` holds, the stack gets the whole
    carried pool plus ``ctx.layer`` and scatters its dirty slots straight
    into it.  Otherwise it gets its group's slice of the carry and writes
    it back at the same index: either way there is no per-layer ``ys``
    buffer to copy out after the loop."""
    whole = carries_whole_pool(stack, ctx, cache_stacked)

    def body(carry, gp):
        xc, cache, i = carry
        if whole:
            xo, cache = stack.apply(gp, xc, dataclasses.replace(ctx, layer=i),
                                    cache)
        else:
            cg = jax.tree.map(
                lambda c: jax.lax.dynamic_index_in_dim(c, i, keepdims=False),
                cache)
            xo, ncg = stack.apply(gp, xc, ctx, cg)
            cache = jax.tree.map(
                lambda c, n: jax.lax.dynamic_update_index_in_dim(c, n, i, 0),
                cache, ncg)
        return (xo, cache, i + 1), None

    (x, cache, _), _ = jax.lax.scan(
        body, (x, cache_stacked, jnp.zeros((), jnp.int32)), params_stacked)
    return x, cache


def abstract_cache_tree(stack: Stack, batch: int, cache_len: int) -> Optional[PyTree]:
    if stack.cache_spec is None:
        return None
    per_group = stack.cache_spec(batch, cache_len)
    return jax.tree.map(
        lambda sd: jax.ShapeDtypeStruct((stack.n,) + sd.shape, sd.dtype), per_group
    )


def cache_axes_tree(stack: Stack) -> Optional[PyTree]:
    if stack.cache_axes is None:
        return None
    per_group = stack.cache_axes()
    return jax.tree.map(
        lambda ax: ("layers",) + ax,
        per_group,
        is_leaf=lambda x: isinstance(x, tuple) and all(isinstance(a, (str, type(None))) for a in x),
    )


def zeros_cache(abstract: PyTree) -> PyTree:
    return jax.tree.map(lambda sd: jnp.zeros(sd.shape, sd.dtype), abstract)
