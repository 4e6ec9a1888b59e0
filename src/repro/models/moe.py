"""Sparse Mixture-of-Experts FFN with sort-based dispatch.

Design notes (TPU adaptation, see DESIGN.md):
  * Dispatch uses argsort + gather/scatter-add — NOT the one-hot einsum
    formulation — so compiled FLOPs stay proportional to *active* experts
    (roofline ratio MODEL_FLOPS/HLO_FLOPs stays ~1) and no [T, E, C]
    dispatch tensor is ever materialized.
  * Expert parallelism runs under shard_map: activations are replicated
    along the "model" mesh axis (they are batch-sharded along data axes),
    so every model-rank routes identically, computes its *local* experts,
    and a single psum combines — collective volume equals one TP
    all-reduce, with no all-to-all required.
  * When num_experts %% tp != 0 (mixtral: 8 experts, tp=16) expert weights
    are replicated and their FFN dim is tensor-sharded instead; the same
    psum then combines partial ff products.  Both variants share this code.
  * Under the pipeline ("pp") strategy the surrounding stage is already a
    shard_map region, so the plain-jnp path runs and GSPMD auto-partitions
    it (decode activations are tiny there).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.configs.base import MoEConfig
from repro.models.common import ParamSpec, ShardCtx


def moe_specs(d_model: int, moe: MoEConfig, tp: int) -> dict:
    e, ff = moe.num_experts, (moe.expert_d_ff or 0)
    assert ff > 0
    ep = e % tp == 0  # expert-parallel vs. ff-tensor-parallel
    ax_e = "experts" if ep else None
    ax_ff = None if ep else "expert_ff"
    return {
        "router": ParamSpec((d_model, e), ("embed", None), "small"),
        "w1": ParamSpec((e, d_model, ff), (ax_e, "embed", ax_ff)),
        "w3": ParamSpec((e, d_model, ff), (ax_e, "embed", ax_ff)),
        "w2": ParamSpec((e, ff, d_model), (ax_e, ax_ff, "embed"), fan_in=ff),
    }


def _capacity(tokens: int, moe: MoEConfig) -> int:
    c = int(math.ceil(tokens * moe.top_k * moe.capacity_factor / moe.num_experts))
    return max(8, int(math.ceil(c / 8)) * 8) if tokens >= 64 else max(c, 4)


def _moe_local(x2d, params, moe: MoEConfig, *, axis_name: Optional[str],
               n_local: int, shared: Optional[dict] = None):
    """Per-device MoE over local tokens x2d [T, d].

    ``n_local`` = experts computed on this device (== num_experts unless
    expert-parallel under shard_map).  ``shared`` (optional, §Perf B1):
    llama4-style shared-expert weights with the ff dim model-sharded; its
    partial product folds into the SAME psum as the routed experts,
    saving one activation all-reduce per MoE layer (fwd and bwd).
    """
    t, d = x2d.shape
    e, k = moe.num_experts, moe.top_k
    cap = _capacity(t, moe)
    ep_sharded = axis_name is not None and n_local < e

    logits = (x2d @ params["router"]).astype(jnp.float32)  # [T, E]
    gate_vals, ids = jax.lax.top_k(logits, k)              # [T, k]
    gates = jax.nn.softmax(gate_vals, axis=-1)             # renormalized over selected

    expert_flat = ids.reshape(-1)                          # [T*k], token-major
    gate_flat = gates.reshape(-1)
    token_flat = jnp.arange(t * k) // k

    order = jnp.argsort(expert_flat)                       # stable
    se = expert_flat[order]
    st = token_flat[order]
    sg = gate_flat[order]
    starts = jnp.searchsorted(se, jnp.arange(e))
    pos = jnp.arange(t * k) - starts[se]                   # slot within expert

    e_lo = jax.lax.axis_index(axis_name) * n_local if ep_sharded else 0
    local = (se >= e_lo) & (se < e_lo + n_local) & (pos < cap)
    dest = jnp.where(local, (se - e_lo) * cap + pos, n_local * cap)  # dump row

    xb = jnp.zeros((n_local * cap + 1, d), x2d.dtype).at[dest].add(x2d[st])
    h = xb[: n_local * cap].reshape(n_local, cap, d)

    a = jnp.einsum("ecd,edf->ecf", h, params["w1"])
    b = jnp.einsum("ecd,edf->ecf", h, params["w3"])
    y = jnp.einsum("ecf,efd->ecd", jax.nn.silu(a) * b, params["w2"])  # [E_loc,C,d]

    y_flat = jnp.concatenate([y.reshape(n_local * cap, d), jnp.zeros((1, d), y.dtype)], 0)
    contrib = y_flat[dest] * sg[:, None].astype(y.dtype)
    out = jnp.zeros((t, d), y.dtype).at[st].add(jnp.where(local[:, None], contrib, 0))
    if shared is not None:  # partial over the local ff shard
        a = jax.nn.silu(x2d @ shared["w1"]) * (x2d @ shared["w3"])
        out = out + (a @ shared["w2"]).astype(out.dtype)
    if axis_name is not None:
        out = jax.lax.psum(out, axis_name)
    return out.astype(x2d.dtype)


def moe_ffn(x: jax.Array, params: dict, moe: MoEConfig, shard: ShardCtx,
            shared: Optional[dict] = None) -> jax.Array:
    """x [B, S, d] -> [B, S, d].  Runs under shard_map when a mesh is present."""
    b, s, d = x.shape
    mesh = shard.mesh

    def plain(xl, pl, sh):
        return _moe_local(xl.reshape(-1, d), pl, moe, axis_name=None,
                          n_local=moe.num_experts, shared=sh).reshape(xl.shape)

    if (
        mesh is None
        or math.prod(mesh.devices.shape) == 1
        or shard.strategy == "pp"
        or shard.tp == 1
    ):
        return plain(x, params, shared)

    mesh_shape = dict(zip(mesh.axis_names, mesh.devices.shape))
    ep = moe.num_experts % shard.tp == 0
    n_local = moe.num_experts // shard.tp if ep else moe.num_experts

    data_axes = tuple(a for a in shard.data_axes if a in mesh_shape)
    dp = math.prod(mesh_shape[a] for a in data_axes)
    if data_axes and b % dp == 0:
        x_spec = P(data_axes if len(data_axes) > 1 else data_axes[0], None, None)
    else:
        x_spec = P(None, None, None)  # tiny batches stay replicated

    w_e = P("model", None, None) if ep else P(None, None, "model")
    w2_e = P("model", None, None) if ep else P(None, "model", None)
    pspecs = {"router": P(None, None), "w1": w_e, "w3": w_e, "w2": w2_e}
    shared_specs = {"w1": P(None, "model"), "w3": P(None, "model"),
                    "w2": P("model", None)} if shared is not None else None

    def inner(xl, pl, sh):
        y = _moe_local(xl.reshape(-1, d), pl, moe, axis_name="model",
                       n_local=n_local, shared=sh)
        return y.reshape(xl.shape)

    fn = shard_map(
        inner,
        mesh=mesh,
        in_specs=(x_spec, pspecs, shared_specs),
        out_specs=x_spec,
        check_vma=False,
    )
    return fn(x, params, shared)
