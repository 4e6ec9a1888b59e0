"""Pipeline-parallel step builders (shard_map, manual over the "pipe" axis).

This is the paper's deployment regime expressed as a single SPMD program
for the multi-pod dry-run: p pipeline stages x t-way tensor parallelism
x data parallelism, on a ("pipe", "data", "model") view of the production
device set (mesh.make_pipeline_mesh).

Decode runs as a *steady-state round*: one jitted call advances all p
in-flight microbatches by one full iteration.  Each of the p ticks inside
the round, stage s processes microbatch (t - s) mod p and ppermutes its
activation to stage s+1 — all stages stay busy every tick, which is the
zero-bubble steady state SiPipe's host-side machinery sustains (the
engine-level techniques keep the gaps BETWEEN these device steps empty;
this module is the device-side program those steps execute).

Embedding and LM head run OUTSIDE the manual region under plain GSPMD
(vocab-sharded over "model"), so their FLOPs are not replicated p times.

The stage body itself stays under GSPMD "auto" for the data/model axes —
TP sharding inside a stage is inherited from the operand shardings, which
is exactly the hybrid PP+TP deployment (p stages x t-way TP) of the paper.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.registry import Model
from repro.models.stacked import run_stack

PyTree = Any


@dataclasses.dataclass
class PPPlan:
    p: int                         # pipeline degree
    microbatch: int                # sequences per microbatch
    mesh: Mesh                     # ("pipe", "data", "model")
    groups_per_stage: int


def plan_pp(model: Model, mesh: Mesh, global_batch: int) -> PPPlan:
    p = dict(zip(mesh.axis_names, mesh.devices.shape))["pipe"]
    st = model.stacks["blocks"]
    assert st.n % p == 0, f"{st.n} scan groups not divisible by pipe={p}"
    assert global_batch % p == 0, (global_batch, p)
    return PPPlan(p, global_batch // p, mesh, st.n // p)


def _restack(params_blocks: PyTree, p: int, gps: int) -> PyTree:
    """[n_groups, ...] -> [p, groups_per_stage, ...] for pipe sharding."""
    return jax.tree.map(lambda x: x.reshape((p, gps) + x.shape[1:]), params_blocks)


def restack_abstract(model: Model, plan: PPPlan):
    import repro.models.common as mc

    abs_p = mc.abstract_params(model.specs)
    blocks = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((plan.p, plan.groups_per_stage) + s.shape[1:],
                                       s.dtype),
        abs_p["stacks"]["blocks"])
    return {**abs_p, "stacks": {"blocks": blocks}}


def pp_decode_round(model: Model, plan: PPPlan) -> Callable:
    """Returns step(params, caches, inflight, tokens, positions) ->
    (logits [p, B_m, V], caches, inflight).

    params["stacks"]["blocks"] must be re-stacked [p, gps, ...].
    caches: model cache trees with leading [p_stage, p_micro, ...].
    inflight: [p, B_m, d] cross-round activations (zeros initially; the
    first p rounds are warmup).
    tokens/positions: [p, B_m] per microbatch.
    """
    p = plan.p
    st = model.stacks["blocks"]
    sub = dataclasses.replace(st, n=plan.groups_per_stage)
    d = model.cfg.d_model
    perm = [(i, (i + 1) % p) for i in range(p)]

    def stage_body(stage_l, blocks_l, caches_l, inflight_l, embeds, positions):
        # stage_l [1]; blocks_l [1, gps, ...]; caches_l [1, p, gps, ...];
        # inflight_l [1, B_m, d].  The stage index arrives as a pipe-sharded
        # operand rather than lax.axis_index: partition-id does not lower
        # under partial-auto SPMD on older XLA versions.
        s = stage_l[0]
        blocks_l = jax.tree.map(lambda x: x[0], blocks_l)
        caches_l = jax.tree.map(lambda x: x[0], caches_l)
        x0 = inflight_l[0]

        def tick(carry, t):
            x, caches = carry
            m = (t - s) % p
            x_in = jnp.where(s == 0, embeds[m].astype(x.dtype), x)
            cache_m = jax.tree.map(lambda c: c[m], caches)
            ctx = model.make_ctx("decode", positions[m])
            x_out, cache_m = run_stack(sub, blocks_l, x_in, ctx,
                                       cache_stacked=cache_m, remat=False)
            caches = jax.tree.map(
                lambda c, n: jax.lax.dynamic_update_index_in_dim(c, n, m, 0),
                caches, cache_m)
            emit = jnp.where(s == p - 1, x_out, jnp.zeros_like(x_out))
            x_next = jax.lax.ppermute(x_out, "pipe", perm)
            return (x_next, caches), emit

        (x_fin, caches_l), emits = jax.lax.scan(tick, (x0, caches_l),
                                                jnp.arange(p))
        pack = lambda t: jax.tree.map(lambda a: a[None], t)
        return pack(caches_l), x_fin[None], emits[None]

    smapped = jax.shard_map(
        stage_body, mesh=plan.mesh, axis_names={"pipe"}, check_vma=False,
        in_specs=(P("pipe"), P("pipe"), P("pipe"), P("pipe"), P(None), P(None)),
        out_specs=(P("pipe"), P("pipe"), P("pipe")))

    def step(params, caches, inflight, tokens, positions):
        # embed all p microbatches under plain GSPMD (vocab-sharded gather)
        embeds = model.embed_tokens(params, tokens)          # [p, B_m, d]
        caches, inflight, emits = smapped(
            jnp.arange(p, dtype=jnp.int32),
            params["stacks"]["blocks"], caches, inflight, embeds, positions)
        # emits[p_stage, tick, B_m, d]: only the last stage's row is live.
        hidden = emits[-1]                                   # [ticks, B_m, d]
        # tick t emitted microbatch (t - (p-1)) mod p -> reorder to m-order
        order = jnp.array([(m + p - 1) % p for m in range(p)])
        hidden = jnp.take(hidden, order, axis=0)
        logits = model.lm_head(params, hidden)               # [p, B_m, V]
        return logits, caches, inflight

    return step


def pp_shardings(model: Model, plan: PPPlan, batch_shape: Tuple[int, int]):
    """NamedShardings for (params, caches, inflight, tokens, positions)."""
    from repro import sharding as shlib
    import repro.models.common as mc

    mesh = plan.mesh
    abs_p = restack_abstract(model, plan)
    ax_p = mc.logical_axes(model.specs)
    ax_blocks = jax.tree.map(
        lambda ax: ("stage",) + ax,
        ax_p["stacks"]["blocks"],
        is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(a, (str, type(None))) for a in x),
    )
    ax_p = {**ax_p, "stacks": {"blocks": ax_blocks}}
    p_sh = shlib.tree_shardings(ax_p, abs_p, "pp", mesh)

    def cache_sh(abs_cache, ax_cache):
        # per-tensor axes ("layers", *t) -> ("stage", micro, gps, *t)
        ax = jax.tree.map(
            lambda a: ("stage", None, None) + a[1:],
            ax_cache,
            is_leaf=lambda x: isinstance(x, tuple) and all(
                isinstance(aa, (str, type(None))) for aa in x),
        )
        return shlib.tree_shardings(ax, abs_cache, "pp", mesh)

    ns = lambda *spec: NamedSharding(mesh, P(*spec))
    v = model.cfg.vocab_size
    logits_sh = shlib.named_sharding((None, "batch", "vocab"),
                                     (plan.p, plan.microbatch, v), "pp", mesh)
    return {
        "params": p_sh,
        "params_abstract": abs_p,
        "cache_sharding_fn": cache_sh,
        "inflight": ns("pipe", "data"),
        "tokens": ns(None, "data"),
        "positions": ns(None, "data"),
        "logits": logits_sh,
    }


def pp_abstract_cache(model: Model, plan: PPPlan, cache_len: int):
    """Cache tree with leading [p_stage, p_micro, gps, B_m, ...]."""
    base = model.abstract_cache(plan.microbatch, cache_len)["blocks"]

    def expand(sd):
        gps = plan.groups_per_stage
        # base leading dim is n_groups = p * gps -> [p, micro(p), gps, ...]
        return jax.ShapeDtypeStruct((plan.p, plan.p, gps) + sd.shape[1:], sd.dtype)

    return jax.tree.map(expand, base)
