"""The system under test, as the benchmark drives it: the HTTP server
over one SiPipe engine replica (:func:`build_server`), and the dense
block's mapping into the program (:func:`build_model`,
:func:`program_params`, used through ``bench/blocks/dense.py``).

Which model the engine serves, and how the weights enter its parameter
tree, is the configuration's block's (``bench/blocks/<name>.py``); a
block checks its tree with :func:`fit`.  Besides the server and the
model, ``bench/run.py`` takes from the program its compile-cache
switch, its chunk bucket rule, the stage step functions (warm-up), the
stage executors' step hooks (traced runs), the engine's counters and
request records, and, in the trace, its spans and program and kernel
names.  The reference takes nothing."""
from __future__ import annotations

import dataclasses
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))

# the widths a configuration file states, by the program's names
ARCH_KEYS = {
    "num_layers": "num_hidden_layers",
    "d_model": "hidden_size",
    "num_heads": "num_attention_heads",
    "num_kv_heads": "num_key_value_heads",
    "head_dim": "head_dim",
    "d_ff": "intermediate_size",
    "vocab_size": "vocab_size",
    "rope_theta": "rope_theta",
    "norm_eps": "rms_norm_eps",
}


def arch_config(cfg: dict):
    """The program's ArchConfig for ``cfg``: its named architecture with
    the file's dense widths (``bench/blocks/dense.py`` refuses any
    other family)."""
    from repro.configs import get_config

    base = get_config(cfg["program"]["arch"])
    return dataclasses.replace(
        base, **{k: type(getattr(base, k))(cfg[v])
                 for k, v in ARCH_KEYS.items()})


def build_model(cfg: dict, kv_quant: bool = False):
    from repro.models import ModelOptions, ShardCtx, build_model as bm

    arch = arch_config(cfg)
    return arch, bm(arch, ShardCtx.single(), ModelOptions(kv_quant=kv_quant))


def program_params(model, w: dict) -> dict:
    """The dense block's weights in the program's tree (the same arrays,
    regrouped; nothing is copied), checked by :func:`fit`."""
    return fit(model, {
        "embed": w["embed"], "lnf": w["lnf"], "head": w["head"],
        "stacks": {"blocks": {"l0": {
            "attn": {"ln": w["ln_attn"], "wq": w["wq"], "wk": w["wk"],
                     "wv": w["wv"], "wo": w["wo"]},
            "ffn": {"ln": w["ln_mlp"], "w1": w["w1"], "w3": w["w3"],
                    "w2": w["w2"]},
        }}},
    })


def fit(model, tree: dict) -> dict:
    """``tree``, after checking its structure, shapes and types against
    the program's own abstract parameters."""
    import jax

    want = model.abstract_params()
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise ValueError("the benchmark's weights do not fit the program's "
                         f"parameter tree: {want} vs {got}")
    return tree


def engine_config(cfg: dict, seed: int):
    from repro.core.engine import EngineConfig

    e = cfg["engine"]
    return EngineConfig(
        pp_degree=e["pp"], max_batch=e["max_batch"],
        max_seq_len=e["max_seq_len"], n_samplers=e["n_samplers"],
        prefill_chunk_tokens=e["chunk_tokens"],
        scheduling_policy=e["policy"], kv_layout="paged",
        kv_block_size=e["block_size"], kv_blocks=e["kv_blocks"],
        max_table_buckets=e["max_table_buckets"], seed=seed)


def build_server(cfg: dict, model, params, seed: int):
    """The user's path: HTTP front end, admission, a router with one
    replica, one SiPipeEngine.  Returns (server, engine); not started."""
    from repro.core.engine import SiPipeEngine
    from repro.serving import CompletionServer, EngineReplica, Router

    eng = SiPipeEngine(model, params, engine_config(cfg, seed))
    server = CompletionServer(
        Router([EngineReplica("r0", eng)]), vocab_size=model.cfg.vocab_size,
        model_name=cfg["name"], max_queue=cfg["engine"]["max_queue"])
    return server, eng
