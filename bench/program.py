"""The system under test, as the benchmark drives it: the program's
model built at a configuration file's widths, the benchmark's weights
put into the program's parameter tree, and the HTTP server over one
SiPipe engine replica.  Besides these, the harness takes from the
program only its compile-cache switch and its chunk bucket rule
(``bench/run.py``); the reference takes nothing."""
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
    the file's widths.  A width the program would change is an error."""
    from repro.configs import get_config

    base = get_config(cfg["program"]["arch"])
    arch = dataclasses.replace(
        base, **{k: type(getattr(base, k))(cfg[v])
                 for k, v in ARCH_KEYS.items()})
    if arch.family != "dense" or arch.window or arch.moe is not None:
        raise ValueError(f"{cfg['name']}: the benchmark's reference covers "
                         f"the dense block only, not {arch.family}")
    return arch


def build_model(cfg: dict, kv_quant: bool = False):
    from repro.models import ModelOptions, ShardCtx, build_model as bm

    arch = arch_config(cfg)
    return arch, bm(arch, ShardCtx.single(), ModelOptions(kv_quant=kv_quant))


def program_params(model, w: dict) -> dict:
    """The benchmark's weights in the program's tree (the same arrays,
    regrouped; nothing is copied).  Shapes and types are checked against
    the program's own abstract parameters."""
    import jax

    tree = {
        "embed": w["embed"], "lnf": w["lnf"], "head": w["head"],
        "stacks": {"blocks": {"l0": {
            "attn": {"ln": w["ln_attn"], "wq": w["wq"], "wk": w["wk"],
                     "wv": w["wv"], "wo": w["wo"]},
            "ffn": {"ln": w["ln_mlp"], "w1": w["w1"], "w3": w["w3"],
                    "w2": w["w2"]},
        }}},
    }
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
