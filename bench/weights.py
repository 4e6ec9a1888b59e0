"""Seeded random weights for a dense decoder, made on the device.

One jitted call draws every leaf from ``--seed`` straight in bfloat16,
the type the model is served in, in the benchmark's own layout (one
array per kind of matrix, stacked over layers).  The same seed gives
the same weights, so the plain reference makes them again after the
program's state is freed instead of keeping anything the program held.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp


def dims(cfg: dict) -> dict:
    """The widths the benchmark code reads, from a configuration file."""
    return {
        "layers": int(cfg["num_hidden_layers"]),
        "d": int(cfg["hidden_size"]),
        "heads": int(cfg["num_attention_heads"]),
        "kv_heads": int(cfg["num_key_value_heads"]),
        "hd": int(cfg["head_dim"]),
        "ff": int(cfg["intermediate_size"]),
        "vocab": int(cfg["vocab_size"]),
        "rope_theta": float(cfg["rope_theta"]),
        "eps": float(cfg["rms_norm_eps"]),
    }


def shapes(m: dict) -> dict:
    """Leaf name -> (shape, init, fan_in) in the benchmark's layout."""
    L, d, h, kv, hd, ff, v = (m[k] for k in
                              ("layers", "d", "heads", "kv_heads", "hd",
                               "ff", "vocab"))
    return {
        "embed": ((v, d), "embed", 0),
        "ln_attn": ((L, d), "norm", 0),
        "wq": ((L, d, h * hd), "normal", d),
        "wk": ((L, d, kv * hd), "normal", d),
        "wv": ((L, d, kv * hd), "normal", d),
        "wo": ((L, h * hd, d), "normal", h * hd),
        "ln_mlp": ((L, d), "norm", 0),
        "w1": ((L, d, ff), "normal", d),
        "w3": ((L, d, ff), "normal", d),
        "w2": ((L, ff, d), "normal", ff),
        "lnf": ((d,), "norm", 0),
        "head": ((d, v), "normal", d),
    }


def seed_key(seed: int) -> jax.Array:
    """A key from any non-negative seed: the low 32 bits seed it and the
    high bits are folded in (``jax.random.key`` alone keeps 32 bits)."""
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def _leaf(key, shape, init, fan_in):
    if init == "norm":
        # norm gains near 1, not exactly 1, so a norm whose gain is
        # dropped or misapplied changes the logits
        return (1.0 + 0.1 * jax.random.normal(key, shape, jnp.bfloat16)
                ).astype(jnp.bfloat16)
    scale = 0.02 if init == "embed" else 1.0 / math.sqrt(fan_in)
    return (jax.random.normal(key, shape, jnp.bfloat16)
            * jnp.bfloat16(scale)).astype(jnp.bfloat16)


@functools.partial(jax.jit, static_argnums=(1,))
def _make(key, spec):
    out = {}
    for i, (name, (shape, init, fan_in)) in enumerate(spec):
        out[name] = _leaf(jax.random.fold_in(key, i), shape, init, fan_in)
    return out


def make(cfg: dict, seed: int) -> dict:
    """Every weight of ``cfg`` as bfloat16 on the default device."""
    spec = tuple(sorted(shapes(dims(cfg)).items()))
    return _make(seed_key(seed), spec)
