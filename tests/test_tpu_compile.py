"""Compile the serving hot path for a TPU v5e that is described, not
attached: the four paged span-attention twins through the engine's
dispatchers, and one full-width paged stage chunk step and decode step.

Nothing runs here.  The TPU compiler refuses what the chip would refuse
(unaligned tiles, too much VMEM, scalar-prefetch shapes), so these tests
guard the kernels' TPU lowering without a chip.  On the CPU the
dispatchers take their jnp branch; each test steers them down the Pallas
branch itself.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker
imports this file.
"""
import dataclasses
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core.engine import _make_stage
from repro.models import attention, build_model

BS = 16           # KV block size the engine serves with
T, B, NB = 256, 8, 16
N_BLOCKS = 2048 + 1   # pool + the trash block


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-device compile is written to the persistent cache but
    # cannot be read back without a chip: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def pallas(monkeypatch):
    monkeypatch.setattr(attention, "use_pallas_paged", lambda: True)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def _span_inputs(h, kv, hd, nb, sh, quant, t=T):
    """q, K/V pool (+ int8 scales), tables, positions, seq_idx."""
    pool = (N_BLOCKS, BS, kv, hd)
    if quant:
        cache = (_sds(pool, jnp.int8, sh), _sds(pool[:3], jnp.bfloat16, sh),
                 _sds(pool, jnp.int8, sh), _sds(pool[:3], jnp.bfloat16, sh))
    else:
        cache = (_sds(pool, jnp.bfloat16, sh), _sds(pool, jnp.bfloat16, sh))
    return (_sds((t, h, hd), jnp.bfloat16, sh), cache,
            _sds((B, nb), jnp.int32, sh), _sds((t,), jnp.int32, sh),
            _sds((t,), jnp.int32, sh))


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
def test_paged_span_twin_compiles(one_chip, pallas, quant):
    """Full-cache twins at stablelm-1.6b widths (H = Kv = 32, hd = 64)."""
    cfg = get_config("stablelm-1.6b")
    q, cache, tables, pos, seq = _span_inputs(
        cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim, NB,
        one_chip, quant)
    fn = (attention.paged_span_attention_quant_exec if quant
          else attention.paged_span_attention_exec)
    text = _compile(fn, q, *cache, tables, pos, seq)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
def test_paged_rolling_twin_compiles(one_chip, pallas, quant):
    """Sliding-window twins at mixtral-8x7b's attention widths (GQA
    H = 32 over Kv = 8, hd = 128, window 4096), fed the scalar
    ``n_valid`` the engine's stage step passes.  The twins hold the
    whole packed span's K/V in VMEM, so at these widths a 256-token
    chunk needs 16.5 MiB of scoped VMEM, over v5e's 16 MiB; 128 fits."""
    cfg = get_config("mixtral-8x7b")
    kv, hd, t = cfg.num_kv_heads, cfg.resolved_head_dim, T // 2
    q, cache, tables, pos, seq = _span_inputs(
        cfg.num_heads, kv, hd, cfg.window // BS, one_chip, quant, t)
    span = (_sds((t, kv, hd), jnp.bfloat16, one_chip),) * 2
    offsets = _sds((t,), jnp.int32, one_chip)
    n_valid = _sds((), jnp.int32, one_chip)
    fn = (attention.paged_span_attention_rolling_quant_exec if quant
          else attention.paged_span_attention_rolling_exec)
    text = _compile(functools.partial(fn, window=cfg.window),
                    q, *cache, *span, tables, pos, seq, offsets, n_valid)
    assert "tpu_custom_call" in text


def _stage(cfg, idx, p, sharding):
    """Stage ``idx`` of a ``p``-stage paged split of ``cfg``, with its
    parameters and KV pool as shapes on the described chip."""
    model = build_model(cfg)
    n = model.stacks["blocks"].n
    lo, hi = round(idx * n / p), round((idx + 1) * n / p)

    def stage_params(prm):
        sp = {"blocks": jax.tree.map(lambda x: x[lo:hi],
                                     prm["stacks"]["blocks"])}
        if idx == 0:
            sp["embed"] = prm["embed"]
        if idx == p - 1:
            sp["lnf"], sp["head"] = prm["lnf"], prm["head"]
        return sp

    on_chip = lambda s: _sds(s.shape, s.dtype, sharding)  # noqa: E731
    sp = jax.tree.map(on_chip, jax.eval_shape(stage_params,
                                              model.abstract_params()))
    stage = _make_stage(model, idx, p, (lo, hi), sp, paged=True)
    template = jax.eval_shape(lambda: stage.init_cache(1, 1))
    cache = jax.tree.map(
        lambda c: _sds((c.shape[0], N_BLOCKS, BS) + c.shape[3:], c.dtype,
                       sharding), template)
    return stage, sp, cache


# a sliding window sends the chunk step down the rolling twins; 256 is a
# whole number of blocks, so the engine keeps the paged layout for it
@pytest.mark.parametrize("window", [0, 256], ids=["published", "window256"])
def test_stage_chunk_step_compiles(one_chip, pallas, window):
    """First stage of a pp=2 split of stablelm-1.6b at published widths
    (12 layers), one packed chunk of T tokens over a 2049-block pool."""
    cfg = dataclasses.replace(get_config("stablelm-1.6b"), window=window)
    stage, sp, cache = _stage(cfg, 0, 2, one_chip)
    i32 = functools.partial(_sds, dtype=jnp.int32, sharding=one_chip)
    text = stage.chunk_fn.lower(
        sp, cache, i32((T,)), i32((T,)), i32((T,)), i32((B,)), i32((B,)),
        i32(()), i32((B, NB))).compile().as_text()
    assert "tpu_custom_call" in text
    # a device trace names the program after the stage, and each op by
    # its HLO text: the benchmark finds the kernel by the label it makes
    # of that text, which the kernel's name must leave alone
    from bench.trace import label

    assert text.startswith("HloModule jit_chunk_fn_stage0")
    kernels = [label(ln.strip()) for ln in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in ln]
    assert kernels
    for k in kernels:
        assert k.startswith("paged_span_attention")
        assert "custom-call:tpu_custom_call" in k


def test_stage_decode_step_compiles(one_chip):
    """Last stage of the same split (LM head, vocab 100352): one decode
    token per row through the block table."""
    cfg = get_config("stablelm-1.6b")
    stage, sp, cache = _stage(cfg, 1, 2, one_chip)
    i32 = functools.partial(_sds, dtype=jnp.int32, sharding=one_chip)
    stage.decode_fn.lower(
        sp, cache, _sds((B, cfg.d_model), jnp.bfloat16, one_chip),
        i32((B,)), i32((B, NB))).compile()
