"""End-to-end engine tests: completion, greedy correctness vs. the
non-pipelined reference, metadata reuse, SAT/TSEM toggles."""
import dataclasses
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.engine import EngineConfig, NaivePPEngine, SiPipeEngine
from repro.core.sampling_params import SamplingParams
from repro.models import ModelOptions, ShardCtx, build_model


@pytest.fixture(scope="module")
def model_and_params():
    cfg = get_config("stablelm-1.6b-smoke")
    model = build_model(cfg, ShardCtx.single())
    params = model.init(jax.random.key(0))
    return cfg, model, params


def _reference_generate(cfg, model, params, prompts, n_new):
    """Non-pipelined greedy reference: prefill + decode loop per batch."""
    outs = []
    for prompt in prompts:
        toks = jnp.asarray([prompt], jnp.int32)
        logits, cache = jax.jit(model.prefill)(params, {"tokens": toks})
        dcache = model.init_cache(1, len(prompt) + n_new + 4)

        def pad_into(dst, src):
            if dst.shape == src.shape:
                return src
            return dst.at[tuple(slice(0, d) for d in src.shape)].set(src)

        cache = jax.tree.map(pad_into, dcache, cache)
        seq = []
        tok = int(np.asarray(logits).argmax(-1)[0])
        seq.append(tok)
        pos = len(prompt)
        for _ in range(n_new - 1):
            logits, cache = jax.jit(model.decode)(params, cache, {
                "token": jnp.asarray([tok], jnp.int32),
                "positions": jnp.asarray([pos], jnp.int32)})
            tok = int(np.asarray(logits).argmax(-1)[0])
            seq.append(tok)
            pos += 1
        outs.append(seq)
    return outs


def test_sipipe_greedy_matches_reference(model_and_params):
    """The pipelined engine with stage splitting + CPU sampling must emit
    exactly the reference greedy continuation (cache/stage correctness)."""
    cfg, model, params = model_and_params
    rng = np.random.default_rng(0)
    prompts = [list(rng.integers(2, cfg.vocab_size, size=n)) for n in (5, 9)]
    n_new = 5
    want = _reference_generate(cfg, model, params, prompts, n_new)

    eng = SiPipeEngine(model, params, EngineConfig(
        pp_degree=2, max_batch=2, max_seq_len=64, n_samplers=2))
    for p in prompts:
        eng.add_request(p, SamplingParams(greedy=True, max_new_tokens=n_new))
    done = sorted(eng.run(), key=lambda s: s.seq_id)
    assert [s.output_ids for s in done] == want


def test_naive_engine_greedy_matches_reference(model_and_params):
    cfg, model, params = model_and_params
    rng = np.random.default_rng(1)
    prompts = [list(rng.integers(2, cfg.vocab_size, size=n)) for n in (4, 7)]
    want = _reference_generate(cfg, model, params, prompts, 4)
    eng = NaivePPEngine(model, params, EngineConfig(
        pp_degree=2, max_batch=2, max_seq_len=64))
    for p in prompts:
        eng.add_request(p, SamplingParams(greedy=True, max_new_tokens=4))
    done = sorted(eng.run(), key=lambda s: s.seq_id)
    assert [s.output_ids for s in done] == want


def test_engines_agree_with_each_other(model_and_params):
    cfg, model, params = model_and_params
    rng = np.random.default_rng(2)
    prompts = [list(rng.integers(2, cfg.vocab_size, size=6)) for _ in range(4)]
    results = {}
    for name, Eng in (("sipipe", SiPipeEngine), ("naive", NaivePPEngine)):
        eng = Eng(model, params, EngineConfig(pp_degree=2, max_batch=2,
                                              max_seq_len=64))
        for p in prompts:
            eng.add_request(p, SamplingParams(greedy=True, max_new_tokens=4))
        done = sorted(eng.run(), key=lambda s: s.seq_id)
        results[name] = [s.output_ids for s in done]
    assert results["sipipe"] == results["naive"]


def test_continuous_batching_backfill(model_and_params):
    """More requests than slots: finished sequences free rows for waiters."""
    cfg, model, params = model_and_params
    eng = SiPipeEngine(model, params, EngineConfig(
        pp_degree=2, max_batch=2, max_seq_len=64))
    rng = np.random.default_rng(3)
    for i in range(7):
        eng.add_request(list(rng.integers(2, cfg.vocab_size, size=4)),
                        SamplingParams(greedy=True, max_new_tokens=2 + i % 3))
    done = eng.run()
    assert len(done) == 7
    for s in done:
        assert len(s.output_ids) == s.params.max_new_tokens


def test_metadata_reuse_counts(model_and_params):
    cfg, model, params = model_and_params
    eng = SiPipeEngine(model, params, EngineConfig(
        pp_degree=1, max_batch=2, max_seq_len=64))
    rng = np.random.default_rng(4)
    for _ in range(2):
        eng.add_request(list(rng.integers(2, cfg.vocab_size, size=4)),
                        SamplingParams(greedy=True, max_new_tokens=6))
    eng.run()
    m = eng.metrics()
    assert m["incremental_hits"] > m["meta_rebuilds"]


def test_pp4_deeper_pipeline(model_and_params):
    cfg, model, params = model_and_params
    eng = SiPipeEngine(model, params, EngineConfig(
        pp_degree=4, max_batch=1, max_seq_len=64, n_samplers=1))
    rng = np.random.default_rng(5)
    want = _reference_generate(
        cfg, model, params,
        [list(rng.integers(2, cfg.vocab_size, size=5))], 4)
    eng.add_request(list(rng.integers(2, cfg.vocab_size, size=5)),
                    SamplingParams(greedy=True, max_new_tokens=4))
    # note: different rng draw -> regenerate the same prompt
    eng2 = SiPipeEngine(model, params, EngineConfig(
        pp_degree=4, max_batch=1, max_seq_len=64, n_samplers=1))
    rng = np.random.default_rng(5)
    prompt = list(rng.integers(2, cfg.vocab_size, size=5))
    eng2.add_request(prompt, SamplingParams(greedy=True, max_new_tokens=4))
    done = eng2.run()
    assert [s.output_ids for s in done] == want


def test_stall_deadlines_skip_running_steps(model_and_params, monkeypatch):
    """A stage step that outlasts the stall deadlines (a cold full-width
    shape compiling inside the jitted call) is progress, not a stall:
    neither the downstream stage's receive nor the driver's wait for the
    iteration may time out while it runs."""
    import repro.core.engine as engine_mod

    monkeypatch.setattr(engine_mod, "RECV_STALL_S", 0.2)
    monkeypatch.setattr(engine_mod, "ITER_STALL_S", 0.2)
    cfg, model, params = model_and_params
    eng = SiPipeEngine(model, params, EngineConfig(
        pp_degree=2, max_batch=1, max_seq_len=64, prefill_chunk_tokens=8))
    stage = eng.stages[0].stage

    def slow_first_call(fn):
        calls = []

        def wrapped(*args):
            if not calls:
                calls.append(1)
                time.sleep(1.5)
            return fn(*args)
        return wrapped

    stage.chunk_fn = slow_first_call(stage.chunk_fn)
    stage.decode_fn = slow_first_call(stage.decode_fn)
    eng.add_request([5, 9, 13], SamplingParams(greedy=True, max_new_tokens=3))
    done = eng.run()
    assert [len(s.output_ids) for s in done] == [3]


PLACEMENT_SCRIPT = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, os.environ["REPRO_SRC"])
import jax, numpy as np
from repro.configs import get_config
from repro.core.engine import EngineConfig, SiPipeEngine
from repro.core.sampling_params import SamplingParams
from repro.models import build_model

devs = jax.devices()
assert len(devs) == 4, devs
cfg = get_config("stablelm-1.6b-smoke")
model = build_model(cfg)
params = model.init(jax.random.key(0))
rng = np.random.default_rng(0)
prompts = [rng.integers(2, cfg.vocab_size, size=n).tolist()
           for n in (11, 5, 19)]


def placement(eng):
    return [({d for x in jax.tree.leaves(w.stage.params) for d in x.devices()},
             {d for x in jax.tree.leaves(w.cache) for d in x.devices()})
            for w in eng.stages]


def serve(devices):
    eng = SiPipeEngine(model, params, EngineConfig(
        pp_degree=4, max_batch=2, max_seq_len=64, prefill_chunk_tokens=8,
        kv_block_size=8), devices=devices)
    before = placement(eng)
    for p in prompts:
        eng.add_request(p, SamplingParams(greedy=True, max_new_tokens=6))
    done = sorted(eng.run(), key=lambda s: s.seq_id)
    assert placement(eng) == before
    return [s.output_ids for s in done], before


spread, where = serve(None)
assert where == [({d}, {d}) for d in devs], where
one, where_one = serve([devs[0]] * 4)
assert where_one == [({devs[0]}, {devs[0]})] * 4, where_one
assert spread == one, (spread, one)
assert all(len(t) == 6 for t in spread), spread
print("PLACEMENT_OK")
"""


def test_stages_placed_one_per_device():
    """With four devices, stage i's parameters and KV pool live on device
    i (the default spread) and greedy tokens are identical to the same
    pp=4 engine with every stage on device 0.  Runs in a child process:
    the virtual device count is fixed when the backend starts."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", REPRO_SRC=os.path.join(
        os.path.dirname(__file__), "..", "src"))
    out = subprocess.run([sys.executable, "-c", PLACEMENT_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert "PLACEMENT_OK" in out.stdout, out.stdout + out.stderr
