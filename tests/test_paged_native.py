"""Paged-native execution path: the per-tile block-table natives must be
BIT-exact to the gather-then-attend oracles (same values, not just close),
and the engine's dirty-block write-back must touch exactly the physical
blocks a span's slots map to — everything else in the pool, including
garbage-filled free blocks, stays bit-identical."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.engine import EngineConfig, NaivePPEngine
from repro.core.sampling_params import SamplingParams
from repro.models import ModelOptions, ShardCtx, build_model
from repro.models import attention as A


def _rand(rng, shape, dtype=jnp.bfloat16):
    return jnp.asarray(rng.normal(size=shape), jnp.float32).astype(dtype)


def _packed_batch(rng, b, s, t):
    seq = np.sort(rng.integers(0, b, t)).astype(np.int32)
    pos = rng.integers(0, s, t).astype(np.int32)
    return jnp.asarray(pos), jnp.asarray(seq)


def _paged_layout(rng, b, s, bs, n_extra=3):
    """Shuffled physical placement + n_extra unused garbage blocks."""
    nb = -(-s // bs)
    n_phys = b * nb + n_extra
    perm = rng.permutation(n_phys)[:b * nb].reshape(b, nb).astype(np.int32)
    return perm, n_phys, nb


def _scatter_blocks(contig, tables, bs, n_phys, rng):
    """Physical [n_phys, bs, ...] cache whose gather under ``tables``
    reproduces ``contig`` [B, S, ...]; unused blocks hold garbage."""
    b, s = contig.shape[:2]
    nb = tables.shape[1]
    pad = nb * bs - s
    if pad:
        widths = [(0, 0), (0, pad)] + [(0, 0)] * (contig.ndim - 2)
        contig = np.pad(np.asarray(contig, np.float32), widths)
    phys = rng.normal(size=(n_phys, bs) + contig.shape[2:]).astype(np.float32)
    blocks = np.asarray(contig, np.float32).reshape(b, nb, bs,
                                                    *contig.shape[2:])
    for i in range(b):
        for j in range(nb):
            phys[tables[i, j]] = blocks[i, j]
    return phys


def _bits(x):
    """Raw-bit view for exact equality across float dtypes."""
    a = np.asarray(jax.device_get(x))
    return a.view(np.uint8) if a.dtype == np.dtype("bfloat16") else a


# ---------------------------------------------------------------------------
# Natives vs. gather-then-attend oracles: bit-exact
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("window", [0, 32])
def test_paged_native_bitexact_to_oracle(window):
    b, s, h, kv, hd, t, bs = 3, 64, 4, 2, 32, 10, 16
    rng = np.random.default_rng(21)
    kc = np.asarray(_rand(rng, (b, s, kv, hd), jnp.float32))
    vc = np.asarray(_rand(rng, (b, s, kv, hd), jnp.float32))
    q = _rand(rng, (t, h, hd))
    pos, seq = _packed_batch(rng, b, s, t)
    tables, n_phys, nb = _paged_layout(rng, b, s, bs)
    kp = jnp.asarray(_scatter_blocks(kc, tables, bs, n_phys, rng),
                     jnp.bfloat16)
    vp = jnp.asarray(_scatter_blocks(vc, tables, bs, n_phys, rng),
                     jnp.bfloat16)
    tb = jnp.asarray(tables)
    o = A.paged_span_attention_native(q, kp, vp, tb, pos, seq,
                                      window=window, kv_block=bs)
    o_ref = A.paged_span_attention(q, kp, vp, tb, pos, seq,
                                   window=window, kv_block=bs)
    np.testing.assert_array_equal(_bits(o), _bits(o_ref))


def test_paged_quant_native_bitexact_to_oracle():
    b, s, h, kv, hd, t, bs = 2, 64, 4, 2, 32, 8, 16
    rng = np.random.default_rng(22)
    kc = _rand(rng, (b, s, kv, hd), jnp.float32)
    vc = _rand(rng, (b, s, kv, hd), jnp.float32)
    k8c, ksc = A.quantize_kv(kc)
    v8c, vsc = A.quantize_kv(vc)
    q = _rand(rng, (t, h, hd))
    pos, seq = _packed_batch(rng, b, s, t)
    tables, n_phys, nb = _paged_layout(rng, b, s, bs)
    tb = jnp.asarray(tables)
    k8 = jnp.asarray(_scatter_blocks(np.asarray(k8c, np.float32), tables,
                                     bs, n_phys, rng), jnp.int8)
    v8 = jnp.asarray(_scatter_blocks(np.asarray(v8c, np.float32), tables,
                                     bs, n_phys, rng), jnp.int8)
    ks = jnp.asarray(_scatter_blocks(np.asarray(ksc, np.float32), tables,
                                     bs, n_phys, rng), jnp.bfloat16)
    vs = jnp.asarray(_scatter_blocks(np.asarray(vsc, np.float32), tables,
                                     bs, n_phys, rng), jnp.bfloat16)
    o = A.paged_span_attention_quant_native(q, k8, ks, v8, vs, tb, pos, seq,
                                            kv_block=bs)
    o_ref = A.paged_span_attention_quant(q, k8, ks, v8, vs, tb, pos, seq,
                                         kv_block=bs)
    np.testing.assert_array_equal(_bits(o), _bits(o_ref))


def test_paged_rolling_native_bitexact_to_oracle():
    b, w, kv, g, hd, t, bs = 2, 32, 2, 2, 32, 6, 8
    h = kv * g
    rng = np.random.default_rng(23)
    kroll = np.asarray(_rand(rng, (b, w, kv, hd), jnp.float32))
    vroll = np.asarray(_rand(rng, (b, w, kv, hd), jnp.float32))
    q = _rand(rng, (t, h, hd))
    ksp = _rand(rng, (t, kv, hd))
    vsp = _rand(rng, (t, kv, hd))
    offs = jnp.asarray([40, 40, 40, 7, 7, 7], jnp.int32)  # row0 wrapped
    pos = jnp.asarray([40, 41, 42, 7, 8, 9], jnp.int32)
    seq = jnp.asarray([0, 0, 0, 1, 1, 1], jnp.int32)
    tables, n_phys, nb = _paged_layout(rng, b, w, bs)
    tb = jnp.asarray(tables)
    kp = jnp.asarray(_scatter_blocks(kroll, tables, bs, n_phys, rng),
                     jnp.bfloat16)
    vp = jnp.asarray(_scatter_blocks(vroll, tables, bs, n_phys, rng),
                     jnp.bfloat16)
    o = A.paged_span_attention_rolling_native(
        q, kp, vp, ksp, vsp, tb, pos, seq, offs, t, window=w, kv_block=bs)
    o_ref = A.paged_span_attention_rolling(
        q, kp, vp, ksp, vsp, tb, pos, seq, offs, t, window=w, kv_block=bs)
    np.testing.assert_array_equal(_bits(o), _bits(o_ref))


def test_paged_rolling_quant_native_bitexact_to_oracle():
    b, w, kv, g, hd, t, bs = 2, 16, 1, 2, 16, 4, 8
    h = kv * g
    rng = np.random.default_rng(24)
    kroll = _rand(rng, (b, w, kv, hd), jnp.float32)
    vroll = _rand(rng, (b, w, kv, hd), jnp.float32)
    k8c, ksc = A.quantize_kv(kroll)
    v8c, vsc = A.quantize_kv(vroll)
    q = _rand(rng, (t, h, hd))
    ksp = _rand(rng, (t, kv, hd))
    vsp = _rand(rng, (t, kv, hd))
    offs = jnp.asarray([20, 20, 5, 5], jnp.int32)
    pos = jnp.asarray([20, 21, 5, 6], jnp.int32)
    seq = jnp.asarray([0, 0, 1, 1], jnp.int32)
    tables, n_phys, nb = _paged_layout(rng, b, w, bs)
    tb = jnp.asarray(tables)
    k8 = jnp.asarray(_scatter_blocks(np.asarray(k8c, np.float32), tables,
                                     bs, n_phys, rng), jnp.int8)
    v8 = jnp.asarray(_scatter_blocks(np.asarray(v8c, np.float32), tables,
                                     bs, n_phys, rng), jnp.int8)
    ks = jnp.asarray(_scatter_blocks(np.asarray(ksc, np.float32), tables,
                                     bs, n_phys, rng), jnp.bfloat16)
    vs = jnp.asarray(_scatter_blocks(np.asarray(vsc, np.float32), tables,
                                     bs, n_phys, rng), jnp.bfloat16)
    o = A.paged_span_attention_rolling_quant_native(
        q, k8, ks, v8, vs, ksp, vsp, tb, pos, seq, offs, t,
        window=w, kv_block=bs)
    o_ref = A.paged_span_attention_rolling_quant(
        q, k8, ks, v8, vs, ksp, vsp, tb, pos, seq, offs, t,
        window=w, kv_block=bs)
    np.testing.assert_array_equal(_bits(o), _bits(o_ref))


# ---------------------------------------------------------------------------
# Engine: the dirty-block write-back scatter set
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def model_and_params():
    cfg = get_config("stablelm-1.6b-smoke")
    model = build_model(cfg, ShardCtx.single())
    params = model.init(jax.random.key(0))
    return cfg, model, params


def _snapshot(worker):
    return [np.asarray(jax.device_get(c))
            for c in jax.tree.leaves(worker.cache)]


def _changed_blocks(before, after):
    """Physical block indices whose content differs in any cache leaf."""
    changed = set()
    for old, new in zip(before, after):
        # leaf [groups, n_blocks + 1, bs, ...]
        diff = (old != new).reshape(old.shape[0], old.shape[1], -1).any((0, 2))
        changed.update(np.flatnonzero(diff).tolist())
    return changed


def _expected_blocks(sched, bs):
    """Blocks a scheduled iteration's slots map to under its own table
    snapshot (no window in the smoke arch: slot == position)."""
    tables = np.asarray(sched.block_tables)
    out = set()
    if sched.packed_width > 1:
        tok, pos, seq, _last = sched.packed_layout()
        for p, s in zip(pos, seq):
            out.add(int(tables[s, min(p // bs, tables.shape[1] - 1)]))
    else:
        for i, p in enumerate(np.asarray(sched.positions)):
            out.add(int(tables[i, min(p // bs, tables.shape[1] - 1)]))
    return out


def test_chunk_scatter_set_equals_touched_blocks(model_and_params):
    """Property: after each iteration, the set of physical blocks that
    changed is exactly the set the iteration's span slots map to (plus,
    possibly, the trash block that absorbs pad-entry writes).  Runs a
    mixed chunked-prefill + decode workload so chunk-carrying and pure
    decode iterations both get checked."""
    cfg, model, params = model_and_params
    rng = np.random.default_rng(31)
    prompts = [list(rng.integers(2, cfg.vocab_size, size=n))
               for n in (21, 13, 5)]
    eng = NaivePPEngine(model, params, EngineConfig(
        pp_degree=1, max_batch=2, max_seq_len=64, kv_layout="paged",
        kv_block_size=8, prefill_chunk_tokens=8))
    bs = eng.cfg.kv_block_size
    trash = eng.kv_manager.pad_block
    for p in prompts:
        eng.add_request(p, SamplingParams(greedy=True, max_new_tokens=4))

    scheds = []
    orig = eng.scheduler.schedule

    def record(it):
        out = orig(it)
        if out is not None:
            scheds.append(out)
        return out

    eng.scheduler.schedule = record
    worker = eng.stages[0]
    checked = mixed = 0
    while eng.has_work:
        before = _snapshot(worker)
        n0 = len(scheds)
        eng.step()
        after = _snapshot(worker)
        changed = _changed_blocks(before, after)
        expected = set()
        for sched in scheds[n0:]:
            expected |= _expected_blocks(sched, bs)
        assert changed - {trash} == expected - {trash}, \
            (changed, expected, trash)
        if scheds[n0:]:
            checked += 1
            mixed += any(s.packed_width > 1 and len(s.seq_ids) > 1
                         for s in scheds[n0:])
    eng.shutdown()
    assert checked >= 4          # the property actually ran
    assert mixed >= 1            # incl. a mixed chunk + decode iteration


def test_untouched_blocks_survive_garbage_poking(model_and_params):
    """E2E pin: free physical blocks are never READ either — poisoning
    every free block before each step leaves the greedy token stream
    identical to the contiguous layout's."""
    cfg, model, params = model_and_params
    rng = np.random.default_rng(32)
    prompts = [list(rng.integers(2, cfg.vocab_size, size=n))
               for n in (17, 9)]
    n_new = 5

    def run(layout, poison):
        eng = NaivePPEngine(model, params, EngineConfig(
            pp_degree=1, max_batch=2, max_seq_len=64, kv_layout=layout,
            kv_block_size=8, prefill_chunk_tokens=8))
        for p in prompts:
            eng.add_request(p, SamplingParams(greedy=True,
                                              max_new_tokens=n_new))
        worker = eng.stages[0]
        done = {}
        while eng.has_work:
            if poison:
                free = jnp.asarray(list(eng.kv_manager.alloc._free),
                                   jnp.int32)
                if free.size:
                    worker.cache = jax.tree.map(
                        lambda c: c.at[:, free].set(
                            127 if c.dtype == jnp.int8 else 1e3),
                        worker.cache)
            for out in eng.step():
                if out.finished:
                    done[out.seq.seq_id] = tuple(out.seq.output_ids)
        eng.shutdown()
        return sorted(done.items())

    assert run("paged", poison=True) == run("contiguous", poison=False)


# ---------------------------------------------------------------------------
# Stage step: the carried layer scan against the per-layer xs/ys scan
# ---------------------------------------------------------------------------

def _xs_ys_scan(stack, params, x, ctx, cache):
    """The per-layer reference: each group's cache slice goes in as a
    scan input and comes out as a fresh stacked output."""
    def body(xc, inp):
        gp, cg = inp
        return stack.apply(gp, xc, ctx, cg)

    return jax.lax.scan(body, x, (params, cache))


def _paged_pool(abstract, n_phys, bs, rng):
    """Random block-major pool: attention leaves [n, B, S, ...] become
    [n, n_phys, bs, ...]; recurrent state leaves keep their shape."""
    def leaf(path, sd):
        shape = sd.shape
        if getattr(path[-1], "key", None) in ("k", "v", "ks", "vs"):
            shape = (shape[0], n_phys, bs) + shape[3:]
        if sd.dtype == jnp.int8:
            return jnp.asarray(rng.integers(-127, 128, shape), jnp.int8)
        return _rand(rng, shape, sd.dtype)

    return jax.tree_util.tree_map_with_path(leaf, abstract)


# head_dim 128 fills a lane tile, so decode carries those pools whole;
# chunk steps, the smoke archs' 16-wide heads and the int8 pool's scales
# take the slice
@pytest.mark.parametrize("arch,head_dim,kv_quant,mode,whole", [
    ("stablelm-1.6b-smoke", 128, False, "decode", True),
    ("stablelm-1.6b-smoke", 128, False, "chunk", False),
    ("stablelm-1.6b-smoke", 128, True, "decode", False),
    ("stablelm-1.6b-smoke", 128, True, "chunk", False),
    ("mixtral-8x7b-smoke", 128, False, "decode", True),   # moe, window
    ("mixtral-8x7b-smoke", 128, False, "chunk", False),
    ("stablelm-1.6b-smoke", 0, False, "decode", False),   # 16-wide head
    ("recurrentgemma-9b-smoke", 0, False, "decode", False),
], ids=["bf16-decode", "bf16-chunk", "int8-decode", "int8-chunk",
        "moe-window-decode", "moe-window-chunk", "narrow-head-decode",
        "hybrid-decode"])
def test_carried_scan_equals_per_layer_scan(arch, head_dim, kv_quant, mode,
                                            whole):
    """Paged decode and chunk through run_stack's carried scan (in decode
    the whole pool where the stack addresses it and its rows fill lane
    tiles, else a slice and write-back, as for the hybrid stack's RG-LRU
    state) give the same logits, bit for bit, and the same returned pool
    as the per-layer xs/ys scan."""
    from repro.models.stacked import carries_whole_pool, run_stack

    cfg = get_config(arch)
    if head_dim:
        cfg = dataclasses.replace(cfg, head_dim=head_dim)
    model = build_model(cfg, ShardCtx.single(), ModelOptions(kv_quant=kv_quant))
    params = model.init(jax.random.key(5))
    stack = model.stacks["blocks"]
    b, s, bs = 3, 32, 8
    rng = np.random.default_rng(41)
    tables, n_phys, nb = _paged_layout(rng, b, s, bs)
    abstract = model.abstract_cache(b, s)["blocks"]
    cache = _paged_pool(abstract, n_phys + 1, bs, rng)   # + trash block
    tables = jnp.asarray(tables)
    if mode == "decode":
        pos = jnp.asarray(rng.integers(0, s, b), jnp.int32)
        ctx = model.make_ctx("decode", pos, block_tables=tables)
        x = _rand(rng, (b, cfg.d_model))
    else:
        lens, starts = np.array([3, 1, 4]), np.array([5, 10, 0])
        seq = np.repeat(np.arange(b), lens).astype(np.int32)
        pos = np.concatenate([st + np.arange(n) for st, n in zip(starts, lens)])
        ctx = model.make_ctx("chunk", jnp.asarray(pos, jnp.int32),
                             seq_idx=jnp.asarray(seq),
                             span_starts=jnp.asarray(starts, jnp.int32),
                             n_valid=jnp.asarray(len(seq), jnp.int32),
                             block_tables=tables)
        x = _rand(rng, (len(seq), cfg.d_model))
    assert carries_whole_pool(stack, ctx, cache) == whole

    def step(scan):
        def f(prm, x, cache):
            xo, new = scan(stack, prm["stacks"]["blocks"], x, ctx, cache)
            return model.lm_head(prm, xo), new
        return jax.jit(f)(params, x, cache)

    got = step(lambda *a: run_stack(*a, remat=False))
    ref = step(_xs_ys_scan)
    for g, r in zip(jax.tree.leaves(got), jax.tree.leaves(ref)):
        np.testing.assert_array_equal(_bits(g), _bits(r))
    # the step wrote something: the pool is not what went in
    assert any(not np.array_equal(_bits(n), _bits(o)) for n, o in
               zip(jax.tree.leaves(got[1]), jax.tree.leaves(cache)))
