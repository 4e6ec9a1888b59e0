"""A configuration names its block, and the harness takes the weights,
the program mapping, the reference and the work counts from that block
alone: a new block is added by files, and the dense block gives the
same numbers as before it was moved behind the seam."""
import gc
import json
import shutil

import jax
import numpy as np
import pytest

from bench import flops, program, registry, run, trace, weights
from bench.blocks import dense

BASE = registry.BENCH / "tests" / "data"
SPEC = registry.load_json(BASE / "benchmark.json")
PEAKS = registry.load_json(registry.BENCH / "peaks.json")["devices"][
    "TPU v5 lite"]
TINY = registry.config("tiny", BASE)
GLM = registry.config("glm4-9b-10l")
MS = 1e6

# a block that is the dense block under another name, and one whose
# reference drops the final norm's gain
TOY = '"""The dense block under another name."""\n' \
      "from bench.blocks.dense import *  # noqa: F401,F403\n"
TOY_WRONG = TOY + '''
import jax.numpy as jnp
from bench.blocks import dense


def served_gaps(w, cfg, prompt, served):
    return dense.served_gaps(dict(w, lnf=jnp.ones_like(w["lnf"])), cfg,
                             prompt, served)
'''


def _base_with_block(tmp_path, source: str, name: str = "toy"):
    """The tests' data with tiny.json naming block ``name``, whose file
    holds ``source``."""
    base = tmp_path / "bench"
    shutil.copytree(BASE, base)
    if source is not None:
        (base / "blocks").mkdir()
        (base / "blocks" / f"{name}.py").write_text(source)
    (base / "configs" / "tiny.json").write_text(json.dumps(
        dict(TINY, block=name)))
    return base


@pytest.mark.parametrize("source,correct", [(TOY, True), (TOY_WRONG, False)],
                         ids=["dense-copy", "final-norm-dropped"])
def test_run_checks_with_the_configurations_block(tmp_path, source, correct):
    base = _base_with_block(tmp_path, source)
    res = run.run("tiny.chat", 2**31 + 7, 3.0, False, jax.devices()[0],
                  PEAKS, SPEC, base)
    assert res["correct"] is correct, res["check"]
    gap = res["check"]["logit_gap_max"]
    assert (gap["value"] <= gap["limit"]) is correct
    jax.clear_caches()


def test_missing_block_fails_before_any_weights(tmp_path, monkeypatch):
    base = _base_with_block(tmp_path, None, "nosuch")

    def never(*a, **kw):
        raise AssertionError("weights drawn or model built")

    monkeypatch.setattr(weights, "make", never)
    monkeypatch.setattr(program, "build_model", never)
    with pytest.raises(FileNotFoundError,
                       match=str(base / "blocks" / "nosuch.py")):
        run.run("tiny.chat", 3, 3.0, False, jax.devices()[0], PEAKS, SPEC,
                base)


def test_block_lacking_a_contract_function_is_refused(tmp_path):
    (tmp_path / "blocks").mkdir()
    (tmp_path / "blocks" / "half.py").write_text(
        "from bench.blocks.dense import dims, make  # noqa: F401\n")
    with pytest.raises(AttributeError, match="program_model"):
        registry.block("half", tmp_path)


def test_dense_block_refuses_a_window_or_experts():
    cfg = dict(TINY, program={"arch": "mixtral-8x7b"})
    with pytest.raises(ValueError, match="dense block only"):
        dense.program_model(cfg)


def test_build_keeps_no_second_handle_on_the_weights(monkeypatch):
    """While the engine cuts its stages from the tree, the tree's dict
    is the only one holding a leaf: the harness kept none of its own."""
    holders = []
    real = program.build_server

    def spy(cfg, model, params, seed):
        leaf = params["embed"]
        holders.append(sum(isinstance(r, dict)
                           for r in gc.get_referrers(leaf)))
        del leaf
        return real(cfg, model, params, seed)

    monkeypatch.setattr(program, "build_server", spy)
    server, eng, _ = run.build(TINY, dense, 5, warm=False)
    run.stop(server, eng)
    assert holders == [1]


# ---- the dense block's numbers are those it gave before the seam ----


def test_make_is_the_dense_weights():
    a = dense.make(TINY, 2**33 + 5)
    b = weights.make(TINY, 2**33 + 5)
    assert sorted(a) == sorted(b)
    for k in a:
        assert a[k].dtype == b[k].dtype
        assert np.array_equal(np.asarray(a[k]).view(np.uint16),
                              np.asarray(b[k]).view(np.uint16)), k


def _step(t0, stage, kind, batch, width, ctx):
    """A step record as ``run.instrument`` makes it: ``ctx`` holds each
    row's context after the step, ``width`` the tokens each row adds."""
    tokens = batch * width
    return {"t0": t0, "t1": t0 + 0.01, "stage": stage, "kind": kind,
            "batch": batch, "width": width if kind == "chunk" else 1,
            "tokens": tokens, "layers": 5, "last": stage == 1,
            "ctx_sum": sum(c * width - width * (width - 1) // 2
                           for c in ctx),
            "rows_ctx_sum": sum(ctx), "sampled": batch}


STEPS = (
    [_step(0.5 + 0.1 * i, i % 2, "decode", 16, 1,
           [300 + 37 * r + i for r in range(16)]) for i in range(6)]
    + [_step(0.05, 0, "chunk", 2, 128, [128, 700]),
       _step(0.09, 0, "chunk", 1, 256, [1024]),
       _step(0.07, 1, "chunk", 2, 128, [128, 700])])


@pytest.mark.parametrize("cfg", [TINY, GLM], ids=["tiny", "glm4-9b-10l"])
def test_dense_counts_are_the_flops_modules(cfg):
    m = dense.dims(cfg)
    assert m == weights.dims(cfg)
    for s in STEPS:
        L = s["layers"]
        assert dense.step_flops(m, s) == flops.step_flops(
            m, L, s["tokens"], s["ctx_sum"], s["sampled"] if s["last"] else 0)
        assert dense.decode_bytes(m, s) == (
            flops.stage_weight_bytes(m, L, s["last"])
            + flops.kv_bytes(m, L, s["rows_ctx_sum"]))
        assert dense.span_attn_work(m, s) == (
            L * flops.attn_flops(m, s["ctx_sum"]),
            flops.span_attn_bytes(m, L, s["tokens"], s["rows_ctx_sum"]))


# decode and chunk programs of both stages and the span kernel inside a
# chunk step, over a 100-ms window
HAND = [
    ("/host:CPU", "python3", "bench/window", 0.0, 100 * MS),
    ("/device:TPU:0", trace.MODULES_LINE, "jit_decode_fn_stage0(1)",
     5 * MS, 11 * MS),
    ("/device:TPU:0", trace.OPS_LINE, "fusion.1 fusion bf16[16,4096]",
     5 * MS, 11 * MS),
    ("/device:TPU:0", trace.MODULES_LINE, "jit_decode_fn_stage1(2)",
     20 * MS, 14 * MS),
    ("/device:TPU:0", trace.OPS_LINE, "fusion.2 fusion f32[16,151552]",
     20 * MS, 14 * MS),
    ("/device:TPU:0", trace.MODULES_LINE, "jit_chunk_fn_stage0(3)",
     40 * MS, 30 * MS),
    ("/device:TPU:0", trace.OPS_LINE,
     "closed_call.17 custom-call:tpu_custom_call bf16[256,32,128]",
     45 * MS, 2.5 * MS),
    ("/device:TPU:0", trace.OPS_LINE, "fusion.3 fusion bf16[256,4096]",
     48 * MS, 20 * MS),
]


def _recorded():
    rec = json.loads((BASE / "trace_v5e.json").read_text())
    return [tuple(e) for e in rec["recording"]]


READERS = ("step_mfu.batch", "decode_hbm_share", "span_attn_roofline")
# read before the counts moved behind the seam, from the same steps and
# traces (the recording holds no decode program)
BEFORE = {
    ("glm4-9b-10l", "recorded"): (0.01853573163218471, None,
                                  0.4200924271139267),
    ("glm4-9b-10l", "hand"): (9.453045708020305, 79.35053268864469,
                              6.802437197969543),
    ("tiny", "recorded"): (9.106198183020732e-06, None,
                           0.008856957376066966),
    ("tiny", "hand"): (0.004644073908629442, 0.18536463003663003,
                       0.1434181918719746),
}


@pytest.mark.parametrize("key", sorted(BEFORE), ids="-".join)
def test_step_metrics_read_as_before(key):
    cfg = {"tiny": TINY, "glm4-9b-10l": GLM}[key[0]]
    events = _recorded() if key[1] == "recorded" else HAND
    lo, hi = trace.window(events)
    ctx = {"t0": 0.0, "t1": 51.0, "steps": list(STEPS),
           "dims": dense.dims(cfg), "block": dense, "peaks": PEAKS,
           "events": events, "trace_lo": lo, "trace_hi": hi}
    got = tuple(registry.reader(r)(ctx) for r in READERS)
    assert got == BEFORE[key]
