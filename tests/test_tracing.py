"""The program's own instrumentation (repro.core.tracing): the spans a
tiny pp=2 engine leaves in a profiler trace, each named with its stage,
and the running counters it keeps in their place, checked against the
benchmark's per-step records of the same run (bench/run.py
``instrument``)."""
import glob
import os
from collections import defaultdict

import jax
import pytest

from repro.configs import get_config
from repro.core.engine import STEP_KINDS, EngineConfig, SiPipeEngine
from repro.core.sampling_params import SamplingParams
from repro.models import ModelOptions, ShardCtx, build_model
from repro.serving.protocol import render_prometheus

PP = 2
STAGE_SPANS = {
    0: {"prepare", "wait_input", "h2d", "launch", "device", "d2h", "send"},
    1: {"prepare", "wait_input", "recv", "h2d", "launch", "device", "d2h",
        "emit"},
}
DRIVER_SPANS = {"schedule", "gate", "sample", "complete"}


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """Serves prompts longer than the 16-token chunk (chunk and decode
    steps both run) under the profiler, with the benchmark's step
    records on; returns (engine, step records, program spans)."""
    from bench.bubbles import spans_of
    from bench.run import instrument
    from jax.profiler import ProfileData

    cfg = get_config("stablelm-1.6b-smoke")
    model = build_model(cfg, ShardCtx.single(), ModelOptions())
    params = model.init(jax.random.key(0))
    eng = SiPipeEngine(model, params, EngineConfig(
        pp_degree=PP, max_batch=2, max_seq_len=64, n_samplers=2,
        kv_layout="paged", kv_block_size=8, prefill_chunk_tokens=16))
    steps: list = []
    instrument(eng, steps)
    prompts = [[3 + (7 * i + j) % 50 for j in range(n)]
               for i, n in enumerate((5, 21, 34))]
    out = tmp_path_factory.mktemp("trace")
    with jax.profiler.trace(str(out)):
        for _ in eng.generate(prompts, SamplingParams(greedy=True,
                                                      max_new_tokens=6)):
            pass
    eng.shutdown()
    path = max(glob.glob(str(out / "**" / "*.xplane.pb"), recursive=True),
               key=os.path.getmtime)
    return eng, steps, spans_of(ProfileData.from_file(path).planes)


def test_trace_holds_every_span_named_with_its_stage(traced):
    _, _, spans = traced
    names = {name for _, name, _, _ in spans}
    want = {f"sipipe/{n}" for n in DRIVER_SPANS}
    for i, kinds in STAGE_SPANS.items():
        want |= {f"sipipe/stage{i}/{k}" for k in kinds}
    assert want <= names
    # a stage's spans are only those its place in the pipeline has
    assert "sipipe/stage0/recv" not in names
    assert "sipipe/stage0/emit" not in names
    assert "sipipe/stage1/send" not in names
    assert names <= want
    # the TSEM prepare runs on another host thread than the stage's step
    lines = defaultdict(set)
    for ln, name, _, _ in spans:
        lines[name].add(ln)
    for i in STAGE_SPANS:
        assert not (lines[f"sipipe/stage{i}/prepare"]
                    & lines[f"sipipe/stage{i}/launch"])


def test_stage_programs_carry_their_stage(traced):
    eng, _, _ = traced
    for w in eng.stages:
        i = w.stage.index
        for fn in (w.stage.prefill_fn, w.stage.decode_fn, w.stage.chunk_fn):
            assert fn.__name__.endswith(f"_fn_stage{i}")


def test_step_counters_equal_the_step_records(traced):
    eng, steps, _ = traced
    want = {(i, k): dict.fromkeys(("steps", "tokens", "ctx_tokens",
                                   "rows_ctx_tokens", "sampled"), 0)
            for i in range(PP) for k in STEP_KINDS}
    for s in steps:
        c = want[(s["stage"], s["kind"])]
        c["steps"] += 1
        c["tokens"] += s["tokens"]
        c["ctx_tokens"] += s["ctx_sum"]
        c["rows_ctx_tokens"] += s["rows_ctx_sum"]
        c["sampled"] += s["sampled"]
    for w in eng.stages:
        for kind in STEP_KINDS:
            assert w.step_counts[kind] == want[(w.stage.index, kind)]
    # both kinds ran on both stages, and the last stage sampled
    assert all(want[(i, k)]["steps"] > 0 for i in range(PP)
               for k in STEP_KINDS)
    assert want[(PP - 1, "decode")]["sampled"] > 0


def test_metrics_publish_the_counters_flat(traced):
    eng, _, _ = traced
    m = eng.metrics()
    assert m["gate_wait_s"] > 0
    for i, st in enumerate(m["stages"]):
        assert "busy_s" not in st and "bubble_frac" not in st
        for k in ("input_wait_s", "recv_wait_s", "d2h_s", "decode_steps",
                  "chunk_rows_ctx_tokens", "decode_sampled"):
            assert m[f"stage{i}_{k}"] == st[k]
        assert st["d2h_s"] > 0 and st["input_wait_s"] > 0
    assert m["stage0_recv_wait_s"] == 0.0 < m["stage1_recv_wait_s"]
    text = render_prometheus({"r0": m})
    for key in ("stage0_input_wait_s", "stage1_recv_wait_s",
                "stage1_d2h_s", "gate_wait_s"):
        assert f'repro_{key}{{replica="r0"}}' in text
