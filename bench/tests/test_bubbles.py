"""SiPipe's bubble kinds (bench/bubbles.py) on hand-made events with the
program's spans, and the existing trace readings left as they were once
the program writes its spans and names its programs by stage."""
import json
from collections import defaultdict
from types import SimpleNamespace as NS

import jax
import pytest

from bench import bubbles, registry, trace
from bench.tests.test_trace import EVENTS

D, H = "/device:TPU:0", "/host:CPU"
OPS, MODS = trace.OPS_LINE, trace.MODULES_LINE
MS = 1e6
METRICS = {"bubble_inter_stage": "inter_stage",
           "bubble_intra_stage": "intra_stage",
           "bubble_token_gate": "token_gate",
           "bubble_sampling": "sampling",
           "bubble_in_program": "in_program",
           "bubble_driver": "driver"}


def _program(stage, a_ms, b_ms, fn="decode_fn"):
    """A stage program that opens 0.1 ms before its op, over [a, b]."""
    return [(D, MODS, f"jit_{fn}_stage{stage}(1)", (a_ms - 0.1) * MS,
             (b_ms - a_ms + 0.1) * MS),
            (D, OPS, f"fusion.{stage}", a_ms * MS, (b_ms - a_ms) * MS)]


def _span(line, name, a_ms, b_ms):
    return (line, "sipipe/" + name, a_ms * MS, (b_ms - a_ms) * MS)


# window 0..100 ms on one chip, stage 1 the last
CAUSE_EVENTS = [
    (H, "python3", "bench/window", 0.0, 100 * MS),
    *_program(0, 10, 20),
    (D, MODS, "jit_convert_element_type(3)", 25.9 * MS, 1.1 * MS),
    (D, OPS, "convert.1", 26 * MS, 1 * MS),
    # stage 1's program idles between its ops over 32-33
    (D, MODS, "jit_decode_fn_stage1(2)", 29.9 * MS, 5.1 * MS),
    (D, OPS, "fusion.1", 30 * MS, 2 * MS),
    (D, OPS, "fusion.1", 33 * MS, 2 * MS),
    *_program(0, 40, 45),
    *_program(1, 50, 55, "chunk_fn"),
    *_program(1, 60, 62),
    *_program(0, 66, 68),
    *_program(0, 75, 77),
    *_program(1, 82, 84),
    *_program(0, 88, 90),
]
# host lines: 0 the driver, 1 the sampling worker, 2 and 3 stage 0's
# device and TSEM threads, 4 and 5 stage 1's
CAUSE_SPANS = [
    # 0-10: stage 0 waits for input, the driver in no span: other
    _span(2, "stage0/wait_input", 0, 9.95),
    # 20-26 and 27-30: stage 1 waits for stage 0's hidden state; its
    # TSEM thread's prepare, shorter, is not its device thread's span
    _span(4, "stage1/recv", 18, 29.95),
    _span(5, "stage1/prepare", 22, 27),
    _span(0, "schedule", 21, 23),
    # 35-40: stage 0 moves its inputs to the device: intra-stage
    _span(2, "stage0/h2d", 35.5, 39.8),
    # 45-50: stage 1 waits for its TSEM prepare: intra-stage
    _span(4, "stage1/wait_input", 44, 49.9),
    _span(5, "stage1/prepare", 46, 49),
    # 55-60: the last stage copies its logits out: sampling
    _span(4, "stage1/d2h", 55, 59.8),
    # 62-66: stage 0 copies its hidden state out: inter-stage
    _span(2, "stage0/d2h", 62, 65.8),
    # 68-75: stage 0 waits at the gate while the sampler samples
    _span(2, "stage0/wait_input", 68, 74.9),
    _span(0, "gate", 67, 74.5),
    _span(1, "sample", 69, 73),
    _span(1, "complete", 73, 73.5),
    # 77-82: stage 1 waits at the gate, the sampler idle: token gate
    _span(4, "stage1/wait_input", 77, 81.9),
    _span(0, "gate", 76, 81.5),
    # 84-88: stage 0 waits while the driver schedules
    _span(2, "stage0/wait_input", 84, 87.9),
    _span(0, "schedule", 85, 87),
    # 90-100: no stage program follows: other
    _span(1, "sample", 91, 99),
]
WANT = {"inter_stage": 0.013, "sampling": 0.012, "intra_stage": 0.010,
        "token_gate": 0.005, "driver": 0.004, "in_program": 0.001,
        "other": 0.020}


def test_idle_causes_one_gap_per_cause():
    lo, hi = trace.window(CAUSE_EVENTS)
    causes = bubbles.idle_causes(CAUSE_EVENTS, CAUSE_SPANS, lo, hi)
    assert causes == pytest.approx(WANT)
    idle = 0.100 - trace.busy_s(CAUSE_EVENTS, lo, hi)
    assert sum(causes.values()) == pytest.approx(idle)
    detail = bubbles.idle_detail(CAUSE_EVENTS, CAUSE_SPANS, lo, hi)
    assert detail == pytest.approx({
        "stage0/wait_input/idle": 0.010, "stage1/recv": 0.009,
        "stage1/in_program": 0.001, "stage0/h2d": 0.005,
        "stage1/wait_input/prepare": 0.005, "stage1/d2h": 0.005,
        "stage0/d2h": 0.004, "stage0/wait_input/gate/sample": 0.007,
        "stage1/wait_input/gate": 0.005,
        "stage0/wait_input/driver/schedule": 0.004, "no_stage": 0.010})


def test_the_readers_share_one_split_of_the_window():
    lo, hi = trace.window(CAUSE_EVENTS)
    ctx = {"events": CAUSE_EVENTS, "spans": CAUSE_SPANS, "trace_lo": lo,
           "trace_hi": hi}
    for name, cause in METRICS.items():
        assert registry.reader(name)(ctx) == pytest.approx(
            100 * WANT[cause] / 0.1)
    assert ctx["idle_causes"] == pytest.approx(WANT)
    # an untraced run reports none of them
    assert all(registry.reader(n)({"t0": 0.0}) is None for n in METRICS)


def test_a_trace_without_the_program_spans():
    """The program before its spans: every gap is other, and the bubble
    metrics are left out."""
    lo, hi = trace.window(EVENTS)
    causes = bubbles.idle_causes(EVENTS, [], lo, hi)
    assert causes["other"] == pytest.approx(0.055)
    assert sum(causes.values()) == causes["other"]
    for name in METRICS:
        ctx = {"events": EVENTS, "spans": [], "trace_lo": lo,
               "trace_hi": hi}
        assert registry.reader(name)(ctx) is None


def _planes(events, spans):
    """Profiler planes as ``ProfileData`` gives them, from tuples."""
    lines = defaultdict(list)
    for p, ln, name, s, d in events:
        lines[(p, ln)].append(NS(name=name, start_ns=s, duration_ns=d))
    host = [NS(name="python3", events=[
        NS(name=name, start_ns=s, duration_ns=d)
        for ln, name, s, d in spans if ln == i])
        for i in range(1 + max((ln for ln, *_ in spans), default=-1))]
    host.append(NS(name="python3", events=lines.pop((H, "python3"), [])))
    planes = defaultdict(list)
    for (p, ln), evs in lines.items():
        planes[p].append(NS(name=ln, events=evs))
    return ([NS(name=H, lines=host)]
            + [NS(name=p, lines=ls) for p, ls in planes.items()])


@pytest.fixture
def profile_of(monkeypatch):
    """Makes ``ProfileData.from_file`` read planes built from tuples."""
    def use(events, spans):
        planes = _planes(events, spans)
        monkeypatch.setattr(jax.profiler, "ProfileData", NS(
            from_file=lambda _path: NS(planes=planes)))
    return use


def test_a_traced_run_loads_the_spans_from_its_trace(profile_of,
                                                     monkeypatch):
    profile_of(CAUSE_EVENTS, CAUSE_SPANS)
    assert sorted(bubbles.load_spans("x")) == sorted(CAUSE_SPANS)
    found = []
    monkeypatch.setattr(trace, "find", lambda d: found.append(d) or d)
    lo, hi = trace.window(CAUSE_EVENTS)
    ctx = {"events": trace.load("x"), "trace_lo": lo, "trace_hi": hi}
    assert registry.reader("bubble_sampling")(ctx) == pytest.approx(12.0)
    assert found == [bubbles._trace_dir()]
    assert found[0].endswith("bench_out/trace")
    assert sorted(ctx["spans"]) == sorted(CAUSE_SPANS)
    assert bubbles.run_causes(ctx) == pytest.approx(WANT)


def test_the_split_by_hand(profile_of, monkeypatch, capsys):
    profile_of(CAUSE_EVENTS, CAUSE_SPANS)
    monkeypatch.setattr(trace, "find", lambda d: d)
    bubbles.main(["some/trace"])
    out = json.loads(capsys.readouterr().out)
    assert out["window_s"] == pytest.approx(0.1)
    assert {k: v[0] for k, v in out["idle_causes"].items()} == \
        pytest.approx(WANT)
    assert sum(v[0] for v in out["idle_causes"].values()) == \
        pytest.approx(out["idle_s"])
    assert dict(out["idle_detail"])["stage0/wait_input/gate/sample"] == \
        pytest.approx(0.007)
    assert out["spans"]["sipipe/gate"] == pytest.approx([2, 6.5, 0.013])


def _recording():
    rec = json.loads((registry.BENCH / "tests" / "data" /
                      "trace_v5e.json").read_text())
    return [tuple(e) for e in rec["recording"]]


def _renamed(events):
    """The same trace from the program as it now is: stage programs
    named with their stage (alternating, as pp=2 runs them)."""
    out, k = [], 0
    for p, ln, name, s, d in sorted(events, key=lambda e: e[3]):
        base, _, rest = name.partition("(")
        if ln == MODS and base in ("jit_chunk_fn", "jit_decode_fn"):
            name, k = f"{base}_stage{k % 2}({rest}", k + 1
        out.append((p, ln, name, s, d))
    return out


def _readings(events):
    """What the existing trace readers and reductions read."""
    lo, hi = trace.window(events)
    hi = min(hi, lo + 100 * MS)
    cfg = registry.config("glm4-9b-10l")
    block = registry.block(cfg["block"])
    peaks = registry.load_json(registry.BENCH / "peaks.json")["devices"][
        "TPU v5 lite"]
    steps = [dict(t0=1.0, stage=i, kind=k, batch=16, width=w, tokens=t,
                  layers=5, last=i == 1, ctx_sum=4000, rows_ctx_sum=3000,
                  sampled=16)
             for i in (0, 1) for k, w, t in (("decode", 1, 16),
                                              ("chunk", 256, 256))]
    ctx = {"events": events, "trace_lo": lo, "trace_hi": hi,
           "dims": block.dims(cfg), "block": block, "peaks": peaks,
           "steps": steps, "t0": 0.0, "t1": 2.0}
    out = {name: registry.reader(name)(ctx) for name in (
        "step_mfu.batch", "step_mfu.chat", "decode_hbm_share",
        "span_attn_roofline")}
    out.update(busy=trace.busy_s(events, lo, hi),
               ops=trace.op_seconds(events, lo, hi),
               idle_gaps=trace.idle_gaps(events, lo, hi),
               devices=trace.devices(events))
    return out


@pytest.mark.parametrize("source", ["hand-made", "recorded-v5e"])
def test_program_spans_leave_existing_readings_alone(source, profile_of):
    """With the program's spans in the trace and its stage programs
    renamed, what the trace loads and every existing reading of it are
    as before."""
    events = EVENTS if source == "hand-made" else _recording()
    lo, _ = trace.window(events)
    spans = [_span(0, "gate", 0, 60), _span(1, "stage1/recv", 5, 50),
             _span(2, "stage0/prepare", 30, 90)]
    spans = [(ln, n, s + lo, d) for ln, n, s, d in spans]
    profile_of(_renamed(events), spans)
    loaded = trace.load("x")
    assert sorted(bubbles.load_spans("x")) == sorted(spans)
    assert not [e for e in loaded if e[2].startswith("sipipe/")]
    before, after = _readings(events), _readings(loaded)
    assert after == before
    found = [k for k, v in before.items() if v not in (None, {}, 0.0)]
    want = {"hand-made": {"decode_hbm_share"},
            "recorded-v5e": {"span_attn_roofline"}}[source]
    assert want <= set(found)
