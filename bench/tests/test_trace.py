"""The trace reduction on a hand-made trace, and on a short recording of
a TPU v5e trace (bench/tests/data/trace_v5e.json, the first 100 ms of a
glm4-9b-10l.batch window)."""
import json

import pytest

from bench import registry, trace

D, H = "/device:TPU:0", "/host:CPU"
OPS, MODS = trace.OPS_LINE, trace.MODULES_LINE
MS = 1e6

# window 0..100 ms; ops 10-30 and 20-40 overlap (busy 10-40), a loop op
# 60-70 holding a kernel 62-68; host in a decode span over 40-60 and in
# prepare over 70-100
EVENTS = [
    (H, "python3", "bench/window", 0.0, 100 * MS),
    (D, MODS, "jit_decode_fn(1)", 10 * MS, 30 * MS),
    (D, OPS, "fusion.1", 10 * MS, 20 * MS),
    (D, OPS, "paged_kernel", 20 * MS, 20 * MS),
    (D, MODS, "jit_chunk_fn(2)", 60 * MS, 10 * MS),
    (D, OPS, "while.1 while tuple", 60 * MS, 10 * MS),
    (D, OPS, "paged_kernel", 62 * MS, 6 * MS),
    (D, OPS, "late", 95 * MS, 20 * MS),
    (H, "python3", "bench/stage1/decode", 35 * MS, 30 * MS),
    (H, "python3", "bench/stage0/prepare", 70 * MS, 30 * MS),
]


def test_window_busy_and_ops():
    lo, hi = trace.window(EVENTS)
    assert (lo, hi) == (0.0, 100 * MS)
    # busy: 10-40, 60-70 and the clipped 95-100
    assert trace.busy_s(EVENTS, lo, hi) == pytest.approx(0.045)
    # self time: the loop op less the kernel inside it; an op that
    # starts in the window counts whole
    ops = trace.op_seconds(EVENTS, lo, hi)
    assert ops == pytest.approx({"fusion.1": 0.02, "paged_kernel": 0.026,
                                 "while.1 while tuple": 0.004,
                                 "late": 0.02})
    by_mod = trace.op_seconds(EVENTS, lo, hi, by_module=True)
    assert by_mod["jit_chunk_fn: paged_kernel"] == pytest.approx(0.006)
    assert trace.matching_seconds(EVENTS, lo, hi, "decode_fn",
                                  MODS) == pytest.approx(0.03)
    assert trace.top(ops, 1) == [["paged_kernel", pytest.approx(0.026)]]


def test_idle_gaps_by_host_span():
    lo, hi = trace.window(EVENTS)
    gaps = trace.idle_gaps(EVENTS, lo, hi)
    # 0-10: no span; 40-60: decode; 70-95: prepare
    assert gaps == pytest.approx({"no span": 0.01,
                                  "bench/stage1/decode": 0.02,
                                  "bench/stage0/prepare": 0.025})


def test_recorded_v5e_trace():
    rec = json.loads((registry.BENCH / "tests" / "data" /
                      "trace_v5e.json").read_text())
    events = [tuple(e) for e in rec["recording"]]
    lo, hi = trace.window(events)
    assert [lo, hi] == rec["window_ns"]
    assert trace.devices(events) == ["/device:TPU:0"]
    end = lo + 100 * MS
    # the closed loop's first requests reach the device ~44 ms in
    busy = trace.busy_s(events, lo, end)
    assert 0.040 < busy <= 0.060
    mods = trace.op_seconds(events, lo, end, MODS)
    chunk = sum(v for k, v in mods.items() if k.startswith("jit_chunk_fn"))
    assert 0.040 < chunk < 0.060
    # the paged span kernel: a Pallas custom call inside the chunk steps
    ops = trace.op_seconds(events, lo, end, by_module=True)
    kernel = {k: v for k, v in ops.items()
              if "custom-call:tpu_custom_call" in k}
    assert kernel and all(k.startswith("jit_chunk_fn: ") for k in kernel)
    idle = trace.idle_gaps(events, lo, end)
    assert sum(idle.values()) == pytest.approx(0.100 - busy, rel=1e-6)
