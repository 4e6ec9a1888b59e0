"""Reduction of a profiler trace to device time, read with
``jax.profiler.ProfileData`` alone.

On a TPU the trace has one plane per chip ("/device:TPU:<n>") whose line
"XLA Modules" holds one event per executed program (a jitted stage step
is named after its function, ``jit_chunk_fn`` or ``jit_decode_fn``) and
whose line "XLA Ops" holds the operations inside them (a Pallas kernel
is a custom call named after its kernel function).  The host plane
("/host:CPU") holds the benchmark's own spans, all named "bench/...":
``bench/window`` marks the measured window, and the stage-step spans
name what the host was doing.  The timestamps of all planes are on one
clock.

:func:`load` keeps only what the reduction reads, as plain tuples
``(plane, line, name, start_ns, dur_ns)``, so a recorded trace can be
stored small and replayed in the tests.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

DEVICE_PREFIX = "/device:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench/"


def find(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


_OPCODE = re.compile(r" ([a-z][a-z0-9-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def label(text: str) -> str:
    """A device op's short name from its HLO text: instruction name,
    opcode (with a custom call's target) and result type, e.g.
    ``closed_call.18 custom-call:tpu_custom_call bf16[256,32,64]``."""
    if " = " not in text:
        return text
    name, rest = text.split(" = ", 1)
    m = _OPCODE.search(rest)
    if not m:
        return name.lstrip("%")
    op = m.group(1)
    t = _TARGET.search(rest)
    if t:
        op += ":" + t.group(1)
    kind = rest[:m.start()].split("{")[0] if not rest.startswith("(") \
        else "tuple"
    return f"{name.lstrip('%')} {op} {kind}"


def load(path: str) -> list:
    """The device ops (by :func:`label`) and modules, and the host's
    bench spans."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        dev = plane.name.startswith(DEVICE_PREFIX)
        for line in plane.lines:
            if dev and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            ops = dev and line.name == OPS_LINE
            for ev in line.events:
                if not dev and not ev.name.startswith(SPAN_PREFIX):
                    continue
                out.append((plane.name, line.name,
                            label(ev.name) if ops else ev.name,
                            float(ev.start_ns), float(ev.duration_ns)))
    return out


def window(events: list):
    """(start_ns, end_ns) of the ``bench/window`` span."""
    for _, _, name, s, d in events:
        if name == SPAN_PREFIX + "window":
            return s, s + d
    raise ValueError("the trace holds no bench/window span")


def _clip(intervals, lo, hi):
    for s, e in intervals:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            yield s, e


def _union(intervals):
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def devices(events: list) -> list:
    return sorted({p for p, line, *_ in events if line == OPS_LINE})


def busy_s(events: list, lo: float, hi: float) -> float:
    """Seconds in [lo, hi] in which an op ran, averaged over the chips."""
    devs = devices(events)
    if not devs:
        return 0.0
    total = 0.0
    for dev in devs:
        iv = [(s, s + d) for p, line, _, s, d in events
              if p == dev and line == OPS_LINE]
        total += sum(e - s for s, e in _union(_clip(iv, lo, hi)))
    return total / len(devs) / 1e9


def _module_of(events: list):
    """op start -> the name of the program (module) running it, without
    its fingerprint: ``jit_chunk_fn``."""
    import bisect

    mods = sorted((s, s + d, name.split("(")[0])
                  for p, ln, name, s, d in events
                  if ln == MODULES_LINE and p.startswith(DEVICE_PREFIX))
    starts = [m[0] for m in mods]

    def find(t):
        i = bisect.bisect_right(starts, t) - 1
        return mods[i][2] if i >= 0 and t < mods[i][1] else "?"
    return find


def _self_time(evs: list) -> list:
    """(name, start, end, self ns) of one line's events: an event's time
    less that of the events nested inside it (a loop op holds its
    body's ops on the same line)."""
    out, stack = [], []
    for name, s, e in sorted(evs, key=lambda v: (v[1], -v[2])):
        while stack and s >= stack[-1][2]:
            out.append(tuple(stack.pop()))
        if stack and e <= stack[-1][2]:
            stack[-1][3] -= e - s
        stack.append([name, s, e, e - s])
    out.extend(tuple(x) for x in stack)
    return out


def op_seconds(events: list, lo: float, hi: float, line=OPS_LINE,
               by_module: bool = False) -> dict:
    """Device self seconds per op (or module) name of the events that
    start within [lo, hi]; with ``by_module`` an op's name is prefixed
    by its program's."""
    where = _module_of(events) if by_module else None
    per_line = defaultdict(list)
    for p, ln, name, s, d in events:
        if ln == line and p.startswith(DEVICE_PREFIX) and lo <= s < hi:
            per_line[p].append((name, s, s + d))
    out = defaultdict(float)
    for evs in per_line.values():
        for name, s, _, own in _self_time(evs):
            key = f"{where(s)}: {name}" if where else name
            out[key] += own / 1e9
    return dict(out)


def matching_seconds(events: list, lo: float, hi: float, substr: str,
                     line=OPS_LINE) -> float:
    return sum(v for k, v in op_seconds(events, lo, hi, line).items()
               if substr in k)


def idle_gaps(events: list, lo: float, hi: float) -> dict:
    """Idle device seconds in [lo, hi] (first chip), by the innermost
    bench span the host was in at each gap's midpoint ("no span" where
    it was in none of them)."""
    devs = devices(events)
    if not devs:
        return {}
    iv = [(s, s + d) for p, line, _, s, d in events
          if p == devs[0] and line == OPS_LINE]
    busy = _union(_clip(iv, lo, hi))
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    spans = [(s, s + d, name) for p, _, name, s, d in events
             if not p.startswith(DEVICE_PREFIX)
             and name != SPAN_PREFIX + "window"]
    out = defaultdict(float)
    for a, b in gaps:
        mid = (a + b) / 2
        inside = [(e - s, name) for s, e, name in spans if s <= mid <= e]
        out[min(inside)[1] if inside else "no span"] += (b - a) / 1e9
    return dict(out)


def top(d: dict, n: int = 10) -> list:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]


def summary(path: str, out: str, keep_ms: float = 300.0):
    """A look at one trace by hand: every plane and line with its event
    count and most frequent names, written to ``out`` (JSON), with the
    events of the window's first ``keep_ms`` as a small recording."""
    import json
    from collections import Counter

    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    planes = []
    for plane in pd.planes:
        lines = []
        for line in plane.lines:
            names = Counter(ev.name for ev in line.events)
            lines.append({"line": line.name, "events": sum(names.values()),
                          "top": names.most_common(12)})
        planes.append({"plane": plane.name, "lines": lines})
    events = load(path)
    lo, hi = window(events)
    cut = lo + keep_ms * 1e6
    keep = [e for e in events if lo <= e[3] < cut
            or e[2] == SPAN_PREFIX + "window"]
    with open(out, "w") as f:
        json.dump({"planes": planes, "window_ns": [lo, hi],
                   "recording": keep}, f)


if __name__ == "__main__":
    import sys

    summary(find(sys.argv[1]), sys.argv[2])
