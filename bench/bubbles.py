"""SiPipe's bubble kinds: the device's idle time in a traced run, split
by what the host was doing, read from the program's own spans.

The program marks its host work with ``sipipe/...`` spans
(``repro.core.tracing``) and names each stage's step programs
``*_stage<i>`` (``jit_decode_fn_stage1``).  :func:`idle_detail` takes
the idle gaps of the first chip in the window, the gaps that
:func:`bench.trace.idle_gaps` takes, and labels each by its **late
stage**, the stage of the first stage program that ends after the gap
does (the one running at its end, or the next to start: a program opens
a little before its first op), and by what that stage's device thread
was doing at the gap's midpoint.  :func:`idle_causes` sums the labels
into :data:`CAUSES`, one for each fix (the S-items of ROADMAP.md):

  inter_stage  the hidden state's host round trip (S3): the late stage
               receiving it (``recv``), or a stage before the last
               copying its own out and handing it on (``d2h``, ``send``)
  sampling     the logits' way to sampled tokens (S6): the last stage
               copying them out and handing them on (``d2h``, ``emit``),
               or waiting for input while the driver waits at the gate
               (``gate``) and the sampler samples (``sample``) or books
               the tokens (``complete``)
  intra_stage  the stage's own step around its program: inputs to the
               device (``h2d``), dispatch (``launch``), the wait for a
               result whose program has ended (``device``), or waiting
               for input while its TSEM ``prepare`` runs
  token_gate   waiting for input while the driver waits at the gate and
               the sampler does neither: the tokens are still on their
               way through the pipeline
  driver       waiting for input while the driver is in another span
               (``schedule``)
  in_program   a gap inside the late stage's running program, between
               its own ops
  other        anything else: no late stage, no span open, or a wait
               for input with the driver in no span

The split assumes TSEM, which gives each stage one device thread whose
spans never overlap.  ``bench/metrics/bubble_<cause>.py`` reads a cause
as a share of the window.  A trace's whole split, with its labels and
the mean of every span, by hand:

  python3 bench/bubbles.py [trace_dir]     # default bench_out/trace
"""
from __future__ import annotations

import bisect
import json
import re
import sys
from collections import defaultdict
from pathlib import Path

if __package__ in (None, ""):        # run as a script
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import trace  # noqa: E402

PREFIX = "sipipe/"
CAUSES = ("inter_stage", "sampling", "intra_stage", "token_gate", "driver",
          "in_program", "other")
SAMPLER = ("sample", "complete")
_STAGE_MODULE = re.compile(r"_stage(\d+)$")
_STAGE_SPAN = re.compile(r"^sipipe/stage(\d+)/(\w+)$")


def load_spans(path: str) -> list:
    """The program's own spans in the trace file ``path``."""
    from jax.profiler import ProfileData

    return spans_of(ProfileData.from_file(path).planes)


def spans_of(planes) -> list:
    """:func:`load_spans` over planes already read: ``(line, name,
    start_ns, dur_ns)``, where ``line`` is the index of the span's host
    thread among its plane's lines (the threads share one name)."""
    out = []
    for plane in planes:
        if plane.name.startswith(trace.DEVICE_PREFIX):
            continue
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    out.append((i, ev.name, float(ev.start_ns),
                                float(ev.duration_ns)))
    return out


def _gaps(events: list, dev: str, lo: float, hi: float) -> list:
    """(start, end) of each stretch of [lo, hi] in which ``dev`` ran no
    op."""
    iv = [(s, s + d) for p, line, _, s, d in events
          if p == dev and line == trace.OPS_LINE]
    gaps, t = [], lo
    for s, e in trace._union(trace._clip(iv, lo, hi)):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return gaps


class _Track:
    """Spans that never overlap one another, looked up by time."""

    def __init__(self, spans):
        self.spans = sorted(spans)
        self.starts = [s for s, _, _ in self.spans]

    def at(self, t: float):
        """The name of the span open at ``t``, or None."""
        i = bisect.bisect_right(self.starts, t) - 1
        if i >= 0 and t <= self.spans[i][1]:
            return self.spans[i][2]
        return None


def _labelled_gaps(events: list, spans: list, lo: float, hi: float):
    """Yields (label, cause, seconds) for each idle gap of the first chip
    in [lo, hi]."""
    devs = trace.devices(events)
    if not devs:
        return
    mods = sorted((s + d, s, int(m.group(1)))
                  for p, ln, name, s, d in events
                  if p == devs[0] and ln == trace.MODULES_LINE
                  and (m := _STAGE_MODULE.search(name.split("(")[0])))
    ends = [e for e, _, _ in mods]
    last = max((i for _, _, i in mods), default=0)
    driver_lines = {ln for ln, name, _, _ in spans
                    if name in (PREFIX + "gate", PREFIX + "schedule")}
    thread, prepare = defaultdict(list), defaultdict(list)
    driver, sampler = [], []
    for ln, name, s, d in spans:
        m = _STAGE_SPAN.match(name)
        if m:
            stage, kind = int(m.group(1)), m.group(2)
            (prepare if kind == "prepare" else thread)[stage].append(
                (s, s + d, kind))
        elif name[len(PREFIX):] in SAMPLER:
            sampler.append((s, s + d, name[len(PREFIX):]))
        elif ln in driver_lines:
            driver.append((s, s + d, name[len(PREFIX):]))
    thread = {k: _Track(v) for k, v in thread.items()}
    prepare = {k: _Track(v) for k, v in prepare.items()}
    driver, sampler = _Track(driver), _Track(sampler)

    for a, b in _gaps(events, devs[0], lo, hi):
        secs = (b - a) / 1e9
        i = bisect.bisect_right(ends, b)
        if i == len(mods):
            yield "no_stage", "other", secs
            continue
        _, start, stage = mods[i]
        if start <= a:
            yield f"stage{stage}/in_program", "in_program", secs
            continue
        mid = (a + b) / 2
        kind = thread[stage].at(mid) if stage in thread else None
        if kind == "recv":
            why, cause = kind, "inter_stage"
        elif kind in ("d2h", "send", "emit"):
            why = kind
            cause = "sampling" if stage == last else "inter_stage"
        elif kind in ("h2d", "launch", "device"):
            why, cause = kind, "intra_stage"
        elif kind != "wait_input":
            why, cause = kind or "no_span", "other"
        elif stage in prepare and prepare[stage].at(mid):
            why, cause = "wait_input/prepare", "intra_stage"
        else:
            held = driver.at(mid)
            busy = sampler.at(mid)
            if held == "gate" and busy:
                why, cause = f"wait_input/gate/{busy}", "sampling"
            elif held == "gate":
                why, cause = "wait_input/gate", "token_gate"
            elif held:
                why, cause = f"wait_input/driver/{held}", "driver"
            else:
                why, cause = "wait_input/idle", "other"
        yield f"stage{stage}/{why}", cause, secs


def idle_causes(events: list, spans: list, lo: float, hi: float) -> dict:
    """Idle seconds of the first chip in [lo, hi] by :data:`CAUSES`;
    they sum to the window less the chip's busy time."""
    out = dict.fromkeys(CAUSES, 0.0)
    for _, cause, secs in _labelled_gaps(events, spans, lo, hi):
        out[cause] += secs
    return out


def idle_detail(events: list, spans: list, lo: float, hi: float) -> dict:
    """Idle seconds of the first chip in [lo, hi] by label:
    ``stage<i>/<span>`` for the late stage's span, ``stage<i>/in_program``,
    ``stage<i>/wait_input/<what>`` for what held its input (``prepare``,
    ``gate``, ``gate/sample``, ``gate/complete``, ``driver/<span>``,
    ``idle``), ``stage<i>/no_span``, or ``no_stage``."""
    out = defaultdict(float)
    for label, _, secs in _labelled_gaps(events, spans, lo, hi):
        out[label] += secs
    return dict(out)


def span_seconds(spans: list, lo: float, hi: float) -> dict:
    """name -> [count, mean ms, total s] of the spans that start in
    [lo, hi]."""
    acc = defaultdict(list)
    for _, name, s, d in spans:
        if lo <= s < hi:
            acc[name].append(d)
    return {k: [len(v), sum(v) / len(v) / 1e6, sum(v) / 1e9]
            for k, v in sorted(acc.items())}


def _trace_dir() -> str:
    from bench.run import TRACE_DIR

    return str(TRACE_DIR)


def run_causes(ctx: dict):
    """:func:`idle_causes` of a traced run's window, computed once and
    kept in ``ctx``; None where it was not traced or its trace holds
    none of the program's spans (a program older than them)."""
    if "events" not in ctx:
        return None
    if "idle_causes" not in ctx:
        spans = ctx.get("spans")
        if spans is None:
            spans = ctx["spans"] = load_spans(trace.find(_trace_dir()))
        ctx["idle_causes"] = idle_causes(
            ctx["events"], spans, ctx["trace_lo"],
            ctx["trace_hi"]) if spans else None
    return ctx["idle_causes"]


def idle_share(ctx: dict, cause: str):
    """Idle seconds of one of :data:`CAUSES` over the traced window, in
    percent; None as :func:`run_causes`."""
    causes = run_causes(ctx)
    if causes is None:
        return None
    return 100.0 * causes[cause] / ((ctx["trace_hi"] - ctx["trace_lo"]) / 1e9)


def main(argv=None):
    argv = sys.argv[1:] if argv is None else argv
    path = trace.find(argv[0] if argv else _trace_dir())
    events, spans = trace.load(path), load_spans(path)
    lo, hi = trace.window(events)
    window_s = (hi - lo) / 1e9
    busy = trace.busy_s(events, lo, hi)
    causes = idle_causes(events, spans, lo, hi)
    print(json.dumps({
        "window_s": window_s, "busy_s": busy, "idle_s": window_s - busy,
        "idle_causes": {k: [v, 100.0 * v / window_s]
                        for k, v in causes.items()},
        "idle_detail": trace.top(idle_detail(events, spans, lo, hi), 40),
        "spans": span_seconds(spans, lo, hi)}, indent=1))


if __name__ == "__main__":
    main()
