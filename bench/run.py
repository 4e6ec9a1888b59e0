"""Runs one benchmark cell once on the chip and prints one JSON line.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell (BENCHMARK.json ``workloads``) is one configuration under one
traffic mix, and the configuration names its block
(``bench/blocks/<name>.py``).  The run makes the block's weights from
the seed on the device, builds the program's HTTP server over one
SiPipe engine, warms up every shape the cell's engine settings can
produce, and starts a load generator in a child process that streams
requests over HTTP.  A closed loop starts the cell's ``ramp_s`` before
the window, so that the engine's seats are full when it opens; set-up
ends at the opening.  The window lasts ``--seconds``, and the streams
still open are read for the cell's tail.
``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` runs
the same window under the profiler and reports its per-layer metrics.
After the window the program is freed and a sample of the finished
requests is checked against the block's plain float32 reference; the
numbers compared are printed beside their limits, last on standard
error and last in the result line.

The run exits non-zero, printing no result, when JAX finds no
accelerator, fewer chips than the cell asks for, or a device that is
not in bench/peaks.json.
"""
from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import registry  # noqa: E402
from bench.loadgen import CUT  # noqa: E402

LEAD_S = 1.0          # the load generator's start after its launch, and
                      # the profiler's before the window
TRACE_DIR = ROOT / "bench_out" / "trace"


def log(msg: str):
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def chip(workload: dict):
    """The first device and its peaks, after checking platform, kind and
    count; exits without a result otherwise."""
    import jax

    devs = jax.devices()
    d = devs[0]
    if d.platform == "cpu":
        raise SystemExit(f"no accelerator: JAX found only {devs}")
    peaks = registry.load_json(registry.BENCH / "peaks.json")["devices"]
    if d.device_kind not in peaks:
        raise SystemExit(f"device kind {d.device_kind!r} is not in "
                   f"bench/peaks.json: {sorted(peaks)}")
    if len(devs) < workload["chips"]:
        raise SystemExit(f"the cell asks for {workload['chips']} chips, JAX "
                   f"found {len(devs)}")
    return d, peaks[d.device_kind]


def compile_cache():
    """The program's persistent compile cache (in the checkout, unless
    JAX_COMPILATION_CACHE_DIR names one), caching every program."""
    import jax

    from bench import program  # noqa: F401  (puts src/ on the path)
    from repro.launch.compile_cache import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileCounter:
    """Counts programs lowered (compiled or loaded from the cache)."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        import jax.monitoring as mon

        self.n = 0
        self.on = False
        mon.register_event_duration_secs_listener(self._hear)

    def _hear(self, name, _secs, **_kw):
        if self.on and name == self.EVENT:
            self.n += 1


def stage_shapes(cfg: dict, eng) -> tuple:
    """Every (batch, width, table) a stage step can see under the cell's
    engine settings: decode steps of 1..max_batch rows, and packed chunk
    steps of every power-of-two bucket from the smallest that can hold
    more tokens than rows up to the token budget."""
    from repro.core.scheduler import bucket_width

    e = cfg["engine"]
    budget = eng.scheduler.token_budget
    widths = eng.kv_manager.table_widths
    decode, chunk = [], []
    for b in range(1, e["max_batch"] + 1):
        for nb in widths:
            decode.append((b, 1, nb))
            for w in sorted({bucket_width(n)
                             for n in range(b + 1, budget + 1)}):
                chunk.append((b, w, nb))
    return decode, chunk


def warm_up(cfg: dict, eng) -> int:
    """Runs each stage step once at every shape of :func:`stage_shapes`,
    with inputs made as the engine makes them and every block-table
    entry on the trash block (the pool's content is untouched)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    decode, chunk = stage_shapes(cfg, eng)
    pad = eng.kv_manager.pad_block
    d = eng.arch.d_model
    n = 0
    for w in eng.stages:
        st = w.stage
        for b, width, nb in decode + chunk:
            rows = width if width > 1 else b
            if st.is_first:
                x = jnp.asarray(np.zeros(rows, np.int32))
            else:
                x = jnp.asarray(np.zeros((rows, d), np.float32), jnp.bfloat16)
            tables = jnp.asarray(np.full((b, nb), pad, np.int32))
            if width > 1:
                out, w.cache = st.chunk_fn(
                    st.params, w.cache, x,
                    jnp.asarray(np.zeros(width, np.int32)),
                    jnp.asarray(np.zeros(width, np.int32)),
                    jnp.asarray(np.zeros(b, np.int32)),
                    jnp.asarray(np.zeros(b, np.int32)),
                    jnp.asarray(np.ones(1, np.int32))[0], tables)
            else:
                out, w.cache = st.decode_fn(
                    st.params, w.cache, x,
                    jnp.asarray(np.zeros(b, np.int32)), tables)
            np.asarray(jax.block_until_ready(out), np.float32)
            n += 1
    return n


def http_warm_up(addr, vocab: int, cfg: dict):
    """Two short streamed requests through the whole path, for the small
    programs outside the stage steps."""
    import http.client

    e = cfg["engine"]
    for plen in (8, e["chunk_tokens"] + 24):
        conn = http.client.HTTPConnection(*addr, timeout=300)
        conn.request("POST", "/v1/completions", json.dumps(
            {"prompt": [2 + (i * 7919) % (vocab - 2) for i in range(plen)],
             "max_tokens": 4, "temperature": 0.0, "stream": True}),
            {"Content-Type": "application/json"})
        resp = conn.getresponse()
        body = resp.read()
        conn.close()
        if resp.status != 200 or b"[DONE]" not in body:
            raise RuntimeError(f"warm-up request failed: {resp.status} "
                               f"{body[-200:]!r}")


def instrument(eng, steps: list):
    """Trace runs only: a bench span around each stage step and each
    TSEM prepare, and a record of each step's work for the FLOP and
    byte counts."""
    import jax
    import numpy as np

    for w in eng.stages:
        ex, st = w.executor, w.stage
        layers = st.groups[1] - st.groups[0]

        def execute(desc, bufs, run=ex.execute_fn, st=st, layers=layers):
            if desc.width > 1:
                kind = "chunk"
                n = int(bufs["n_valid"][0])
                pos = bufs["pack_positions"][:n]
                seq = bufs["pack_seq"][:n]
            else:
                kind = "decode"
                n = desc.batch
                pos = bufs["positions"][:n]
                seq = np.arange(n)
            ctx = np.zeros(desc.batch, np.int64)
            np.maximum.at(ctx, seq, pos.astype(np.int64) + 1)
            t0 = time.monotonic()
            with jax.profiler.TraceAnnotation(
                    f"bench/stage{st.index}/{kind}"):
                out = run(desc, bufs)
            steps.append({
                "t0": t0, "t1": time.monotonic(), "stage": st.index,
                "kind": kind, "batch": desc.batch, "width": desc.width,
                "tokens": n, "layers": layers, "last": st.is_last,
                "ctx_sum": int(pos.astype(np.int64).sum() + n),
                "rows_ctx_sum": int(ctx.sum()),
                "sampled": len(desc.sched.sample_indices())})
            return out

        def prepare(sched, bufs, run=ex.prepare_fn, st=st):
            with jax.profiler.TraceAnnotation(f"bench/stage{st.index}/prepare"):
                return run(sched, bufs)

        ex.execute_fn = execute
        ex.prepare_fn = prepare


def counters(eng) -> dict:
    """Engine counters read between steps (plain attribute reads)."""
    return {
        "sample_s": eng.sample_time,
        "prep_s": sum(w.executor.prep_time for w in eng.stages),
        "preemptions": eng.scheduler.n_preemptions,
        "waiting": len(eng.scheduler.waiting),
        "seats": sum(len(m) for m in eng.scheduler.slot_members),
    }


def start_load(plan: dict):
    gen = subprocess.Popen(
        [sys.executable, str(registry.BENCH / "loadgen.py")],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
    gen.stdin.write(json.dumps(plan))
    gen.stdin.close()
    return gen


def sample(records: list, check: dict, seed: int) -> list:
    """Finished requests to check, as the cell's ``check`` asks: the
    longest whole, and ``requests`` - 1 others drawn from the seed, each
    cut to its first ``tokens_per_request`` served tokens, so that the
    sample spans many of the engine's concurrent slots."""
    import numpy as np

    done = [r for r in records if r["done"] and r["status"] == 200
            and len(r["tokens"]) == r["max_tokens"]]
    if not done:
        return []
    done.sort(key=lambda r: -(r["prompt_len"] + r["max_tokens"]))
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 0xC4EC])
    rest = [done[i] for i in 1 + rng.permutation(len(done) - 1)]
    cap = check["tokens_per_request"]
    return [done[0]] + [dict(r, tokens=r["tokens"][:cap])
                        for r in rest[:check["requests"] - 1]]


def state_below_dtype(eng, dtype: str) -> int:
    """Leaves of the served state (stage parameters and KV pools) held in
    another type than the configuration states: 0 for a sound engine;
    the program's own int8 KV cache reads 2 per stage (its K and V pools)."""
    import jax

    return sum(str(a.dtype) != dtype for w in eng.stages
               for a in jax.tree.leaves((w.stage.params, w.cache)))


def check(cfg: dict, block, cell: dict, seed: int, picked: list,
          reqs: list, window_compiles: int, state_leaves: int) -> tuple:
    """Compares the sample with the block's reference; (correct,
    numbers)."""
    t0 = time.monotonic()
    w = block.make(cfg, seed)
    worst, n_tok = 0.0, 0
    for r in picked:
        g = block.served_gaps(w, cfg, reqs[r["i"]]["prompt"], r["tokens"])
        worst = max(worst, float(g.max()))
        n_tok += len(g)
    del w
    log(f"reference over {len(picked)} requests, {n_tok} tokens: "
        f"{time.monotonic() - t0:.1f} s")
    limit = cell["check"]["logit_gap_limit"]
    numbers = {
        "logit_gap_max": {"value": worst, "limit": limit},
        "tokens_checked": {"value": n_tok, "limit": 1},
        "state_not_" + cfg["dtype"]: {"value": state_leaves, "limit": 0},
        "window_compiles": {"value": window_compiles, "limit": 0},
    }
    correct = (bool(picked) and worst <= limit and n_tok >= 1
               and state_leaves == 0 and window_compiles == 0)
    return correct, numbers


def build(cfg: dict, block, seed: int, kv_quant: bool = False,
          warm: bool = True):
    """The block's weights from the seed, the server over one engine,
    every stage shape warmed (unless ``warm`` is off), the server
    started.  Returns (server, engine, stage steps warmed)."""
    from bench import program

    _, model = block.program_model(cfg, kv_quant=kv_quant)
    # no name here holds the weights: while the engine cuts its stages
    # from them, the program alone decides how long the full tree lives
    server, eng = program.build_server(
        cfg, model, block.program_params(model, block.make(cfg, seed)),
        seed)
    gc.collect()
    n_warm = warm_up(cfg, eng) if warm else 0
    server.start()
    http_warm_up(server.address, cfg["vocab_size"], cfg)
    return server, eng, n_warm


def plan_requests(cell: dict, mix: dict, cfg: dict, seconds: float,
                  seed: int) -> list:
    from bench import traffic

    if cell["loop"] == "open":
        return traffic.open_loop(mix, cell["rate_rps"], seconds, seed,
                                 cfg["vocab_size"])
    return traffic.requests(mix, cell["pool"], seed, cfg["vocab_size"],
                            block=cell["concurrency"])


def drive(server, eng, cell: dict, reqs: list, seconds: float,
          trace: bool = False, counter: CompileCounter = None) -> dict:
    """Starts the load generator, which streams ``reqs`` (a closed loop
    from the cell's ``ramp_s`` before the window), then opens the window
    for ``seconds``; open streams are read for the cell's tail.  Returns
    the records and the engine counters at the window's edges."""
    host, port = server.address
    start = time.monotonic() + LEAD_S
    t0 = start + cell.get("ramp_s", 0.0)
    gen = start_load({"host": host, "port": port, "start": start, "t0": t0,
                      "seconds": seconds, "tail_s": cell["tail_s"],
                      "loop": cell["loop"],
                      "concurrency": cell.get("concurrency", 0),
                      "requests": reqs})
    try:
        ctx = _window(gen, eng, t0, seconds, trace, counter)
        ctx["start"] = start
        return ctx
    finally:
        if gen.poll() is None:
            gen.kill()
            gen.wait()


def _window(gen, eng, t0: float, seconds: float, trace: bool,
            counter) -> dict:
    import jax

    time.sleep(max(0.0, t0 - LEAD_S - time.monotonic()))
    if trace:
        import shutil

        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
    time.sleep(max(0.0, t0 - time.monotonic()))
    if counter is not None:
        counter.on = True
    c0 = counters(eng)
    with jax.profiler.TraceAnnotation("bench/window"):
        time.sleep(max(0.0, t0 + seconds - time.monotonic()))
    c1 = counters(eng)
    if counter is not None:
        counter.on = False
    if trace:
        jax.profiler.stop_trace()
    out = gen.stdout.read()
    gen.wait()
    load = json.loads(out)
    # the streams the load generator cut are aborted as the server next
    # writes to them: let the engine go idle before anything else
    deadline = time.monotonic() + 60
    while eng.has_work and time.monotonic() < deadline:
        time.sleep(0.05)
    return {"t0": t0, "t1": t0 + seconds, "seconds": seconds,
            "records": load["records"], "late_s": load["late_s"],
            "c0": c0, "c1": c1}


def stop(server, eng) -> dict:
    """Stops the server and engine; returns the engine's request records."""
    server.close(drain=True, timeout=30)
    return eng.metrics()["requests"]


def report(spec: dict, name: str, per_layer: bool, ctx: dict,
           setup_s: float, base: Path = registry.BENCH) -> dict:
    """The cell's metrics for this kind of run, each from its reader
    (``setup_s`` from the harness's own clock); a reader that finds
    nothing to read leaves its metric out."""
    metrics = {}
    for m in registry.metrics_for(spec, name, per_layer):
        v = setup_s if m["name"] == "setup_s" else \
            registry.reader(m["name"], base)(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    return metrics


def run(name: str, seed: int, seconds: float, trace: bool, device=None,
        peaks: dict = None, spec: dict = None,
        base: Path = registry.BENCH) -> dict:
    """One run of cell ``name``; returns the result object.  ``base`` is
    where its cell, configuration, block and mix files are found."""
    import jax

    spec = spec or registry.benchmark()
    wl = registry.workload(name, spec)
    cell = registry.cell(name, base)
    cfg = registry.config(wl["config"], base)
    block = registry.block(cfg["block"], base)
    mix = registry.traffic(wl["traffic"], base)
    device = device or jax.devices()[0]
    counter = CompileCounter()

    server, eng, n_warm = build(cfg, block, seed)
    steps: list = []
    if trace:
        instrument(eng, steps)
    reqs = plan_requests(cell, mix, cfg, seconds, seed)
    ctx = drive(server, eng, cell, reqs, seconds, trace, counter)
    setup_s = ctx["t0"] - T_PROCESS
    state_leaves = state_below_dtype(eng, cfg["dtype"])
    stats = device.memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))
    ctx["engine_requests"] = stop(server, eng)
    del server, eng
    gc.collect()
    log(f"after teardown {sum(a.nbytes for a in jax.live_arrays())} bytes "
        f"of arrays live; peak {peak}")
    t0, t1 = ctx["t0"], ctx["t1"]
    ctx.update(dims=block.dims(cfg), block=block, peaks=peaks, steps=steps,
               cfg=cfg, cell=cell)
    if trace:
        from bench import trace as tr

        events = tr.load(tr.find(str(TRACE_DIR)))
        lo, hi = tr.window(events)
        ctx.update(events=events, trace_lo=lo, trace_hi=hi)
    metrics = report(spec, name, trace, ctx, setup_s)

    records = ctx["records"]
    due = [r for r in records if r["due"] < t1]
    attempted = len(due)
    failed = len([r for r in due if r["status"] not in (None, 200)
                  or (r["error"] and r["error"] != CUT)])
    from bench.stats import tokens_in_window

    log(f"setup {setup_s:.3f} s, {n_warm} stage steps warmed, "
        f"{attempted} requests in the window, load generator late "
        f"p50 {ctx['late_s']['p50']} s max {ctx['late_s']['max']} s")
    firsts = sorted(r["events"][0][0] for r in records if r["events"])
    seats = cfg["engine"]["pp"] * cfg["engine"]["max_batch"]
    print(json.dumps({"output_tok_s": tokens_in_window(ctx) / seconds,
                      "requests_due": attempted, "late_s": ctx["late_s"],
                      "seats_at_open": ctx["c0"]["seats"],
                      "waiting_at_open": ctx["c0"]["waiting"],
                      "seats_first_token_s": firsts[seats - 1] - ctx["start"]
                      if len(firsts) >= seats else None,
                      "other_metrics": {k: registry.reader(k)(ctx)
                                        for k in ("output_tok_s",
                                                  "ttft_p95_ms",
                                                  "itl_p95_ms")}}),
          flush=True)

    picked = sample(records, cell["check"], seed)
    correct, numbers = check(cfg, block, cell, seed, picked, reqs,
                             counter.n, state_leaves)
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics,
              "device": {"platform": device.platform,
                         "kind": device.device_kind,
                         "count": len(jax.devices()),
                         "memory_peak_bytes": peak}}
    if trace:
        result["device"]["busy_s"] = tr.busy_s(events, lo, hi)
        result["device"]["window_s"] = (hi - lo) / 1e9
        result["breakdown"] = {
            "device_ops": tr.top(tr.op_seconds(events, lo, hi,
                                               by_module=True)),
            "idle_gaps": tr.top(tr.idle_gaps(events, lo, hi))}
    result["check"] = numbers
    for k, v in numbers.items():
        log(f"check {k}: {v['value']!r} (limit {v['limit']!r})")
    return result


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    # the TPU runtime's logs stay inside the checkout, not in /tmp
    logs = ROOT / "bench_out" / "tpu_logs"
    logs.mkdir(parents=True, exist_ok=True)
    os.environ.setdefault("TPU_LOG_DIR", str(logs))
    spec = registry.benchmark()
    compile_cache()
    device, peaks = chip(registry.workload(args.workload, spec))
    result = run(args.workload, args.seed, args.seconds, bool(args.trace),
                 device, peaks, spec)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
