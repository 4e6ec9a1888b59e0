"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  Component latencies used by
the simulator are MEASURED from this repo's real implementations (sampler,
SAT channels, TSEM executors); the pipeline-level reproductions of the
paper's H100 figures come from the calibrated discrete-event simulator
(benchmarks/pp_sim.py) since this container exposes one CPU device.

  PYTHONPATH=src python -m benchmarks.run            # everything
  PYTHONPATH=src python -m benchmarks.run --only sampler,ablation
"""
from __future__ import annotations

import argparse
import time
from typing import Callable, Dict, List

import numpy as np

ROWS: List[str] = []


def emit(name: str, us_per_call: float, derived: str = ""):
    row = f"{name},{us_per_call:.2f},{derived}"
    ROWS.append(row)
    print(row, flush=True)


def _time(fn: Callable, *args, reps: int = 5, warmup: int = 1) -> float:
    for _ in range(warmup):
        fn(*args)
    t0 = time.perf_counter()
    for _ in range(reps):
        fn(*args)
    return (time.perf_counter() - t0) / reps


# ---------------------------------------------------------------------------
# §5.1 — column-wise CPU sampling microbenchmark (real measurement)
# ---------------------------------------------------------------------------

def bench_sampler() -> Dict[str, float]:
    """CPU sampling cost — incremental vs naive recompute at serving scale
    (V ~ 152k, B up to 256) and realistic history depth (512 generated +
    prompt tokens, where the naive path's per-iteration recompute hurts)."""
    from repro.core.sampler import ColumnWiseSampler, NaiveSampler
    from repro.core.sampling_params import SamplingParams

    out = {}
    params = SamplingParams(temperature=0.8, top_k=50,
                            frequency_penalty=0.5, presence_penalty=0.2)
    HIST = 512
    for v, b in ((151_936, 64), (151_936, 256), (32_000, 256)):
        rng = np.random.default_rng(0)
        z = rng.normal(size=(b, v)).astype(np.float32)
        cw = ColumnWiseSampler(v, b, max_len=4096)
        nv = NaiveSampler(v)
        # seed realistic histories: incremental folds them once; naive will
        # recompute them on every subsequent iteration
        hist = [rng.integers(0, v, HIST) for _ in range(b)]
        cw.seed_prompt(0, b, list(range(b)), hist)
        nv.history[0] = [h.astype(np.int64) for h in hist]
        t_cw = _time(lambda: cw.sample(z, params), reps=3)
        t_nv = _time(lambda: nv.sample(z, params), reps=3)
        emit(f"sampler/incremental_v{v}_b{b}", t_cw * 1e6,
             f"hist={HIST} speedup_vs_naive={t_nv / t_cw:.2f}x")
        emit(f"sampler/naive_recompute_v{v}_b{b}", t_nv * 1e6, f"hist={HIST}")
        # penalty-path isolation (greedy: no softmax/top-k in either path)
        g = SamplingParams(greedy=True, frequency_penalty=0.5,
                           presence_penalty=0.2)
        t_cwp = _time(lambda: cw.sample(z, g), reps=3)
        t_nvp = _time(lambda: nv.sample(z, g), reps=3)
        emit(f"sampler/penalty_only_incremental_v{v}_b{b}", t_cwp * 1e6,
             f"speedup_vs_naive={t_nvp / t_cwp:.2f}x")
        # transposed-shard ingestion path (§5.1(3))
        zt = np.ascontiguousarray(z.T)
        cw_t = ColumnWiseSampler(v, b, max_len=4096)
        t_cwt = _time(lambda: cw_t.sample(zt, params, transposed=True), reps=3)
        emit(f"sampler/transposed_shards_v{v}_b{b}", t_cwt * 1e6,
             "zero-gather TP-shard concat path")
        out[f"cw_{v}_{b}"] = t_cw
    return out


# ---------------------------------------------------------------------------
# §5.3 — SAT vs structure-unaware transmission (real channel objects)
# ---------------------------------------------------------------------------

def bench_sat() -> Dict[str, float]:
    from repro.core.sat import StructureAwareChannel, StructureUnawareChannel

    b, d = 256, 8192
    tensors = {"hidden": np.zeros((b, d), np.float16),
               "residual": np.zeros((b, d), np.float16)}
    round_lat = 0.0007  # 0.7 ms per synchronous round (RDMA-scale, §5.3)

    def unaware_iter():
        ch = StructureUnawareChannel(round_lat)
        ch.send(tensors)
        ch.recv()

    aware = StructureAwareChannel(round_lat)
    aware.send(tensors)
    aware.recv()  # capture iteration

    def aware_iter():
        aware.send(tensors)
        aware.recv()

    t_u = _time(unaware_iter, reps=3)
    t_a = _time(aware_iter, reps=3)
    emit("sat/structure_unaware_per_edge", t_u * 1e6, "rounds=4")
    emit("sat/structure_aware_per_edge", t_a * 1e6,
         f"rounds=1 speedup={t_u / t_a:.2f}x")
    return {"t_edge_unaware": t_u, "t_edge_aware": t_a}


# ---------------------------------------------------------------------------
# §5.2 — TSEM overlap (real executor threads)
# ---------------------------------------------------------------------------

def bench_tsem() -> None:
    from repro.core.scheduler import SchedulingOutput
    from repro.core.tsem import SynchronousExecutor, TokenSafeExecutor

    PREP = EXEC = 0.004
    N = 24

    def prepare(s, bufs):
        time.sleep(PREP)

    def execute(d, bufs):
        time.sleep(EXEC)
        return True

    def sched(it):
        return SchedulingOutput(it, 0, [0], np.zeros(1, np.int32),
                                np.zeros(1, np.int32), False)

    sync = SynchronousExecutor(prepare, execute)
    t0 = time.perf_counter()
    for it in range(N):
        sync.run(sched(it))
    t_sync = (time.perf_counter() - t0) / N

    ex = TokenSafeExecutor(prepare, execute)
    ex.start()
    t0 = time.perf_counter()
    for it in range(N):
        ex.submit(sched(it))
    for it in range(N):
        ex.result(it, timeout=30)
    t_tsem = (time.perf_counter() - t0) / N
    ex.stop()
    emit("tsem/synchronous_per_iter", t_sync * 1e6, "prep+exec serialized")
    emit("tsem/token_safe_per_iter", t_tsem * 1e6,
         f"overlap_gain={t_sync / t_tsem:.2f}x")


# ---------------------------------------------------------------------------
# Fig. 1 / 8 — throughput across engines and parallel configs (simulator)
# ---------------------------------------------------------------------------

PAPER_SAMPLE_S = 0.0015 * 48  # the paper's engineered samplers finish a
# microbatch in 1-2 ms *per sampler*; expressed pre-pool-division


def bench_throughput(measured: Dict[str, float]) -> None:
    """Two calibrations of the async sampling latency:
      paper  — the paper's engineered C-level samplers (1.5 ms pooled)
      meas   — this repo's numpy sampler (single-core full batch / pool)
    """
    from benchmarks.pp_sim import paper_costs, simulate

    t_meas = measured.get("cw_151936_256", 0.10)
    for model in ("qwen-2.5-72b", "llama-3.1-70b", "mixtral-8x7b",
                  "deepseek-v3", "llama-3.1-405b"):
        for p in (2, 4):
            base = simulate(paper_costs(model, p,
                                        measured_cpu_sample_s=PAPER_SAMPLE_S),
                            sipipe=False)
            emit(f"throughput/{model}_p{p}_baseline",
                 1e6 / base.tokens_per_s, f"iters_per_s={base.tokens_per_s:.1f}")
            for calib, t_s in (("paper", PAPER_SAMPLE_S), ("meas", t_meas)):
                sip = simulate(paper_costs(model, p, measured_cpu_sample_s=t_s,
                                           sipipe=True), sipipe=True)
                emit(f"throughput/{model}_p{p}_sipipe_{calib}",
                     1e6 / sip.tokens_per_s,
                     f"iters_per_s={sip.tokens_per_s:.1f} "
                     f"speedup={sip.tokens_per_s / base.tokens_per_s:.2f}x")


# ---------------------------------------------------------------------------
# Fig. 3 / 4 / 11 — per-stage bubble anatomy (simulator timelines)
# ---------------------------------------------------------------------------

def bench_bubbles(measured: Dict[str, float]) -> None:
    from benchmarks.pp_sim import paper_costs, simulate

    t_cpu = PAPER_SAMPLE_S
    for name, sip in (("baseline", False), ("sipipe", True)):
        r = simulate(paper_costs("deepseek-v3", 4,
                                 measured_cpu_sample_s=t_cpu, sipipe=sip),
                     sipipe=sip)
        fr = " ".join(f"s{i}={f:.2f}" for i, f in enumerate(r.bubble_fracs))
        emit(f"bubbles/deepseek-v3_p4_{name}", r.tpot_mean * 1e6,
             f"bubble_fracs: {fr}")


# ---------------------------------------------------------------------------
# Fig. 9 — batch size sweep  /  Fig. 10 — GPU-count scalability
# ---------------------------------------------------------------------------

def bench_batch_sweep(measured: Dict[str, float]) -> None:
    import dataclasses as dc

    from benchmarks.pp_sim import paper_costs, simulate

    t_cpu = PAPER_SAMPLE_S
    for bs_scale, tag in ((0.5, "b256"), (1.0, "b512"), (2.0, "b1024")):
        for sip in (False, True):
            c = paper_costs("qwen-2.5-72b", 4, measured_cpu_sample_s=t_cpu,
                            sipipe=sip)
            c = dc.replace(c, t_fwd=c.t_fwd * (0.6 + 0.4 * bs_scale),
                           t_sample_stage=c.t_sample_stage * bs_scale,
                           t_sample_async=c.t_sample_async * bs_scale)
            r = simulate(c, sipipe=sip)
            emit(f"batch_sweep/qwen72b_{tag}_{'sipipe' if sip else 'baseline'}",
                 1e6 / r.tokens_per_s, f"iters_per_s={r.tokens_per_s:.1f}")


def bench_scalability(measured: Dict[str, float]) -> None:
    from benchmarks.pp_sim import paper_costs, simulate

    t_cpu = PAPER_SAMPLE_S
    tput = {}
    for p in (2, 4, 8):
        for sip in (False, True):
            r = simulate(paper_costs("llama-3.1-70b", p,
                                     measured_cpu_sample_s=t_cpu, sipipe=sip),
                         sipipe=sip)
            key = "sipipe" if sip else "baseline"
            tput[(key, p)] = r.tokens_per_s
            scale = r.tokens_per_s / tput.get((key, p // 2), r.tokens_per_s)
            emit(f"scalability/llama70b_p{p}_{key}", 1e6 / r.tokens_per_s,
                 f"iters_per_s={r.tokens_per_s:.1f} scale_vs_half={scale:.2f}x")


# ---------------------------------------------------------------------------
# Fig. 12 / 13 — TPOT distribution (per-iteration latency percentiles)
# ---------------------------------------------------------------------------

def bench_tpot_cdf(measured: Dict[str, float]) -> None:
    from benchmarks.pp_sim import paper_costs, simulate

    t_cpu = PAPER_SAMPLE_S
    for model, p in (("qwen-2.5-72b", 4), ("deepseek-v3", 4)):
        for sip in (False, True):
            r = simulate(paper_costs(model, p, measured_cpu_sample_s=t_cpu,
                                     sipipe=sip), sipipe=sip, n_iters=128)
            ts = np.array(r.iteration_times)
            pct = {q: float(np.percentile(ts, q)) for q in (50, 90, 99)}
            emit(f"tpot/{model}_p{p}_{'sipipe' if sip else 'baseline'}",
                 r.tpot_mean * 1e6,
                 f"p50={pct[50]*1e3:.1f}ms p90={pct[90]*1e3:.1f}ms "
                 f"p99={pct[99]*1e3:.1f}ms")


# ---------------------------------------------------------------------------
# Fig. 16 — per-component ablation
# ---------------------------------------------------------------------------

def bench_ablation(measured: Dict[str, float]) -> None:
    """Reproduces Fig. 16's component ordering under the paper's sampler
    calibration (bench_throughput reports the measured calibration)."""
    from benchmarks.pp_sim import ablation_variants, simulate_variant

    for model in ("qwen-2.5-72b", "mixtral-8x7b", "deepseek-v3"):
        variants = ablation_variants(model, 4, PAPER_SAMPLE_S)
        base_tput = None
        for name, (costs, mode) in variants.items():
            r = simulate_variant(costs, mode)
            if base_tput is None:
                base_tput = r.tokens_per_s
            emit(f"ablation/{model}_{name}", 1e6 / r.tokens_per_s,
                 f"gain_vs_baseline={r.tokens_per_s / base_tput:.2f}x")


# ---------------------------------------------------------------------------
# Chunked prefill vs monolithic prefill on a mixed long-prompt workload
# ---------------------------------------------------------------------------

def _time_chunk_step(stage, spans, bucket, s_max=160):
    """Wall time of one real packed chunk step carrying ``spans``, with
    the packed vectors padded (last-valid duplicates) to ``bucket``."""
    import jax
    import jax.numpy as jnp

    b = len(spans)
    cache = stage.init_cache(b, s_max)
    pt, pp_, ps, last = [], [], [], []
    for i, (off, n) in enumerate(spans):
        pt.extend([3] * n)
        pp_.extend(range(off, off + n))
        ps.extend([i] * n)
        last.append(len(pt) - 1)
    t = len(pt)
    while len(pt) < bucket:
        pt.append(pt[-1])
        pp_.append(pp_[-1])
        ps.append(ps[-1])
    args = (stage.params, cache, jnp.asarray(pt, jnp.int32),
            jnp.asarray(pp_, jnp.int32), jnp.asarray(ps, jnp.int32),
            jnp.asarray([off for off, _ in spans], jnp.int32),
            jnp.asarray(last, jnp.int32), jnp.asarray(t, jnp.int32))

    def call():
        out, _ = stage.chunk_fn(*args)
        jax.block_until_ready(out)

    return _time(call, reps=3, warmup=2)


def bench_chunked_prefill() -> None:
    """Packed-vs-padded model time on a skewed mixed batch, plus the
    mixed-workload simulation with t_token/t_fixed CALIBRATED from the
    measured chunk-step latencies of the real engine stage (rather than
    the previous hard-coded guesses), all recorded in BENCH_chunked.json.

    Since PR 3 this is a THREE-way scheduling-policy comparison
    (monolithic / chunked / disaggregated, docs/scheduling.md
    §Scheduling policies), plus a prefill-heavy long-prompt trace where
    TD-Pipe-style temporal disaggregation beats chunked piggybacking:
    its prefill phases carry no sampling, so phase chunks stream through
    the pipeline without the per-slot sampler round-trip."""
    import json

    import jax

    from benchmarks.pp_sim import simulate_mixed_workload
    from repro.configs import get_config
    from repro.core.engine import split_for_pp
    from repro.models import ShardCtx, build_model

    cfg = get_config("stablelm-1.6b-smoke")
    model = build_model(cfg, ShardCtx.single())
    params = model.init(jax.random.key(0))
    stage = split_for_pp(model, params, 1)[0]

    # -- calibration: stage latency is ~ t_fixed + t_token * tokens --------
    t_small = _time_chunk_step(stage, [(0, 8)], 8)
    t_large = _time_chunk_step(stage, [(0, 64)], 64)
    t_token = max((t_large - t_small) / (64 - 8), 1e-7)
    t_fixed = max(t_small - 8 * t_token, 1e-6)
    emit("chunked_prefill/calibration", t_large * 1e6,
         f"t_token_us={t_token * 1e6:.2f} t_fixed_us={t_fixed * 1e6:.2f}")

    # -- packed vs padded: 1 long chunk piggybacked on 7 decodes ----------
    budget = 32
    skewed = [(0, budget - 7)] + [(100, 1)] * 7      # T = 32 valid tokens
    t_packed = _time_chunk_step(stage, skewed, budget)
    # the padded [B, C] execution the packed layout replaced is exactly a
    # packed batch clamp-padded to B x C duplicate tokens
    t_padded = _time_chunk_step(stage, skewed, len(skewed) * budget)
    reduction = 1.0 - t_packed / t_padded
    emit("chunked_prefill/packed_model_time", t_packed * 1e6,
         f"tokens={budget}")
    emit("chunked_prefill/padded_model_time", t_padded * 1e6,
         f"tokens={len(skewed) * budget} reduction={reduction:.2%}")

    POLICIES = ("monolithic", "chunked", "disaggregated")
    prompts = [200, 8, 150, 6, 180, 10, 90, 120, 5, 160, 7, 140]
    # per-stage heterogeneity (Obs. 3): the same deterministic alternating
    # jitter paper_costs feeds PipeCosts — stages no longer charge
    # identical durations, so the slowest stage paces every policy
    JITTER = 0.05
    sim = {"fwd_jitter": JITTER}
    for p in (2, 4):
        results = {}
        for policy in POLICIES:
            r = simulate_mixed_workload(
                p=p, max_batch=4, token_budget=budget, prompt_lens=prompts,
                max_new_tokens=24, policy=policy,
                t_token=t_token, t_fixed=t_fixed, fwd_jitter=JITTER)
            results[policy] = r
            emit(f"chunked_prefill/p{p}_{policy}", r.wall_s * 1e6,
                 f"occupancy={r.occupancy:.3f} bubble_ticks={r.bubble_ticks} "
                 f"bubble_frac={max(r.bubble_fracs):.3f} "
                 f"prefill_block_ms={r.prefill_block_s * 1e3:.1f}")
        gain = results["monolithic"].wall_s / results["chunked"].wall_s
        emit(f"chunked_prefill/p{p}_speedup", 0.0,
             f"wall_gain={gain:.2f}x occupancy "
             f"{results['monolithic'].occupancy:.3f}->"
             f"{results['chunked'].occupancy:.3f}")
        sim[f"p{p}"] = {
            "wall_gain": gain,
            "wall_s": {k: results[k].wall_s for k in POLICIES},
            "occupancy_monolithic": results["monolithic"].occupancy,
            "occupancy_chunked": results["chunked"].occupancy,
            "occupancy_disaggregated": results["disaggregated"].occupancy,
            "bubble_ticks_monolithic": results["monolithic"].bubble_ticks,
            "bubble_ticks_chunked": results["chunked"].bubble_ticks,
            "bubble_ticks_disaggregated": results["disaggregated"].bubble_ticks,
        }

    # -- prefill-heavy long-prompt trace: the TD-Pipe regime --------------
    # chunked piggybacks decodes into every iteration, so every iteration
    # pays the per-slot pipeline+sampler round-trip before the slot's next
    # batch can be built; disaggregated prefill phases sample nothing and
    # stream their chunks back-to-back (engine run-loop per-slot gate)
    heavy = [2400, 40, 2000, 30, 2200, 50, 1800, 60]
    heavy_budget, heavy_new = 512, 16
    hres = {}
    for policy in POLICIES:
        r = simulate_mixed_workload(
            p=2, max_batch=4, token_budget=heavy_budget, prompt_lens=heavy,
            max_new_tokens=heavy_new, policy=policy,
            t_token=t_token, t_fixed=t_fixed, fwd_jitter=JITTER)
        hres[policy] = r
        emit(f"chunked_prefill/prefill_heavy_{policy}", r.wall_s * 1e6,
             f"occupancy={r.occupancy:.3f} iterations={r.iterations}")
    d_vs_c = hres["chunked"].wall_s / hres["disaggregated"].wall_s
    d_vs_m = hres["monolithic"].wall_s / hres["disaggregated"].wall_s
    emit("chunked_prefill/prefill_heavy_disagg_gain", 0.0,
         f"wall_gain_vs_chunked={d_vs_c:.2f}x vs_monolithic={d_vs_m:.2f}x")

    # -- overlapped CPU sampling on the calibrated trace: t_sample is
    # the MEASURED smoke-scale ColumnWiseSampler latency; the overlap
    # frees the last stage at forward-end (engine SamplingWorker), so
    # the sampling bubble closes for every slot but the sampled one
    from repro.core.sampler import ColumnWiseSampler
    from repro.core.sampling_params import SamplingParams

    smp = ColumnWiseSampler(cfg.vocab_size, 4, max_len=512)
    z = np.random.default_rng(0).normal(
        size=(4, cfg.vocab_size)).astype(np.float32)
    t_sample = _time(lambda: smp.sample(
        z, SamplingParams(temperature=0.8, top_k=40)), reps=3)
    ores = {}
    for ov in (True, False):
        ores[ov] = simulate_mixed_workload(
            p=2, max_batch=4, token_budget=budget, prompt_lens=prompts,
            max_new_tokens=24, policy="chunked", t_token=t_token,
            t_fixed=t_fixed, t_sample=t_sample, overlap_sampling=ov,
            fwd_jitter=JITTER)
    ov_gain = ores[False].wall_s / ores[True].wall_s
    emit("chunked_prefill/sampling_overlap", ores[True].wall_s * 1e6,
         f"t_sample_us={t_sample * 1e6:.1f} sync_wall_us="
         f"{ores[False].wall_s * 1e6:.0f} closed_bubble_gain={ov_gain:.3f}x")

    with open("BENCH_chunked.json", "w") as f:
        json.dump({
            "calibration": {"t_token_s": t_token, "t_fixed_s": t_fixed,
                            "source": "measured stablelm-smoke stage "
                                      "chunk_fn latency at widths 8/64"},
            "packed_vs_padded": {
                "skewed_batch": "1 long chunk (25 tok) + 7 decodes",
                "packed_tokens": budget,
                "padded_tokens": len(skewed) * budget,
                "t_packed_us": t_packed * 1e6,
                "t_padded_us": t_padded * 1e6,
                "model_time_reduction": reduction,
            },
            "simulation": sim,
            "sampling_overlap": {
                "t_sample_s": t_sample,
                "wall_s_overlap": ores[True].wall_s,
                "wall_s_sync": ores[False].wall_s,
                "closed_bubble_gain": ov_gain,
                "bubble_fracs_overlap": ores[True].bubble_fracs,
                "bubble_fracs_sync": ores[False].bubble_fracs,
            },
            "prefill_heavy": {
                "trace": heavy,
                "token_budget": heavy_budget,
                "max_new_tokens": heavy_new,
                "p": 2,
                "wall_s": {k: hres[k].wall_s for k in POLICIES},
                "wall_gain_disaggregated_vs_chunked": d_vs_c,
                "wall_gain_disaggregated_vs_monolithic": d_vs_m,
            },
        }, f, indent=2)
    emit("chunked_prefill/bench_json", 0.0, "wrote BENCH_chunked.json")


# ---------------------------------------------------------------------------
# Online continuous serving (step-driven request API, Poisson arrivals)
# ---------------------------------------------------------------------------

def bench_serving() -> None:
    """Online Poisson-arrival serving on the REAL engine through the
    step-driven request API (serve.py run_online, docs/serving.md):
    throughput + p50/p99 TTFT and TPOT per scheduling policy, recorded
    in BENCH_serving.json.  CPU-scale absolute numbers; the point is the
    per-policy latency SHAPE — chunked keeps TPOT flat, disaggregated
    trades TPOT tails for prefill streaming, adaptive walks its chunk
    budget to the live TPOT."""
    import json

    import jax

    from repro.configs import get_config
    from repro.launch.serve import run_online
    from repro.models import ShardCtx, build_model

    cfg = get_config("stablelm-1.6b-smoke")
    model = build_model(cfg, ShardCtx.single())
    prebuilt = (cfg, model, model.init(jax.random.key(0)))
    results = {}
    for policy in ("chunked", "disaggregated", "adaptive"):
        m = run_online("stablelm-1.6b-smoke", policy=policy, pp=2, requests=10,
                       max_batch=2, max_new_tokens=8, chunk_tokens=16,
                       arrival_rate=8.0, seed=0, verbose=False,
                       prebuilt=prebuilt)
        keep = {
            "throughput_tok_s": m["throughput_tok_s"],
            "ttft_p50_s": m["ttft_p50_s"],
            "ttft_p99_s": m["ttft_p99_s"],
            "tpot_p50_s": m["tpot_p50_s"],
            "tpot_p99_s": m["tpot_p99_s"],
            "queue_mean_s": m["queue_mean_s"],
            "requests_finished": m["requests_finished"],
            "wall_s": m["wall_s"],
        }
        for k in [k for k in m if k.startswith("policy_")]:
            keep[k] = m[k]
        results[policy] = keep
        emit(f"serving/{policy}_ttft_p50", m["ttft_p50_s"] * 1e6,
             f"tok_per_s={m['throughput_tok_s']:.2f} "
             f"ttft_p99_ms={m['ttft_p99_s'] * 1e3:.0f} "
             f"tpot_p99_ms={m['tpot_p99_s'] * 1e3:.0f}")

    # -- overlapped CPU sampling on/off (docs/serving.md §Overlapped
    # sampling): same trace, sampling either on the host worker (the
    # logits hand-off frees the last stage at forward-end) or dispatched
    # synchronously inside emit_logits.  Token streams are identical;
    # the delta is the per-iteration sampling bubble the worker closes.
    ov = {}
    for overlap in (True, False):
        m = run_online("stablelm-1.6b-smoke", policy="chunked", pp=2, requests=10,
                       max_batch=2, max_new_tokens=8, chunk_tokens=16,
                       arrival_rate=8.0, seed=0, verbose=False,
                       overlap_sampling=overlap, prebuilt=prebuilt)
        ov["overlap_on" if overlap else "overlap_off"] = {
            "wall_s": m["wall_s"],
            "throughput_tok_s": m["throughput_tok_s"],
            "tpot_p50_s": m["tpot_p50_s"],
            "tpot_p99_s": m["tpot_p99_s"],
        }
    gain = (ov["overlap_off"]["wall_s"] / ov["overlap_on"]["wall_s"]
            if ov["overlap_on"]["wall_s"] else 0.0)
    ov["wall_gain"] = gain
    emit("serving/overlap_sampling", ov["overlap_on"]["wall_s"] * 1e6,
         f"wall_gain_vs_sync={gain:.3f}x "
         f"tok_per_s={ov['overlap_on']['throughput_tok_s']:.2f}")

    with open("BENCH_serving.json", "w") as f:
        json.dump({
            "workload": {"arch": "stablelm-1.6b-smoke", "requests": 10,
                         "arrival_rate_rps": 8.0, "max_new_tokens": 8,
                         "token_budget": 16, "pp": 2, "max_batch": 2},
            "policies": results,
            "overlap_sampling": ov,
        }, f, indent=2)
    emit("serving/bench_json", 0.0, "wrote BENCH_serving.json")


# ---------------------------------------------------------------------------
# Paged vs contiguous KV at equal cache budget (memory-pressure scenario)
# ---------------------------------------------------------------------------

def bench_paged() -> None:
    """Paged-vs-contiguous on the REAL engine, recorded in
    BENCH_paged.json.  Two stories:

    CAPACITY (equal cache budget): contiguous rows reserve a worst-case
    ``max_seq_len`` row per sequence, hard-capping concurrency at the
    row count; the paged layout holds sequences at their ACTUAL lengths
    in blocks, admits by block budget, and preempts (recompute) under
    decode growth — strictly more concurrency on a mixed-length trace,
    greedy outputs bit-identical.

    SPEED (equal composition): same max_batch, ample blocks — isolates
    what the paged-native hot path (in-kernel block gather + dirty-block
    write-back + bucket-capped table widths) costs per token against
    contiguous rows.  Reported as STEADY-STATE tok/s over the steps that
    paid no XLA compile (per-step ``engine.compile_stats()`` window), so
    the paged run's extra (batch, nb)-shape warmup compiles don't
    pollute the per-token comparison.  The kv_layout='auto' default rides on this ratio
    staying near 1x."""
    import json

    import jax

    from repro.configs import get_config
    from repro.core.engine import EngineConfig, SiPipeEngine
    from repro.core.sampling_params import SamplingParams
    from repro.core.sequence import SeqStatus
    from repro.models import ShardCtx, build_model

    ARCH, PP, MSL, BS = "stablelm-1.6b-smoke", 2, 64, 8
    ROWS = 2                     # contiguous: max_batch(1) x pp(2) rows
    SLOT_BUDGET = ROWS * MSL     # 128 KV slots for BOTH layouts
    N_NEW = 20                   # decode growth deep enough to hit the pool
    cfg = get_config(ARCH)
    model = build_model(cfg, ShardCtx.single())
    # key/seed 1: a trace with no greedy near-ties, so the pressured and
    # unpressured runs compare bit-exactly despite their different batch
    # compositions (composition shifts bf16 matmul rounding; see the
    # matched-composition parity note below)
    params = model.init(jax.random.key(1))
    rng = np.random.default_rng(1)
    # mixed-length trace: a few long prompts among many short ones, with
    # enough decode growth to hit the block budget (preemption exercised)
    lens = [30, 6, 24, 4, 20, 8, 5, 26]
    prompts = [list(map(int, rng.integers(2, cfg.vocab_size, size=n)))
               for n in lens]

    def drive(layout, max_batch, kv_blocks=None):
        eng = SiPipeEngine(model, params, EngineConfig(
            pp_degree=PP, max_batch=max_batch, max_seq_len=MSL,
            n_samplers=2, prefill_chunk_tokens=16, scheduling_policy="chunked",
            kv_layout=layout, kv_block_size=BS, kv_blocks=kv_blocks))
        handles = {}
        for p in prompts:
            rid = eng.add_request(p, SamplingParams(greedy=True,
                                                    max_new_tokens=N_NEW))
            handles[rid] = eng.requests[rid].seq
        outs, max_conc, steps = {}, 0, []
        t0 = time.perf_counter()
        while eng.has_work:
            s0 = time.perf_counter()
            toks = 0
            for out in eng.step():
                toks += len(out.new_token_ids)
                if out.finished:
                    outs[out.request_id] = out.token_ids.to_list()
            steps.append((time.perf_counter() - s0, toks,
                          eng.compile_stats()["jit_executables"]))
            max_conc = max(max_conc, sum(
                1 for q in eng.scheduler.seqs.values()
                if q.status == SeqStatus.RUNNING))
        wall = time.perf_counter() - t0
        eng.shutdown()
        m = eng.metrics()
        # steady-state window: every step that paid NO compile (the
        # per-step jit-executable count is flat across it) — drain-end
        # batch-shrink compiles are excluded too, not just warmup
        final_c = steps[-1][2] if steps else 0
        tail = [s for i, s in enumerate(steps)
                if i and s[2] == steps[i - 1][2]]
        st_wall = sum(d for d, _, _ in tail)
        st_toks = sum(t for _, t, _ in tail)
        return {
            "outs": outs, "max_conc": max_conc, "wall": wall, "m": m,
            "victims": [rid for rid, q in handles.items() if q.preemptions],
            "compiles": final_c, "steady_steps": len(tail),
            "steady_tok_s": st_toks / st_wall if st_wall else 0.0,
        }

    # -- capacity story: equal budget — contiguous spends it as ROWS
    # worst-case rows; paged as SLOT_BUDGET // BS blocks.  The
    # unpressured reference (same max_batch, abundant blocks) isolates
    # what the pressure dynamics — block-deferred admission + preemption
    # — do to tokens: nothing.  (Greedy outputs across DIFFERENT
    # concurrency are not comparable even between two contiguous runs:
    # chunk composition shifts bf16 rounding enough to flip near-tie
    # argmaxes, so the cross-layout parity contract is
    # matched-composition — the policy x config matrix in
    # tests/test_paged_engine.py.)
    cap_c = drive("contiguous", max_batch=1)
    cap_p = drive("paged", max_batch=2, kv_blocks=SLOT_BUDGET // BS)
    ref_p = drive("paged", max_batch=2, kv_blocks=4 * SLOT_BUDGET // BS)
    assert ref_p["m"]["kv_preemptions"] == 0   # reference is unpressured
    match = cap_p["outs"] == ref_p["outs"]
    victims = cap_p["victims"]
    victims_match = all(cap_p["outs"][r] == ref_p["outs"][r]
                        for r in victims)
    ratio = cap_p["max_conc"] / max(cap_c["max_conc"], 1)
    emit("paged/contiguous_max_concurrent", cap_c["wall"] * 1e6,
         f"max_concurrent={cap_c['max_conc']} rows={ROWS}")
    emit("paged/paged_max_concurrent", cap_p["wall"] * 1e6,
         f"max_concurrent={cap_p['max_conc']} ratio={ratio:.2f}x "
         f"preemptions={cap_p['m']['kv_preemptions']} "
         f"outputs_match={match}")

    # -- speed story: equal composition (contiguous max_batch=2 vs the
    # ample-block paged run) — matched composition also means the token
    # streams must be bit-identical across layouts
    spd_c = drive("contiguous", max_batch=2)
    layouts_match = spd_c["outs"] == ref_p["outs"]
    steady_ratio = (spd_c["steady_tok_s"] / ref_p["steady_tok_s"]
                    if ref_p["steady_tok_s"] else float("inf"))
    emit("paged/steady_state_contiguous", 1e6 / max(
        spd_c["steady_tok_s"], 1e-9),
         f"tok_per_s={spd_c['steady_tok_s']:.2f} "
         f"compiles={spd_c['compiles']}")
    emit("paged/steady_state_paged", 1e6 / max(ref_p["steady_tok_s"], 1e-9),
         f"tok_per_s={ref_p['steady_tok_s']:.2f} "
         f"compiles={ref_p['compiles']} "
         f"wall_ratio_vs_contiguous={steady_ratio:.2f}x "
         f"table_widths={ref_p['m'].get('kv_table_widths')}")

    with open("BENCH_paged.json", "w") as f:
        json.dump({
            "workload": {"arch": ARCH, "pp": PP, "max_seq_len": MSL,
                         "block_size": BS, "kv_slot_budget": SLOT_BUDGET,
                         "prompt_lens": lens, "max_new_tokens": N_NEW,
                         "policy": "chunked"},
            "contiguous": {"max_concurrent": cap_c["max_conc"],
                           "wall_s": cap_c["wall"],
                           "throughput_tok_s": cap_c["m"]["throughput_tok_s"],
                           "jit_executables": cap_c["compiles"],
                           "rows": ROWS},
            "paged": {"max_concurrent": cap_p["max_conc"],
                      "wall_s": cap_p["wall"],
                      "throughput_tok_s": cap_p["m"]["throughput_tok_s"],
                      "jit_executables": cap_p["compiles"],
                      "blocks": SLOT_BUDGET // BS,
                      "preemptions": cap_p["m"]["kv_preemptions"],
                      "table_widths": cap_p["m"].get("kv_table_widths")},
            "concurrency_ratio": ratio,
            "wall_gain": cap_c["wall"] / cap_p["wall"],
            "outputs_match_unpressured": match,
            "preempted_requests": victims,
            "preempted_outputs_match": victims_match,
            "steady_state": {
                "definition": "tok/s over the steps that paid no XLA "
                              "compile (per-step compile_stats window)",
                "contiguous_b2": {
                    "tok_s": spd_c["steady_tok_s"],
                    "steps": spd_c["steady_steps"],
                    "jit_executables": spd_c["compiles"]},
                "paged_b2_ample": {
                    "tok_s": ref_p["steady_tok_s"],
                    "steps": ref_p["steady_steps"],
                    "jit_executables": ref_p["compiles"],
                    "table_widths": ref_p["m"].get("kv_table_widths")},
                "paged_over_contiguous_wall_ratio": steady_ratio,
                "outputs_bit_identical": layouts_match,
            },
            "note": "capacity target: concurrency ratio at equal cache "
                    "budget.  speed target: steady-state wall ratio near "
                    "1x at equal composition — the basis for the "
                    "kv_layout='auto' paged default; warmup compiles are "
                    "excluded via the per-step compile count window",
        }, f, indent=2)
    assert match, "memory pressure perturbed greedy outputs"
    # the per-victim check is the corruption canary: a preempted sequence
    # resumes by recomputing its full history, so its stream must be
    # bit-exact regardless of composition effects elsewhere
    assert victims_match, "a preempted sequence's resumed output diverged"
    assert cap_p["m"]["kv_preemptions"] > 0, "pressure never preempted"
    assert ratio >= 1.5, f"concurrency ratio {ratio:.2f} < 1.5"
    assert layouts_match, "equal-composition layouts diverged"
    emit("paged/bench_json", 0.0, "wrote BENCH_paged.json")


# ---------------------------------------------------------------------------
# Prefix caching + CoW forks (shared-prefix traffic on the real engine)
# ---------------------------------------------------------------------------

def bench_prefix() -> None:
    """Shared-prefix KV reuse priced on the real engine, recorded in
    BENCH_prefix.json.  Two stories:

    TTFT COLLAPSE: a warm request whose prompt shares its leading full
    blocks with a cached prefix prefills only the unshared tail — its
    TTFT drops to roughly tail/prompt of the cold TTFT.  Measured
    cold-vs-warm on the SAME engine after a shape-warmup run, so XLA
    compiles pollute neither number.

    SUBLINEAR BLOCKS: K concurrent requests over one shared prefix hold
    the prefix blocks ONCE (refcounted) plus per-request unique tails,
    not K full copies.  Peak live blocks are tracked per step
    (pin-only cached blocks excluded: they are reclaimable capacity,
    not working set) against the naive K * blocks_for(len) footprint.
    A parallel-sampling (n=K) request is priced the same way: one
    prompt, CoW-forked decode tails."""
    import json

    import jax

    from repro.configs import get_config
    from repro.core.engine import EngineConfig, SiPipeEngine
    from repro.core.sampling_params import SamplingParams
    from repro.models import ShardCtx, build_model

    ARCH, PP, MSL, BS, CHUNK, N_NEW = "stablelm-1.6b-smoke", 2, 64, 8, 8, 6
    BASE, TAIL, K = 48, 4, 4          # 6 shared full blocks + unique tails
    cfg = get_config(ARCH)
    model = build_model(cfg, ShardCtx.single())
    params = model.init(jax.random.key(1))
    rng = np.random.default_rng(2)

    def mk(n):
        return list(map(int, rng.integers(2, cfg.vocab_size, size=n)))

    base_a, base_b = mk(BASE), mk(BASE)
    eng = SiPipeEngine(model, params, EngineConfig(
        pp_degree=PP, max_batch=K, max_seq_len=MSL, n_samplers=2,
        prefill_chunk_tokens=CHUNK, scheduling_policy="chunked",
        kv_layout="paged", kv_block_size=BS))
    kvm = eng.kv_manager

    def drive(reqs):
        """Run to drain; returns (rids, peak live blocks)."""
        rids = [eng.add_request(p, sp) for p, sp in reqs]
        peak = 0
        while eng.has_work:
            eng.step()
            live = (kvm.n_blocks - kvm.alloc.free_blocks
                    - kvm.reclaimable_cached_blocks)
            peak = max(peak, live)
        return rids, peak

    def ttft(rid):
        return eng.metrics()["requests"][rid]["ttft_s"]

    sp = SamplingParams(greedy=True, max_new_tokens=N_NEW)
    drive([(base_a + mk(TAIL), sp)])          # shape warmup + seeds base_a
    [cold], _ = drive([(base_b + mk(TAIL), sp)])   # fresh prefix: cold
    warm_rids = []
    for _ in range(3):                        # warm: base_b is now cached
        [r], _ = drive([(base_b + mk(TAIL), sp)])
        warm_rids.append(r)
    cold_ttft = ttft(cold)
    warm_ttft = float(np.mean([ttft(r) for r in warm_rids]))
    emit("prefix/cold_ttft", cold_ttft * 1e6, f"prompt={BASE + TAIL}")
    emit("prefix/warm_ttft", warm_ttft * 1e6,
         f"ratio={warm_ttft / cold_ttft:.3f} cached_tokens={BASE}")

    # -- sublinear blocks: K concurrent shared-prefix requests
    naive = K * kvm.blocks_for(BASE + TAIL + N_NEW)
    reqs, shared_peak = drive([(base_b + mk(TAIL), sp) for _ in range(K)])
    emit("prefix/shared_blocks_peak", 0.0,
         f"peak={shared_peak} naive={naive} "
         f"ratio={shared_peak / naive:.2f}")
    # -- same shape via parallel sampling: one prompt, n=K fork tails
    [fr], fork_peak = drive([(base_a + mk(TAIL),
                              SamplingParams(greedy=True,
                                             max_new_tokens=N_NEW, n=K))])
    emit("prefix/fork_blocks_peak", 0.0,
         f"peak={fork_peak} naive={naive} ratio={fork_peak / naive:.2f}")

    m = eng.metrics()
    eng.shutdown()
    with open("BENCH_prefix.json", "w") as f:
        json.dump({
            "workload": {"arch": ARCH, "pp": PP, "max_seq_len": MSL,
                         "block_size": BS, "chunk_tokens": CHUNK,
                         "base_tokens": BASE, "tail_tokens": TAIL,
                         "max_new_tokens": N_NEW, "k": K,
                         "policy": "chunked"},
            "ttft": {"cold_s": cold_ttft, "warm_s": warm_ttft,
                     "warm_over_cold": warm_ttft / cold_ttft},
            "blocks": {"naive_k_times_full": naive,
                       "shared_prefix_peak": shared_peak,
                       "fork_n_peak": fork_peak,
                       "shared_over_naive": shared_peak / naive,
                       "fork_over_naive": fork_peak / naive},
            "counters": {k: v for k, v in m.items()
                         if k.startswith(("kv_prefix", "kv_cow",
                                          "kv_fork", "kv_blocks"))},
            "note": "warm TTFT gate < 0.5x cold: a cache-hit request "
                    "prefills only its unshared tail.  blocks gates "
                    "< 0.7x naive: K streams over one prefix hold the "
                    "shared blocks once (refcounted), unique tails per "
                    "stream — sublinear in K.",
        }, f, indent=2)
    assert m["kv_prefix_hits"] >= K + 3, "warm admissions missed the cache"
    assert warm_ttft < 0.5 * cold_ttft, \
        f"warm TTFT {warm_ttft:.4f}s not < 0.5x cold {cold_ttft:.4f}s"
    assert shared_peak < 0.7 * naive, \
        f"shared-prefix peak {shared_peak} not sublinear vs naive {naive}"
    assert fork_peak < 0.7 * naive, \
        f"fork peak {fork_peak} not sublinear vs naive {naive}"
    emit("prefix/bench_json", 0.0, "wrote BENCH_prefix.json")


# ---------------------------------------------------------------------------
# HTTP front-end: open-loop Poisson client over the fleet (docs/http.md)
# ---------------------------------------------------------------------------

def bench_http() -> None:
    """Open-loop Poisson clients against the REAL HTTP stack (server +
    admission + router + 2 engine replicas), recorded in BENCH_http.json.
    Three stories: CLIENT-side TTFT/TPOT percentiles measured over the
    wire (transport overhead included), router balance (routed counts +
    per-replica peak block occupancy stay bounded), and the 429 burst —
    a full admission queue rejects instantly with Retry-After while the
    held streams finish undisturbed."""
    import http.client
    import json
    import threading
    import time as _t

    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.launch.serve import build_http_server
    from repro.models import ShardCtx, build_model

    cfg = get_config("stablelm-1.6b-smoke")
    model = build_model(cfg, ShardCtx.single())
    prebuilt = (cfg, model, model.init(jax.random.key(0)))
    rng = np.random.default_rng(0)
    N_REQ, RATE, N_NEW = 10, 4.0, 6

    def post_stream(addr, prompt, max_tokens, record=None):
        """One streamed completion; returns (status, token_count)."""
        conn = http.client.HTTPConnection(*addr, timeout=300)
        t0 = _t.monotonic()
        conn.request("POST", "/v1/completions", json.dumps(
            {"prompt": prompt, "max_tokens": max_tokens,
             "temperature": 0.0, "stream": True}),
            {"Content-Type": "application/json"})
        resp = conn.getresponse()
        if resp.status != 200:
            resp.read()
            conn.close()
            return resp.status, 0
        stamps = []
        while True:
            line = resp.readline()
            if not line:
                break
            if not line.startswith(b"data: ") or line == b"\n":
                continue
            if line.startswith(b"data: [DONE]"):
                break
            ev = json.loads(line[len(b"data: "):])
            if any(c["token_ids"] for c in ev["choices"]):
                stamps.append(_t.monotonic())
        conn.close()
        if record is not None and stamps:
            record["ttft"].append(stamps[0] - t0)
            if len(stamps) > 1:
                record["tpot"].extend(np.diff(stamps).tolist())
        return 200, len(stamps)

    # -- phase 1: Poisson open loop over 2 replicas -------------------------
    _, server = build_http_server(
        "stablelm-1.6b-smoke", replicas=2, pp=2, max_batch=2,
        max_seq_len=64, kv_layout="paged", block_size=8,
        max_queue=64, prebuilt=prebuilt)
    server.start()
    addr = server.address
    record = {"ttft": [], "tpot": []}
    rec_lock = threading.Lock()

    def client(delay, prompt):
        _t.sleep(delay)
        r = {"ttft": [], "tpot": []}
        status, n_tok = post_stream(addr, prompt, N_NEW, r)
        with rec_lock:
            record["ttft"] += r["ttft"]
            record["tpot"] += r["tpot"]
        assert status == 200 and n_tok == N_NEW, (status, n_tok)

    # warm both replicas first (jit compile) so the measured phase sees
    # steady-state service times; two concurrent requests spread by load
    warm = [threading.Thread(target=post_stream,
                             args=(addr, [5, 9, 13], 2)) for _ in range(2)]
    for t in warm:
        t.start()
    for t in warm:
        t.join()

    arrivals = np.cumsum(rng.exponential(1.0 / RATE, size=N_REQ))
    prompts = [list(map(int, rng.integers(2, cfg.vocab_size, size=n)))
               for n in rng.integers(4, 12, size=N_REQ)]
    t0 = _t.monotonic()
    threads = [threading.Thread(target=client, args=(a, p))
               for a, p in zip(arrivals, prompts)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = _t.monotonic() - t0
    routed = dict(server.router.routed)
    peaks = {r.name: r.peak_busy_blocks for r in server.router.replicas}
    adm = server.admission.snapshot()
    server.close()
    balance_routed = max(routed.values()) / max(1, min(routed.values()))
    balance_blocks = (max(peaks.values()) / max(1, min(peaks.values()))
                      if min(peaks.values()) else float("inf"))
    ttft = np.array(record["ttft"])
    tpot = np.array(record["tpot"]) if record["tpot"] else np.zeros(1)

    # -- phase 2: burst past tiny caps -> 429s, held stream undisturbed ----
    _, server = build_http_server(
        "stablelm-1.6b-smoke", replicas=1, pp=2, max_batch=2,
        max_seq_len=64, kv_layout="paged", block_size=8,
        max_queue=1, max_active=1, prebuilt=prebuilt)
    server.start()
    addr = server.address
    post_stream(addr, [5, 9, 13], 2)                  # warm the replica
    statuses = []
    st_lock = threading.Lock()

    def burst_client(prompt):
        status, n_tok = post_stream(addr, prompt, N_NEW)
        with st_lock:
            statuses.append((status, n_tok))

    burst = [threading.Thread(target=burst_client, args=(p,))
             for p in prompts[:6]]
    for t in burst:
        t.start()
    for t in burst:
        t.join()
    n_ok = sum(1 for s, _ in statuses if s == 200)
    n_429 = sum(1 for s, _ in statuses if s == 429)
    ok_complete = all(n == N_NEW for s, n in statuses if s == 200)
    server.close()

    with open("BENCH_http.json", "w") as f:
        json.dump({
            "workload": {"arch": "stablelm-1.6b-smoke", "replicas": 2,
                         "requests": N_REQ, "arrival_rate_rps": RATE,
                         "max_new_tokens": N_NEW, "pp": 2, "max_batch": 2},
            "client_latency": {
                "ttft_p50_s": float(np.percentile(ttft, 50)),
                "ttft_p99_s": float(np.percentile(ttft, 99)),
                "tpot_p50_s": float(np.percentile(tpot, 50)),
                "tpot_p99_s": float(np.percentile(tpot, 99)),
                "wall_s": wall,
            },
            "router_balance": {
                "routed": routed,
                "peak_busy_blocks": peaks,
                "routed_max_over_min": balance_routed,
                "blocks_max_over_min": balance_blocks,
            },
            "admission": {**adm, "rejected_rate":
                          adm["admission_rejected_total"]
                          / max(1, adm["admission_admitted_total"]
                                + adm["admission_rejected_total"])},
            "burst": {"clients": len(burst), "ok": n_ok, "rejected": n_429,
                      "ok_streams_complete": ok_complete},
            "note": "client-side latencies over a real socket (SSE); "
                    "routed/blocks ratios gate the router's spread; the "
                    "burst phase gates 429-on-full with live streams "
                    "finishing token-complete.",
        }, f, indent=2)
    assert all(v > 0 for v in routed.values()), \
        f"router starved a replica: {routed}"
    assert balance_routed <= 4.0, f"routed imbalance {routed}"
    assert n_429 > 0, "burst past caps produced no 429"
    assert ok_complete, "a 429 burst perturbed an admitted stream"
    emit("http/poisson_ttft_p50", float(np.percentile(ttft, 50)) * 1e6,
         f"ttft_p99_ms={float(np.percentile(ttft, 99)) * 1e3:.0f} "
         f"routed={routed} burst_429={n_429}/{len(burst)}")
    emit("http/bench_json", 0.0, "wrote BENCH_http.json")


# ---------------------------------------------------------------------------
# Hybrid online/offline serving (docs/hybrid.md)
# ---------------------------------------------------------------------------

def bench_hybrid() -> None:
    """Selling pipeline slack to an offline tier, recorded in
    BENCH_hybrid.json.  Two gates:

    SLACK SELLS: on the REAL engine (paged KV), an online Poisson trace
    with an offline backlog enqueued produces offline tokens (> 0 tok/s)
    and every request of both tiers completes — the bubbles carried paid
    work.

    ONLINE UNDISTURBED: in the deterministic virtual-time simulator
    (same real scheduler, pipeline timing model), adding a SATURATING
    offline backlog leaves the online tier's token count bit-identical
    and its virtual-time TPOT p99 within 5% of the online-only run.
    The engine-level bit-exactness of the online sub-trace itself is
    a unit property (tests/test_hybrid.py); this bench prices it.
    """
    import json

    import jax

    from benchmarks.pp_sim import simulate_mixed_workload
    from repro.configs import get_config
    from repro.launch.serve import run_online
    from repro.models import ShardCtx, build_model

    # -- deterministic virtual-time comparison (simulator) ----------------
    ONLINE_LENS = [48, 40, 12, 8, 32, 16, 24, 20]
    OFFLINE_LENS = [24] * 12          # saturating backlog
    sim = {}
    for pol, factor in (("chunked", 1), ("disaggregated", 4)):
        kw = dict(p=2, max_batch=2, token_budget=16,
                  prompt_lens=ONLINE_LENS, max_new_tokens=12,
                  # bubble-dominated regime (the paper's testbed): the
                  # per-iteration fixed cost dwarfs the marginal token
                  t_token=1e-6, t_fixed=5e-4, policy=pol)
        base = simulate_mixed_workload(**kw)
        hyb = simulate_mixed_workload(
            offline_prompt_lens=OFFLINE_LENS, offline_max_new_tokens=16,
            decode_enlarge_factor=factor, **kw)
        degr = (hyb.online_tpot_p99_s / base.online_tpot_p99_s - 1.0
                if base.online_tpot_p99_s else 0.0)
        sim[pol] = {
            "online_tokens_base": base.online_tokens,
            "online_tokens_hybrid": hyb.online_tokens,
            "offline_tokens": hyb.offline_tokens,
            "online_tpot_p99_base_s": base.online_tpot_p99_s,
            "online_tpot_p99_hybrid_s": hyb.online_tpot_p99_s,
            "online_tpot_p99_degradation": degr,
            "decode_enlarge_factor": factor,
        }
        emit(f"hybrid/sim_{pol}_tpot_p99", hyb.online_tpot_p99_s * 1e6,
             f"degradation={degr * 100:.2f}% "
             f"offline_tokens={hyb.offline_tokens}")
        assert hyb.online_tokens == base.online_tokens, \
            (pol, base.online_tokens, hyb.online_tokens)
        assert hyb.offline_tokens > 0, f"{pol}: no slack sold in sim"
        assert degr <= 0.05, \
            f"{pol}: online TPOT p99 degraded {degr * 100:.1f}% > 5%"

    # -- real engine: offline tok/s under online load ---------------------
    cfg = get_config("stablelm-1.6b-smoke")
    model = build_model(cfg, ShardCtx.single())
    prebuilt = (cfg, model, model.init(jax.random.key(0)))
    m = run_online("stablelm-1.6b-smoke", policy="chunked", pp=2, requests=8,
                   max_batch=2, max_new_tokens=8, chunk_tokens=16,
                   kv_layout="paged", arrival_rate=8.0,
                   offline_requests=4, seed=0, verbose=False,
                   prebuilt=prebuilt)
    off_tok_s = m["offline_streamed_tokens"] / m["wall_s"]
    real = {
        "wall_s": m["wall_s"],
        "online_throughput_tok_s": m["throughput_tok_s"],
        "offline_tok_s": off_tok_s,
        "offline_finished": m["offline_finished"],
        "offline_streamed_tokens": m["offline_streamed_tokens"],
        "online_tpot_p99_s": m["tpot_p99_s"],
        "slack_seats_seen": m["slack_seats_seen"],
        "slack_tokens_sold": m["slack_tokens_sold"],
        "offline_preemptions": m["offline_preemptions"],
    }
    emit("hybrid/real_offline_tok_s", 1e6 / max(off_tok_s, 1e-9),
         f"offline_tok_s={off_tok_s:.2f} "
         f"slack_sold={m['slack_tokens_sold']} "
         f"offline_preemptions={m['offline_preemptions']}")

    # -- real engine: enlarged decode batches (disaggregated + ladder) ----
    me = run_online("stablelm-1.6b-smoke", policy="disaggregated", pp=2,
                    requests=4, max_batch=2, max_new_tokens=8,
                    chunk_tokens=16, kv_layout="paged", arrival_rate=8.0,
                    offline_requests=6, decode_enlarge_factor=2,
                    seed=0, verbose=False, prebuilt=prebuilt)
    enlarged = {
        "enlarged_decode_iters": me["policy_enlarged_decode_iters"],
        "decode_enlarge_factor": me["policy_decode_enlarge_factor"],
        "jit_executables": me["jit_executables"],
        "offline_streamed_tokens": me["offline_streamed_tokens"],
        "slack_tokens_sold": me["slack_tokens_sold"],
    }
    emit("hybrid/enlarged_decode", float(me["policy_enlarged_decode_iters"]),
         f"factor={me['policy_decode_enlarge_factor']} "
         f"jit_executables={me['jit_executables']}")

    with open("BENCH_hybrid.json", "w") as f:
        json.dump({
            "workload": {"arch": "stablelm-1.6b-smoke", "pp": 2,
                         "max_batch": 2, "token_budget": 16,
                         "online_requests": 8, "offline_requests": 4,
                         "arrival_rate_rps": 8.0},
            "simulated": sim,
            "real_engine": real,
            "enlarged_decode": enlarged,
            "gates": {
                "offline_tok_s_gt_0": off_tok_s > 0,
                "online_tpot_p99_degradation_max":
                    max(s["online_tpot_p99_degradation"]
                        for s in sim.values()),
                "online_tpot_p99_degradation_limit": 0.05,
            },
            "note": "simulated degradation is the deterministic gate "
                    "(virtual time, same scheduler); the real-engine "
                    "numbers price slack sale + the enlargement ladder "
                    "at CPU scale.",
        }, f, indent=2)
    assert off_tok_s > 0, "real engine sold no offline tokens"
    assert m["offline_finished"] == 4
    assert me["offline_streamed_tokens"] > 0
    emit("hybrid/bench_json", 0.0, "wrote BENCH_hybrid.json")


# ---------------------------------------------------------------------------
# Real-engine end-to-end (CPU-scale, structural validation)
# ---------------------------------------------------------------------------

def bench_engine_e2e() -> None:
    from repro.launch.serve import run as serve_run

    for engine in ("naive", "sipipe"):
        m = serve_run("stablelm-1.6b-smoke", engine=engine, pp=2, requests=4,
                      max_batch=2, max_new_tokens=5, n_samplers=2,
                      verbose=False)
        emit(f"engine_e2e/{engine}", 1e6 / max(m["throughput_tok_s"], 1e-9),
             f"tok_per_s={m['throughput_tok_s']:.2f} "
             f"tpot_ms={m['tpot_mean_s'] * 1e3:.0f}")


# ---------------------------------------------------------------------------
# Pallas kernels (interpret-mode; TPU-target timing is out of scope here)
# ---------------------------------------------------------------------------

def bench_kernels() -> None:
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops

    ks = jax.random.split(jax.random.key(0), 3)
    b, s, h, kv, hd = 1, 512, 8, 2, 64
    q = jax.random.normal(ks[0], (b, s, h, hd), jnp.float32).astype(jnp.bfloat16)
    k = jax.random.normal(ks[1], (b, s, kv, hd), jnp.float32).astype(jnp.bfloat16)
    v = jax.random.normal(ks[2], (b, s, kv, hd), jnp.float32).astype(jnp.bfloat16)

    def krn():
        ops.flash_attention_bshd(q, k, v, q_block=128,
                                 kv_block=128).block_until_ready()

    t = _time(krn, reps=2)
    emit("kernels/flash_attention_interpret_512", t * 1e6,
         "interpret-mode; allclose-validated vs ref in tests")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    args, _ = ap.parse_known_args()
    only = set(args.only.split(",")) if args.only else None
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    def want(name):
        return only is None or name in only

    print("name,us_per_call,derived")
    measured: Dict[str, float] = {}
    if want("sampler"):
        measured.update(bench_sampler())
    if want("sat"):
        bench_sat()
    if want("tsem"):
        bench_tsem()
    if want("throughput"):
        bench_throughput(measured)
    if want("bubbles"):
        bench_bubbles(measured)
    if want("batch"):
        bench_batch_sweep(measured)
    if want("tpot"):
        bench_tpot_cdf(measured)
    if want("scalability"):
        bench_scalability(measured)
    if want("ablation"):
        bench_ablation(measured)
    if want("chunked"):
        bench_chunked_prefill()
    if want("serving"):
        bench_serving()
    if want("paged"):
        bench_paged()
    if want("prefix"):
        bench_prefix()
    if want("http"):
        bench_http()
    if want("hybrid"):
        bench_hybrid()
    if want("engine"):
        bench_engine_e2e()
    if want("kernels"):
        bench_kernels()


if __name__ == "__main__":
    main()
