"""Bring-up smoke of the SiPipe serving path on TPU, at stablelm-1.6b's
published widths (24 layers, d_model 2048, vocab 100352) with random
weights drawn from ``--seed``.  A smoke run, not a benchmark: the times
it prints are one cold process's set-up and wall clocks.

  python chip_smoke.py             one chip: HTTP serving through
                                   SiPipeEngine (pp=2, paged KV, chunked
                                   policy), a check that the chunk step
                                   runs the Pallas kernel, and chunk-vs-
                                   dense logits on the same prompts
  python chip_smoke.py --chips 4   four chips, this phase only: pp=4 with
                                   stage i on chip i, against the same
                                   engine with every stage on chip 0

The last line of standard output is one JSON object with "ok" and the
device.  The script exits non-zero without that line when JAX finds no
TPU or when any check fails.  It runs in one process and starts none.
"""
from __future__ import annotations

import argparse
import concurrent.futures as cf
import http.client
import json
import sys
import time
from importlib import metadata
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "stablelm-1.6b"
PUBLISHED = {"num_layers": 24, "d_model": 2048, "vocab_size": 100_352}
N_PROMPTS, MIN_PROMPT, MAX_PROMPT = 8, 100, 400
NEW_TOKENS = 32
CHUNK_TOKENS = 256
BLOCK = 16
MAX_BATCH = 4
# prompt + output <= 432 tokens; 512 keeps the default pool at
# MAX_BATCH * pp * 512 / BLOCK = 256 blocks per stage (384 MiB), small
# beside the parameters and the step's own temporaries
MAX_SEQ_LEN = 512


class SmokeFailure(RuntimeError):
    pass


def check(cond, msg: str):
    if not cond:
        raise SmokeFailure(msg)


def log(msg: str):
    print(f"[smoke] {msg}", flush=True)


def device_report(chips: int):
    """Versions and devices; refuses to go on without ``chips`` TPUs."""
    import jax
    import jaxlib

    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "not installed"
    log(f"jax {jax.__version__}, jaxlib {jaxlib.__version__}, "
        f"libtpu {libtpu}")
    devices = jax.devices()
    log(f"devices: {devices}")
    if devices[0].platform != "tpu":
        raise SystemExit(f"no TPU: JAX found {devices[0].platform} devices")
    if len(devices) < chips:
        raise SystemExit(f"--chips {chips} needs {chips} TPU chips, "
                         f"JAX found {len(devices)}")
    return devices


def smoke_prompts(vocab_size: int, seed: int):
    """N_PROMPTS ShareGPT-shaped prompts of MIN_PROMPT..MAX_PROMPT tokens:
    each spans several chunks of the packed step and several KV blocks."""
    from repro.runtime.data import ShareGPTLike

    wl = ShareGPTLike(vocab_size, n_requests=64, seed=seed,
                      prompt_len_median=200, max_prompt=MAX_PROMPT)
    prompts = [p for p, _ in wl.requests()
               if len(p) >= MIN_PROMPT][:N_PROMPTS]
    check(len(prompts) == N_PROMPTS, f"only {len(prompts)} prompts drawn")
    return prompts


def serve_over_http(prebuilt, prompts, seed: int):
    """The user's path: HTTP front end -> router -> SiPipeEngine.  Every
    request must return 200 with NEW_TOKENS tokens; afterwards every
    replica must be healthy with its whole KV pool free again."""
    from repro.launch.serve import _read_sse, build_http_server

    t0 = time.monotonic()
    _, server = build_http_server(
        ARCH, pp=2, max_batch=MAX_BATCH, max_seq_len=MAX_SEQ_LEN,
        chunk_tokens=CHUNK_TOKENS, policy="chunked", kv_layout="paged",
        block_size=BLOCK, seed=seed, prebuilt=prebuilt)
    server.start()
    host, port = server.address
    log(f"engine + HTTP server up in {time.monotonic() - t0:.1f} s "
        f"(smoke set-up)")

    def request(method, path, body=None):
        conn = http.client.HTTPConnection(host, port, timeout=900)
        conn.request(method, path, body and json.dumps(body),
                     {"Content-Type": "application/json"})
        return conn, conn.getresponse()

    def complete(i):
        stream = i % 4 == 0                  # two of the eight stream
        conn, resp = request("POST", "/v1/completions", {
            "prompt": prompts[i], "max_tokens": NEW_TOKENS,
            "temperature": 0.0, "stream": stream})
        try:
            check(resp.status == 200, f"request {i}: HTTP {resp.status}")
            if stream:
                events = _read_sse(resp)
                check(events and events[-1] == "[DONE]",
                      f"request {i}: stream ended {events[-2:]}")
                toks = [t for ev in events[:-1]
                        for t in json.loads(ev)["choices"][0]["token_ids"]]
            else:
                toks = json.loads(resp.read())["choices"][0]["token_ids"]
        finally:
            conn.close()
        check(len(toks) == NEW_TOKENS,
              f"request {i}: {len(toks)} tokens, asked {NEW_TOKENS}")
        return toks

    def health():
        conn, resp = request("GET", "/health")
        try:
            return resp.status, json.loads(resp.read())
        finally:
            conn.close()

    try:
        t1 = time.monotonic()
        with cf.ThreadPoolExecutor(len(prompts)) as pool:
            outs = list(pool.map(complete, range(len(prompts))))
        wall = time.monotonic() - t1
        log(f"{len(outs)} requests x {NEW_TOKENS} tokens over HTTP in "
            f"{wall:.1f} s wall, compiles included (smoke, not a "
            f"benchmark)")
        deadline = time.monotonic() + 60
        while True:
            status, h = health()
            reps = h["replicas"]
            drained = all(r.get("kv_blocks_free") == r.get("kv_blocks_total")
                          for r in reps.values())
            if drained or time.monotonic() > deadline:
                break
            time.sleep(0.5)
        check(status == 200 and all(r["healthy"] for r in reps.values()),
              f"unhealthy replica: {h}")
        for name, r in reps.items():
            log(f"replica {name}: healthy, KV blocks free "
                f"{r['kv_blocks_free']}/{r['kv_blocks_total']}")
            check(r["kv_blocks_free"] == r["kv_blocks_total"],
                  f"replica {name} holds KV blocks after the drain: {r}")
    finally:
        server.close()
    eng = server.router.replicas[0].engine
    m = eng.metrics()
    check(m["kv_blocks_free"] == m["kv_blocks_total"],
          f"KV blocks leaked: {m['kv_blocks_free']}/{m['kv_blocks_total']}")
    log(f"engine: {m['requests_finished']} finished, {m['tokens']} tokens, "
        f"{m['jit_executables']} jit executables, table widths "
        f"{m['kv_table_widths']}, policy {m['policy']}")
    return eng


def kernel_in_chunk_step(eng):
    """The stage's compiled packed chunk step must call the Pallas paged
    span-attention kernel (``tpu_custom_call``), not the jnp path."""
    import jax
    import jax.numpy as jnp

    w = eng.stages[0]
    like = lambda a: jax.ShapeDtypeStruct(  # noqa: E731
        a.shape, a.dtype, sharding=a.sharding)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    t, b, nb = CHUNK_TOKENS, MAX_BATCH, MAX_SEQ_LEN // BLOCK
    text = w.stage.chunk_fn.lower(
        jax.tree.map(like, w.stage.params), jax.tree.map(like, w.cache),
        i32(t), i32(t), i32(t), i32(b), i32(b), i32(), i32(b, nb),
    ).compile().as_text()
    n = text.count("tpu_custom_call")
    check(n > 0, "the compiled chunk step has no tpu_custom_call: the "
          "paged span attention did not take the Pallas kernel")
    log(f"chunk step (T={t}, B={b}, table {nb}): {n} tpu_custom_call "
        f"sites in the compiled program")


def chunk_vs_dense(eng, prompts, num_layers: int):
    """Last-token logits of the prompts through two packed chunk steps
    over a fresh paged pool (Pallas kernel; the second step attends to
    the first one's K/V through the block table) against the dense
    prefill step (no kernel), with the engine's own stage functions.

    Tolerance, fixed from bf16 before any run: both paths take the same
    bf16 weights and inputs and differ only in how attention is reduced,
    so each layer's output may differ by one bf16 rounding (unit roundoff
    u = 2^-8).  Summed without cancellation over the layers, the
    difference stays within num_layers * u of the logits' scale (max
    |logit| of the dense path)."""
    import jax.numpy as jnp

    s0, s1 = (w.stage for w in eng.stages)
    n = len(prompts)
    lens = np.array([len(p) for p in prompts])
    toks = np.zeros((n, lens.max()), np.int32)
    for i, p in enumerate(prompts):
        toks[i, :len(p)] = p
    last = jnp.asarray(lens - 1, jnp.int32)
    hidden, _ = s0.prefill_fn(s0.params, jnp.asarray(toks), 0, last)
    dense, _ = s1.prefill_fn(s1.params, hidden, 0, last)
    dense = np.asarray(dense, np.float32)

    nbr = -(-int(lens.max()) // BLOCK)
    tables = jnp.asarray(np.arange(n * nbr, dtype=np.int32).reshape(n, nbr))
    pools = [zeroed_pool(w.cache, n * nbr + 1) for w in eng.stages]
    half = lens // 2
    for lo, hi in ((np.zeros_like(lens), half), (half, lens)):
        pos = np.concatenate([np.arange(a, b) for a, b in zip(lo, hi)])
        seq = np.concatenate([np.full(b - a, i)
                              for i, (a, b) in enumerate(zip(lo, hi))])
        tok = np.concatenate([p[a:b] for p, a, b in zip(prompts, lo, hi)])
        last_idx = np.cumsum(hi - lo) - 1
        args = [jnp.asarray(a, jnp.int32) for a in (pos, seq, lo, last_idx)]
        args += [jnp.asarray(len(tok), jnp.int32), tables]
        x, pools[0] = s0.chunk_fn(s0.params, pools[0],
                                  jnp.asarray(tok, jnp.int32), *args)
        chunk, pools[1] = s1.chunk_fn(s1.params, pools[1], x, *args)
    chunk = np.asarray(chunk, np.float32)

    check(np.isfinite(chunk).all() and np.isfinite(dense).all(),
          "non-finite logits")
    scale = float(np.abs(dense).max())
    diff = float(np.abs(chunk - dense).max())
    tol = num_layers * 2.0 ** -8 * scale
    agree = int((chunk.argmax(-1) == dense.argmax(-1)).sum())
    log(f"chunk (Pallas) vs dense prefill last-token logits over {n} "
        f"prompts: max |diff| {diff!r}, tolerance {tol!r} "
        f"(= {num_layers} x 2^-8 x max|logit| {scale!r}); argmax agrees "
        f"on {agree}/{n}")
    check(diff <= tol, f"logits differ by {diff} > {tol}")


def zeroed_pool(cache, n_blocks: int):
    """A zeroed paged pool shaped like ``cache`` but with ``n_blocks``
    physical blocks, on the same device."""
    import jax
    import jax.numpy as jnp

    return jax.tree.map(
        lambda c: jnp.zeros((c.shape[0], n_blocks) + c.shape[2:], c.dtype,
                            device=c.sharding), cache)


def four_chips(prebuilt, prompts, devices):
    """pp=4 with stage i's parameters and KV pool on chip i, against the
    same engine with every stage on chip 0: greedy token streams must be
    identical."""
    import jax

    from repro.core.engine import EngineConfig, SiPipeEngine
    from repro.core.sampling_params import SamplingParams

    _, model, params = prebuilt
    ecfg = EngineConfig(pp_degree=4, max_batch=MAX_BATCH,
                        max_seq_len=MAX_SEQ_LEN,
                        prefill_chunk_tokens=CHUNK_TOKENS,
                        scheduling_policy="chunked", kv_layout="paged",
                        kv_block_size=BLOCK)

    def serve(stage_devices, label):
        t0 = time.monotonic()
        eng = SiPipeEngine(model, params, ecfg, devices=stage_devices)
        where = []
        for i, w in enumerate(eng.stages):
            pdev = {d for x in jax.tree.leaves(w.stage.params)
                    for d in x.devices()}
            cdev = {d for x in jax.tree.leaves(w.cache) for d in x.devices()}
            where.append((pdev, cdev))
            log(f"{label}: stage {i} (layers {w.stage.groups}) params on "
                f"{sorted(map(str, pdev))}, KV pool on "
                f"{sorted(map(str, cdev))}")
        for p in prompts:
            eng.add_request(p, SamplingParams(greedy=True,
                                              max_new_tokens=NEW_TOKENS))
        done = sorted(eng.run(), key=lambda s: s.seq_id)
        m = eng.metrics()
        log(f"{label}: {len(done)} requests, {m['tokens']} tokens in "
            f"{time.monotonic() - t0:.1f} s wall, compiles included "
            f"(smoke, not a benchmark); {m['jit_executables']} jit "
            f"executables")
        check(len(done) == len(prompts)
              and all(len(s.output_ids) == NEW_TOKENS for s in done),
              f"{label}: incomplete outputs")
        return [s.output_ids for s in done], where

    spread, where = serve(list(devices[:4]), "4 chips")
    check(where == [({d}, {d}) for d in devices[:4]],
          f"stages not one per chip: {where}")
    one, where = serve([devices[0]] * 4, "chip 0 only")
    check(where == [({devices[0]}, {devices[0]})] * 4,
          f"comparison stages not all on chip 0: {where}")
    same = sum(a == b for a, b in zip(spread, one))
    log(f"greedy token streams identical for {same}/{len(spread)} "
        f"requests ({NEW_TOKENS} tokens each)")
    check(spread == one, "token streams differ between the four-chip "
          "pipeline and the one-chip run")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the stages-on-four-chips phase")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and the prompts")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    devices = device_report(args.chips)

    import jax

    from repro.launch.serve import build_random_model

    t0 = time.monotonic()
    prebuilt = build_random_model(ARCH, args.seed)
    cfg = prebuilt[0]
    widths = {k: getattr(cfg, k) for k in PUBLISHED}
    check(widths == PUBLISHED, f"{ARCH} is not at published widths: {widths}")
    n_params = sum(x.size for x in jax.tree.leaves(
        jax.block_until_ready(prebuilt[2])))
    log(f"{ARCH}: {widths}, {n_params / 1e9:.3f}B random parameters "
        f"(seed {args.seed}) in {time.monotonic() - t0:.1f} s")
    prompts = smoke_prompts(cfg.vocab_size, args.seed)
    log(f"prompt lengths {[len(p) for p in prompts]}")

    if args.chips == 4:
        four_chips(prebuilt, prompts, devices)
    else:
        eng = serve_over_http(prebuilt, prompts, args.seed)
        del prebuilt
        kernel_in_chunk_step(eng)
        chunk_vs_dense(eng, prompts, cfg.num_layers)
    stats = devices[0].memory_stats() or {}
    log(f"device 0 peak bytes in use: {stats.get('peak_bytes_in_use')}")
    d = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
