"""End-to-end serving driver: the SiPipe engine vs the naive PP baseline on
a real (reduced) model with a ShareGPT-shaped batched workload — the
paper's architecture running for real: scheduler -> BIC-I -> stage workers
(TSEM CPU/device executors) -> SAT channels -> CPU sampler pool -> BIC-O.
Plus a taste of the continuous-serving request API (docs/serving.md):
streaming generate(), per-request sampling params and mid-flight abort.

  PYTHONPATH=src python examples/serve_engine.py
"""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent.parent / "src"))

import jax
import numpy as np

from repro.configs import get_config
from repro.core.engine import EngineConfig, SiPipeEngine
from repro.core.sampling_params import SamplingParams
from repro.launch.serve import run
from repro.models import ShardCtx, build_model


def streaming_demo():
    """generate() streams tokens incrementally; each request carries its
    own SamplingParams; abort() cancels mid-decode."""
    print("\n=== streaming request API ===")
    cfg = get_config("stablelm-1.6b-smoke")
    model = build_model(cfg, ShardCtx.single())
    params = model.init(jax.random.key(0))
    eng = SiPipeEngine(model, params, EngineConfig(
        pp_degree=2, max_batch=2, max_seq_len=64))
    rng = np.random.default_rng(0)
    prompts = [list(map(int, rng.integers(2, cfg.vocab_size, size=6)))
               for _ in range(2)]
    per_request = [SamplingParams(greedy=True, max_new_tokens=8),
                   SamplingParams(temperature=0.7, top_k=40,
                                  frequency_penalty=0.5, max_new_tokens=8)]
    for out in eng.generate(prompts, per_request):
        print(f"  req{out.request_id} +{out.new_token_ids}"
              + (f"  [done: {out.finish_reason}, "
                 f"ttft={out.metrics.ttft_s * 1e3:.0f}ms]"
                 if out.finished else ""))
    eng.shutdown()


def main():
    for engine in ("naive", "sipipe"):
        print(f"\n=== engine: {engine} ===")
        m = run("stablelm-1.6b-smoke", engine=engine, pp=2, requests=6,
                max_batch=3, max_new_tokens=8, n_samplers=2)
        print(f"-> {m['finished']} finished, "
              f"{m['throughput_tok_s']:.1f} tok/s, "
              f"p50 ttft {m['ttft_p50_s'] * 1e3:.0f}ms, "
              f"incremental metadata hits {m['incremental_hits']} "
              f"vs rebuilds {m['meta_rebuilds']}")
    streaming_demo()


if __name__ == "__main__":
    main()
