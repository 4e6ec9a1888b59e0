"""System-level behaviour: the serving engine and training driver run
end-to-end through their public entry points (the paper's system as a
whole, not individual components)."""
import jax
import numpy as np
import pytest


def test_serve_driver_end_to_end():
    from repro.launch.serve import run

    m = run("stablelm-1.6b-smoke", engine="sipipe", pp=2, requests=4, max_batch=2,
            max_new_tokens=4, n_samplers=2, verbose=False)
    assert m["finished"] == 4
    assert m["tokens"] == 16
    assert m["throughput_tok_s"] > 0
    assert len(m["stages"]) == 2


def test_train_driver_loss_decreases():
    from repro.launch.train import run

    out = run("stablelm-1.6b", steps=30, batch=4, seq=64, log_every=1000)
    head = float(np.mean(out["losses"][:5]))
    tail = float(np.mean(out["losses"][-5:]))
    assert np.isfinite(tail)
    assert tail < head  # a real optimization signal on synthetic data


def test_grad_compression_trains():
    from repro.launch.train import run

    out = run("stablelm-1.6b", steps=8, batch=2, seq=32,
              grad_compression=True, log_every=1000)
    assert np.isfinite(out["final_loss"])


def test_benchmark_harness_importable_and_quick():
    """The benchmark entrypoint's cheap benches run without error."""
    import subprocess
    import sys

    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--only", "tsem"],
        capture_output=True, text=True, timeout=300,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             "HOME": "/root", "JAX_PLATFORMS": "cpu"},
        cwd=".",
    )
    assert "tsem/token_safe_per_iter" in out.stdout, out.stdout + out.stderr
