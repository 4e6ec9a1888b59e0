"""The controls, at a size a test run holds (their readings at the
cells' own sizes, from the chip, are in PERF.md).

- The program's own lower-precision path, its int8 KV cache, switched
  on: the run reads the served state's type and comes out not correct.
- The reference computed with float8 (e4m3) weights in the program's
  place: its widest gap lies above the cell's limit, the sound
  program's below it, and its greedy tokens, put in place of the served
  ones, make the run's own check (``run.check``) come out not
  correct."""
import numpy as np

from bench import program, reference, registry, run, weights

BASE = registry.BENCH / "tests" / "data"
SPEC = registry.load_json(BASE / "benchmark.json")
PEAKS = registry.load_json(registry.BENCH / "peaks.json")["devices"][
    "TPU v5 lite"]
CFG = registry.config("tiny", BASE)
BLOCK = registry.block(CFG["block"], BASE)
LIMIT = registry.cell("tiny.chat", BASE)["check"]["logit_gap_limit"]


def test_program_int8_kv_path_is_not_correct(monkeypatch):
    import jax

    build = program.build_model
    monkeypatch.setattr(program, "build_model",
                        lambda cfg, kv_quant=False: build(cfg, True))
    res = run.run("tiny.chat", 21, 3.0, False, jax.devices()[0], PEAKS,
                  SPEC, BASE)
    assert not res["correct"]
    # the int8 K and V pools of both stages
    assert res["check"]["state_not_bfloat16"]["value"] == 2 * 2


def test_reference_float8_control_fails_the_limit():
    """The program's served tokens pass the run's own check; the float8
    control's, put in their place (greedy, teacher-forced reading too),
    fail it on every seed."""
    from repro.core.engine import SiPipeEngine
    from repro.core.sampling_params import SamplingParams

    cell = registry.cell("tiny.chat", BASE)
    worst_prog = worst_ctrl = 0.0
    for seed in (3, 4, 5):
        _, model = program.build_model(CFG)
        w = weights.make(CFG, seed)
        eng = SiPipeEngine(model, program.program_params(model, w),
                           program.engine_config(CFG, 0))
        rng = np.random.default_rng(seed)
        reqs = [{"prompt": rng.integers(2, 256, n).tolist()} for n in (60, 90)]
        ids = [eng.add_request(r["prompt"], SamplingParams(
            greedy=True, max_new_tokens=20)) for r in reqs]
        done = {s.seq_id: s for s in eng.run()}
        served = [{"i": i, "tokens": done[rid].output_ids}
                  for i, rid in enumerate(ids)]
        ok, nums = run.check(CFG, BLOCK, cell, seed, served, reqs, 0, 0)
        assert ok, nums
        ctrl = [dict(r, tokens=reference.control_decode(
            w, CFG, reqs[r["i"]]["prompt"], len(r["tokens"])))
            for r in served]
        ok, nums = run.check(CFG, BLOCK, cell, seed, ctrl, reqs, 0, 0)
        assert not ok, nums
        assert nums["logit_gap_max"]["value"] > LIMIT
        for r in served:
            p = reqs[r["i"]]["prompt"]
            worst_prog = max(worst_prog, reference.served_gaps(
                w, CFG, p, r["tokens"]).max())
            worst_ctrl = max(worst_ctrl, reference.control_gaps(
                w, CFG, p, r["tokens"]).max())
    assert worst_prog <= LIMIT < worst_ctrl, (worst_prog, LIMIT, worst_ctrl)
