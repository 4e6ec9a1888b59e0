"""The program's chunked prefill and paged decode against the plain
float32 reference, by logits, at a small size on the CPU.

Tolerance: the program runs in bfloat16 and the reference in float32,
on the same bfloat16 weights.  Each layer rounds the residual stream to
bfloat16 (unit roundoff 2^-8) at three points: the attention output,
the MLP output and each residual sum.  Over L layers, with the LM head
as one more, the logits may move by about 3 (L + 1) x 2^-8 of their
scale.  (The program's own math, run in float32, agrees with the
reference to about 1e-6 of that scale.)"""

TOL = lambda m, scale: 3 * (m["layers"] + 1) * 2.0 ** -8 * scale  # noqa
import jax
import numpy as np

from bench import program, reference, registry, weights

CFG = registry.config("tiny", registry.BENCH / "tests" / "data")


def test_prefill_and_paged_decode_match_reference():
    from repro.core.engine import SiPipeEngine
    from repro.core.sampling_params import SamplingParams

    _, model = program.build_model(CFG)
    w = weights.make(CFG, 2**31 + 99)
    eng = SiPipeEngine(model, program.program_params(model, w),
                       program.engine_config(CFG, 0))
    rows = {}

    def emit(desc, logits, orig=eng.emit_logits):
        for i in desc.sched.sample_indices():
            rows.setdefault(desc.sched.seq_ids[i], []).append(
                np.array(logits[i]))
        orig(desc, logits)

    eng.emit_logits = emit
    rng = np.random.default_rng(0)
    prompts = [rng.integers(2, 256, n).tolist() for n in (5, 40, 77)]
    ids = [eng.add_request(p, SamplingParams(greedy=True, max_new_tokens=9))
           for p in prompts]
    done = {s.seq_id: s for s in eng.run()}
    m = weights.dims(CFG)
    scale = worst = 0.0
    for rid, prompt in zip(ids, prompts):
        out = done[rid].output_ids
        assert len(out) == 9 and len(rows[rid]) == 9
        seq = prompt + out
        x = reference.hidden(w, m, seq)
        pos = np.arange(len(prompt) - 1, len(seq) - 1)
        ref = np.concatenate([np.asarray(lg)[:n] for _, n, lg in
                              reference.logits_at(w, m, x, pos)])
        got = np.stack(rows[rid])
        scale = max(scale, float(np.abs(ref).max()))
        worst = max(worst, float(np.abs(got - ref).max()))
        gaps = reference.served_gaps(w, CFG, prompt, out)
        assert gaps.max() <= TOL(m, scale)
    assert worst <= TOL(m, scale), (worst, scale)
    jax.clear_caches()
