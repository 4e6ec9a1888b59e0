"""A whole run (everything but the look for a chip) with the timed path
broken underneath must come out not correct, once for each fault a
served cell can have; and come out correct unbroken."""
import pytest

from bench import registry, run

BASE = registry.BENCH / "tests" / "data"
SPEC = registry.load_json(BASE / "benchmark.json")
PEAKS = registry.load_json(registry.BENCH / "peaks.json")["devices"][
    "TPU v5 lite"]


def _run(cell="tiny.chat", seed=2**31 + 7):
    import jax

    return run.run(cell, seed, 3.0, False, jax.devices()[0], PEAKS, SPEC,
                   BASE)


def test_sound_run_is_correct():
    res = _run()
    assert res["correct"], res["check"]
    assert res["check"]["window_compiles"]["value"] == 0
    assert list(res)[-1] == "check"


def test_altered_token_fails(monkeypatch):
    """A token altered where it is produced: the sampler's choice."""
    from repro.core import sampler

    orig = sampler.ColumnWiseSampler.sample

    def altered(self, logits, *a, **kw):
        ids = orig(self, logits, *a, **kw)
        return (ids + 1) % logits.shape[-1]

    monkeypatch.setattr(sampler.ColumnWiseSampler, "sample", altered)
    res = _run()
    assert not res["correct"]
    gap = res["check"]["logit_gap_max"]
    assert gap["value"] > gap["limit"]


def test_step_returning_its_state_unchanged_fails(monkeypatch):
    """Each stage step hands back the KV state it was given: what a
    step writes never reaches later steps."""
    from repro.models import transformer

    orig = transformer.self_attn_block

    def unchanged(p, x, ctx, cache, cfg, **kw):
        x, new = orig(p, x, ctx, cache, cfg, **kw)
        return x, (cache if ctx.mode in ("chunk", "decode") else new)

    monkeypatch.setattr(transformer, "self_attn_block", unchanged)
    res = _run()
    assert not res["correct"]
    gap = res["check"]["logit_gap_max"]
    assert gap["value"] > gap["limit"]


@pytest.mark.parametrize("cell", ["tiny.batch"])
def test_closed_loop_run_is_correct(cell):
    res = _run(cell, seed=11)
    assert res["correct"], res["check"]
    assert res["metrics"]["output_tok_s"]["value"] > 0
