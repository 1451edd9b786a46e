"""Whole runs of a small language-model cell on the CPU, past the look for
a chip: a sound run is correct, and ``correct`` comes out false under each
fault the cell can have and under the float8 control."""
from __future__ import annotations

import time

import pytest


def _lm_fault(monkeypatch, kind):
    from repro.serve import llm

    if kind == "unchanged":                  # decode leaves the cache alone
        make = llm.make_decode_step

        def frozen(cfg):
            step = make(cfg)
            return lambda params, cache, tok, pos: (
                step(params, cache, tok, pos)[0], cache)
        monkeypatch.setattr(llm, "make_decode_step", frozen)
    elif kind == "half":                     # half of the batch left out
        gen = llm.Engine.generate
        monkeypatch.setattr(llm.Engine, "generate",
                            lambda self, ps, max_new: gen(
                                self, ps[:len(ps) // 2], max_new))
    elif kind == "altered":                  # a token altered where produced
        sample = llm.Engine._sample
        calls = []

        def bad(self, logits, rng):
            out = sample(self, logits, rng)
            calls.append(1)
            return out.at[:].set((out + 1) % logits.shape[-1]) \
                if len(calls) % 3 == 2 else out
        monkeypatch.setattr(llm.Engine, "_sample", bad)
    else:
        raise ValueError(kind)


def test_lm_sound_run_is_correct(checkout, capsys):
    res = checkout.run(capsys, "t-lm", seed=21)
    assert res["correct"] is True
    assert set(res["metrics"]) == {"tokens_per_s", "setup_s"}
    gap = res["checks"]["served_logit_gap"]
    assert gap["value"] < gap["limit"]


@pytest.mark.parametrize("kind", ["unchanged", "half", "altered"])
def test_lm_faults_make_the_run_incorrect(checkout, capsys, monkeypatch,
                                          kind):
    _lm_fault(monkeypatch, kind)
    res = checkout.run(capsys, "t-lm", seed=22)
    assert res["correct"] is False


def test_lm_float8_control_fails_the_gap_limit(checkout):
    """The lower-precision control: the reference in float8 ranks tokens
    whose float32 logits lie further below the best than the limit."""
    import jax

    from chipbench import control, harness
    checkout.save()
    spec = harness.Spec(checkout.root)
    rows = control.lm_readings(spec, "t-lm", seeds=[31, 32, 33],
                               devices=jax.devices()[:1],
                               t_start=time.perf_counter())
    limit = spec.config("tiny-llama")["limits"]["served_logit_gap"]
    for row in rows:
        assert row["served"] <= limit < row["control"], row
