"""The metric arithmetic of the chip benchmark: rates over whole jobs and
waves, roofline and MFU from shapes, and the peak table."""
from __future__ import annotations

import types
from pathlib import Path

import pytest

from chipbench import harness, trace
ROOT = Path(__file__).resolve().parents[2]

SPEC = harness.Spec(ROOT)


def reader(name):
    return SPEC.reader(name)


def fake_run(**kw):
    run = types.SimpleNamespace(records={}, window=(10.0, 30.0), trace=None,
                                spans=[], devices=[None], setup_s=3.5,
                                window_compiles=0)
    run.__dict__.update(kw)
    run.peaks = lambda: harness.device_peaks(SPEC.bench_dir, "TPU v5 lite")
    return run


def test_launch_rate_is_every_launch_over_the_whole_window():
    run = fake_run(records={"launches": 48, "jobs": 3})
    assert reader("launches_per_s").read(run) == pytest.approx(48 / 20)


def test_token_rate_counts_whole_waves():
    run = fake_run(records={"new_tokens": 3 * 16 * 256, "waves": 3})
    assert reader("tokens_per_s").read(run) == pytest.approx(12288 / 20)


FLASH = ("%flash_attention.7 = bf16[240,1024,64]{2,1,0:T(8,128)(2,1)} "
         "custom-call(bf16[240,1024,64]{2,1,0} %q, bf16[80,1024,64]{2,1,0} "
         "%k, bf16[80,1024,64]{2,1,0} %v), custom_call_target=\"tpu_custom_call\"")


def test_flash_cost_from_the_call_shapes():
    m = reader("flash_attention_roofline")
    flops, nbytes = m.call_cost(FLASH)
    assert flops == 4 * 64 * 240 * (1024 * 1025 // 2)
    assert nbytes == 2 * (2 * 240 * 1024 * 64 + 2 * 80 * 1024 * 64)
    peaks = harness.device_peaks(SPEC.bench_dir, "TPU v5 lite")
    # compute-bound: FLOPs over peak exceed bytes over bandwidth
    assert m.least_seconds(FLASH, peaks) == pytest.approx(flops / 197e12)


def test_flash_roofline_share_from_traced_calls():
    m = reader("flash_attention_roofline")
    least = m.least_seconds(FLASH, harness.device_peaks(SPEC.bench_dir,
                                                        "TPU v5 lite"))
    spent_ns = int(least * 4 * 1e9)                 # 4x the least time
    chips = {"/device:TPU:0": {"modules": [], "ops": [
        (FLASH, 0, spent_ns), (FLASH, spent_ns, 2 * spent_ns),
        ("%fusion.3 = f32[8]", 0, 5)]}}
    red = trace.reduce_events(chips, [("chipbench.window", 0, 10 ** 12)])
    assert m.read(fake_run(trace=red)) == pytest.approx(25.0, rel=1e-4)
    assert m.read(fake_run(trace=trace.reduce_events(
        {"/device:TPU:0": {"modules": [], "ops": []}},
        [("chipbench.window", 0, 1)]))) is None


def test_mfu_counts_prefill_decode_and_logits_from_shapes():
    m = reader("model_step.mfu")
    c = {"hidden_size": 8, "intermediate_size": 16, "num_attention_heads": 2,
         "num_key_value_heads": 1, "num_hidden_layers": 3, "vocab_size": 10}
    t = {"prompts": 2, "prompt_len": 4, "max_new": 3}
    hd = 4
    per_tok = 2 * (8 * 8 + 2 * 8 * 4 + 8 * 8 + 3 * 8 * 16) * 3
    tokens = 2 * (4 + 2)
    pairs = 4 * 5 // 2 + (5 + 6)
    attn = 4 * 2 * hd * 3 * 2 * pairs
    logits = 2 * 8 * 10 * 2 * 3
    assert m.wave_flops(c, t) == per_tok * tokens + attn + logits
    cfg = SPEC.config("smollm-360m")
    run = fake_run(records={"waves": 2}, config=cfg,
                   traffic=SPEC.traffic("offline-1024x256"))
    want = 100 * 2 * m.wave_flops(cfg, run.traffic) / 20 / 197e12
    assert m.read(run) == pytest.approx(want)
    assert 0 < m.read(run) < 100


def test_stepper_time_per_simulated_step():
    m = reader("stepper.device_us_per_step")
    chips = {f"/device:TPU:{c}": {"modules": [
        ("jit__run_cohort(7)", 0, 4_000_000), ("jit_convert(1)", 0, 10)],
        "ops": []} for c in range(2)}
    red = trace.reduce_events(chips, [("chipbench.window", 0, 10 ** 10)])
    run = fake_run(trace=red, records={"traced": {
        "launches": 16, "dispatches": 8, "steps": 2000}})
    assert m.read(run) == pytest.approx(4e-3 / 2000 * 1e6)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no peaks"):
        harness.device_peaks(SPEC.bench_dir, "TPU v99")


def test_every_declared_metric_has_a_reader():
    names = [m["name"] for m in SPEC.data["end_to_end"]
             + SPEC.data["per_layer"]]
    for name in names:
        assert callable(reader(name).read), name
