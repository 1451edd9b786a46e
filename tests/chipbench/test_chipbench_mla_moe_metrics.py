"""The arithmetic of the DeepSeek-V2-type cell's per-layer metrics: the
model FLOPs of the chip's share, the flash kernel's roofline at unequal
query and value widths, and the decode step's least bytes."""
from __future__ import annotations

import types
from pathlib import Path

import pytest

from chipbench import harness, trace

ROOT = Path(__file__).resolve().parents[2]
SPEC = harness.Spec(ROOT)
CELL = "deepseek-v2-lite-offline"


def fake_run(**kw):
    run = types.SimpleNamespace(records={}, window=(10.0, 30.0), trace=None,
                                devices=[None],
                                config=SPEC.config("deepseek-v2-lite-ep8"),
                                traffic=SPEC.traffic("offline-4096x256"))
    run.__dict__.update(kw)
    run.peaks = lambda: harness.device_peaks(SPEC.bench_dir, "TPU v5 lite")
    return run


MLA_FLASH = ("%flash_attention.3 = bf16[256,4096,128]{2,1,0:T(8,128)(2,1)} "
             "custom-call(bf16[256,4096,192]{2,1,0} %q, bf16[256,4096,192]"
             "{2,1,0} %k, bf16[256,4096,128]{2,1,0} %v), "
             "custom_call_target=\"tpu_custom_call\"")


def test_mla_flash_cost_counts_each_head_size():
    m = SPEC.reader("flash_attention_roofline.mla")
    flops, nbytes = m.call_cost(MLA_FLASH)
    assert flops == 2 * (192 + 128) * 256 * (4096 * 4097 // 2)
    assert nbytes == 2 * 256 * 4096 * (192 + 128 + 192 + 128)
    # at equal sizes it counts what flash_attention_roofline counts
    gqa = ("%flash_attention.7 = bf16[240,1024,64]{2,1,0} custom-call("
           "bf16[240,1024,64]{2,1,0} %q, bf16[80,1024,64]{2,1,0} %k, "
           "bf16[80,1024,64]{2,1,0} %v)")
    assert m.call_cost(gqa) == SPEC.reader(
        "flash_attention_roofline").call_cost(gqa)


def test_mla_flash_roofline_share_from_traced_calls():
    m = SPEC.reader("flash_attention_roofline.mla")
    least = m.least_seconds(MLA_FLASH, fake_run().peaks())
    ns = int(least * 5 * 1e9)
    red = trace.reduce_events(
        {"/device:TPU:0": {"modules": [], "ops": [(MLA_FLASH, 0, ns)]}},
        [("chipbench.window", 0, 10 ** 12)])
    assert m.read(fake_run(trace=red)) == pytest.approx(20.0, rel=1e-4)


def test_mla_moe_mfu_counts_the_chips_share_from_shapes():
    m = SPEC.reader("model_step.mfu.mla_moe")
    c = {"hidden_size": 8, "num_attention_heads": 2, "kv_lora_rank": 4,
         "qk_nope_head_dim": 3, "qk_rope_head_dim": 2, "v_head_dim": 3,
         "vocab_size": 10, "num_hidden_layers": 3, "first_k_dense_replace": 1,
         "n_routed_experts_published": 8, "n_routed_experts": 2,
         "num_experts_per_tok": 4, "moe_intermediate_size": 5,
         "n_shared_experts": 2, "intermediate_size": 12}
    t = {"prompts": 2, "prompt_len": 4, "max_new": 3}
    proj = 8 * 2 * 5 + 8 * 6 + 4 * 2 * 6 + 2 * 3 * 8
    moe = 8 * 8 + 3 * 8 * 10 + (4 * 2 / 8) * 3 * 8 * 5
    per_tok = 2 * (3 * proj + 3 * 8 * 12 + 2 * moe)
    pairs = 4 * 5 // 2 + (5 + 6)
    attn = 2 * (3 + 2 + 3) * 2 * 3 * 2 * pairs
    logits = 2 * 8 * 10 * 2 * 3
    assert m.wave_flops(c, t) == pytest.approx(per_tok * 2 * 6 + attn
                                               + logits)
    run = fake_run(records={"waves": 2})
    want = 100 * 2 * m.wave_flops(run.config, run.traffic) / 20 / 197e12
    assert m.read(run) == pytest.approx(want)
    # a wave of the cell is about 190 TFLOP of this chip's share
    assert 1.7e14 < m.wave_flops(run.config, run.traffic) < 2.1e14


def test_decode_hbm_roofline_from_the_least_bytes_of_a_step():
    m = SPEC.reader("decode.hbm_roofline")
    run = fake_run()
    nbytes = m.step_bytes(run.config, run.traffic)
    # weights held, less the embedding, with ~6.3 of 8 experts touched,
    # plus ~2.1 GB of live latent cache
    assert 6.9e9 < nbytes < 7.4e9
    step_ns = int(nbytes / 819e9 * 2 * 1e9)          # half the bandwidth
    chips = {"/device:TPU:0": {"modules": [
        ("jit_decode_step(3)", 0, step_ns),
        ("jit_decode_step(3)", step_ns, 2 * step_ns),
        ("jit_other(1)", 0, 5)], "ops": []}}
    red = trace.reduce_events(chips, [("chipbench.window", 0, 10 ** 12)])
    assert m.read(fake_run(trace=red)) == pytest.approx(50.0, rel=1e-4)
    assert m.read(fake_run(trace=trace.reduce_events(
        {"/device:TPU:0": {"modules": [], "ops": []}},
        [("chipbench.window", 0, 1)]))) is None


def test_new_metrics_are_read_in_the_new_cell_only():
    names = {m["name"] for m in SPEC.metrics_for(CELL, traced=True)}
    assert {"model_step.mfu.mla_moe", "flash_attention_roofline.mla",
            "decode.hbm_roofline", "decode.device_ms_per_step"} <= names
    assert "flash_attention_roofline" not in names
    smollm = {m["name"] for m in SPEC.metrics_for("smollm-offline", True)}
    assert not smollm & {"model_step.mfu.mla_moe",
                         "flash_attention_roofline.mla",
                         "decode.hbm_roofline"}
