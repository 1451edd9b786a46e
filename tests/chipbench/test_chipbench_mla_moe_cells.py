"""Whole runs of a small DeepSeek-V2-type cell (latent attention, a dense
first layer, MoE layers holding a share of the routed experts plus shared
ones) on the CPU, past the look for a chip: a sound run is correct, a
fault makes it incorrect, and the float8 control fails the gap limit; and
the four-chip simulator cell's job on four forced CPU devices."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]

TINY_MLA = {"hidden_size": 64, "num_attention_heads": 4,
            "num_key_value_heads": 4, "kv_lora_rank": 32,
            "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
            "intermediate_size": 96, "moe_intermediate_size": 24,
            "n_routed_experts": 4, "n_routed_experts_published": 16,
            "expert_shard": 1, "num_experts_per_tok": 4,
            "num_hidden_layers": 3, "vocab_size": 512}
MLA_TRAFFIC = {"kind": "offline_waves", "prompts": 4, "prompt_len": 16,
               "max_new": 16, "slots": 4, "check_requests": 4}
# the tiny model's gap limit, from its own readings on the CPU (seeds
# 31-40): sound runs 0.0-0.089, the float8 control 0.40-0.89
TINY_MLA_GAP_LIMIT = 0.2


@pytest.fixture
def mla_checkout(checkout):
    cfg = json.loads((ROOT / "chipbench/configs/deepseek-v2-lite-ep8.json")
                     .read_text())
    cfg.update(TINY_MLA, name="tiny-mla")
    # YaRN's scaling live past the tiny prompt's 16 positions
    cfg["rope_scaling"] = dict(cfg["rope_scaling"],
                               original_max_position_embeddings=16)
    cfg["serving"]["use_pallas"] = False     # no kernel off the chip
    cfg["limits"]["served_logit_gap"] = TINY_MLA_GAP_LIMIT
    checkout.add_config("tiny-mla", cfg)
    checkout.write("chipbench/traffic/t-mla-waves.json", MLA_TRAFFIC)
    checkout.add_cell("t-mla", "tiny-mla", "t-mla-waves",
                      like="deepseek-v2-lite-offline")
    checkout.save()
    return checkout


def _mla_fault(monkeypatch, kind):
    import jax.numpy as jnp

    from repro.models import mla, moe

    if kind == "decode_without_rope":        # the shared rope key left out
        absorbed = mla.absorbed_decode

        def no_rope(q_nope, q_pe, cache, *a, **k):
            return absorbed(q_nope, q_pe * 0, cache, *a, **k)
        monkeypatch.setattr(mla, "absorbed_decode", no_rope)
    elif kind == "routed_dropped":           # no pair reaches its expert
        part = moe.held_experts_part

        def dropped(p, hx, gate, idx, cfg):
            return part(p, hx, gate, jnp.full_like(idx, -1), cfg)
        monkeypatch.setattr(moe, "held_experts_part", dropped)
    elif kind == "no_shared":                # the shared experts left out
        swiglu = moe._swiglu
        monkeypatch.setattr(moe, "_swiglu",
                            lambda x, wi, wo, cfg: 0 * swiglu(x, wi, wo, cfg))
    else:
        raise ValueError(kind)


def test_mla_sound_run_is_correct(mla_checkout, capsys):
    res = mla_checkout.run(capsys, "t-mla", seed=2**31 + 21)
    assert res["correct"] is True
    assert set(res["metrics"]) == {"tokens_per_s", "setup_s"}
    gap = res["checks"]["served_logit_gap"]
    assert gap["value"] < gap["limit"]
    assert res["checks"]["requests_malformed"]["value"] == 0


def test_mla_traced_run_reports_its_per_layer_metrics(mla_checkout, capsys):
    res = mla_checkout.run(capsys, "t-mla", seed=23, trace=1)
    assert res["correct"] is True
    # the CPU trace has no TPU planes: the device metrics find nothing
    assert "model_step.mfu.mla_moe" in res["metrics"]
    assert 0 < res["metrics"]["model_step.mfu.mla_moe"]["value"]


@pytest.mark.parametrize("kind", ["decode_without_rope", "routed_dropped",
                                  "no_shared"])
def test_mla_faults_make_the_run_incorrect(mla_checkout, capsys, monkeypatch,
                                           kind):
    _mla_fault(monkeypatch, kind)
    res = mla_checkout.run(capsys, "t-mla", seed=22)
    assert res["correct"] is False


def test_mla_float8_control_fails_the_gap_limit(mla_checkout):
    """The lower-precision control: the reference in float8 ranks tokens
    whose float32 logits lie further below the best than the limit."""
    import jax

    from chipbench import control, harness
    spec = harness.Spec(mla_checkout.root)
    rows = control.lm_readings(spec, "t-mla", seeds=[31, 34],
                               devices=jax.devices()[:1],
                               t_start=time.perf_counter())
    limit = spec.config("tiny-mla")["limits"]["served_logit_gap"]
    for row in rows:
        assert row["served"] <= limit < row["control"], row


def test_mla_params_made_here_are_the_programs_layout_at_full_size():
    """The driver's weights at the cell's own sizes, as shapes only."""
    import jax

    from chipbench import harness
    from repro.models.schema import abstract_params
    spec = harness.Spec(ROOT)
    drv = spec.driver("mla_moe")
    c = spec.config("deepseek-v2-lite-ep8")
    cfg = drv.program_config(c)
    got = jax.eval_shape(lambda: drv.make_params(c, 2**33 + 5))
    want = abstract_params(cfg)
    assert jax.tree.map(lambda a: (a.shape, a.dtype), got) \
        == jax.tree.map(lambda a: (a.shape, a.dtype), want)
    n = sum(a.size for a in jax.tree.leaves(got))
    assert n == pytest.approx(3.11e9, rel=0.01)          # 6.2 GB in bf16


SIM4_CHILD = r"""
import sys, time
from pathlib import Path
sys.path[:0] = [{root!r}, {tests!r}]
import jax
from conftest import Checkout
from chipbench import run as bench_run

co = Checkout(Path({tmp!r}))
co.save()
args = bench_run.parse(["--workload", "sim-suite-4chip", "--seed",
                        "3000000041", "--seconds", "1", "--trace", "0"])
sys.exit(bench_run.run_cell(co.root, args, jax.devices()[:4],
                            time.perf_counter()))
"""


def test_four_chip_suite_cell_is_correct_on_four_devices(tmp_path):
    """sim-suite-4chip as declared: one job of the whole suite, 8 launches
    of each bench, each cohort sharded 2 launches per chip."""
    code = SIM4_CHILD.format(root=str(ROOT), tests=str(Path(__file__).parent),
                             tmp=str(tmp_path / "checkout"))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(ROOT / "src"))
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["device"]["count"] == 4
    assert res["correct"] is True
    assert res["attempted"] % 64 == 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"launches_per_s", "setup_s"}
