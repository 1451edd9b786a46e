"""Fixtures of the chip benchmark's CPU tests: a checkout in a temporary
directory holding the benchmark's files plus small cells of its own, and a
way to run one cell there on the CPU past the look for a chip."""
from __future__ import annotations

import json
import shutil
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

# short benches at their Table III sizes: cheap enough for the CPU
SIM_CELLS = {
    "t-jobs": {"kind": "closed_jobs", "launches_per_bench": 2,
               "benches": ["copy", "div_int", "mat_mul"]},
}
TINY_LLAMA = {"hidden_size": 64, "intermediate_size": 128,
              "num_attention_heads": 4, "num_key_value_heads": 2,
              "num_hidden_layers": 2, "vocab_size": 1024}
LM_TRAFFIC = {"kind": "offline_waves", "prompts": 4, "prompt_len": 16,
              "max_new": 16, "slots": 4, "check_requests": 4}
# the tiny model's gap limit, from its own readings on the CPU (seeds
# 31-36): sound runs 0.0002-0.0060, the float8 control 0.037-0.084
TINY_GAP_LIMIT = 0.015


class Checkout:
    """A copy of the benchmark under ``root`` with the program beside it."""

    def __init__(self, root: Path):
        self.root = root
        shutil.copytree(ROOT / "chipbench", root / "chipbench",
                        ignore=shutil.ignore_patterns("__pycache__"))
        (root / "src").symlink_to(ROOT / "src")
        self.spec = json.loads((ROOT / "BENCHMARK.json").read_text())

    def write(self, rel: str, data) -> None:
        path = self.root / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(data if isinstance(data, str) else json.dumps(data))

    def add_cell(self, name: str, config: str, traffic: str,
                 like: str, chips: int = 1) -> None:
        """A cell reporting what the existing cell ``like`` reports."""
        self.spec["workloads"].append({"name": name, "config": config,
                                       "traffic": traffic, "chips": chips,
                                       "why": "a test cell"})
        for m in self.spec["end_to_end"] + self.spec["per_layer"]:
            if like in m.get("workloads", []):
                m["workloads"].append(name)

    def add_config(self, name: str, data: dict) -> None:
        self.write(f"chipbench/configs/{name}.json", data)
        self.spec["configs"].append({"name": name, "source": "test",
                                     "file": f"chipbench/configs/{name}.json",
                                     "reduced": [], "why": "a test config"})

    def save(self) -> None:
        self.write("BENCHMARK.json", self.spec)

    def run(self, capsys, workload: str, seed: int = 5, seconds: float = 1.0,
            trace: int = 0, devices=None) -> dict:
        """Run ``workload`` here on the CPU; returns the result line."""
        import jax

        from chipbench import run as bench_run
        self.save()
        args = bench_run.parse(["--workload", workload, "--seed", str(seed),
                                "--seconds", str(seconds), "--trace",
                                str(trace)])
        capsys.readouterr()
        rc = bench_run.run_cell(self.root, args,
                                devices or jax.devices()[:1],
                                time.perf_counter())
        out = capsys.readouterr().out.strip().splitlines()
        assert rc == 0
        return json.loads(out[-1])


@pytest.fixture
def checkout(tmp_path):
    co = Checkout(tmp_path / "checkout")
    for name, traffic in SIM_CELLS.items():
        co.write(f"chipbench/traffic/{name}.json", traffic)
    co.add_cell("t-jobs", "ggpu-8cu-shared", "t-jobs", like="sim-suite")
    lm = json.loads((ROOT / "chipbench/configs/smollm-360m.json").read_text())
    lm.update(TINY_LLAMA, name="tiny-llama")
    lm["serving"]["use_pallas"] = False      # no kernel off the chip
    lm["limits"]["served_logit_gap"] = TINY_GAP_LIMIT
    co.add_config("tiny-llama", lm)
    co.write("chipbench/traffic/t-waves.json", LM_TRAFFIC)
    co.add_cell("t-lm", "tiny-llama", "t-waves", like="smollm-offline")
    # traced runs on the CPU read peaks for its device kind
    peaks = json.loads((ROOT / "chipbench/peaks.json").read_text())
    peaks["cpu"] = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
    co.write("chipbench/peaks.json", peaks)
    co.save()
    return co
