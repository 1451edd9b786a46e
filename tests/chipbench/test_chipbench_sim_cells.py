"""Whole runs of small simulator cells on the CPU, past the look for a
chip: the shape of the result line, discovery of cells added as files
alone, the refusal off a TPU, and ``correct`` coming out false under each
fault a cell can have and under the configuration's control."""
from __future__ import annotations

import json
import os
import subprocess
import sys

from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]

RESULT_KEYS = ["correct", "attempted", "failed", "metrics", "device",
               "checks"]


def test_off_a_tpu_the_command_exits_2_and_prints_nothing():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "chipbench/run.py", "--workload",
                        "sim-suite", "--seed", "3000000001", "--seconds", "1",
                        "--trace", "0"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 2
    assert p.stdout == ""
    assert "needs a TPU" in p.stderr


def test_result_line_of_a_closed_loop_cell(checkout, capsys):
    res = checkout.run(capsys, "t-jobs", seed=2**31 + 12345)
    assert list(res) == RESULT_KEYS               # the checks come last
    assert res["correct"] is True
    assert res["failed"] == 0 and res["attempted"] % 6 == 0
    assert set(res["metrics"]) == {"launches_per_s", "setup_s"}
    assert res["metrics"]["launches_per_s"]["unit"] == "launches/s"
    assert res["device"]["platform"] == "cpu" and res["device"]["count"] == 1
    assert res["checks"] == {"launches_missing": {"value": 0, "limit": 0},
                             "outputs_wrong": {"value": 0, "limit": 0},
                             "stats_wrong": {"value": 0, "limit": 0},
                             "pins_wrong": {"value": 0, "limit": 0}}


def test_a_cell_added_as_files_alone_is_found_by_name(checkout, capsys):
    """A new configuration, traffic mix and per-layer metric: files and
    BENCHMARK.json entries only, no edit to a file that is there."""
    cfg = json.loads((ROOT / "chipbench/configs/ggpu-8cu-shared.json")
                     .read_text())
    cfg.update(name="ggpu-8cu-shared-fuse1",
               machine=dict(cfg["machine"], fuse=1))
    checkout.add_config("ggpu-8cu-shared-fuse1", cfg)
    checkout.write("chipbench/traffic/copy-pairs.json",
                   {"kind": "closed_jobs", "launches_per_bench": 2,
                    "benches": ["copy"]})
    checkout.write("chipbench/metrics/jobs_in_window.py",
                   "def read(run):\n    return run.records.get('jobs')\n")
    checkout.spec["workloads"].append(
        {"name": "t-new", "config": "ggpu-8cu-shared-fuse1",
         "traffic": "copy-pairs", "chips": 1, "why": "a test cell"})
    checkout.spec["end_to_end"][0]["workloads"].append("t-new")
    checkout.spec["per_layer"].append(
        {"name": "jobs_in_window", "unit": "jobs", "better": "higher",
         "source": "program_counter", "layer": "scheduler and executor (host)",
         "moves": "launches_per_s", "workloads": ["t-new"]})
    res = checkout.run(capsys, "t-new", seed=3, trace=1)
    assert res["correct"] is True
    assert res["metrics"]["jobs_in_window"]["value"] >= 1


# -- faults of the timed path: each must make ``correct`` false -------------

def _sim_fault(monkeypatch, kind):
    from repro.serve import executors, scheduler

    if kind == "unchanged":                  # the step left its state alone
        run = executors.Executor.collect

        def frozen(self, pending):
            return [r._replace(mem=req.mem0[slice(*req.out_region)])
                    for req, r in zip(pending.reqs, run(self, pending))]
        monkeypatch.setattr(executors.Executor, "collect", frozen)
        return
    collect = scheduler.Scheduler.collect

    def broken(self):
        out = collect(self)
        if kind == "half":                   # half of the batch left out
            return out[::2]
        if kind == "altered":                # one answer altered
            r = out[-1]
            mem = np.array(r.mem)
            mem[0] += 1
            return out[:-1] + [r._replace(mem=mem)]
        if kind == "pins":                   # one cycle count off by one
            out[0].info["cycles"] += 1
            return out
        if kind == "stats":                  # one instruction count off
            out[-1].info["instrs"] += 1
            return out
        raise ValueError(kind)

    monkeypatch.setattr(scheduler.Scheduler, "collect", broken)


@pytest.mark.parametrize("kind", ["unchanged", "half", "altered", "stats",
                                  "pins"])
def test_simulator_faults_make_the_run_incorrect(checkout, capsys,
                                                 monkeypatch, kind):
    _sim_fault(monkeypatch, kind)
    res = checkout.run(capsys, "t-jobs", seed=11)
    assert res["correct"] is False
    assert any(c["value"] > c["limit"] for c in res["checks"].values())


SHARDED_CHILD = r"""
import sys, time
from pathlib import Path
sys.path[:0] = [{root!r}, {tests!r}]
import jax
from conftest import Checkout
from chipbench import run as bench_run
from repro.serve import executors

co = Checkout(Path({tmp!r}))
co.write("chipbench/traffic/t-jobs4.json", {{"kind": "closed_jobs",
         "launches_per_bench": 8, "benches": ["copy", "div_int"]}})
co.add_cell("t-jobs4", "ggpu-8cu-shared", "t-jobs4", like="sim-suite",
            chips=4)
co.save()
if {broken!r}:
    # the exchange between chips left out: only the first chip's share of
    # each chunk comes back computed, the others' images come back as sent
    collect = executors.Executor.collect
    def lost(self, pending):
        out = collect(self, pending)
        first = -(-len(out) // 4)
        return out[:first] + [r._replace(mem=q.mem0[slice(*q.out_region)])
                              for q, r in zip(pending.reqs[first:],
                                              out[first:])]
    executors.Executor.collect = lost
args = bench_run.parse(["--workload", "t-jobs4", "--seed", "41",
                        "--seconds", "1", "--trace", "0"])
sys.exit(bench_run.run_cell(co.root, args, jax.devices()[:4],
                            time.perf_counter()))
"""


@pytest.mark.parametrize("broken", [False, True])
def test_four_chip_cell_and_the_exchange_between_chips_left_out(tmp_path,
                                                                broken):
    code = SHARDED_CHILD.format(root=str(ROOT), tests=str(Path(__file__).parent),
                                tmp=str(tmp_path / "checkout"), broken=broken)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(ROOT / "src"))
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["device"]["count"] == 4
    assert res["correct"] is (not broken)
    if broken:
        assert res["checks"]["outputs_wrong"]["value"] > 0


def test_simulator_control_breaking_the_stats_guarantee_is_incorrect(
        checkout, capsys):
    """The configuration's control: the same cell on the banked memory
    system, whose cache statistics differ from the shared one's."""
    cfg = json.loads((checkout.root / "chipbench/configs/"
                      "ggpu-8cu-shared.json").read_text())
    cfg["machine"]["memsys"] = "banked"
    checkout.write("chipbench/configs/ggpu-8cu-shared.json", cfg)
    checkout.write("chipbench/traffic/t-jobs.json",
                   {"kind": "closed_jobs", "launches_per_bench": 2,
                    "benches": ["fir", "mat_mul"]})
    res = checkout.run(capsys, "t-jobs", seed=13)
    assert res["correct"] is False
    assert res["checks"]["pins_wrong"]["value"] > 0
    assert res["checks"]["outputs_wrong"]["value"] == 0
    assert res["checks"]["stats_wrong"]["value"] == 0


@pytest.mark.parametrize("n, seed", [(256, 1), (512, 2)])
def test_reference_counts_a_data_dependent_kernel_as_the_simulator_does(
        n, seed):
    """parallel_sel's branches depend on the image: the reference's count
    of its instructions and steps must follow the image as the simulator's
    does (here at sizes a CPU run holds)."""
    from chipbench import harness
    from repro.ggpu import programs
    from repro.ggpu.engine import GGPUConfig, run_kernel

    spec = harness.Spec(ROOT)
    ref = spec.reference("ggpu_suite")
    machine = spec.config("ggpu-8cu-shared")["machine"]
    b = programs._parallel_sel(n_gpu=n)
    mem = np.random.default_rng(seed).integers(
        -5, 5, b.gpu_mem.shape[0]).astype(np.int32)
    _, info = run_kernel(b.gpu_prog, mem, b.gpu_items, GGPUConfig(**machine))
    want = ref.derived_stats("parallel_sel", {"n": n}, machine, mem)
    assert want == {k: int(info[k]) for k in want}
