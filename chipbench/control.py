#!/usr/bin/env python3
"""Readings that set the limits of ``correct``: the program's sound runs
and each configuration's control, at a cell's own size, over many seeds in
one process. The benchmark's own runs never run this.

    python3 chipbench/control.py --workload <name> --seeds 1,2,3 [--seconds S]

- Language models: per seed, one window of the cell's traffic, then the
  widest gap of the served tokens under the float32 reference (what the
  check compares) and, at the same positions, the widest gap of the token
  the float8 reference ranks first (the control).
- Simulator configurations: per seed, the cell's check on the program as
  configured and on the control, the configuration on the banked memory
  system, which breaks the guarantee that the simulated statistics are
  those of the configuration.

One JSON line per seed on standard output; off a TPU it exits 2.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:] = [p for p in sys.path
               if Path(p or ".").resolve() != ROOT / "chipbench"]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import harness  # noqa: E402


def _window(spec, workload, seed, seconds, devices, t_start, config=None):
    run = harness.Run(spec, workload, seed, seconds, False, devices, t_start)
    if config is not None:
        run.config = config
    drv = spec.driver(run.config["driver"])
    st = drv.setup(run)
    drv.window(run, st)
    return run, drv, drv.release(run, st)


def lm_readings(spec, workload, seeds, devices, t_start, seconds=0.0):
    rows = []
    for seed in seeds:
        run, drv, st = _window(spec, workload, seed, seconds, devices,
                               t_start)
        pairs = drv.gaps(run, st, fp8=True)
        rows.append({"seed": seed,
                     "served": max(float(g.max()) for g, _ in pairs),
                     "control": max(float(c.max()) for _, c in pairs),
                     "requests_malformed": drv.counts(run, st)[1]})
        del st
    return rows


def sim_readings(spec, workload, seeds, devices, t_start, seconds=0.0):
    rows = []
    base = spec.config(spec.workload(workload)["config"])
    ctrl = dict(base, machine=dict(base["machine"], memsys="banked"))
    for seed in seeds:
        row = {"seed": seed}
        for label, cfg in (("sound", base), ("control", ctrl)):
            run, drv, st = _window(spec, workload, seed, seconds, devices,
                                   t_start, cfg)
            row[label] = {c["name"]: c["value"] for c in drv.check(run, st)}
        rows.append(row)
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=0.0)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    devices = jax.devices()
    spec = harness.Spec(ROOT)
    chips = spec.workload(args.workload)["chips"]
    if devices[0].platform != "tpu" or len(devices) < chips:
        print("chipbench.control: needs a TPU with enough chips",
              file=sys.stderr)
        return 2
    harness.enable_compile_cache(ROOT)
    sys.path.insert(0, str(ROOT / "src"))
    seeds = [int(s) for s in args.seeds.split(",")]
    driver = spec.config(spec.workload(args.workload)["config"])["driver"]
    rows = (lm_readings(spec, args.workload, seeds, devices[:chips],
                        T_START, args.seconds) if driver == "lm" else
            sim_readings(spec, args.workload, seeds, devices[:chips],
                         T_START, args.seconds))
    for row in rows:
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
