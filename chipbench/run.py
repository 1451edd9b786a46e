#!/usr/bin/env python3
"""Run one cell of the chip benchmark on the accelerator this process sees.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and its traffic mix are looked up by name in
``BENCHMARK.json`` at the root of the checkout (see ``harness``). Set-up
builds the system under test and warms every shape the traffic uses; the
window then runs for ``--seconds``; after it, ``correct`` is decided by
comparing what the window produced with the plain reference. ``--trace 1``
runs the same window, then records a profiler trace of a slice of the same
traffic after it (the driver's ``traced_slice``), and reports the
per-layer metrics instead of the end-to-end ones: those timed by the host
from the window, those read from the trace from the slice.

The first line on standard output names the device; the last is the
result. Off a TPU, or with fewer chips than the cell asks for, the command
exits 2 and prints no result: there is no CPU fallback.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
# the package, not this script's directory: ``chipbench/trace.py`` must
# not shadow the standard library's ``trace``
sys.path[:] = [p for p in sys.path
               if Path(p or ".").resolve() != ROOT / "chipbench"]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import harness, trace  # noqa: E402


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def run_cell(root: Path, args, devices, t_start: float) -> int:
    """Everything after the look for a chip: set-up, window, check,
    metrics and the result line, on ``devices``."""
    spec = harness.Spec(root)
    run = harness.Run(spec, args.workload, args.seed, args.seconds,
                      bool(args.trace), devices, t_start)
    src = root / "src"
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))
    import repro  # noqa: F401  (the system under test must be present)

    device = harness.device_record(run.devices)
    print(json.dumps({"device": device, "workload": run.workload,
                      "seed": run.seed}), flush=True)
    driver = spec.driver(run.config["driver"])
    readers = {m["name"]: spec.reader(m["name"])
               for m in spec.metrics_for(run.workload, run.traced)}

    with run.clock:
        state = driver.setup(run)
        before = run.clock.compiles
        t0 = time.perf_counter()
        run.setup_s = t0 - run.t_start
        driver.window(run, state)
        if run.window is None:
            run.window = (t0, time.perf_counter())
        run.window_compiles = run.clock.compiles - before
        if run.traced:
            # the window runs as in an untraced run; the profiler records
            # a slice of the same traffic after it
            tdir = root / ".chipbench" / "trace" / f"{run.workload}-{run.seed}"
            shutil.rmtree(tdir, ignore_errors=True)
            with trace.profile(tdir):
                driver.traced_slice(run, state)

    run.memory_peak_bytes = max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
        for d in run.devices)
    if run.traced:
        run.trace = trace.reduce(tdir, run)
        shutil.rmtree(tdir, ignore_errors=True)
        if run.trace.dropped:
            print("chipbench: the profiler dropped events; the trace is "
                  "incomplete", file=sys.stderr, flush=True)

    attempted, failed = driver.counts(run, state)
    state = driver.release(run, state)
    checks = driver.check(run, state)
    correct = harness.judge(checks)

    metrics = {}
    for m in spec.metrics_for(run.workload, run.traced):
        value = readers[m["name"]].read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    if run.traced:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
    device["memory_peak_bytes"] = int(run.memory_peak_bytes)
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if run.traced:
        result["breakdown"] = run.trace.breakdown()
    harness.emit(result, checks)
    return 0


def main(argv=None) -> int:
    args = parse(argv)
    spec = harness.Spec(ROOT)
    cell = spec.workload(args.workload)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    if args.trace:
        # a configuration may trace its programs at module level only:
        # the simulator's loop would otherwise fill the profiler's buffer
        extra = spec.config(cell["config"]).get("traced_run", {}).get(
            "libtpu_init_args", [])
        os.environ["LIBTPU_INIT_ARGS"] = " ".join(
            [os.environ.get("LIBTPU_INIT_ARGS", "")] + extra).strip()
    import jax
    devices = jax.devices()
    chips = cell["chips"]
    if devices[0].platform != "tpu":
        print(f"chipbench: needs a TPU; JAX found {devices[0].platform}",
              file=sys.stderr)
        return 2
    if len(devices) < chips:
        print(f"chipbench: {args.workload} needs {chips} chips; JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 2
    harness.enable_compile_cache(ROOT)
    return run_cell(ROOT, args, devices[:chips], T_START)


if __name__ == "__main__":
    sys.exit(main())
