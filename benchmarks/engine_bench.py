"""Simulator engine micro-benchmark (``python -m benchmarks.run --engine``).

Measures the execution-engine refactor itself, not the simulated machine:

  * fused dispatch   — wall-clock of the inner-loop-heavy xcorr bench under
    the seed-faithful legacy reference stepper (``legacy=True``: one round
    per iteration, memory pipeline every round, one-hot scatter cache
    accounting) vs fused dispatch (``fuse=8``); cycles are bit-identical,
    only simulator speed changes.
  * batched launches — N same-kernel launches sequentially vs one
    ``LaunchQueue`` flush (cohort-folded into a single stepper call).
  * async launches   — N single launches serially (each ``run_kernel``
    blocks on its own download) vs N one-launch ``launch`` dispatches
    resolved after the last one is in flight; the cold trace (first
    call, pays the jit compile) is reported separately from both
    steady-state rates.
  * memsys sweep     — the cache-organization DSE on the bench the paper
    flags as cache-thrashing (xcorr at 8 CUs).
  * dse sweep        — the unified analytic+cycle-accurate Pareto search
    (``repro.dse``); writes the standardized ``BENCH_dse.json`` artifact.

Warm timings exclude compilation (each variant runs once to compile).
"""
from __future__ import annotations

import time

import numpy as np


def _time(fn, reps: int = 1):
    """Warm (compile) then time; returns (seconds_per_rep, last_result)."""
    fn()                                    # compile + warm caches
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn()
    return (time.perf_counter() - t0) / reps, out


def bench_fused_dispatch(emit, n_gpu: int = 1024, n_cus: int = 2) -> float:
    from repro.ggpu import programs
    from repro.ggpu.engine import GGPUConfig, run_kernel

    b = programs._xcorr(64, n_gpu)
    # the legacy point runs the seed-faithful reference stepper: one round
    # per iteration, memory pipeline engaged every round, one-hot scatter
    # cache accounting, dense writeback, full unpruned datapath
    variants = [
        ("legacy", GGPUConfig(n_cus=n_cus, fuse=1), True),
        ("fused_fuse8", GGPUConfig(n_cus=n_cus, fuse=8), False),
    ]
    times, cycles = {}, {}
    for name, cfg, legacy in variants:
        times[name], (_, info) = _time(
            lambda cfg=cfg, legacy=legacy: run_kernel(
                b.gpu_prog, b.gpu_mem, b.gpu_items, cfg, legacy=legacy))
        cycles[name] = info["cycles"]
        emit(f"engine/xcorr{n_gpu}/{name}", times[name] * 1e6,
             f"cycles={info['cycles']} steps={info['steps']}")
    speedup = times["legacy"] / times["fused_fuse8"]
    assert cycles["legacy"] == cycles["fused_fuse8"], \
        "fused dispatch changed the cycle count"
    emit(f"engine/xcorr{n_gpu}/fused_speedup", 0.0,
         f"speedup={speedup:.2f}x (target >=2x) bit_exact_cycles=True")
    return speedup


def bench_batched_launch(emit, n_launches: int = 8, n: int = 512) -> float:
    from repro.ggpu import programs
    from repro.ggpu.engine import ScalarConfig, run_kernel
    from repro.serve import LaunchQueue

    # same-kernel launch burst over distinct memory images: the RISC-V
    # baseline div_int program (tiny 1-lane machine, thousands of rounds —
    # the case where folding launches into one stepper amortizes the most;
    # this is exactly the serial workload the Table III harness runs)
    cfg = ScalarConfig()
    b = programs._div_int(n, 2 * n)
    rng = np.random.default_rng(7)
    mems = [np.concatenate([rng.integers(-1000, 1000, n).astype(np.int32),
                            rng.integers(1, 50, n).astype(np.int32),
                            np.zeros(n, np.int32)])
            for _ in range(n_launches)]

    def sequential():
        return [run_kernel(b.scalar_prog, m, 1, cfg) for m in mems]

    def batched():
        q = LaunchQueue(cfg)
        for m in mems:
            q.submit(b.scalar_prog, m, 1)
        return q.flush()

    t_seq, seq_out = _time(sequential)
    t_bat, bat_out = _time(batched)
    exact = all(np.array_equal(ms, mb) and is_["cycles"] == ib["cycles"]
                for (ms, is_), (mb, ib) in zip(seq_out, bat_out))
    emit(f"engine/batch{n_launches}x_div_int{n}/sequential", t_seq * 1e6, "")
    emit(f"engine/batch{n_launches}x_div_int{n}/launch_queue", t_bat * 1e6,
         f"speedup={t_seq / t_bat:.2f}x bit_exact={exact}")
    return t_seq / t_bat


def bench_async_launch(emit, n_launches: int = 16, n: int = 512) -> float:
    """Sync-vs-async single-launch streams at the engine level: the async
    path dispatches every launch before resolving any, so staging and
    download of launch k+1 overlap launch k's device compute. Results are
    asserted bit-exact; returns the steady-state speedup."""
    from repro.ggpu import programs
    from repro.ggpu.engine import GGPUConfig, launch, run_kernel

    cfg = GGPUConfig(n_cus=2)
    b = programs._vec_mul(32, n)
    rng = np.random.default_rng(3)
    nm = b.gpu_mem.shape[0]
    mems = [np.concatenate([rng.integers(-100, 100,
                                         2 * b.gpu_n).astype(np.int32),
                            np.zeros(nm - 2 * b.gpu_n, np.int32)])
            for _ in range(n_launches)]

    t0 = time.perf_counter()
    run_kernel(b.gpu_prog, mems[0], b.gpu_items, cfg)   # cold: jit compile
    cold_s = time.perf_counter() - t0
    emit(f"engine/async{n_launches}x_vec_mul{n}/cold_trace", cold_s * 1e6,
         "first launch incl. jit compile")

    def sync():
        return [run_kernel(b.gpu_prog, m, b.gpu_items, cfg) for m in mems]

    def asy():
        handles = [launch([b.gpu_prog], [m], [b.gpu_items], cfg)
                   for m in mems]
        return [h.result() for h in handles]

    t_sync, sync_out = _time(sync, reps=3)
    t_async, async_out = _time(asy, reps=3)
    exact = all(np.array_equal(ms, ma) and is_["cycles"] == ia["cycles"]
                for (ms, is_), (ma, ia) in zip(sync_out, async_out))
    assert exact, "async launch path diverged from sync results"
    emit(f"engine/async{n_launches}x_vec_mul{n}/sync", t_sync * 1e6,
         f"launches_per_sec={n_launches / t_sync:.0f}")
    emit(f"engine/async{n_launches}x_vec_mul{n}/async", t_async * 1e6,
         f"launches_per_sec={n_launches / t_async:.0f} "
         f"speedup={t_sync / t_async:.2f}x bit_exact={exact}")
    return t_sync / t_async


def bench_memsys_sweep(emit, sizes=(64, 1024)) -> None:
    from repro.dse import sweep_memsys

    sweep = sweep_memsys(bench="xcorr", n_cus=(1, 8), sizes=sizes)
    for (c, ms), info in sweep.items():
        emit(f"engine/memsys/{ms}/{c}cu", info["time_us"],
             f"cycles={info['cycles']} hits={info['hits']} "
             f"misses={info['misses']}")


def bench_dse(emit, fast: bool = False, out: str = None):
    """The unified DSE sweep: plan + cycle-evaluate a design grid, emit the
    Pareto frontier, and write the standardized ``BENCH_dse.json`` artifact
    (path overridable via ``GGPU_DSE_OUT``). ``fast`` runs the 2-point
    smoke grid CI uses. Returns (artifact dict, problems list) so
    ``benchmarks.run`` can fail the build on a broken sweep."""
    import os

    from repro import dse

    out = out or os.environ.get("GGPU_DSE_OUT", "BENCH_dse.json")
    if fast:
        specs = dse.enumerate_specs(cus=(1,),
                                    freq_targets=(500.0, 667.0))
        ev = dse.Evaluator(benches=("xcorr",), sizes={"xcorr": (16, 128)})
    else:
        specs = dse.enumerate_specs(
            cus=(1, 2, 4, 8), freq_targets=(500.0, 590.0, 667.0, 750.0),
            memsys=("shared", "banked", "banked-iso"))
        ev = dse.Evaluator(benches=("xcorr",), sizes={"xcorr": (64, 1024)})
    res = dse.search(specs=specs, evaluator=ev)
    for row in res.report():
        emit(f"dse/point/{row['label']}", row["time_us"],
             f"area={row['area_mm2']:.2f} "
             f"analytic_us={row['analytic_time_us']:.1f} "
             f"frontier={row['on_frontier']}")
    emit("dse/frontier", 0.0,
         " ".join(p.label() for p in res.frontier))
    emit("dse/excluded_analytic", 0.0,
         " ".join(p.label() for p in res.excluded_analytic) or "-")
    problems = []
    if not res.frontier:
        problems.append("DSE Pareto frontier is empty")
    reference = min(res.frontier or res.points, key=lambda p: p.time_us)
    path = dse.write_artifact(out, reference, res)
    emit("dse/artifact", 0.0, f"wrote {path} reference={reference.label()}")
    return dse.dse_artifact(reference, res), problems


def main(emit, fast: bool = False) -> None:
    if fast:
        bench_fused_dispatch(emit, n_gpu=256)
        bench_batched_launch(emit, n_launches=4, n=128)
        bench_async_launch(emit, n_launches=8)
        bench_memsys_sweep(emit, sizes=(32, 256))
    else:
        bench_fused_dispatch(emit)
        bench_batched_launch(emit)
        bench_async_launch(emit)
        bench_memsys_sweep(emit)
    bench_dse(emit, fast=fast)


def run_dse_section(emit, fast: bool = False) -> list:
    """Registry section runner (``repro.registry`` SECTIONS ``dse``)."""
    _art, problems = bench_dse(emit, fast=fast)
    return problems


def run_engine_section(emit, fast: bool = False) -> list:
    """Registry section runner (``engine``): micro-benches, no gate."""
    main(emit, fast=fast)
    return []
