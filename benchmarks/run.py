"""Benchmark harness: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV. Usage:
  PYTHONPATH=src python -m benchmarks.run [--fast] [--engine] [--dse] \
      [--serve] [--compiler]

Section flags are dispatched through the scenario registry
(``repro.registry`` SECTIONS axis): each registered ``BenchSection``
carries its CLI flag and a ``module:function`` runner spec, and the CI
smoke/nightly matrices are generated from the same axis by
``python -m repro.registry --ci-matrix {smoke,nightly}``.
``--fast`` skips the O(n^2) cycle simulations (xcorr/parallel_sel) and
shrinks the engine/DSE grids.
``--engine`` runs only the simulator-engine micro-benchmarks (fused
dispatch, batched launch queue, memory-system DSE sweep, unified DSE
search) and writes the ``BENCH_dse.json`` artifact.
``--dse`` runs only the unified DSE Pareto sweep + artifact
(``--dse --fast`` is the 2-point CI smoke).
``--serve`` runs the serving-subsystem benchmark — throughput,
mesh-sharded scheduler vs single-device, open-loop Poisson tail latency,
fleet routing, and device-resident kernel graphs — and writes the
``BENCH_serve.json`` artifact (schema ``ggpu-serve/4``; ``--serve
--fast`` is the CI ``serve-smoke`` job, and the ``fleet-smoke`` job runs
it again under ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` to
exercise real 8-way sharding).
``--graph`` runs only the kernel-graph section (device-resident
pipelined vs host-staged chain execution, the CI ``graph-smoke`` job)
and writes the partial ``BENCH_graph.json`` artifact that ``check_bench
--section graph`` gates against the full serve baseline.
``--compiler`` runs the tensor-DSL compiler sweep (suite parity vs the
hand-written benches + a compiled-workload DSE search) and writes
``BENCH_compiler.json`` (the nightly ``compiler-sweep`` job).

Smoke invariants (fleet routing must beat both pins, the executor cache
must be hitting, sharded results must be bit-exact — and >= 1.5x faster
at >= 8 simulated devices — DSE frontiers must be non-empty, compiled
kernels must be bit-exact) are re-checked after each artifact-producing
mode; any
violation exits non-zero so CI fails instead of uploading a broken
artifact.
"""
from __future__ import annotations

import sys
from typing import List


def _fail(problems: List[str]) -> None:
    if problems:
        for p in problems:
            print(f"INVARIANT FAILED: {p}", file=sys.stderr)
        sys.exit(1)


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    fast = "--fast" in sys.argv

    def emit(name, us, derived=""):
        print(f"{name},{us:.3f},{derived}")

    print("name,us_per_call,derived")
    # flag-bearing sections dispatch through the scenario registry: each
    # BenchSection names its runner as a "module:function" spec, so a
    # section added in one file is reachable here with no edit
    from repro.registry import SECTIONS
    from repro.registry.core import resolve
    for name in SECTIONS.names():
        sec = SECTIONS.get(name)
        if sec.flag and sec.flag in sys.argv:
            _fail(resolve(sec.runner)(emit, fast=fast))
            return
    from benchmarks import ggpu_tables, roofline_table
    ggpu_tables.table1_ppa(emit)
    ggpu_tables.table2_wires(emit)
    if not fast:
        ggpu_tables.simulate_all(verbose=False)
    if fast:
        # shrink the quadratic kernels for a quick pass
        from repro.ggpu import programs
        b = programs.all_benches()
        small = programs._xcorr(64, 512)
        b["xcorr"] = small
        ggpu_tables._cycle_cache.clear()
    ggpu_tables.table3_cycles(emit)
    ggpu_tables.fig5_speedup(emit)
    ggpu_tables.fig6_area_derated(emit)
    # the memsys sweep simulates the quadratic xcorr: shrink it under --fast
    ggpu_tables.table_memsys(emit, sizes=(32, 256) if fast else (64, 1024))
    import benchmarks.roofline_table as rt
    rt.DRYRUN_DIR = __import__("pathlib").Path("experiments/dryrun")
    emit("roofline/baseline", 0.0, "paper-faithful baseline sweep")
    roofline_table.roofline_table(emit)
    roofline_table.summary(emit)
    rt.DRYRUN_DIR = __import__("pathlib").Path("experiments/dryrun_opt")
    emit("roofline/optimized", 0.0,
         "optimized sweep (EXPERIMENTS.md §Perf)")
    roofline_table.roofline_table(emit)
    roofline_table.summary(emit)


if __name__ == "__main__":
    main()
