"""Serving-subsystem benchmark (``python -m benchmarks.run --serve``).

Five sections, all recorded in the standardized ``BENCH_serve.json``
artifact (schema ``ggpu-serve/4``, path overridable via
``GGPU_SERVE_OUT``):

  * **throughput** — a bursty same-kernel trace served through the
    continuous-batching ``Scheduler`` (submit interleaved with
    incremental drains), measured twice over identical traffic: a **sync
    serial** drain (``max_inflight=1``: every chunk is collected before
    the next is staged — the pre-async behavior) and the **pipelined
    async** drain (chunks dispatched ahead of collection). The cold
    trace (first drain, which pays the jit compile) is reported
    separately from the steady-state rates; ``async_speedup`` is the
    steady-state ratio and must stay >= ``ASYNC_MIN_SPEEDUP`` (a smoke
    invariant ``check_bench`` also enforces — on hosts with >= 2 CPUs
    only: with a single core there is no second core to overlap onto,
    so the artifact records the ratio and ``host_cpus`` but the gate is
    report-only). Batch occupancy (launches
    per compiled-stepper dispatch) and the executor trace-cache hit rate
    are measured on the async scheduler — repeat traffic must not
    re-trace.
  * **sharded** — the same bursty trace served through a data-parallel
    scheduler whose chunks shard their launch axis over every JAX device
    (``mesh=make_launch_mesh()``; CPU CI simulates 8 devices with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8``), compared
    against the single-device async scheduler over identical traffic.
    The sharded scheduler plans ``max_batch * shards``-wide chunks, so
    one dispatch covers what the single-device path pipelines as
    ``shards`` dispatches. Results are checked bit-exact against direct
    ``run_kernel``; at >= 8 devices ``speedup`` must clear
    ``SHARDED_MIN_SPEEDUP`` (enforced by the invariants below).
  * **latency** — open-loop tail latency: a Poisson arrival trace
    (``repro.serve.loadgen``, deterministic seed) offered at a fixed
    fraction of the measured async capacity, replayed against the
    sharded scheduler; reports p50/p99/mean launch latency and the
    sustained rate.
  * **fleet** — the routing demo connecting the DSE output to the serving
    path: a mixed wide+narrow trace is served across two configs picked
    from a ``repro.dse.search`` Pareto front (every device dispatched
    before any is collected), and the routed fleet's modeled makespan is
    compared against pinning the whole trace to either single config.
  * **graph** — device-resident kernel graphs: N instances of a 3-stage
    map→reduce→scale chain (split out of one traced expression by
    ``repro.compiler.compile_graph``) served three ways. **pipelined**
    submits every stage up front with dependency edges and drains once —
    the dependency-aware scheduler folds each stage across instances
    into one cohort dispatch and feeds producers into consumers entirely
    on the device (one all-launch ``Patch``), zero host round-trips between
    stages. **host_staged** is the gated baseline: the pre-graph DAG
    idiom, each chain executed stage-by-stage with a full
    ``LaunchHandle`` download and host re-staging per edge (without
    dependency edges the per-chain barrier structure also hides
    cross-chain folding from the scheduler). **host_folded** is reported
    for calibration: the strongest manual workaround, stage-major
    submission with one drain barrier + download + re-stage per stage —
    it recovers cohort folding, so the residual vs pipelined isolates
    the pure round-trip/overlap cost (parity on a single-core host
    where simulator compute dominates). ``speedup`` (pipelined vs
    host_staged) must clear ``GRAPH_MIN_SPEEDUP``, the pipelined run
    must execute in at most one dispatch per stage, and all three paths
    must be bit-exact against the ``Program`` oracle — all enforced by
    the invariants below and by ``check_bench``.

``--fast`` shrinks the traces and the DSE grid (the CI ``serve-smoke``,
``fleet-smoke``, and ``graph-smoke`` jobs; ``benchmarks.run --graph``
runs the graph section alone and writes a partial ``BENCH_graph.json``
that ``check_bench --section graph`` gates against the full baseline).
"""
from __future__ import annotations

import json
import os
import time

import numpy as np

SCHEMA = "ggpu-serve/4"
# pipelined async drain must beat the sync serial drain by this factor
ASYNC_MIN_SPEEDUP = 1.5
# device-resident pipelined graph execution must beat the host-staged
# per-chain baseline by this factor (the win is structural — folding plus
# zero host round-trips — so it holds even on a single-core host)
GRAPH_MIN_SPEEDUP = 1.5
# sharded scheduler must beat the single-device async scheduler by this
# factor when >= this many devices are simulated (dispatch amortization
# alone clears it on one core; real parallel hardware adds more)
SHARDED_MIN_SPEEDUP = 1.5
SHARDED_MIN_DEVICES = 8
# offered Poisson load as a fraction of measured async capacity
LATENCY_LOAD_FRACTION = 0.6


def _bursty_mems(b, k, rng):
    """k fresh memory images for bench ``b`` (same envelope, new data)."""
    n = b.gpu_mem.shape[0]
    return [np.concatenate([rng.integers(-100, 100,
                                         2 * b.gpu_n).astype(np.int32),
                            np.zeros(n - 2 * b.gpu_n, np.int32)])
            for _ in range(k)]


def bench_throughput(emit, fast: bool) -> dict:
    from repro.ggpu import programs
    from repro.ggpu.engine import GGPUConfig
    from repro.serve import Scheduler

    cfg = GGPUConfig(n_cus=2)
    b = programs._vec_mul(32, 512)
    burst, max_batch = 16, 2                 # 8 same-kernel chunks per drain
    n_bursts = 3 if fast else 8
    reps = 3                                 # steady state: best of reps
    rng = np.random.default_rng(0)

    def steady(sched):
        """Best-of-``reps`` steady-state launches/sec over identical
        traffic: bursts of submissions interleaved with drains."""
        best, served = 0.0, 0
        for _ in range(reps):
            t0 = time.perf_counter()
            served = 0
            for _ in range(n_bursts):
                for m in _bursty_mems(b, burst, rng):
                    sched.submit(b.gpu_prog, m, b.gpu_items)
                served += len(sched.drain())
            best = max(best, served / (time.perf_counter() - t0))
        return best, served

    # sync serial reference: every chunk collected before the next one is
    # staged (the pre-async launch path). Its first drain pays the jit
    # compile for the chunk envelopes — the cold trace, reported apart
    # from every steady-state number.
    sync_sched = Scheduler(cfg, max_batch=max_batch, max_inflight=1)
    for m in _bursty_mems(b, burst, rng):
        sync_sched.submit(b.gpu_prog, m, b.gpu_items)
    t0 = time.perf_counter()
    sync_sched.drain()
    cold_trace_s = time.perf_counter() - t0
    sync_rate, served = steady(sync_sched)

    # pipelined async drain over the same traffic shape
    async_sched = Scheduler(cfg, max_batch=max_batch, max_inflight=8)
    for m in _bursty_mems(b, burst, rng):
        async_sched.submit(b.gpu_prog, m, b.gpu_items)
    async_sched.drain()                      # own envelope-cache warm-up
    st = async_sched.executor.stats
    l0, d0 = st.launches, st.dispatches
    h0, m0 = st.trace_hits, st.trace_misses
    async_rate, _ = steady(async_sched)
    hits = st.trace_hits - h0
    misses = st.trace_misses - m0
    speedup = async_rate / sync_rate
    row = {
        "device": f"{cfg.n_cus}cu/{cfg.memsys}",
        "kernel": b.name,
        "burst": burst,
        "max_batch": max_batch,
        "launches": served,
        "cold_trace_s": round(cold_trace_s, 4),
        "sync": {"launches_per_sec": round(sync_rate, 2),
                 "wall_s": round(served / sync_rate, 4)},
        "async": {"launches_per_sec": round(async_rate, 2),
                  "wall_s": round(served / async_rate, 4),
                  "max_inflight": 8},
        "async_speedup": round(speedup, 3),
        "launches_per_sec": round(async_rate, 2),
        "batch_occupancy": round((st.launches - l0)
                                 / (st.dispatches - d0), 3),
        "executor_cache": {"hits": hits, "misses": misses,
                           "hit_rate": round(hits / (hits + misses), 3)
                           if hits + misses else 0.0},
    }
    emit("serve/throughput/cold_trace", cold_trace_s * 1e6,
         "first drain incl. jit compile")
    emit("serve/throughput/sync", 1e6 / sync_rate,
         f"launches_per_sec={row['sync']['launches_per_sec']} "
         "(serial drain, max_inflight=1)")
    emit("serve/throughput/async", 1e6 / async_rate,
         f"launches_per_sec={row['async']['launches_per_sec']} "
         f"speedup={row['async_speedup']}x "
         f"occupancy={row['batch_occupancy']} "
         f"cache_hit_rate={row['executor_cache']['hit_rate']}")
    return row


def bench_sharded(emit, fast: bool) -> dict:
    """Sharded vs single-device scheduler over identical bursty traffic,
    plus a bit-exactness audit of the sharded results."""
    import jax

    from repro.ggpu import programs
    from repro.ggpu.engine import GGPUConfig, run_kernel
    from repro.launch.mesh import make_launch_mesh
    from repro.serve import Scheduler

    cfg = GGPUConfig(n_cus=2)
    # the smallest suite kernel at a high burst: the dispatch-bound regime
    # sharding targets. One sharded dispatch plans max_batch*shards
    # launches, replacing `shards` pipelined dispatches — on a single host
    # core the win is pure dispatch amortization (~1.6x at 8 shards);
    # real parallel devices add compute concurrency on top.
    b = programs._vec_mul(16, 64)
    burst, max_batch = 32, 2
    n_bursts = 3 if fast else 8
    reps = 3
    rng = np.random.default_rng(2)
    mesh = make_launch_mesh()
    n_devices = jax.device_count()

    def steady(sched):
        best, served = 0.0, 0
        for _ in range(reps):
            t0 = time.perf_counter()
            served = 0
            for _ in range(n_bursts):
                for m in _bursty_mems(b, burst, rng):
                    sched.submit(b.gpu_prog, m, b.gpu_items)
                served += len(sched.drain())
            best = max(best, served / (time.perf_counter() - t0))
        return best, served

    def warm(sched):
        for m in _bursty_mems(b, burst, rng):
            sched.submit(b.gpu_prog, m, b.gpu_items)
        sched.drain()

    single = Scheduler(cfg, max_batch=max_batch, max_inflight=8)
    warm(single)
    single_rate, served = steady(single)

    sharded = Scheduler(cfg, max_batch=max_batch, max_inflight=8, mesh=mesh)
    warm(sharded)
    sharded_rate, _ = steady(sharded)

    # bit-exactness: one burst through the sharded scheduler vs direct
    # single-launch execution of every member
    audit = _bursty_mems(b, burst, rng)
    tickets = [sharded.submit(b.gpu_prog, m, b.gpu_items) for m in audit]
    by_ticket = {r.info["ticket"]: r for r in sharded.drain()}
    bit_exact = True
    for tk, m in zip(tickets, audit):
        mem, info = run_kernel(b.gpu_prog, m, b.gpu_items, cfg)
        r = by_ticket[tk]
        if not (np.array_equal(r.mem, mem)
                and r.info["cycles"] == info["cycles"]):
            bit_exact = False

    speedup = sharded_rate / single_rate
    row = {
        "device": f"{cfg.n_cus}cu/{cfg.memsys}",
        "kernel": b.name,
        "burst": burst,
        "max_batch": max_batch,
        "n_devices": n_devices,
        "shards": sharded.executor.shards,
        "plan_batch": sharded.plan_batch,
        "launches": served,
        "single": {"launches_per_sec": round(single_rate, 2)},
        "sharded": {"launches_per_sec": round(sharded_rate, 2)},
        "speedup": round(speedup, 3),
        "bit_exact": bit_exact,
    }
    emit("serve/sharded", 1e6 / sharded_rate,
         f"launches_per_sec={row['sharded']['launches_per_sec']} "
         f"speedup={row['speedup']}x over single-device "
         f"(shards={row['shards']}, n_devices={n_devices}, "
         f"bit_exact={bit_exact})")
    return row


def bench_latency(emit, fast: bool, capacity_per_s: float) -> dict:
    """Open-loop Poisson tail latency at a fixed fraction of measured
    capacity, against the sharded scheduler (falls back to single-device
    with one JAX device)."""
    from repro.ggpu import programs
    from repro.ggpu.engine import GGPUConfig
    from repro.launch.mesh import make_launch_mesh
    from repro.serve import Request, Scheduler, poisson_arrivals, replay

    cfg = GGPUConfig(n_cus=2)
    b = programs._vec_mul(32, 512)
    rng = np.random.default_rng(3)
    n = 48 if fast else 200
    rate = LATENCY_LOAD_FRACTION * capacity_per_s
    arrivals = poisson_arrivals(rate, n, seed=42)
    mems = _bursty_mems(b, 32, rng)

    sched = Scheduler(cfg, max_batch=2, max_inflight=8,
                      mesh=make_launch_mesh())
    # warm every chunk envelope open-loop traffic can produce: cohort
    # sizes are bucketed to powers of two (engine ``cohort_rows``), so
    # draining bursts of plan_batch, plan_batch/2, ... 2, and 1 covers
    # them all — the replay itself then never pays a jit compile
    k = sched.plan_batch
    while k >= 1:
        for m in _bursty_mems(b, k, rng):
            sched.submit(b.gpu_prog, m, b.gpu_items)
        sched.drain()
        k //= 2

    res = replay(sched, arrivals,
                 lambda i: Request(b.gpu_prog, mems[i % len(mems)],
                                   b.gpu_items))
    row = {
        "arrivals": "poisson",
        "seed": 42,
        "n": n,
        "offered_rate_per_s": round(rate, 2),
        "load_fraction": LATENCY_LOAD_FRACTION,
        "shards": sched.executor.shards,
        **res.report(),
    }
    emit("serve/latency/p50", row["p50_ms"] * 1e3,
         f"open-loop poisson @ {row['offered_rate_per_s']}/s "
         f"({LATENCY_LOAD_FRACTION:.0%} of capacity), n={n}")
    emit("serve/latency/p99", row["p99_ms"] * 1e3,
         f"p50={row['p50_ms']}ms mean={row['mean_ms']}ms "
         f"sustained={row['rate_per_s']}/s served={row['served']}")
    return row


def bench_fleet(emit, fast: bool) -> dict:
    from repro import dse
    from repro.ggpu import programs
    from repro.serve import Fleet, pinned_makespan

    # DSE-selected devices: the (fastest, smallest) ends of a Pareto front
    if fast:
        specs = dse.enumerate_specs(cus=(1, 8), freq_targets=(667.0,))
        ev = dse.Evaluator(benches=("xcorr",), sizes={"xcorr": (16, 128)})
    else:
        specs = dse.enumerate_specs(cus=(1, 2, 4, 8),
                                    freq_targets=(500.0, 667.0))
        ev = dse.Evaluator(benches=("xcorr",), sizes={"xcorr": (32, 256)})
    res = dse.search(specs=specs, evaluator=ev)
    frontier = sorted(res.frontier, key=lambda p: p.time_us)
    picks = [frontier[0], frontier[-1]]
    if picks[0] is picks[1]:
        raise RuntimeError("DSE frontier collapsed to one design: nothing "
                           "to route across — widen the spec grid")
    devices = [(p.label(), p.point.config) for p in picks]

    wide = programs._copy(16, 1024 if fast else 4096)      # many wavefronts
    narrow = programs._reduction(64, 256 if fast else 1024)  # W=1
    rng = np.random.default_rng(1)
    trace = []
    for _ in range(3 if fast else 8):
        trace.append((wide.gpu_prog, _bursty_mems(wide, 1, rng)[0],
                      wide.gpu_items))
        trace.append((narrow.gpu_prog, _bursty_mems(narrow, 1, rng)[0],
                      narrow.gpu_items))

    fleet = Fleet(devices)
    for prog, mem0, n_items in trace:
        fleet.submit(prog, mem0, n_items)
    fleet.drain()
    rep = fleet.report()
    pinned = {name: round(pinned_makespan(cfg, trace), 3)
              for name, cfg in devices}
    best_pin = min(pinned.values())
    rep.update({
        "pinned_us": pinned,
        "speedup_vs_best_pin": round(best_pin / rep["makespan_us"], 3),
        "beats_both_pins": rep["makespan_us"] < best_pin,
    })
    emit("serve/fleet/makespan", rep["makespan_us"],
         f"devices={'+'.join(rep['devices'])} "
         f"placement={rep['placement']} "
         f"pinned_us={pinned} speedup={rep['speedup_vs_best_pin']}x")
    return rep


def bench_graph(emit, fast: bool) -> dict:
    """Device-resident pipelined kernel-graph execution vs the
    host-staged baselines (module doc) on a 3-stage map→reduce→scale
    chain, bit-exact against the ``Program`` oracle."""
    from repro.compiler import compile_graph
    from repro.ggpu.engine import GGPUConfig
    from repro.serve import (Scheduler, extract_outputs,
                             run_chains_host_staged,
                             run_programs_host_staged, submit_programs)

    cfg = GGPUConfig(n_cus=2)
    n, seg = 256, 64
    n_inst = 8 if fast else 16
    reps = 3
    program = compile_graph(
        lambda a, b: (a * b).seg_sum(seg) * 3 + 1,
        {"a": n, "b": n}, name="map_reduce_scale")
    rng = np.random.default_rng(7)

    def instances():
        return [{"a": rng.integers(-100, 100, n).astype(np.int32),
                 "b": rng.integers(-100, 100, n).astype(np.int32)}
                for _ in range(n_inst)]

    # one scheduler per path, same config (shared executor/envelope
    # cache); max_batch = n_inst so each stage folds into one cohort
    pipe = Scheduler(cfg, max_batch=n_inst, max_inflight=8)
    staged = Scheduler(cfg, max_batch=n_inst, max_inflight=8)
    folded = Scheduler(cfg, max_batch=n_inst, max_inflight=8)

    # warm every path's chunk envelopes so steady state never re-traces
    submit_programs(pipe, program, instances())
    pipe.drain()
    run_chains_host_staged(staged, program, instances())
    run_programs_host_staged(folded, program, instances())

    best_pipe = best_staged = best_folded = float("inf")
    outs = staged_outs = None
    dispatches = 0
    for _ in range(reps):
        ins = instances()
        st = pipe.executor.stats
        d0 = st.dispatches
        t0 = time.perf_counter()
        handles = submit_programs(pipe, program, ins)
        outs = extract_outputs(pipe.drain(), handles)
        best_pipe = min(best_pipe, time.perf_counter() - t0)
        dispatches = st.dispatches - d0
        t0 = time.perf_counter()
        staged_outs = run_chains_host_staged(staged, program, ins)
        best_staged = min(best_staged, time.perf_counter() - t0)
        t0 = time.perf_counter()
        folded_outs = run_programs_host_staged(folded, program, ins)
        best_folded = min(best_folded, time.perf_counter() - t0)
    # audit the last rep's results against the NumPy oracle: all three
    # execution paths must agree bit-for-bit
    refs = [program.reference(i) for i in ins]
    bit_exact = all(
        np.array_equal(o, r) and np.array_equal(s, r)
        and np.array_equal(f, r)
        for o, s, f, r in zip(outs, staged_outs, folded_outs, refs))

    speedup = best_staged / best_pipe
    row = {
        "device": f"{cfg.n_cus}cu/{cfg.memsys}",
        "program": program.name,
        "stages": [ck.name for ck in program.stages],
        "n": n,
        "seg": seg,
        "instances": n_inst,
        "launches": 3 * n_inst,
        "pipelined": {"wall_s": round(best_pipe, 4),
                      "chains_per_sec": round(n_inst / best_pipe, 2),
                      "dispatches": dispatches},
        "host_staged": {"wall_s": round(best_staged, 4),
                        "chains_per_sec": round(n_inst / best_staged, 2)},
        "host_folded": {"wall_s": round(best_folded, 4),
                        "chains_per_sec": round(n_inst / best_folded, 2)},
        "speedup": round(speedup, 3),
        "folded_speedup": round(best_folded / best_pipe, 3),
        "bit_exact": bit_exact,
    }
    emit("serve/graph/pipelined", best_pipe * 1e6 / n_inst,
         f"chains_per_sec={row['pipelined']['chains_per_sec']} "
         f"dispatches={dispatches} for {3 * n_inst} launches")
    emit("serve/graph/host_staged", best_staged * 1e6 / n_inst,
         f"speedup={row['speedup']}x pipelined over per-chain host "
         f"staging (folded_speedup={row['folded_speedup']}x, "
         f"bit_exact={bit_exact})")
    return row


def graph_invariant_problems(art: dict) -> list:
    """Absolute health invariants of the ``graph`` section (shared by the
    full-artifact check and the partial ``--graph`` smoke run)."""
    problems = []
    g = art.get("graph", {})
    if not g:
        return ["graph section missing from artifact"]
    if not g.get("bit_exact"):
        problems.append(
            "graph.bit_exact: device-resident pipelined results diverge "
            "from the Program oracle / independently-run stages")
    spd = g.get("speedup", 0)
    if spd < GRAPH_MIN_SPEEDUP:
        problems.append(
            f"graph.speedup {spd} < {GRAPH_MIN_SPEEDUP}: device-resident "
            "pipelined execution must beat the host-staged baseline")
    stages = len(g.get("stages", ())) or 3
    disp = g.get("pipelined", {}).get("dispatches", -1)
    if not 0 < disp <= stages:
        problems.append(
            f"graph.pipelined.dispatches {disp}: {g.get('instances')} "
            f"chains x {stages} stages must fold into at most "
            f"{stages} dispatches (one cohort per stage)")
    return problems


def invariant_problems(art: dict) -> list:
    """Smoke invariants a healthy serve run must satisfy — checked by
    ``benchmarks.run`` after the artifact is written so a broken result
    fails the build instead of uploading quietly."""
    problems = []
    fleet = art.get("fleet", {})
    if not fleet.get("beats_both_pins"):
        problems.append(
            "fleet.beats_both_pins: routing does not beat both pinned "
            f"configs (makespan={fleet.get('makespan_us')} "
            f"pinned={fleet.get('pinned_us')})")
    if art.get("cache_hit_rate", 0) <= 0:
        problems.append("cache_hit_rate: executor trace-cache hit rate "
                        "is 0 — repeat traffic is re-tracing")
    if art.get("batch_occupancy", 0) <= 1:
        problems.append(
            f"batch occupancy {art.get('batch_occupancy')} <= 1: the "
            "scheduler is not folding same-kernel launches")
    spd = art.get("async_speedup", 0)
    if art.get("n_devices", 1) == 1 and art.get("host_cpus", 2) >= 2 \
            and spd < ASYNC_MIN_SPEEDUP:
        # the async-vs-sync comparison measures host-pipelining overlap;
        # forcing multiple host devices (the fleet-smoke job) partitions
        # XLA's thread pool and perturbs exactly that overlap, so the
        # gate binds on the single-device job only — the multi-device
        # job is gated on the sharded speedup instead. Below 2 host CPUs
        # there is no second core to overlap onto, so the speedup is
        # recorded in the artifact but not gated (report-only)
        problems.append(
            f"async_speedup {spd} < {ASYNC_MIN_SPEEDUP}: the pipelined "
            "async drain must beat the sync serial drain")
    sharded = art.get("sharded", {})
    if not sharded.get("bit_exact"):
        problems.append("sharded.bit_exact: sharded scheduler results "
                        "diverge from direct run_kernel")
    if art.get("n_devices", 1) >= SHARDED_MIN_DEVICES \
            and sharded.get("speedup", 0) < SHARDED_MIN_SPEEDUP:
        problems.append(
            f"sharded.speedup {sharded.get('speedup')} < "
            f"{SHARDED_MIN_SPEEDUP} at {art.get('n_devices')} devices: "
            "the sharded scheduler must beat the single-device async one")
    lat = art.get("latency", {})
    if lat.get("served", 0) != lat.get("n", -1):
        problems.append(
            f"latency: served {lat.get('served')} != offered {lat.get('n')}"
            " — the open-loop replay dropped or quarantined requests")
    if not (0 < lat.get("p50_ms", 0) <= lat.get("p99_ms", 0)):
        problems.append(
            f"latency percentiles malformed: p50={lat.get('p50_ms')} "
            f"p99={lat.get('p99_ms')}")
    if fleet.get("quarantined"):
        problems.append(
            f"fleet quarantined launches: {fleet['quarantined']}")
    problems += graph_invariant_problems(art)
    return problems


def bench_serve(emit, fast: bool = False, out: str = None) -> dict:
    """Run all five sections and write the ``BENCH_serve.json`` artifact;
    returns the artifact dict."""
    import jax

    out = out or os.environ.get("GGPU_SERVE_OUT", "BENCH_serve.json")
    throughput = bench_throughput(emit, fast)
    sharded = bench_sharded(emit, fast)
    latency = bench_latency(emit, fast,
                            throughput["async"]["launches_per_sec"])
    fleet = bench_fleet(emit, fast)
    graph = bench_graph(emit, fast)
    art = {
        "schema": SCHEMA,
        "n_devices": jax.device_count(),
        "host_cpus": os.cpu_count(),
        "launches_per_sec": throughput["launches_per_sec"],
        "sync_launches_per_sec": throughput["sync"]["launches_per_sec"],
        "async_speedup": throughput["async_speedup"],
        "sharded_speedup": sharded["speedup"],
        "graph_speedup": graph["speedup"],
        "cold_trace_s": throughput["cold_trace_s"],
        "batch_occupancy": throughput["batch_occupancy"],
        "cache_hit_rate": throughput["executor_cache"]["hit_rate"],
        "throughput": throughput,
        "sharded": sharded,
        "latency": latency,
        "fleet": fleet,
        "graph": graph,
    }
    with open(out, "w") as f:
        json.dump(art, f, indent=2, sort_keys=True)
        f.write("\n")
    emit("serve/artifact", 0.0, f"wrote {out}")
    return art


def bench_graph_only(emit, fast: bool = False, out: str = None) -> dict:
    """Run just the graph section (the CI ``graph-smoke`` job) and write
    a partial ``BENCH_graph.json`` artifact — same schema tag plus a
    ``sections`` marker so ``check_bench --section graph`` knows it is
    gating a subset against the full committed baseline."""
    import jax

    out = out or os.environ.get("GGPU_GRAPH_OUT", "BENCH_graph.json")
    graph = bench_graph(emit, fast)
    art = {
        "schema": SCHEMA,
        "sections": ["graph"],
        "n_devices": jax.device_count(),
        "graph_speedup": graph["speedup"],
        "graph": graph,
    }
    with open(out, "w") as f:
        json.dump(art, f, indent=2, sort_keys=True)
        f.write("\n")
    emit("serve/artifact", 0.0, f"wrote {out}")
    return art


def run_serve_section(emit, fast: bool = False) -> list:
    """Registry section runner (``repro.registry`` SECTIONS ``serve`` and
    ``fleet``): run the serve suite, return invariant violations."""
    return invariant_problems(bench_serve(emit, fast=fast))


def run_graph_section(emit, fast: bool = False) -> list:
    """Registry section runner (``graph``): partial-artifact variant."""
    return graph_invariant_problems(bench_graph_only(emit, fast=fast))
