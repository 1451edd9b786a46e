"""Unified DSE subsystem: pipeline-latency feedback bit-exactness,
DesignPoint/inventory composition, Pareto-dominance properties, the joint
analytic+cycle-accurate search, and the BENCH_dse.json artifact schema.

The depth-0 bit-exactness matrix runs all seven paper benches; under
``GGPU_FAST_TESTS=1`` the machine axis is trimmed (the knob is gated on a
static config field, so one machine proves the graph is unchanged —
the full matrix is the paper-faithful check for the default tier-1 run)."""
import functools
import json
import os

import numpy as np
import pytest

from repro import dse
from repro.dse import (DesignSpec, Evaluator, design_point, dominates,
                       memsys_inventory, pareto_frontier)
from repro.ggpu import programs
from repro.ggpu.engine import GGPUConfig, ScalarConfig, run_kernel

FAST = os.environ.get("GGPU_FAST_TESTS", "0") not in ("", "0")

# 512-item GPU sizes: W=8 wavefronts, divisible by every CU count (the
# legacy reference stepper predates ragged-W rounding and needs W % n_cus
# == 0); mat_mul dim 32 -> 1024 items, W=16
BENCH_BUILDERS = {
    "copy": lambda: programs._copy(32, 512),
    "vec_mul": lambda: programs._vec_mul(32, 512),
    "mat_mul": lambda: programs._mat_mul(4, 32),
    "fir": lambda: programs._fir(32, 512),
    "div_int": lambda: programs._div_int(16, 512),
    "xcorr": lambda: programs._xcorr(16, 512),
    "parallel_sel": lambda: programs._parallel_sel(32, 512),
    # the PR-3 extension bench (seg=8 keeps gpu_items=512 -> W=8)
    "reduction": lambda: programs._reduction(64, 4096, seg=8),
}
MACHINES = ["scalar", 2] if FAST else ["scalar", 1, 2, 4, 8]


@functools.lru_cache(maxsize=None)
def _bench(name):
    return BENCH_BUILDERS[name]()


# ---------------------------------------------------------------------------
# pipeline-depth knob: bit-exact at depth 0, architectural above it
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("machine", MACHINES)
@pytest.mark.parametrize("name", sorted(BENCH_BUILDERS))
def test_depth0_bit_exact_vs_legacy(name, machine):
    """pipeline_depth=0 (the default) must be bit-exact with the seed
    engine — results, cycles, stats, steps — on every bench and machine.
    The legacy reference stepper IS the pre-knob engine."""
    b = _bench(name)
    if machine == "scalar":
        cfg = ScalarConfig()
        args = (b.scalar_prog, b.scalar_mem, 1)
    else:
        cfg = GGPUConfig(n_cus=machine)
        args = (b.gpu_prog, b.gpu_mem, b.gpu_items)
    assert cfg.pipeline_depth == 0
    mem_n, i_n = run_kernel(*args, cfg)
    mem_l, i_l = run_kernel(*args, cfg, legacy=True)
    np.testing.assert_array_equal(mem_n, mem_l)
    for k in ("cycles", "instrs", "mem_ops", "hits", "misses", "steps"):
        assert i_n[k] == i_l[k], k


def test_depth_increases_cpi_not_results():
    """Deeper pipelines cost cycles (dependency bubbles + branch refill)
    but never change functional results — the fmax-vs-CPI trade-off the
    analytic map cannot see."""
    b = _bench("xcorr")
    cycles = {}
    for d in (0, 1, 2):
        mem, info = run_kernel(b.gpu_prog, b.gpu_mem, b.gpu_items,
                               GGPUConfig(n_cus=2, pipeline_depth=d))
        np.testing.assert_array_equal(mem[b.gpu_out],
                                      b.ref(b.gpu_mem, b.gpu_n))
        cycles[d] = info["cycles"]
    assert cycles[0] < cycles[1] < cycles[2]


def test_depth_batching_invariants():
    """Cohort/batch launches charge the pipeline feedback identically to a
    single launch."""
    from repro.ggpu.engine import launch
    b = _bench("xcorr")
    cfg = GGPUConfig(n_cus=2, pipeline_depth=2)
    mem_s, i_s = run_kernel(b.gpu_prog, b.gpu_mem, b.gpu_items, cfg)
    padded = np.vstack([b.gpu_prog, np.zeros_like(b.gpu_prog[:1])])
    (mem_c, i_c), _ = launch([b.gpu_prog] * 2, [b.gpu_mem] * 2,
                             [b.gpu_items] * 2, cfg).results()
    (mem_b, i_b), _ = launch([b.gpu_prog, padded], [b.gpu_mem] * 2,
                             [b.gpu_items] * 2, cfg).results()
    np.testing.assert_array_equal(mem_s, mem_c)
    np.testing.assert_array_equal(mem_s, mem_b)
    assert i_s["cycles"] == i_c["cycles"] == i_b["cycles"]


def test_legacy_rejects_pipeline_depth():
    b = _bench("copy")
    with pytest.raises(ValueError):
        run_kernel(b.gpu_prog, b.gpu_mem, b.gpu_items,
                   GGPUConfig(pipeline_depth=1), legacy=True)


# ---------------------------------------------------------------------------
# DesignPoint composition
# ---------------------------------------------------------------------------

def test_design_point_closes_the_loop():
    """The engine config inherits the map's inserted pipeline stages and
    the achieved (possibly derated) frequency."""
    p = design_point(DesignSpec(n_cus=1, freq_target_mhz=667.0))
    assert p.plan.achieved
    assert p.config.pipeline_depth == p.version.pipelines > 0
    assert p.freq_mhz == 667.0
    # the paper's failure case derates to the interconnect-bound fmax
    p8 = design_point(DesignSpec(n_cus=8, freq_target_mhz=667.0))
    assert not p8.plan.achieved
    assert 580 <= p8.freq_mhz <= 620
    assert p8.config.freq_mhz == p8.version.freq_mhz


def test_design_point_depth_override():
    p = design_point(DesignSpec(n_cus=1, freq_target_mhz=667.0,
                                pipeline_depth=0))
    assert p.config.pipeline_depth == 0
    assert p.version.pipelines > 0          # the map still inserted stages


def test_memsys_inventory_area_coupling():
    """The analytic map prices the cache organization: full-size per-CU
    banks cost area, capacity-split banks stay near the shared point."""
    from repro.core.ppa import GGPUVersion
    areas = {}
    for ms in ("shared", "banked", "banked-iso"):
        v = GGPUVersion(8, 500.0, memsys_inventory(ms, 8))
        areas[ms] = v.total_area_mm2()
    assert areas["banked"] > areas["shared"]
    assert areas["shared"] < areas["banked-iso"] < areas["banked"]
    with pytest.raises(KeyError):
        memsys_inventory("l3-victim", 8)


# ---------------------------------------------------------------------------
# Pareto dominance
# ---------------------------------------------------------------------------

def test_dominates_properties():
    assert dominates((1, 1), (2, 1))
    assert dominates((1, 1), (2, 2))
    assert not dominates((1, 1), (1, 1))          # irreflexive on ties
    assert not dominates((1, 2), (2, 1))          # incomparable
    assert not dominates((2, 1), (1, 2))
    with pytest.raises(ValueError):
        dominates((1,), (1, 2))


def test_pareto_frontier_basic():
    pts = [(1, 5), (2, 2), (5, 1), (3, 3), (2, 2)]
    front = pareto_frontier(pts, key=lambda p: p)
    # (3,3) dominated by (2,2); equal points are both kept, order stable
    assert front == [(1, 5), (2, 2), (5, 1), (2, 2)]


def test_pareto_frontier_single_and_empty():
    assert pareto_frontier([], key=lambda p: p) == []
    assert pareto_frontier([(4, 2)], key=lambda p: p) == [(4, 2)]


# ---------------------------------------------------------------------------
# the joint search (the PR's acceptance shape: >= 24 points, non-empty
# frontier, analytic-only picks excluded by the cycle model)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=1)
def _search_result():
    specs = dse.enumerate_specs(cus=(1, 2, 4, 8),
                                freq_targets=(500.0, 667.0, 750.0),
                                memsys=("shared", "banked"))
    assert len(specs) >= 24
    ev = Evaluator(benches=("xcorr",), sizes={"xcorr": (16, 128)})
    return dse.search(specs=specs, evaluator=ev), ev


def test_search_frontier_excludes_analytic_pick():
    res, _ = _search_result()
    assert len(res.points) >= 24
    assert res.frontier                               # non-empty Pareto set
    assert res.excluded_analytic, \
        "the cycle model must reject some free-pipelining analytic pick"
    front_ids = {id(p) for p in res.frontier}
    for p in res.excluded_analytic:
        assert id(p) not in front_ids
        # excluded points really are dominated under cycle-accurate metrics
        assert any(dominates((q.time_us, q.area_mm2),
                             (p.time_us, p.area_mm2)) for q in res.points)
        # ...and they are the deep-pipeline high-frequency-target designs
        assert p.point.config.pipeline_depth > 0


def test_search_points_are_consistent():
    res, _ = _search_result()
    for p in res.points:
        assert p.time_us >= p.analytic_time_us > 0    # depth never helps CPI
        assert p.area_mm2 > 0 and p.power_w > 0
        assert p.energy_uj == pytest.approx(p.power_w * p.time_us)
        for m in p.per_bench.values():
            assert m.cycles >= m.analytic_cycles > 0


def test_evaluator_caches_configs():
    """Re-evaluating the same sweep must not simulate anything new, and
    config-sharing points (same depth from different freq targets) share
    cache entries (memoized on the shared serve executors)."""
    res, ev = _search_result()
    n_cached = ev.cache_size()
    ev.evaluate([p.point for p in res.points])
    assert ev.cache_size() == n_cached
    assert n_cached < 2 * len(res.points)     # folding actually happened


def test_evaluator_shares_executor_cycle_cache():
    """Two evaluators with identical bench content share the memo on the
    process-wide executor: the second evaluation dispatches nothing."""
    from repro.serve.executors import get_executor
    cfg = GGPUConfig(n_cus=2)
    ev1 = Evaluator(benches=("copy",), sizes={"copy": (16, 128)})
    info1, _ = ev1.cycles(cfg, "copy")
    dispatches = get_executor(cfg).stats.dispatches
    ev2 = Evaluator(benches=("copy",), sizes={"copy": (16, 128)})
    info2, _ = ev2.cycles(cfg, "copy")
    assert get_executor(cfg).stats.dispatches == dispatches
    assert info2["cycles"] == info1["cycles"]


def test_evaluator_check_reverifies_despite_shared_memo():
    """check=True must actually verify results even when an unchecked
    evaluator already memoized the bench on the shared executor (it
    re-simulates once, then trusts its own verification)."""
    from repro.serve.executors import get_executor
    cfg = GGPUConfig(n_cus=2)
    ev1 = Evaluator(benches=("vec_mul",), sizes={"vec_mul": (16, 128)})
    ev1.cycles(cfg, "vec_mul")
    d0 = get_executor(cfg).stats.dispatches
    ev2 = Evaluator(benches=("vec_mul",), sizes={"vec_mul": (16, 128)},
                    check=True)
    ev2.cycles(cfg, "vec_mul")
    assert get_executor(cfg).stats.dispatches == d0 + 1   # re-simulated
    ev2.cycles(cfg, "vec_mul")                            # now verified
    assert get_executor(cfg).stats.dispatches == d0 + 1


def test_artifact_schema(tmp_path):
    res, _ = _search_result()
    ref = min(res.frontier, key=lambda p: p.time_us)
    path = dse.write_artifact(tmp_path / "BENCH_dse.json", ref, res)
    art = json.loads(path.read_text())
    assert art["schema"] == "ggpu-dse/1"
    assert art["reference"] == ref.label()
    for bench, row in art["benches"].items():
        for key in ("cycles", "sim_wall_s", "fmax_mhz", "area_mm2",
                    "perf_per_area", "time_us"):
            assert key in row, (bench, key)
    assert set(art["frontier"]) == {p.label() for p in res.frontier}
    assert art["excluded_analytic"] == [p.label()
                                        for p in res.excluded_analytic]
    assert len(art["points"]) == len(res.points)
    on_front = [r["label"] for r in art["points"] if r["on_frontier"]]
    assert set(on_front) == set(art["frontier"])


def test_sweep_memsys_moved_and_shimmed():
    """The unified subsystem owns the sweep; the old planner entry point
    still works but warns."""
    sweep = dse.sweep_memsys(bench="xcorr", n_cus=(1,), sizes=(16, 128))
    assert {(1, ms) for ms in ("shared", "banked", "banked-iso")} == \
        set(sweep)
    from repro.core.planner import sweep_memsys as old_sweep
    with pytest.warns(DeprecationWarning):
        legacy = old_sweep(bench="xcorr", n_cus=(1,), sizes=(16, 128))
    assert {k: v["cycles"] for k, v in legacy.items()} == \
        {k: v["cycles"] for k, v in sweep.items()}
