"""G-GPU SIMT simulator: functional correctness of all seven paper
benchmarks on GPU + scalar machines, divergence handling, and the paper's
scaling trends.

Simulations are memoized per (bench, machine, CU count) behind the
session-scoped ``sim`` fixture so compiled steppers and results are reused
across tests. ``GGPU_FAST_TESTS=1`` downscales the bench inputs for the
correctness assertions; the Table-III trend assertions always use the
paper's sizes (they are cheap — the quadratic kernels are not involved)."""
import functools
import os

import numpy as np
import pytest

from repro.ggpu import programs
from repro.ggpu.isa import Assembler
from repro.ggpu.engine import GGPUConfig, ScalarConfig, run_kernel
from repro.ggpu.programs import all_benches

FAST = os.environ.get("GGPU_FAST_TESTS", "0") not in ("", "0")
FAST_TESTS = ["copy", "vec_mul", "div_int", "mat_mul", "fir", "parallel_sel",
              "reduction"]


@functools.lru_cache(maxsize=1)
def _paper_benches():
    return all_benches()


@functools.lru_cache(maxsize=1)
def _correctness_benches():
    if not FAST:
        return _paper_benches()
    small = [programs._mat_mul(8, 32), programs._copy(128, 4096),
             programs._vec_mul(128, 8192), programs._fir(32, 1024),
             programs._div_int(64, 1024), programs._xcorr(32, 256),
             programs._parallel_sel(32, 512), programs._reduction(256, 4096)]
    return {b.name: b for b in small}


BENCHES = _correctness_benches()


def _sim(name, kind="gpu", ncu=1, paper_size=False):
    # normalize before the cache key: without FAST the sizes coincide, so
    # paper_size=True must hit the same memoized entry
    return _sim_cached(name, kind, ncu, paper_size and FAST)


@functools.lru_cache(maxsize=None)
def _sim_cached(name, kind, ncu, paper_size):
    """Memoized kernel simulation; results (and the stepper compiled for
    the shape) are shared by every test in the session."""
    b = _paper_benches()[name] if paper_size else BENCHES[name]
    if kind == "gpu":
        return run_kernel(b.gpu_prog, b.gpu_mem, b.gpu_items,
                          GGPUConfig(n_cus=ncu)) + (b,)
    return run_kernel(b.scalar_prog, b.scalar_mem, 1, ScalarConfig()) + (b,)


@pytest.fixture(scope="session")
def sim():
    return _sim


@pytest.mark.parametrize("name", list(BENCHES))
def test_gpu_kernel_correct(name, sim):
    if name == "xcorr" and not FAST:
        # keep CI time bounded at paper size: covered by test_xcorr_small
        pytest.skip("covered by test_xcorr_small")
    mem, info, b = sim(name, "gpu", 2)
    np.testing.assert_array_equal(mem[b.gpu_out], b.ref(b.gpu_mem, b.gpu_n))
    assert info["cycles"] > 0


@pytest.mark.parametrize("name", FAST_TESTS)
def test_scalar_kernel_correct(name, sim):
    mem, info, b = sim(name, "scalar")
    np.testing.assert_array_equal(mem[b.scalar_out],
                                  b.ref(b.scalar_mem, b.scalar_n))


def test_xcorr_small():
    """xcorr correctness on a reduced size (full size runs in benchmarks)."""
    b = programs._xcorr(n_scalar=64, n_gpu=256)
    mem, _ = run_kernel(b.gpu_prog, b.gpu_mem, b.gpu_items, GGPUConfig())
    np.testing.assert_array_equal(mem[b.gpu_out], b.ref(b.gpu_mem, 256))
    mem, _ = run_kernel(b.scalar_prog, b.scalar_mem, 1, ScalarConfig())
    np.testing.assert_array_equal(mem[b.scalar_out], b.ref(b.scalar_mem, 64))


def test_divergence_serializes_correctly():
    """Work-items taking different branches all produce correct results
    (full thread divergence, min-PC reconvergence)."""
    n = 128
    a = Assembler()
    a.tid(1)
    a.andi(2, 1, 1)                       # odd/even
    a.beq(2, 0, "even")
    a.mul(3, 1, 1).sw(3, 1, n).beq(0, 0, "end")   # odd: tid^2
    a.label("even").slli(3, 1, 1).sw(3, 1, n)     # even: 2*tid
    a.label("end").halt()
    mem0 = np.zeros(2 * n, np.int32)
    mem, _ = run_kernel(a.assemble(), mem0, n, GGPUConfig())
    tid = np.arange(n)
    expect = np.where(tid % 2 == 1, tid * tid, 2 * tid).astype(np.int32)
    np.testing.assert_array_equal(mem[n:2 * n], expect)


def test_cu_scaling_parallel_kernel(sim):
    """mat_mul scales near-linearly 1 -> 8 CUs (the paper's headline).
    Always at the paper's Table-III input size."""
    cycles = {}
    for ncu in (1, 2, 8):
        _, info, _ = sim("mat_mul", "gpu", ncu, paper_size=True)
        cycles[ncu] = info["cycles"]
    assert cycles[1] / cycles[2] > 1.8
    assert cycles[1] / cycles[8] > 6.0


def test_streaming_kernel_saturates(sim):
    """copy is DRAM-bound: 8 CUs buy little (paper Table III trend)."""
    _, c1, _ = sim("copy", "gpu", 1, paper_size=True)
    _, c8, _ = sim("copy", "gpu", 8, paper_size=True)
    assert c1["cycles"] / c8["cycles"] < 4.0       # far from linear


def test_divider_weakness(sim):
    """div_int per-element cost is much worse on the G-GPU than the scalar
    core (FGPU lacks a native divider; Fig. 5's weakest kernel)."""
    _, g, b = sim("div_int", "gpu", 1, paper_size=True)
    _, s, _ = sim("div_int", "scalar", paper_size=True)
    gpu_per_elem = g["cycles"] / b.gpu_n
    scalar_per_elem = s["cycles"] / b.scalar_n
    _, gc, copy_b = sim("copy", "gpu", 1, paper_size=True)
    _, sc, _ = sim("copy", "scalar", paper_size=True)
    # relative advantage on div is much smaller than on copy
    adv_div = scalar_per_elem / gpu_per_elem
    adv_copy = (sc["cycles"] / copy_b.scalar_n) / (gc["cycles"] / copy_b.gpu_n)
    assert adv_div < adv_copy


def test_store_load_roundtrip():
    a = Assembler()
    a.tid(1).slli(2, 1, 2).sw(2, 1, 0).lw(3, 1, 0).addi(3, 3, 7) \
     .sw(3, 1, 64).halt()
    mem, _ = run_kernel(a.assemble(), np.zeros(128, np.int32), 64,
                        GGPUConfig())
    np.testing.assert_array_equal(mem[64:128], np.arange(64) * 4 + 7)


def test_halts_and_stats():
    a = Assembler()
    a.tid(1).halt()
    mem, info = run_kernel(a.assemble(), np.zeros(4, np.int32), 64,
                           GGPUConfig())
    assert info["steps"] >= 2
    assert info["cycles"] >= 16
