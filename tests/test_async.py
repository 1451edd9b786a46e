"""Async launch pipeline: LaunchHandle futures are bit-exact vs the sync
entry point on all 8 benches across one launch, a cohort and a batch, and
through
interleaved pipelined drains; donation never invalidates caller data and
is never read back after dispatch; failures surface through the handle;
the executor registry is frequency-faithful; opcode sets are
content-cached on requests."""
import numpy as np
import pytest

from repro.ggpu import programs
from repro.ggpu.engine import GGPUConfig, KernelLaunchError, launch, run_kernel
from repro.ggpu.engine.stepper import _static_ops
from repro.ggpu.isa import Assembler
from repro.serve import Request, Scheduler, get_executor, sim_key

CFG = GGPUConfig(n_cus=2)
STAT_KEYS = ("cycles", "instrs", "mem_ops", "hits", "misses", "steps")

SMALL = {
    "copy": lambda: programs._copy(16, 128),
    "vec_mul": lambda: programs._vec_mul(16, 128),
    "mat_mul": lambda: programs._mat_mul(4, 8),
    "fir": lambda: programs._fir(16, 64),
    "div_int": lambda: programs._div_int(16, 64),
    "xcorr": lambda: programs._xcorr(16, 64),
    "parallel_sel": lambda: programs._parallel_sel(16, 64),
    "reduction": lambda: programs._reduction(64, 256),
}


def _pad_prog(prog, rows):
    return np.vstack([prog, np.zeros((rows, prog.shape[1]), np.int32)])


def _variant_mem(b, seed):
    rng = np.random.default_rng(seed)
    return rng.integers(-20, 20, b.gpu_mem.shape[0]).astype(np.int32)


def _check(result, direct):
    mem, info = result
    dmem, dinfo = direct
    np.testing.assert_array_equal(mem, dmem)
    for k in STAT_KEYS:
        assert info[k] == dinfo[k], k


@pytest.mark.parametrize("name", sorted(SMALL))
def test_async_bitexact_all_paths_and_interleaved_drain(name):
    """Handles from ``launch`` on one launch, a cohort and a batch, and a
    pipelined scheduler drain interleaved under a budget, return the same
    bits (mem, cycles, stats) as direct sync ``run_kernel`` on every
    bench."""
    b = SMALL[name]()
    progA = b.gpu_prog
    progB = _pad_prog(progA, 1)
    progC = _pad_prog(progA, 2)
    m0, m1, m2 = b.gpu_mem, _variant_mem(b, 1), _variant_mem(b, 2)
    launches = [(progB, m1), (progA, m0), (progA, m2), (progC, m0)]
    direct = [run_kernel(p, m, b.gpu_items, CFG) for p, m in launches]

    # engine-level async handles: one launch / cohort / batch
    _check(launch([progA], [m0], [b.gpu_items], CFG).result(), direct[1])
    hc = launch([progA, progA], [m0, m2], [b.gpu_items] * 2, CFG)
    for out, d in zip(hc.results(), (direct[1], direct[2])):
        _check(out, d)
    hb = launch([progB, progC], [m1, m0], [b.gpu_items] * 2, CFG)
    for out, d in zip(hb.results(), (direct[0], direct[3])):
        _check(out, d)

    # pipelined scheduler: cohort + batch chunks in flight together,
    # drains interleaved under a budget
    s = Scheduler(CFG, max_inflight=2)
    for p, m in launches:
        s.submit(p, m, b.gpu_items)
    out = s.drain(budget=1)
    out += s.drain()
    assert len(s) == 0 and not s.quarantined
    got = {r.info["ticket"]: r for r in out}
    assert sorted(got) == [0, 1, 2, 3]
    for t, d in enumerate(direct):
        _check(got[t], d)


def test_out_region_sliced_download():
    """A declared out_region downloads exactly that slice of the final
    image — on every path, including through the scheduler — and (0, 0)
    transfers nothing while cycles stay exact."""
    b = SMALL["vec_mul"]()
    lo, hi = b.gpu_out.start, b.gpu_out.stop
    full, dinfo = run_kernel(b.gpu_prog, b.gpu_mem, b.gpu_items, CFG)

    h = launch([b.gpu_prog], [b.gpu_mem], [b.gpu_items], CFG,
               out_regions=[(lo, hi)])
    mem, info = h.result()
    np.testing.assert_array_equal(mem, full[lo:hi])
    assert info["cycles"] == dinfo["cycles"]

    m2 = _variant_mem(b, 5)
    full2, _ = run_kernel(b.gpu_prog, m2, b.gpu_items, CFG)
    hc = launch([b.gpu_prog] * 2, [b.gpu_mem, m2], [b.gpu_items] * 2, CFG,
                out_regions=[(lo, hi), None])
    outs = hc.results()
    np.testing.assert_array_equal(outs[0][0], full[lo:hi])
    np.testing.assert_array_equal(outs[1][0], full2)   # None: full image

    hb = launch([b.gpu_prog, _pad_prog(b.gpu_prog, 1)], [b.gpu_mem, m2],
                [b.gpu_items] * 2, CFG, out_regions=[(lo, hi), (0, 0)])
    outs = hb.results()
    np.testing.assert_array_equal(outs[0][0], full[lo:hi])
    assert outs[1][0].shape == (0,)                    # cycles-only
    assert outs[1][1]["cycles"] == dinfo["cycles"]

    s = Scheduler(CFG)
    s.submit(b.gpu_prog, b.gpu_mem, b.gpu_items, out_region=(lo, hi))
    s.submit(b.gpu_prog, m2, b.gpu_items, out_region=(0, 0))
    r0, r1 = s.drain()
    np.testing.assert_array_equal(r0.mem, full[lo:hi])
    assert r1.mem.shape == (0,) and r1.info["cycles"] == dinfo["cycles"]

    with pytest.raises(ValueError):
        launch([b.gpu_prog], [b.gpu_mem], [b.gpu_items], CFG,
               out_regions=[(0, b.gpu_mem.shape[0] + 1)])


def test_donation_safety():
    """The staged device buffer is donated at dispatch (XLA invalidates
    it — proof nothing reads it afterwards), while the caller's host
    array is never touched; results stay correct after donation, and a
    sync re-run from the same host array is unaffected."""
    b = SMALL["copy"]()
    before = b.gpu_mem.copy()
    h = launch([b.gpu_prog], [b.gpu_mem], [b.gpu_items], CFG)
    assert h.donated.is_deleted()            # donated, not merely unused
    np.testing.assert_array_equal(b.gpu_mem, before)   # caller untouched
    mem, _ = h.result()
    np.testing.assert_array_equal(mem[b.gpu_out], b.ref(b.gpu_mem, b.gpu_n))

    hc = launch([b.gpu_prog] * 2, [b.gpu_mem, b.gpu_mem], [b.gpu_items] * 2,
                CFG)
    assert hc.donated.is_deleted()
    hb = launch([b.gpu_prog, _pad_prog(b.gpu_prog, 1)],
                [b.gpu_mem, b.gpu_mem], [b.gpu_items] * 2, CFG)
    assert hb.donated.is_deleted()
    np.testing.assert_array_equal(b.gpu_mem, before)
    # the same host image dispatches again cleanly (fresh staging copy)
    _check(launch([b.gpu_prog], [b.gpu_mem], [b.gpu_items], CFG).result(),
           run_kernel(b.gpu_prog, b.gpu_mem, b.gpu_items, CFG))


def _spinner():
    a = Assembler()
    a.label("spin").beq(0, 0, "spin")
    return a.assemble()


def test_launch_handle_surfaces_failure():
    """A launch that hits max_steps raises KernelLaunchError out of the
    handle at resolution time, naming the failing position — on every
    path — and the error repeats on re-resolution."""
    cfg = GGPUConfig(max_steps=50)
    b = programs._copy(8, 64)
    h = launch([_spinner()], [np.zeros(8, np.int32)], [8], cfg)
    with pytest.raises(KernelLaunchError) as exc:
        h.result()
    assert exc.value.index == 0
    with pytest.raises(KernelLaunchError):   # sticky: wait() re-raises
        h.wait()

    hc = launch([_spinner()] * 2, [np.zeros(8, np.int32)] * 2, [8, 8], cfg)
    with pytest.raises(KernelLaunchError):
        hc.results()

    hb = launch([b.gpu_prog, _spinner()], [b.gpu_mem, np.zeros(8, np.int32)],
                [b.gpu_items, 8], cfg)
    with pytest.raises(KernelLaunchError) as exc:
        hb.results()
    assert exc.value.index == 1


@pytest.mark.parametrize("max_inflight", (1, 8))
def test_pipelined_drain_quarantines_at_any_depth(max_inflight):
    """Pipeline depth never changes results or quarantine behavior: a
    poisoned launch in a deep in-flight queue is isolated, survivors
    complete bit-exact, and stats stay coherent."""
    cfg = GGPUConfig(max_steps=50)
    b = programs._copy(16, 128)
    c2 = programs._copy(8, 64)               # W=1: shares spinner's bucket
    s = Scheduler(cfg, max_inflight=max_inflight)
    t0 = s.submit(b.gpu_prog, b.gpu_mem, b.gpu_items)
    t_bad = s.submit(_spinner(), np.zeros(8, np.int32), 8)
    t2 = s.submit(c2.gpu_prog, c2.gpu_mem, c2.gpu_items)
    t3 = s.submit(b.gpu_prog, _variant_mem(b, 3), b.gpu_items)
    results = s.drain()
    assert len(s) == 0
    assert [r.info["ticket"] for r in results] == [t0, t2, t3]
    assert set(s.quarantined) == {t_bad}
    _check(results[1],
           run_kernel(c2.gpu_prog, c2.gpu_mem, c2.gpu_items, cfg))
    st = s.executor.stats
    assert st.trace_hits + st.trace_misses == st.dispatches


def test_registry_is_frequency_faithful():
    """get_executor at a non-default frequency returns a view sharing the
    canonical executor's compiled-envelope cache, stats, and memo — but
    its Results report time_us rescaled from cycles at the TRUE freq_mhz
    (the PR-3 registry reported it at the normalized 500 MHz)."""
    cfg667 = GGPUConfig(n_cus=4, freq_mhz=667.0)
    ex = get_executor(cfg667)
    assert ex.cfg.freq_mhz == 667.0
    assert ex.sim_cfg == sim_key(cfg667)
    canon = get_executor(sim_key(cfg667))
    assert canon is not ex
    assert ex.memo is canon.memo and ex.stats is canon.stats
    assert ex._envelopes is canon._envelopes
    assert get_executor(cfg667) is ex        # views are cached too

    b = SMALL["copy"]()
    (res,) = ex.run([Request(b.gpu_prog, b.gpu_mem, b.gpu_items)])
    assert res.info["time_us"] == pytest.approx(
        res.info["cycles"] / 667.0)
    # same envelope through the canonical executor: shared trace cache hits
    (res500,) = canon.run([Request(b.gpu_prog, b.gpu_mem, b.gpu_items)])
    assert res500.info["cycles"] == res.info["cycles"]
    assert res500.info["time_us"] == pytest.approx(
        res.info["cycles"] / 500.0)
    assert canon.stats.trace_hits >= 1


def test_bad_out_region_bounces_at_admission():
    """A malformed out_region raises at submit (per-request,
    handleable) — it must never be admitted, where it would poison every
    later drain from inside the dispatch path."""
    b = SMALL["copy"]()
    s = Scheduler(CFG)
    with pytest.raises(ValueError):
        s.submit(b.gpu_prog, b.gpu_mem, b.gpu_items,
                 out_region=(0, b.gpu_mem.shape[0] + 1))
    with pytest.raises(ValueError):
        s.submit(b.gpu_prog, b.gpu_mem, b.gpu_items, out_region=(-1, 0))
    assert len(s) == 0                       # nothing admitted
    s.submit(b.gpu_prog, b.gpu_mem, b.gpu_items)
    assert len(s.drain()) == 1               # scheduler unharmed


def test_trace_hits_counted_across_pipeline_window():
    """Identical-envelope chunks dispatched ahead in one pipeline window
    are trace hits: the jit trace is paid at dispatch, so only the first
    chunk is a miss even before anything is collected."""
    b = SMALL["vec_mul"]()
    s = Scheduler(CFG, max_batch=2, max_inflight=8)
    for seed in range(8):                    # 4 identical cohort envelopes
        s.submit(b.gpu_prog, _variant_mem(b, seed), b.gpu_items)
    assert len(s.drain()) == 8
    st = s.executor.stats
    assert st.dispatches == 4
    assert st.trace_misses == 1 and st.trace_hits == 3
    assert st.trace_hits + st.trace_misses == st.dispatches


def test_sync_entries_accept_iterators():
    """``launch`` materializes sequence inputs exactly once — generator
    arguments are not consumed by its checks — on the cohort and the
    batch path, and an empty call raises rather than dispatching."""
    b = SMALL["copy"]()
    direct = run_kernel(b.gpu_prog, b.gpu_mem, b.gpu_items, CFG)
    outs = launch((p for p in [b.gpu_prog] * 2),
                  (m for m in [b.gpu_mem, _variant_mem(b, 1)]),
                  (n for n in [b.gpu_items] * 2), CFG).results()
    assert len(outs) == 2
    _check(outs[0], direct)
    outs = launch((p for p in [b.gpu_prog, _pad_prog(b.gpu_prog, 1)]),
                  (m for m in [b.gpu_mem, b.gpu_mem]),
                  (n for n in [b.gpu_items] * 2), CFG).results()
    _check(outs[0], direct)
    with pytest.raises(ValueError):
        launch(iter([]), iter([]), iter([]), CFG)


def test_request_static_ops_content_cached():
    """Opcode sets are cached by program *content*: two distinct Request
    objects over equal programs share one cached tuple, and it matches
    the engine's own scan."""
    b = SMALL["fir"]()
    r1 = Request(b.gpu_prog, b.gpu_mem, b.gpu_items)
    r2 = Request(b.gpu_prog.copy(), _variant_mem(b, 1), b.gpu_items)
    assert r1.static_ops() == _static_ops(b.gpu_prog)
    assert r1.static_ops() is r2.static_ops()   # one cache entry


def test_fleet_dispatches_all_devices_before_collecting():
    """Fleet.drain puts every device's chunks in flight before resolving
    any: each device's scheduler dispatch precedes every collect."""
    from repro.serve import Fleet
    events = []
    b = SMALL["copy"]()
    fleet = Fleet([("a", GGPUConfig(n_cus=1)), ("b", GGPUConfig(n_cus=2))])
    for dev in fleet.devices:
        sched = dev.scheduler

        def spy(kind, fn, name):
            def wrapper(*a, **k):
                events.append((kind, name))
                return fn(*a, **k)
            return wrapper
        sched.dispatch = spy("dispatch", sched.dispatch, dev.name)
        sched.collect = spy("collect", sched.collect, dev.name)
    # one launch per device (wide vs narrow routing not needed here)
    fleet.submit(b.gpu_prog, b.gpu_mem, b.gpu_items)
    fleet.submit(b.gpu_prog, _variant_mem(b, 1), b.gpu_items)
    fleet.drain()
    kinds = [k for k, _ in events]
    assert kinds == ["dispatch", "dispatch", "collect", "collect"]
