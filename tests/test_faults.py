"""Deterministic fault injection (``repro.faults``): the seed-keyed
FaultPlan draw primitive, the FAULTS registry axis, injector
transparency with an inactive plan (injection-off is byte-identical),
and full-run determinism — same seed + plan => identical decision logs
and byte-identical results across runs, including through a drain that
returns with an abandoned hedge-loser chunk still in flight."""
import numpy as np
import pytest

from repro.faults import FaultInjector, FaultPlan
from repro.ggpu import programs
from repro.ggpu.engine import GGPUConfig, run_kernel
from repro.registry import FAULTS
from repro.serve import Fleet, Request, Scheduler
from repro.serve.request import result_checksum

CFG = GGPUConfig(n_cus=2)


def _copy_bench():
    return programs._copy(16, 128)


def _mems(b, n, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(-30, 30, b.gpu_mem.shape[0]).astype(np.int32)
            for _ in range(n)]


# ------------------------------------------------------------- FaultPlan

def test_plan_draws_are_pure_and_seed_keyed():
    """Decisions are pure functions of (seed, kind, ticket, attempt):
    two plan instances agree, different seeds disagree somewhere, and a
    retry (attempt+1) is a fresh draw."""
    a = FaultPlan(seed=7, seu_rate=0.5)
    b = FaultPlan(seed=7, seu_rate=0.5)
    hits = [a.seu_hit(t, 0) for t in range(64)]
    assert hits == [b.seu_hit(t, 0) for t in range(64)]
    assert any(hits) and not all(hits)          # rate 0.5 lands both ways
    other = FaultPlan(seed=8, seu_rate=0.5)
    assert hits != [other.seu_hit(t, 0) for t in range(64)]
    assert hits != [a.seu_hit(t, 1) for t in range(64)]  # attempt-aware


def test_plan_rate_monotone_and_extremes():
    never = FaultPlan(seed=3, seu_rate=0.0, seu_post_rate=0.0)
    always = FaultPlan(seed=3, seu_rate=1.0, seu_post_rate=1.0)
    some = FaultPlan(seed=3, seu_rate=0.4)
    for t in range(32):
        assert not never.seu_hit(t, 0) and not never.post_hit(t, 0)
        assert always.seu_hit(t, 0) and always.post_hit(t, 0)
        # a launch hit at rate r stays hit at any higher rate (the draw
        # is shared; only the threshold moves)
        if some.seu_hit(t, 0):
            assert always.seu_hit(t, 0)


def test_plan_flip_coordinates_in_range():
    plan = FaultPlan(seed=1, seu_rate=1.0, seu_post_rate=1.0)
    for t in range(32):
        word, bit = plan.seu_flip(t, 0, msize=17)
        assert 0 <= word < 17
        assert 0 <= bit < 31          # int32 sign bit is never drawn
        word, bit = plan.post_flip(t, 0, msize=5)
        assert 0 <= word < 5 and 0 <= bit < 31


def test_plan_inactive_flag_and_stuck():
    assert not FaultPlan().active
    assert FaultPlan(seu_rate=0.1).active
    assert FaultPlan(stuck_devices=("d",)).active
    plan = FaultPlan(stuck_devices=("dev0",), stuck_after=2)
    assert not plan.stuck("dev0", 1)
    assert plan.stuck("dev0", 2) and plan.stuck("dev0", 5)
    assert not plan.stuck("dev1", 99)


# ----------------------------------------------------- FAULTS axis

def test_faults_axis_builtins():
    assert {"none", "seu", "straggler", "device-loss"} \
        <= set(FAULTS.names())
    sc = FAULTS.get("none")(seed=3)
    assert not sc.plan.active and sc.resilience is None and not sc.audit
    sc = FAULTS.get("seu")(seed=3)
    assert sc.plan.active and sc.audit and sc.retry is not None
    sc = FAULTS.get("straggler")(seed=3)
    assert sc.resilience.hedge is not None and sc.timeout_s
    sc = FAULTS.get("device-loss")(seed=3)
    assert sc.plan.stuck_devices == ("dev0",)


# --------------------------------------- injector off == byte-identical

def test_inactive_injector_is_byte_identical_passthrough():
    """An interposed injector with an inactive plan changes nothing:
    same bits, same stats, empty decision log — the committed-baseline
    byte-identity guarantee."""
    b = _copy_bench()
    mems = _mems(b, 4)

    plain = Scheduler(CFG, max_batch=2)
    for m in mems:
        plain.submit(b.gpu_prog, m, b.gpu_items)
    expect = plain.flush()

    wrapped = Scheduler(CFG, max_batch=2)
    inj = FaultInjector("d", wrapped.executor, FaultPlan(seed=5))
    wrapped.executor = inj
    for m in mems:
        wrapped.submit(b.gpu_prog, m, b.gpu_items)
    got = wrapped.flush()

    assert inj.injected == []
    assert inj.cfg is CFG                 # protocol passthrough
    assert len(got) == len(expect) == 4
    for g, e in zip(got, expect):
        np.testing.assert_array_equal(g.mem, e.mem)
        assert g.info["cycles"] == e.info["cycles"]


# --------------------------------------------- full-run determinism

def _chaos_run(seed: int, n: int = 10):
    """One audited chaos serve under the ``seu`` scenario; returns every
    determinism-relevant surface for cross-run comparison."""
    b = _copy_bench()
    mems = _mems(b, n)
    refs = [run_kernel(b.gpu_prog, m, b.gpu_items, CFG) for m in mems]
    sc = FAULTS.get("seu")(seed=seed, rate=0.6, max_retries=4)
    fleet = Fleet([("dev0", CFG), ("dev1", GGPUConfig(n_cus=1))],
                  max_batch=4, **sc.fleet_kwargs())
    for m, ref in zip(mems, refs):
        fleet.submit_request(Request(
            b.gpu_prog, m, b.gpu_items, audit=result_checksum(ref[0])))
    results = fleet.drain()
    return (sc.decision_log(),
            tuple(r.info["ticket"] for r in results),
            tuple(np.asarray(r.mem, np.int32).tobytes() for r in results),
            tuple(sorted(fleet.quarantined)),
            refs)


def test_same_seed_same_decisions_and_bits():
    """Two runs at one seed are indistinguishable: identical injection
    decision logs (the determinism surface), identical served tickets,
    byte-identical result memories, identical quarantine sets — through
    retry interleaving and checksum-audit re-dispatches."""
    log1, served1, bits1, quar1, refs = _chaos_run(seed=0)
    log2, served2, bits2, quar2, _ = _chaos_run(seed=0)
    assert log1 == log2
    assert len(log1) > 0                  # chaos actually happened
    assert served1 == served2
    assert bits1 == bits2
    assert quar1 == quar2
    # and the audit held: every served result is bit-exact (corruption
    # was retried, never silently returned)
    for t, raw in zip(served1, bits1):
        np.testing.assert_array_equal(
            np.frombuffer(raw, np.int32), refs[t][0])


def test_different_seed_different_decisions():
    log0 = _chaos_run(seed=0)[0]
    log9 = _chaos_run(seed=9)[0]
    assert log0 != log9


def test_determinism_through_abandoned_drain():
    """Same-seed determinism holds through the abandoned-loser path: a
    resilient drain that returns while a hedge-loser chunk is still in
    flight (discarded by a later drain's collect) serves the same bits
    both runs."""
    def run():
        b = _copy_bench()
        mems = _mems(b, 3, seed=2)
        plan = FaultPlan(seed=0, straggler_rate=1.0, straggler_delay_s=0.4)

        def wrap(name, ex):
            # only dev0 straggles; dev1 is the clean hedge target
            return FaultInjector(name, ex, plan) if name == "dev0" else ex

        from repro.serve.fleet import FleetResilience, HedgePolicy
        fleet = Fleet([("dev0", CFG), ("dev1", CFG)], max_batch=1,
                      resilience=FleetResilience(
                          hedge=HedgePolicy(after_s=0.03)),
                      timeout_s=5.0, executor_wrap=wrap)
        for m in mems:
            fleet.submit(b.gpu_prog, m, b.gpu_items)
        out = fleet.drain()
        injector = fleet.devices[0].scheduler.executor
        log = tuple(sorted(injector.injected))
        import time
        time.sleep(0.5)                   # let the abandoned holds expire
        late = fleet.drain()              # losers collected and discarded
        assert late == []
        return (log, tuple(r.info["ticket"] for r in out),
                tuple(np.asarray(r.mem, np.int32).tobytes() for r in out))

    log1, served1, bits1 = run()
    log2, served2, bits2 = run()
    assert log1 == log2 and len(log1) >= 1
    assert served1 == served2 == (0, 1, 2)
    assert bits1 == bits2


def test_seu_flip_lands_in_staged_memory():
    """A pre-dispatch SEU really flips the staged bit: the result is
    bit-exact with running the kernel over the host-side image with the
    drawn bit flipped — the corruption is in the staged input, not a
    host-side fiction."""
    b = _copy_bench()
    plan = FaultPlan(seed=4, seu_rate=1.0)
    s = Scheduler(CFG)
    inj = FaultInjector("d", s.executor, plan)
    s.executor = inj
    m = _mems(b, 1)[0]
    s.submit(b.gpu_prog, m, b.gpu_items)
    (res,) = s.flush()
    assert [e[0] for e in inj.injected] == ["seu"]
    word, bit = plan.seu_flip(0, 0, int(m.shape[0]))
    flipped = m.copy()
    flipped[word] ^= np.int32(1) << bit
    expect = run_kernel(b.gpu_prog, flipped, b.gpu_items, CFG)
    np.testing.assert_array_equal(res.mem, expect[0])


def test_stuck_device_never_ready():
    plan = FaultPlan(seed=0, stuck_devices=("d",), stuck_after=0)
    from repro.serve.executors import DeviceTimeout, Executor
    ex = Executor(CFG, timeout_s=0.05)
    inj = FaultInjector("d", ex, plan)
    b = _copy_bench()
    pending = inj.submit([Request(b.gpu_prog, b.gpu_mem, b.gpu_items)])
    assert not inj.chunk_ready(pending)
    with pytest.raises(DeviceTimeout):
        inj.collect(pending)
