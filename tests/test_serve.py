"""Serving subsystem: facade compatibility, bit-exact round-trips through
every launch path (cohort / vmap-batch / singleton) under both monolithic
flush and incremental drain on all 8 benches, scheduler quarantine and
priority planning, the shared executor cache, and the fleet router."""
import numpy as np
import pytest

from repro.ggpu import programs
from repro.ggpu.engine import GGPUConfig, run_kernel
from repro.ggpu.isa import Assembler
from repro.serve import (AdmissionError, Fleet, LaunchQueue, Request,
                         Scheduler, plan_chunks, plan_waves, pinned_makespan)

CFG = GGPUConfig(n_cus=2)
STAT_KEYS = ("cycles", "instrs", "mem_ops", "hits", "misses", "steps")

# reduced-size builders for all 8 benches (7 paper + reduction)
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
    """Append unreachable HALT rows: a distinct program (new kernel key,
    new cohort identity) with identical behavior."""
    return np.vstack([prog, np.zeros((rows, prog.shape[1]), np.int32)])


def _variant_mem(b, seed):
    rng = np.random.default_rng(seed)
    m = rng.integers(-20, 20, b.gpu_mem.shape[0]).astype(np.int32)
    return m


def _check(result, direct):
    mem, info = result
    dmem, dinfo = direct
    np.testing.assert_array_equal(mem, dmem)
    for k in STAT_KEYS:
        assert info[k] == dinfo[k], k


def test_facade_imports_unchanged():
    from repro.serve.engine import (Engine, EngineConfig,  # noqa: F401
                                    KernelLaunch, LaunchQueue)
    q = LaunchQueue(CFG)
    assert len(q) == 0
    kl = KernelLaunch(np.zeros((1, 5), np.int32), np.zeros(4, np.int32), 1,
                      "t")
    assert kl.tag == "t" and kl.priority == 0


@pytest.mark.parametrize("name", sorted(SMALL))
def test_roundtrip_all_paths_flush_and_drain(name):
    """All three launch paths, monolithic flush AND incremental drain, are
    bit-exact vs direct ``run_kernel`` — results, cycles, and stats — on
    every bench."""
    b = SMALL[name]()
    progA = b.gpu_prog
    progB = _pad_prog(progA, 1)
    progC = _pad_prog(progA, 2)
    m0, m1, m2 = b.gpu_mem, _variant_mem(b, 1), _variant_mem(b, 2)
    # tickets: 0 = B/m1 and 3 = C/m0 share a wavefront bucket (vmap batch);
    # 1, 2 = A over two mems (cohort)
    launches = [(progB, m1), (progA, m0), (progA, m2), (progC, m0)]
    direct = [run_kernel(p, m, b.gpu_items, CFG) for p, m in launches]

    q = LaunchQueue(CFG)
    for p, m in launches:
        q.submit(p, m, b.gpu_items)
    flushed = q.flush()
    assert [r.info["batch_size"] for r in flushed] == [2, 2, 2, 2]
    for res, d in zip(flushed, direct):
        _check(res, d)

    # singleton path
    q.submit(progA, m0, b.gpu_items)
    (single,) = q.flush()
    assert single.info["batch_size"] == 1
    _check(single, direct[1])

    # incremental drain with interleaved submissions
    s = Scheduler(CFG)
    s.submit(progB, m1, b.gpu_items)        # ticket 0
    s.submit(progA, m0, b.gpu_items)        # ticket 1
    first = s.drain(budget=1)               # serves only ticket 0's chunk
    s.submit(progA, m2, b.gpu_items)        # ticket 2
    s.submit(progC, m0, b.gpu_items)        # ticket 3
    rest = s.drain()
    assert len(s) == 0 and not s.quarantined
    got = {r.info["ticket"]: r for r in first + rest}
    assert sorted(got) == [0, 1, 2, 3]
    assert [r.info["ticket"] for r in rest] == sorted(
        r.info["ticket"] for r in rest)
    for t, d in enumerate(direct):
        _check(got[t], d)


def test_interleaved_drain_matches_monolithic_flush():
    """Any submit/drain interleaving returns the same per-ticket bits as
    one monolithic flush of the same submission sequence."""
    b = SMALL["copy"]()
    mems = [b.gpu_mem] + [_variant_mem(b, s) for s in range(1, 5)]
    sub = [(b.gpu_prog, m, b.gpu_items) for m in mems]

    mono = Scheduler(CFG)
    for p, m, n in sub:
        mono.submit(p, m, n)
    expect = {r.info["ticket"]: r for r in mono.flush()}

    inc = Scheduler(CFG)
    inc.submit(*sub[0])
    inc.submit(*sub[1])
    out = inc.drain()                        # cohort of 2
    inc.submit(*sub[2])
    out += inc.drain(budget=1)               # singleton
    inc.submit(*sub[3])
    inc.submit(*sub[4])
    out += inc.drain()                       # cohort of 2
    assert sorted(r.info["ticket"] for r in out) == sorted(expect)
    for r in out:
        _check(r, expect[r.info["ticket"]])


def _spinner():
    a = Assembler()
    a.label("spin").beq(0, 0, "spin")
    return a.assemble()


def test_scheduler_quarantines_poisoned_launch():
    """A launch that never halts is isolated into ``quarantined``; the
    rest of its chunk (and the drain) completes in the same call."""
    cfg = GGPUConfig(max_steps=50)
    b = programs._copy(16, 128)
    c2 = programs._copy(8, 64)               # W=1: shares spinner's bucket
    s = Scheduler(cfg)
    t0 = s.submit(b.gpu_prog, b.gpu_mem, b.gpu_items, tag="good0")
    t_bad = s.submit(_spinner(), np.zeros(8, np.int32), 8, tag="spinner")
    t2 = s.submit(c2.gpu_prog, c2.gpu_mem, c2.gpu_items, tag="good2")
    t3 = s.submit(b.gpu_prog, _variant_mem(b, 3), b.gpu_items, tag="good3")
    results = s.drain()
    assert len(s) == 0
    assert [r.info["ticket"] for r in results] == [t0, t2, t3]
    assert set(s.quarantined) == {t_bad}
    assert s.quarantined[t_bad].request.tag == "spinner"
    assert "max_steps" in str(s.quarantined[t_bad].error)
    # survivors are still bit-exact
    _check(results[1], run_kernel(c2.gpu_prog, c2.gpu_mem, c2.gpu_items,
                                  cfg))
    # the scheduler remains serviceable
    s.submit(b.gpu_prog, b.gpu_mem, b.gpu_items)
    assert len(s.drain()) == 1
    # stats stay coherent through the failure path
    st = s.executor.stats
    assert st.trace_hits + st.trace_misses == st.dispatches


def test_fleet_surfaces_quarantined_launches():
    """A launch quarantined on its routed device appears in
    ``Fleet.quarantined`` under its *fleet* ticket; the drain still
    returns every healthy result."""
    cfg = GGPUConfig(max_steps=50)
    b = programs._copy(16, 128)
    fleet = Fleet([("only", cfg)])
    t0 = fleet.submit(b.gpu_prog, b.gpu_mem, b.gpu_items)
    t_bad = fleet.submit(_spinner(), np.zeros(8, np.int32), 8, tag="spin")
    results = fleet.drain()
    assert [r.info["ticket"] for r in results] == [t0]
    assert set(fleet.quarantined) == {t_bad}
    assert fleet.quarantined[t_bad].request.tag == "spin"
    assert fleet.report()["quarantined"] == [t_bad]


def test_scheduler_drain_loses_nothing_on_unexpected_failure():
    """A non-launch failure mid-drain (not a max_steps quarantine) must
    not lose work: in-flight and unexecuted requests stay pending, and
    results already computed in the same drain are buffered for the next
    one."""
    b = SMALL["copy"]()
    fir = SMALL["fir"]()
    s = Scheduler(CFG)
    t0 = s.submit(b.gpu_prog, b.gpu_mem, b.gpu_items)        # cohort of 2
    t1 = s.submit(b.gpu_prog, _variant_mem(b, 1), b.gpu_items)
    t2 = s.submit(fir.gpu_prog, fir.gpu_mem, fir.gpu_items)  # later single
    real_collect = s.executor.collect
    calls = []

    def explode_on_second(pending):
        calls.append(len(pending.reqs))
        if len(calls) == 2:
            raise ValueError("malformed launch")
        return real_collect(pending)

    s.executor.collect = explode_on_second
    with pytest.raises(ValueError):
        s.drain()
    # the cohort completed (buffered); the single — already dispatched and
    # in flight when the failure hit — is abandoned back to pending
    assert s.pending_tickets == [t2]
    s.executor.collect = real_collect
    results = s.drain()
    assert [r.info["ticket"] for r in results] == [t0, t1, t2]
    for t, (p, m, n) in [(t0, (b.gpu_prog, b.gpu_mem, b.gpu_items)),
                         (t2, (fir.gpu_prog, fir.gpu_mem, fir.gpu_items))]:
        _check(results[[r.info["ticket"] for r in results].index(t)],
               run_kernel(p, m, n, CFG))


def test_fleet_rejects_duplicate_device_names():
    with pytest.raises(ValueError):
        Fleet([("dev", GGPUConfig(n_cus=1)), ("dev", GGPUConfig(n_cus=2))])


def test_scheduler_quarantines_whole_poisoned_cohort():
    cfg = GGPUConfig(max_steps=50)
    s = Scheduler(cfg)
    for _ in range(2):
        s.submit(_spinner(), np.zeros(8, np.int32), 8)
    assert s.drain() == []
    assert sorted(s.quarantined) == [0, 1]


def test_plan_chunks_priority_and_deadline_order():
    b = SMALL["copy"]()
    fir = SMALL["fir"]()
    reqs = [
        Request(b.gpu_prog, b.gpu_mem, b.gpu_items),                # 0
        Request(fir.gpu_prog, fir.gpu_mem, fir.gpu_items,
                priority=1),                                        # 1
        Request(b.gpu_prog, _variant_mem(b, 1), b.gpu_items),       # 2
    ]
    chunks = plan_chunks(reqs, CFG)
    # the priority-1 single jumps ahead of the earlier-ticket cohort
    assert [c.members for c in chunks] == [(1,), (0, 2)]
    # deadlines break ties within a priority class
    reqs[0].deadline_us = reqs[2].deadline_us = 5.0
    assert [c.members for c in plan_chunks(reqs, CFG)] == [(1,), (0, 2)]
    reqs[1].priority = 0
    reqs[1].deadline_us = 1.0
    assert [c.members for c in plan_chunks(reqs, CFG)] == [(1,), (0, 2)]
    # defaults reproduce the legacy first-ticket order exactly
    legacy = [Request(r.prog, r.mem0, r.n_items) for r in reqs]
    assert [c.members for c in plan_chunks(legacy, CFG)] == [(0, 2), (1,)]


def test_scheduler_admission_limit():
    b = SMALL["copy"]()
    s = Scheduler(CFG, max_pending=1)
    s.submit(b.gpu_prog, b.gpu_mem, b.gpu_items)
    with pytest.raises(AdmissionError):
        s.submit(b.gpu_prog, b.gpu_mem, b.gpu_items)
    s.drain()
    s.submit(b.gpu_prog, b.gpu_mem, b.gpu_items)   # freed by the drain


def test_plan_waves_slots():
    assert plan_waves(range(5), 2) == [[0, 1], [2, 3], [4]]
    assert plan_waves([], 3) == []
    with pytest.raises(ValueError):
        plan_waves([1], 0)


def test_executor_envelope_cache_hits_on_repeat_traffic():
    """Repeat traffic with the same envelope is a trace-cache hit; the
    stats expose the hit rate BENCH_serve.json reports."""
    b = SMALL["vec_mul"]()
    s = Scheduler(CFG)
    for seed in (1, 2):
        s.submit(b.gpu_prog, _variant_mem(b, seed), b.gpu_items)
    s.drain()
    stats0 = s.executor.stats
    assert stats0.dispatches == 1 and stats0.trace_misses == 1
    for seed in (3, 4):
        s.submit(b.gpu_prog, _variant_mem(b, seed), b.gpu_items)
    s.drain()
    assert s.executor.stats.trace_hits == 1
    assert s.executor.stats.batch_occupancy == 2.0
    assert 0 < s.executor.stats.hit_rate <= 0.5


def test_fleet_routes_mixed_trace_and_beats_pinning():
    """Mixed trace over two complementary configs: wide launches land on
    the many-CU device, narrow ones on the high-clock device, results stay
    bit-exact, and the fleet's modeled makespan beats pinning the whole
    trace to either config."""
    small_cfg = GGPUConfig(n_cus=1, freq_mhz=667.0)
    wide_cfg = GGPUConfig(n_cus=8, freq_mhz=500.0)
    wide_b = programs._copy(16, 1024)        # W=16: wants CUs
    narrow_b = programs._reduction(64, 256)  # W=1: wants clock
    trace = []
    for seed in range(3):
        m = np.random.default_rng(seed).integers(
            -50, 50, wide_b.gpu_mem.shape[0]).astype(np.int32)
        trace.append((wide_b.gpu_prog, m, wide_b.gpu_items))
        m = np.random.default_rng(10 + seed).integers(
            -50, 50, narrow_b.gpu_mem.shape[0]).astype(np.int32)
        trace.append((narrow_b.gpu_prog, m, narrow_b.gpu_items))

    fleet = Fleet([("small", small_cfg), ("wide", wide_cfg)])
    tickets = [fleet.submit(p, m, n) for p, m, n in trace]
    results = fleet.drain()
    assert [r.info["ticket"] for r in results] == tickets
    report = fleet.report()
    assert all(report["placement"][d] > 0 for d in ("small", "wide"))
    # routed results are bit-exact on their device's config
    by_cfg = {"small": small_cfg, "wide": wide_cfg}
    for (p, m, n), res in zip(trace, results):
        _check(res, run_kernel(p, m, n, by_cfg[res.info["device"]]))
    # the routed fleet beats both pinned placements on modeled wall-clock
    for cfg in (small_cfg, wide_cfg):
        assert fleet.makespan_us() < pinned_makespan(cfg, trace)


def test_fleet_shard_width_wins_large_cohorts():
    """Two identical configs, one backed by a 4-wide physical mesh slice
    (stubbed via ``Executor.shards`` — real meshes are covered by the
    sharding subprocess tests): the router discounts the wide device's
    backlog by its shard width, so a large same-shape cohort
    overwhelmingly lands there, while modeled compute (busy_us /
    makespan) stays shard-agnostic."""
    b = programs._copy(16, 128)
    fleet = Fleet([("narrow", CFG), ("wide", CFG)], max_batch=4)
    wide = next(d for d in fleet.devices if d.name == "wide")
    wide.scheduler.executor.shards = 4          # stub the physical width

    for seed in range(16):
        fleet.submit(b.gpu_prog, _variant_mem(b, seed), b.gpu_items)
    rep = fleet.report()
    assert rep["placement"]["wide"] > rep["placement"]["narrow"]
    # estimate_us itself is shard-agnostic; only the finish model differs
    req = Request(b.gpu_prog, b.gpu_mem, b.gpu_items)
    narrow = next(d for d in fleet.devices if d.name == "narrow")
    assert fleet.estimate_us(wide, req) == fleet.estimate_us(narrow, req)
    assert fleet.finish_us(wide, req) < fleet.finish_us(narrow, req)

    results = fleet.drain()
    assert len(results) == 16
    for res in results:
        _check(res, run_kernel(b.gpu_prog,
                               _variant_mem(b, res.info["ticket"]),
                               b.gpu_items, CFG))
    # modeled compute accounting is unchanged by the routing discount
    rep = fleet.report()
    assert rep["busy_us"]["wide"] >= rep["busy_us"]["narrow"]
    assert fleet.makespan_us() == max(rep["busy_us"].values())


def test_engine_prefill_eos_regression():
    """A sequence whose *first* generated token (sampled from prefill) is
    EOS must stop immediately instead of decoding for max_new steps."""
    import jax

    from repro.configs import get_smoke
    from repro.models.schema import init_params
    from repro.serve.engine import Engine, EngineConfig

    cfg = get_smoke("qwen1.5-0.5b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    free = Engine(cfg, params, EngineConfig(slots=1, temperature=0.0)) \
        .generate([[1, 2]], max_new=6)[0]
    first = free[2]                       # the prefill-sampled token
    out = Engine(cfg, params,
                 EngineConfig(slots=1, temperature=0.0, eos_id=int(first))) \
        .generate([[1, 2]], max_new=6)[0]
    assert out == [1, 2, int(first)]


# Prompts of uneven lengths: two waves of a 3-slot engine, so the rng a
# wave leaves behind feeds the next one's sampling.
ID_PROMPTS = [[1, 2, 3], [4, 5], [6, 7, 8, 9, 10], [11], [12, 13, 14, 15]]


@pytest.fixture(scope="module")
def stepwise():
    """``(engine, free)`` for ``(slots, temperature, max_new)``: one engine
    per slots and temperature, and the step-by-step loop's tokens with no
    EOS, both shared by the cases that need them."""
    import jax

    from repro.configs import get_smoke
    from repro.models.schema import init_params
    from repro.serve import Engine, EngineConfig
    cfg = get_smoke("qwen1.5-0.5b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    engines, frees = {}, {}

    def get(slots, temperature, max_new):
        key = (slots, temperature)
        if key not in engines:
            engines[key] = Engine(cfg, params, EngineConfig(
                slots=slots, temperature=temperature, seed=5))
        engine = engines[key]
        engine.ecfg.eos_id = -1
        if key + (max_new,) not in frees:
            frees[key + (max_new,)] = _stepwise_generate(engine, ID_PROMPTS,
                                                         max_new)
        return engine, frees[key + (max_new,)]
    return get


def _stepwise_generate(engine, prompts, max_new):
    """The engine's algorithm one step at a time: each decode step is
    dispatched only after the host has read the last step's tokens, one
    ``int()`` per row, and EOS is checked before every step."""
    import jax
    import jax.numpy as jnp

    from repro.models import model as M
    ecfg = engine.ecfg
    results = [None] * len(prompts)
    rng = jax.random.PRNGKey(ecfg.seed)
    for wave in plan_waves(range(len(prompts)), ecfg.slots):
        plen = max(len(prompts[i]) for i in wave)
        batch = np.zeros((len(wave), plen), np.int32)
        for r, i in enumerate(wave):
            batch[r, plen - len(prompts[i]):] = prompts[i]
        logits, cache = M.prefill(engine.params, engine.cfg,
                                  tokens=jnp.asarray(batch),
                                  pad_to=plen + max_new + 1)
        toks = [list(prompts[i]) for i in wave]
        last = engine._sample(logits, rng)
        done = np.zeros(len(wave), bool)
        for r in range(len(wave)):
            toks[r].append(int(last[r]))
            done[r] = toks[r][-1] == ecfg.eos_id
        for t in range(max_new - 1):
            if done.all():
                break
            rng, sub = jax.random.split(rng)
            logits, cache = engine.decode_fn(
                engine.params, cache, last[:, None],
                jnp.asarray(plen + t, jnp.int32))
            last = engine._sample(logits, sub)
            for r in range(len(wave)):
                if not done[r]:
                    toks[r].append(int(last[r]))
                    done[r] = toks[r][-1] == ecfg.eos_id
        for r, i in enumerate(wave):
            results[i] = toks[r]
    return results


def _staggered_eos(outs, prompts):
    """The new token first reached at the most distinct steps across
    requests."""
    firsts = {}
    for p, o in zip(prompts, outs):
        new = o[len(p):]
        for tok in set(new):
            firsts.setdefault(tok, set()).add(new.index(tok))
    return max(sorted(firsts), key=lambda tok: len(firsts[tok]))


@pytest.mark.parametrize("slots,temperature,max_new,eos", [
    (3, temp, n, eos) for temp in (0.0, 0.7) for n in (1, 2, 8)
    for eos in ("none", "prefill", "staggered")] + [
    (1, temp, 8, "staggered") for temp in (0.0, 0.7)])
def test_engine_matches_stepwise_generation(stepwise, slots, temperature,
                                            max_new, eos):
    """The one-step-behind loop returns the tokens of the step-by-step
    loop: greedy and sampled, EOS out of prefill and EOS at different steps
    per row (with one slot, whole waves end early and the next wave samples
    from the rng the early one left)."""
    engine, free = stepwise(slots, temperature, max_new)
    assert all(len(o) - len(p) == max_new
               for p, o in zip(ID_PROMPTS, free))
    want = free
    if eos != "none":
        engine.ecfg.eos_id = (free[0][len(ID_PROMPTS[0])] if eos == "prefill"
                              else _staggered_eos(free, ID_PROMPTS))
        want = _stepwise_generate(engine, ID_PROMPTS, max_new)
    if eos == "staggered" and max_new == 8:
        stops = {len(o) - len(p) for p, o in zip(ID_PROMPTS, want)
                 if o[-1] == engine.ecfg.eos_id}
        assert len(stops) >= 2 and min(stops) < max_new
    assert engine.generate(ID_PROMPTS, max_new) == want


def test_decode_step_donates_the_cache_and_keeps_its_name(stepwise):
    """One ``engine.decode_fn`` call consumes the cache it is given: every
    leaf of it is deleted afterwards (the step writes its rows into those
    buffers, which the returned cache takes over), and the lowered step
    keeps the name ``jit_decode_step`` and aliases each cache input to an
    output."""
    import jax
    import jax.numpy as jnp

    from repro.models import model as M
    engine, _ = stepwise(3, 0.0, 1)
    _, cache = M.prefill(engine.params, engine.cfg,
                         tokens=jnp.ones((2, 5), jnp.int32), pad_to=9)
    args = (engine.params, cache, jnp.zeros((2, 1), jnp.int32),
            jnp.asarray(5, jnp.int32))
    text = engine.decode_fn.lower(*args).as_text()
    assert text.startswith("module @jit_decode_step")
    leaves = jax.tree.leaves(cache)
    assert text.count("tf.aliasing_output") == len(leaves)
    _, new = engine.decode_fn(*args)
    assert all(a.is_deleted() for a in leaves)
    assert jax.tree.structure(new) == jax.tree.structure(cache)
