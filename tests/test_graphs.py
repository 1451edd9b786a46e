"""Device-resident kernel graphs: ``Request.deps`` edges through the
dependency-aware scheduler, engine-level staged-buffer patches,
``compile_graph`` reduction-boundary splitting, the ``serve.graphs``
program surface, fleet co-location with learned (kernel, schedule)
service times, cascade quarantine, and the open-loop load generator
replayed against a ``Fleet``."""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

from repro.compiler import compile_graph
from repro.ggpu import programs
from repro.ggpu.engine import GGPUConfig, Patch, launch, run_kernel
from repro.ggpu.isa import Assembler
from repro.serve import (Dep, DependencyError, Fleet, Request, Scheduler,
                         bursty_arrivals, extract_outputs, replay,
                         run_chains_host_staged, run_program,
                         run_program_host_staged, run_programs_host_staged,
                         submit_program, submit_programs)

CFG = GGPUConfig(n_cus=2)
N, SEG = 64, 16


@pytest.fixture(scope="module")
def program():
    """3-stage map -> segmented reduce -> scale chain."""
    return compile_graph(lambda a, b: (a * b).seg_sum(SEG) * 3 + 1,
                         {"a": N, "b": N}, name="mrs")


def _inputs(seed):
    rng = np.random.default_rng(seed)
    return {"a": rng.integers(-50, 50, N).astype(np.int32),
            "b": rng.integers(-50, 50, N).astype(np.int32)}


def _spinner():
    a = Assembler()
    a.label("spin").beq(0, 0, "spin")
    return a.assemble()


# -- engine: staged-buffer patches ---------------------------------------


def test_single_launch_patch_matches_host_patch():
    """A device patch applied to the staged buffer is bit-exact with
    patching the host image before launch."""
    import jax.numpy as jnp
    b = programs._copy(16, 128)
    lo, hi = 3, 19
    src = np.arange(lo, hi, dtype=np.int32) * 7
    patched = b.gpu_mem.copy()
    patched[lo:hi] = src
    direct = run_kernel(b.gpu_prog, patched, b.gpu_items, CFG)
    h = launch([b.gpu_prog], [b.gpu_mem], [b.gpu_items], CFG,
               patches=[Patch(lo, hi, jnp.asarray(src)[None])])
    mem, info = h.result()
    np.testing.assert_array_equal(mem, direct[0])
    assert info["cycles"] == direct[1]["cycles"]


def test_cohort_patch_block_and_per_launch_match():
    """A cohort patched by one all-launch ``Patch`` and by one ``Patch``
    per launch (``rows=(i,)``) both reproduce host-side patching, member
    by member."""
    import jax.numpy as jnp
    b = programs._copy(16, 128)
    B, lo, hi = 3, 8, 24
    rng = np.random.default_rng(0)
    mems = [rng.integers(-20, 20, b.gpu_mem.shape[0]).astype(np.int32)
            for _ in range(B)]
    block = rng.integers(-99, 99, (B, hi - lo)).astype(np.int32)
    direct = []
    for m, row in zip(mems, block):
        p = m.copy()
        p[lo:hi] = row
        direct.append(run_kernel(b.gpu_prog, p, b.gpu_items, CFG))
    args = ([b.gpu_prog] * B, mems, [b.gpu_items] * B, CFG)
    fused = launch(*args, patches=[Patch(lo, hi, jnp.asarray(block))])
    per = launch(*args, patches=[Patch(lo, hi, jnp.asarray(row)[None],
                                       rows=(i,))
                                 for i, row in enumerate(block)])
    for (dm, di), (fm, fi), (pm, pi) in zip(direct, fused.results(),
                                            per.results()):
        np.testing.assert_array_equal(fm, dm)
        np.testing.assert_array_equal(pm, dm)
        assert fi["cycles"] == di["cycles"] == pi["cycles"]


@pytest.mark.parametrize("bad", [
    Patch(5, 2, np.zeros((1, 0), np.int32)),              # hi < lo
    Patch(0, 4, np.zeros((1, 3), np.int32)),              # block width
    Patch(0, 4, np.zeros((2, 4), np.int32)),              # block rows
    Patch(0, 4, np.zeros((1, 4), np.int32), rows=(1,)),   # no launch 1
    Patch(10**6, 10**6 + 4, np.zeros((1, 4), np.int32)),  # past the image
    Patch(0, 4, np.zeros((1, 4), np.int32), op="add"),    # unknown op
])
def test_patch_validation(bad):
    b = programs._copy(16, 128)
    with pytest.raises(ValueError):
        launch([b.gpu_prog], [b.gpu_mem], [b.gpu_items], CFG,
               patches=[bad])


@pytest.mark.parametrize("path", ["cohort", "batch"])
def test_device_mem_reads_each_launch_on_the_row_view(path):
    """``device_mem`` and ``device_mem_block`` read each launch's final
    words out of the row view of either layout — including a padded
    cohort's real members and an empty region — without a host hop."""
    b = programs._copy(16, 128)
    rng = np.random.default_rng(4)
    mems = [rng.integers(-20, 20, b.gpu_mem.shape[0]).astype(np.int32)
            for _ in range(3)]                     # a cohort of 3 pads to 4
    progs = [b.gpu_prog] * 3
    if path == "batch":
        progs[1] = np.vstack([b.gpu_prog,
                              np.zeros_like(b.gpu_prog[:1])])
    want = [run_kernel(b.gpu_prog, m, b.gpu_items, CFG)[0] for m in mems]
    h = launch(progs, mems, [b.gpu_items] * 3, CFG)
    assert h.envelope[0] == path
    lo, hi = b.gpu_out.start, b.gpu_out.stop
    for i, w in enumerate(want):
        np.testing.assert_array_equal(np.asarray(h.device_mem(i)), w)
        np.testing.assert_array_equal(np.asarray(h.device_mem(i, (lo, hi))),
                                      w[lo:hi])
        assert h.device_mem(i, (lo, lo)).shape == (0,)
    np.testing.assert_array_equal(np.asarray(h.device_mem_block(lo, hi)),
                                  np.stack([w[lo:hi] for w in want]))


# One interpreter with 8 forced host devices runs one Patch list — a set
# on a row subset, then an xor on every row — on each memory layout and on
# the host images it describes. The sharded cohort splits 8 launches 1 a
# device over the 8-way mesh.
PATCH_LAYOUTS = textwrap.dedent("""
    import json, os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import numpy as np
    from repro.ggpu import programs
    from repro.ggpu.engine import GGPUConfig, Patch, launch, run_kernel
    from repro.launch.mesh import make_launch_mesh
    assert jax.device_count() == 8, jax.device_count()
    cfg = GGPUConfig(n_cus=2)
    b = programs._copy(16, 128)
    rng = np.random.default_rng(3)
    n = 8
    mems = [rng.integers(-20, 20, b.gpu_mem.shape[0]).astype(np.int32)
            for _ in range(n)]
    pad = np.vstack([b.gpu_prog, np.zeros((1, b.gpu_prog.shape[1]),
                                          np.int32)])
    sets = (1, 4, 6)
    set_block = rng.integers(-99, 99, (len(sets), 16)).astype(np.int32)
    xor_block = rng.integers(0, 2**20, (n, 8)).astype(np.int32)
    patches = [Patch(3, 19, jax.numpy.asarray(set_block), rows=sets),
               Patch(10, 18, xor_block, op="xor")]
    host = [m.copy() for m in mems]
    for k, i in enumerate(sets):
        host[i][3:19] = set_block[k]
    for i in range(n):
        host[i][10:18] ^= xor_block[i]
    out = {}
    for layout, progs, mesh in [
            ("flat-cohort", [b.gpu_prog] * n, None),
            ("sharded-cohort", [b.gpu_prog] * n, make_launch_mesh()),
            ("batch", [b.gpu_prog, pad] * (n // 2), None)]:
        h = launch(progs, mems, [b.gpu_items] * n, cfg, patches=patches,
                   mesh=mesh)
        want = [run_kernel(p, m, b.gpu_items, cfg)
                for p, m in zip(progs, host)]
        got = h.results()
        out[layout] = {
            "path": h.envelope[0], "devices": len(h.devices()),
            "bits": all(np.array_equal(g[0], w[0])
                        for g, w in zip(got, want)),
            "cycles": all(g[1]["cycles"] == w[1]["cycles"]
                          for g, w in zip(got, want))}
    print("LAYOUTS " + json.dumps(out))
""")


@pytest.fixture(scope="module")
def patched_layouts():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    src = str(Path(__file__).resolve().parent.parent / "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", PATCH_LAYOUTS], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr
    line = [x for x in proc.stdout.splitlines()
            if x.startswith("LAYOUTS ")][-1]
    return json.loads(line[len("LAYOUTS "):])


@pytest.mark.parametrize("layout, path, devices", [
    ("flat-cohort", "cohort", 1), ("sharded-cohort", "cohort", 8),
    ("batch", "batch", 1)])
def test_patch_list_matches_host_patching(patched_layouts, layout, path,
                                          devices):
    """The same ``Patch`` list (a set on a row subset, then an xor on all
    rows) gives the bits and cycles of patching the host images before
    launch, on every layout of the staged memory."""
    got = patched_layouts[layout]
    assert (got["path"], got["devices"]) == (path, devices)
    assert got["bits"] and got["cycles"]


# -- scheduler: dependency edges -----------------------------------------


def test_manual_dep_chain_bit_exact():
    """A hand-built producer->consumer edge: the consumer's window is
    overwritten with the producer's output on the device, matching the
    host-composed run exactly — and both serve in ONE drain call."""
    b = programs._copy(16, 128)
    s = Scheduler(CFG)
    t0 = s.submit(b.gpu_prog, b.gpu_mem, b.gpu_items)
    lo, hi = b.gpu_out.start, b.gpu_out.stop
    consumer_mem = b.gpu_mem.copy()
    consumer_mem[lo:hi] = 0                          # placeholder words
    t1 = s.submit(b.gpu_prog, consumer_mem, b.gpu_items,
                  deps=[Dep(t0, (lo, hi), (lo, hi))])
    results = {r.info["ticket"]: r for r in s.drain()}
    assert set(results) == {t0, t1}
    prod_mem, _ = run_kernel(b.gpu_prog, b.gpu_mem, b.gpu_items, CFG)
    host = consumer_mem.copy()
    host[lo:hi] = np.asarray(prod_mem)[lo:hi]
    cons_mem, _ = run_kernel(b.gpu_prog, host, b.gpu_items, CFG)
    np.testing.assert_array_equal(results[t1].mem, cons_mem)
    # residency released once the last consumer collected
    assert s._resident == {} and s._dep_waiters == {}


def test_dep_src_defaults_to_producer_out_region():
    """``Dep.src=None`` pins to the producer's declared out_region."""
    b = programs._copy(16, 128)
    lo, hi = b.gpu_out.start, b.gpu_out.stop
    s = Scheduler(CFG)
    t0 = s.submit(b.gpu_prog, b.gpu_mem, b.gpu_items, out_region=(lo, hi))
    t1 = s.submit(b.gpu_prog, b.gpu_mem, b.gpu_items,
                  deps=[Dep(t0, (lo, hi))])
    req = s._pending[t1]
    assert req.deps[0].src == (lo, hi)
    assert len(s.drain()) == 2


def test_dep_validation_bounces_at_admission():
    b = programs._copy(16, 128)
    s = Scheduler(CFG)
    t0 = s.submit(b.gpu_prog, b.gpu_mem, b.gpu_items)
    with pytest.raises(ValueError):                  # unknown producer
        s.submit(b.gpu_prog, b.gpu_mem, b.gpu_items,
                 deps=[Dep(999, (0, 4), (0, 4))])
    with pytest.raises(ValueError):                  # width mismatch
        s.submit(b.gpu_prog, b.gpu_mem, b.gpu_items,
                 deps=[Dep(t0, (0, 4), (0, 8))])
    with pytest.raises(ValueError):                  # src out of bounds
        s.submit(b.gpu_prog, b.gpu_mem, b.gpu_items,
                 deps=[Dep(t0, (0, 4), (10 ** 6, 10 ** 6 + 4))])
    # producer with the empty out_region needs an explicit src
    t2 = s.submit(b.gpu_prog, b.gpu_mem, b.gpu_items, out_region=(0, 0))
    with pytest.raises(ValueError):
        s.submit(b.gpu_prog, b.gpu_mem, b.gpu_items, deps=[Dep(t2, (0, 4))])
    # a consumer cannot cancel a producer out from under its waiters
    t3 = s.submit(b.gpu_prog, b.gpu_mem, b.gpu_items,
                  deps=[Dep(t0, (0, 4), (0, 4))])
    with pytest.raises(ValueError):
        s.cancel(t0)
    s.cancel(t3)
    assert len(s.drain()) == 2                   # t0 and t2 still pending


def test_residency_survives_across_drains():
    """A producer collected in an earlier drain stays resident (its
    device buffer sliceable) while consumers admitted before that drain
    are still pending — the consumer completes bit-exactly later."""
    b = programs._copy(16, 128)
    lo, hi = b.gpu_out.start, b.gpu_out.stop
    s = Scheduler(CFG)
    t0 = s.submit(b.gpu_prog, b.gpu_mem, b.gpu_items)
    t1 = s.submit(b.gpu_prog, b.gpu_mem, b.gpu_items,
                  deps=[Dep(t0, (lo, hi), (lo, hi))])
    first = s.drain(budget=1)                    # serves only the producer
    assert [r.info["ticket"] for r in first] == [t0]
    assert t0 in s._resident                     # held for the consumer
    prod_mem, _ = run_kernel(b.gpu_prog, b.gpu_mem, b.gpu_items, CFG)
    host = b.gpu_mem.copy()
    host[lo:hi] = np.asarray(prod_mem)[lo:hi]
    (res,) = s.drain()
    assert res.info["ticket"] == t1
    np.testing.assert_array_equal(
        res.mem, run_kernel(b.gpu_prog, host, b.gpu_items, CFG)[0])
    assert s._resident == {}


def test_dependency_cascade_quarantine():
    """A poisoned producer quarantines its consumers transitively with
    ``DependencyError`` — they never compute on placeholder zeros."""
    cfg = GGPUConfig(max_steps=50)
    b = programs._copy(16, 128)
    s = Scheduler(cfg)
    t_bad = s.submit(_spinner(), np.zeros(8, np.int32), 8)
    t_mid = s.submit(b.gpu_prog, b.gpu_mem, b.gpu_items,
                     deps=[Dep(t_bad, (0, 4), (0, 4))])
    t_leaf = s.submit(b.gpu_prog, b.gpu_mem, b.gpu_items,
                      deps=[Dep(t_mid, (0, 4), (0, 4))])
    t_ok = s.submit(b.gpu_prog, b.gpu_mem, b.gpu_items)
    results = s.drain()
    assert [r.info["ticket"] for r in results] == [t_ok]
    assert set(s.quarantined) == {t_bad, t_mid, t_leaf}
    assert "max_steps" in str(s.quarantined[t_bad].error)
    for t in (t_mid, t_leaf):
        assert isinstance(s.quarantined[t].error, DependencyError)
    assert len(s) == 0 and s.inflight_chunks == 0
    assert s._resident == {} and s._dep_waiters == {} and s._poisoned == {}


def test_drain_abandons_cleanly_through_repeated_failures():
    """Regression: two successive unexpected mid-drain failures must not
    double-count ``inflight_chunks`` or double-serve — abandoned chunks
    go back to pending exactly once, and the final drain returns every
    ticket exactly once (dep chains included)."""
    b = programs._copy(16, 128)
    fir = programs._fir(16, 64)
    lo, hi = b.gpu_out.start, b.gpu_out.stop
    s = Scheduler(CFG)
    t0 = s.submit(b.gpu_prog, b.gpu_mem, b.gpu_items)
    t1 = s.submit(b.gpu_prog, b.gpu_mem, b.gpu_items,
                  deps=[Dep(t0, (lo, hi), (lo, hi))])
    t2 = s.submit(fir.gpu_prog, fir.gpu_mem, fir.gpu_items)
    real_collect = s.executor.collect
    boom = {"armed": True}

    def exploding(pending):
        if boom["armed"]:
            raise ValueError("malformed launch")
        return real_collect(pending)

    s.executor.collect = exploding
    for attempt in range(2):
        with pytest.raises(ValueError):
            s.drain()
        assert s.inflight_chunks == 0, "abandoned chunks must not linger"
        assert sorted(s.pending_tickets) == [t0, t1, t2]
        assert s._completed == [] or attempt == 0
    s.executor.collect = real_collect
    boom["armed"] = False
    results = s.drain()
    assert [r.info["ticket"] for r in results] == [t0, t1, t2]
    assert s.drain() == []                       # nothing double-served
    assert s.inflight_chunks == 0 and len(s) == 0
    # the dep chain still executed device-resident and bit-exact
    prod_mem, _ = run_kernel(b.gpu_prog, b.gpu_mem, b.gpu_items, CFG)
    host = b.gpu_mem.copy()
    host[lo:hi] = np.asarray(prod_mem)[lo:hi]
    np.testing.assert_array_equal(
        results[1].mem, run_kernel(b.gpu_prog, host, b.gpu_items, CFG)[0])


# -- compiler: reduction-boundary splitting ------------------------------


def test_compile_graph_splits_at_reduction(program):
    assert [ck.name for ck in program.stages] == ["mrs_s0", "mrs_s1",
                                                  "mrs_s2"]
    kinds = [sorted(k for k, _ in program.sources[i].values())
             for i in range(3)]
    assert kinds[0] == ["input", "input"]        # map: a, b
    assert kinds[1] == ["stage"]                 # reduce feeds on the map
    assert kinds[2] == ["stage"]                 # scale feeds on the reduce
    ins = _inputs(0)
    expect = ((ins["a"].astype(np.int64) * ins["b"])
              .reshape(-1, SEG).sum(axis=1) * 3 + 1).astype(np.int32)
    np.testing.assert_array_equal(program.reference(ins), expect)
    np.testing.assert_array_equal(program.run_host(ins, CFG), expect)


def test_compile_graph_single_stage_when_no_reduction():
    prog = compile_graph(lambda a, b: a * b + 1, {"a": 16, "b": 16})
    assert len(prog.stages) == 1
    ins = {"a": np.arange(16, dtype=np.int32),
           "b": np.full(16, 3, np.int32)}
    sched = Scheduler(CFG)
    np.testing.assert_array_equal(run_program(sched, prog, ins),
                                  prog.reference(ins))


def test_compile_graph_chained_reductions():
    prog = compile_graph(lambda a: (a * 2).seg_sum(8).seg_sum(4),
                         {"a": 64})
    assert len(prog.stages) >= 3                 # map, reduce, reduce
    ins = {"a": np.arange(64, dtype=np.int32)}
    sched = Scheduler(CFG)
    np.testing.assert_array_equal(run_program(sched, prog, ins),
                                  prog.reference(ins))


# -- serve.graphs: programs end to end -----------------------------------


def test_run_program_matches_reference_and_host_staged(program):
    ins = _inputs(1)
    sched = Scheduler(CFG)
    out = run_program(sched, program, ins)
    ref = program.reference(ins)
    np.testing.assert_array_equal(out, ref)
    np.testing.assert_array_equal(
        run_program_host_staged(Scheduler(CFG), program, ins), ref)
    assert sched.quarantined == {}
    # interior stages never declared a download
    assert sched._resident == {} and len(sched) == 0


def test_submit_programs_folds_stage_major(program):
    """N instances stage-major: every stage folds into one cohort
    dispatch, every output is bit-exact, and both host-staged references
    agree."""
    n_inst = 4
    ins = [_inputs(10 + i) for i in range(n_inst)]
    refs = [program.reference(i) for i in ins]
    sched = Scheduler(CFG, max_batch=n_inst)
    d0 = sched.executor.stats.dispatches
    handles = submit_programs(sched, program, ins)
    outs = extract_outputs(sched.drain(), handles)
    assert sched.executor.stats.dispatches - d0 == len(program.stages)
    for o, r in zip(outs, refs):
        np.testing.assert_array_equal(o, r)
    for o, r in zip(run_chains_host_staged(Scheduler(CFG), program, ins),
                    refs):
        np.testing.assert_array_equal(o, r)
    for o, r in zip(run_programs_host_staged(Scheduler(CFG), program, ins),
                    refs):
        np.testing.assert_array_equal(o, r)


def test_submit_program_interleaves_with_other_traffic(program):
    """Graph requests coexist with plain launches in one drain."""
    b = programs._copy(16, 128)
    sched = Scheduler(CFG)
    t_plain = sched.submit(b.gpu_prog, b.gpu_mem, b.gpu_items)
    ins = _inputs(2)
    handle = submit_program(sched, program, ins, tag="g")
    results = sched.drain()
    tickets = [r.info["ticket"] for r in results]
    assert t_plain in tickets and handle.final in tickets
    np.testing.assert_array_equal(
        extract_outputs(results, [handle])[0], program.reference(ins))
    tags = {r.info.get("tag") for r in results}
    assert f"g:{program.stages[-1].name}" in tags


def test_graph_quarantine_surfaces_as_none(program):
    """A quarantined ancestor leaves that chain's final output as ``None``
    in ``extract_outputs`` while an independent healthy instance of the
    same program completes in the same drain."""
    from repro.serve import GraphTickets
    # generous step budget: the real program must complete — only the
    # spinner (which never halts) trips the bound
    cfg = GGPUConfig(n_cus=2, max_steps=5000)
    b = programs._copy(16, 128)
    sched = Scheduler(cfg)
    t_bad = sched.submit(_spinner(), np.zeros(8, np.int32), 8)
    t_leaf = sched.submit(b.gpu_prog, b.gpu_mem, b.gpu_items,
                          deps=[Dep(t_bad, (0, 4), (0, 4))])
    poisoned_chain = GraphTickets([t_bad, t_leaf])
    ins = _inputs(3)
    healthy = submit_program(sched, program, ins)
    outs = extract_outputs(sched.drain(), [poisoned_chain, healthy])
    assert outs[0] is None
    np.testing.assert_array_equal(outs[1], program.reference(ins))
    assert isinstance(sched.quarantined[t_leaf].error, DependencyError)


# -- fleet: co-location + learned service times --------------------------


def test_fleet_colocates_graph_and_learns_schedules(program):
    fleet = Fleet([("wide", GGPUConfig(n_cus=8)),
                   ("narrow", GGPUConfig(n_cus=1))])
    ins = _inputs(4)
    out = run_program(fleet, program, ins)
    np.testing.assert_array_equal(out, program.reference(ins))
    # all stages landed on one device
    assert len(set(fleet.placement.values())) == 1
    # learned table keys: (device, content-addressed kernel, schedule)
    assert fleet._learned
    for dev, kk, sched in fleet._learned:
        assert dev in ("wide", "narrow")
        assert isinstance(kk, tuple) and isinstance(sched, str)
    # a dep on a ticket this fleet never issued is rejected
    b = programs._copy(16, 128)
    with pytest.raises(ValueError):
        fleet.submit_request(Request(b.gpu_prog, b.gpu_mem, b.gpu_items,
                                     deps=(Dep(10 ** 6, (0, 4), (0, 4)),)))


def test_fleet_learned_table_updates_routing():
    """Learned service times are keyed per (kernel, schedule) and feed
    ``estimate_us``: after serving, the estimate for that exact kernel
    reflects the measured time, not the generic model."""
    b = programs._copy(16, 128)
    fleet = Fleet([("only", CFG)])
    fleet.submit(b.gpu_prog, b.gpu_mem, b.gpu_items)
    fleet.drain()
    (key,) = [k for k in fleet._learned]
    assert key[0] == "only" and key[2] == ""     # untuned: empty schedule
    assert fleet._learned[key] > 0


# -- loadgen: bursty arrivals against a Fleet ----------------------------


def test_bursty_arrivals_deterministic_per_seed():
    a = bursty_arrivals(3, 4, 0.002, seed=5)
    b = bursty_arrivals(3, 4, 0.002, seed=5)
    c = bursty_arrivals(3, 4, 0.002, seed=6)
    np.testing.assert_array_equal(a, b)
    assert a.shape == (12,) and not np.array_equal(a, c)
    # bursts are simultaneous: 3 distinct start times
    assert len(np.unique(a)) == 3


def test_bursty_replay_against_fleet_populates_latency():
    b = programs._copy(16, 128)
    fleet = Fleet([("wide", GGPUConfig(n_cus=4)), ("narrow", CFG)])
    # warm both devices so the replay measures steady state
    for _ in range(2):
        fleet.submit(b.gpu_prog, b.gpu_mem, b.gpu_items)
    fleet.drain()
    trace = bursty_arrivals(2, 3, 0.001, seed=9)
    res = replay(fleet, trace,
                 lambda i: Request(b.gpu_prog, b.gpu_mem, b.gpu_items))
    assert res.served == trace.size and res.quarantined == 0
    assert not np.isnan(res.latencies).any()
    assert (res.latencies > 0).all() and res.duration_s > 0
    rep = res.report()
    assert rep["p50_ms"] <= rep["p99_ms"] and rep["rate_per_s"] > 0


def test_bursty_replay_propagates_quarantine():
    """A spinner inside the burst is quarantined by its device scheduler,
    surfaces through ``Fleet.quarantined``, and the replay marks it nan
    without stalling the open loop."""
    cfg = GGPUConfig(max_steps=50)
    b = programs._copy(16, 128)
    fleet = Fleet([("only", cfg)])
    fleet.submit(b.gpu_prog, b.gpu_mem, b.gpu_items)
    fleet.drain()
    trace = bursty_arrivals(2, 2, 0.001, seed=3)
    bad = 2

    def make(i):
        if i == bad:
            return Request(_spinner(), np.zeros(8, np.int32), 8)
        return Request(b.gpu_prog, b.gpu_mem, b.gpu_items)

    res = replay(fleet, trace, make)
    assert res.quarantined == 1 and res.served == trace.size - 1
    assert np.isnan(res.latencies).sum() == 1
    assert len(fleet.quarantined) == 1
