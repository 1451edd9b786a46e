"""Execution-engine invariants: fused dispatch and multi-launch calls
(cohort and batch) are bit-exact vs the single-launch path; ``launch``
picks its path from its input; the pluggable memory systems preserve
functional results; the LaunchQueue groups and orders correctly."""
import numpy as np
import pytest

from repro.ggpu import programs
from repro.ggpu.engine import (MEMSYS_REGISTRY, GGPUConfig, ScalarConfig,
                               get_memsys, launch, run_kernel)
from repro.ggpu.isa import Assembler
from repro.serve.engine import LaunchQueue


def _divergent_prog(n):
    a = Assembler()
    a.tid(1).andi(2, 1, 1).beq(2, 0, "even")
    a.mul(3, 1, 1).sw(3, 1, n).beq(0, 0, "end")
    a.label("even").slli(3, 1, 1).sw(3, 1, n)
    a.label("end").halt()
    return a.assemble()


def test_fused_dispatch_bit_exact():
    """fuse=1 (legacy, memsys every round) and fuse=8 (fused fast path)
    agree on results, cycles, stats, and step count."""
    b = programs._xcorr(32, 256)
    runs = {}
    for fuse in (1, 8):
        cfg = GGPUConfig(n_cus=2, fuse=fuse)
        runs[fuse] = run_kernel(b.gpu_prog, b.gpu_mem, b.gpu_items, cfg)
    mem1, i1 = runs[1]
    mem8, i8 = runs[8]
    np.testing.assert_array_equal(mem1, mem8)
    for k in ("cycles", "instrs", "mem_ops", "hits", "misses", "steps"):
        assert i1[k] == i8[k], k


def test_legacy_reference_bit_exact():
    """The seed-faithful legacy stepper and the optimized engine agree on
    everything observable."""
    b = programs._xcorr(32, 256)
    cfg = GGPUConfig(n_cus=2)
    mem_n, i_n = run_kernel(b.gpu_prog, b.gpu_mem, b.gpu_items, cfg)
    mem_l, i_l = run_kernel(b.gpu_prog, b.gpu_mem, b.gpu_items, cfg,
                            legacy=True)
    np.testing.assert_array_equal(mem_n, mem_l)
    for k in ("cycles", "instrs", "mem_ops", "hits", "misses", "steps"):
        assert i_n[k] == i_l[k], k


def _launch_inputs(path):
    """Three xcorr launches over different images (one kernel: a cohort),
    or a copy and a divergent kernel of different programs, memory sizes
    and item counts (a batch)."""
    if path == "cohort":
        b = programs._xcorr(32, 256)
        rng = np.random.default_rng(11)
        mems = [np.concatenate([rng.integers(-20, 20, 512).astype(np.int32),
                                np.zeros(256, np.int32)]) for _ in range(3)]
        return [(b.gpu_prog, m, b.gpu_items) for m in mems]
    c = programs._copy(64, 1024)
    n = 128
    return [(c.gpu_prog, c.gpu_mem, c.gpu_items),
            (_divergent_prog(n), np.zeros(2 * n, np.int32), n)]


@pytest.mark.parametrize("path", ["cohort", "batch"])
def test_launch_matches_single(path):
    """A same-kernel cohort (folded into the wavefront axis) and a batch
    of different programs/memory sizes/item counts (vmapped) reproduce
    each single launch bit-exactly, results AND cycle counts."""
    cfg = GGPUConfig(n_cus=2)
    launches = _launch_inputs(path)
    singles = [run_kernel(p, m, k, cfg) for p, m, k in launches]
    h = launch(*zip(*launches), cfg)
    assert h.envelope[0] == path
    for (ms, is_), (ml, il) in zip(singles, h.results()):
        np.testing.assert_array_equal(ms, ml)
        for key in ("cycles", "instrs", "mem_ops", "hits", "misses",
                    "steps"):
            assert is_[key] == il[key], key
        assert il["batch_size"] == len(launches)


def _pad(prog, rows=1):
    return np.vstack([prog, np.zeros((rows, prog.shape[1]), np.int32)])


@pytest.mark.parametrize("differ", ["nothing", "mem_size", "program",
                                    "n_items"])
def test_launch_picks_path_from_input(differ):
    """``launch`` stages a cohort exactly when every launch shares program
    bytes, item count and memory size, a batch otherwise — and reports the
    signature it jitted (cohort: bucketed rows, memory size; batch: the
    padded memory envelope) as ``envelope``."""
    b = programs._copy(64, 256)
    cfg = GGPUConfig()
    progs, mems, ns = [b.gpu_prog] * 3, [b.gpu_mem] * 3, [b.gpu_items] * 3
    if differ == "mem_size":
        mems = mems[:2] + [np.zeros(b.gpu_mem.shape[0] + 5, np.int32)]
    elif differ == "program":
        progs = progs[:2] + [_pad(b.gpu_prog)]
    elif differ == "n_items":
        ns = ns[:2] + [b.gpu_items - 1]
    h = launch(progs, mems, ns, cfg)
    W = -(-b.gpu_items // cfg.wavefront)
    if differ == "nothing":
        assert h.envelope[:5] == ("cohort", 4, W, b.gpu_prog.shape[0],
                                  b.gpu_mem.shape[0])
    else:
        assert h.envelope[:5] == ("batch", 3, W,
                                  max(p.shape[0] for p in progs),
                                  max(m.shape[0] for m in mems))
    assert len(h.results()) == 3


def test_batch_clips_at_each_launchs_own_memory_size():
    """In a mixed-size batch, an out-of-range address must clip at the
    launch's own memory boundary (reading its last word), not at the
    padded batch envelope (which would read padding zeros)."""
    n = 64
    a = Assembler()
    a.tid(1).li(2, 5000).lw(3, 2, 0).sw(3, 1, n).halt()   # read way OOB
    prog = a.assemble()
    mem_small = np.arange(2 * n, dtype=np.int32)          # last word: 127
    big = programs._copy(64, 1024)                        # forces padding
    single = run_kernel(prog, mem_small, n, GGPUConfig())
    batch = launch([prog, big.gpu_prog], [mem_small, big.gpu_mem],
                   [n, big.gpu_items], GGPUConfig()).results()
    np.testing.assert_array_equal(single[0], batch[0][0])
    assert batch[0][0][n] == 2 * n - 1                    # clipped in-image
    assert single[1]["cycles"] == batch[0][1]["cycles"]


def test_launch_empty_and_single():
    """One launch is a cohort of one, bit-exact with ``run_kernel``; an
    empty call, mismatched argument lengths and a multi-launch legacy
    call are refused."""
    b = programs._copy(64, 256)
    h = launch([b.gpu_prog], [b.gpu_mem], [b.gpu_items], GGPUConfig())
    assert h.envelope[:2] == ("cohort", 1)
    mem_l, info_l = h.result()
    mem_s, info_s = run_kernel(b.gpu_prog, b.gpu_mem, b.gpu_items,
                               GGPUConfig())
    np.testing.assert_array_equal(mem_l, mem_s)
    assert info_l["cycles"] == info_s["cycles"]
    with pytest.raises(ValueError):
        launch([], [], [], GGPUConfig())
    with pytest.raises(ValueError):
        launch([b.gpu_prog] * 2, [b.gpu_mem], [b.gpu_items] * 2,
               GGPUConfig())
    with pytest.raises(ValueError):
        launch([b.gpu_prog] * 2, [b.gpu_mem] * 2, [b.gpu_items] * 2,
               GGPUConfig(), legacy=True)


@pytest.mark.parametrize("memsys", sorted(MEMSYS_REGISTRY))
def test_memsys_functional_results_identical(memsys):
    """The memory system only changes cycle accounting — functional results
    are identical across organizations."""
    b = programs._xcorr(32, 256)
    cfg = GGPUConfig(n_cus=2, memsys=memsys)
    mem, info = run_kernel(b.gpu_prog, b.gpu_mem, b.gpu_items, cfg)
    np.testing.assert_array_equal(mem[b.gpu_out], b.ref(b.gpu_mem, 256))
    assert info["cycles"] > 0
    assert info["memsys"] == memsys


def test_banked_1cu_equals_shared():
    """With one CU and full-size banks the banked organization degenerates
    to the shared cache: cycles must match exactly."""
    b = programs._xcorr(32, 256)
    _, shared = run_kernel(b.gpu_prog, b.gpu_mem, b.gpu_items,
                           GGPUConfig(n_cus=1, memsys="shared"))
    _, banked = run_kernel(b.gpu_prog, b.gpu_mem, b.gpu_items,
                           GGPUConfig(n_cus=1, memsys="banked"))
    for k in ("cycles", "hits", "misses"):
        assert shared[k] == banked[k], k


def test_banked_8cu_model_properties():
    """At 8 CUs on a working set that fits every organization: banks fill
    independently (no cross-CU MSHR coalescing), so the banked cache pays
    at least the shared cache's compulsory misses — while hits split across
    banks. The DSE sweep (table_memsys) reports which effect wins."""
    b = programs._xcorr(32, 512)
    _, shared = run_kernel(b.gpu_prog, b.gpu_mem, b.gpu_items,
                           GGPUConfig(n_cus=8, memsys="shared"))
    _, banked = run_kernel(b.gpu_prog, b.gpu_mem, b.gpu_items,
                           GGPUConfig(n_cus=8, memsys="banked"))
    assert banked["misses"] >= shared["misses"]
    assert banked["hits"] + banked["misses"] == shared["hits"] + shared["misses"]
    assert banked["cycles"] > 0


def test_get_memsys_unknown_name():
    with pytest.raises(KeyError):
        get_memsys("l3-victim")


def test_launch_queue_orders_and_groups():
    """Tickets come back in submission order; same-wavefront launches share
    one batch, odd shapes fall back to singletons."""
    cfg = GGPUConfig(n_cus=2)
    q = LaunchQueue(cfg)
    c1 = programs._copy(64, 1024)       # W = 16
    c2 = programs._copy(64, 256)        # W = 4
    rng = np.random.default_rng(3)
    mems = [np.concatenate([rng.integers(-50, 50, 1024).astype(np.int32),
                            np.zeros(1024, np.int32)]) for _ in range(3)]
    t0 = q.submit(c1.gpu_prog, mems[0], c1.gpu_items)
    t1 = q.submit(c2.gpu_prog, c2.gpu_mem, c2.gpu_items)
    t2 = q.submit(c1.gpu_prog, mems[1], c1.gpu_items)
    t3 = q.submit(c1.gpu_prog, mems[2], c1.gpu_items)
    assert [t0, t1, t2, t3] == [0, 1, 2, 3]
    assert len(q) == 4
    results = q.flush()
    assert len(q) == 0 and len(results) == 4
    for t, m in zip((t0, t2, t3), mems):
        mem, info = results[t]
        np.testing.assert_array_equal(mem[c1.gpu_out], m[:1024])
        assert info["batch_size"] == 3          # grouped by wavefront count
    mem, info = results[t1]
    np.testing.assert_array_equal(mem[c2.gpu_out], c2.gpu_mem[:256])
    assert info["batch_size"] == 1              # singleton fallback


def test_launch_queue_restores_on_failure_and_surfaces_tags():
    """A failed flush re-queues every launch (retryable after dropping the
    bad request); submission tags come back in info['tag']."""
    q = LaunchQueue(GGPUConfig(max_steps=50))
    b = programs._copy(64, 256)
    spin = Assembler()
    spin.label("spin").beq(0, 0, "spin")        # never halts
    q.submit(b.gpu_prog, b.gpu_mem, b.gpu_items, tag="good")
    t_bad = q.submit(spin.assemble(), np.zeros(8, np.int32), 8,
                     tag="spinner")
    with pytest.raises(RuntimeError):
        q.flush()
    assert len(q) == 2                           # nothing lost
    assert q.discard(t_bad).tag == "spinner"     # drop the poisoned launch
    (_, info), = q.flush()                       # rest of the burst retries
    assert info["tag"] == "good"


def test_launch_queue_drains_in_ticket_order(monkeypatch):
    """Regression: chunks must execute ordered by their earliest ticket —
    a pure function of submission order — not by cohort-dict/group
    iteration order (which used to run all cohorts before any batch or
    singleton, regardless of when they were submitted)."""
    import repro.serve.executors as sx
    order = []

    def spy(fn):
        def wrapper(progs, *args, **kw):
            order.append(len(progs))
            return fn(progs, *args, **kw)
        return wrapper

    monkeypatch.setattr(sx, "launch", spy(sx.launch))

    cfg = GGPUConfig(n_cus=2)
    q = LaunchQueue(cfg)
    big = programs._copy(64, 1024)       # W=16: singleton bucket
    small = programs._copy(64, 256)      # W=4: cohort group
    t0 = q.submit(big.gpu_prog, big.gpu_mem, big.gpu_items)      # single
    t1 = q.submit(small.gpu_prog, small.gpu_mem, small.gpu_items)
    t2 = q.submit(small.gpu_prog, small.gpu_mem, small.gpu_items)
    results = q.flush()
    # ticket 0's singleton chunk must run before ticket 1's cohort
    assert order == [1, 2]
    assert [info["batch_size"] for _, info in results] == [1, 2, 2]
    for t in (t0, t1, t2):
        assert results[t] is not None


def test_launch_queue_chunk_plan_is_submission_deterministic():
    """The drain plan is identical for identical submission sequences and
    orders chunks by first ticket."""
    cfg = GGPUConfig()
    b1 = programs._copy(64, 256)
    b2 = programs._copy(64, 1024)

    def build():
        q = LaunchQueue(cfg, max_batch=2)
        q.submit(b2.gpu_prog, b2.gpu_mem, b2.gpu_items)   # 0: singleton
        for _ in range(3):                                # 1-3: cohort x2
            q.submit(b1.gpu_prog, b1.gpu_mem, b1.gpu_items)
        return q

    plan_a = build()._plan_chunks(build()._pending)
    plan_b = build()._plan_chunks(build()._pending)
    assert plan_a == plan_b
    firsts = [chunk[0] for _, chunk in plan_a]
    assert firsts == sorted(firsts)
    assert [k for k, _ in plan_a] == ["single", "cohort", "cohort"]


def test_launch_queue_respects_max_batch():
    cfg = GGPUConfig()
    q = LaunchQueue(cfg, max_batch=2)
    b = programs._copy(64, 256)
    for _ in range(3):
        q.submit(b.gpu_prog, b.gpu_mem, b.gpu_items)
    results = q.flush()
    assert [info["batch_size"] for _, info in results] == [2, 2, 1]


def test_scalar_runs_on_engine():
    """The scalar baseline flows through the same engine stages."""
    b = programs._copy(64, 256)
    mem, info = run_kernel(b.scalar_prog, b.scalar_mem, 1, ScalarConfig())
    np.testing.assert_array_equal(mem[b.scalar_out],
                                  b.ref(b.scalar_mem, b.scalar_n))
    assert info["cycles"] > 0


def test_planner_memsys_sweep():
    from repro.dse import sweep_memsys
    sweep = sweep_memsys(bench="xcorr", n_cus=(1,), sizes=(32, 128))
    # defaults must track the engine registry (single source of truth)
    assert set(sweep) == {(1, ms) for ms in MEMSYS_REGISTRY}
    for info in sweep.values():
        assert info["cycles"] > 0
