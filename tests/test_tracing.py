"""``repro.tracing``: program spans on the profiler's clock, the compile
counter, and the named scopes of the model and the stepper."""
import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import tracing


@pytest.fixture
def tracer():
    tracing.reset_counters()
    tracing.enable()
    try:
        yield tracing
    finally:
        tracing.disable()
        tracing.reset_counters()


def _profiled(tmp_path, fn):
    """``fn()`` under the profiler; every host event as (name, start, end,
    stats)."""
    with jax.profiler.trace(str(tmp_path)):
        fn()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    return [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
             {k: v for k, v in ev.stats})
            for plane in pd.planes for line in plane.lines
            for ev in line.events]


def _program(events):
    return [e for e in events if e[0].startswith(tracing.PREFIX)]


def test_off_emits_no_event_and_keeps_no_stack(tmp_path):
    assert not tracing.enabled()
    assert tracing.span("a", wave=1) is tracing.span("b")
    depth = []

    def work():
        with tracing.span("outer", wave=0):
            with tracing.span("inner", chunk=1):
                depth.append(len(getattr(tracing._local, "stack", [])))
                jnp.ones(3).block_until_ready()
    assert _program(_profiled(tmp_path, work)) == []
    assert depth == [0]


def test_spans_nest_carry_ids_and_are_named_repro(tmp_path, tracer):
    def work():
        with tracing.span("outer", wave=3):
            with tracing.span("inner", chunk=7, launches=2):
                jnp.ones(3).block_until_ready()
    spans = {e[0]: e for e in _program(_profiled(tmp_path, work))}
    assert set(spans) == {"repro.outer", "repro.inner"}
    outer, inner = spans["repro.outer"], spans["repro.inner"]
    assert outer[1] <= inner[1] and inner[2] <= outer[2]
    assert outer[3] == {"wave": 3}
    assert inner[3] == {"chunk": 7, "launches": 2}
    assert getattr(tracing._local, "stack", []) == []


def test_a_compile_is_counted_under_its_innermost_span_only(tracer):
    x = jnp.arange(7.0)
    with tracing.span("outer"):
        with tracing.span("inner"):
            jax.jit(lambda v: v * 3.0 + 1.0)(x).block_until_ready()
        with tracing.span("quiet"):
            pass
    rows = tracing.counters()
    assert rows["inner"]["compiles"] == 1 and rows["inner"]["compile_s"] > 0
    assert set(rows) <= {"inner", ""}
    tracing.disable()
    jax.jit(lambda v: v * 5.0)(x).block_until_ready()
    assert tracing.counters() == rows


def test_a_cache_read_is_counted_under_its_span(tracer):
    with tracing.span("engine.prefill"):
        jax.monitoring.record_event(tracing.CACHE_READ_EVENT)
    assert tracing.counters()["engine.prefill"]["cache_reads"] == 1
    tracing.reset_counters()
    assert tracing.counters() == {}


def _scopes(lowered) -> set:
    """Every component of the op names in a lowered module's locations."""
    names = re.findall(r'loc\("([^"]*)"', lowered.as_text(debug_info=True))
    return {part for name in names for part in name.split("/")}


def _smoke_model():
    from repro.configs import get_smoke
    from repro.models.schema import init_params
    cfg = get_smoke("qwen1.5-0.5b")
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


def test_decode_step_carries_the_model_scopes_and_keeps_its_name():
    from repro.models import model as M
    from repro.models.steps import make_decode_step
    cfg, params = _smoke_model()
    cache = M.init_cache(cfg, 2, 8)
    lowered = jax.jit(make_decode_step(cfg)).lower(
        params, cache, jnp.zeros((2, 1), jnp.int32),
        jnp.asarray(3, jnp.int32))
    assert lowered.as_text().startswith("module @jit_decode_step")
    assert {"attn", "mlp", "lm_head"} <= _scopes(lowered)


def test_cohort_stepper_carries_the_stage_scopes_and_keeps_its_name():
    from repro.ggpu import programs
    from repro.ggpu.engine import GGPUConfig, stepper
    b = programs._fir(16, 64)
    cfg = GGPUConfig(n_cus=2)
    B = 2
    lowered = stepper._run_cohort.lower(
        jnp.asarray(b.gpu_prog),
        jnp.zeros((B * b.gpu_mem.shape[0] + 1,), jnp.int32),
        jnp.asarray(b.gpu_items, jnp.int32), cfg=cfg, B=B,
        W=stepper._n_wavefronts(b.gpu_items, cfg),
        prog_len=b.gpu_prog.shape[0], ops=stepper._static_ops(b.gpu_prog))
    assert lowered.as_text().startswith("module @jit__run_cohort")
    assert {"schedule", "frontend", "alu", "memsys", "cost"} \
        <= _scopes(lowered)


def test_engine_spans_one_wave(tmp_path, tracer, monkeypatch):
    from repro.serve import Engine, EngineConfig
    cfg, params = _smoke_model()
    engine = Engine(cfg, params, EngineConfig(slots=2))
    max_new = 4
    pulled = []
    device_get = jax.device_get

    def counting(x):
        pulled.append(np.shape(x))
        return device_get(x)
    monkeypatch.setattr(jax, "device_get", counting)
    events = _program(_profiled(
        tmp_path, lambda: engine.generate([[1, 2, 3], [4, 5]], max_new)))
    assert pulled == [(2,)] * max_new        # one whole-vector pull a step
    names = [e[0][len(tracing.PREFIX):] for e in events]
    assert names.count("engine.generate") == 1
    assert names.count("engine.wave") == 1
    assert names.count("engine.prefill") == 1
    assert names.count("engine.decode") == max_new - 1
    assert names.count("engine.sample") == max_new
    assert names.count("engine.token_pull") == max_new
    # each pull but the last has the next decode step queued behind it
    pulls = sorted((e for e in events if e[0] == "repro.engine.token_pull"),
                   key=lambda e: e[1])
    assert [p[3] for p in pulls] \
        == [{"ahead": 1}] * (max_new - 1) + [{"ahead": 0}]
    wave, = [e for e in events if e[0] == "repro.engine.wave"]
    assert wave[3] == {"wave": 0}
    assert all(wave[1] <= e[1] and e[2] <= wave[2] for e in events
               if e[0] not in ("repro.engine.generate", "repro.engine.wave"))
    # the prefill's and decode's first compiles happened in their spans
    rows = tracing.counters()
    assert rows["engine.prefill"]["compiles"] >= 1
    assert rows["engine.decode"]["compiles"] >= 1


def test_engine_dispatches_one_step_past_a_wave_done(tmp_path, tracer):
    """Every row reaches EOS at its k-th new token: the step-by-step loop
    would dispatch k - 1 decode steps; the engine dispatches one more and
    drops its tokens."""
    from repro.serve import Engine, EngineConfig
    cfg, params = _smoke_model()
    prompts = [[7, 8, 9]] * 2                 # rows that agree, greedily
    max_new = 6
    free = Engine(cfg, params, EngineConfig(slots=2)).generate(prompts,
                                                               max_new)
    new = free[0][3:]
    assert free[1] == free[0]
    k = next(j for j in range(2, max_new) if new[j - 1] not in new[:j - 1])
    engine = Engine(cfg, params, EngineConfig(slots=2, eos_id=new[k - 1]))
    out = []
    events = _program(_profiled(
        tmp_path, lambda: out.extend(engine.generate(prompts, max_new))))
    assert out == [free[0][:3 + k]] * 2
    names = [e[0][len(tracing.PREFIX):] for e in events]
    assert names.count("engine.decode") == k <= max_new - 1
    assert names.count("engine.token_pull") == k
    assert names.count("engine.sample") == k + 1


def test_scheduler_and_executor_spans_share_the_chunk_id(tmp_path, tracer):
    from repro.ggpu import programs
    from repro.ggpu.engine import GGPUConfig
    from repro.serve import Request, Scheduler
    b = programs._copy(16, 128)
    sched = Scheduler(GGPUConfig(n_cus=2))
    for k in range(3):
        mem = np.full(b.gpu_mem.shape[0], k, np.int32)
        sched.submit_request(Request(b.gpu_prog, mem, b.gpu_items))
    events = _program(_profiled(tmp_path, sched.drain))
    by = {}
    for name, _, _, ids in events:
        by.setdefault(name[len(tracing.PREFIX):], []).append(ids)
    assert len(by["scheduler.plan"]) >= 1
    stage, = by["executor.stage"]
    assert stage["launches"] == 3
    assert by["executor.wait"] == [stage] == by["executor.download"]
