"""``chipbench/spans.py``: the readers of the program's spans and scopes,
on a small profile built to the profiler's schema (one chip with module
and operation lines, the operations carrying their op-name metadata, and a
host line with the harness's and the program's spans), and on a real CPU
profile of a tiny engine."""
from __future__ import annotations

import pytest

from chipbench import spans

US = 1_000          # ns per microsecond of the times below

DECODE = "jit(decode_step)/while/body/closed_call"
# (module, start, end) and (path, start, end), microseconds
MODULES = [("jit_scan(1)", 30, 90), ("jit_decode_step(2)", 210, 300),
           ("jit_decode_step(2)", 410, 500)]
OPS = [("jit(scan)/attn/flash", 30, 90)]
for base in (0, 200):
    OPS += [("jit(decode_step)/while", base + 210, base + 290),
            (f"{DECODE}/attn/dot_general", base + 215, base + 245),
            (f"{DECODE}/mlp/dot_general", base + 250, base + 280),
            ("jit(decode_step)/lm_head/dot_general", base + 290, base + 300)]
HARNESS = [("chipbench.window", 0, 1000, {}),
           ("chipbench.generate", 0, 1000, {})]
ENGINE = [("repro.engine.generate", 10, 990, {}),
          ("repro.engine.wave", 20, 980, {"wave": 0}),
          ("repro.engine.prefill", 20, 100, {}),
          ("repro.engine.sample", 100, 110, {}),
          ("repro.engine.token_pull", 110, 200, {})]
for base in (0, 200):
    ENGINE += [("repro.engine.decode", base + 200, base + 210, {}),
               ("repro.engine.sample", base + 210, base + 215, {}),
               ("repro.engine.token_pull", base + 215, base + 400, {})]


class _Meta:
    def __init__(self):
        self.events, self.stats, self.event_stats = {}, {}, {}

    def event(self, name, stats=None):
        if stats:
            self.event_stats[name] = stats
        return self.events.setdefault(name, len(self.events) + 1)

    def stat(self, name):
        return self.stats.setdefault(name, 100 + len(self.stats))

    def stats_text(self, stats) -> str:
        out = []
        for k, v in stats.items():
            kind = "str_value" if isinstance(v, str) else "int64_value"
            val = f'"{v}"' if isinstance(v, str) else v
            out.append(f"stats {{ metadata_id: {self.stat(k)} {kind}: {val} }}")
        return " ".join(out)

    def text(self):
        ev = " ".join(
            f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" '
            f"{self.stats_text(self.event_stats.get(n, {}))} }} }}"
            for n, i in self.events.items())
        st = " ".join(f'stat_metadata {{ key: {i} value {{ id: {i} name: '
                      f'"{n}" }} }}' for n, i in self.stats.items())
        return f"{ev} {st}"


def _event(meta, name, s, e, stats, op_stats=None) -> str:
    """An event with its own ``stats``; ``op_stats`` go to its metadata,
    where the profiler keeps an operation's op-name (``tf_op``)."""
    return (f"events {{ metadata_id: {meta.event(name, op_stats)} "
            f"offset_ps: {s * US * 1000} duration_ps: {(e - s) * US * 1000} "
            f"{meta.stats_text(stats)} }}")


def _plane(pid, name, lines) -> str:
    meta, body = _Meta(), []
    for lid, (lname, events) in enumerate(lines, 1):
        evs = " ".join(_event(meta, *ev) for ev in events)
        body.append(f'lines {{ id: {lid} name: "{lname}" timestamp_ns: 0 '
                    f"{evs} }}")
    return f'planes {{ id: {pid} name: "{name}" ' + " ".join(body) \
        + f" {meta.text()} }}"


def build(tmp_path, host, modules=MODULES, ops=OPS):
    import jax
    chip = _plane(10, "/device:TPU:0", [
        ("XLA Modules", [(n, s, e, {}) for n, s, e in modules]),
        ("XLA Ops", [(f"%op.{i}", s, e, {}, {"tf_op": f"{p}: 0"} if p
                      else None) for i, (p, s, e) in enumerate(ops)])])
    text = chip + "\n" + _plane(1, "/host:CPU", [("python", host)])
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(
        jax.profiler.ProfileData.text_proto_to_serialized_xspace(text))
    return spans.read_profile(str(path))


@pytest.fixture
def prof(tmp_path):
    return build(tmp_path, HARNESS + ENGINE)


CHIP = "/device:TPU:0"


def test_program_spans_keep_their_ids_and_lose_the_prefix(prof):
    wave, = prof.named("engine.wave")
    assert wave.ids == {"wave": 0}
    assert [s.name for s in prof.harness] == ["generate"]
    assert prof.ops[CHIP][1].path == "jit(decode_step)/while: 0"


def test_time_to_first_token_ends_with_the_first_pull(prof):
    assert spans.ttft_ms(prof) == pytest.approx((200 - 20) / 1e3)


def test_pull_idle_is_the_idle_time_inside_pulls_per_decode(prof):
    # idle inside pulls: 110-200, 300-400, 500-600
    assert spans.pull_idle_ms_per_step(prof, CHIP) == pytest.approx(
        (90 + 100 + 100) / 2 / 1e3)


def test_compile_per_wave_counts_the_spans_under_the_wave(prof):
    counters = {"engine.prefill": {"compile_s": 0.25},
                "engine.decode": {"compile_s": 0.05},
                "engine.generate": {"compile_s": 0.5}}
    assert spans.compile_ms_per_wave(prof, counters) == pytest.approx(300)


def test_scoped_self_time_per_decode_call(prof):
    # the while holds attn and mlp: its own 20 us count for neither
    assert spans.scope_ms_per_call(prof, CHIP, spans.DECODE_MODULE,
                                   "attn") == pytest.approx(0.030)
    assert spans.scope_ms_per_call(prof, CHIP, spans.DECODE_MODULE,
                                   "mlp") == pytest.approx(0.030)
    by_path, calls = spans.scoped_ns(prof, CHIP, (spans.DECODE_MODULE,))
    assert calls == 2
    assert by_path["jit(decode_step)/while: 0"] == 2 * 20 * US


def test_an_idle_gap_is_named_by_the_inner_program_span(prof):
    gaps = dict((round(t * 1e6), n) for n, t in spans.idle_gaps(prof, CHIP))
    assert gaps == {500: "engine.wave", 120: "engine.token_pull",
                    110: "engine.token_pull", 30: "engine.generate"}


def test_idle_is_split_by_span_and_mostly_inside_program_spans(prof):
    by, covered = spans.idle_by_span(prof, CHIP)
    us = {k: round(v * 1e6) for k, v in by.items()}
    assert us == {"generate": 20, "engine.generate": 20,
                  "engine.prefill": 20, "engine.sample": 10,
                  "engine.token_pull": 290, "engine.decode": 20,
                  "engine.wave": 380}
    assert covered == pytest.approx(740 / 760)


def test_executor_stage_and_the_stepper_stage_split(tmp_path):
    host = HARNESS + [("repro.executor.stage", 10, 20, {"chunk": 0}),
                      ("repro.executor.stage", 30, 50, {"chunk": 1})]
    body = "jit(_run_cohort)/while/body"
    # a hoisted convert carries no op-name metadata: it counts as other
    ops = [("", 90, 100), ("jit(_run_cohort)/while", 100, 200),
           (f"{body}/schedule/sort", 100, 110),
           (f"{body}/frontend/gather", 110, 130),
           (f"{body}/alu/add", 130, 140),
           (f"{body}/cond/branch_1_fun/memsys/scatter", 140, 180),
           (f"{body}/cost/reduce", 180, 190)]
    prof = build(tmp_path, host, [("jit__run_cohort(5)", 90, 200)], ops)
    assert prof.ops[CHIP][0].path == ""
    assert spans.mean_span_ms(prof, "executor.stage") == pytest.approx(0.015)
    split = spans.stage_split(prof, CHIP)
    assert {k: round(v["s"] * 1e6) for k, v in split.items()} == {
        "schedule": 10, "frontend": 20, "alu": 10, "memsys": 40,
        "cost": 10, "other": 20}
    assert sum(v["share"] for v in split.values()) == pytest.approx(1)


def test_no_program_span_reads_nothing(tmp_path):
    prof = build(tmp_path, HARNESS)
    assert spans.metrics(prof, {}) == {
        "decode.attn_ms_per_step": pytest.approx(0.030),
        "decode.mlp_ms_per_step": pytest.approx(0.030)}
    # a program without the named scopes: operations, but none under one
    unscoped = [(p.replace("/attn", "").replace("/mlp", ""), s, e)
                for p, s, e in OPS]
    assert spans.metrics(build(tmp_path, HARNESS, ops=unscoped), {}) == {}
    assert spans.metrics(build(tmp_path, HARNESS, ops=[]), {}) == {}
    assert spans.ttft_ms(prof) is None
    assert spans.compile_ms_per_wave(prof, {"engine.decode": {}}) is None
    assert spans.mean_span_ms(prof, "executor.stage") is None


def test_a_profile_without_a_window_is_refused(tmp_path):
    with pytest.raises(RuntimeError, match="window"):
        build(tmp_path, ENGINE)


def test_a_cpu_profile_of_the_engine_reads_its_first_token(tmp_path):
    import jax

    from chipbench import trace
    from repro import tracing
    from repro.configs import get_smoke
    from repro.models.schema import init_params
    from repro.serve import Engine, EngineConfig
    cfg = get_smoke("qwen1.5-0.5b")
    engine = Engine(cfg, init_params(cfg, jax.random.PRNGKey(0)),
                    EngineConfig(slots=2))
    engine.generate([[1, 2, 3], [4, 5]], 3)            # warm
    tracing.reset_counters()
    tracing.enable()
    try:
        with trace.profile(tmp_path):
            engine.generate([[1, 2, 3], [4, 5]], 3)
    finally:
        tracing.disable()
    path, = tmp_path.glob("**/*.xplane.pb")
    prof = spans.read_profile(str(path))
    assert len(prof.named("engine.decode")) == 2
    assert len(prof.named("engine.token_pull")) == 3
    wave, = prof.named("engine.wave")
    first, *_ = prof.named("engine.token_pull")
    assert spans.ttft_ms(prof) == pytest.approx(
        (first.end - wave.start) / 1e6)
    assert 0 < spans.ttft_ms(prof) < (wave.end - wave.start) / 1e6
