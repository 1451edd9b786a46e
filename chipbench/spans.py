#!/usr/bin/env python3
"""The program's own spans and scopes, read from a profile on the device's
clock: where the chip's idle time goes, by the span the host was in, and
the per-layer numbers that only the program's spans can give.

    python3 chipbench/spans.py --workload <cell> --seed <n> --seconds <s>
    python3 chipbench/spans.py --stages <bench> --seed <n>

The first form sets a cell up as ``run.py`` does (``BENCHMARK.json``, its
driver and its configuration's traced-run flags), runs the cell's window
four times, ``repro.tracing`` off, on, on, off, with no profiler (what
the tracer costs), then profiles the cell's traced slice with tracing on
and prints one JSON line: the window metrics of each pass, the metrics
below, and the idle time of the slice split by span. The second form runs
one cohort of two launches of ``bench`` (a G-GPU suite bench at its Table
III size) on the configuration of ``sim-suite``, traced at operation
level, and splits the stepper program's device time over the named
stages of its round. ``--out DIR`` also keeps the line and the gzipped
profile there.

Metrics (each ``None`` where the profile holds no program span):

- ``engine.ttft_ms``: per ``engine.wave``, the end of its first
  ``engine.token_pull`` less its start; mean over waves.
- ``engine.pull_idle_ms_per_step``: device-idle time inside
  ``engine.token_pull`` spans, over the number of ``engine.decode`` spans.
- ``engine.compile_ms_per_wave``: compile milliseconds (persistent-cache
  reads included) that the tracer's counter puts in spans under
  ``engine.wave``, over the waves of the slice.
- ``decode.attn_ms_per_step`` / ``decode.mlp_ms_per_step``: self time
  (nested operations subtracted) of the operations of ``jit_decode_step``
  under named scope ``attn`` / ``mlp``, per call of the module.
- ``executor.stage_ms``: mean duration of ``executor.stage`` spans.

The benchmark's harness does not read these yet: ``trace.read_xspace``
keeps neither the program's spans nor the operations' metadata, and
``trace.profile`` does not turn the tracer on.
"""
from __future__ import annotations

import bisect
import glob
import gzip
import json
import os
import shutil
import statistics
import sys
import time
from pathlib import Path
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

ROOT = Path(__file__).resolve().parents[1]
# the package, not this script's directory (see run.py)
sys.path[:] = [p for p in sys.path
               if Path(p or ".").resolve() != ROOT / "chipbench"]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import trace  # noqa: E402

PROGRAM_PREFIX = "repro."
#: the stat of an operation's event metadata that carries its op-name
#: metadata, where the named scopes are (``jit(f)/.../attn/dot_general``)
OP_PATH_STAT = "tf_op"
DECODE_MODULE = "jit_decode_step"
STEPPER_MODULES = ("jit__run_cohort", "jit__run_batch", "jit__run_single")
STAGES = ("schedule", "frontend", "alu", "memsys", "cost")
WAVE_SPANS = ("engine.wave", "engine.prefill", "engine.sample",
              "engine.decode", "engine.token_pull")


class Span(NamedTuple):
    name: str               # prefix stripped
    start: int              # ns, the profile's clock
    end: int
    ids: dict


class Op(NamedTuple):
    name: str
    start: int
    end: int
    path: str               # op-name metadata ("" where the trace has none)


class Profile:
    """What the readers here need of one profile, clipped to the harness's
    window span: per chip its module events and its operations with their
    op-name paths; the program's spans; the harness's spans."""

    def __init__(self, window: Tuple[int, int], modules: Dict[str, list],
                 ops: Dict[str, List[Op]], program: List[Span],
                 harness: List[Span]):
        self.window = window
        self.modules = modules
        self.ops = ops
        self.program = sorted(program, key=lambda s: (s.start, -s.end))
        self.harness = harness

    def busy(self, chip: str) -> List[Tuple[int, int]]:
        evs = self.ops.get(chip) or self.modules.get(chip) or []
        return [(e[1], e[2]) for e in evs]

    def idle(self, chip: str) -> List[Tuple[int, int]]:
        return trace.gaps_ns(self.busy(chip), *self.window)

    def named(self, name: str) -> List[Span]:
        return [s for s in self.program if s.name == name]


def _stats(ev) -> dict:
    return {k: v for k, v in ev.stats}


def read_profile(path: str) -> Profile:
    """Read an ``.xplane.pb`` (see ``Profile``)."""
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    paths = op_paths(path)
    modules, ops, program, harness = {}, {}, [], []
    for plane in pd.planes:
        if plane.name.startswith(trace.DEVICE_PREFIX):
            where = paths.get(plane.name, {})
            for line in plane.lines:
                if line.name == trace.MODULE_LINE:
                    modules[plane.name] = [
                        (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                        for ev in line.events]
                elif line.name == trace.OP_LINE:
                    ops[plane.name] = [
                        Op(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                           where.get(ev.name, "")) for ev in line.events]
            continue
        for line in plane.lines:
            for ev in line.events:
                for prefix, out in ((PROGRAM_PREFIX, program),
                                    (trace.SPAN_PREFIX, harness)):
                    if ev.name.startswith(prefix):
                        out.append(Span(ev.name[len(prefix):], ev.start_ns,
                                        ev.start_ns + ev.duration_ns,
                                        _stats(ev)))
    return clip(modules, ops, program, harness)


def _xspace_message():
    """A message class for the parts of the profiler's ``XSpace`` proto
    that hold event metadata, which ``ProfileData`` does not expose."""
    from google.protobuf import (descriptor_pb2, descriptor_pool,
                                 message_factory)
    F = descriptor_pb2.FieldDescriptorProto
    fdp = descriptor_pb2.FileDescriptorProto(
        name="chipbench_xplane.proto", package="chipbench", syntax="proto3")

    def msg(name, *fields):
        m = fdp.message_type.add(name=name)
        for fname, number, ftype, ref in fields:
            f = m.field.add(name=fname, number=number, type=ftype,
                            label=F.LABEL_REPEATED if ref and ref[0] == "*"
                            else F.LABEL_OPTIONAL)
            if ref:
                f.type_name = ".chipbench." + ref.lstrip("*")
    msg("XStat", ("metadata_id", 1, F.TYPE_INT64, None),
        ("str_value", 5, F.TYPE_STRING, None),
        ("ref_value", 7, F.TYPE_UINT64, None))
    msg("XStatMetadata", ("name", 2, F.TYPE_STRING, None))
    msg("XEventMetadata", ("name", 2, F.TYPE_STRING, None),
        ("stats", 5, F.TYPE_MESSAGE, "*XStat"))
    for entry, value in (("EventEntry", "XEventMetadata"),
                         ("StatEntry", "XStatMetadata")):
        msg(entry, ("key", 1, F.TYPE_INT64, None),
            ("value", 2, F.TYPE_MESSAGE, value))
    msg("XPlane", ("name", 2, F.TYPE_STRING, None),
        ("event_metadata", 4, F.TYPE_MESSAGE, "*EventEntry"),
        ("stat_metadata", 5, F.TYPE_MESSAGE, "*StatEntry"))
    msg("XSpace", ("planes", 1, F.TYPE_MESSAGE, "*XPlane"))
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fdp)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("chipbench.XSpace"))


def op_paths(path: str) -> Dict[str, Dict[str, str]]:
    """Per device plane, each operation's name (its HLO text, as
    ``ProfileData`` names the event) to its op-name metadata."""
    space = _xspace_message()()
    with open(path, "rb") as fh:
        space.ParseFromString(fh.read())
    out: Dict[str, Dict[str, str]] = {}
    for plane in space.planes:
        if not plane.name.startswith(trace.DEVICE_PREFIX):
            continue
        names = {e.key: e.value.name for e in plane.stat_metadata}
        want = {k for k, n in names.items() if n == OP_PATH_STAT}
        where = out.setdefault(plane.name, {})
        for e in plane.event_metadata:
            for st in e.value.stats:
                if st.metadata_id in want:
                    where[e.value.name] = st.str_value \
                        or names.get(st.ref_value, "")
    return out


def clip(modules, ops, program, harness) -> Profile:
    win = [s for s in harness if s.name == "window"]
    if not win:
        raise RuntimeError("the profile holds no window span")
    lo, hi = win[0].start, win[0].end

    def inside(evs):
        return [e for e in evs if e[2] > lo and e[1] < hi]
    return Profile((lo, hi), {p: inside(v) for p, v in modules.items()},
                   {p: inside(v) for p, v in ops.items()}, inside(program),
                   [s for s in inside(harness) if s.name != "window"])


# -- spans --------------------------------------------------------------------

def innermost(spans: Sequence[Span], starts: Sequence[int], t: int
              ) -> Optional[Span]:
    """The innermost of properly nested ``spans`` (sorted by start, with
    ``starts`` their starts) open at time ``t``."""
    i = bisect.bisect_right(starts, t) - 1
    while i >= 0:
        if spans[i].end > t:
            return spans[i]
        i -= 1
    return None


def idle_by_span(prof: Profile, chip: str
                 ) -> Tuple[Dict[str, float], Optional[float]]:
    """Device-idle seconds of ``chip`` split by the innermost span of
    either kind the host was in (``"host (no span)"`` outside all), and
    the share of the idle time that lies inside some program span."""
    spans = sorted(prof.program + prof.harness,
                   key=lambda s: (s.start, -s.end))
    starts = [s.start for s in spans]
    pstarts = [s.start for s in prof.program]
    cuts = sorted({t for s in spans for t in (s.start, s.end)})
    out: Dict[str, float] = {}
    in_program = total = 0
    for a, b in prof.idle(chip):
        edges = [a] + cuts[bisect.bisect_right(cuts, a):
                           bisect.bisect_left(cuts, b)] + [b]
        for x, y in zip(edges, edges[1:]):
            mid = (x + y) // 2
            sp = innermost(spans, starts, mid)
            name = sp.name if sp else "host (no span)"
            out[name] = out.get(name, 0.0) + (y - x) / 1e9
            total += y - x
            if innermost(prof.program, pstarts, mid) is not None:
                in_program += y - x
    return out, (in_program / total if total else None)


def idle_gaps(prof: Profile, chip: str, top: int = 10) -> list:
    """The longest idle gaps of ``chip``, each named by the innermost span
    of either kind at its middle (as ``trace.Reduced.span_at``)."""
    spans = sorted(prof.program + prof.harness,
                   key=lambda s: (s.start, -s.end))
    starts = [s.start for s in spans]
    gaps = sorted(prof.idle(chip), key=lambda g: g[0] - g[1])[:top]
    out = []
    for a, b in gaps:
        sp = innermost(spans, starts, (a + b) // 2)
        out.append([sp.name if sp else "host (no span)", (b - a) / 1e9])
    return out


def ttft_ms(prof: Profile) -> Optional[float]:
    """Mean over ``engine.wave`` spans of the end of the wave's first
    ``engine.token_pull`` less the wave's start."""
    pulls = prof.named("engine.token_pull")
    out = []
    for w in prof.named("engine.wave"):
        first = [p for p in pulls if w.start <= p.start < w.end]
        if first:
            out.append((min(first, key=lambda p: p.start).end - w.start)
                       / 1e6)
    return statistics.fmean(out) if out else None


def overlap_ns(intervals, spans: Sequence[Span]) -> int:
    """Total overlap of ``(start, end)`` intervals with the spans (neither
    list overlaps itself)."""
    iv = sorted(intervals)
    ends = [b for _, b in iv]
    total = 0
    for s in spans:
        i = bisect.bisect_right(ends, s.start)
        while i < len(iv) and iv[i][0] < s.end:
            total += min(iv[i][1], s.end) - max(iv[i][0], s.start)
            i += 1
    return total


def pull_idle_ms_per_step(prof: Profile, chip: str) -> Optional[float]:
    """Device-idle time inside ``engine.token_pull`` spans (a leaf span, so
    always the innermost there) over the ``engine.decode`` spans."""
    steps = len(prof.named("engine.decode"))
    if not steps:
        return None
    return overlap_ns(prof.idle(chip), prof.named("engine.token_pull")) \
        / steps / 1e6


def compile_ms_per_wave(prof: Profile, counters: dict) -> Optional[float]:
    """Compile milliseconds the tracer's counter puts in spans under
    ``engine.wave``, over the waves of the profile."""
    waves = len(prof.named("engine.wave"))
    if not waves:
        return None
    return sum(counters.get(n, {}).get("compile_s", 0.0)
               for n in WAVE_SPANS) / waves * 1e3


def mean_span_ms(prof: Profile, name: str) -> Optional[float]:
    spans = prof.named(name)
    if not spans:
        return None
    return statistics.fmean((s.end - s.start) / 1e6 for s in spans)


# -- operations and scopes ---------------------------------------------------

def self_times(ops: Sequence[Op]) -> List[Tuple[Op, int]]:
    """Each operation with its duration less that of the operations nested
    directly in it (a ``while`` holds its body's operations)."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i].start,
                                                   -ops[i].end))
    own = [o.end - o.start for o in ops]
    stack: List[int] = []
    for i in order:
        while stack and ops[stack[-1]].end <= ops[i].start:
            stack.pop()
        if stack:
            own[stack[-1]] -= ops[i].end - ops[i].start
        stack.append(i)
    return [(o, t) for o, t in zip(ops, own)]


def _module_of(name: str) -> str:
    return name.split("(")[0]


def scoped_ns(prof: Profile, chip: str, modules: Sequence[str]
              ) -> Tuple[Dict[str, int], int]:
    """Self time of the operations that run inside executions of
    ``modules`` on ``chip``, keyed by their op-name path; and how many
    executions there were."""
    runs = sorted((s, e) for n, s, e in prof.modules.get(chip, [])
                  if _module_of(n) in modules)
    if not runs:
        return {}, 0
    starts = [s for s, _ in runs]
    ops = []
    for op in prof.ops.get(chip, []):
        i = bisect.bisect_right(starts, op.start) - 1
        if i >= 0 and op.start < runs[i][1]:
            ops.append(op)
    out: Dict[str, int] = {}
    for op, t in self_times(ops):
        out[op.path] = out.get(op.path, 0) + t
    return out, len(runs)


def in_scope(path: str, scope: str) -> bool:
    return scope in path.split("/")


def scope_ms_per_call(prof: Profile, chip: str, module: str, scope: str
                      ) -> Optional[float]:
    """Self milliseconds per execution of ``module`` of its operations
    under named scope ``scope``; ``None`` where no operation carries it."""
    by_path, calls = scoped_ns(prof, chip, (module,))
    times = [t for p, t in by_path.items() if in_scope(p, scope)]
    if not calls or not times:
        return None
    return sum(times) / calls / 1e6


def stage_split(prof: Profile, chip: str) -> Optional[dict]:
    """The stepper programs' operation self time by round stage: seconds
    and share of each of ``STAGES``, the rest as ``other``."""
    by_path, calls = scoped_ns(prof, chip, STEPPER_MODULES)
    total = sum(by_path.values())
    if not total:
        return None
    secs = {st: 0 for st in STAGES + ("other",)}
    for path, t in by_path.items():
        st = next((s for s in STAGES if in_scope(path, s)), "other")
        secs[st] += t
    return {st: {"s": t / 1e9, "share": t / total}
            for st, t in secs.items()}


def metrics(prof: Profile, counters: dict) -> dict:
    """Every metric of the module doc on the first chip of ``prof``."""
    chip = min(prof.modules, default="")
    out = {
        "engine.ttft_ms": ttft_ms(prof),
        "engine.pull_idle_ms_per_step": pull_idle_ms_per_step(prof, chip),
        "engine.compile_ms_per_wave": compile_ms_per_wave(prof, counters),
        "decode.attn_ms_per_step": scope_ms_per_call(
            prof, chip, DECODE_MODULE, "attn"),
        "decode.mlp_ms_per_step": scope_ms_per_call(
            prof, chip, DECODE_MODULE, "mlp"),
        "executor.stage_ms": mean_span_ms(prof, "executor.stage"),
    }
    return {k: v for k, v in out.items() if v is not None}


# -- on the chip -------------------------------------------------------------

def _profile(tdir: Path, fn) -> str:
    shutil.rmtree(tdir, ignore_errors=True)
    with trace.profile(tdir):
        fn()
    files = glob.glob(str(tdir / "**" / "*.xplane.pb"), recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one profile under {tdir}")
    return files[0]


def span_cost_ns(n: int = 100_000) -> Dict[str, float]:
    """Host nanoseconds of one empty span, tracing off and on (no
    profiler running)."""
    from repro import tracing
    out = {}
    for label in ("off", "on"):
        (tracing.enable if label == "on" else tracing.disable)()
        t0 = time.perf_counter_ns()
        for _ in range(n):
            with tracing.span("cost", k=1):
                pass
        out[label] = (time.perf_counter_ns() - t0) / n
    tracing.disable()
    return out


def _keep(out: Optional[Path], name: str, line: dict, xplane: str) -> None:
    if out is None:
        return
    out.mkdir(parents=True, exist_ok=True)
    (out / f"{name}.json").write_text(json.dumps(line))
    with open(xplane, "rb") as src, \
            gzip.open(out / f"{name}.xplane.pb.gz", "wb") as dst:
        shutil.copyfileobj(src, dst)


def cell(args, devices) -> dict:
    """The first form of the module doc."""
    from chipbench import harness
    from repro import tracing
    spec = harness.Spec(ROOT)
    run = harness.Run(spec, args.workload, args.seed, args.seconds, True,
                      devices, time.perf_counter())
    driver = spec.driver(run.config["driver"])
    e2e = [m["name"] for m in spec.metrics_for(run.workload, False)
           if m["name"] != "setup_s"]
    host = [m["name"] for m in spec.metrics_for(run.workload, True)
            if m["source"] == "host_clock"]
    state = driver.setup(run)
    line = {"device": harness.device_record(devices),
            "workload": run.workload, "seed": run.seed,
            "setup_s": time.perf_counter() - run.t_start, "windows": []}
    for on in (False, True, True, False):
        (tracing.enable if on else tracing.disable)()
        run.spans.clear()
        driver.window(run, state)
        line["windows"].append(
            {"tracing": on, **{n: spec.reader(n).read(run)
                               for n in e2e + host}})
    tracing.disable()
    tracing.reset_counters()
    tracing.enable()
    tdir = ROOT / ".chipbench" / "spans" / f"{run.workload}-{run.seed}"
    xplane = _profile(tdir, lambda: driver.traced_slice(run, state))
    tracing.disable()
    counters = tracing.counters()
    prof = read_profile(xplane)
    chip = min(prof.modules, default="")
    idle, covered = idle_by_span(prof, chip)
    busy = trace.union_ns(prof.busy(chip)) / 1e9
    win = (prof.window[1] - prof.window[0]) / 1e9
    line.update(
        metrics=metrics(prof, counters), counters=counters,
        span_cost_ns=span_cost_ns(),
        spans={n: len(prof.named(n)) for n in
               sorted({s.name for s in prof.program})},
        slice={"window_s": win, "busy_s": busy,
               "idle_share": 1 - busy / win,
               "idle_in_program_span": covered},
        idle_by_span=dict(sorted(idle.items(), key=lambda kv: -kv[1])),
        idle_gaps=idle_gaps(prof, chip))
    _keep(args.out, f"{run.workload}-{run.seed}", line, xplane)
    shutil.rmtree(tdir, ignore_errors=True)
    return line


def stages(args, devices) -> dict:
    """The second form of the module doc."""
    import numpy as np

    from chipbench import harness
    from repro.ggpu import programs
    from repro.ggpu.engine import GGPUConfig
    from repro.serve import Request, Scheduler
    spec = harness.Spec(ROOT)
    machine = spec.config(spec.workload("sim-suite")["config"])["machine"]
    b = programs.all_benches()[args.stages]
    rng = np.random.default_rng(args.seed % 2**63)
    sched = Scheduler(GGPUConfig(**machine))

    def job():
        for k in range(2):
            mem = rng.integers(-50, 50, b.gpu_mem.shape[0], dtype=np.int32)
            sched.submit_request(Request(b.gpu_prog, mem, b.gpu_items,
                                         out_region=(b.gpu_out.start,
                                                     b.gpu_out.stop)))
        return sched.drain()
    job()
    tdir = ROOT / ".chipbench" / "spans" / f"stages-{args.stages}"
    xplane = _profile(tdir, job)
    prof = read_profile(xplane)
    chip = min(prof.modules, default="")
    line = {"device": harness.device_record(devices), "bench": args.stages,
            "seed": args.seed, "split": stage_split(prof, chip),
            "stepper_s": sum(e - s for n, s, e in prof.modules.get(chip, [])
                             if _module_of(n) in STEPPER_MODULES) / 1e9,
            "ops": sum(len(v) for v in prof.ops.values())}
    _keep(args.out, f"stages-{args.stages}", line, xplane)
    shutil.rmtree(tdir, ignore_errors=True)
    return line


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    what = ap.add_mutually_exclusive_group(required=True)
    what.add_argument("--workload")
    what.add_argument("--stages")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--out", type=Path)
    args = ap.parse_args(argv)
    from chipbench import harness
    spec = harness.Spec(ROOT)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    if args.workload:
        # the cell's traced-run flags, as run.py sets them
        extra = spec.config(spec.workload(args.workload)["config"]).get(
            "traced_run", {}).get("libtpu_init_args", [])
        os.environ["LIBTPU_INIT_ARGS"] = " ".join(
            [os.environ.get("LIBTPU_INIT_ARGS", "")] + extra).strip()
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"spans: needs a TPU; JAX found {devices[0].platform}",
              file=sys.stderr)
        return 2
    harness.enable_compile_cache(ROOT)
    src = ROOT / "src"
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))
    line = (cell if args.workload else stages)(args, devices[:1])
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
