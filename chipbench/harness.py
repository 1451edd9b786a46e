"""What every cell of the chip benchmark shares: the spec, discovery of
configurations, traffic mixes, drivers and metric readers by name, the
run context with its host spans and compile counter, and the result line.

Nothing here names a cell, a configuration or a metric. ``BENCHMARK.json``
names them, and each lives in a file of its own:

- ``chipbench/configs/<config>.json``  sizes of one configuration; its
  ``driver`` key names ``chipbench/drivers/<driver>.py`` and its
  ``reference`` key ``chipbench/references/<reference>.py``;
- ``chipbench/traffic/<traffic>.json``  parameters of one traffic mix;
- ``chipbench/metrics/<metric>.py``     a reader ``read(run)`` that returns
  the metric's value, or ``None`` where it finds nothing to read.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import sys
import time
from pathlib import Path
from typing import Dict, List, Optional

SPEC_FILE = "BENCHMARK.json"


class SpecError(ValueError):
    """``BENCHMARK.json`` or a file it names is missing or inconsistent."""


# -- discovery ---------------------------------------------------------------

def load_module(path: Path, name: str):
    """Import the Python file at ``path`` under ``name`` (file names here
    are metric names, which may hold dots and dashes)."""
    if not path.is_file():
        raise SpecError(f"no file {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path: Path) -> dict:
    if not path.is_file():
        raise SpecError(f"no file {path}")
    with open(path) as fh:
        return json.load(fh)


class Spec:
    """``BENCHMARK.json`` and the files it names, found under ``root``."""

    def __init__(self, root: Path):
        self.root = Path(root)
        self.data = load_json(self.root / SPEC_FILE)
        self.bench_dir = self.root / "chipbench"

    def workload(self, name: str) -> dict:
        for w in self.data["workloads"]:
            if w["name"] == name:
                return w
        raise SpecError(f"no workload {name!r} in {SPEC_FILE}")

    def config_entry(self, name: str) -> dict:
        for c in self.data["configs"]:
            if c["name"] == name:
                return c
        raise SpecError(f"no config {name!r} in {SPEC_FILE}")

    def config(self, name: str) -> dict:
        return load_json(self.root / self.config_entry(name)["file"])

    def traffic(self, name: str) -> dict:
        return load_json(self.bench_dir / "traffic" / f"{name}.json")

    def driver(self, name: str):
        return load_module(self.bench_dir / "drivers" / f"{name}.py",
                           f"chipbench_driver_{name}")

    def reference(self, name: str):
        return load_module(self.bench_dir / "references" / f"{name}.py",
                           f"chipbench_reference_{name}")

    def reader(self, metric: str):
        return load_module(self.bench_dir / "metrics" / f"{metric}.py",
                           f"chipbench_metric_{metric}")

    def metrics_for(self, workload: str, traced: bool) -> List[dict]:
        """The metrics a run of ``workload`` reports: its end-to-end ones
        untraced, its per-layer ones traced. A metric with a ``workloads``
        list applies to those cells; one without, to every cell (a
        per-layer one: every cell that reports the metric it moves)."""
        e2e = [m for m in self.data["end_to_end"]
               if workload in m.get("workloads", [workload])]
        if not traced:
            return e2e
        moved = {m["name"] for m in e2e}
        return [m for m in self.data["per_layer"]
                if workload in m["workloads"]
                or ("workloads" not in m and m["moves"] in moved)]


# -- timing of the host ------------------------------------------------------

class CompileClock:
    """Counts JAX's backend compiles (persistent-cache reads included) and
    sums their seconds while active."""

    def __init__(self):
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0

    def _duration(self, event: str, duration: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compiles += 1

    def _event(self, event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)


class Run:
    """One run of one cell: what the driver reads (sizes, traffic, seed,
    devices) and what it leaves for the metric readers (host spans,
    compile counts, records, the reduced trace)."""

    def __init__(self, spec: Spec, workload: str, seed: int, seconds: float,
                 trace: bool, devices, t_start: float):
        self.spec = spec
        self.cell = spec.workload(workload)
        self.workload = workload
        self.config_name = self.cell["config"]
        self.config = spec.config(self.config_name)
        self.traffic_name = self.cell["traffic"]
        self.traffic = spec.traffic(self.traffic_name)
        self.seed = int(seed)
        self.traced = bool(trace)
        self.seconds = float(seconds)
        self.devices = list(devices)
        self.t_start = t_start
        self.spans: List[tuple] = []          # (name, t0, t1) perf_counter
        self.records: Dict[str, object] = {}  # the driver's, for readers
        self.window: Optional[tuple] = None   # (t0, t1) perf_counter
        self.setup_s: Optional[float] = None
        self.clock = CompileClock()
        self.window_compiles = 0
        self.trace = None                     # trace.Reduced
        self.memory_peak_bytes = 0

    @property
    def device_kind(self) -> str:
        return self.devices[0].device_kind

    @contextlib.contextmanager
    def span(self, name: str):
        """A host span of the harness around a call into a layer. It goes
        into the profiler's trace too, so idle gaps can be named by it."""
        import jax
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(f"chipbench.{name}"):
            try:
                yield
            finally:
                self.spans.append((name, t0, time.perf_counter()))

    def span_seconds(self, name: str) -> float:
        """Seconds the host spent in span ``name`` inside the window."""
        lo, hi = self.window
        return sum(t1 - t0 for n, t0, t1 in self.spans
                   if n == name and t0 >= lo and t1 <= hi)

    def peaks(self) -> dict:
        """This device's row of ``chipbench/peaks.json``; a device kind
        that is not in the table is an error, never a default."""
        return device_peaks(self.spec.bench_dir, self.device_kind)


def device_peaks(bench_dir: Path, kind: str) -> dict:
    table = load_json(Path(bench_dir) / "peaks.json")
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json; "
                       f"known: {sorted(k for k in table if k != '_source')}")
    return table[kind]


# -- the result line ---------------------------------------------------------

def device_record(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def emit(result: dict, checks: list) -> None:
    """Print the checks as the last lines of standard error and the result
    as the last line of standard output, with the checks under a key of
    their own that comes last."""
    for c in checks:
        print(f"check {c['name']}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    result = dict(result)
    result["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                        for c in checks}
    print(json.dumps(result), flush=True)


def judge(checks) -> bool:
    """Each number compared must lie at or under its limit."""
    return all(c["value"] is not None and c["value"] <= c["limit"]
               for c in checks)


def enable_compile_cache(root: Path) -> str:
    """Keep compiled programs in ``JAX_COMPILATION_CACHE_DIR`` where it is
    set, else in a fixed directory inside the checkout: the path is part of
    the cache key."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or str(Path(root) / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    # every program goes to the cache, so only a cell's first run compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path
