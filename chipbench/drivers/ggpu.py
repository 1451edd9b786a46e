"""Driver of G-GPU simulator configurations: the served simulator
(``repro.serve.Scheduler`` over the cycle-approximate stepper) under the
traffic kind below, checked launch by launch against the reference.

Traffic kind ``closed_jobs`` (``chipbench/traffic/<name>.json``): one
client submits a job (``launches_per_bench`` launches of each of
``benches``), dispatches, collects, and submits the next job when the
results are back. The window closes when the first job that ends past
``--seconds`` is back, so it holds whole jobs only. A traced run traces
one more job after the window.

On more than one chip the scheduler shards each chunk's launch axis over
a ``("data",)`` mesh of the cell's chips.
"""
from __future__ import annotations

import time
import zlib

import numpy as np

WARM, WINDOW, TRACE = 1, 2, 3          # image streams of one seed


def _image(run, words: int, stream: int, name: str, k: int) -> np.ndarray:
    img = run.config["image"]
    rng = np.random.default_rng(
        [run.seed % 2**63, stream, zlib.crc32(name.encode()), k])
    return rng.integers(img["low"], img["high"], words, dtype=np.int32)


class State:
    def __init__(self, run):
        from repro.ggpu import programs
        from repro.ggpu.engine import GGPUConfig
        from repro.serve import Scheduler

        self.ref = run.spec.reference(run.config["reference"])
        sizes = run.config["benches"]
        names = run.traffic.get("benches") or list(sizes)
        built = programs.all_benches()
        self.benches, self.sizes = {}, {}
        for name in names:
            b, s = built[name], sizes[name]
            words, lo, hi = self.ref.layout(name, s)
            if (b.gpu_mem.shape[0], b.gpu_out.start, b.gpu_out.stop) \
                    != (words, lo, hi):
                raise ValueError(
                    f"{name}: the program's image ({b.gpu_mem.shape[0]} "
                    f"words, out {b.gpu_out}) is not the configuration's "
                    f"({words} words, out [{lo}, {hi}))")
            self.benches[name], self.sizes[name] = b, s
        mesh = None
        if len(run.devices) > 1:
            mesh = jax_mesh(run.devices)
        self.sched = Scheduler(GGPUConfig(**run.config["machine"]),
                               mesh=mesh)
        self.served = []        # (bench, image, Result) served
        self.missing = 0
        self.attempted = 0

    def request(self, run, name: str, stream: int, k: int):
        from repro.serve import Request
        b = self.benches[name]
        mem = _image(run, b.gpu_mem.shape[0], stream, name, k)
        req = Request(b.gpu_prog, mem, b.gpu_items, tag=f"{name}/{k}",
                      out_region=(b.gpu_out.start, b.gpu_out.stop))
        return name, mem, req


def jax_mesh(devices):
    import jax
    return jax.sharding.Mesh(np.asarray(devices), ("data",))


# -- set-up ------------------------------------------------------------------

def _drain(state, reqs) -> list:
    for _, _, req in reqs:
        state.sched.submit_request(req)
    return state.sched.drain()


def setup(run):
    state = State(run)
    if run.traffic["kind"] != "closed_jobs":
        raise ValueError(f"unknown traffic kind {run.traffic['kind']!r}")
    with run.span("warmup"):
        _drain(state, _job(run, state, WARM, 0))
    if state.sched.quarantined:
        raise RuntimeError(f"warm-up quarantined "
                           f"{sorted(state.sched.quarantined)}")
    return state


# -- the window --------------------------------------------------------------

def _job(run, state, stream: int, j: int) -> list:
    per = run.traffic["launches_per_bench"]
    return [state.request(run, name, stream, j * per + k)
            for name in state.benches for k in range(per)]


def _serve_job(run, state, stream: int, j: int) -> dict:
    """Submit job ``j``, dispatch it, collect it; its dispatches and the
    simulated steps they ran (a dispatch runs as many steps as its longest
    member)."""
    sched = state.sched
    with run.span("prepare"):
        reqs = _job(run, state, stream, j)
    by_ticket = {sched.submit_request(req): (name, mem)
                 for name, mem, req in reqs}
    state.attempted += len(reqs)
    d0 = sched.executor.stats.dispatches
    with run.span("dispatch"):
        sched.dispatch()
    with run.span("collect"):
        results = sched.collect()
    steps = {}
    for res in results:
        name, mem = by_ticket.pop(res.info["ticket"])
        state.served.append((name, mem, res))
        steps[name] = max(steps.get(name, 0), int(res.info["steps"]))
    state.missing += len(by_ticket)
    return {"launches": len(results),
            "dispatches": sched.executor.stats.dispatches - d0,
            "steps": sum(steps.values())}


def _closed_jobs(run, state) -> None:
    t0 = time.perf_counter()
    jobs = []
    while True:
        jobs.append(_serve_job(run, state, WINDOW, len(jobs)))
        t1 = time.perf_counter()
        if t1 - t0 >= run.seconds:
            break
    run.window = (t0, t1)
    run.records.update(launches=sum(j["launches"] for j in jobs),
                       jobs=len(jobs), job_steps=jobs)


def window(run, state) -> None:
    _closed_jobs(run, state)


def traced_slice(run, state) -> None:
    """What a traced run traces, after the window: one more job."""
    run.records["traced"] = _serve_job(run, state, TRACE, 0)


def counts(run, state):
    return state.attempted, state.missing


def release(run, state):
    state.sched = None
    return state


# -- the check ---------------------------------------------------------------

def check(run, state) -> list:
    """Every launch served in the window against the reference: its output
    region exact; the statistics its kernel fixes equal to the reference's
    count, with ``hits + misses == mem_ops``; the rest equal to the
    configuration's pins, recorded on the CPU backend. Every launch
    attempted must have come back."""
    pins, limits = run.config["pins"], run.config["limits"]
    machine = run.config["machine"]
    wrong_out = wrong_stats = wrong_pins = 0
    for name, mem, res in state.served:
        size = state.sizes[name]
        if not np.array_equal(np.asarray(res.mem),
                              state.ref.expected(name, size, mem)):
            wrong_out += 1
        got = {k: int(v) for k, v in res.info.items()
               if k in ("cycles", "instrs", "mem_ops", "hits", "misses",
                        "steps")}
        want = state.ref.derived_stats(name, size, machine, mem)
        if any(got[k] != v for k, v in want.items()) \
                or got["hits"] + got["misses"] != got["mem_ops"]:
            wrong_stats += 1
        if any(got[k] != per[name] for k, per in pins.items()
               if name in per):
            wrong_pins += 1
    return [
        {"name": "launches_missing", "value": state.missing,
         "limit": limits["launches_missing"]},
        {"name": "outputs_wrong", "value": wrong_out,
         "limit": limits["outputs_wrong"]},
        {"name": "stats_wrong", "value": wrong_stats,
         "limit": limits["stats_wrong"]},
        {"name": "pins_wrong", "value": wrong_pins,
         "limit": limits["pins_wrong"]},
    ]
