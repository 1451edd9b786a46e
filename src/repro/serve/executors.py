"""Compiled-executor layer: runs planned chunks on one device config.

An ``Executor`` owns the engine entry points for one ``GGPUConfig`` and
tracks the **envelope cache**: the set of compiled-stepper signatures
(chunk kind, batch size, wavefront count, program length, memory size,
opcode set) this process has already traced. The jit cache inside
``repro.ggpu.engine`` is keyed on exactly these statics, so a chunk whose
envelope has been seen re-uses the compiled stepper — repeat serving
traffic never re-traces — and the executor's hit/miss counters make that
visible (``BENCH_serve.json`` reports the hit rate).

Every executor separates its **simulation config** (``sim_cfg``:
``freq_mhz`` normalized out, the engine/compile key — frequency never
enters the traced cycle computation, and as a static jit argument every
distinct frequency target would otherwise recompile) from its
**reporting config** (``cfg``: the caller's true frequency).
``Result.info["time_us"]`` is always rescaled from cycles at the true
``freq_mhz``, so results are frequency-faithful even off the shared
registry — and executors at different frequency targets of the same
design share one compiled-stepper cache.

The launch path is **asynchronous**: ``submit`` stages and dispatches a
chunk, returning a ``PendingChunk`` immediately while the device runs;
``collect`` resolves it into ``Result``s (fetching only the small
cycles/stats arrays, plus each request's declared ``out_region`` slice of
memory — or the full image when none was declared). ``run`` is the
blocking composition of the two, so sync and async callers share one code
path and are bit-exact by construction.

``get_executor`` is a process-wide registry keyed by the simulation key;
callers with a non-default frequency get a lightweight view that shares
the envelope cache, stats, and memo with the canonical executor but
reports at the caller's true frequency. The registry is shared with
``repro.dse.Evaluator``, whose cycle cache lives on the executor
(``Executor.memo``): a DSE sweep and a serving fleet that touch the same
config share both the compiled steppers and the memoized bench results.

An executor also carries its **placement**: a ``mesh`` shards every
cohort/batch chunk's launch axis across the mesh's data-parallel devices
(``repro.ggpu.engine`` ``mesh=`` entry points — one dispatch, each
physical device stepping its own slice), and a ``device`` pins dispatch
to one ``jax.Device`` (how a fleet puts different simulated configs on
different physical devices so their compute genuinely overlaps). Both
default off; with one JAX device everything degrades to the PR-5
single-device behavior. ``shards`` reports the mesh's data-parallel
extent (1 = unsharded) — schedulers scale their chunk planning by it.
"""
from __future__ import annotations

import contextlib
import dataclasses
import itertools
import time
from typing import Dict, List, Optional, Sequence

import jax

from repro import tracing
from repro.ggpu.engine import (BlockPatch, GGPUConfig, KernelLaunchError,
                               LaunchHandle, XorBlockPatch,
                               cohort_rows, launch_shards)
from repro.ggpu.engine import (run_kernel_async, run_kernel_batch_async,
                               run_kernel_cohort_async)
from repro.ggpu.engine.stepper import _n_wavefronts

from repro.serve.request import Request, Result


class DeviceTimeout(KernelLaunchError):
    """A dispatched chunk did not resolve within the executor's
    ``timeout_s`` — the stuck-device failure mode (DESIGN.md §Fault
    injection). ``index`` is ``None``: the whole chunk is suspect, every
    member is retried or quarantined by the scheduler. ``device_fault``
    marks it as the *device's* failure (not the program's), which is what
    a fleet counts toward eviction and re-routes to survivors."""

    device_fault = True

    def __init__(self, message: str, index: Optional[int] = None):
        super().__init__(message, 0 if index is None else index)
        self.index = index


@dataclasses.dataclass
class ExecutorStats:
    """Counts *executed* work: a launch re-run after a failed chunk (the
    LaunchQueue restore-and-retry path, or quarantine survivors) counts
    each time it actually runs — these are simulator-activity stats, not
    unique-request stats. hits + misses == dispatches always holds, and
    both are counted at *collection* (a dispatch that fails to halt is
    retried with fewer members, a different envelope)."""
    launches: int = 0        # kernel launches executed
    dispatches: int = 0      # compiled-stepper calls issued
    trace_hits: int = 0      # dispatches whose envelope was already traced
    trace_misses: int = 0    # dispatches that paid a trace/compile

    @property
    def batch_occupancy(self) -> float:
        """Mean launches per dispatch — the continuous-batching win."""
        return self.launches / self.dispatches if self.dispatches else 0.0

    @property
    def hit_rate(self) -> float:
        return (self.trace_hits / self.dispatches) if self.dispatches else 0.0

    def report(self) -> dict:
        return {
            "launches": self.launches,
            "dispatches": self.dispatches,
            "batch_occupancy": round(self.batch_occupancy, 3),
            "trace_hits": self.trace_hits,
            "trace_misses": self.trace_misses,
            "hit_rate": round(self.hit_rate, 3),
        }


def sim_key(cfg: GGPUConfig) -> GGPUConfig:
    """Normalize ``freq_mhz`` out of the executor/compile key: frequency
    scales reported ``time_us`` but never the traced cycle computation."""
    return dataclasses.replace(cfg, freq_mhz=500.0)


@dataclasses.dataclass
class PendingChunk:
    """One dispatched chunk in flight on the device, awaiting collection.
    ``t_dispatch`` is the wall clock at dispatch — the reference point for
    executor timeouts and fleet-level hedging. ``seq`` numbers dispatches
    in this process; the executor's program spans carry it as ``chunk``."""
    handle: LaunchHandle
    kind: str
    reqs: List[Request]
    env: tuple
    traced: bool
    t_dispatch: float = 0.0
    seq: int = 0


_DISPATCH_SEQ = itertools.count()


class Executor:
    """Runs (kind, requests) chunks on one config, with envelope-cache
    accounting and a memo dict shared across its users (see module doc).

    ``share`` hands this executor another one's mutable state (envelope
    cache, stats, memo) — how the registry builds frequency-faithful views
    over one canonical executor per simulation key. ``mesh`` and ``device``
    set placement (module doc): a mesh shards cohort/batch launch axes
    data-parallel, a device pins dispatch. Placement enters the envelope
    key, so differently-placed chunks never alias a compiled signature."""

    def __init__(self, cfg: GGPUConfig, *,
                 share: Optional["Executor"] = None,
                 mesh=None, device=None,
                 timeout_s: Optional[float] = None):
        self.cfg = cfg                    # reporting config (true freq)
        self.sim_cfg = sim_key(cfg)       # engine/compile config
        self.mesh = mesh
        self.device = device
        self.shards = launch_shards(mesh)
        # wall-clock budget a dispatched chunk gets before ``collect``
        # gives up with ``DeviceTimeout`` (None: wait forever — the
        # pre-fault-model behavior, and the default)
        self.timeout_s = timeout_s
        if share is None:
            self.stats = ExecutorStats()
            self.memo: Dict[tuple, object] = {}  # e.g. the DSE cycle cache
            self._envelopes: set = set()
        else:
            if share.sim_cfg != self.sim_cfg:
                raise ValueError("shared executors must agree on the "
                                 "simulation key")
            self.stats = share.stats
            self.memo = share.memo
            self._envelopes = share._envelopes

    # -- envelope accounting ------------------------------------------------

    def _envelope(self, kind: str, reqs: Sequence[Request]) -> tuple:
        """The static signature the engine jit-caches on for this chunk
        (opcode sets come from the requests' content-keyed cache), suffixed
        with this executor's placement — a sharded or pinned dispatch is a
        different compiled artifact than the plain one."""
        cfg = self.sim_cfg
        place = (self.shards, None if self.device is None else self.device.id)
        if kind == "cohort":
            # the engine buckets cohort sizes (cohort_rows), so the traced
            # envelope is the bucket, not the raw member count
            r = reqs[0]
            return ("cohort", cohort_rows(len(reqs), self.shards),
                    _n_wavefronts(r.n_items, cfg),
                    r.prog.shape[0], r.mem0.shape[0], r.static_ops(), place)
        if kind == "batch":
            P = max(r.prog.shape[0] for r in reqs)
            M = max(r.mem0.shape[0] for r in reqs)
            W = max(_n_wavefronts(r.n_items, cfg) for r in reqs)
            ops = tuple(sorted(set().union(
                *(r.static_ops() for r in reqs))))
            return ("batch", len(reqs), W, P, M, ops, place)
        r = reqs[0]
        return ("single", _n_wavefronts(r.n_items, cfg), r.prog.shape[0],
                r.mem0.shape[0], r.static_ops(), place)

    # -- execution ----------------------------------------------------------

    def submit(self, kind: str, reqs: Sequence[Request],
               patches=None) -> PendingChunk:
        """Stage and dispatch one planned chunk asynchronously; returns
        while the device still runs. Pair with ``collect``. ``patches``
        optionally overwrites regions of the chunk's staged memory with
        device arrays before dispatch — a ``repro.ggpu.engine.BlockPatch``
        or one ``[(lo, hi, src), ...]`` list per launch — the
        device-resident chaining path a dependency-aware scheduler uses to
        feed a producer's output into a consumer with no host transfer.
        The work runs in program span ``executor.stage``."""
        reqs = list(reqs)
        seq = next(_DISPATCH_SEQ)
        with tracing.span("executor.stage", chunk=seq, launches=len(reqs)):
            return self._submit(kind, reqs, patches, seq)

    def _submit(self, kind, reqs, patches, seq) -> PendingChunk:
        if len(reqs) == 1:
            kind = "single"          # a degenerate chunk needs no folding
        env = self._envelope(kind, reqs)
        traced = env in self._envelopes
        # the jit trace is paid HERE, at dispatch — record the envelope
        # now so identical-envelope chunks dispatched ahead in the same
        # pipeline window count as the hits they really are
        self._envelopes.add(env)
        regions = [r.out_region for r in reqs]
        if all(r is None for r in regions):
            regions = None
        cfg = self.sim_cfg
        place = (jax.default_device(self.device) if self.device is not None
                 else contextlib.nullcontext())
        with place:
            if kind == "cohort":
                h = run_kernel_cohort_async(
                    reqs[0].prog, [r.mem0 for r in reqs], reqs[0].n_items,
                    cfg, out_regions=regions, patches=patches,
                    mesh=self.mesh)
            elif kind == "batch":
                h = run_kernel_batch_async(
                    [r.prog for r in reqs], [r.mem0 for r in reqs],
                    [r.n_items for r in reqs], cfg, out_regions=regions,
                    patches=patches, mesh=self.mesh)
            else:
                # normalize the chunk-level patch forms down to the
                # single-launch flat list the engine entry point takes
                single = None
                if isinstance(patches, XorBlockPatch):
                    single = [(patches.lo, patches.hi, patches.block[0],
                               "xor")]
                elif isinstance(patches, BlockPatch):
                    single = [(patches.lo, patches.hi, patches.block[0])]
                elif patches is not None:
                    single = patches[0]
                h = run_kernel_async(
                    reqs[0].prog, reqs[0].mem0, reqs[0].n_items, cfg,
                    out_region=regions[0] if regions else None,
                    patches=single)
        return PendingChunk(h, kind, reqs, env, traced,
                            t_dispatch=time.monotonic(), seq=seq)

    def chunk_ready(self, pending: PendingChunk) -> bool:
        """Non-blocking: has the device finished this chunk? (The hook a
        fault injector overrides to model stuck devices and stragglers.)"""
        return pending.handle.ready()

    def collect(self, pending: PendingChunk) -> List[Result]:
        """Resolve a dispatched chunk into per-launch ``Result``s in the
        chunk's own order, rescaling ``time_us`` to this executor's true
        frequency. Raises ``KernelLaunchError`` (with ``index`` naming the
        failing position) when a launch did not halt — stat counters move
        on successful collections only, preserving hits + misses ==
        dispatches (a failed chunk is retried with fewer members, a
        different envelope). With ``timeout_s`` set, a chunk still
        unresolved ``timeout_s`` after its dispatch raises
        ``DeviceTimeout`` (``index=None``: the whole chunk is suspect).
        Program spans: ``executor.wait`` while the device finishes the
        chunk, ``executor.download`` while its results come back."""
        ids = {"chunk": pending.seq, "launches": len(pending.reqs)}
        with tracing.span("executor.wait", **ids):
            if self.timeout_s is not None:
                deadline = pending.t_dispatch + self.timeout_s
                while not self.chunk_ready(pending):
                    now = time.monotonic()
                    if now >= deadline:
                        raise DeviceTimeout(
                            f"chunk of {len(pending.reqs)} launch(es) not "
                            f"resolved within {self.timeout_s}s of dispatch")
                    time.sleep(min(1e-3, deadline - now))
            pending.handle.wait()
        with tracing.span("executor.download", **ids):
            outs = pending.handle.results()
        if pending.traced:
            self.stats.trace_hits += 1
        else:
            self.stats.trace_misses += 1
        self.stats.launches += len(pending.reqs)
        self.stats.dispatches += 1
        results = []
        for mem, info in outs:
            info.setdefault("batch_size", 1)
            info["time_us"] = info["cycles"] / self.cfg.freq_mhz
            results.append(Result(mem, info))
        return results

    def run(self, kind: str, reqs: Sequence[Request]) -> List[Result]:
        """Execute one planned chunk synchronously (dispatch + collect)."""
        return self.collect(self.submit(kind, reqs))


# -- process-wide registry (shared with repro.dse.Evaluator) ----------------

_EXECUTORS: Dict[GGPUConfig, Executor] = {}       # canonical, by sim key
_VIEWS: Dict[tuple, Executor] = {}                # freq/placement views


def get_executor(cfg: GGPUConfig, *, mesh=None, device=None) -> Executor:
    """The shared executor for ``cfg``'s simulation key, reporting at
    ``cfg``'s true frequency: a non-default-frequency caller gets a view
    sharing the canonical executor's compiled-envelope cache, stats, and
    memo, with ``time_us`` rescaled from cycles at the caller's
    ``freq_mhz``. ``mesh``/``device`` placement likewise produces a view
    (keyed by placement) over the same canonical state — a sharded fleet
    and an unsharded DSE sweep of one config share one stats/memo pool."""
    key = sim_key(cfg)
    canon = _EXECUTORS.get(key)
    if canon is None:
        canon = _EXECUTORS.setdefault(key, Executor(key))
    if cfg == key and mesh is None and device is None:
        return canon
    vkey = (cfg, mesh, device)
    view = _VIEWS.get(vkey)
    if view is None:
        view = _VIEWS.setdefault(
            vkey, Executor(cfg, share=canon, mesh=mesh, device=device))
    return view
