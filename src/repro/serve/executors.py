"""Compiled-executor layer: runs planned chunks on one device config.

An ``Executor`` runs chunks of requests for one ``GGPUConfig`` through
the engine's one entry point, ``repro.ggpu.engine.launch``, which picks
the cohort or batch path from the chunk itself, and tracks the **envelope
cache**: the set of compiled-stepper signatures this process has already
traced. Each dispatch's envelope is the signature the engine reports it
jitted (``LaunchHandle.envelope``), suffixed with the executor's
placement, so a chunk whose envelope has been seen re-uses the compiled
stepper — repeat serving traffic never re-traces — and the executor's
hit/miss counters make that visible (``BENCH_serve.json`` reports the hit
rate).

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
multi-launch chunk's launch axis across the mesh's data-parallel devices
(``launch(mesh=)`` — one dispatch, each physical device stepping its own
slice), and a ``device`` pins dispatch to one ``jax.Device`` (how a fleet
puts different simulated configs on different physical devices so their
compute genuinely overlaps). Both
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
from repro.ggpu.engine import (GGPUConfig, KernelLaunchError, LaunchHandle,
                               Patch, launch, launch_shards)

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
    reqs: List[Request]
    traced: bool
    t_dispatch: float = 0.0
    seq: int = 0


_DISPATCH_SEQ = itertools.count()


class Executor:
    """Runs chunks of requests on one config, with envelope-cache
    accounting and a memo dict shared across its users (see module doc).

    ``share`` hands this executor another one's mutable state (envelope
    cache, stats, memo) — how the registry builds frequency-faithful views
    over one canonical executor per simulation key. ``mesh`` and ``device``
    set placement (module doc): a mesh shards multi-launch chunks
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

    # -- execution ----------------------------------------------------------

    def submit(self, reqs: Sequence[Request],
               patches: Sequence[Patch] = ()) -> PendingChunk:
        """Stage and dispatch one planned chunk asynchronously; returns
        while the device still runs. Pair with ``collect``. ``patches``
        (``repro.ggpu.engine.Patch``, applied in order) write device
        arrays into the chunk's staged memory before dispatch — the
        device-resident chaining path a dependency-aware scheduler uses to
        feed a producer's output into a consumer with no host transfer.
        The work runs in program span ``executor.stage``."""
        reqs = list(reqs)
        seq = next(_DISPATCH_SEQ)
        with tracing.span("executor.stage", chunk=seq, launches=len(reqs)):
            return self._submit(reqs, patches, seq)

    def _submit(self, reqs, patches, seq) -> PendingChunk:
        place = (jax.default_device(self.device) if self.device is not None
                 else contextlib.nullcontext())
        with place:
            h = launch([r.prog for r in reqs], [r.mem0 for r in reqs],
                       [r.n_items for r in reqs], self.sim_cfg,
                       out_regions=[r.out_region for r in reqs],
                       patches=patches, mesh=self.mesh)
        # a sharded or pinned dispatch is a different compiled artifact
        # than the plain one: placement suffixes the engine's envelope
        env = h.envelope + (self.shards,
                            None if self.device is None else self.device.id)
        traced = env in self._envelopes
        # the jit trace is paid HERE, at dispatch — record the envelope
        # now so identical-envelope chunks dispatched ahead in the same
        # pipeline window count as the hits they really are
        self._envelopes.add(env)
        return PendingChunk(h, reqs, traced,
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
            info["time_us"] = info["cycles"] / self.cfg.freq_mhz
            results.append(Result(mem, info))
        return results

    def run(self, reqs: Sequence[Request]) -> List[Result]:
        """Execute one planned chunk synchronously (dispatch + collect)."""
        return self.collect(self.submit(reqs))


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
