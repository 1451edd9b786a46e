"""The fault injector: a transparent ``Executor`` wrapper that applies a
:class:`~repro.faults.plan.FaultPlan` at the dispatch boundary.

The injector sits where a fleet's ``executor_wrap`` hook puts it —
between each device's ``Scheduler`` and its real ``Executor`` — and
implements the executor protocol (``submit`` / ``chunk_ready`` /
``collect``; everything else delegates). Faults enter at exactly three
points:

  * **submit** — launches drawn for a pre-dispatch SEU get their flips
    appended to the chunk's staged-memory patches as one ``op="xor"``
    ``Patch`` over the hit launches: the bit flip rides the engine's
    patch path, so injection costs one XLA dispatch and is *in* the
    staged buffer the kernel reads — not a host-side fiction. Chunks
    drawn as stragglers (or dispatched on a wedged device) are recorded
    in ``_holds``.
  * **chunk_ready** — held chunks report not-ready until their hold
    expires (never, for a stuck device). The scheduler's readiness-
    ordered collection and the fleet's hedging both key off this.
  * **collect** — a held chunk past the executor ``timeout_s`` raises
    ``DeviceTimeout`` exactly as the real executor would; an expired
    straggler hold sleeps out its remainder and resolves normally.
    Collected results drawn for post-compute corruption get one bit
    flipped — the silent-data-corruption path only a checksum audit can
    catch.

Every decision is appended to ``injected`` — ``(kind, device, ticket,
attempt, ...)`` tuples — which is the determinism surface the fault
tests compare across runs: same seed, same plan, byte-identical log.
"""
from __future__ import annotations

import time
from typing import List, Sequence

import numpy as np

from repro.faults.plan import FaultPlan
from repro.ggpu.engine import Patch
from repro.serve.executors import DeviceTimeout, Executor, PendingChunk
from repro.serve.request import Request, Result


class FaultInjector:
    """Wraps one device's executor with a deterministic fault plan
    (module doc). With an inactive plan every call is pure delegation
    plus one dict lookup — and ``submit`` adds nothing at all."""

    def __init__(self, name: str, executor: Executor, plan: FaultPlan):
        self.name = name
        self.inner = executor
        self.plan = plan
        self._holds: dict = {}      # id(pending) -> None (stuck) | ready-at
        self._dispatches = 0
        self.injected: List[tuple] = []   # the decision log (module doc)

    def __getattr__(self, attr):
        # transparent protocol passthrough: cfg, stats, shards, memo,
        # timeout_s, run, ... — the scheduler never knows we're here
        return getattr(self.inner, attr)

    # -- submit: pre-dispatch SEUs + hold decisions --------------------------

    def submit(self, reqs: Sequence[Request],
               patches: Sequence[Patch] = ()) -> PendingChunk:
        reqs = list(reqs)
        ordinal = self._dispatches
        self._dispatches += 1
        if self.plan.seu_rate:
            patches = list(patches) + self._seu_patches(reqs)
        pending = self.inner.submit(reqs, patches)
        first = reqs[0]
        if self.plan.stuck(self.name, ordinal):
            self._holds[id(pending)] = None
            self.injected.append(("stuck", self.name, first.ticket,
                                  first.attempts, ordinal))
        elif self.plan.straggler_rate \
                and self.plan.straggler_hit(first.ticket, first.attempts):
            self._holds[id(pending)] = time.monotonic() \
                + self.plan.straggler_delay_s
            self.injected.append(("straggler", self.name, first.ticket,
                                  first.attempts, ordinal))
        return pending

    def _seu_patches(self, reqs: List[Request]) -> List[Patch]:
        """This chunk's drawn bit flips as XOR patches, one over the hit
        launches of each memory size (a cohort has one size). Each mask
        spans its launches' whole image, so the patch envelope is the
        same whichever words were hit."""
        hits: dict = {}                 # memory size -> [(launch, word, bit)]
        for i, r in enumerate(reqs):
            if self.plan.seu_hit(r.ticket, r.attempts):
                msize = int(r.mem0.shape[0])
                word, bit = self.plan.seu_flip(r.ticket, r.attempts, msize)
                hits.setdefault(msize, []).append((i, word, bit))
                self.injected.append(("seu", self.name, r.ticket,
                                      r.attempts, word, bit))
        patches = []
        for msize, flips in hits.items():
            masks = np.zeros((len(flips), msize), np.int32)
            for k, (_, word, bit) in enumerate(flips):
                masks[k, word] = np.int32(1) << bit
            patches.append(Patch(0, msize, masks,
                                 rows=[i for i, _, _ in flips], op="xor"))
        return patches

    # -- readiness + collection: holds, timeouts, post-compute SDC -----------

    def chunk_ready(self, pending: PendingChunk) -> bool:
        hold = self._holds.get(id(pending), False)
        if hold is None:                      # stuck: never ready
            return False
        if hold is not False and time.monotonic() < hold:
            return False                      # straggler: not yet
        return self.inner.chunk_ready(pending)

    def collect(self, pending: PendingChunk) -> List[Result]:
        key = id(pending)
        if key in self._holds:
            hold = self._holds.pop(key)
            deadline = None if self.inner.timeout_s is None \
                else pending.t_dispatch + self.inner.timeout_s
            if hold is None:
                # a wedged device only ever resolves via the timeout
                if deadline is not None:
                    time.sleep(max(0.0, deadline - time.monotonic()))
                raise DeviceTimeout(
                    f"device {self.name} stuck: chunk of "
                    f"{len(pending.reqs)} launch(es) never resolved")
            if deadline is not None and hold >= deadline:
                time.sleep(max(0.0, deadline - time.monotonic()))
                raise DeviceTimeout(
                    f"device {self.name} straggled past its "
                    f"{self.inner.timeout_s}s timeout")
            time.sleep(max(0.0, hold - time.monotonic()))
        results = self.inner.collect(pending)
        if self.plan.seu_post_rate:
            results = [self._corrupt(r, res)
                       for r, res in zip(pending.reqs, results)]
        return results

    def _corrupt(self, req: Request, res: Result) -> Result:
        """Post-compute silent corruption: flip one drawn bit of the
        collected words (no-op for cycles-only results)."""
        msize = int(np.asarray(res.mem).shape[0])
        if not msize or not self.plan.post_hit(req.ticket, req.attempts):
            return res
        word, bit = self.plan.post_flip(req.ticket, req.attempts, msize)
        mem = np.array(res.mem, np.int32, copy=True)
        mem[word] ^= np.int32(1) << bit
        self.injected.append(("sdc", self.name, req.ticket, req.attempts,
                              word, bit))
        return Result(mem, res.info)
