"""LLM serving engine: prefill + decode with slot-based continuous
batching, expressed on the serving core's slot accounting.

A fixed decode batch of ``slots``; finished sequences free their slot and
the next queued request is prefilled into it (its KV written into the
shared cache at the slot's batch row). Greedy or temperature sampling.
This is the serve-side driver the decode dry-run cells lower. Admission
uses the same FIFO slot-wave planner (``scheduler.plan_waves``) the kernel
scheduler exposes, and results return in ticket (submission) order — the
same contract as the kernel path.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import tracing
from repro.models import model as M
from repro.models.config import ModelConfig
from repro.models.steps import make_decode_step
from repro.serve.scheduler import plan_waves


@dataclasses.dataclass
class EngineConfig:
    max_len: int = 256
    slots: int = 4
    temperature: float = 0.0
    eos_id: int = -1              # -1: never stop early
    seed: int = 0


class Engine:
    def __init__(self, cfg: ModelConfig, params, ecfg: EngineConfig):
        self.cfg, self.params, self.ecfg = cfg, params, ecfg
        # the cache is donated: each step writes its rows into it in place
        self.decode_fn = jax.jit(make_decode_step(cfg), donate_argnums=(1,))

    def _sample(self, logits, rng):
        if self.ecfg.temperature <= 0.0:
            return jnp.argmax(logits, axis=-1)
        return jax.random.categorical(rng, logits / self.ecfg.temperature,
                                      axis=-1)

    def generate(self, prompts: List[List[int]], max_new: int
                 ) -> List[List[int]]:
        """Slot-batched generation. Prompts are queued; each batch wave
        prefills up to ``slots`` prompts padded to a common length.

        Tokens come back one step behind: decode step ``t+1`` and its
        sampling are dispatched before the host pulls the ``(B,)`` tokens
        of step ``t`` in one transfer, so the pull waits on a device that
        already has the next step queued. EOS book-keeping runs on the
        host one step late; when every row is done, the one step dispatched
        past that is dropped, and its rng split is not kept, so the tokens
        are those of a step-by-step loop.

        Program spans (``repro.tracing``): ``engine.generate`` around the
        call, ``engine.wave`` around each slot wave, and inside it
        ``engine.prefill``, ``engine.decode`` (each decode dispatch),
        ``engine.sample`` and ``engine.token_pull`` (the blocking transfer
        of one step's tokens, where the host waits for the device). A pull
        carries ``ahead=``, the decode steps dispatched and not yet pulled
        when it began: 1 inside the loop, 0 for a wave's last pull."""
        with tracing.span("engine.generate"):
            return self._generate(prompts, max_new)

    def _generate(self, prompts, max_new):
        ecfg = self.ecfg
        results: List[Optional[List[int]]] = [None] * len(prompts)
        rng = jax.random.PRNGKey(ecfg.seed)
        for w, wave in enumerate(plan_waves(range(len(prompts)),
                                            ecfg.slots)):
            with tracing.span("engine.wave", wave=w):
                plen = max(len(prompts[i]) for i in wave)
                batch = np.zeros((len(wave), plen), np.int32)
                for r, i in enumerate(wave):
                    batch[r, plen - len(prompts[i]):] = prompts[i]  # left-pad
                cap = plen + max_new + 1
                with tracing.span("engine.prefill"):
                    logits, cache = M.prefill(self.params, self.cfg,
                                              tokens=jnp.asarray(batch),
                                              pad_to=cap)
                toks = [list(prompts[i]) for i in wave]
                done = np.zeros(len(wave), bool)

                def pull(last, ahead):
                    """Append one step's tokens to the rows not yet done;
                    True once every row is."""
                    with tracing.span("engine.token_pull", ahead=ahead):
                        got = jax.device_get(last).tolist()
                    for r, tok in enumerate(got):
                        if not done[r]:
                            toks[r].append(tok)
                            done[r] = tok == ecfg.eos_id
                    return done.all()

                with tracing.span("engine.sample"):
                    last = self._sample(logits, rng)
                for t in range(max_new - 1):
                    nrng, sub = jax.random.split(rng)
                    with tracing.span("engine.decode"):
                        logits, cache = self.decode_fn(
                            self.params, cache, last[:, None],
                            jnp.asarray(plen + t, jnp.int32))
                    with tracing.span("engine.sample"):
                        nxt = self._sample(logits, sub)
                    if pull(last, ahead=1):
                        break                    # step t is dropped
                    rng, last = nrng, nxt
                else:
                    pull(last, ahead=0)
                for r, i in enumerate(wave):
                    results[i] = toks[r]
        return results  # type: ignore
