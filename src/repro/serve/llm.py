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
        self.decode_fn = jax.jit(make_decode_step(cfg))

    def _sample(self, logits, rng):
        if self.ecfg.temperature <= 0.0:
            return jnp.argmax(logits, axis=-1)
        return jax.random.categorical(rng, logits / self.ecfg.temperature,
                                      axis=-1)

    def generate(self, prompts: List[List[int]], max_new: int
                 ) -> List[List[int]]:
        """Slot-batched generation. Prompts are queued; each batch wave
        prefills up to ``slots`` prompts padded to a common length.

        Program spans (``repro.tracing``): ``engine.generate`` around the
        call, ``engine.wave`` around each slot wave, and inside it
        ``engine.prefill``, ``engine.decode`` (each decode dispatch),
        ``engine.sample`` and ``engine.token_pull`` (each loop of
        ``int(last[r])``, where the host waits for the device)."""
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
                with tracing.span("engine.sample"):
                    last = self._sample(logits, rng)
                done = np.zeros(len(wave), bool)
                with tracing.span("engine.token_pull"):
                    for r in range(len(wave)):
                        tok = int(last[r])
                        toks[r].append(tok)
                        if tok == ecfg.eos_id:
                            done[r] = True       # EOS straight out of prefill
                for t in range(max_new - 1):
                    if done.all():
                        break
                    rng, sub = jax.random.split(rng)
                    with tracing.span("engine.decode"):
                        logits, cache = self.decode_fn(
                            self.params, cache, last[:, None],
                            jnp.asarray(plen + t, jnp.int32))
                    with tracing.span("engine.sample"):
                        last = self._sample(logits, sub)
                    with tracing.span("engine.token_pull"):
                        for r in range(len(wave)):
                            if not done[r]:
                                tok = int(last[r])
                                toks[r].append(tok)
                                if tok == ecfg.eos_id:
                                    done[r] = True
                for r, i in enumerate(wave):
                    results[i] = toks[r]
        return results  # type: ignore
