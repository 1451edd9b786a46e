"""Model assembly: grouped layer-stack scan, caches, chunked LM loss.

The layer stack is partitioned into homogeneous *groups* (see
``schema.layer_groups``): a uniform arch is one group scanned ``n_layers``
times; RecurrentGemma is ``(rglru, rglru, local) x 8`` plus a remainder
group; xLSTM is ``(mlstm x3, slstm) x 6``. Scanning keeps the HLO (and
compile time) independent of depth — essential when dry-running 80-layer
models for 512 devices.

Caches mirror the group structure with a leading ``repeats`` dim. Prefill
produces them as the scans' stacked outputs; a decode step carries each
group's stack through its scan and each layer writes only its new entries
into it in place (one row per sequence, or a recurrent layer's whole
state), so no step copies a whole layer's cache.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.models import recurrent as rec
from repro.models.attention import (KVCache, attn_block, cache_layer,
                                    cache_write)
from repro.models.config import ATTENTION_KINDS, ModelConfig
from repro.models.layers import (apply_mlp, apply_norm, cdt, cross_entropy,
                                 embed_tokens, linear, unembed)
from repro.models.mla import MLACache, mla_block
from repro.models.moe import apply_moe
from repro.models.schema import layer_groups, moe_group
from repro.sharding import shard_hint


# ---------------------------------------------------------------------------
# caches
# ---------------------------------------------------------------------------

def cache_capacity(n: int) -> int:
    """Positions a decode cache holds when ``n`` are asked for: ``n``
    rounded up to a multiple of 8, the TPU's sublane tile, so that a cache
    of rows a multiple of 128 wide keeps its row-major layout there."""
    return -(-n // 8) * 8


def _attn_cache_init(cfg: ModelConfig, kind: str, b: int, cap: int):
    window = cfg.window if kind in ("swa", "local") else 0
    c = min(window, cap) if window else cap
    shape = (b, c, cfg.n_kv_heads, cfg.hd)
    return KVCache(jnp.zeros(shape, cdt(cfg)), jnp.zeros(shape, cdt(cfg)))


def _mixer_cache_init(cfg: ModelConfig, kind: str, b: int, cap: int):
    d = cfg.d_model
    if kind == "mla":                       # the latent cache
        return MLACache(
            jnp.zeros((b, cap, cfg.kv_lora_rank), cdt(cfg)),
            jnp.zeros((b, cap, cfg.qk_rope_head_dim), cdt(cfg)))
    if kind in ("attn", "swa", "local"):
        return _attn_cache_init(cfg, kind, b, cap)
    if kind == "mlstm":
        de = 2 * d
        return rec.mlstm_state_init(b, cfg.n_heads, de // cfg.n_heads, de)
    if kind == "slstm":
        return rec.slstm_state_init(b, d)
    if kind == "rglru":
        return rec.rglru_state_init(b, cfg.lru_d)
    raise ValueError(kind)


def init_cache(cfg: ModelConfig, batch: int, cap: int):
    """Decode cache pytree matching the params group structure, of
    ``cache_capacity(cap)`` positions."""
    cap = cache_capacity(cap)
    groups = {}
    for gi, (unit, reps) in enumerate(layer_groups(cfg)):
        g = {str(i): _mixer_cache_init(cfg, kind, batch, cap)
             for i, kind in enumerate(unit)}
        groups[str(gi)] = jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (reps, *x.shape)).copy(), g)
    return groups


# ---------------------------------------------------------------------------
# one unit of blocks (the scan body)
# ---------------------------------------------------------------------------

def _apply_unit(unit, p_unit, x, cfg: ModelConfig, caches, positions,
                cache_pos, mode: str, prefill_pad: int = 0,
                moe: bool = False, layer=None):
    """Apply the blocks of one pattern unit. Returns (x, new_caches, aux).

    In decode, ``caches`` are the unit's caches stacked over the group's
    layers and ``layer`` is this unit's index in them; the returned caches
    are the stacks with this layer's new entries written in.

    Named scopes (op-name metadata only): ``attn`` around an attention
    mixer, its cache write and read included (for MLA, ``attn/latent``
    around the latent-cache write and the absorbed scores and values); the
    recurrent kind's name around a recurrent mixer; ``mlp`` around the MLP
    or MoE (held-expert MoE: ``mlp/router``, ``mlp/experts``,
    ``mlp/shared``)."""
    aux = jnp.zeros((), jnp.float32)
    new_caches: Dict[str, Any] = {}
    for idx, kind in enumerate(unit):
        bp = p_unit[str(idx)]
        ci = caches.get(str(idx)) if caches is not None else None
        attention = kind in ATTENTION_KINDS
        with jax.named_scope("attn" if attention else kind):
            if kind == "mla":
                out, c_new = mla_block(bp["mixer"], x, cfg,
                                       positions=positions, cache=ci,
                                       cache_pos=cache_pos, layer=layer)
            elif attention:
                out, c_new = attn_block(bp["mixer"], x, cfg, kind,
                                        positions=positions, cache=ci,
                                        cache_pos=cache_pos, layer=layer)
            else:
                block = {"mlstm": rec.mlstm_block, "slstm": rec.slstm_block,
                         "rglru": rec.rglru_block}[kind]
                state = None if ci is None else cache_layer(ci, layer)
                out, c_new = block(bp["mixer"], x, cfg, state)
                if ci is not None:
                    c_new = cache_write(ci, layer, c_new)
            if attention and mode == "train":
                c_new = None
            elif attention and mode == "prefill":
                c_new = (_pad_cache(cfg, c_new, prefill_pad) if kind == "mla"
                         else _prefill_attn_cache(cfg, kind, c_new,
                                                  prefill_pad))
        x = shard_hint(x + out, "acts")
        if "mlp" in bp:
            with jax.named_scope("mlp"):
                if moe:
                    mo, a = apply_moe(bp["mlp"], x, cfg)
                    aux = aux + a
                else:
                    mo = apply_mlp(bp["mlp"], x, cfg)
            x = shard_hint(x + mo, "acts")
        if c_new is not None:
            new_caches[str(idx)] = c_new
    return x, (new_caches or None), aux


def _pad_cache(cfg: ModelConfig, cache, pad_to: int):
    """A prefill-computed full-sequence cache (positions on axis 1) padded
    to ``pad_to`` capacity, in the compute dtype."""
    def pad(a):
        extra = max(pad_to - a.shape[1], 0)
        a = jnp.pad(a, ((0, 0), (0, extra)) + ((0, 0),) * (a.ndim - 2))
        return a.astype(cdt(cfg))
    return jax.tree.map(pad, cache)


def _prefill_attn_cache(cfg: ModelConfig, kind: str, kv: KVCache,
                        pad_to: int = 0) -> KVCache:
    """Convert prefill-computed (k, v) into a decode cache (window tail,
    ring-buffer aligned; full-attn caches padded to ``pad_to`` capacity)."""
    window = cfg.window if kind in ("swa", "local") else 0
    k, v = kv.k, kv.v
    s = k.shape[1]
    if window and s > window:
        k, v = k[:, -window:], v[:, -window:]
        shift = s % window
        k = jnp.roll(k, shift, axis=1)
        v = jnp.roll(v, shift, axis=1)
    elif window and s < window:
        # ring decode indexes slots mod window: pad short prefills to the
        # full window (slot i == position i while the buffer first fills)
        pad = ((0, 0), (0, window - s), (0, 0), (0, 0))
        k, v = jnp.pad(k, pad), jnp.pad(v, pad)
    elif not window and pad_to > s:
        pad = ((0, 0), (0, pad_to - s), (0, 0), (0, 0))
        k, v = jnp.pad(k, pad), jnp.pad(v, pad)
    return KVCache(k.astype(jnp.dtype(cfg.compute_dtype)),
                   v.astype(jnp.dtype(cfg.compute_dtype)))


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _remat(fn, cfg: ModelConfig):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots)
    return jax.checkpoint(fn, policy=jax.checkpoint_policies.nothing_saveable)


def _group_k(cfg: ModelConfig) -> int:
    """remat='group:k' -> k (0 = plain per-layer remat)."""
    if cfg.remat.startswith("group:"):
        return int(cfg.remat.split(":")[1])
    return 0


def forward(params, cfg: ModelConfig, *, tokens=None, embeds=None,
            positions=None, cache=None, cache_pos=None, mode: str = "train",
            prefill_pad: int = 0):
    """Run the stack. Returns (x_final, new_cache, aux_loss).

    mode: train (no caches) | prefill (produce caches) | decode (update the
    given caches in place).
    """
    if embeds is not None:
        x = linear(params["frontend_proj"], embeds.astype(cdt(cfg)), cfg)
    else:
        x = embed_tokens(params["embed"], tokens, cfg)
    x = shard_hint(x, "acts")
    if positions is None:
        base = jnp.arange(x.shape[1])[None, :]
        if mode == "decode":
            base = base + cache_pos
        positions = jnp.broadcast_to(base, (3, *x.shape[:2])) if cfg.mrope \
            else jnp.broadcast_to(base, x.shape[:2])

    aux_total = jnp.zeros((), jnp.float32)
    new_cache: Dict[str, Any] = {}
    for gi, (unit, reps) in enumerate(layer_groups(cfg)):
        gp = params["groups"][str(gi)]
        gcache = cache[str(gi)] if cache is not None else None
        apply_unit = functools.partial(_apply_unit, moe=moe_group(cfg, gi))

        if mode == "train":
            def body(carry, p_unit, _unit=unit):
                xc, auxc = carry
                xo, _, a = apply_unit(_unit, p_unit, xc, cfg, None,
                                      positions, cache_pos, mode)
                return (xo, auxc + a), None
            k = _group_k(cfg)
            if k > 1 and reps % k == 0 and reps > k:
                # sqrt(L)-style recursive checkpointing: the outer scan
                # saves x once per k layers (residual stack / k); the
                # backward recomputes each group's k layers transiently.
                # See EXPERIMENTS.md §Perf (qwen2-vl-72b iteration 3).
                grouped = jax.tree.map(
                    lambda t: t.reshape(reps // k, k, *t.shape[1:]), gp)

                def group_body(carry, p_group, _unit=unit):
                    def inner(c, p_u):
                        xc, auxc = c
                        xo, _, a = apply_unit(_unit, p_u, xc, cfg, None,
                                              positions, cache_pos, mode)
                        return (xo, auxc + a), None
                    # recursive: the inner layers are checkpointed too,
                    # else the group recompute saves k layers of internals
                    c2, _ = jax.lax.scan(
                        jax.checkpoint(
                            inner,
                            policy=jax.checkpoint_policies.nothing_saveable),
                        carry, p_group)
                    return c2, None
                (x, aux_total), _ = jax.lax.scan(
                    jax.checkpoint(
                        group_body,
                        policy=jax.checkpoint_policies.nothing_saveable),
                    (x, aux_total), grouped)
            else:
                (x, aux_total), _ = jax.lax.scan(
                    _remat(body, cfg), (x, aux_total), gp)
        elif mode == "prefill":
            # caches are produced, not consumed: xs carries params only
            def body(carry, p_unit, _unit=unit):
                xc, auxc = carry
                xo, c_new, a = apply_unit(_unit, p_unit, xc, cfg, None,
                                          positions, cache_pos, mode,
                                          prefill_pad)
                return (xo, auxc + a), c_new
            (x, aux_total), new_cache[str(gi)] = jax.lax.scan(
                body, (x, aux_total), gp)
        else:
            # the group's cache rides in the carry, updated in place layer
            # by layer; xs carries the params and each layer's index
            def body(carry, xs, _unit=unit):
                xc, auxc, cc = carry
                p_unit, i = xs
                xo, cc, a = apply_unit(_unit, p_unit, xc, cfg, cc, positions,
                                       cache_pos, mode, layer=i)
                return (xo, auxc + a, cc), None
            (x, aux_total, new_cache[str(gi)]), _ = jax.lax.scan(
                body, (x, aux_total, gcache), (gp, jnp.arange(reps)))

    x = apply_norm(params["final_norm"], x, cfg)
    return x, (new_cache or None), aux_total


# ---------------------------------------------------------------------------
# losses / logits
# ---------------------------------------------------------------------------

def chunked_lm_loss(params, cfg: ModelConfig, x, labels, chunk: int = 1024):
    """Cross-entropy without materializing (B, S, V): scan over S chunks,
    rematerializing logits in the backward pass."""
    b, s, d = x.shape
    ck = min(chunk, s)
    n = s // ck
    xs = jnp.moveaxis(x.reshape(b, n, ck, d), 1, 0)
    ls = jnp.moveaxis(labels.reshape(b, n, ck), 1, 0)

    @jax.checkpoint
    def body(tot, inp):
        xc, lc = inp
        logits = unembed(params, xc, cfg)
        logits = shard_hint(logits, "logits")
        nll = cross_entropy(logits, lc)
        return tot + nll, None

    tot, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (xs, ls))
    return tot / n


def lm_logits(params, cfg: ModelConfig, x):
    with jax.named_scope("lm_head"):
        return shard_hint(unembed(params, x, cfg), "logits")


# ---------------------------------------------------------------------------
# public entry points (what the steps / dry-run lower)
# ---------------------------------------------------------------------------

def loss_fn(params, cfg: ModelConfig, batch):
    """batch: {tokens | embeds, labels?, positions?}."""
    tokens = batch.get("tokens")
    embeds = batch.get("embeds")
    positions = batch.get("positions")
    if "labels" in batch:                   # pipeline provides shifted labels
        labels = batch["labels"]
        inputs = tokens
    else:                                   # causal LM fallback: shift here
        inputs, labels = tokens[:, :-1], tokens[:, 1:]
        if positions is not None:
            positions = positions[..., :-1]
    x, _, aux = forward(params, cfg, tokens=inputs, embeds=embeds,
                        positions=positions, mode="train")
    return chunked_lm_loss(params, cfg, x, labels) + aux


def prefill(params, cfg: ModelConfig, *, tokens=None, embeds=None,
            positions=None, pad_to: int = 0):
    """Returns (last_token_logits, cache); with ``pad_to``, the cache holds
    ``cache_capacity(pad_to)`` positions."""
    x, cache, _ = forward(params, cfg, tokens=tokens, embeds=embeds,
                          positions=positions, mode="prefill",
                          prefill_pad=cache_capacity(pad_to))
    logits = lm_logits(params, cfg, x[:, -1:, :])
    return logits[:, 0, :], cache


def decode_step(params, cfg: ModelConfig, cache, token, pos):
    """One decode step. token: (B, 1) int32; pos: scalar int32 (write slot).
    Returns (logits (B, V), new_cache): ``cache`` with the step's entries
    written in, the same structure (jitted with ``cache`` donated, the new
    cache takes its buffers)."""
    x, new_cache, _ = forward(params, cfg, tokens=token, cache=cache,
                              cache_pos=pos, mode="decode")
    logits = lm_logits(params, cfg, x)
    return logits[:, 0, :], new_cache


def encode(params, cfg: ModelConfig, embeds):
    """Encoder-only forward (HuBERT): full-sequence logits."""
    x, _, _ = forward(params, cfg, embeds=embeds, mode="train")
    return lm_logits(params, cfg, x)
