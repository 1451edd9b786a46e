"""Mixture-of-Experts FFN: a capacity-based scatter dispatch over every
expert, and a dropless dispatch over the experts one chip holds.

Token-choice top-k routing (Mixtral: k=2 of 8; Llama4-Scout: k=1 of 16;
DeepSeek-V2-Lite: k=6 of 64 plus 2 shared experts).

``apply_moe`` picks the path: a config that holds a share of the experts
(``experts_held``) runs ``apply_moe_held``, which routes over all
``n_experts``, computes the part its own experts give for the tokens routed
to them with a grouped matmul over (token, expert) pairs sorted by expert,
drops nothing, and adds the shared experts. What absent experts add is left
to the chips that hold them (expert parallelism without its exchange).

The capacity path:

Dispatch is *grouped by batch row*: each row computes its own
position-in-expert cumsum and scatters into an (E, C_row, d) slice. This
keeps the cumsum and scatter local to the data shard — a global cumsum over
the flattened token stream creates a cross-shard sequential dependency that
XLA resolves by all-gathering every token onto every device (measured:
215 GiB/device and a 5x collective blow-up on mixtral train_4k; see
EXPERIMENTS.md §Perf iteration 1). With experts sharded on the model axis
the (B, E, C, d) buffer reshard lowers to an all-to-all, as in production
MoE stacks.

Returns ``(out, aux_loss)`` where aux is the standard load-balancing loss.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.models.layers import apply_norm, cdt
from repro.sharding import shard_hint


def apply_moe(p, x, cfg: ModelConfig):
    """(out, aux_loss) of the MoE MLP on the residual stream ``x``."""
    if cfg.experts_held:                 # a share of the experts: dropless
        return apply_moe_held(p, x, cfg), jnp.zeros((), jnp.float32)
    return _apply_moe_capacity(p, x, cfg)


def route(p, hx, cfg: ModelConfig):
    """f32 softmax router over all ``n_experts``: top-k gate weights
    (renormalized if ``norm_topk_prob``, times ``routed_scaling``) and
    expert ids, each (..., k)."""
    logits = jnp.einsum("...d,de->...e", hx.astype(jnp.float32),
                        p["router"]["w"].astype(jnp.float32))
    gate, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), cfg.topk)
    if cfg.norm_topk_prob:
        gate = gate / jnp.clip(gate.sum(-1, keepdims=True), 1e-9)
    return gate * cfg.routed_scaling, idx


def _swiglu(x, wi, wo, cfg: ModelConfig):
    g, u = jnp.split(x @ wi.astype(cdt(cfg)), 2, axis=-1)
    return (jax.nn.silu(g) * u) @ wo.astype(cdt(cfg))


#: tokens per grouped-matmul pass of the held-expert path
TOKEN_CHUNK = 8192


def held_experts_part(p, hx, gate, idx, cfg: ModelConfig):
    """What this chip's experts give each token: (T, d) for ``hx`` (T, d)
    and its routing (T, k). The T*k (token, expert) pairs are sorted by
    held expert (pairs routed elsewhere last); one grouped matmul per
    weight runs each held expert on exactly its own pairs, so no pair is
    dropped whatever the routing."""
    t, d = hx.shape
    held, k = cfg.n_experts_held, cfg.topk
    local = idx.reshape(-1) - cfg.expert_shard * held        # (T*k,)
    mine = (local >= 0) & (local < held)
    local = jnp.where(mine, local, held)
    order = jnp.argsort(local, stable=True)
    sizes = jnp.bincount(local, length=held + 1)[:held].astype(jnp.int32)
    rows = hx[order // k]                                    # (T*k, d)
    wi = p["wi"].astype(cdt(cfg))
    wo = p["wo"].astype(cdt(cfg))
    gu = jax.lax.ragged_dot(rows, wi, sizes)
    g, u = jnp.split(gu, 2, axis=-1)
    y = jax.lax.ragged_dot(jax.nn.silu(g) * u, wo, sizes)     # (T*k, d)
    # rows past the held pairs belong to no group: zero them, never scale
    w = jnp.where(mine, gate.reshape(-1), 0.0)[order]
    y = jnp.where((jnp.arange(t * k) < sizes.sum())[:, None],
                  y.astype(jnp.float32) * w[:, None], 0.0)
    return jnp.zeros((t, d), jnp.float32).at[order // k].add(y)


def apply_moe_held(p, x, cfg: ModelConfig):
    """Dropless MoE over the held experts plus the shared experts."""
    b, s, d = x.shape
    hx = apply_norm(p["norm"], x, cfg).reshape(b * s, d)
    with jax.named_scope("router"):
        gate, idx = route(p, hx, cfg)
    with jax.named_scope("experts"):
        n = b * s // TOKEN_CHUNK
        if n > 1 and b * s % TOKEN_CHUNK == 0:
            # long prefills in chunks: the sorted pairs of all tokens at
            # once would take gigabytes
            out = jax.lax.map(
                lambda c: held_experts_part(p, *c, cfg),
                (hx.reshape(n, TOKEN_CHUNK, d),
                 gate.reshape(n, TOKEN_CHUNK, -1),
                 idx.reshape(n, TOKEN_CHUNK, -1))).reshape(b * s, d)
        else:
            out = held_experts_part(p, hx, gate, idx, cfg)
    if "shared" in p:
        with jax.named_scope("shared"):
            sh = p["shared"]
            out = out + _swiglu(hx, sh["wi"]["w"], sh["wo"]["w"],
                                cfg).astype(jnp.float32)
    return out.astype(cdt(cfg)).reshape(b, s, d)


def _apply_moe_capacity(p, x, cfg: ModelConfig):
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.topk
    cap = int(max(1, (s * k / e) * cfg.capacity_factor))   # per batch row
    cap = ((cap + 3) // 4) * 4

    hx = apply_norm(p["norm"], x, cfg)                     # (B, S, d)

    # --- routing (f32) ---
    logits = jnp.einsum("bsd,de->bse", hx.astype(jnp.float32),
                        p["router"]["w"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)                # (B, S, E)
    gate_vals, expert_idx = jax.lax.top_k(probs, k)        # (B, S, K)
    gate_vals = gate_vals / jnp.clip(gate_vals.sum(-1, keepdims=True), 1e-9)

    # --- per-row capacity assignment ---
    flat_e = expert_idx.reshape(b, s * k)                  # (B, S*K)
    onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)    # (B, S*K, E)
    pos_in_e = jnp.cumsum(onehot, axis=1) - 1              # row-local count
    position = jnp.take_along_axis(pos_in_e, flat_e[..., None],
                                   axis=2)[..., 0]         # (B, S*K)
    keep = position < cap
    slot = jnp.where(keep, flat_e * cap + position, e * cap)

    # --- dispatch: per-row scatter into (B, E*C+1, d) ---
    src = jnp.repeat(hx, k, axis=1)                        # (B, S*K, d)
    buf = jnp.zeros((b, e * cap + 1, d), cdt(cfg))
    buf = jax.vmap(lambda bf, sl, sr: bf.at[sl].add(sr))(
        buf, slot, src * keep[..., None].astype(cdt(cfg)))
    buf = buf[:, : e * cap].reshape(b, e, cap, d)
    buf = shard_hint(buf, "expert_buf4")                   # -> all-to-all

    # --- expert FFN (swiglu); experts sharded on the model axis ---
    wi = p["wi"].astype(cdt(cfg))
    wo = p["wo"].astype(cdt(cfg))
    gu = jnp.einsum("becd,edf->becf", buf, wi)
    g, u = jnp.split(gu, 2, axis=-1)
    h = jax.nn.silu(g) * u
    out_buf = jnp.einsum("becf,efd->becd", h, wo)          # (B, E, C, d)
    out_buf = shard_hint(out_buf, "expert_buf4")

    # --- combine: gather each (token, slot)'s row, weight, sum over K ---
    flat = jnp.concatenate(
        [out_buf.reshape(b, e * cap, d),
         jnp.zeros((b, 1, d), cdt(cfg))], axis=1)
    gathered = jax.vmap(lambda fl, sl: fl[sl])(flat, slot)  # (B, S*K, d)
    w = (gate_vals.reshape(b, s * k) * keep).astype(cdt(cfg))
    out = (gathered * w[..., None]).reshape(b, s, k, d).sum(axis=2)

    # --- load balancing aux (Switch/Mixtral form) ---
    frac_tokens = jnp.mean(
        jax.nn.one_hot(expert_idx[..., 0], e, dtype=jnp.float32), axis=(0, 1))
    frac_probs = jnp.mean(probs, axis=(0, 1))
    aux = e * jnp.sum(frac_tokens * frac_probs) * cfg.router_aux_coef

    return out, aux
