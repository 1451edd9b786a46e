"""Attention: GQA with RoPE/M-RoPE, full/sliding-window/local variants.

Two execution paths:
  * pure-JAX *blocked* attention (``lax.scan`` over q/kv chunks with online
    softmax) — O(S·chunk) memory, compiles on any backend; this is what the
    dry-run lowers. Used as the oracle for the Pallas kernel.
  * Pallas TPU flash kernel (``repro.kernels.flash_attention``) selected by
    ``cfg.use_pallas`` — the TPU hot path; compiled only for a TPU, and
    checked against the blocked path in interpret mode on the CPU.

Shapes: q (B,S,H,hd); k,v (B,Skv,Hkv,hd); GQA folds H = Hkv * G.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental.layout import Layout, with_layout_constraint

from repro.models.config import ModelConfig
from repro.models.layers import apply_mrope, apply_norm, apply_rope, cdt, linear
from repro.sharding import shard_hint


class KVCache(NamedTuple):
    k: jax.Array          # (B, Smax, Hkv, hd); (L, B, Smax, Hkv, hd) stacked
    v: jax.Array


NEG_INF = -1e30


def cache_layer(stack, layer):
    """Layer ``layer`` of a cache stacked over layers (leading axis), a
    slice of ``stack`` for the einsums that read it."""
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, layer, 0, keepdims=False),
        stack)


def cache_write(stack, layer, new, slot=None):
    """``stack`` with ``new`` written into layer ``layer`` in place.

    Each leaf of ``new`` is a layer's leaf: the rows (B, 1, ...) of one
    step, put at position ``slot`` of a positions axis (axis 2 of the
    stack), or with ``slot`` None a whole recurrent state. A stack of rows
    keeps, on a TPU, the layout it arrives with (``_tpu_cache_layout``)."""
    def put(a, n):
        start = [layer] + [0] * (a.ndim - 1)
        if slot is not None:
            start[2] = slot
        a = jax.lax.dynamic_update_slice(a, n[None].astype(a.dtype), start)
        if slot is None:
            return a
        return jax.lax.platform_dependent(a, tpu=_tpu_cache_layout,
                                          default=lambda x: x)
    return jax.tree.map(put, stack, new)


def _tpu_cache_layout(a):
    """Pin a cache stack (L, B, S, ...) inside the decode loop to the layout
    a TPU gives such an array by default, so that the loop neither copies
    the stack on the way in and out nor copies each layer for its einsums:
    rows a multiple of 128 lanes wide stay minor (row-major: the einsums
    read a layer in place, and the write of one step's rows pulls towards
    batch-minor without it); narrower rows (64-wide heads, MLA's rope key)
    go position-minor, the positions on the lanes. A row-major cache keeps
    that layout only with a multiple of 8 positions (``cache_capacity``)."""
    order = tuple(range(a.ndim))
    if a.shape[-1] % 128:
        order = order[:2] + order[3:] + (2,)
    return with_layout_constraint(a, Layout(order))


def _fold_gqa(q, n_kv: int):
    b, s, h, d = q.shape
    return q.reshape(b, s, n_kv, h // n_kv, d)


def _chunked(x, chunk: int, axis: int):
    """Reshape axis into (n_chunks, chunk)."""
    n = x.shape[axis] // chunk
    new_shape = x.shape[:axis] + (n, chunk) + x.shape[axis + 1:]
    return x.reshape(new_shape)


def blocked_attention(q, k, v, *, causal: bool, window: int, q_offset: int,
                      chunk_q: int, chunk_kv: int, scale: float):
    """Online-softmax blocked attention (flash-style, pure JAX).

    Scans q chunks (outer) and kv chunks (inner) carrying (m, l, acc); memory
    is O(B·H·chunk_q·hd) instead of O(S²). Values may be narrower than
    queries and keys (MLA: 128 beside 192).
    """
    b, sq, hkv, g = q.shape[0], q.shape[1], k.shape[2], q.shape[2] // k.shape[2]
    hdv = v.shape[3]
    skv_real = k.shape[1]
    cq = min(chunk_q, sq)
    ck = min(chunk_kv, skv_real)
    pq, pk = (-sq) % cq, (-skv_real) % ck
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
    q = _fold_gqa(q, hkv)                                     # (B,Sq,Hkv,G,hd)
    nq, nk = (sq + pq) // cq, (skv_real + pk) // ck

    qc = jnp.moveaxis(_chunked(q, cq, 1), 1, 0)               # (nq,B,cq,Hkv,G,hd)
    kc = jnp.moveaxis(_chunked(k, ck, 1), 1, 0)               # (nk,B,ck,Hkv,hd)
    vc = jnp.moveaxis(_chunked(v, ck, 1), 1, 0)

    qpos_base = jnp.arange(cq)
    kpos_base = jnp.arange(ck)

    # Each chunk body is checkpointed: without this, reverse-mode stacks
    # every (q,kv) chunk pair's f32 scores for the backward pass (measured
    # 16 GiB per layer at 4k/72B — EXPERIMENTS.md §Perf). With it, the
    # backward recomputes scores chunk-by-chunk: the remat analogue of
    # flash attention's O(S) memory.
    def q_step(_, qi):
        qblk, qidx = qi                                       # (B,cq,Hkv,G,hd)
        qpos = q_offset + qidx * cq + qpos_base               # (cq,)

        @jax.checkpoint
        def kv_step(carry, ki):
            m, l, acc = carry
            kblk, vblk, kidx = ki
            kpos = kidx * ck + kpos_base                      # (ck,)
            s = jnp.einsum("bqhgd,bkhd->bhgqk", qblk.astype(jnp.float32),
                           kblk.astype(jnp.float32)) * scale  # (B,Hkv,G,cq,ck)
            # additive (cq, ck) mask, added pre-broadcast: XLA hoists the
            # loop-invariant per-chunk-pair table out of the scan, so keep
            # it tiny (a post-broadcast boolean select materializes a
            # (nq*nk*B*H*cq*ck) monster — gigabytes at 4k, terabytes at 32k).
            mask = jnp.broadcast_to(kpos[None, :] < skv_real, (cq, ck))
            if causal:
                mask &= kpos[None, :] <= qpos[:, None]
            if window > 0:
                mask &= kpos[None, :] > qpos[:, None] - window
            s = s + jnp.where(mask, 0.0, NEG_INF).astype(jnp.float32)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            pv = jnp.einsum("bhgqk,bkhd->bhgqd", p, vblk.astype(jnp.float32))
            acc_new = acc * corr[..., None] + pv
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, hkv, g, cq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, hkv, g, cq), jnp.float32)
        a0 = jnp.zeros((b, hkv, g, cq, hdv), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0), (kc, vc, jnp.arange(nk)))
        out = acc / jnp.maximum(l, 1e-30)[..., None]          # (B,Hkv,G,cq,hd)
        return None, jnp.moveaxis(out, 3, 1)                  # (B,cq,Hkv,G,hd)

    _, outs = jax.lax.scan(jax.checkpoint(q_step), None,
                           (qc, jnp.arange(nq)))
    out = jnp.moveaxis(outs, 0, 1).reshape(b, sq + pq, hkv * g, hdv)
    return out[:, :sq]                                        # (B,Sq,H,hdv)


def windowed_attention(q, k, v, *, window: int, chunk_q: int, scale: float):
    """Local/SWA attention with per-q-chunk KV slicing — O(S·window) FLOPs.

    For each q chunk starting at t, attends keys in [t - window, t + cq).
    KV is padded on the left by ``window`` so slices are static-size.
    """
    b, sq, h, hd = q.shape
    hkv = k.shape[2]
    cq = min(chunk_q, sq)
    pq = (-sq) % cq
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
    nq = (sq + pq) // cq
    span = window + cq
    q = _fold_gqa(q, hkv)
    kp = jnp.pad(k, ((0, 0), (window, pq), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (window, pq), (0, 0), (0, 0)))
    qc = jnp.moveaxis(_chunked(q, cq, 1), 1, 0)               # (nq,B,cq,Hkv,G,hd)

    qpos_base = jnp.arange(cq)
    kpos_base = jnp.arange(span)

    @jax.checkpoint
    def q_step(_, qi):
        qblk, qidx = qi
        start = qidx * cq                                     # kv slice start in padded coords
        kblk = jax.lax.dynamic_slice_in_dim(kp, start, span, axis=1)
        vblk = jax.lax.dynamic_slice_in_dim(vp, start, span, axis=1)
        qpos = start + qpos_base                              # unpadded q position
        kpos = start + kpos_base - window                     # unpadded key position
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qblk.astype(jnp.float32),
                       kblk.astype(jnp.float32)) * scale
        mask = (kpos[None, :] <= qpos[:, None]) \
            & (kpos[None, :] > qpos[:, None] - window) \
            & (kpos[None, :] >= 0) & (kpos[None, :] < sq)
        s = s + jnp.where(mask, 0.0, NEG_INF).astype(jnp.float32)  # pre-broadcast
        p = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bhgqk,bkhd->bhgqd", p, vblk.astype(jnp.float32))
        return None, jnp.moveaxis(out, 3, 1)                  # (B,cq,Hkv,G,hd)

    _, outs = jax.lax.scan(q_step, None, (qc, jnp.arange(nq)))
    return jnp.moveaxis(outs, 0, 1).reshape(b, sq + pq, h, hd)[:, :sq]


def decode_attention(q, cache: KVCache, pos, *, window: int, scale: float):
    """Single-token attention against a cache. q: (B,1,H,hd); pos: scalar
    current position (number of valid cache entries is pos+1 after insert)."""
    b, _, h, hd = q.shape
    hkv = cache.k.shape[2]
    smax = cache.k.shape[1]
    qf = _fold_gqa(q, hkv).astype(jnp.float32)                # (B,1,Hkv,G,hd)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qf, cache.k.astype(jnp.float32)) * scale
    kpos = jnp.arange(smax)
    mask = kpos <= pos
    if window > 0:
        mask &= kpos > pos - window
    s = jnp.where(mask[None, None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bhgqd", p, cache.v.astype(jnp.float32))
    return jnp.moveaxis(out, 3, 1).reshape(b, 1, h, hd)


# ---------------------------------------------------------------------------
# full attention block (projections + rope + attention + out proj)
# ---------------------------------------------------------------------------

def attn_block(p, x, cfg: ModelConfig, kind: str, *,
               positions=None, cache: Optional[KVCache] = None,
               cache_pos=None, layer=None, layer_window: int = 0):
    """Returns (out, new_cache). kind: attn | swa | local.

    Train/prefill: cache is None (prefill callers build the cache from the
    returned k/v via ``make_cache``); decode: x is (B,1,d), ``cache`` is
    the group's cache stacked over layers and ``layer`` this layer's index
    in it. The step's K and V rows are written into the stack in place,
    then attention reads the layer out of the updated stack.
    """
    b, s, _ = x.shape
    hd = cfg.hd
    h, hkv = cfg.n_heads, cfg.n_kv_heads
    window = layer_window or (cfg.window if kind in ("swa", "local") else 0)
    scale = hd ** -0.5

    hx = apply_norm(p["norm"], x, cfg)
    # GQA tensor-parallel attention: q heads shard over the model axis
    # whenever divisible; kv heads replicate when below the axis size
    # (kv=8 on a 16-way axis would otherwise force the WHOLE attention to
    # replicate — measured as the per-layer transient floor on 72B).
    q = shard_hint(linear(p["wq"], hx, cfg).reshape(b, s, h, hd), "heads")
    k = shard_hint(linear(p["wk"], hx, cfg).reshape(b, s, hkv, hd), "heads")
    v = shard_hint(linear(p["wv"], hx, cfg).reshape(b, s, hkv, hd), "heads")

    if positions is None:
        positions = jnp.arange(s)[None, :]
    if cfg.mrope:
        pos3 = positions if positions.ndim == 3 else jnp.broadcast_to(
            positions, (3, *positions.shape))
        q = apply_mrope(q, pos3, cfg.rope_theta, cfg.mrope_sections)
        k = apply_mrope(k, pos3, cfg.rope_theta, cfg.mrope_sections)
    else:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)

    if cache is not None:                                     # decode
        slot = cache_pos if window == 0 else cache_pos % cache.k.shape[2]
        new_cache = cache_write(cache, layer, KVCache(k, v), slot)
        kv = cache_layer(new_cache, layer)
        if window == 0:
            out = decode_attention(q, kv, cache_pos, window=0, scale=scale)
        else:
            # ring-buffer cache of size window: every live entry is in range
            out = _decode_ring(q, kv, cache_pos, window, scale)
        out = out.reshape(b, s, h * hd)
        return linear(p["wo"], out.astype(cdt(cfg)), cfg), new_cache

    if cfg.use_pallas:
        from repro.kernels import ops as kops
        out = kops.flash_attention(q, k, v, causal=cfg.causal, window=window,
                                   scale=scale)
    elif window > 0:
        out = windowed_attention(q, k, v, window=window,
                                 chunk_q=cfg.attn_q_chunk, scale=scale)
    else:
        out = blocked_attention(q, k, v, causal=cfg.causal, window=0, q_offset=0,
                                chunk_q=cfg.attn_q_chunk,
                                chunk_kv=cfg.attn_kv_chunk, scale=scale)
    out = out.reshape(b, s, h * hd).astype(cdt(cfg))
    kv = KVCache(k, v)                                        # for prefill cache build
    return linear(p["wo"], out, cfg), kv


def _decode_ring(q, cache: KVCache, pos, window: int, scale: float):
    """Decode attention over a ring-buffer window cache (size == window)."""
    b, _, h, hd = q.shape
    hkv = cache.k.shape[2]
    qf = _fold_gqa(q, hkv).astype(jnp.float32)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qf, cache.k.astype(jnp.float32)) * scale
    # slot i holds absolute position p_i with p_i ≡ i (mod window); valid iff
    # p_i in (pos - window, pos]; since buffer is overwritten mod window, a
    # slot is stale only before the buffer first fills.
    idx = jnp.arange(window)
    age = (pos - idx) % window                                # distance back
    valid = (pos - age) >= 0
    s = jnp.where(valid[None, None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bhgqd", p, cache.v.astype(jnp.float32))
    return jnp.moveaxis(out, 3, 1).reshape(b, 1, h, hd)
