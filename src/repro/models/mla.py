"""Multi-head latent attention (DeepSeek-V2 §2.1), without query compression.

With ``h = norm(x)``, per head: ``q = h Wq = [q_nope | q_pe]``; the joint
latent ``[c_kv | k_pe] = h Wkv_a`` with ``c_kv = kv_norm(c_kv)``; ``[k_nope |
v] = c_kv Wkv_b``; ``q_pe`` and the one ``k_pe`` all heads share are rotated
(YaRN where configured, rotating the two halves of the rope columns).
Scores are ``(q_nope.k_nope + q_pe.k_pe) * scale``, causal softmax, then
``softmax . v`` and ``Wo``; ``scale = (nope + rope) ** -0.5 * m**2`` with
YaRN's ``m = 0.1 * mscale_all_dim * ln(factor) + 1``.

Two serving forms of the same equations:

* prefill (and training) decompresses: ``k = [k_nope | k_pe]`` per head,
  192 wide beside 128-wide values, through the flash kernel (or the
  blocked jnp path off the TPU);
* decode is absorbed and attends over the *latent* cache ``(c_kv, k_pe)``:
  ``W_UK`` is folded into the query (``q_lat = q_nope W_UK^T``, per head
  nope -> rank) and ``W_UV`` into the output (``o = (p . c_kv) W_UV``), in
  bf16 operands with f32 accumulation and no f32 copy of the cache.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.models.attention import (NEG_INF, blocked_attention, cache_layer,
                                    cache_write)
from repro.models.config import ModelConfig
from repro.models.layers import (apply_norm, apply_rope, cdt, linear,
                                 rope_freqs, yarn_mscale)


class MLACache(NamedTuple):
    c_kv: jax.Array       # (B, Smax, kv_lora_rank)  normed latent
    k_pe: jax.Array       # (B, Smax, qk_rope_head_dim)  rotated
    # (a decode step holds them stacked over layers: (L, B, Smax, ...))


def softmax_scale(cfg: ModelConfig) -> float:
    scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    if cfg.yarn_factor and cfg.yarn_mscale_all_dim:
        scale *= yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim) ** 2
    return scale


def _rope(cfg: ModelConfig, x, positions):
    """Rotate ``x`` (..., S, H, rope) at ``positions`` (..., S)."""
    out = apply_rope(x, positions, cfg.rope_theta,
                     inv=rope_freqs(cfg, x.shape[-1]))
    if cfg.yarn_factor:
        # YaRN scales cos and sin by m(mscale) / m(mscale_all_dim)
        mag = yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale) / yarn_mscale(
            cfg.yarn_factor, cfg.yarn_mscale_all_dim or cfg.yarn_mscale)
        if mag != 1.0:
            out = (out.astype(jnp.float32) * mag).astype(x.dtype)
    return out


def _project(p, x, cfg: ModelConfig, positions):
    """Query heads and the rotated latent of ``x`` (B, S, d)."""
    b, s, _ = x.shape
    h, nope, rope = cfg.n_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    r = cfg.kv_lora_rank
    hx = apply_norm(p["norm"], x, cfg)
    q = linear(p["wq"], hx, cfg).reshape(b, s, h, nope + rope)
    q_nope, q_pe = q[..., :nope], _rope(cfg, q[..., nope:], positions)
    kv_a = linear(p["wkv_a"], hx, cfg)
    c_kv = apply_norm(p["kv_norm"], kv_a[..., :r], cfg)
    k_pe = _rope(cfg, kv_a[..., None, r:], positions)[:, :, 0]
    return q_nope, q_pe, c_kv, k_pe


def mla_block(p, x, cfg: ModelConfig, *, positions=None,
              cache: Optional[MLACache] = None, cache_pos=None, layer=None):
    """Returns (out, new_cache). Train/prefill: ``cache`` is None and the
    returned cache holds the sequence's latent ``(c_kv, k_pe)``; decode:
    ``x`` is (B, 1, d), ``cache`` is the group's latent cache stacked over
    layers and ``layer`` this layer's index in it: the step's latent row is
    written into the stack in place at ``cache_pos``, then the absorbed
    scores and values read the layer out of the updated stack."""
    b, s, _ = x.shape
    h, nope, vd = cfg.n_heads, cfg.qk_nope_head_dim, cfg.v_head_dim
    r = cfg.kv_lora_rank
    scale = softmax_scale(cfg)
    if positions is None:
        positions = jnp.arange(s)[None, :]
    q_nope, q_pe, c_kv, k_pe = _project(p, x, cfg, positions)
    wkv_b = p["wkv_b"]["w"].astype(cdt(cfg)).reshape(r, h, nope + vd)

    if cache is not None:
        with jax.named_scope("latent"):
            new_cache = cache_write(cache, layer, MLACache(c_kv, k_pe),
                                    cache_pos)
            out = absorbed_decode(q_nope[:, 0], q_pe[:, 0],
                                  cache_layer(new_cache, layer),
                                  wkv_b[..., :nope], wkv_b[..., nope:],
                                  cache_pos, scale)
        out = out.reshape(b, 1, h * vd).astype(cdt(cfg))
        return linear(p["wo"], out, cfg), new_cache

    kv = (c_kv @ wkv_b.reshape(r, h * (nope + vd))).reshape(
        b, s, h, nope + vd)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_pe[:, :, None, :],
                                          (b, s, h, k_pe.shape[-1]))], -1)
    q = jnp.concatenate([q_nope, q_pe], -1)
    v = kv[..., nope:]
    if cfg.use_pallas:
        from repro.kernels import ops as kops
        out = kops.flash_attention(q, k, v, causal=cfg.causal, scale=scale)
    else:
        out = blocked_attention(q, k, v, causal=cfg.causal, window=0,
                                q_offset=0, chunk_q=cfg.attn_q_chunk,
                                chunk_kv=cfg.attn_kv_chunk, scale=scale)
    out = out.reshape(b, s, h * vd).astype(cdt(cfg))
    return linear(p["wo"], out, cfg), MLACache(c_kv, k_pe)


def absorbed_decode(q_nope, q_pe, cache: MLACache, w_uk, w_uv, pos,
                    scale: float):
    """One query per row against the latent cache, in the absorbed form.

    q_nope (B, H, nope), q_pe (B, H, rope); cache (B, Smax, rank|rope);
    w_uk (rank, H, nope), w_uv (rank, H, v); entries past ``pos`` are
    masked. bf16 operands, f32 accumulation; returns (B, H, v) f32."""
    dt = cache.c_kv.dtype
    f32 = jnp.float32
    q_lat = jnp.einsum("bhn,rhn->bhr", q_nope.astype(dt), w_uk.astype(dt),
                       preferred_element_type=f32)
    s = jnp.einsum("bhr,bsr->bhs", q_lat.astype(dt), cache.c_kv,
                   preferred_element_type=f32)
    s = s + jnp.einsum("bhp,bsp->bhs", q_pe.astype(dt), cache.k_pe,
                       preferred_element_type=f32)
    s = s * scale
    live = jnp.arange(cache.c_kv.shape[1]) <= pos
    s = jnp.where(live[None, None, :], s, NEG_INF)
    prob = jax.nn.softmax(s, axis=-1)
    o_lat = jnp.einsum("bhs,bsr->bhr", prob.astype(dt), cache.c_kv,
                       preferred_element_type=f32)
    return jnp.einsum("bhr,rhv->bhv", o_lat.astype(dt), w_uv.astype(dt),
                      preferred_element_type=f32)
