"""Shared layer math: norms, MLPs, rotary embeddings, embedding/unembedding.

All functions are pure; params are plain dict subtrees produced by
``repro.models.schema``. Params are stored in ``cfg.param_dtype`` (f32) and
cast to ``cfg.compute_dtype`` (bf16) at the point of use. Training keeps
full-precision master weights for the optimizer, so it casts at every step;
a serving engine holds the weights as ``schema.served_params`` gives them,
each leaf the forward reads only in compute dtype already cast to it once at
load, so the cast here is a no-op. Norm scales and the few leaves read in
f32 (``ParamSpec.compute_read`` False) keep their stored dtype.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig


def cdt(cfg: ModelConfig):
    return jnp.dtype(cfg.compute_dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def apply_norm(p, x, cfg: ModelConfig):
    """RMSNorm or LayerNorm, computed in f32, returned in compute dtype."""
    xf = x.astype(jnp.float32)
    if cfg.norm == "rmsnorm":
        xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + cfg.norm_eps)
    else:
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
        xf = (xf - mu) * jax.lax.rsqrt(var + cfg.norm_eps)
    out = xf * p["scale"].astype(jnp.float32)
    if "bias" in p:
        out = out + p["bias"].astype(jnp.float32)
    return out.astype(cdt(cfg))


def rms_head_norm(scale, x, eps=1e-6):
    """Per-head RMS norm used by mLSTM output (f32 in/out preserved)."""
    xf = x.astype(jnp.float32)
    xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * scale.astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# linear / mlp
# ---------------------------------------------------------------------------

def linear(p, x, cfg: ModelConfig):
    y = x @ p["w"].astype(cdt(cfg))
    if "b" in p:
        y = y + p["b"].astype(cdt(cfg))
    return y


def apply_mlp(p, x, cfg: ModelConfig):
    h = apply_norm(p["norm"], x, cfg)
    if cfg.mlp == "swiglu":
        gu = linear(p["wi"], h, cfg)
        g, u = jnp.split(gu, 2, axis=-1)
        h = jax.nn.silu(g) * u
    else:
        h = jax.nn.gelu(linear(p["wi"], h, cfg))
    return linear(p["wo"], h, cfg)


# ---------------------------------------------------------------------------
# rotary position embeddings (RoPE and Qwen2-VL M-RoPE)
# ---------------------------------------------------------------------------

def _rope_freqs(hd: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention-temperature factor ``0.1 * mscale * ln(factor) + 1``."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_freqs(dim: int, theta: float, factor: float, original: int,
               beta_fast: float, beta_slow: float):
    """YaRN (NTK-by-parts) inverse frequencies of a ``dim``-wide rotary
    head: the original frequencies ``theta ** (-2i/dim)`` where a
    dimension turns more than ``beta_fast`` times over the original
    context, those divided by ``factor`` where it turns fewer than
    ``beta_slow`` times, and a linear ramp between."""
    def corr(rot):
        return dim * math.log(original / (rot * 2 * math.pi)) \
            / (2 * math.log(theta))
    low = max(math.floor(corr(beta_fast)), 0)
    high = min(math.ceil(corr(beta_slow)), dim - 1)
    extra = _rope_freqs(dim, theta)
    ramp = (jnp.arange(dim // 2, dtype=jnp.float32) - low) \
        / (high - low if high > low else 0.001)
    keep = 1.0 - jnp.clip(ramp, 0.0, 1.0)        # 1: extrapolate (original)
    return extra / factor * (1.0 - keep) + extra * keep


def rope_freqs(cfg: ModelConfig, dim: int):
    """Inverse frequencies of a ``dim``-wide rotary head under ``cfg``."""
    if cfg.yarn_factor:
        return yarn_freqs(dim, cfg.rope_theta, cfg.yarn_factor,
                          cfg.yarn_original_max_pos, cfg.yarn_beta_fast,
                          cfg.yarn_beta_slow)
    return _rope_freqs(dim, cfg.rope_theta)


def apply_rope(x, positions, theta: float, inv=None):
    """x: (..., S, H, hd), positions: broadcastable to (..., S). ``inv``
    overrides the plain frequencies of ``theta`` (YaRN)."""
    hd = x.shape[-1]
    if inv is None:
        inv = _rope_freqs(hd, theta)                          # (hd/2,)
    ang = positions[..., None].astype(jnp.float32) * inv      # (..., S, hd/2)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x, positions3, theta: float, sections):
    """Qwen2-VL multimodal RoPE. positions3: (3, ..., S) for (t, h, w) streams;
    head dim is split into ``sections`` (summing to hd/2), each rotated by its
    own position stream."""
    hd = x.shape[-1]
    inv = _rope_freqs(hd, theta)                              # (hd/2,)
    # build a per-frequency position by selecting the stream for its section
    sec_id = jnp.repeat(jnp.arange(len(sections)), jnp.array(sections),
                        total_repeat_length=hd // 2)          # (hd/2,)
    pos = jnp.take(positions3, sec_id, axis=0)                # (hd/2, ..., S)
    pos = jnp.moveaxis(pos, 0, -1)                            # (..., S, hd/2)
    ang = pos.astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# embedding / unembedding / loss
# ---------------------------------------------------------------------------

def embed_tokens(p, tokens, cfg: ModelConfig):
    return jnp.take(p["w"], tokens, axis=0).astype(cdt(cfg))


def unembed(params, x, cfg: ModelConfig):
    if cfg.tie_embeddings:
        w = params["embed"]["w"].astype(cdt(cfg)).T
        return x @ w
    return linear(params["lm_head"], x, cfg)


def cross_entropy(logits, labels, mask=None):
    """Mean token CE in f32. logits: (..., V), labels: (...) int32."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = lse - ll
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)
