"""Plain reference of a Llama-architecture decoder (SmolLM, Llama 2):
float32 ``jax.numpy`` at full matmul precision, no kernels, no cache, no
batching, written from the published description. It imports nothing of
the program.

    h = embed[tokens]
    per layer:  h += Wo . attn(rope(Wq . rms(h)), rope(Wk . rms(h)), Wv . rms(h))
                h += Wdown . (silu(Wgate . rms(h)) * (Wup . rms(h)))
    logits = rms(h) . embed^T              (tied embeddings)

``rms(x) = x / sqrt(mean(x^2) + eps) * w``; attention is causal softmax
with scale ``head_dim ** -0.5``, query head ``i`` reading key/value head
``i // (heads / kv_heads)``; RoPE rotates the two halves of each head,
``[x1, x2] -> [x1 cos - x2 sin, x2 cos + x1 sin]``, at frequencies
``theta ** (-2j / head_dim)``.

Weights (``Weights``) are float32, layers stacked on a leading axis:
``embed (V, d)``, ``attn_norm``/``mlp_norm`` ``(L, d)``, ``wq (L, d, H*hd)``,
``wk``/``wv (L, d, Hkv*hd)``, ``wo (L, H*hd, d)``, ``w_gate``/``w_up
(L, d, ff)``, ``w_down (L, ff, d)``, ``final_norm (d,)``.

``fp8=True`` is the lower-precision control: every matrix product takes
both operands rounded to float8 (e4m3, one scale per row of each operand)
and accumulates in float32.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp


class Weights(NamedTuple):
    embed: jax.Array
    attn_norm: jax.Array
    wq: jax.Array
    wk: jax.Array
    wv: jax.Array
    wo: jax.Array
    mlp_norm: jax.Array
    w_gate: jax.Array
    w_up: jax.Array
    w_down: jax.Array
    final_norm: jax.Array


class Sizes(NamedTuple):
    d: int
    ff: int
    heads: int
    kv_heads: int
    layers: int
    vocab: int
    eps: float
    theta: float

    @classmethod
    def of(cls, c: dict) -> "Sizes":
        """From a configuration file's Hugging Face keys."""
        return cls(c["hidden_size"], c["intermediate_size"],
                   c["num_attention_heads"], c["num_key_value_heads"],
                   c["num_hidden_layers"], c["vocab_size"],
                   float(c["rms_norm_eps"]), float(c["rope_theta"]))

    @property
    def hd(self) -> int:
        return self.d // self.heads


def _q8(x, axis):
    """Round to float8 e4m3 with one scale per slice along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(a, b, fp8: bool):
    """``a @ b`` over the last axis of ``a`` and the first of ``b``."""
    if fp8:
        a, b = _q8(a, -1), _q8(b, 0)
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    """x: (S, H, hd); pos: (S,)."""
    hd = x.shape[-1]
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * inv            # (S, hd/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(s: Sizes, fp8: bool, h, lw):
    (an, wq, wk, wv, wo, mn, wg, wu, wd) = lw
    n, hd = h.shape[0], s.hd
    pos = jnp.arange(n)
    x = _rms(h, an, s.eps)
    q = _rope(_mm(x, wq, fp8).reshape(n, s.heads, hd), pos, s.theta)
    k = _rope(_mm(x, wk, fp8).reshape(n, s.kv_heads, hd), pos, s.theta)
    v = _mm(x, wv, fp8).reshape(n, s.kv_heads, hd)
    rep = s.heads // s.kv_heads
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    qh, kh, vh = (t.transpose(1, 0, 2) for t in (q, k, v))  # (H, S, hd)
    sc = jax.vmap(lambda a, b: _mm(a, b.T, fp8))(qh, kh) * hd ** -0.5
    sc = jnp.where(pos[None, :, None] >= pos[None, None, :], sc, -jnp.inf)
    p = jax.nn.softmax(sc, axis=-1)
    o = jax.vmap(lambda a, b: _mm(a, b, fp8))(p, vh)        # (H, S, hd)
    h = h + _mm(o.transpose(1, 0, 2).reshape(n, s.heads * hd), wo, fp8)
    x = _rms(h, mn, s.eps)
    g = _mm(x, wg, fp8)
    h = h + _mm(jax.nn.silu(g) * _mm(x, wu, fp8), wd, fp8)
    return h, None


@functools.partial(jax.jit, static_argnames=("s", "first", "fp8"))
def logits(w: Weights, s: Sizes, tokens, first: int, fp8: bool = False):
    """float32 logits ``(len(tokens) - first, V)`` at positions ``first``
    onward of one sequence ``tokens`` (1-D int32)."""
    h = w.embed[tokens].astype(jnp.float32)
    layers = (w.attn_norm, w.wq, w.wk, w.wv, w.wo, w.mlp_norm, w.w_gate,
              w.w_up, w.w_down)
    h, _ = jax.lax.scan(functools.partial(_layer, s, fp8), h, layers)
    x = _rms(h[first:], w.final_norm, s.eps)
    return _mm(x, w.embed.T, fp8)


def served_gaps(w: Weights, s: Sizes, prompt, served, fp8: bool = False):
    """For each served token, how far its reference logit lies below the
    reference's best at that position; with ``fp8`` also, for the token
    the float8 control ranks first, the same gap. Returns
    ``(served_gaps, control_gaps)`` (the second ``None`` without fp8)."""
    toks = jnp.asarray(list(prompt) + list(served[:-1]), jnp.int32)
    first = len(prompt) - 1
    with jax.default_matmul_precision("highest"):
        ref = logits(w, s, toks, first)
        best = jnp.max(ref, axis=-1)
        idx = jnp.asarray(served, jnp.int32)[:, None]
        gaps = best - jnp.take_along_axis(ref, idx, axis=-1)[:, 0]
        ctrl = None
        if fp8:
            top = jnp.argmax(logits(w, s, toks, first, fp8=True), axis=-1)
            ctrl = best - jnp.take_along_axis(ref, top[:, None], -1)[:, 0]
    return gaps, ctrl
