"""Plain reference of a DeepSeek-V2 decoder (multi-head latent attention and
DeepSeekMoE; DeepSeek-V2-Lite's block): float32 ``jax.numpy`` at full
matmul precision, no kernels, no cache, no batching, written from the
published description (arXiv:2405.04434 §2.1-2.2 and the model's
``config.json``). It imports nothing of the program.

    h = embed[tokens]
    per layer:  x = rms(h)
        q = x Wq = [q_nope | q_pe] per head;  [c | k_pe] = x Wkv_a;  c = rms_kv(c)
        [k_nope | v] = c Wkv_b per head;  q_pe, k_pe rotated (k_pe shared)
        h += Wo . softmax(causal, (q_nope.k_nope + q_pe.k_pe) * scale) . v
        x = rms(h)
        dense layers:  h += Wdown . (silu(Wgate x) * (Wup x))
        MoE layers:    s = softmax(x Wr) over all routed experts; top-k;
                       h += sum over held experts e of w_e FFN_e(x) + FFN_shared(x)
    logits = rms(h) . Whead                  (untied head)

``rms(x) = x / sqrt(mean(x^2) + eps) * w``. ``w_e`` is the top-k score of
expert ``e`` where the token chose it and 0 elsewhere, renormalized over the
top-k if ``norm_topk_prob``, times ``routed_scaling_factor``. Every held
expert runs densely over every token and is weighted so: the plain form of
"the chip's own experts' part for the tokens routed to them". The held
experts are ``[shard * held, (shard + 1) * held)`` of the router's
``n_routed_experts_published``; what absent experts add is left out.

Rotary embedding: YaRN with ``inv_freq = inter (1 - mask) + extra mask``,
``extra = theta ** (-2i / rope)``, ``inter = extra / factor``, ``mask = 1 -
clip((i - low) / (high - low), 0, 1)`` over the correction range
``[floor(d(beta_fast)), ceil(d(beta_slow))]`` clamped to ``[0, rope - 1]``,
``d(r) = rope ln(original / (2 pi r)) / (2 ln theta)``; cos and sin scaled by
``m(mscale) / m(mscale_all_dim)``, ``m(a) = 0.1 a ln(factor) + 1``; softmax
scale ``(nope + rope) ** -0.5 * m(mscale_all_dim) ** 2``. The rotation here
turns the two halves of the rope columns, ``[x1, x2] -> [x1 cos - x2 sin,
x2 cos + x1 sin]``; the published code turns interleaved pairs, which is
the same up to a fixed permutation of Wq's and Wkv_a's rope columns.

Weights (``from_program``) are those served, in any float dtype, upcast to
float32 one layer at a time inside the layer scan; layers of each kind are
stacked on a leading axis. Attention runs in query blocks and the logits in
vocabulary blocks, so that a sequence of a few thousand tokens fits beside
the served weights.

``fp8=True`` is the lower-precision control: every matrix product takes
both operands rounded to float8 (e4m3, one scale per row of each operand)
and accumulates in float32.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

Q_BLOCK = 512          # queries per attention block
V_BLOCK = 12_800       # vocabulary entries per logits block


class Sizes(NamedTuple):
    d: int
    heads: int
    rank: int
    nope: int
    rope: int
    vdim: int
    dense_ff: int
    moe_ff: int
    shared_ff: int
    experts: int           # the router's width
    held: int
    shard: int
    topk: int
    norm_topk: bool
    scaling: float
    layers: int
    dense_layers: int
    vocab: int
    eps: float
    theta: float
    yarn: tuple            # (factor, original, beta_fast, beta_slow,
    #                         mscale, mscale_all_dim), or () for none

    @classmethod
    def of(cls, c: dict) -> "Sizes":
        """From a configuration file's Hugging Face keys; the experts held
        are ``n_routed_experts`` of ``n_routed_experts_published``."""
        y = c.get("rope_scaling") or {}
        yarn = (float(y["factor"]), int(y["original_max_position_embeddings"]),
                float(y["beta_fast"]), float(y["beta_slow"]),
                float(y["mscale"]), float(y["mscale_all_dim"])) if y else ()
        held = c["n_routed_experts"]
        return cls(c["hidden_size"], c["num_attention_heads"],
                   c["kv_lora_rank"], c["qk_nope_head_dim"],
                   c["qk_rope_head_dim"], c["v_head_dim"],
                   c["intermediate_size"], c["moe_intermediate_size"],
                   c["n_shared_experts"] * c["moe_intermediate_size"],
                   c.get("n_routed_experts_published", held), held,
                   c.get("expert_shard", 0), c["num_experts_per_tok"],
                   bool(c["norm_topk_prob"]),
                   float(c["routed_scaling_factor"]),
                   c["num_hidden_layers"], c["first_k_dense_replace"],
                   c["vocab_size"], float(c["rms_norm_eps"]),
                   float(c["rope_theta"]), yarn)


def from_program(params) -> dict:
    """The reference's layout of the program's parameter tree: group "0"
    the dense layers, group "1" the MoE layers, fused gate|up split."""
    def attn(m):
        return {"norm": m["norm"]["scale"], "wq": m["wq"]["w"],
                "wkv_a": m["wkv_a"]["w"], "kv_norm": m["kv_norm"]["scale"],
                "wkv_b": m["wkv_b"]["w"], "wo": m["wo"]["w"]}

    def split(wi):
        ff = wi.shape[-1] // 2
        return wi[..., :ff], wi[..., ff:]

    dense = params["groups"]["0"]["0"]
    moe = params["groups"]["1"]["0"]
    dg, du = split(dense["mlp"]["wi"]["w"])
    eg, eu = split(moe["mlp"]["wi"])
    sg, su = split(moe["mlp"]["shared"]["wi"]["w"])
    return {
        "embed": params["embed"]["w"],
        "final_norm": params["final_norm"]["scale"],
        "head": params["lm_head"]["w"],
        "dense": {"attn": attn(dense["mixer"]),
                  "mlp": {"norm": dense["mlp"]["norm"]["scale"],
                          "gate": dg, "up": du,
                          "down": dense["mlp"]["wo"]["w"]}},
        "moe": {"attn": attn(moe["mixer"]),
                "mlp": {"norm": moe["mlp"]["norm"]["scale"],
                        "router": moe["mlp"]["router"]["w"],
                        "gate": eg, "up": eu, "down": moe["mlp"]["wo"],
                        "shared_gate": sg, "shared_up": su,
                        "shared_down": moe["mlp"]["shared"]["wo"]["w"]}},
    }


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


def _m(factor, a):
    return 0.1 * a * math.log(factor) + 1.0 if factor > 1 else 1.0


def inv_freq(s: Sizes):
    """The rotary inverse frequencies, YaRN where configured."""
    extra = s.theta ** (-jnp.arange(0, s.rope, 2, dtype=jnp.float32)
                        / s.rope)
    if not s.yarn:
        return extra
    factor, original, fast, slow = s.yarn[:4]

    def d(r):
        return s.rope * math.log(original / (2 * math.pi * r)) \
            / (2 * math.log(s.theta))
    low = max(math.floor(d(fast)), 0)
    high = min(math.ceil(d(slow)), s.rope - 1)
    span = high - low if high > low else 0.001
    mask = 1.0 - jnp.clip((jnp.arange(s.rope // 2) - low) / span, 0.0, 1.0)
    return extra / factor * (1.0 - mask) + extra * mask


def softmax_scale(s: Sizes) -> float:
    scale = (s.nope + s.rope) ** -0.5
    return scale * _m(s.yarn[0], s.yarn[5]) ** 2 if s.yarn else scale


def _rope(s: Sizes, x, pos):
    """x: (S, H, rope); pos: (S,)."""
    ang = pos[:, None].astype(jnp.float32) * inv_freq(s)    # (S, rope/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    if s.yarn:
        mag = _m(s.yarn[0], s.yarn[4]) / _m(s.yarn[0], s.yarn[5])
        cos, sin = cos * mag, sin * mag
    x1, x2 = x[..., :s.rope // 2], x[..., s.rope // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(s: Sizes, fp8: bool, h, aw):
    n = h.shape[0]
    pos = jnp.arange(n)
    x = _rms(h, aw["norm"], s.eps)
    q = _mm(x, aw["wq"], fp8).reshape(n, s.heads, s.nope + s.rope)
    kv_a = _mm(x, aw["wkv_a"], fp8)
    c = _rms(kv_a[:, :s.rank], aw["kv_norm"], s.eps)
    kv = _mm(c, aw["wkv_b"], fp8).reshape(n, s.heads, s.nope + s.vdim)
    k_pe = _rope(s, kv_a[:, None, s.rank:], pos)            # (S, 1, rope)
    q = jnp.concatenate([q[..., :s.nope], _rope(s, q[..., s.nope:], pos)],
                        -1)
    k = jnp.concatenate(
        [kv[..., :s.nope], jnp.broadcast_to(k_pe, (n, s.heads, s.rope))], -1)
    kh = k.transpose(1, 0, 2)                               # (H, S, qk)
    vh = kv[..., s.nope:].transpose(1, 0, 2)                # (H, S, v)
    nb = -(-n // Q_BLOCK)
    qp = jnp.pad(q, ((0, nb * Q_BLOCK - n), (0, 0), (0, 0)))
    scale = softmax_scale(s)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(qp, i * Q_BLOCK, Q_BLOCK, 0)
        sc = jax.vmap(lambda a, b: _mm(a, b.T, fp8))(
            qb.transpose(1, 0, 2), kh) * scale              # (H, Qb, S)
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        sc = jnp.where(qpos[None, :, None] >= pos[None, None, :], sc,
                       -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return jax.vmap(lambda a, b: _mm(a, b, fp8))(p, vh)  # (H, Qb, v)

    o = jax.lax.map(block, jnp.arange(nb))                  # (nb, H, Qb, v)
    o = o.transpose(0, 2, 1, 3).reshape(nb * Q_BLOCK, s.heads * s.vdim)[:n]
    return h + _mm(o, aw["wo"], fp8)


def _ffn(x, g, u, dn, fp8):
    return _mm(jax.nn.silu(_mm(x, g, fp8)) * _mm(x, u, fp8), dn, fp8)


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def _dense_layer(s: Sizes, fp8: bool, h, lw):
    lw = _f32(lw)
    h = _attention(s, fp8, h, lw["attn"])
    m = lw["mlp"]
    return h + _ffn(_rms(h, m["norm"], s.eps), m["gate"], m["up"],
                    m["down"], fp8), None


def moe_part(s: Sizes, fp8: bool, x, m):
    """The MoE layer's output for normed inputs ``x`` (S, d): the held
    experts' part plus the shared experts."""
    probs = jax.nn.softmax(_mm(x, m["router"], fp8), axis=-1)
    top, ids = jax.lax.top_k(probs, s.topk)
    if s.norm_topk:
        top = top / jnp.sum(top, -1, keepdims=True)
    top = top * s.scaling
    first = s.shard * s.held

    def expert(acc, e):
        w = jnp.sum(jnp.where(ids == first + e, top, 0.0), -1)  # (S,)
        y = _ffn(x, m["gate"][e].astype(jnp.float32),
                 m["up"][e].astype(jnp.float32),
                 m["down"][e].astype(jnp.float32), fp8)
        return acc + w[:, None] * y, None

    out, _ = jax.lax.scan(expert, jnp.zeros_like(x), jnp.arange(s.held))
    return out + _ffn(x, m["shared_gate"], m["shared_up"], m["shared_down"],
                      fp8)


def _moe_layer(s: Sizes, fp8: bool, h, lw):
    h = _attention(s, fp8, h, _f32(lw["attn"]))
    m = lw["mlp"]
    shared = _f32({k: m[k] for k in ("norm", "router", "shared_gate",
                                     "shared_up", "shared_down")})
    x = _rms(h, shared["norm"], s.eps)
    return h + moe_part(s, fp8, x, dict(m, **shared)), None


@functools.partial(jax.jit, static_argnames=("s", "first", "fp8"))
def final_hidden(w: dict, s: Sizes, tokens, first: int, fp8: bool = False):
    """Normed float32 hidden states ``(len(tokens) - first, d)`` at
    positions ``first`` onward of one sequence ``tokens`` (1-D int32)."""
    h = w["embed"][tokens].astype(jnp.float32)
    h, _ = jax.lax.scan(functools.partial(_dense_layer, s, fp8), h,
                        w["dense"])
    h, _ = jax.lax.scan(functools.partial(_moe_layer, s, fp8), h, w["moe"])
    return _rms(h[first:], w["final_norm"].astype(jnp.float32), s.eps)


def _head_blocks(head):
    v = head.shape[1]
    nb = -(-v // V_BLOCK)
    hp = jnp.pad(head, ((0, 0), (0, nb * V_BLOCK - v)))
    return hp.reshape(head.shape[0], nb, V_BLOCK).transpose(1, 0, 2), v


@functools.partial(jax.jit, static_argnames=("fp8",))
def best_logits(x, head, fp8: bool = False):
    """Per row of ``x``, the largest logit and its vocabulary id, over
    vocabulary blocks of the head."""
    blocks, v = _head_blocks(head)

    def step(carry, ib):
        best, arg = carry
        i, wb = ib
        lg = _mm(x, wb.astype(jnp.float32), fp8)
        ids = i * V_BLOCK + jnp.arange(V_BLOCK)
        lg = jnp.where(ids[None, :] < v, lg, -jnp.inf)
        bi = jnp.argmax(lg, -1)
        bv = jnp.take_along_axis(lg, bi[:, None], -1)[:, 0]
        better = bv > best
        return (jnp.where(better, bv, best),
                jnp.where(better, i * V_BLOCK + bi, arg)), None

    init = (jnp.full(x.shape[:1], -jnp.inf, jnp.float32),
            jnp.zeros(x.shape[:1], jnp.int32))
    (best, arg), _ = jax.lax.scan(step, init,
                                  (jnp.arange(blocks.shape[0]), blocks))
    return best, arg


@jax.jit
def logits_at(x, head, ids):
    """Per row of ``x``, its logit at vocabulary id ``ids[row]``, from the
    same vocabulary blocks as ``best_logits``."""
    blocks, _ = _head_blocks(head)

    def step(acc, ib):
        i, wb = ib
        lg = _mm(x, wb.astype(jnp.float32), False)
        local = ids - i * V_BLOCK
        inside = (local >= 0) & (local < V_BLOCK)
        got = jnp.take_along_axis(lg, jnp.clip(local, 0, V_BLOCK - 1)[:, None],
                                  -1)[:, 0]
        return acc + jnp.where(inside, got, 0.0), None

    out, _ = jax.lax.scan(step, jnp.zeros(x.shape[:1], jnp.float32),
                          (jnp.arange(blocks.shape[0]), blocks))
    return out


def logits(w: dict, s: Sizes, tokens, first: int = 0, fp8: bool = False):
    """Full float32 logits ``(len(tokens) - first, V)`` (small sizes)."""
    with jax.default_matmul_precision("highest"):
        x = final_hidden(w, s, jnp.asarray(tokens, jnp.int32), first, fp8)
        return _mm(x, w["head"].astype(jnp.float32), fp8)


def served_gaps(w: dict, s: Sizes, prompt, served, fp8: bool = False):
    """For each served token, how far its reference logit lies below the
    reference's best at that position; with ``fp8`` also, for the token
    the float8 control ranks first, the same gap. Returns
    ``(served_gaps, control_gaps)`` (the second ``None`` without fp8)."""
    toks = jnp.asarray(list(prompt) + list(served[:-1]), jnp.int32)
    first = len(prompt) - 1
    with jax.default_matmul_precision("highest"):
        x = final_hidden(w, s, toks, first)
        best, _ = best_logits(x, w["head"])
        gaps = best - logits_at(x, w["head"], jnp.asarray(served, jnp.int32))
        ctrl = None
        if fp8:
            xc = final_hidden(w, s, toks, first, fp8=True)
            _, top = best_logits(xc, w["head"], fp8=True)
            ctrl = best - logits_at(x, w["head"], top)
    return gaps, ctrl
