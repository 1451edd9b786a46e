"""Parameter schema: the single source of truth for every architecture.

``schema(cfg)`` returns a nested dict whose leaves are :class:`ParamSpec`
(shape, logical axes, init scale). From it we derive — with zero drift —
  * ``abstract_params``  : ShapeDtypeStruct tree (dry-run, no allocation)
  * ``init_params``      : materialized random tree (smoke tests / training)
  * ``param_axes``       : logical-axis tree consumed by the sharding rules
  * ``count_params``     : analytic parameter count for roofline MODEL_FLOPS
  * ``served_params``    : the tree a serving engine holds, each leaf the
    forward reads only in compute dtype cast to it once (and its abstract
    twin ``served_abstract_params``)

Layer stacks are stored *stacked*: each repeated group has params with a
leading ``repeats`` dim and is executed with ``lax.scan``. Attention
projections are stored 2-D ``(d, H*hd)`` so the flattened output dim shards
evenly on the model axis regardless of head count (heads like 40, 20, 15, 10
do not divide a 16-way axis; 5120, 2560, … do).
"""
from __future__ import annotations

import math
from dataclasses import dataclass, replace
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.config import ATTENTION_KINDS, ModelConfig


@dataclass(frozen=True)
class ParamSpec:
    shape: Tuple[int, ...]
    axes: Tuple[object, ...]          # logical axis name (str) or None per dim
    init: str = "normal"              # normal | zeros | ones | lambda_lru
    scale: float = 1.0
    # the forward reads this leaf only cast to ``cfg.compute_dtype``, so a
    # copy cast once gives the same operands (False: read in float32)
    compute_read: bool = False


def _dense(d_in: int, d_out: int, ax_in: str, ax_out: str, *, bias: bool = False,
           init: str = "normal", scale: float | None = None,
           compute_read: bool = True) -> Dict[str, ParamSpec]:
    scale = (1.0 / math.sqrt(d_in)) if scale is None else scale
    out = {"w": ParamSpec((d_in, d_out), (ax_in, ax_out), init, scale,
                          compute_read)}
    if bias:
        out["b"] = ParamSpec((d_out,), (ax_out,), "zeros",
                             compute_read=compute_read)
    return out


def _conv(cfg: ModelConfig, width: int) -> Dict[str, ParamSpec]:
    """Depthwise causal conv, read in its input's (compute) dtype."""
    return {"w": ParamSpec((cfg.conv_width, width), (None, "ffn"), "normal",
                           0.1, compute_read=True),
            "b": ParamSpec((width,), ("ffn",), "zeros", compute_read=True)}


def _norm(d: int, kind: str) -> Dict[str, ParamSpec]:
    out = {"scale": ParamSpec((d,), ("embed",), "ones")}
    if kind == "layernorm":
        out["bias"] = ParamSpec((d,), ("embed",), "zeros")
    return out


# ---------------------------------------------------------------------------
# per-block-kind schemas
# ---------------------------------------------------------------------------

def _attn_schema(cfg: ModelConfig) -> Dict[str, ParamSpec | dict]:
    d, hd = cfg.d_model, cfg.hd
    q_dim, kv_dim = cfg.n_heads * hd, cfg.n_kv_heads * hd
    s: Dict[str, ParamSpec | dict] = {"norm": _norm(d, cfg.norm)}
    s["wq"] = _dense(d, q_dim, "embed", "qkv", bias=cfg.attn_bias)
    s["wk"] = _dense(d, kv_dim, "embed", "kv", bias=cfg.attn_bias)
    s["wv"] = _dense(d, kv_dim, "embed", "kv", bias=cfg.attn_bias)
    s["wo"] = _dense(q_dim, d, "qkv", "embed", bias=(cfg.norm == "layernorm"))
    return s


def _mlp_schema(cfg: ModelConfig) -> Dict[str, ParamSpec | dict]:
    d, ff = cfg.d_model, cfg.d_ff
    s: Dict[str, ParamSpec | dict] = {"norm": _norm(d, cfg.norm)}
    if cfg.mlp == "swiglu":
        s["wi"] = _dense(d, 2 * ff, "embed", "ffn")           # fused gate|up
        s["wo"] = _dense(ff, d, "ffn", "embed")
    else:                                                     # gelu (HuBERT)
        s["wi"] = _dense(d, ff, "embed", "ffn", bias=True)
        s["wo"] = _dense(ff, d, "ffn", "embed", bias=True)
    return s


def _moe_schema(cfg: ModelConfig) -> Dict[str, ParamSpec | dict]:
    """The router scores all ``n_experts``; the expert weights are those of
    the ``n_experts_held`` experts this chip holds; shared experts (one
    SwiGLU of width ``n_shared_experts * moe_ff``) see every token."""
    d, ff, e = cfg.d_model, cfg.moe_ff, cfg.n_experts
    held = cfg.n_experts_held
    s: Dict[str, ParamSpec | dict] = {
        "norm": _norm(d, cfg.norm),
        "router": {"w": ParamSpec((d, e), ("embed", None), "normal", 1.0 / math.sqrt(d))},
        "wi": ParamSpec((held, d, 2 * ff), ("experts", "embed", "ffn"),
                        "normal", 1.0 / math.sqrt(d), compute_read=True),
        "wo": ParamSpec((held, ff, d), ("experts", "ffn", "embed"),
                        "normal", 1.0 / math.sqrt(ff), compute_read=True),
    }
    if cfg.n_shared_experts:
        sff = cfg.n_shared_experts * ff
        s["shared"] = {"wi": _dense(d, 2 * sff, "embed", "ffn"),
                       "wo": _dense(sff, d, "ffn", "embed")}
    return s


def _mla_schema(cfg: ModelConfig) -> Dict[str, ParamSpec | dict]:
    """Multi-head latent attention (DeepSeek-V2, no query compression):
    per head, ``wq`` gives [nope | rope] query columns; ``wkv_a`` gives the
    joint latent ``c_kv`` (``kv_lora_rank``, normed by ``kv_norm``) and one
    rope key shared by all heads; ``wkv_b`` expands the latent to per-head
    [k_nope | v]."""
    d, h, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    nope, rope, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    return {
        "norm": _norm(d, cfg.norm),
        "wq": _dense(d, h * (nope + rope), "embed", "qkv"),
        "wkv_a": _dense(d, r + rope, "embed", None),
        "kv_norm": {"scale": ParamSpec((r,), (None,), "ones")},
        "wkv_b": _dense(r, h * (nope + vd), None, "qkv"),
        "wo": _dense(h * vd, d, "qkv", "embed"),
    }


def _rglru_schema(cfg: ModelConfig) -> Dict[str, ParamSpec | dict]:
    """Griffin recurrent block: x -> [conv4 -> RG-LRU] * gelu(gate) -> out."""
    d, dr = cfg.d_model, cfg.lru_d
    return {
        "norm": _norm(d, cfg.norm),
        "wx": _dense(d, dr, "embed", "ffn"),                   # recurrent branch in
        "wg": _dense(d, dr, "embed", "ffn"),                   # gate branch
        "conv": _conv(cfg, dr),
        "lru": {                                               # read in f32
            "lam": ParamSpec((dr,), ("ffn",), "lambda_lru"),   # Λ, a = σ(Λ)^(c·r)
            "wa": _dense(dr, dr, "ffn", None, scale=1.0 / math.sqrt(dr),
                         compute_read=False),
            "ba": ParamSpec((dr,), (None,), "zeros"),
            "wi": _dense(dr, dr, "ffn", None, scale=1.0 / math.sqrt(dr),
                         compute_read=False),
            "bi": ParamSpec((dr,), (None,), "zeros"),
        },
        "wo": _dense(dr, d, "ffn", "embed"),
    }


def _mlstm_schema(cfg: ModelConfig) -> Dict[str, ParamSpec | dict]:
    """xLSTM mLSTM block (up-proj x2, conv, per-head matrix memory)."""
    d = cfg.d_model
    de = 2 * d                        # expansion 2 (xLSTM paper)
    h = cfg.n_heads
    return {
        "norm": _norm(d, cfg.norm),
        "wup": _dense(d, 2 * de, "embed", "ffn"),              # fused x|gate
        "conv": _conv(cfg, de),
        "wq": _dense(de, de, "ffn", None),
        "wk": _dense(de, de, "ffn", None),
        "wv": _dense(de, de, "ffn", None),
        "wif": _dense(de, 2 * h, "ffn", None),                 # i/f gate pre-acts
        "onorm": {"scale": ParamSpec((de,), ("ffn",), "ones")},
        "wdown": _dense(de, d, "ffn", "embed"),
    }


def _slstm_schema(cfg: ModelConfig) -> Dict[str, ParamSpec | dict]:
    """xLSTM sLSTM block: 4 gates, per-head block-diagonal recurrence."""
    d, h = cfg.d_model, cfg.n_heads
    hd = d // h
    return {
        "norm": _norm(d, cfg.norm),
        "wg": _dense(d, 4 * d, "embed", "ffn"),                # i|f|z|o from x_t
        "rg": ParamSpec((h, hd, 4 * hd), (None, None, None), "normal",
                        1.0 / math.sqrt(hd)),                  # recurrent, per head
        "bg": ParamSpec((4 * d,), ("ffn",), "zeros", compute_read=True),
        "wo": _dense(d, d, "embed", "qkv"),
    }


_KIND_SCHEMA = {
    "attn": _attn_schema, "swa": _attn_schema, "local": _attn_schema,
    "mla": _mla_schema, "rglru": _rglru_schema, "mlstm": _mlstm_schema, "slstm": _slstm_schema,
}


def _block_schema(cfg: ModelConfig, kind: str,
                  moe: bool) -> Dict[str, ParamSpec | dict]:
    s = {"mixer": _KIND_SCHEMA[kind](cfg)}
    if cfg.d_ff > 0 and kind in ATTENTION_KINDS:
        s["mlp"] = _moe_schema(cfg) if moe else _mlp_schema(cfg)
    return s


# ---------------------------------------------------------------------------
# whole-model schema
# ---------------------------------------------------------------------------

def layer_groups(cfg: ModelConfig):
    """[(unit_kinds, repeats), ...] covering all n_layers in order. The
    ``first_k_dense`` leading layers (whole units) form a group of their
    own: the one group whose MLP is dense in an MoE model."""
    unit = cfg.pattern_unit
    groups = []
    dense = cfg.first_k_dense
    if dense:
        if dense % len(unit):
            raise ValueError("first_k_dense must cover whole pattern units")
        groups.append((unit, dense // len(unit)))
    reps, rem = divmod(cfg.n_layers - dense, len(unit))
    if reps:
        groups.append((unit, reps))
    if rem:
        groups.append((unit[:rem], 1))
    return groups


def moe_group(cfg: ModelConfig, gi: int) -> bool:
    """Whether group ``gi`` of ``layer_groups`` has MoE MLPs."""
    return bool(cfg.n_experts) and not (cfg.first_k_dense and gi == 0)


def _stack(tree, n: int):
    """Prepend a stacked layer dim (axis name None) to every ParamSpec."""
    if isinstance(tree, ParamSpec):
        return replace(tree, shape=(n, *tree.shape), axes=(None, *tree.axes))
    return {k: _stack(v, n) for k, v in tree.items()}


def schema(cfg: ModelConfig) -> Dict:
    d = cfg.d_model
    s: Dict = {}
    if cfg.frontend:
        s["frontend_proj"] = _dense(cfg.d_frontend, d, None, "embed")
    if cfg.frontend != "audio_frames":          # HuBERT: no token embedding
        s["embed"] = {"w": ParamSpec((cfg.vocab_size, d), ("vocab", "embed"),
                                     "normal", 0.02, compute_read=True)}
    groups = []
    for gi, (unit, reps) in enumerate(layer_groups(cfg)):
        g = {str(i): _block_schema(cfg, kind, moe_group(cfg, gi))
             for i, kind in enumerate(unit)}
        groups.append(_stack(g, reps) if cfg.scan_layers else _unroll(g, reps))
    s["groups"] = {str(i): g for i, g in enumerate(groups)}
    s["final_norm"] = _norm(d, cfg.norm)
    if not cfg.tie_embeddings:
        s["lm_head"] = _dense(d, cfg.vocab_size, "embed", "vocab")
    return s


def _unroll(g, reps):
    return {f"L{r}": g for r in range(reps)} if reps > 1 else g


# ---------------------------------------------------------------------------
# derivations
# ---------------------------------------------------------------------------

def _is_spec(x):
    return isinstance(x, ParamSpec)


def tree_map_schema(fn, sch):
    if _is_spec(sch):
        return fn(sch)
    return {k: tree_map_schema(fn, v) for k, v in sch.items()}


def abstract_params(cfg: ModelConfig):
    dt = jnp.dtype(cfg.param_dtype)
    return tree_map_schema(lambda s: jax.ShapeDtypeStruct(s.shape, dt), schema(cfg))


def _served_dtype(cfg: ModelConfig, spec: ParamSpec, dtype):
    """The dtype a serving engine holds a leaf of ``dtype`` in: the compute
    dtype where the forward reads the leaf only in it and it is narrower
    (widening would only add memory), else ``dtype``."""
    cd = jnp.dtype(cfg.compute_dtype)
    if spec.compute_read and cd.itemsize < jnp.dtype(dtype).itemsize:
        return cd
    return jnp.dtype(dtype)


def served_abstract_params(cfg: ModelConfig):
    """``abstract_params`` as a serving engine holds them."""
    dt = jnp.dtype(cfg.param_dtype)
    return tree_map_schema(
        lambda s: jax.ShapeDtypeStruct(s.shape, _served_dtype(cfg, s, dt)),
        schema(cfg))


def served_casts(cfg: ModelConfig, params):
    """The leaves of ``params`` that ``served_params`` casts, in tree order."""
    return [a for s, a in zip(jax.tree.leaves(schema(cfg), is_leaf=_is_spec),
                              jax.tree.leaves(params))
            if _served_dtype(cfg, s, a.dtype) != a.dtype]


def served_params(cfg: ModelConfig, params):
    """``params`` with every leaf the forward reads only in a narrower
    compute dtype cast to it, once. The forward then computes on the same
    operands (the cast it makes at use becomes a no-op); every other leaf
    is returned as the same array, so a tree stored in compute dtype is
    served as it is, with no copy."""
    def serve(s, a):
        dt = _served_dtype(cfg, s, a.dtype)
        return a if dt == a.dtype else a.astype(dt)
    return jax.tree.map(serve, schema(cfg), params, is_leaf=_is_spec)


def param_axes(cfg: ModelConfig):
    return tree_map_schema(lambda s: s.axes, schema(cfg))


def count_params(cfg: ModelConfig) -> int:
    total = [0]
    tree_map_schema(lambda s: total.__setitem__(0, total[0] + int(np.prod(s.shape))),
                    schema(cfg))
    return total[0]


def init_params(cfg: ModelConfig, rng: jax.Array):
    """Materialize parameters (smoke tests / real training only)."""
    dt = jnp.dtype(cfg.param_dtype)
    sch = schema(cfg)
    leaves: list[ParamSpec] = []
    tree_map_schema(lambda s: leaves.append(s), sch)
    keys = iter(jax.random.split(rng, max(len(leaves), 1)))

    def mk(s: ParamSpec):
        k = next(keys)
        if s.init == "zeros":
            return jnp.zeros(s.shape, dt)
        if s.init == "ones":
            return jnp.ones(s.shape, dt)
        if s.init == "lambda_lru":
            # a = sigmoid(lam) uniformly in [0.9, 0.999] (Griffin init)
            u = jax.random.uniform(k, s.shape, dt, 0.9, 0.999)
            return jnp.log(u / (1 - u))
        return (jax.random.normal(k, s.shape, dt) * s.scale).astype(dt)

    return tree_map_schema(mk, sch)
