"""Flash attention Pallas TPU kernel (blocked online softmax).

TPU adaptation of the memory-division insight: the (Sq x Skv) score matrix
is the "monolithic memory" — it never touches HBM. The grid tiles
(batch*head, q block, kv block); q/k/v tiles stream HBM->VMEM via
BlockSpecs, scores/softmax state live in VMEM scratch, and the MXU sees
(block_q x hd) @ (hd x block_k) matmuls with 128-aligned tiles.

Supports causal, sliding-window and bidirectional masking, GQA (the
kv BlockSpec index map folds the query-head group onto its kv head), and
values narrower than queries and keys (MLA: 128 beside 192).

Grid semantics: ("parallel", "parallel", "arbitrary") — the kv dimension is
innermost and sequential, so the scratch accumulators carry across kv steps
(standard TPU flash pattern).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, causal: bool, window: int, block_q: int,
                  block_k: int, nk: int, sq: int, skv: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    qpos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                   (block_q, block_k), 0)
    kpos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                   (block_q, block_k), 1)
    # skip fully-masked blocks (the causal-waste fix vs the jnp path)
    need = kpos[0, 0] < skv
    if causal:
        need &= (ki * block_k) <= (qi * block_q + block_q - 1)
    if window > 0:
        need &= (ki * block_k + block_k) > (qi * block_q - window)

    @pl.when(need)
    def _compute():
        q = q_ref[0].astype(jnp.float32)                   # (bq, hd)
        k = k_ref[0].astype(jnp.float32)                   # (bk, hd)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        mask = (kpos < skv) & (qpos < sq)
        if causal:
            mask &= kpos <= qpos
        if window > 0:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]
        l_prev = l_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * corr + pv
        m_scr[...] = m_new
        l_scr[...] = l_new

    @pl.when(ki == nk - 1)
    def _epilogue():
        o_ref[0] = (acc_scr[...]
                    / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "scale", "block_q", "block_k", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    scale: float = 0.0, block_q: int = 128,
                    block_k: int = 512, interpret: bool = False):
    """q: (BH, Sq, hd); k: (BHkv, Skv, hd); v: (BHkv, Skv, hd_v),
    BH = BHkv * G. Returns (BH, Sq, hd_v) in q's dtype. Sq/Skv are padded
    to block multiples internally; hd and hd_v should be 128-aligned for
    MXU efficiency (any width works functionally)."""
    bh, sq, hd = q.shape
    bhkv, skv, _ = k.shape
    hd_v = v.shape[-1]
    g = bh // bhkv
    scale = scale or (1.0 / math.sqrt(hd))
    bq = min(block_q, max(sq, 8))
    bk = min(block_k, max(skv, 8))
    pq = (-sq) % bq
    pk = (-skv) % bk
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0)))
    nq = (sq + pq) // bq
    nk = (skv + pk) // bk

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        block_q=bq, block_k=bk, nk=nk, sq=sq, skv=skv)
    out = pl.pallas_call(
        kernel,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j, g_=g: (b // g_, j, 0)),
            pl.BlockSpec((1, bk, hd_v), lambda b, i, j, g_=g: (b // g_, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd_v), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq + pq, hd_v), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, hd_v), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
    return out[:, :sq, :]
