"""Jitted public wrappers for the Pallas kernels.

Model code calls these (the ``use_pallas=True`` paths); each compiles its
Pallas kernel for the TPU — there is no interpreter fallback, so a caller
on another backend fails instead of silently running the interpreter —
and handles the model-side layout ((B, S, H, hd) <-> the kernels'
(BH, S, hd) folding). Tests run the kernels in interpret mode by calling
them directly with ``interpret=True``.
"""
from __future__ import annotations

from repro.kernels import flash_attention as _fa
from repro.kernels import pe_simd as _pe
from repro.kernels import rglru_scan as _rg


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    scale: float = 0.0):
    """q: (B, S, H, hd); k: (B, Skv, Hkv, hd); v: (B, Skv, Hkv, hd_v)
    -> (B, S, H, hd_v)."""
    bsz, sq, h, hd = q.shape
    _, skv, hkv, _ = k.shape
    hd_v = v.shape[-1]
    g = h // hkv
    # fold (B, Hkv, G) so consecutive q heads share a kv head block
    qf = (q.transpose(0, 2, 1, 3)
           .reshape(bsz, hkv, g, sq, hd)
           .reshape(bsz * hkv * g, sq, hd))
    kf = k.transpose(0, 2, 1, 3).reshape(bsz * hkv, skv, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(bsz * hkv, skv, hd_v)
    o = _fa.flash_attention(qf, kf, vf, causal=causal, window=window,
                            scale=scale)
    return (o.reshape(bsz, hkv * g, sq, hd_v).transpose(0, 2, 1, 3))


def rglru_scan(a, b, h0):
    """(B, S, D) recurrence; see rglru_scan.py."""
    return _rg.rglru_scan(a, b, h0)


def pe_execute(op, imm, a, b):
    return _pe.pe_execute(op, imm, a, b)
