"""RG-LRU diagonal linear recurrence, blocked Pallas TPU kernel.

h_t = a_t * h_{t-1} + b_t, elementwise over the channel dim. The TPU
adaptation: channels tile across the grid (VPU lanes, 128-aligned blocks);
the sequence dim is walked as a sequential grid axis of chunks, with the
carried state h in VMEM scratch — VMEM holds one (chunk, block_d) tile of
each operand, never the whole sequence, and HBM traffic is exactly one read
of (a, b) and one write of h (the associative-scan jnp path re-materializes
log-depth intermediates instead).

Grid: (B, D/block_d, S/chunk) as ("parallel", "parallel", "arbitrary"):
the chunk axis is innermost and sequential, so the carry in scratch flows
from one chunk to the next. Rows are read and written through the refs
(``ref[0, pl.ds(t, 1), :]``): Mosaic cannot index a loaded value with a
traced ``t``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rglru_kernel(a_ref, b_ref, h0_ref, h_ref, hf_ref, carry, *, chunk: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        carry[...] = h0_ref[0].astype(jnp.float32)         # (1, bd)

    def step(t, h):
        a = a_ref[0, pl.ds(t, 1), :].astype(jnp.float32)
        b = b_ref[0, pl.ds(t, 1), :].astype(jnp.float32)
        h = a * h + b
        h_ref[0, pl.ds(t, 1), :] = h.astype(h_ref.dtype)
        return h

    carry[...] = jax.lax.fori_loop(0, chunk, step, carry[...])

    @pl.when(k == pl.num_programs(2) - 1)
    def _final():
        hf_ref[0] = carry[...].astype(hf_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_d", "chunk", "interpret"))
def rglru_scan(a, b, h0, *, block_d: int = 128, chunk: int = 128,
               interpret: bool = False):
    """a, b: (B, S, D) f32; h0: (B, D) f32 -> (h (B,S,D), h_final (B,D))."""
    bsz, seq, d = a.shape
    bd = min(block_d, d)
    pad_d = (-d) % bd
    if pad_d:
        pw = ((0, 0), (0, 0), (0, pad_d))
        a = jnp.pad(a, pw)
        b = jnp.pad(b, pw)
        h0 = jnp.pad(h0, ((0, 0), (0, pad_d)))
    ck = min(chunk, seq)
    pad_s = (-seq) % ck
    if pad_s:
        # padded steps: a=1 (keep state), b=0 (no input)
        a = jnp.pad(a, ((0, 0), (0, pad_s), (0, 0)), constant_values=1.0)
        b = jnp.pad(b, ((0, 0), (0, pad_s), (0, 0)))
    sp = seq + pad_s
    dp = d + pad_d

    # h0/h_final travel as (B, 1, D) so each block's last two dims are
    # (1, bd): 1 equals the array's own dim, as the TPU tiling requires
    seq_block = pl.BlockSpec((1, ck, bd), lambda i, j, k: (i, k, j))
    state_block = pl.BlockSpec((1, 1, bd), lambda i, j, k: (i, 0, j))
    h, hf = pl.pallas_call(
        functools.partial(_rglru_kernel, chunk=ck),
        grid=(bsz, dp // bd, sp // ck),
        in_specs=[seq_block, seq_block, state_block],
        out_specs=[seq_block, state_block],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, sp, dp), a.dtype),
            jax.ShapeDtypeStruct((bsz, 1, dp), a.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((1, bd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a, b, h0[:, None, :])
    return h[:, :seq, :d], hf[:, 0, :d]
