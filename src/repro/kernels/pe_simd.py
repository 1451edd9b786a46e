"""G-GPU PE execute stage as a Pallas TPU kernel.

The CU's 8 Processing Elements executing one instruction per wavefront is a
classic SIMD select-tree: every lane computes all candidate ALU results and
the per-wavefront opcode selects one. On TPU this maps onto the VPU: lanes
tile the (wavefront, lane) plane in VMEM blocks; the opcode/immediate
stream sits in SMEM-like narrow blocks. This is the hot inner loop of the
cycle simulator (`repro.ggpu.engine.alu.select_alu` is the shared datapath:
the same case table traces here inside the Pallas kernel and inside the
engine's `lax.while_loop` stepper, so the two can never drift).

Integer division (the paper's weak spot) is implemented as a bounded
Newton/long-division loop to stay VPU-friendly — mirroring FGPU's
soft-divide microkernel, see ISA cost table.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.ggpu.engine.alu import select_alu


def _pe_kernel(op_ref, imm_ref, a_ref, b_ref, out_ref):
    op = op_ref[...]                                       # (bw, 1) int32
    imm = imm_ref[...]
    a = a_ref[...]                                         # (bw, L) int32
    b = b_ref[...]
    out_ref[...] = select_alu(op, a, b, imm)


@functools.partial(jax.jit, static_argnames=("block_w", "interpret"))
def pe_execute(op, imm, a, b, *, block_w: int = 8, interpret: bool = False):
    """op, imm: (W, 1) int32; a, b: (W, L) int32 -> (W, L) results.
    Grid tiles wavefronts; a block of 8 wavefronts x 64 lanes = one CU's
    PE array across 8 issue beats."""
    w, l = a.shape
    bw = min(block_w, w)
    pad = (-w) % bw
    if pad:
        op = jnp.pad(op, ((0, pad), (0, 0)))
        imm = jnp.pad(imm, ((0, pad), (0, 0)))
        a = jnp.pad(a, ((0, pad), (0, 0)))
        b = jnp.pad(b, ((0, pad), (0, 0)))
    wp = w + pad
    out = pl.pallas_call(
        _pe_kernel,
        grid=(wp // bw,),
        in_specs=[
            pl.BlockSpec((bw, 1), lambda i: (i, 0)),
            pl.BlockSpec((bw, 1), lambda i: (i, 0)),
            pl.BlockSpec((bw, l), lambda i: (i, 0)),
            pl.BlockSpec((bw, l), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bw, l), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((wp, l), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(op, imm, a, b)
    return out[:w]
