"""1-bit gradient compression with error feedback - Pallas TPU kernels.

The paper (§3.7) credits CNTK's Data-Parallel SGD with the 1-bit trick:
quantize gradients to sign bits + a per-row L1 scale, add the quantization
error to the next step's gradient (error feedback).  These kernels do the
pack/unpack on-chip so the wire payload is bits, not floats - a
distributed-optimization feature of the framework (strategy
``compression="onebit"``).

quantize:  g, err [R, C] f32 -> packed u32 [R, C/32], scale [R, 1], err'
dequantize: packed, scale -> +-scale  [R, C]

Grid over row blocks; C is the lane dim (multiple of 128); the pack is a
shift-and-add over a [bm, C/32, 32] view.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _quant_kernel(g_ref, e_ref, packed_ref, scale_ref, err_ref):
    g = g_ref[...].astype(jnp.float32)
    e = e_ref[...].astype(jnp.float32)
    q = g + e
    scale = jnp.mean(jnp.abs(q), axis=1, keepdims=True)      # [bm, 1]
    signs = (q >= 0)
    deq = jnp.where(signs, scale, -scale)
    err_ref[...] = (q - deq).astype(err_ref.dtype)
    scale_ref[...] = jnp.broadcast_to(scale, scale_ref.shape).astype(
        scale_ref.dtype)
    packed_ref[...] = jax.lax.bitcast_convert_type(_pack_words(signs),
                                                   jnp.uint32)


def _pack_words(signs):
    """bool [bm, C] -> int32 words [bm, C/32], bit j of word w = column
    32w + j.  Mosaic cannot split the lane dim into [C/32, 32], so the
    pack is two matmuls against place-value matrices, one per 16-bit
    half: every product and partial sum is an integer below 2^16, exact
    in bf16 inputs with f32 accumulation."""
    C = signs.shape[1]
    row = jax.lax.broadcasted_iota(jnp.int32, (C, C // 32), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (C, C // 32), 1)
    bit = row & 31
    own = (row >> 5) == col
    s = signs.astype(jnp.bfloat16)

    def half(lo_bit):
        here = own & (bit >= lo_bit) & (bit < lo_bit + 16)
        w = jnp.where(here, 1 << jnp.maximum(bit - lo_bit, 0), 0)
        return jnp.dot(s, w.astype(jnp.float32).astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32
                       ).astype(jnp.int32)
    return half(0) | (half(16) << 16)


def _dequant_kernel(packed_ref, scale_ref, out_ref):
    packed = jax.lax.bitcast_convert_type(packed_ref[...],
                                          jnp.int32)           # [bm, C/32]
    scale = scale_ref[:, :1].astype(jnp.float32)              # [bm, 1]
    shifts = jnp.arange(32, dtype=jnp.int32)
    bits = jax.lax.shift_right_logical(
        packed[:, :, None], shifts[None, None, :]) & 1
    bm = packed.shape[0]
    signs = bits.reshape(bm, -1).astype(jnp.float32)
    out_ref[...] = ((2.0 * signs - 1.0) * scale).astype(out_ref.dtype)


def onebit_quantize(g, err, *, block_rows: int = 256,
                    interpret: bool = False):
    """g, err: [R, C] (C % 128 == 0). -> (packed u32 [R,C/32],
    scale [R,128] (lane-replicated), new_err [R,C])."""
    R, C = g.shape
    assert C % 128 == 0, C
    block_rows = min(block_rows, R)
    assert R % block_rows == 0
    grid = (R // block_rows,)
    return pl.pallas_call(
        _quant_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, C), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, C), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_rows, C // 32), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, 128), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, C), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((R, C // 32), jnp.uint32),
            jax.ShapeDtypeStruct((R, 128), jnp.float32),
            jax.ShapeDtypeStruct((R, C), g.dtype),
        ],
        interpret=interpret,
    )(g, err)


def onebit_dequantize(packed, scale, *, block_rows: int = 256,
                      interpret: bool = False):
    """packed: [R, C/32] u32; scale: [R, 128] -> [R, C] f32."""
    R, Cp = packed.shape
    C = Cp * 32
    block_rows = min(block_rows, R)
    assert R % block_rows == 0
    grid = (R // block_rows,)
    return pl.pallas_call(
        _dequant_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, Cp), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, 128), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, C), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R, C), jnp.float32),
        interpret=interpret,
    )(packed, scale)
