"""Gradient compression for the data-parallel wire (beyond-paper trick,
credited by the paper to CNTK's 1-bit SGD, §3.7).

1-bit exchange with error feedback over fused buckets:
  1. pack local gradient buckets to sign bits (uint32 bitmaps) + per-row
     L1 scales, folding the running quantization error in first;
  2. all-gather the bitmaps+scales across the dp axes (wire ~ 1/30 of f32);
  3. dequantize every rank's contribution and average locally.

The jnp pack/unpack here mirror kernels/onebit.py bit-for-bit (tested).
The in-mesh ``onebit`` strategy runs these jnp functions on every
backend; the Pallas kernels serve the fabric-DDP codec
(``distrib/collectives.py``) through ``kernels/ops.py``.
"""
from __future__ import annotations

import jax.numpy as jnp
from jax import lax

from ..core import fusion

ROW = 1024  # bucket rows are reshaped to [R, ROW] for per-row scales


def pack_bits(signs):
    """bool [R, C] -> uint32 [R, C/32] (little-endian bit order)."""
    R, C = signs.shape
    bits = signs.reshape(R, C // 32, 32).astype(jnp.uint32)
    weights = jnp.left_shift(jnp.uint32(1), jnp.arange(32, dtype=jnp.uint32))
    return jnp.sum(bits * weights[None, None, :], axis=-1, dtype=jnp.uint32)


def unpack_bits(packed):
    """uint32 [R, C/32] -> bool [R, C]."""
    shifts = jnp.arange(32, dtype=jnp.uint32)
    bits = jnp.bitwise_and(
        jnp.right_shift(packed[:, :, None], shifts[None, None, :]),
        jnp.uint32(1))
    return bits.reshape(packed.shape[0], -1).astype(bool)


def pack_signs(signs):
    """bool [n] (any n, ragged ok) -> uint32 [ceil(n/32)] bitmap.

    The 1-D face of :func:`pack_bits` for wire payloads whose length is
    not a multiple of 32: pads with zeros, packs little-endian.  Inverse
    is ``unpack_signs(packed, n)``.
    """
    n = signs.shape[0]
    pad = (-n) % 32
    flat = jnp.pad(signs.astype(bool), (0, pad))
    return pack_bits(flat.reshape(1, -1))[0]


def unpack_signs(packed, n: int):
    """uint32 [ceil(n/32)] -> bool [n]; inverse of :func:`pack_signs`."""
    return unpack_bits(packed.reshape(1, -1))[0, :n]


def quantize_bucket(buf, err):
    """1-D bucket (len % ROW*32 == 0) -> (packed, scales, new_err)."""
    q = buf.astype(jnp.float32).reshape(-1, ROW) + err
    scale = jnp.mean(jnp.abs(q), axis=1, keepdims=True)
    signs = q >= 0
    deq = jnp.where(signs, scale, -scale)
    new_err = (q - deq).reshape(err.shape)
    return pack_bits(signs), scale, new_err


def dequantize_bucket(packed, scale, n: int):
    signs = unpack_bits(packed)
    deq = jnp.where(signs, scale, -scale)
    return deq.reshape(-1)[:n]


def make_plan(grads_structs, dp_degree: int) -> fusion.FusionPlan:
    """Fusion plan whose buckets are divisible by both the dp axes and the
    [R, 1024] quantization view."""
    import math
    pad = math.lcm(max(dp_degree, 1), ROW * 32)
    return fusion.make_plan(grads_structs, cap_bytes=32 << 20, pad_to=pad)


def init_error_state(plan: fusion.FusionPlan):
    return [jnp.zeros((b.size // ROW, ROW), jnp.float32)
            for b in plan.buckets]


def exchange_onebit(grads, err_state, dp_axes, plan):
    """Inside shard_map: compressed all-gather + local average.

    Returns (mean gradients, new error state).  Wire per bucket:
    size/32 (bits) + size/1024 (scales) floats vs size floats uncompressed.
    """
    axes = tuple(dp_axes)
    ndp = 1
    for a in axes:
        ndp *= lax.axis_size(a)
    bufs = fusion.pack(grads, plan)
    out_bufs, new_err = [], []
    for buf, err in zip(bufs, err_state):
        packed, scale, err2 = quantize_bucket(buf, err)
        all_packed = lax.all_gather(packed, axes, tiled=False)  # [ndp, R, C/32]
        all_scale = lax.all_gather(scale, axes, tiled=False)    # [ndp, R, 1]
        signs = unpack_bits(all_packed.reshape(-1, packed.shape[-1]))
        signs = signs.reshape((ndp,) + packed.shape[:1] + (-1,))
        deq = jnp.where(signs, all_scale, -all_scale)      # [ndp, R, ROW]
        mean = jnp.mean(deq, axis=0).reshape(-1)[:buf.shape[0]]
        out_bufs.append(mean.astype(buf.dtype))
        new_err.append(err2)
    return fusion.unpack(out_bufs, plan), new_err
