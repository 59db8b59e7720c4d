"""Jitted dispatch wrappers for the Pallas kernels.

On a TPU the kernels run compiled; on any other backend the wrappers
return the jnp oracle of ``kernels/ref.py`` in the kernel's output format
(the tests run the kernel bodies themselves in the Pallas interpreter).
The fabric-DDP 1-bit codec (``distrib/collectives.py``) calls the onebit
wrappers; latent attention (``models/attention.py`` ``mla_layer``) calls
``causal_attention`` where ``causal_attention_applies``.  No model code
calls ``flash_attention`` or ``mamba2_chunk_scan`` yet; the tests sweep
them against ``kernels/ref.py`` and compile them for a v5e topology.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.splash_attention import (
    splash_attention_kernel as _splash, splash_attention_mask as _mask)

from . import flash_attention as _fa
from . import mamba2_scan as _m2
from . import onebit as _ob
from . import ref
from ..core import sharding as _sharding
from ..optim.compression import pack_bits, unpack_bits

# Tiles of the fused causal attention, the fastest of a sweep on a TPU v5e
# at Moonlight's training shapes (2 x 8192, 16 heads, q.k 192, v 128;
# PERF.md section 6): 1024-row tiles, one fused dq+dkv backward kernel.
# 2048-row query tiles overflow the kernels' VMEM.
CAUSAL_BLOCKS = dict(block_q=1024, block_kv=1024, block_kv_compute=512,
                     block_q_dkv=1024, block_kv_dkv=1024,
                     block_kv_dkv_compute=512, use_fused_bwd_kernel=True)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def causal_attention_applies() -> bool:
    """Whether ``causal_attention`` runs where a caller traces now: on a
    TPU, with attention not partitioned (no activation hook installed, or
    its mesh has one device).  A partitioned attention needs the kernel
    under ``shard_map``, which is not built."""
    hook = _sharding._ACT_HOOK[0]
    return _on_tpu() and (hook is None or hook[0].size == 1)


def causal_attention(q, k, v, *, interpret: bool = False):
    """Causal softmax attention through the Pallas TPU splash kernel: the
    forward and the backward (dq and dkv in one kernel) under one custom
    VJP, each tile's logits held in VMEM.  q, k: [B, H, S, dk];
    v: [B, H, S, dv] (dv may be narrower) -> [B, H, S, dv].  The kernel
    takes no softmax scale: fold it into q.  Tiles are ``CAUSAL_BLOCKS``,
    clipped to S."""
    H, S = q.shape[1:3]
    sizes = {n: b if isinstance(b, bool) else min(b, S)
             for n, b in CAUSAL_BLOCKS.items()}
    mask = _mask.MultiHeadMask([_mask.CausalMask((S, S))] * H)
    kernel = _splash.make_splash_mha(
        mask, block_sizes=_splash.BlockSizes(**sizes), head_shards=1,
        q_seq_shards=1, interpret=interpret)
    return jax.vmap(kernel)(q, k, v)


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_kv"))
def flash_attention(q, k, v, *, causal: bool = True, window=None,
                    block_q: int = 128, block_kv: int = 128):
    """q: [B, H, S, d]; k, v: [B, Hkv, S, d] -> [B, H, S, d]."""
    if not _on_tpu():
        return ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    return _fa.flash_attention(q, k, v, causal=causal, window=window,
                               block_q=block_q, block_kv=block_kv)


@functools.partial(jax.jit, static_argnames=("chunk",))
def mamba2_chunk_scan(xdt, a, Bm, Cm, *, chunk: int = 128):
    if not _on_tpu():
        return ref.mamba2_scan_ref(xdt, a, Bm, Cm)
    return _m2.mamba2_chunk_scan(xdt, a, Bm, Cm, chunk=chunk)


@functools.partial(jax.jit, static_argnames=("block_rows",))
def onebit_quantize(g, err, *, block_rows: int = 256):
    """g, err: [R, C] -> (packed u32 [R, C/32], scale [R, 128]
    lane-replicated, new_err [R, C]) on every backend."""
    if not _on_tpu():
        signs, scale, new_err = ref.onebit_quantize_ref(g, err)
        return (pack_bits(signs),
                jnp.broadcast_to(scale, (g.shape[0], 128)), new_err)
    return _ob.onebit_quantize(g, err, block_rows=block_rows)


@functools.partial(jax.jit, static_argnames=("block_rows",))
def onebit_dequantize(packed, scale, *, block_rows: int = 256):
    """packed: u32 [R, C/32]; scale: [R, 128] -> [R, C] f32."""
    if not _on_tpu():
        return ref.onebit_dequantize_ref(unpack_bits(packed), scale[:, :1])
    return _ob.onebit_dequantize(packed, scale, block_rows=block_rows)
