"""Jitted dispatch wrappers for the Pallas kernels.

On a TPU the kernels run compiled; on any other backend the wrappers
return the jnp oracle of ``kernels/ref.py`` in the kernel's output format
(the tests run the kernel bodies themselves in the Pallas interpreter).
The fabric-DDP 1-bit codec (``distrib/collectives.py``) calls the onebit
wrappers.  No model code calls ``flash_attention`` or
``mamba2_chunk_scan`` yet; the tests sweep them against ``kernels/ref.py``
and compile them for a v5e topology.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import flash_attention as _fa
from . import mamba2_scan as _m2
from . import onebit as _ob
from . import ref
from ..optim.compression import pack_bits, unpack_bits


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


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
