"""Named asynchronous collectives and communication schedules.

Maps Phylanx's asynchronous active-messaging collectives onto jax.lax
collectives (asynchronous-by-construction under XLA's latency-hiding
scheduler) plus explicitly scheduled variants built from collective_permute
for the cases where we control the schedule ourselves (ring pipelines, halo
exchange, flash-decoding split-KV combines).

Everything here is usable inside ``jax.shard_map`` bodies; the top-level
pjit path instead relies on the SPMD partitioner inserting the equivalent
ops from sharding constraints.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


# ---------------------------------------------------------------------------
# Fused collectives over pytrees (tensor fusion applied to collectives)
# ---------------------------------------------------------------------------
def fused_psum(tree, axis_name, cap_bytes: int = 32 * 1024 * 1024):
    """All-reduce a pytree in dtype-homogeneous fused buckets (paper R5)."""
    from . import fusion
    return fusion.fused_apply(tree, lambda b: lax.psum(b, axis_name), cap_bytes)


def fused_pmean(tree, axis_name, cap_bytes: int = 32 * 1024 * 1024):
    from . import fusion
    return fusion.fused_apply(tree, lambda b: lax.pmean(b, axis_name), cap_bytes)


def naive_psum(tree, axis_name):
    """Horovod-baseline: one all-reduce per tensor, no fusion (Fig. 1)."""
    return jax.tree.map(lambda g: lax.psum(g, axis_name), tree)


def reduce_scatter(x: jax.Array, axis_name: str, *, axis: int = 0):
    """psum_scatter with tiling (ZeRO-style gradient shard)."""
    return lax.psum_scatter(x, axis_name, scatter_dimension=axis, tiled=True)


def all_gather(x: jax.Array, axis_name: str, *, axis: int = 0):
    return lax.all_gather(x, axis_name, axis=axis, tiled=True)


# ---------------------------------------------------------------------------
# Explicit ring schedules (collective_permute based)
# ---------------------------------------------------------------------------
def _ring_perm(n: int, step: int = 1):
    return [(i, (i + step) % n) for i in range(n)]


def ring_all_reduce(x: jax.Array, axis_name: str) -> jax.Array:
    """Bandwidth-optimal ring all-reduce written as reduce-scatter +
    all-gather over collective_permute steps.

    This is the schedule Horovod's ring_allreduce and Phylanx's asynchronous
    collectives both lower to; having it explicit lets the pipeline examples
    overlap each hop with compute and lets tests count hops.
    """
    n = lax.axis_size(axis_name)
    if n == 1:
        return x
    if x.size % n != 0:  # fallback for indivisible payloads
        return lax.psum(x, axis_name)
    flat = x.reshape(n, -1)
    idx = lax.axis_index(axis_name)

    # reduce-scatter phase: at step s each rank sends its accumulated
    # chunk (idx - s) % n to the right neighbour; after n-1 hops rank r
    # holds the fully reduced chunk (r + 1) % n.
    send = lax.dynamic_index_in_dim(flat, idx % n, 0, keepdims=False)
    for s in range(n - 1):
        recv = lax.ppermute(send, axis_name, _ring_perm(n, +1))
        c = (idx - s - 1) % n
        send = lax.dynamic_index_in_dim(flat, c, 0, keepdims=False) + recv

    # all-gather phase: row r of the gather holds chunk (r+1)%n, so chunk i
    # lives at row (i-1)%n.
    full = lax.all_gather(send, axis_name, axis=0, tiled=False)
    order = (jnp.arange(n) - 1) % n
    return full[order].reshape(x.shape)


def halo_exchange(x: jax.Array, axis_name: str, halo: int, *, dim: int = 0):
    """Overlapped tiling (paper: spatial parallelism halo exchange).

    Each shard sends its ``halo`` boundary slices to both neighbours and
    returns the tile extended with received ghost cells (edge shards are
    zero-padded: non-periodic boundary).
    """
    n = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    lo = lax.slice_in_dim(x, 0, halo, axis=dim)
    hi = lax.slice_in_dim(x, x.shape[dim] - halo, x.shape[dim], axis=dim)
    from_left = lax.ppermute(hi, axis_name, _ring_perm(n, +1))    # rank i-1's hi
    from_right = lax.ppermute(lo, axis_name, _ring_perm(n, -1))   # rank i+1's lo
    zeros = jnp.zeros_like(lo)
    from_left = jnp.where(idx == 0, zeros, from_left)
    from_right = jnp.where(idx == n - 1, zeros, from_right)
    return jnp.concatenate([from_left, x, from_right], axis=dim)


# ---------------------------------------------------------------------------
# Flash-decoding split-KV combine (long-context decode over sharded KV)
# ---------------------------------------------------------------------------
def softmax_combine(partials: tuple[jax.Array, jax.Array, jax.Array],
                    axis_name: str):
    """Combine per-shard (m, l, o) softmax partials across a sharded KV axis.

    m: running max [...,1], l: running denominator [...,1], o: weighted
    values [...,d].  Exact merge of block-local softmaxes; communication is
    two small psums + one psum over o - O(d) per token instead of an O(S)
    all-gather of the KV cache.
    """
    m, l, o = partials
    m_glob = lax.pmax(m, axis_name)
    scale = jnp.exp(m - m_glob)
    l_glob = lax.psum(l * scale, axis_name)
    o_glob = lax.psum(o * scale, axis_name)
    return o_glob / jnp.maximum(l_glob, 1e-30)


# ---------------------------------------------------------------------------
# Pipeline (GPipe-style) primitives
# ---------------------------------------------------------------------------
def pipeline_shift(x: jax.Array, axis_name: str, *, reverse: bool = False):
    """Hand activations (or grads, reverse) to the neighbouring stage."""
    n = lax.axis_size(axis_name)
    return lax.ppermute(x, axis_name, _ring_perm(n, -1 if reverse else 1))
