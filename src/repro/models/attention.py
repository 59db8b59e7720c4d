"""Grouped-query attention: training (chunked/flash-style), prefill, decode.

Implementations:
  * ``full``    - materialized logits; oracle for tests and small models.
  * ``chunked`` - online-softmax over query blocks (lax.scan + checkpoint),
                  the memory shape of FlashAttention expressed in pure jnp;
                  this is what full-size dry-run configs lower.
  * Pallas kernel (kernels/flash_attention.py) plugs in through the same
    signature on TPU via kernels/ops.py.

Decode attends a single new token against a KV cache; for long contexts the
cache's sequence dim may be sharded (tiling plan "kv_seq"), in which case the
softmax reduction spans shards - XLA partitions those reductions, and the
optimized path uses the explicit flash-decoding combine in core.collectives.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from ..core.sharding import ParamSpec
from . import layers

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Parameter specs
# ---------------------------------------------------------------------------
def attn_specs(d: int, n_heads: int, n_kv: int, head_dim: int, *,
               qkv_bias: bool = False, qk_norm: bool = False) -> dict:
    sp = {
        "wq": ParamSpec((d, n_heads, head_dim), ("embed", "heads", "head_dim")),
        "wk": ParamSpec((d, n_kv, head_dim), ("embed", "kv_heads", "head_dim")),
        "wv": ParamSpec((d, n_kv, head_dim), ("embed", "kv_heads", "head_dim")),
        "wo": ParamSpec((n_heads, head_dim, d), ("heads", "head_dim", "embed")),
    }
    if qkv_bias:
        sp["bq"] = ParamSpec((n_heads, head_dim), ("heads", "head_dim"), init="zeros")
        sp["bk"] = ParamSpec((n_kv, head_dim), ("kv_heads", "head_dim"), init="zeros")
        sp["bv"] = ParamSpec((n_kv, head_dim), ("kv_heads", "head_dim"), init="zeros")
    if qk_norm:
        sp["q_norm"] = ParamSpec((head_dim,), ("head_dim",), init="ones")
        sp["k_norm"] = ParamSpec((head_dim,), ("head_dim",), init="ones")
    return sp


def project_qkv(x, p, *, positions=None, rope_theta: float = 10000.0,
                use_rope: bool = True):
    """x: [B, S, D] -> q [B,S,H,hd], k/v [B,S,Hkv,hd]."""
    dt = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dt))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(dt))
    if "bq" in p:
        q = q + p["bq"].astype(dt)
        k = k + p["bk"].astype(dt)
        v = v + p["bv"].astype(dt)
    if "q_norm" in p:
        q = layers.rms_norm(q, p["q_norm"])
        k = layers.rms_norm(k, p["k_norm"])
    if use_rope:
        if positions is None:
            positions = jnp.arange(x.shape[1])[None, :]
        q = layers.apply_rope(q, positions, rope_theta)
        k = layers.apply_rope(k, positions, rope_theta)
    return q, k, v


def _expand_kv(k, n_heads: int):
    """[B,S,Hkv,hd] -> [B,S,H,hd] by repeating each kv head (GQA)."""
    n_kv = k.shape[2]
    if n_kv == n_heads:
        return k
    return jnp.repeat(k, n_heads // n_kv, axis=2)


def _mask_bias(q_pos, k_pos, *, causal: bool, window: Optional[int]):
    """[.., Sq, Sk] additive bias from position grids."""
    m = jnp.zeros(q_pos.shape[:-1] + (q_pos.shape[-1], k_pos.shape[-1]),
                  jnp.float32)
    diff = q_pos[..., :, None] - k_pos[..., None, :]
    if causal:
        m = jnp.where(diff < 0, NEG_INF, m)
    if window is not None:
        m = jnp.where(diff >= window, NEG_INF, m)
    return m


# ---------------------------------------------------------------------------
# Training / prefill attention
# ---------------------------------------------------------------------------
def attend_full(q, k, v, *, causal: bool = True, window: Optional[int] = None,
                q_offset: int = 0, scale: Optional[float] = None):
    """Oracle: materialized [B,H,Sq,Sk] logits."""
    B, Sq, H, hd = q.shape
    Sk = k.shape[1]
    scale = scale or (1.0 / math.sqrt(hd))
    k = _expand_kv(k, H)
    v = _expand_kv(v, H)
    lg = jnp.einsum("bqhk,bshk->bhqs", q, k).astype(jnp.float32) * scale
    q_pos = (jnp.arange(Sq) + q_offset)[None, :]
    k_pos = jnp.arange(Sk)[None, :]
    lg = lg + _mask_bias(q_pos, k_pos, causal=causal, window=window)[:, None]
    pr = jax.nn.softmax(lg, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqs,bshk->bqhk", pr, v)


def _row_blocks(iq: int, nk: int, q_chunk: int, kv_chunk: int,
                q_offset: int, causal: bool, window: Optional[int]):
    """kv-block indices visible to query chunk ``iq`` (static)."""
    q_lo = iq * q_chunk + q_offset
    q_hi = q_lo + q_chunk - 1
    out = []
    for ik in range(nk):
        k_lo = ik * kv_chunk
        k_hi = k_lo + kv_chunk - 1
        if causal and q_hi < k_lo:
            continue  # entirely in the future
        if window is not None and q_lo - k_hi >= window:
            continue  # entirely behind the window
        out.append(ik)
    return out


def attend_chunked(q, k, v, *, causal: bool = True,
                   window: Optional[int] = None, q_chunk: int = 1024,
                   kv_chunk: int = 1024, remat_chunks: bool = True,
                   q_offset: int = 0, scale: Optional[float] = None):
    """Online-softmax blocked attention (FlashAttention's shape in jnp).

    The outer loop over query chunks is a *Python* unroll, so each chunk's
    inner lax.scan runs over exactly the kv blocks it can see - causal
    attention pays the triangle's FLOPs, not the square's, with a small
    per-chunk carry (O(q_chunk*hd)).  Probabilities are never stored: the
    block body is rematerialized in the backward pass.
    """
    from ..core.sharding import act_constrain
    B, Sq, H, hd = q.shape
    Sk = k.shape[1]
    scale = scale or (1.0 / math.sqrt(hd))
    k = _expand_kv(k, H)
    v = _expand_kv(v, H)
    # pin attention tensors to head-TP: without this the partitioner may
    # shard the kv-block dim instead and all-gather K/V per query row
    # (observed at 4.8 TB/step wire on chameleon prefill_32k, §Perf)
    q = act_constrain(q, ("batch", None, "heads", "head_dim"))
    k = act_constrain(k, ("batch", None, "heads", "head_dim"))
    v = act_constrain(v, ("batch", None, "heads", "head_dim"))

    def _snap(S, c):
        c = min(c, S)
        while S % c:
            c -= 1
        return c

    q_chunk = _snap(Sq, q_chunk)
    kv_chunk = _snap(Sk, kv_chunk)
    nq, nk = Sq // q_chunk, Sk // kv_chunk

    kb = jnp.moveaxis(k.reshape(B, nk, kv_chunk, H, hd), 1, 0)
    vb = jnp.moveaxis(v.reshape(B, nk, kv_chunk, H, hd), 1, 0)

    outs = []
    for iq in range(nq):
        qi = jax.lax.slice_in_dim(q, iq * q_chunk, (iq + 1) * q_chunk, axis=1)
        blocks = _row_blocks(iq, nk, q_chunk, kv_chunk, q_offset, causal,
                             window)
        if not blocks:
            outs.append(jnp.zeros((B, q_chunk, H, hd), q.dtype))
            continue
        lo, hi = blocks[0], blocks[-1]       # always a contiguous range

        def block(carry, inputs, iq=iq):
            o, m, l = carry                  # [B,H,qc,hd],[B,H,qc],[B,H,qc]
            kj, vj, ik = inputs
            lg = jnp.einsum("bqhk,bshk->bhqs", qi, kj
                            ).astype(jnp.float32) * scale
            q_pos = iq * q_chunk + q_offset + jnp.arange(q_chunk)
            k_pos = ik * kv_chunk + jnp.arange(kv_chunk)
            diff = q_pos[:, None] - k_pos[None, :]
            bias = jnp.zeros_like(diff, jnp.float32)
            if causal:
                bias = jnp.where(diff < 0, NEG_INF, bias)
            if window is not None:
                bias = jnp.where(diff >= window, NEG_INF, bias)
            lg = lg + bias[None, None]
            m_new = jnp.maximum(m, lg.max(-1))
            m_safe = jnp.where(m_new <= NEG_INF, 0.0, m_new)  # all-masked rows
            p = jnp.exp(lg - m_safe[..., None])
            corr = jnp.exp(m - m_safe)
            l = l * corr + p.sum(-1)
            o = o * corr[..., None] + jnp.einsum(
                "bhqs,bshk->bhqk", p.astype(qi.dtype), vj).astype(jnp.float32)
            return (o, m_new, l), None

        o0 = jnp.zeros((B, H, q_chunk, hd), jnp.float32)
        m0 = jnp.full((B, H, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, H, q_chunk), jnp.float32)
        body = jax.checkpoint(block) if remat_chunks else block
        (o, m, l), _ = jax.lax.scan(
            body, (o0, m0, l0),
            (jax.lax.slice_in_dim(kb, lo, hi + 1, axis=0),
             jax.lax.slice_in_dim(vb, lo, hi + 1, axis=0),
             jnp.arange(lo, hi + 1, dtype=jnp.int32)))
        o = (o / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)
        outs.append(o.transpose(0, 2, 1, 3))     # [B, qc, H, hd]
    return jnp.concatenate(outs, axis=1)


def attend(q, k, v, *, impl: str = "chunked", **kw):
    qc = kw.get("q_chunk", 1024)
    kc = kw.get("kv_chunk", 1024)
    indivisible = (q.shape[1] % min(qc, q.shape[1]) != 0
                   or k.shape[1] % min(kc, k.shape[1]) != 0)
    if impl == "full" or indivisible:
        kw.pop("q_chunk", None); kw.pop("kv_chunk", None); kw.pop("remat_chunks", None)
        return attend_full(q, k, v, **kw)
    return attend_chunked(q, k, v, **kw)


# ---------------------------------------------------------------------------
# Decode (KV-cache) attention
# ---------------------------------------------------------------------------
def decode_attend(q, k_cache, v_cache, pos, *, scale: Optional[float] = None,
                  window: Optional[int] = None):
    """q: [B,1,H,hd]; caches [B,S,Hkv,hd]; pos: scalar current index, or a
    ``[B]`` vector when batch rows sit at different offsets (the serving
    gateway's continuous batch, where each slot decodes its own token
    index - DESIGN.md §14).

    Grouped-GQA form: KV heads are never expanded, so the only shardable
    names are (batch, kv_heads, kv_seq) - a sequence-sharded cache keeps its
    sharding through the softmax (partial max/sum + psum) instead of being
    re-sharded by heads (which costs a full-cache all-gather; see §Perf
    granite-decode iterations).
    """
    from ..core.sharding import act_constrain
    B, _, H, hd = q.shape
    S = k_cache.shape[1]
    Hkv = k_cache.shape[2]
    G = H // Hkv
    scale = scale or (1.0 / math.sqrt(hd))
    qg = q.reshape(B, 1, Hkv, G, hd)
    lg = jnp.einsum("bqhgk,bshk->bhgqs", qg, k_cache
                    ).astype(jnp.float32) * scale      # [B,Hkv,G,1,S]
    lg = act_constrain(lg, ("batch", "kv_heads", None, None, "kv_seq"))
    k_pos = jnp.arange(S)
    pos = jnp.asarray(pos)
    if pos.ndim == 0:
        valid = k_pos <= pos
        if window is not None:
            valid = valid & (k_pos > pos - window)
        mask = valid[None, None, None, None, :]
    else:                                   # per-row positions: [B] -> [B,S]
        valid = k_pos[None, :] <= pos[:, None]
        if window is not None:
            valid = valid & (k_pos[None, :] > pos[:, None] - window)
        mask = valid[:, None, None, None, :]
    lg = jnp.where(mask, lg, NEG_INF)
    pr = jax.nn.softmax(lg, axis=-1).astype(q.dtype)
    o = jnp.einsum("bhgqs,bshk->bqhgk", pr, v_cache)
    return o.reshape(B, 1, H, hd)


def cache_update(k_cache, v_cache, k_new, v_new, pos, *, mode: str = "dus"):
    """Write the new token's K/V at ``pos`` (scalar, or ``[B]`` for
    per-row write offsets).

    mode="dus": dynamic-update-slice (minimal write, but the SPMD
    partitioner reshards a cache whose sequence dim is sharded).
    mode="masked": one-hot select over the sequence dim - elementwise, so a
    sequence-sharded cache updates locally with zero collectives at the cost
    of a full cache rewrite.  A ``[B]`` pos always takes this form: there
    is no per-row dynamic-update-slice, and the one-hot write is exactly
    row-independent, which the gateway's bit-parity guarantees rely on.
    """
    pos = jnp.asarray(pos)
    if mode == "masked" or pos.ndim:
        S = k_cache.shape[1]
        hit = ((jnp.arange(S) == pos)[None, :, None, None] if pos.ndim == 0
               else (jnp.arange(S)[None, :] == pos[:, None])[:, :, None,
                                                             None])
        k_cache = jnp.where(hit, k_new.astype(k_cache.dtype), k_cache)
        v_cache = jnp.where(hit, v_new.astype(v_cache.dtype), v_cache)
        return k_cache, v_cache
    k_cache = jax.lax.dynamic_update_slice_in_dim(
        k_cache, k_new.astype(k_cache.dtype), pos, axis=1)
    v_cache = jax.lax.dynamic_update_slice_in_dim(
        v_cache, v_new.astype(v_cache.dtype), pos, axis=1)
    return k_cache, v_cache


# ---------------------------------------------------------------------------
# Whole attention layer (projections + attend + out proj)
# ---------------------------------------------------------------------------
def attn_layer(x, p, cfg, *, impl: str = "chunked", positions=None,
               kv_override=None, causal: bool = True):
    """cfg needs: n_heads, n_kv_heads, head_dim, rope_theta, use_rope,
    sliding_window, q_chunk/kv_chunk optional.

    kv_override: (k, v) from an encoder for cross-attention.
    """
    dt = x.dtype
    q, k, v = project_qkv(
        x, p, positions=positions, rope_theta=cfg.rope_theta,
        use_rope=cfg.use_rope and kv_override is None)
    if kv_override is not None:
        k, v = kv_override
        causal = False
    o = attend(q, k, v, impl=impl, causal=causal,
               window=cfg.sliding_window,
               q_chunk=getattr(cfg, "q_chunk", 1024),
               kv_chunk=getattr(cfg, "kv_chunk", 1024))
    return jnp.einsum("bqhk,hkd->bqd", o, p["wo"].astype(dt))
