"""Grouped-query attention: training (chunked/flash-style), prefill, decode.

Implementations:
  * ``full``    - materialized logits; oracle for tests and small models.
  * ``chunked`` - online-softmax over query blocks (lax.scan + checkpoint),
                  the memory shape of FlashAttention expressed in pure jnp;
                  this is what full-size dry-run configs lower.
  * Pallas kernel (kernels/flash_attention.py) plugs in through the same
    signature on TPU via kernels/ops.py.

Latent attention (MLA, DeepSeek-V2/V3) has its own projections
(``mla_specs``/``mla_layer``): its q and k are [nope | rope] per head and
wider than v.  On a TPU with attention not partitioned it attends through
the fused splash kernel (``kernels/ops.py`` ``causal_attention``),
elsewhere through the same ``attend``.  It is wired for training and full
forwards only; a latent decode cache does not exist yet.

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
from ..kernels import ops
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


# Hugging Face's DeepseekV3 builds kv_a_layernorm with its RMSNorm's default
# eps (1e-6), not the model's rms_norm_eps
MLA_KV_NORM_EPS = 1e-6


def mla_specs(d: int, n_heads: int, kv_rank: int, nope: int, rope: int,
              v_dim: int) -> dict:
    """Latent attention without a q LoRA: ``wq`` [d, H, nope+rope];
    ``wkv_a`` [d, kv_rank+rope] (the latent, then the shared rope key);
    ``kv_norm`` on the latent; ``wkv_b`` [kv_rank, H, nope+v] (each head's
    k_nope, then its v); ``wo`` [H, v, d]."""
    return {
        "wq": ParamSpec((d, n_heads, nope + rope),
                        ("embed", "heads", "head_dim")),
        "wkv_a": ParamSpec((d, kv_rank + rope), ("embed", None)),
        "kv_norm": ParamSpec((kv_rank,), (None,), init="ones"),
        "wkv_b": ParamSpec((kv_rank, n_heads, nope + v_dim),
                           (None, "heads", "head_dim")),
        "wo": ParamSpec((n_heads, v_dim, d), ("heads", "head_dim", "embed")),
    }


def mla_layer(x, p, cfg, *, impl: str = "chunked"):
    """x: [B, S, D] -> [B, S, D], causal latent attention.

    q_nope|q_pe = x Wq; c|k_pe = x Wkv_a; c = rms(c) * kv_norm;
    k_nope|v = c Wkv_b; q_pe and k_pe (one key, shared by every head) are
    rotated; q = [q_nope | q_pe], k = [k_nope | k_pe], scale 1/sqrt(nope +
    rope).  RoPE pairs dimension i with i + rope/2 of the rope part
    (rotate-half); Hugging Face's DeepseekV3 stores those dimensions
    interleaved and de-interleaves them before the same rotation, which
    is a fixed permutation of the rope columns of Wq and Wkv_a.

    Where ``ops.causal_attention_applies`` (a TPU, attention not
    partitioned) the attention runs as the fused Pallas kernel instead of
    the chunked jnp blocks (``_mla_fused``)."""
    if impl == "chunked" and ops.causal_attention_applies():
        return _mla_fused(x, p, cfg)
    dt = x.dtype
    nope, r = cfg.qk_nope_dim, cfg.kv_lora_rank
    with jax.named_scope("mla"):
        pos = jnp.arange(x.shape[1])[None, :]
        q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dt))
        kv = x @ p["wkv_a"].astype(dt)
        c = layers.rms_norm(kv[..., :r], p["kv_norm"], MLA_KV_NORM_EPS)
        k_pe = layers.apply_rope(kv[..., None, r:], pos, cfg.rope_theta)
        kvb = jnp.einsum("bsr,rhk->bshk", c, p["wkv_b"].astype(dt))
        k_nope, v = kvb[..., :nope], kvb[..., nope:]
        q = jnp.concatenate(
            [q[..., :nope],
             layers.apply_rope(q[..., nope:], pos, cfg.rope_theta)], -1)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_pe, k_nope.shape[:-1]
                                      + (k_pe.shape[-1],))], -1)
        o = attend(q, k, v, impl=impl, causal=True, q_chunk=cfg.q_chunk,
                   kv_chunk=cfg.kv_chunk)
        return jnp.einsum("bqhk,hkd->bqd", o, p["wo"].astype(dt))


def _mla_fused(x, p, cfg):
    """``mla_layer`` through ``ops.causal_attention``: q, k and v are built
    in the kernel's [B, H, S, d] order by the projections' einsums, and q
    takes the softmax scale in float32 before its one cast to x's dtype."""
    dt = x.dtype
    nope, rope, r = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.kv_lora_rank
    with jax.named_scope("mla"):
        pos = jnp.arange(x.shape[1])
        q = jnp.einsum("bsd,dhk->bhsk", x, p["wq"].astype(dt),
                       preferred_element_type=jnp.float32)
        q_pe = layers.apply_rope(q[..., None, nope:], pos, cfg.rope_theta)
        q = (jnp.concatenate([q[..., :nope], q_pe[..., 0, :]], -1)
             * (1.0 / math.sqrt(nope + rope))).astype(dt)
        kv = x @ p["wkv_a"].astype(dt)
        c = layers.rms_norm(kv[..., :r], p["kv_norm"], MLA_KV_NORM_EPS)
        k_pe = layers.apply_rope(kv[:, None, :, None, r:], pos,
                                 cfg.rope_theta)[..., 0, :]   # [B, 1, S, rope]
        kvb = jnp.einsum("bsr,rhk->bhsk", c, p["wkv_b"].astype(dt))
        k_nope, v = kvb[..., :nope], kvb[..., nope:]
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_pe, k_nope.shape[:-1] + (rope,))], -1)
        o = ops.causal_attention(q, k, v)
        return jnp.einsum("bhsk,hkd->bsd", o, p["wo"].astype(dt))


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
    Sk, vd = k.shape[1], v.shape[-1]       # v may be narrower than q, k
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
    vb = jnp.moveaxis(v.reshape(B, nk, kv_chunk, H, vd), 1, 0)

    outs = []
    for iq in range(nq):
        qi = jax.lax.slice_in_dim(q, iq * q_chunk, (iq + 1) * q_chunk, axis=1)
        blocks = _row_blocks(iq, nk, q_chunk, kv_chunk, q_offset, causal,
                             window)
        if not blocks:
            outs.append(jnp.zeros((B, q_chunk, H, vd), q.dtype))
            continue
        lo, hi = blocks[0], blocks[-1]       # always a contiguous range

        def block(carry, inputs, iq=iq):
            o, m, l = carry                  # [B,H,qc,vd],[B,H,qc],[B,H,qc]
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

        o0 = jnp.zeros((B, H, q_chunk, vd), jnp.float32)
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


def cache_update(k_cache, v_cache, k_new, v_new, pos, *, mode: str = "dus",
                 layer=None):
    """Write the new token's K/V (``[B,1,Hkv,hd]``) into each row's own
    position ``pos`` (a scalar broadcasts to every row, or ``[B]``).

    The caches are ``[B,S,Hkv,hd]``, or a layer stack ``[L,B,S,Hkv,hd]``
    written at index ``layer`` (the decode loop carries the stack and
    writes it in place).  Each row writes only its own position, so the
    write is row-independent, which the gateway's bit-identical streams
    rely on; a row whose position lies outside ``[0, S)`` writes nothing.

    mode="dus": one scatter of a single position per row, updated in place
    (but the SPMD partitioner reshards a cache whose sequence dim is
    sharded).
    mode="masked": one-hot select over the sequence dim - elementwise, so a
    sequence-sharded cache updates locally with zero collectives at the cost
    of rewriting the layer's whole cache.
    """
    return (_write_rows(k_cache, k_new, pos, mode, layer),
            _write_rows(v_cache, v_new, pos, mode, layer))


def _write_rows(cache, new, pos, mode, layer):
    B, S = cache.shape[-4:-2]
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    new = new[:, 0].astype(cache.dtype)                    # [B, Hkv, hd]
    if mode == "masked":
        sl = (cache if layer is None
              else jax.lax.dynamic_index_in_dim(cache, layer, 0, False))
        hit = (jnp.arange(S)[None, :] == pos[:, None])[:, :, None, None]
        sl = jnp.where(hit, new[:, None], sl)
        return (sl if layer is None
                else jax.lax.dynamic_update_index_in_dim(cache, sl, layer, 0))
    rows = jnp.arange(B)
    idx = (rows, pos) if layer is None else (layer, rows, pos)
    return cache.at[idx].set(new, mode="drop", unique_indices=True,
                             wrap_negative_indices=False)


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
