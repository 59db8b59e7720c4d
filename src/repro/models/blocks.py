"""Composable blocks: pre-norm residual transformer / moe / ssm variants.

Each block kind provides three functions with a uniform contract:
  *_specs(cfg)                      -> ParamSpec tree
  *_apply(x, p, cfg, **ctx)         -> x            (train / prefill)
  *_decode(x, p, cfg, cache, pos)   -> (x, cache)   (single-token step)

Caches are ParamSpec trees too (init="zeros"), so the same sharding engine
places them on the mesh.
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from ..core.sharding import ParamSpec
from . import attention, layers, moe, ssm


# ---------------------------------------------------------------------------
# Transformer block (attention + MLP or MoE), optional cross-attention
# ---------------------------------------------------------------------------
def tblock_specs(cfg, *, cross: bool = False, use_moe: bool = False) -> dict:
    if cfg.mla:
        attn = attention.mla_specs(cfg.d_model, cfg.n_heads, cfg.kv_lora_rank,
                                   cfg.qk_nope_dim, cfg.qk_rope_dim,
                                   cfg.v_head_dim)
    else:
        attn = attention.attn_specs(
            cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
            qkv_bias=cfg.qkv_bias, qk_norm=cfg.qk_norm)
    sp = {
        "ln_attn": layers.norm_specs(cfg.d_model, cfg.norm),
        "attn": attn,
        "ln_mlp": layers.norm_specs(cfg.d_model, cfg.norm),
    }
    if use_moe:
        sp["moe"] = moe.moe_specs(
            cfg.d_model, cfg.moe_d_ff, cfg.n_experts, n_held=cfg.experts_held,
            shared_ff=cfg.n_shared_experts * cfg.moe_d_ff,
            router_bias=cfg.router == "sigmoid")
    else:
        sp["mlp"] = layers.mlp_specs(cfg.d_model, cfg.d_ff, cfg.mlp_kind)
    if cross:
        sp["ln_cross"] = layers.norm_specs(cfg.d_model, cfg.norm)
        sp["cross"] = attention.attn_specs(
            cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
            qkv_bias=cfg.qkv_bias, qk_norm=False)
    return sp


def tblock_apply(x, p, cfg, *, impl: str = "chunked", causal: bool = True,
                 positions=None, enc_kv=None):
    """-> (x, stats): the MoE layer's (``moe.apply_moe``), or
    ``{"aux": 0}``."""
    eps = cfg.norm_eps
    h = layers.apply_norm(x, p["ln_attn"], cfg.norm, eps)
    if cfg.mla:
        x = x + attention.mla_layer(h, p["attn"], cfg, impl=impl)
    else:
        x = x + attention.attn_layer(h, p["attn"], cfg, impl=impl,
                                     positions=positions, causal=causal)
    if "cross" in p:
        h = layers.apply_norm(x, p["ln_cross"], cfg.norm, eps)
        x = x + attention.attn_layer(h, p["cross"], cfg, impl=impl,
                                     kv_override=enc_kv)
    h = layers.apply_norm(x, p["ln_mlp"], cfg.norm, eps)
    if "moe" in p:
        y, stats = _moe(h, p["moe"], cfg)
        return x + y, stats
    return (x + layers.apply_mlp(h, p["mlp"], cfg.mlp_kind),
            {"aux": jnp.zeros((), jnp.float32)})


def _moe(h, p, cfg):
    return moe.apply_moe(h, p, top_k=cfg.top_k, group_size=cfg.moe_group,
                         cap_factor=cfg.capacity_factor,
                         dispatch=cfg.moe_dispatch, scoring=cfg.router,
                         routed_scale=cfg.routed_scale,
                         expert_offset=cfg.expert_offset)


def kv_cache_specs(cfg, batch: int, seq: int, n_layers: Optional[int] = None,
                   *, prefix: tuple = ()) -> dict:
    shape = prefix + (batch, seq, cfg.n_kv_heads, cfg.head_dim)
    dims = tuple("layers" for _ in prefix) + ("batch", "kv_seq", "kv_heads",
                                              "head_dim")
    mk = lambda: ParamSpec(shape, dims, dtype=cfg.cache_dtype, init="zeros")
    return {"k": mk(), "v": mk()}


def tblock_decode(x, p, cfg, cache, pos, *, enc_kv=None):
    """x: [B,1,D]; cache: {"k","v"} [B,S,Hkv,hd]; pos: scalar int, or
    ``[B]`` per-row positions (continuous batch, one offset per slot)."""
    q, k, v = tblock_decode_project(x, p, cfg, pos)
    kc, vc = attention.cache_update(cache["k"], cache["v"], k, v, pos,
                                    mode=cfg.cache_update)
    new_cache = {"k": kc, "v": vc}
    if "cross" in p:
        if enc_kv is None:
            enc_kv = (cache["ck"], cache["cv"])
        new_cache["ck"], new_cache["cv"] = enc_kv
    return tblock_decode_attend(x, p, cfg, q, kc, vc, pos,
                                enc_kv=enc_kv), new_cache


def tblock_decode_project(x, p, cfg, pos):
    """The block up to the cache write: the new token's q, k, v
    (``[B,1,H|Hkv,hd]``), RoPE'd at each row's position."""
    h = layers.apply_norm(x, p["ln_attn"], cfg.norm)
    pos = jnp.asarray(pos)
    positions = (pos[:, None] if pos.ndim
                 else jnp.full((h.shape[0], 1), pos))
    return attention.project_qkv(
        h, p["attn"], positions=positions,
        rope_theta=cfg.rope_theta, use_rope=cfg.use_rope)


def tblock_decode_attend(x, p, cfg, q, kc, vc, pos, *, enc_kv=None):
    """The block after the cache write: attention over the written cache
    ``kc``/``vc`` [B,S,Hkv,hd], then cross-attention over ``enc_kv`` and
    the MLP or MoE -> x."""
    o = attention.decode_attend(q, kc, vc, pos, window=cfg.sliding_window)
    x = x + jnp.einsum("bqhk,hkd->bqd", o, p["attn"]["wo"].astype(x.dtype))
    if "cross" in p:
        h = layers.apply_norm(x, p["ln_cross"], cfg.norm)
        q = jnp.einsum("bsd,dhk->bshk", h, p["cross"]["wq"].astype(x.dtype))
        if "bq" in p["cross"]:
            q = q + p["cross"]["bq"].astype(x.dtype)
        ck, cv = enc_kv
        o = attention.attend_full(q, ck, cv, causal=False)
        x = x + jnp.einsum("bqhk,hkd->bqd", o,
                           p["cross"]["wo"].astype(x.dtype))
    h = layers.apply_norm(x, p["ln_mlp"], cfg.norm)
    if "moe" in p:
        return x + _moe(h, p["moe"], cfg)[0]
    return x + layers.apply_mlp(h, p["mlp"], cfg.mlp_kind)


# ---------------------------------------------------------------------------
# Mamba-2 block (pre-norm + mixer; no separate MLP, as in Mamba/Zamba)
# ---------------------------------------------------------------------------
def mamba_block_specs(cfg) -> dict:
    return {
        "ln": layers.norm_specs(cfg.d_model, cfg.norm),
        "mixer": ssm.mamba2_specs(
            cfg.d_model, expand=cfg.expand, head_dim=cfg.ssm_head_dim,
            state=cfg.ssm_state, n_groups=cfg.ssm_groups,
            d_conv=cfg.ssm_d_conv),
    }


def mamba_block_apply(x, p, cfg, *, chunk: int = 256):
    h = layers.apply_norm(x, p["ln"], cfg.norm)
    return x + ssm.mamba2_chunked(h, p["mixer"], cfg, chunk=chunk)


def mamba_block_decode(x, p, cfg, state, pos):
    h = layers.apply_norm(x, p["ln"], cfg.norm)
    y, state = ssm.mamba2_step(h, state, p["mixer"], cfg)
    return x + y, state


def mamba_state_specs(cfg, batch: int, *, prefix: tuple = ()) -> dict:
    d_in = cfg.expand * cfg.d_model
    H = d_in // cfg.ssm_head_dim
    conv_ch = d_in + 2 * cfg.ssm_groups * cfg.ssm_state
    pdims = tuple("layers" for _ in prefix)
    return {
        "ssm": ParamSpec(prefix + (batch, H, cfg.ssm_head_dim, cfg.ssm_state),
                         pdims + ("batch", "heads", "head_dim", "state"),
                         dtype=jnp.float32, init="zeros"),
        "conv": ParamSpec(prefix + (batch, cfg.ssm_d_conv - 1, conv_ch),
                          pdims + ("batch", "conv", "inner"),
                          dtype=cfg.cache_dtype, init="zeros"),
    }


# ---------------------------------------------------------------------------
# xLSTM blocks
# ---------------------------------------------------------------------------
def mlstm_block_specs(cfg) -> dict:
    return {
        "ln": layers.norm_specs(cfg.d_model, cfg.norm),
        "mixer": ssm.mlstm_specs(cfg.d_model, n_heads=cfg.n_heads,
                                 expand=cfg.expand, d_conv=cfg.ssm_d_conv),
    }


def mlstm_block_apply(x, p, cfg, *, chunk: int = 256):
    h = layers.apply_norm(x, p["ln"], cfg.norm)
    return x + ssm.mlstm_chunked(h, p["mixer"], cfg, chunk=chunk)


def mlstm_block_decode(x, p, cfg, state, pos):
    h = layers.apply_norm(x, p["ln"], cfg.norm)
    y, state = ssm.mlstm_step(h, state, p["mixer"], cfg)
    return x + y, state


def mlstm_state_specs(cfg, batch: int, *, prefix: tuple = ()) -> dict:
    d_in = cfg.expand * cfg.d_model
    H = cfg.n_heads
    P = d_in // H
    pdims = tuple("layers" for _ in prefix)
    f32 = jnp.float32
    return {
        "C": ParamSpec(prefix + (batch, H, P, P),
                       pdims + ("batch", "heads", "head_dim", "state"),
                       dtype=f32, init="zeros"),
        "n": ParamSpec(prefix + (batch, H, P),
                       pdims + ("batch", "heads", "head_dim"),
                       dtype=f32, init="zeros"),
        "m": ParamSpec(prefix + (batch, H), pdims + ("batch", "heads"),
                       dtype=f32, init="zeros"),
        "conv": ParamSpec(prefix + (batch, cfg.ssm_d_conv - 1, d_in),
                          pdims + ("batch", "conv", "inner"),
                          dtype=cfg.cache_dtype, init="zeros"),
    }


def slstm_block_specs(cfg) -> dict:
    return {
        "ln": layers.norm_specs(cfg.d_model, cfg.norm),
        "mixer": ssm.slstm_specs(cfg.d_model, n_heads=cfg.slstm_heads),
    }


def slstm_block_apply(x, p, cfg):
    h = layers.apply_norm(x, p["ln"], cfg.norm)

    class _C:
        n_heads = cfg.slstm_heads
        d_model = cfg.d_model
    return x + ssm.slstm_apply(h, p["mixer"], _C)


def slstm_block_decode(x, p, cfg, state, pos):
    h = layers.apply_norm(x, p["ln"], cfg.norm)

    class _C:
        n_heads = cfg.slstm_heads
        d_model = cfg.d_model
    y, state = ssm.slstm_step(h, state, p["mixer"], _C)
    return x + y, state


def slstm_state_specs(cfg, batch: int, *, prefix: tuple = ()) -> dict:
    H = cfg.slstm_heads
    P = cfg.d_model // H
    pdims = tuple("layers" for _ in prefix)
    f32 = jnp.float32
    mk = lambda *s, dims: ParamSpec(prefix + s, pdims + dims, dtype=f32,
                                    init="zeros")
    return {
        "h": mk(batch, H, P, dims=("batch", "heads", "head_dim")),
        "c": mk(batch, H, P, dims=("batch", "heads", "head_dim")),
        "n": mk(batch, H, P, dims=("batch", "heads", "head_dim")),
        "m": mk(batch, H, dims=("batch", "heads")),
    }


# ---------------------------------------------------------------------------
# Prefill variants: apply + return decode state / populated KV cache
# ---------------------------------------------------------------------------
def tblock_prefill(x, p, cfg, cache_len: int, *, impl: str = "chunked",
                   enc_kv=None):
    """Run the block over a full prompt, returning (x, kv-cache padded to
    cache_len).  Cross-attention K/V (enc-dec) are cached too."""
    h = layers.apply_norm(x, p["ln_attn"], cfg.norm)
    q, k, v = attention.project_qkv(
        h, p["attn"], rope_theta=cfg.rope_theta, use_rope=cfg.use_rope)
    o = attention.attend(q, k, v, impl=impl, causal=True,
                         window=cfg.sliding_window,
                         q_chunk=cfg.q_chunk, kv_chunk=cfg.kv_chunk)
    x = x + jnp.einsum("bqhk,hkd->bqd", o, p["attn"]["wo"].astype(x.dtype))
    pad = cache_len - k.shape[1]
    kc = jnp.pad(k.astype(cfg.cache_dtype), ((0, 0), (0, pad), (0, 0), (0, 0)))
    vc = jnp.pad(v.astype(cfg.cache_dtype), ((0, 0), (0, pad), (0, 0), (0, 0)))
    cache = {"k": kc, "v": vc}
    if "cross" in p:
        h = layers.apply_norm(x, p["ln_cross"], cfg.norm)
        x = x + attention.attn_layer(h, p["cross"], cfg, impl=impl,
                                     kv_override=enc_kv)
        cache["ck"], cache["cv"] = enc_kv
    h = layers.apply_norm(x, p["ln_mlp"], cfg.norm)
    if "moe" in p:
        x = x + _moe(h, p["moe"], cfg)[0]
    else:
        x = x + layers.apply_mlp(h, p["mlp"], cfg.mlp_kind)
    return x, cache


def mamba_block_prefill(x, p, cfg, *, chunk: int = 256):
    h = layers.apply_norm(x, p["ln"], cfg.norm)
    y, st = ssm.mamba2_chunked(h, p["mixer"], cfg, chunk=chunk,
                               return_state=True)
    return x + y, st


def mlstm_block_prefill(x, p, cfg, *, chunk: int = 256):
    h = layers.apply_norm(x, p["ln"], cfg.norm)
    y, st = ssm.mlstm_chunked(h, p["mixer"], cfg, chunk=chunk,
                              return_state=True)
    return x + y, st


def slstm_block_prefill(x, p, cfg):
    h = layers.apply_norm(x, p["ln"], cfg.norm)

    class _C:
        n_heads = cfg.slstm_heads
        d_model = cfg.d_model
    y, st = ssm.slstm_apply(h, p["mixer"], _C, return_state=True)
    return x + y, st
