"""ArchConfig: declarative architecture description (paper R8 - the user
describes the network; distribution is the framework's job)."""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax.numpy as jnp

_DTYPES = {"f32": jnp.float32, "bf16": jnp.bfloat16, "f16": jnp.float16}


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str                      # dense | moe | xlstm | zamba | encdec
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int = 0                # 0 -> d_model // n_heads

    # attention details
    norm: str = "rms"                # rms | ln
    norm_eps: float = 1e-6
    mlp_kind: str = "swiglu"         # swiglu | gelu
    qk_norm: bool = False
    qkv_bias: bool = False
    use_rope: bool = True
    rope_theta: float = 10000.0
    sliding_window: Optional[int] = None
    q_chunk: int = 1024
    kv_chunk: int = 1024

    # latent attention (MLA, training only), on when kv_lora_rank > 0: per
    # head q = [nope | rope]; x -> [c (kv_lora_rank, RMS-normed) | k_rope
    # (one rotated key for all heads)]; c -> [k_nope | v] per head
    kv_lora_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0

    # MoE
    n_experts: int = 0               # the router's width
    top_k: int = 0
    moe_group: int = 512
    moe_dispatch: str = "einsum"     # einsum (GShard, capacity) | sort (dropless)
    capacity_factor: float = 1.25
    moe_d_ff: int = 0                # an expert's width; 0 -> d_ff
    n_shared_experts: int = 0        # one SwiGLU of n_shared * moe_d_ff
    first_dense: int = 0             # leading dense layers (width d_ff)
    router: str = "softmax"          # softmax | sigmoid (+ correction bias)
    routed_scale: float = 1.0        # on the normalised top-k weights
    # the expert share: the layer holds experts [expert_offset,
    # expert_offset + experts_held) of the router's n_experts (0: all)
    experts_held: int = 0
    expert_offset: int = 0

    # SSM / recurrent
    expand: int = 2
    ssm_head_dim: int = 64
    ssm_state: int = 64
    ssm_groups: int = 1
    ssm_d_conv: int = 4
    ssm_chunk: int = 256
    slstm_every: int = 8             # xlstm: 1 sLSTM per this many layers
    slstm_heads: int = 4
    shared_every: int = 6            # zamba: shared attn block cadence

    # encoder-decoder
    n_enc_layers: int = 0
    enc_frames: int = 1500           # stub audio frontend output length
    max_dec_len: int = 65536

    # decode cache write: "dus" (dynamic-update-slice) or "masked"
    # (iota-mask select: no resharding when the seq dim is sharded)
    cache_update: str = "dus"

    # numerics
    param_dtype: str = "f32"
    compute_dtype: str = "bf16"
    cache_dtype_str: str = "bf16"

    # stacking / remat
    scan_layers: bool = True
    remat: bool = True

    # metadata
    source: str = ""
    aux_weight: float = 0.01

    def __post_init__(self):
        if self.head_dim == 0:
            object.__setattr__(self, "head_dim", self.d_model // self.n_heads)
        if self.moe_d_ff == 0:
            object.__setattr__(self, "moe_d_ff", self.d_ff)
        if self.experts_held == 0:
            object.__setattr__(self, "experts_held", self.n_experts)

    @property
    def mla(self) -> bool:
        return self.kv_lora_rank > 0

    # -- dtypes ---------------------------------------------------------------
    @property
    def p_dtype(self):
        return _DTYPES[self.param_dtype]

    @property
    def c_dtype(self):
        return _DTYPES[self.compute_dtype]

    @property
    def cache_dtype(self):
        return _DTYPES[self.cache_dtype_str]

    # -- parameter counts (for 6ND roofline bookkeeping) ----------------------
    def _attn_params(self) -> int:
        d, H = self.d_model, self.n_heads
        if self.mla:
            r, nope, rope, v = (self.kv_lora_rank, self.qk_nope_dim,
                                self.qk_rope_dim, self.v_head_dim)
            return (d * H * (nope + rope) + d * (r + rope) + r
                    + r * H * (nope + v) + H * v * d)
        return d * self.head_dim * (H * 2 + self.n_kv_heads * 2)

    def _layer_params(self) -> tuple[int, int]:
        """(total, active) params per layer (of the published model: every
        expert, whatever the share held)."""
        d, ff = self.d_model, self.d_ff
        attn = self._attn_params()
        if self.family in ("dense", "encdec"):
            mlp_mults = 3 if self.mlp_kind == "swiglu" else 2
            return attn + mlp_mults * d * ff, attn + mlp_mults * d * ff
        if self.family == "moe":
            router = d * self.n_experts
            expert = 3 * d * self.moe_d_ff
            shared = self.n_shared_experts * expert
            tot = attn + router + self.n_experts * expert + shared
            act = attn + router + self.top_k * expert + shared
            return tot, act
        if self.family == "xlstm":
            d_in = self.expand * d
            m = d * 2 * d_in + 3 * d_in * d_in + d_in * d
            return m, m
        if self.family == "zamba":
            d_in = self.expand * d
            H = d_in // self.ssm_head_dim
            gn = self.ssm_groups * self.ssm_state
            mamba = d * (2 * d_in + 2 * gn + H) + d_in * d
            return mamba, mamba
        raise ValueError(self.family)

    def n_params(self) -> tuple[int, int]:
        """(total, active) including embeddings."""
        tot, act = self._layer_params()
        n_l = self.n_layers + self.n_enc_layers - self.first_dense
        tot, act = tot * n_l, act * n_l
        if self.first_dense:
            dense = self._attn_params() + 3 * self.d_model * self.d_ff
            tot += self.first_dense * dense
            act += self.first_dense * dense
        if self.family == "zamba":
            # shared transformer block, one copy
            d, ff = self.d_model, self.d_ff
            attn = d * self.head_dim * (self.n_heads * 2 + self.n_kv_heads * 2)
            shared = attn + 3 * d * ff
            tot += shared
            act += shared * (self.n_layers // self.shared_every)
        emb = self.vocab * self.d_model * 2   # embed + unembed
        return tot + emb, act + emb

    # -- reductions for smoke tests -------------------------------------------
    def tiny(self) -> "ArchConfig":
        changes = dict(
            n_layers=min(self.n_layers, 4 if self.family in ("xlstm", "zamba")
                         else 2),
            d_model=128, n_heads=4, head_dim=32,
            n_kv_heads=min(self.n_kv_heads, 2) if self.n_kv_heads < self.n_heads else 4,
            d_ff=256, vocab=512,
            q_chunk=64, kv_chunk=64, ssm_chunk=32, moe_group=64,
            expand=2, ssm_head_dim=32, ssm_state=16, slstm_heads=2,
            compute_dtype="f32", cache_dtype_str="f32",
        )
        if self.family == "moe":
            changes.update(n_experts=min(self.n_experts, 4),
                           top_k=min(self.top_k, 2), moe_d_ff=0,
                           experts_held=0, expert_offset=0,
                           first_dense=min(self.first_dense, 1))
        if self.mla:
            changes.update(kv_lora_rank=32, qk_nope_dim=16, qk_rope_dim=8,
                           v_head_dim=16)
        if self.family == "xlstm":
            changes.update(n_layers=4, slstm_every=4)
        if self.family == "zamba":
            changes.update(n_layers=4, shared_every=2)
        if self.family == "encdec":
            changes.update(n_enc_layers=2, enc_frames=16)
        return dataclasses.replace(self, **changes)
