"""moonlight-16b-a3b [moe]: DeepSeek-V3 block - latent attention (MLA, no
q LoRA), one leading dense layer, then 64 routed SwiGLU experts (top 6 by
sigmoid score + correction bias, weights normalised and scaled by 2.446)
beside 2 shared ones [hf:moonshotai/Moonlight-16B-A3B, config.json]."""
from .base import ArchConfig

CONFIG = ArchConfig(
    name="moonlight-16b-a3b", family="moe",
    n_layers=27, d_model=2048, n_heads=16, n_kv_heads=16, head_dim=128,
    d_ff=11264, vocab=163840,
    norm="rms", norm_eps=1e-5, mlp_kind="swiglu", rope_theta=50000.0,
    kv_lora_rank=512, qk_nope_dim=128, qk_rope_dim=64, v_head_dim=128,
    n_experts=64, top_k=6, moe_d_ff=1408, n_shared_experts=2,
    first_dense=1, router="sigmoid", routed_scale=2.446,
    moe_dispatch="sort", aux_weight=0.0,
    # attention in 2048-row blocks: at 2 x 8192 tokens a step fits a v5e
    # with 0.96 GiB to spare, 0.20 in 1024-row blocks (each query block
    # takes its key blocks as copies)
    q_chunk=2048, kv_chunk=2048,
    source="hf:moonshotai/Moonlight-16B-A3B",
)
