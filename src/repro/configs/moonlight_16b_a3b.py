"""moonlight-16b-a3b [moe]: the DeepSeek-V3 block at Moonlight's published
sizes [hf:moonshotai/Moonlight-16B-A3B config.json]: 27 layers of d_model
2048, 16 heads of multi-head latent attention (kv_lora_rank 512, q/k
128 + 64 rotary, v 128, no query low-rank), one leading dense SwiGLU of
11264, then 26 MoE layers of 64 sigmoid-routed experts of 1408 (top-6,
top-k normalised, scaled by 2.446) and 2 shared experts; vocab 163840,
untied, RoPE theta 5e4, RMSNorm eps 1e-5, context 8192."""

import dataclasses
from .base import MLAParams, ModelConfig, MoEParams

CONFIG = ModelConfig(
    name="moonlight-16b-a3b", family="moe",
    num_layers=27, d_model=2048, heads=16, kv_heads=16, d_ff=11264,
    vocab=163840, rope_theta=5e4, tie_embeddings=False, rms_norm_eps=1e-5,
    first_dense_layers=1,
    mla=MLAParams(kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
                  v_head_dim=128),
    moe=MoEParams(num_experts=64, top_k=6, d_ff=1408, num_shared_experts=2,
                  scoring="sigmoid", routed_scale=2.446,
                  aux_loss_coeff=0.0),
)

# a tiny model with every part of the block: latent attention, a dense
# first layer, shared experts and a held share (2 of 8 experts)
SMOKE = dataclasses.replace(
    CONFIG, name="moonlight-smoke",
    num_layers=3, d_model=64, heads=4, kv_heads=4, d_ff=96, vocab=128,
    mla=MLAParams(kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
                  v_head_dim=16),
    moe=dataclasses.replace(CONFIG.moe, num_experts=8, top_k=3, d_ff=32,
                            held_experts=2, first_held=2),
)
