"""moonlight-16b-a3b — DeepSeek-V3 layout: latent attention, 64 routed
experts top-6 with 2 shared, sigmoid routing with a selection bias
[hf:moonshotai/Moonlight-16B-A3B config.json].

27 layers (the first dense, width 11264; 26 MoE layers of 64 experts of
width 1408), hidden 2048, 16 heads; MLA with a KV latent of 512, no query
latent, q/k heads of 128 + 64 (RoPE) and v heads of 128; rope theta 50000,
no scaling; vocabulary 163840, untied; RMSNorm eps 1e-5.  The balance loss
weight (1e-4) and the bias rate (1e-3) are DeepSeek-V3's (arXiv:2412.19437
section 4.2); the config gives neither.
"""
from .base import MLAConfig, ModelConfig, MoEConfig

CONFIG = ModelConfig(
    name="moonlight-16b-a3b",
    family="moe",
    n_layers=27,
    n_dense_layers=1,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_ff=11264,
    vocab_size=163840,
    head_dim=192,
    activation="swiglu",
    norm="rms",
    positional="rope",
    rope_theta=50000.0,
    tie_embeddings=False,
    max_train_seq=8192,
    mla=MLAConfig(kv_lora_rank=512, qk_nope_head_dim=128,
                  qk_rope_head_dim=64, v_head_dim=128),
    moe=MoEConfig(n_experts=64, top_k=6, dropless=True, d_expert=1408,
                  n_shared_experts=2, routed_scale=2.446,
                  aux_loss_weight=1e-4, router_z_loss_weight=0.0,
                  bias_rate=1e-3),
    source="https://huggingface.co/moonshotai/Moonlight-16B-A3B/blob/main/"
           "config.json",
)
