"""deepseek-v2-lite — DeepSeek-V2-Lite [hf:deepseek-ai/DeepSeek-V2-Lite]
(arXiv:2405.04434).

27L d_model=2048 16H vocab=102400, untied head, RMSNorm eps 1e-6.
Latent attention (MLA): kv_lora_rank 512, qk_nope_head_dim 128,
qk_rope_head_dim 64, v_head_dim 128, no q compression; YaRN rope (theta
1e4, factor 40 over 4096 positions, beta 32 / 1, mscale = mscale_all_dim
= 0.707).  Layer 0 is a dense SwiGLU of 10944; layers 1-26 are MoE: 64
routed SwiGLU experts of 1408, softmax top-6 not renormalised, scale 1,
plus 2 shared experts run as one SwiGLU of 2816.  One chip holds every
expert here; ``held_experts`` is where a deployment gives it a share.
"""
import dataclasses

from repro.configs.base import AttentionConfig, ModelConfig, MoEConfig

CONFIG = ModelConfig(
    name="deepseek-v2-lite",
    family="moe",
    num_layers=27,
    d_model=2048,
    d_ff=10944,                     # the leading dense layer's MLP
    vocab_size=102_400,
    attention=AttentionConfig(
        num_heads=16, num_kv_heads=16, head_dim=192, rope_theta=10_000.0,
        kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
        v_head_dim=128, rope_factor=40.0, rope_original_max_pos=4096,
        rope_mscale_all_dim=0.707),
    moe=MoEConfig(num_experts=64, top_k=6, d_ff_expert=1408,
                  d_ff_shared=2816, first_dense_layers=1,
                  held_experts=(0, 64)),
    tie_embeddings=False,
)


def smoke_config() -> ModelConfig:
    return dataclasses.replace(
        CONFIG, num_layers=3, d_model=64, d_ff=96, vocab_size=512,
        attention=dataclasses.replace(
            CONFIG.attention, num_heads=4, num_kv_heads=4, head_dim=24,
            kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=16, rope_original_max_pos=16),
        moe=dataclasses.replace(CONFIG.moe, num_experts=8, top_k=2,
                                d_ff_expert=32, d_ff_shared=64,
                                held_experts=(0, 8)))
