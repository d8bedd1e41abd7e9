"""deepseek-v2-lite — MLA attention, a leading dense layer, then MoE layers
of 64 routed experts (top-6 softmax gates, not renormalised) and 2 shared.

[https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite/blob/main/config.json]
27L d_model=2048 16H; MLA kv_lora_rank=512, qk_nope 128, qk_rope 64, v 128,
no query compression; layer 0 dense (intermediate 10,944); layers 1-26 MoE
(moe_intermediate 1,408); vocab 102,400 untied; RMSNorm eps 1e-6; YaRN rope
(factor 40 over 4,096 positions, beta_fast 32, beta_slow 1, mscale 0.707).
"""
from repro.configs.base import ATTN_MLA, ArchConfig, MLAConfig, MoEConfig, YarnScaling


def make_config() -> ArchConfig:
    return ArchConfig(
        name="deepseek-v2-lite",
        family="moe",
        num_layers=27,
        d_model=2048,
        num_heads=16,
        num_kv_heads=16,
        d_ff=10_944,
        vocab_size=102_400,
        pattern=(ATTN_MLA,),
        moe=MoEConfig(num_experts=64, top_k=6, aux_loss_weight=0.0, expert_ff=1408,
                      num_shared_experts=2, norm_topk_prob=False,
                      routed_scaling_factor=1.0, dropless=True),
        mla=MLAConfig(kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
                      v_head_dim=128),
        rope_scaling=YarnScaling(factor=40.0, original_max_position=4096, beta_fast=32.0,
                                 beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707),
        first_dense_layers=1,
        norm="rmsnorm",
        act="silu",
        gated_mlp=True,
        rope_theta=10_000.0,
        max_position=163_840,
        residual_dtype="float32",
        citation="https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite (config.json, "
                 "modeling_deepseek.py)",
    )
