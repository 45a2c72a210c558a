"""kimi-k2-1t-a32b — Kimi-K2-Instruct, the DeepSeek-V3 layer equations at
1.04T parameters: 61 layers (the first dense), latent attention (MLA) with
64 heads, a yarn-scaled rope, and 384 sigmoid-routed experts, 8 a token,
beside one shared expert.
Source: https://huggingface.co/moonshotai/Kimi-K2-Instruct (config.json)."""

import dataclasses

from repro.configs import _shrink
from repro.models.config import ModelConfig, Yarn

CONFIG = ModelConfig(
    name="kimi-k2-1t-a32b",
    family="moe",
    num_layers=61,
    d_model=7168,
    num_heads=64,
    num_kv_heads=64,           # MLA: every head reads the one latent
    d_ff=18432,                # the dense first layer
    vocab_size=163840,
    gated_mlp=True,
    mlp_act="silu",
    norm_eps=1e-6,
    rope_theta=50000.0,
    moe_experts=384,
    moe_top_k=8,
    moe_d_ff=2048,
    moe_shared_d_ff=2048,      # n_shared_experts (1) x moe_intermediate_size
    moe_first_dense=1,
    moe_router="sigmoid",      # topk_method noaux_tc, norm_topk_prob
    moe_routed_scale=2.827,
    q_lora_rank=1536,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    rope_yarn=Yarn(factor=32.0, original_max=4096, beta_fast=1.0,
                   beta_slow=1.0, mscale=1.0, mscale_all_dim=1.0),
)


def smoke_config() -> ModelConfig:
    return _shrink(CONFIG, moe_first_dense=1, num_kv_heads=4, q_lora_rank=32,
                   kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8,
                   v_head_dim=8,
                   rope_yarn=dataclasses.replace(CONFIG.rope_yarn,
                                                 original_max=16))
