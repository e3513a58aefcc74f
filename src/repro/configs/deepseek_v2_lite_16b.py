"""deepseek-v2-lite-16b [arXiv:2405.04434].

27L d_model=2048 16H MLA (kv_lora=512) d_ff_expert=1408 vocab=102400,
MoE 64 routed experts top-6 (softmax weights, not renormalised) + 2 shared,
first layer dense; YaRN rotary scaling (factor 40, mscale 0.707); random
weights at std 0.02 (initializer_range).
"""
from repro.models.model import ArchConfig


def config() -> ArchConfig:
    return ArchConfig(
        name="deepseek-v2-lite-16b",
        family="moe",
        n_layers=27,
        d_model=2048,
        n_heads=16,
        n_kv_heads=16,
        d_ff=10944,  # dense first layer FFN width (V2-Lite)
        d_ff_expert=1408,
        n_experts=64,
        top_k=6,
        n_shared_experts=2,
        first_k_dense=1,
        vocab=102400,
        attn_type="mla",
        kv_lora_rank=512,
        qk_rope_dim=64,
        qk_nope_dim=128,
        v_head_dim=128,
        tie_embeddings=False,
        norm_eps=1e-6,
        norm_topk_prob=False,
        # YaRN rope_scaling: factor 40 over 4096 original positions
        rope_scaling_factor=40.0,
        rope_original_max_pos=4096,
        rope_beta_fast=32.0,
        rope_beta_slow=1.0,
        rope_mscale=0.707,
        rope_mscale_all_dim=0.707,
        # random weights as the published modelling code draws them
        # (initializer_range), not at 1/sqrt(fan-in)
        init_std=0.02,
    )


def reduced() -> ArchConfig:
    return config().replace(
        name="deepseek-v2-lite-smoke",
        n_layers=3,
        d_model=64,
        n_heads=4,
        n_kv_heads=4,
        d_ff=128,
        d_ff_expert=32,
        n_experts=16,
        top_k=3,
        n_shared_experts=2,
        first_k_dense=1,
        vocab=257,
        kv_lora_rank=32,
        qk_rope_dim=8,
        qk_nope_dim=16,
        v_head_dim=16,
        moe_group_size=32,
        init_std=0.125,  # 1/sqrt(64), as 0.02 is about 1/sqrt(2048)
    )
