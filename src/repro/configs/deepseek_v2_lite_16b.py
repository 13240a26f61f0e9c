"""deepseek-v2-lite-16b [moe] — MLA (kv_lora=512) + 2 shared / 64 routed
top-6 experts.  [arXiv:2405.04434; hf:deepseek-ai/DeepSeek-V2-Lite]
27L d_model=2048 16H (kv=16) vocab=102400; layer 0 is dense
(intermediate_size 10944), layers 1-26 are expert layers
(moe_intermediate_size 1408); softmax top-6 routing without
renormalisation (norm_topk_prob false); YaRN RoPE (factor 40 over 4096
original positions, mscale 0.707)."""

from repro.configs.base import ArchConfig

CONFIG = ArchConfig(
    arch_id="deepseek_v2_lite_16b",
    family="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_ff=10944,
    vocab=102400,
    n_experts=64,
    n_shared_experts=2,
    top_k=6,
    moe_d_ff=1408,
    capacity_factor=0.0,
    norm_topk_prob=False,
    routed_scaling_factor=1.0,
    first_k_dense=1,
    kv_lora_rank=512,
    qk_rope_dim=64,
    qk_nope_dim=128,
    v_head_dim=128,
    rope_theta=10000.0,
    rope_factor=40.0,
    rope_original_max_pos=4096,
    rope_beta_fast=32.0,
    rope_beta_slow=1.0,
    rope_mscale=0.707,
    rope_mscale_all_dim=0.707,
)

SMOKE = CONFIG.replace(
    n_layers=3,
    d_model=64,
    n_heads=4,
    n_kv_heads=4,
    d_ff=96,
    vocab=256,
    n_experts=8,
    n_shared_experts=1,
    top_k=2,
    moe_d_ff=64,
    kv_lora_rank=32,
    qk_rope_dim=8,
    qk_nope_dim=16,
    v_head_dim=16,
    rope_original_max_pos=16,
    compute_dtype="float32",
)
